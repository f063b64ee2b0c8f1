"""Share of its roofline that the compressed transport's quantize and
dequantize reach: the least time of one step's work on one chip (its HBM
bytes from the configuration's leaf shapes as a chip holds them,
`bench.transport.quantize_hbm_bytes`, over the HBM bandwidth) over the
device time per step of the ops under `transport/quantize` and
`transport/dequantize`, averaged over the cell's chips."""
from bench.transport import per_step_ms, quantize_hbm_bytes


def read(r):
    peak = r.facts.get("peak")
    ms = per_step_ms(r, ("transport", "quantize"), ("transport", "dequantize"))
    if not peak or ms is None:
        return None
    least_ms = 1e3 * quantize_hbm_bytes(r.run.cell.config, len(r.devices)) / peak["hbm_bytes_per_s"]
    return 100.0 * least_ms / ms
