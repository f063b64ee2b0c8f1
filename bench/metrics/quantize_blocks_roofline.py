"""Share of its roofline that `quantize_blocks` reaches over the window:
the summed least time of its calls (`bench.flops.quantize_roofline_s`,
bound by the HBM bytes of each call's operands and results as the trace
places them) over their summed device time. Nothing is read where no call
touches HBM."""
from bench.flops import quantize_roofline_s

PATTERN = r"^quantize_blocks"


def read(r):
    peak = r.facts.get("peak")
    if r.trace is None or not peak:
        return None
    ids = r.trace.device_ids(r.devices)
    ops = r.trace.kernel_ops(ids[0], PATTERN) if ids else []
    least = sum(quantize_roofline_s(o.text, peak)[0] for o in ops)
    if not ops or least <= 0:
        return None
    return 100.0 * least / sum(o.dur for o in ops)
