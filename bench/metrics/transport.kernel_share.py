"""Share of the compressed transport's quantize and dequantize device time
that runs in the leaf kernels (`leaf_quantize`, `leaf_dequantize_sum`, by
instruction name): their device time over that of the ops under
`transport/quantize` and `transport/dequantize` (matched as
`bench.transport` matches phases), summed over the cell's chips. Nothing
is read where no such kernel ran."""
from bench.stages import load
from bench.trace import union
from bench.transport import scope_s

PATTERN = r"^leaf_(quantize|dequantize_sum)\b"


def read(r):
    view = load(r.out.trace_dir)
    if r.trace is None or view is None:
        return None
    ids = r.trace.device_ids(r.devices)
    kernels = sum(e - s for d in ids for s, e in union(
        (o.start, o.end) for o in r.trace.kernel_ops(d, PATTERN)))
    phases = sum(scope_s(view, d, ("transport", "quantize"), ("transport", "dequantize"))
                 for d in ids)
    if kernels <= 0 or phases <= 0:
        return None
    return 100.0 * kernels / phases
