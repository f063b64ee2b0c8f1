"""Mean host time per batch spent waiting on the wire path's device
programs: the stages `wire.encode.kernel` and `wire.decode.kernel`, each a
call of the fused quantize or dequantize program and its
`block_until_ready`, summed over the window."""
from bench.stages import mean_ms

STAGES = ("wire.encode.kernel", "wire.decode.kernel")


def read(r):
    return mean_ms(r, STAGES, "batches")
