"""Mean host time per batch in the wire path's transfers between host and
device: the stages `wire.encode.h2d`, `wire.encode.d2h` (with `tobytes`),
`wire.decode.h2d` and `wire.decode.d2h`, summed over the window."""
from bench.stages import mean_ms

STAGES = ("wire.encode.h2d", "wire.encode.d2h", "wire.decode.h2d",
          "wire.decode.d2h")


def read(r):
    return mean_ms(r, STAGES, "batches")
