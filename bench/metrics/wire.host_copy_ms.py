"""Mean host time per batch in the wire path's host copies: the stages
`wire.encode.pack` (asarray, concat, pad), `wire.encode.frame` (chunking
into frames), `wire.decode.join` (reassembly's join) and
`wire.decode.split` (the split into messages), summed over the window."""
from bench.stages import mean_ms

STAGES = ("wire.encode.pack", "wire.encode.frame", "wire.decode.join",
          "wire.decode.split")


def read(r):
    return mean_ms(r, STAGES, "batches")
