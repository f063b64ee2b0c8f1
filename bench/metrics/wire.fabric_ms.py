"""Mean host time per batch in the in-process fabric: the stages
`wire.send.fabric` (the sender's one send of its frames) and
`wire.recv.fabric` (each read of frames on the receiving side), summed
over the window."""
from bench.stages import mean_ms

STAGES = ("wire.send.fabric", "wire.recv.fabric")


def read(r):
    return mean_ms(r, STAGES, "batches")
