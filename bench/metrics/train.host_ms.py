"""Mean host time per step in the trainer loop outside the device wait:
the stages `train.input` (the batch callable and its transfers),
`train.dispatch` (the jitted step until it returns), `train.readback`
(the `float` of the metrics) and `train.bookkeeping` (checkpoint,
straggler, telemetry, controller), summed over the window."""
from bench.stages import mean_ms

STAGES = ("train.input", "train.dispatch", "train.readback", "train.bookkeeping")


def read(r):
    return mean_ms(r, STAGES, "steps")
