"""Mean device time per step of the gradient transport: the ops whose HLO
`op_name` lies under `jax.named_scope("transport")` in the train step, the
union of their intervals on each chip, averaged over the cell's chips."""
from bench.transport import per_step_ms


def read(r):
    return per_step_ms(r, ("transport",))
