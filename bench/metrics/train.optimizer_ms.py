"""Mean device time per step of the optimizer: the ops whose HLO `op_name`
lies under `jax.named_scope("optimizer")` in the train step (clipping and
the AdamW update), the union of their intervals on the cell's first chip,
over the window's steps."""
from bench.stages import load


def read(r):
    view = load(r.out.trace_dir)
    steps = r.facts.get("steps")
    if view is None or not steps:
        return None
    t = view.phase_s(r.devices[0].id, "optimizer")
    return 1e3 * t / steps if t > 0 else None
