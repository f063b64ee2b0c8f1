"""Training launcher: negotiate the step stack, train, checkpoint, reconfigure.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --steps 50 \\
      --smoke --transport xla --ckpt /tmp/ckpt

On a CPU use --smoke (reduced config). ``--mesh none`` runs on one device and
``--mesh pods`` spreads pod=2 x data=n/2 over every visible device, which is
the mesh the gradient transports engage on. On a real cluster the same
entrypoint runs per host; the rendezvous store is where hosts agree on the
stack before compiling (SPMD safety).
"""
from __future__ import annotations

import argparse
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import numpy as np

from repro import compat
from repro.configs import get_config, get_smoke_config
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.data.synthetic import batches_for
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_pod_mesh, make_production_mesh, make_test_mesh
from repro.train.step import TrainState
from repro.train.trainer import HostSpec, ReconfigurableTrainer

MESHES = {
    "none": lambda: make_test_mesh((1, 1)),
    "pods": make_pod_mesh,
    "test": make_test_mesh,
    "single": make_production_mesh,
    "multi": lambda: make_production_mesh(multi_pod=True),
}


@dataclass
class TrainRun:
    """A negotiated trainer, its live state and its batch stream."""

    cfg: ModelConfig
    trainer: ReconfigurableTrainer
    state: TrainState
    batches: Callable[[int], dict]
    losses: List[float] = field(default_factory=list)

    def steps(self, n: int, **run_kw) -> "TrainRun":
        """Take ``n`` more steps, carrying the state on."""
        self.state, hist = self.trainer.run(self.state, self.batches, n, **run_kw)
        self.losses += [h["loss"] for h in hist]
        return self

    @property
    def step_times(self) -> List[float]:
        """Seconds per step; a step after a (re)build includes its compile."""
        return self.trainer.step_times


def start(arch: str = "llama3.2-1b", *, steps: int = 20, smoke: bool = False,
          layers: Optional[int] = None, seq: int = 128, batch: int = 8,
          transport: str = "xla", mesh: str = "none", ckpt: Optional[str] = None,
          warmup: int = 10) -> TrainRun:
    """Negotiate ``transport``, build the step on ``mesh`` and initialise the
    model from a fixed seed; ``steps`` is the length of the learning-rate
    schedule. ``layers`` cuts the depth only: every width stays as the
    config publishes it."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if layers is not None:
        cfg = cfg.replace(num_layers=layers)
    shape = ShapeConfig("cli", seq, batch, "train")
    m = compat.set_mesh(MESHES[mesh]())
    trainer = ReconfigurableTrainer(
        cfg, shape, m,
        tcfg=TrainConfig(warmup_steps=warmup, total_steps=steps),
        transport=transport, ckpt_dir=ckpt,
        hosts=[HostSpec(0, [transport, "xla"])],
    )
    state = trainer.init_state(jax.random.PRNGKey(0))
    return TrainRun(cfg, trainer, state, batches_for(cfg, shape))


def train(arch: str = "llama3.2-1b", *, steps: int = 20, ckpt_every: int = 0,
          resume: bool = False, **start_kw) -> TrainRun:
    """``start`` a run, optionally resume it from its checkpoint, and take
    ``steps`` steps."""
    run = start(arch, steps=steps, **start_kw)
    if resume and run.trainer.ckpt:
        run.state, at = run.trainer.restore()
        print(f"resumed from step {at}")
    return run.steps(steps, ckpt_every=ckpt_every)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--transport", default="xla")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default="none", choices=tuple(MESHES))
    args = ap.parse_args()

    enable_compile_cache()
    run = train(**vars(args))
    losses = run.losses
    print(f"arch={run.cfg.name} layers={run.cfg.num_layers} "
          f"transport={run.trainer.transport_name} steps={len(losses)} "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"({np.mean(run.step_times)*1e3:.0f} ms/step incl. compile)")
    if not np.isfinite(losses[-1]):
        raise SystemExit("non-finite loss")
    if run.trainer.reconfig_log:
        print("reconfigurations:", run.trainer.reconfig_log)


if __name__ == "__main__":
    main()
