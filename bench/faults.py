"""Faults planted under the timed path, to read what the comparison makes of
them (`--control fault:<name>`) and to test that it catches them. Each
`plant(name)` patches the program where the fault would live and returns
the function that takes it out again.

- `unchanged`: the train step returns the state it was given (its step
  counter aside, so that batches still differ).
- `half_batch`: the loss is the mean over half of the batch's rows (half of
  the positions of a single row).
- `altered`: the receiver's first value of every batch is off by 1.0.
- `dropped`: the receiver loses the last message of every batch.
"""
from __future__ import annotations

from typing import Callable, Dict


def half_batch(batch: dict) -> dict:
    b, s = batch["tokens"].shape
    if b >= 2:
        return {k: v[:b // 2] for k, v in batch.items()}
    return {k: v[:, :s // 2] for k, v in batch.items()}


def _patch(obj, name: str, new) -> Callable[[], None]:
    old = getattr(obj, name)
    setattr(obj, name, new)
    return lambda: setattr(obj, name, old)


def _unchanged():
    from repro.train import step as step_mod

    jit_step = step_mod.jit_train_step

    def build(*a, **kw):
        f = jit_step(*a, **dict(kw, donate=False))

        def step(state, batch):
            new, metrics = f(state, batch)
            return state._replace(step=new.step), metrics

        return step

    return _patch(step_mod, "jit_train_step", build)


def _half_batch():
    from repro.models import registry

    loss = registry.Model.loss
    return _patch(registry.Model, "loss", lambda self, p, b: loss(self, p, half_batch(b)))


def _altered():
    from repro.comm import wire

    decode = wire.decode_blob

    def altered(payload, hdr, **kw):
        out = decode(payload, hdr, **kw)
        out[0] = out[0].copy()
        out[0].reshape(-1)[0] += 1.0
        return out

    return _patch(wire, "decode_blob", altered)


def _dropped():
    from repro.comm import wire

    decode = wire.decode_blob
    return _patch(wire, "decode_blob", lambda p, h, **kw: decode(p, h, **kw)[:-1])


FAULTS: Dict[str, Callable[[], Callable[[], None]]] = {
    "unchanged": _unchanged, "half_batch": _half_batch,
    "altered": _altered, "dropped": _dropped,
}


def plant(name: str) -> Callable[[], None]:
    return FAULTS[name]()
