"""The plain reference of `bench.reference.lm`, for a batch whose gradient
does not fit one chip beside the parameters and `lm`'s row accumulator.

Each of k devices holds a copy of the f32 parameters and computes one row's
loss and gradient at a time (one program, sharded over the rows); each
row's gradient is copied to the host leaf by leaf and the rows are summed
there in f32, in row order. Everything else is `lm`'s: the same `Reference`
arithmetic (`row_loss` at `HIGHEST`), the mean over every position of every
row, the clipped AdamW with its moments on the host, and the same readings.
Only where each row is computed changes, and the order of the sum with it.
"""
from __future__ import annotations

import math
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from bench import compare, weights
from bench.reference import lm


def _row_grads(ref: "lm.Reference", mesh):
    """(losses, grads) of one row a device, stacked over the mesh's rows."""
    def one(params, tokens, labels):
        loss, grads = jax.value_and_grad(ref.row_loss)(params, tokens[0], labels[0])
        return loss[None], jax.tree.map(lambda g: g[None], grads)

    # check_vma off: each device keeps its own row's gradient, with no psum
    # over the rows put in for the parameters held alike on every device
    return jax.jit(jax.shard_map(one, mesh=mesh, in_specs=(P(), P("row"), P("row")),
                                 out_specs=P("row"), check_vma=False))


def run_reference(cfg: dict, opt: dict, seed: int, batches: List[dict], *,
                  precision: str = "f32", steps: int = 3, device=None, devices=None,
                  variant: Optional[Callable[[dict], dict]] = None) -> dict:
    """As `lm.run_reference`, over `devices` (as many as divide the batch's
    rows; the first holds the update); `[device]` where none are given."""
    devices = list(devices or [device or jax.devices()[0]])
    devices = devices[:math.gcd(len(devices), int(np.shape(batches[0]["tokens"])[0]))]
    home = jax.sharding.SingleDeviceSharding(devices[0])
    with jax.set_mesh(jax.sharding.Mesh(np.array(devices[:1]), ("ref",))):
        return _run(cfg, opt, seed, batches, precision, steps, devices, home, variant)


def _run(cfg, opt, seed, batches, precision, steps, devices, home, variant):
    lay = lm.layout(cfg)
    vocab = cfg["vocab_size"]
    mesh = jax.sharding.Mesh(np.array(devices), ("row",))
    copies, rows_sh = NamedSharding(mesh, P()), NamedSharding(mesh, P("row"))
    ref = lm.Reference(cfg, precision)
    row_grads = _row_grads(ref, mesh)
    params = weights.make_params(seed, lay, shardings={p: home for p, _ in lay})
    host_m, host_v = {}, {}
    losses, gnorms, grad1 = [], [], None
    for step in range(steps):
        batch = batches[step] if variant is None else variant(batches[step])
        toks, labs = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
        held = {p: jax.device_put(params.pop(p), copies) for p, _ in lay}
        total = {p: np.zeros(shape, np.float32) for p, shape in lay}
        loss_sum = 0.0
        for first in range(0, toks.shape[0], len(devices)):
            part = slice(first, first + len(devices))
            with jax.set_mesh(mesh):
                loss, grads = row_grads(held, jax.device_put(toks[part], rows_sh),
                                        jax.device_put(labs[part], rows_sh))
            loss_sum += float(np.sum(np.asarray(loss, np.float64)))
            for p in list(grads):
                g = grads.pop(p)
                for shard in sorted(g.addressable_shards, key=lambda s: s.index[0].start):
                    total[p] += np.asarray(shard.data)[0]
                del g
        # the first device's copy goes on as the parameters; the others go
        params = {p: next(s.data for s in held[p].addressable_shards
                          if s.device == devices[0]) for p, _ in lay}
        del held
        n = np.float32(toks.size)
        grads = {p: jax.device_put(total.pop(p) / n, home) for p, _ in lay}
        gnorm = float(jnp.sqrt(lm._sq_sum(grads)))
        clip = min(1.0, opt["grad_clip"] / max(gnorm, 1e-12)) if opt["grad_clip"] > 0 else 1.0
        losses.append(loss_sum / toks.size)
        gnorms.append(gnorm)
        if step == 0:
            grad1 = compare.leaf_norms(grads, vocab, scale=clip, stacked=lm.stacked(lay))
        lr = lm.lr_at(step, opt)
        for p, shape in lay:
            if step == 0:
                m = v = jnp.zeros(shape, jnp.float32, device=home)
            else:
                m, v = jax.device_put(host_m.pop(p), home), jax.device_put(host_v.pop(p), home)
            params[p], m, v = lm._adam_leaf(
                params[p], grads.pop(p), m, v, np.float32(lr), np.float32(clip),
                np.float32(step + 1), np.float32(opt["beta1"]), np.float32(opt["beta2"]),
                np.float32(opt["eps"]), np.float32(opt["weight_decay"]))
            if step + 1 < steps:  # the last step's moments are not needed
                host_m[p], host_v[p] = np.asarray(m), np.asarray(v)
            del m, v
        del grads
    change = lm.change_norms(params, seed, lay, vocab)
    del params
    return {"losses": losses, "grad_norms": gnorms, "grad1": grad1, "change": change}
