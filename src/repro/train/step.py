"""Train/serve step builders with the Bertha seam.

The gradient path is:  value_and_grad  ->  [grad chunnel stack]  ->  AdamW.

With the paper-faithful 'xla' transport the step is a plain jit function and
XLA schedules every collective (the 'kernel networking' default). Any other
transport takes MANUAL control of its mesh axes (usually the pod/DCN tier) by
wrapping the whole step in a partial-auto shard_map: inside, the batch is the
pod-local shard, XLA still auto-partitions data/model, and the chunnel stack
explicitly places the cross-pod collectives. Reconfiguring the transport
re-traces the step with a different stack — state (params/opt/EF-residuals)
carries over, connections (the mesh) do not re-establish (paper req. #4).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P
from repro import compat

from repro.comm.chunnels import (
    StepChunnel,
    apply_grad_stack,
    init_grad_states,
    stack_manual_axes,
)
from repro.configs.base import ModelConfig, ShardingConfig, TrainConfig
from repro.models.registry import Model
from repro.models.sharding import data_spec
from repro.optim import adamw


class TrainState(NamedTuple):
    params: Any
    opt: adamw.AdamWState
    comm: Any  # chunnel states (EF residuals, localsgd counters, ...)
    step: jnp.ndarray


def init_state(model: Model, rng, tcfg: TrainConfig = TrainConfig()) -> TrainState:
    params = model.init(rng)
    return TrainState(
        params=params,
        opt=adamw.init(params, jnp.dtype(tcfg.opt_dtype)),
        comm=(),
        step=jnp.zeros((), jnp.int32),
    )


def state_shapes(model: Model, grad_chunnels: Sequence[StepChunnel],
                 tcfg: TrainConfig = TrainConfig()) -> TrainState:
    p = model.param_shapes()
    return TrainState(
        params=p,
        opt=adamw.init_shape(p, jnp.dtype(tcfg.opt_dtype)),
        comm=init_grad_states(grad_chunnels, p),
        step=jax.ShapeDtypeStruct((), jnp.int32),
    )


def make_train_step(
    model: Model,
    tcfg: TrainConfig,
    grad_chunnels: Sequence[StepChunnel],
    mesh,
) -> Callable:
    """Returns step(state, batch) -> (state, metrics)."""
    lr_fn = adamw.lr_schedule(tcfg)
    manual = stack_manual_axes(grad_chunnels) & set(mesh.axis_names)
    ctx = {"mesh": mesh}

    def grads_of(params, batch):
        if tcfg.microbatches <= 1:
            return jax.value_and_grad(model.loss)(params, batch)
        # gradient accumulation: scan over microbatch splits of the batch's
        # leading dim; activation live-set shrinks by the microbatch factor
        n = tcfg.microbatches

        def split(x):
            return x.reshape((n, x.shape[0] // n) + x.shape[1:])

        mb = jax.tree.map(split, batch)

        def acc_body(carry, mb_i):
            loss_acc, g_acc = carry
            l, g = jax.value_and_grad(model.loss)(params, mb_i)
            g_acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32) / n, g_acc, g)
            return (loss_acc + l / n, g_acc), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grads), _ = jax.lax.scan(acc_body, (jnp.zeros((), jnp.float32), zeros), mb)
        return loss, grads

    def core(params, opt, comm, step, batch, pod_scale):
        # the scopes name each device op's phase in a profiler trace
        with jax.named_scope("grads"):
            loss, grads = grads_of(params, batch)
            grads = jax.tree.map(lambda g: g * pod_scale, grads)
        with jax.named_scope("transport"):
            grads, comm = apply_grad_stack(grad_chunnels, grads, comm, ctx)
        with jax.named_scope("optimizer"):
            params, opt, metrics = adamw.update(grads, opt, params, lr_fn(step), tcfg)
        return params, opt, comm, loss, metrics

    if not manual:

        def step_fn(state: TrainState, batch) -> tuple:
            params, opt, comm, loss, metrics = core(
                state.params, state.opt, state.comm, state.step, batch, 1.0)
            return (
                TrainState(params, opt, comm, state.step + 1),
                {"loss": loss, **metrics},
            )

        return step_fn

    n_manual = 1
    for a in manual:
        n_manual *= mesh.shape[a]

    def step_fn(state: TrainState, batch) -> tuple:
        # XLA-CPU workaround (see moe_ffn): bf16 operands crossing a
        # partial-manual shard_map boundary crash the CPU backend under grad.
        # Cross in f32 and restore the original dtypes at both edges.
        opt_dtypes = jax.tree.map(lambda a: a.dtype, state.opt)

        def widen(tree):
            return jax.tree.map(
                lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a, tree)

        def narrow(tree, dtypes):
            return jax.tree.map(lambda a, d: a.astype(d), tree, dtypes)

        def inner(params, opt, comm, step, batch_local):
            # batch_local is this pod's shard; grads averaged across `manual`
            # axes by the transport chunnel itself (each applies 1/n or pmean).
            opt_n = narrow(opt, opt_dtypes)
            params, opt_n, comm, loss, metrics = core(
                params, opt_n, comm, step, batch_local, 1.0)
            # mean over every manual axis at once: averaging per-axis means
            # would weight the shards unevenly when two axes are manual
            axes = tuple(sorted(manual))
            loss = jax.lax.pmean(loss, axes)
            metrics = {k: jax.lax.pmean(v, axes) for k, v in metrics.items()}
            return params, widen(opt_n), comm, loss, metrics

        batch_specs = jax.tree.map(lambda _: P(*(tuple(manual),)), batch)
        rep = lambda tree: jax.tree.map(lambda _: P(), tree)
        f = compat.shard_map(
            inner,
            mesh=mesh,
            in_specs=(rep(state.params), rep(state.opt), rep(state.comm), P(),
                      batch_specs),
            out_specs=(rep(state.params), rep(state.opt), rep(state.comm), P(), P()),
            check_vma=False,
            axis_names=manual,
        )
        params, opt, comm, loss, metrics = f(
            state.params, widen(state.opt), state.comm, state.step, batch)
        return TrainState(params, narrow(opt, opt_dtypes), comm, state.step + 1), \
            {"loss": loss, **metrics}

    return step_fn


# ---------------------------------------------------------------------------
# jit wrappers with production shardings
# ---------------------------------------------------------------------------


def _zero1_pod(spec: P, shape, mesh) -> P:
    """ZeRO-1 over the pod axis: optimizer moments additionally shard their
    FSDP ('data') dim over 'pod'. Params stay pod-replicated; the update's
    pod all-gather is the standard ZeRO-1 cost."""
    if "pod" not in mesh.axis_names:
        return spec
    pod = mesh.shape["pod"]
    data = mesh.shape.get("data", 1)
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax == "data" and dim % (data * pod) == 0:
            out.append(("data", "pod"))
        else:
            out.append(ax)
    return P(*out)


def shardings_for(model: Model, mesh, sh: ShardingConfig, grad_chunnels=()):
    """(state_shardings, batch_sharding_fn) for jit in/out_shardings."""
    pspecs = model.param_specs(sh)
    pshapes = model.param_shapes()
    ns = lambda spec: NamedSharding(mesh, spec)
    param_sh = jax.tree.map(ns, pspecs)
    mom_sh = jax.tree.map(
        lambda spec, shp: ns(_zero1_pod(spec, shp.shape, mesh)), pspecs, pshapes)
    opt_sh = adamw.AdamWState(m=mom_sh, v=mom_sh,
                              count=ns(P()))
    comm_shapes = init_grad_states(grad_chunnels, model.param_shapes())
    comm_sh = jax.tree.map(
        lambda leaf: ns(P()), comm_shapes,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    # EF residuals share the param tree structure -> reuse param specs
    comm_sh = []
    for ch, st in zip(grad_chunnels, comm_shapes):
        if st == ():
            comm_sh.append(())
        elif isinstance(st, dict) and "step" in st:
            comm_sh.append(jax.tree.map(lambda _: ns(P()), st))
        else:
            comm_sh.append(param_sh)
    state_sh = TrainState(params=param_sh, opt=opt_sh, comm=tuple(comm_sh), step=ns(P()))

    def batch_sharding(batch_specs: dict):
        return {
            k: ns(data_spec(v.shape, mesh)) for k, v in batch_specs.items()
        }

    return state_sh, batch_sharding


def jit_train_step(model, tcfg, grad_chunnels, mesh, sh: ShardingConfig,
                   batch_specs: dict, donate: bool = True):
    step_fn = make_train_step(model, tcfg, grad_chunnels, mesh)
    state_sh, batch_sh_fn = shardings_for(model, mesh, sh, grad_chunnels)
    metrics_sh = None  # let XLA pick (scalars)
    return jax.jit(
        step_fn,
        in_shardings=(state_sh, batch_sh_fn(batch_specs)),
        out_shardings=(state_sh, metrics_sh),
        donate_argnums=(0,) if donate else (),
    )
