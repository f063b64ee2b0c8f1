"""Activation sharding constraints (data-parallel batch pinning).

With FSDP-sharded params (contraction dims over 'data'), the XLA partitioner
may legally choose tensor-parallel-over-data activation layouts (batch
replicated) — catastrophic for memory at global-batch scale. Pinning the batch
dim of activations at layer boundaries forces ZeRO-3 semantics: weights are
all-gathered, activations stay batch-sharded.

Helpers no-op when no mesh context / axes are unavailable (smoke tests run on
one device), and only constrain over AUTO axes (so they compose with the
partial-manual shard_map used by explicit transports).
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro import compat


def _auto_batch_axes():
    mesh = compat.current_mesh()
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return None, ()
    axes = tuple(a for a in ("pod", "data")
                 if a in mesh.axis_names and compat.axis_is_auto(mesh, a))
    return mesh, axes


def shard_batch(x, dim: int = 0):
    """Constrain x's dim to be sharded over the (auto) batch axes."""
    mesh, axes = _auto_batch_axes()
    if mesh is None or not axes or x.ndim <= dim:
        return x
    n = 1
    for a in axes:
        n *= compat.axis_size(mesh, a)
    if x.shape[dim] % n != 0 or x.shape[dim] == 0:
        return x
    spec = [None] * x.ndim
    spec[dim] = axes if len(axes) > 1 else axes[0]
    return jax.lax.with_sharding_constraint(x, P(*spec))


def shard_tree_batch(tree, dim: int = 0):
    return jax.tree.map(lambda x: shard_batch(x, dim), tree)


def shard_activations(x, batch_dim: int = 0, seq_dim: int = 1):
    """Sequence-parallel residual stream (Korthikanti et al.): batch over the
    data axes AND sequence over 'model' at layer boundaries, so remat-saved
    layer inputs are L x (B/dp) x (S/tp) x D instead of TP-replicated in S.
    The partitioner inserts the standard SP all-gather/reduce-scatter pair
    around each layer's TP blocks."""
    mesh, axes = _auto_batch_axes()
    if mesh is None or x.ndim < 3:
        return shard_batch(x, batch_dim) if mesh is not None else x
    spec = [None] * x.ndim
    if axes:
        n = 1
        for a in axes:
            n *= compat.axis_size(mesh, a)
        if x.shape[batch_dim] % n == 0 and x.shape[batch_dim] > 0:
            spec[batch_dim] = axes if len(axes) > 1 else axes[0]
    if "model" in mesh.axis_names:
        is_auto = compat.axis_is_auto(mesh, "model")
        m = compat.axis_size(mesh, "model")
        if is_auto and x.shape[seq_dim] % m == 0 and x.shape[seq_dim] >= m:
            spec[seq_dim] = "model"
    if all(a is None for a in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def shard_model_dim(x, dim: int, batch_dim: int = 0):
    """Batch over the data axes; ``dim`` over 'model' when divisible. Used by
    the SSM branch: the time recurrence cannot shard S, but the state channels
    (d_in) are embarrassingly parallel over the model axis."""
    mesh, axes = _auto_batch_axes()
    if mesh is None or x.ndim <= dim:
        return x
    spec = [None] * x.ndim
    if axes:
        n = 1
        for a in axes:
            n *= compat.axis_size(mesh, a)
        if x.shape[batch_dim] % n == 0 and x.shape[batch_dim] > 0:
            spec[batch_dim] = axes if len(axes) > 1 else axes[0]
    if "model" in mesh.axis_names and compat.axis_is_auto(mesh, "model"):
        m = compat.axis_size(mesh, "model")
        if x.shape[dim] % m == 0 and x.shape[dim] >= m:
            spec[dim] = "model"
    if all(a is None for a in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def shard_heads(x, batch_dim: int = 0, head_dim: int = 2):
    """Constrain (B, S, H, hd) attention tensors: batch over the data axes,
    heads over 'model' when divisible (GQA kv heads fall back to replicated).
    Pins multi-pod attention layouts the propagator otherwise replicates."""
    mesh, axes = _auto_batch_axes()
    if mesh is None or x.ndim <= head_dim:
        return x
    spec = [None] * x.ndim
    if axes:
        n = 1
        for a in axes:
            n *= compat.axis_size(mesh, a)
        if x.shape[batch_dim] % n == 0 and x.shape[batch_dim] > 0:
            spec[batch_dim] = axes if len(axes) > 1 else axes[0]
    if "model" in mesh.axis_names and compat.axis_is_auto(mesh, "model"):
        m = compat.axis_size(mesh, "model")
        if x.shape[head_dim] % m == 0:
            spec[head_dim] = "model"
    if all(a is None for a in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))
