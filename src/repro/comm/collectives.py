"""Collective implementations over a mesh axis (the TPU 'transports').

These are the alternative implementations behind the gradient-transport Select
(``comm/chunnels.py``; docs/architecture.md §8): all compute the same
all-reduce, with different schedules and wire formats, hence different
collective-roofline terms:

  psum_tree          XLA-native all-reduce (one fused AR)
  ring_tree          explicit bidirectional-ring RS+AG via ppermute
                     (2(n-1) steps; overlap-friendly schedule on real links)
  hierarchical_tree  reduce-scatter over the fast (intra-pod ICI) axis, then
                     all-reduce over the slow (DCN) axis on 1/|fast| shards,
                     then all-gather — per-chip DCN bytes divided by |fast|
  compressed_tree    int8 block-quantized all-gather over the slow axis, one
                     leaf at a time (4x fewer DCN bytes than fp32), with
                     error feedback upstream

All functions run INSIDE a shard_map manual over the named axes and are
numerically interchangeable (tested against psum_tree).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro import compat

from repro.comm import compress
from repro.kernels.quantize import leaf


def dcn_bytes_factor(schedule: str, *, n_fast: int = 1, sync_every: int = 1,
                     wire_ratio: float = 1.0) -> float:
    """Per-payload-byte DCN traffic of each schedule, relative to one fused
    f32 all-reduce — the ``dcn_bytes_per_byte`` cost-model term behind the
    gradient-transport Select:

      psum/ring/xla   1.0   (full f32 gradients cross the slow tier)
      hierarchical    1/n_fast  (each chip moves only its RS shard over DCN)
      compressed      wire_ratio (see ``compress.int8_wire_ratio``)
      hier_compressed wire_ratio/n_fast
      localsgd        1/sync_every (full sync every H steps, amortized)
    """
    if schedule in ("hierarchical",):
        return 1.0 / max(n_fast, 1)
    if schedule in ("compressed", "compressed_int8", "cag"):
        return wire_ratio
    if schedule in ("hier_compressed", "hiercag"):
        return wire_ratio / max(n_fast, 1)
    if schedule == "localsgd":
        return 1.0 / max(sync_every, 1)
    return 1.0  # xla / psum / ring


def _flatten(tree) -> Tuple[jnp.ndarray, list, list]:
    leaves = jax.tree.leaves(tree)
    shapes = [l.shape for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves]) if leaves else jnp.zeros((0,))
    return flat, shapes, jax.tree.structure(tree)


def _unflatten(flat: jnp.ndarray, shapes, treedef, like_tree):
    out, off = [], 0
    dtypes = [l.dtype for l in jax.tree.leaves(like_tree)]
    for shp, dt in zip(shapes, dtypes):
        n = 1
        for s in shp:
            n *= s
        out.append(flat[off : off + n].reshape(shp).astype(dt))
        off += n
    return jax.tree.unflatten(treedef, out)


def psum_tree(tree, axis: str):
    return jax.tree.map(lambda g: jax.lax.psum(g, axis), tree)


def pmean_tree(tree, axis: str):
    return jax.tree.map(lambda g: jax.lax.pmean(g, axis), tree)


def ring_allreduce(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Ring all-reduce of a flat vector via 2(n-1) collective-permutes."""
    n = compat.named_axis_size(axis)
    if n == 1:
        return x
    rank = jax.lax.axis_index(axis)
    perm = [(j, (j + 1) % n) for j in range(n)]
    size = x.shape[0]
    pad = (-size) % n
    xp = jnp.pad(x, (0, pad))
    chunks = xp.reshape(n, -1)

    def rs_step(i, c):
        send = c[(rank - i + 1) % n]
        recv = jax.lax.ppermute(send, axis, perm)
        return c.at[(rank - i) % n].add(recv)

    chunks = jax.lax.fori_loop(1, n, rs_step, chunks, unroll=True)
    my = (rank + 1) % n
    cur = chunks[my]
    out = jnp.zeros_like(chunks).at[my].set(cur)

    def ag_step(i, st):
        acc, cur = st
        nxt = jax.lax.ppermute(cur, axis, perm)
        return acc.at[(rank - i + 1) % n].set(nxt), nxt

    out, _ = jax.lax.fori_loop(1, n, ag_step, (out, cur), unroll=True)
    return out.reshape(-1)[:size]


def ring_tree(tree, axis: str):
    flat, shapes, treedef = _flatten(tree)
    return _unflatten(ring_allreduce(flat, axis), shapes, treedef, tree)


def hierarchical_tree(tree, fast_axis: str, slow_axis: str):
    """RS(fast) -> AR(slow) on 1/|fast| shards -> AG(fast).

    Balances DCN traffic: every chip moves only its 1/|fast| gradient shard
    across the slow tier instead of the full tree.

    The scatter and gather are tiled over the flat vector: the untiled form
    on a (|fast|, n/|fast|) reshape compiles many times slower for the TPU
    (PERF.md, PR 12 findings).
    """
    flat, shapes, treedef = _flatten(tree)
    shard = _scatter_fast(flat, fast_axis)
    shard = jax.lax.psum(shard, slow_axis)
    full = jax.lax.all_gather(shard, fast_axis, axis=0, tiled=True)
    return _unflatten(full[: flat.shape[0]], shapes, treedef, tree)


def _scatter_fast(flat: jnp.ndarray, fast_axis: str) -> jnp.ndarray:
    """Reduce-scatter of a flat vector over ``fast_axis``, padded to split."""
    pad = (-flat.shape[0]) % compat.named_axis_size(fast_axis)
    return jax.lax.psum_scatter(jnp.pad(flat, (0, pad)), fast_axis,
                                scatter_dimension=0, tiled=True)


def compressed_leaf_sum(x: jnp.ndarray, axis: str, *, block: int = 256,
                        mean: bool = False, residual=None):
    """All-reduce of one leaf over ``axis`` in the int8 block wire format.

    Returns ``(sum, residual)``: the sum over ``axis`` (the mean where
    ``mean``) in ``x``'s shape, and ``x - dq(q(x))`` of the codes this rank
    sent, for error feedback. Where ``residual`` is given (f32, ``x``'s
    shape), ``x + residual`` is what is sent, and the sum is f32. ``x`` is
    quantized in blocks of ``block`` along its trailing axis, keeping its
    shape and so the sharding of its other axes; the (int8, f32 scale per
    block) pair is all-gathered and the n copies are dequantized into one
    f32 accumulator, so no f32 array n times the leaf's size is made. Each
    value of a copy is within half a step (its block's scale / 2) of what
    was quantized.

    A leaf whose shape the Pallas kernels take (``leaf.takes_kernel``) is
    quantized and summed by them in its own 2-D tiling; any other (a 1-D
    leaf, a trailing width of part blocks) goes through the jnp blocks of
    ``compress.to_blocks``. Both give the same codes, scales and sums.
    """
    dtype = jnp.float32 if residual is not None else x.dtype
    n = compat.named_axis_size(axis)
    if n == 1:
        if residual is not None:
            x = x.astype(jnp.float32) + residual
        return x, jnp.zeros(x.shape, jnp.float32)
    kernel = leaf.takes_kernel(x.shape, block)
    with jax.named_scope("quantize"):
        if kernel:  # codes (rows, width), scales (rows, nblocks)
            q, scales, new_residual = leaf.quantize_leaf(x, residual, block=block)
        else:  # codes (..., nblocks, block), scales (..., nblocks)
            if residual is not None:
                x = x.astype(jnp.float32) + residual
            xb = compress.to_blocks(x, block)
            q, scales = compress.quantize_blocks_int8(xb)
            new_residual = compress.from_blocks(
                xb - compress.dequantize_blocks_int8(q, scales), x.shape)
    with jax.named_scope("exchange"):
        q_all = jax.lax.all_gather(q, axis, axis=0, tiled=False)  # (n, ...)
        s_all = jax.lax.all_gather(scales, axis, axis=0, tiled=False)
    with jax.named_scope("dequantize"):
        if kernel:
            out = leaf.dequantize_sum(q_all, s_all, x.shape, dtype, mean=mean, block=block)
        else:
            total = compress.dequantize_blocks_int8(q_all[0], s_all[0])
            for i in range(1, n):
                total = total + compress.dequantize_blocks_int8(q_all[i], s_all[i])
            if mean:
                total = total / n
            out = compress.from_blocks(total, x.shape).astype(dtype)
    return out, new_residual


def compressed_tree_ef(tree, slow_axis: str, *, block: int = 256,
                       mean: bool = False, specs=None, residual=None):
    """``compressed_leaf_sum`` of every leaf: (summed tree, residual tree).
    ``residual``, a tree like ``tree``, is the error feedback each leaf
    carries in (None: none).

    Where the mesh has axes that are not manual yet, the leaves run in a
    nested shard_map, manual over them, each leaf placed as ``specs`` (a
    PartitionSpec per leaf, as the leaves are placed; None: replicated)
    says: each chip then quantizes, sends and sums its own shard. Left
    auto, XLA gathers a sharded leaf whole over those axes before the
    all-gather over ``slow_axis``, and every chip sends and sums all of it;
    and a Mosaic kernel lowers only where every axis is manual.
    """
    def run(leaves, residuals):
        pairs = [compressed_leaf_sum(g, slow_axis, block=block, mean=mean, residual=r)
                 for g, r in zip(leaves, residuals or [None] * len(leaves))]
        return [o for o, _ in pairs], [r for _, r in pairs]

    leaves, treedef = jax.tree.flatten(tree)
    residuals = treedef.flatten_up_to(residual) if residual is not None else []
    mesh = compat.current_mesh()
    auto = {a for a in mesh.axis_names if compat.axis_is_auto(mesh, a)} if mesh else set()
    if auto:
        specs = (treedef.flatten_up_to(specs) if specs is not None
                 else [jax.sharding.PartitionSpec()] * len(leaves))
        run = compat.shard_map(run, mesh=mesh,
                               in_specs=(specs, specs if residuals else []),
                               out_specs=(specs, specs),
                               check_vma=False, axis_names=auto | {slow_axis})
    out, new_residual = run(leaves, residuals)
    return jax.tree.unflatten(treedef, out), jax.tree.unflatten(treedef, new_residual)


def compressed_tree(tree, slow_axis: str, *, block: int = 256):
    """int8 all-reduce of a gradient tree over ``slow_axis``, one leaf at a
    time (``compressed_leaf_sum``): no leaf is joined to another."""
    return compressed_tree_ef(tree, slow_axis, block=block)[0]


def hierarchical_compressed_tree(tree, fast_axis: str, slow_axis: str, *, block: int = 256):
    """Beyond-paper combination: RS(fast) -> compressed AR(slow) -> AG(fast)."""
    flat, shapes, treedef = _flatten(tree)
    shard = _scatter_fast(flat, fast_axis)
    shard = compressed_leaf_sum(shard, slow_axis, block=block)[0]
    full = jax.lax.all_gather(shard, fast_axis, axis=0, tiled=True)
    return _unflatten(full[: flat.shape[0]], shapes, treedef, tree)
