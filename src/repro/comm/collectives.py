"""Collective implementations over a mesh axis (the TPU 'transports').

These are the alternative implementations behind the gradient-transport Select
(DESIGN.md §2): all compute the same all-reduce, with different schedules and
wire formats, hence different collective-roofline terms:

  psum_tree          XLA-native all-reduce (one fused AR)
  ring_tree          explicit bidirectional-ring RS+AG via ppermute
                     (2(n-1) steps; overlap-friendly schedule on real links)
  hierarchical_tree  reduce-scatter over the fast (intra-pod ICI) axis, then
                     all-reduce over the slow (DCN) axis on 1/|fast| shards,
                     then all-gather — per-chip DCN bytes divided by |fast|
  compressed_tree    int8 block-quantized all-gather over the slow axis
                     (4x DCN bytes vs fp32) with error feedback upstream

All functions run INSIDE a shard_map manual over the named axes and are
numerically interchangeable (tested against psum_tree).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro import compat

from repro.comm import compress


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


def compressed_allgather_sum(x: jnp.ndarray, axis: str, *, block: int = 256,
                             use_kernel: bool = False) -> jnp.ndarray:
    """All-reduce with an int8 block-quantized wire format over ``axis``.

    Each rank quantizes its vector, all-gathers the (int8, fp32-scale) pair
    (1/4 the fp32 bytes + ~1/block scale overhead) and dequant-sums locally.
    """
    n = compat.named_axis_size(axis)
    if n == 1:
        return x
    q, scales = compress.quantize_int8(x, block=block, use_kernel=use_kernel)
    q_all = jax.lax.all_gather(q, axis, axis=0, tiled=False)  # (n, ...)
    s_all = jax.lax.all_gather(scales, axis, axis=0, tiled=False)
    deq = jax.vmap(lambda qq, ss: compress.dequantize_int8(qq, ss, x.shape, block=block))(
        q_all, s_all
    )
    return jnp.sum(deq, axis=0)


def compressed_tree(tree, slow_axis: str, *, block: int = 256, use_kernel: bool = False):
    flat, shapes, treedef = _flatten(tree)
    out = compressed_allgather_sum(flat, slow_axis, block=block, use_kernel=use_kernel)
    return _unflatten(out, shapes, treedef, tree)


def hierarchical_compressed_tree(tree, fast_axis: str, slow_axis: str, *, block: int = 256,
                                 use_kernel: bool = False):
    """Beyond-paper combination: RS(fast) -> compressed AR(slow) -> AG(fast)."""
    flat, shapes, treedef = _flatten(tree)
    shard = _scatter_fast(flat, fast_axis)
    shard = compressed_allgather_sum(shard, slow_axis, block=block, use_kernel=use_kernel)
    full = jax.lax.all_gather(shard, fast_axis, axis=0, tiled=True)
    return _unflatten(full[: flat.shape[0]], shapes, treedef, tree)
