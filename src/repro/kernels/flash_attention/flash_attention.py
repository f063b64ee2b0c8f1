"""Pallas TPU flash attention (blockwise online softmax, GQA via index maps).

Motivation (DESIGN.md / §Perf): the pure-jnp chunked attention computes the
full S x S masked score matrix (2x the causal-optimal FLOPs) and streams
scores through HBM. This kernel keeps the (block_q x block_k) score tile in
VMEM, skips strictly-upper causal tiles entirely, and accumulates in fp32
VMEM scratch.

Grid: (B, H, n_q, n_kv) with the kv dimension innermost (sequential
revisiting of the same output block). GQA is handled in the K/V BlockSpec
index maps (kv_head = q_head // group) — no materialized head expansion.

Block sizes default to (128, 128): MXU-aligned; the VMEM working set
(q,k,v tiles + fp32 score tile + fp32 acc) is ~0.5 MB, leaving headroom for
double buffering within the ~16 MB budget.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *, scale, causal,
               window, block_q, block_k, n_kv, seq_kv):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_start = i * block_q
    k_start = j * block_k
    # Tiles strictly above the causal diagonal contribute nothing.
    run = (k_start <= q_start + block_q - 1) if causal else True
    if window is not None:
        run = jnp.logical_and(run, q_start - (k_start + block_k - 1) < window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (block_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = (q @ k.T) * scale  # (block_q, block_k)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = kpos < seq_kv
        if causal:
            ok &= kpos <= qpos
        if window is not None:
            ok &= qpos - kpos < window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + p @ v
        m_sc[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_sc[...], 1e-20)
        o_ref[0, 0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, hd)
    k: jnp.ndarray,  # (B, Skv, KH, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window=None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
):
    B, Sq, H, hd = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    assert H % KH == 0, (H, KH)
    group = H // KH
    scale = hd**-0.5

    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    qt = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kt = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    n_q = qt.shape[2] // block_q
    n_kv = kt.shape[2] // block_k

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_kv=n_kv, seq_kv=Skv)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            # GQA: the kv head index is derived from the q head in the index map
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)[:, :Sq]
