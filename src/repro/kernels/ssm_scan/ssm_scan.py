"""Pallas TPU kernel: chunked selective-scan (mamba/hymba hot spot).

The SSM recurrence h_t = a_t * h_{t-1} + bx_t is sequential in t but fully
parallel over the (d_in, N) state lanes — a natural TPU shape: iterate t on
the scalar core, vectorize (d_in x N) tiles on the VPU, keep the running
state h in VMEM scratch for the whole chunk (no HBM round-trips per step).

Grid: (B, n_d_tiles); each program instance scans its (chunk, D_TILE, N)
slab serially in t. VMEM: a/bx slabs 2 * chunk*D_TILE*N*4B (chunk=64,
D_TILE=256, N=16 -> 4 MB) + h scratch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

D_TILE = 256


def _scan_kernel(a_ref, bx_ref, h0_ref, hseq_ref, hlast_ref, h_sc, *, chunk):
    h_sc[...] = h0_ref[0]

    def step(t, _):
        h = a_ref[0, t] * h_sc[...] + bx_ref[0, t]
        h_sc[...] = h
        hseq_ref[0, t] = h
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)
    hlast_ref[0] = h_sc[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_scan_chunk(a: jnp.ndarray, bx: jnp.ndarray, h0: jnp.ndarray, *,
                   interpret: Optional[bool] = None):
    """One chunk of h_t = a_t h_{t-1} + bx_t.

    a, bx: (B, C, d_in, N) fp32; h0: (B, d_in, N).
    Returns (h_seq (B, C, d_in, N), h_last (B, d_in, N)).
    """
    B, C, d_in, N = a.shape
    tile = min(D_TILE, d_in)
    pad = (-d_in) % tile
    if pad:
        padded = lambda x: jnp.pad(x, ((0, 0),) * (x.ndim - 2) + ((0, pad), (0, 0)),
                                   constant_values=1.0 if x is a else 0.0)
        a = jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)), constant_values=1.0)
        bx = jnp.pad(bx, ((0, 0), (0, 0), (0, pad), (0, 0)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad), (0, 0)))
    d_p = a.shape[2]
    grid = (B, d_p // tile)
    kernel = functools.partial(_scan_kernel, chunk=C)
    h_seq, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, C, tile, N), lambda b, d: (b, 0, d, 0)),
            pl.BlockSpec((1, C, tile, N), lambda b, d: (b, 0, d, 0)),
            pl.BlockSpec((1, tile, N), lambda b, d: (b, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, tile, N), lambda b, d: (b, 0, d, 0)),
            pl.BlockSpec((1, tile, N), lambda b, d: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(a.shape, jnp.float32),
            jax.ShapeDtypeStruct(h0.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tile, N), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(a.astype(jnp.float32), bx.astype(jnp.float32), h0.astype(jnp.float32))
    if pad:
        h_seq = h_seq[:, :, :d_in]
        h_last = h_last[:, :d_in]
    return h_seq, h_last
