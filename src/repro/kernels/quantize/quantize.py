"""Pallas TPU kernel: int8 block quantization (the compressed-wire hot spot).

The gradient-compression chunnel quantizes the full gradient vector every step
— O(N_params) elementwise work that sits on the critical path right before the
DCN collective. The kernel tiles rows of blocks into VMEM, computes per-block
amax/scale on the VPU, and writes int8 + fp32 scales.

Tiling: input reshaped to (n_blocks, block); grid over row tiles of
ROWS_PER_TILE blocks so each tile is ROWS x block fp32 = 128KB in VMEM
(well under the ~16MB budget, leaving room for double buffering).

Scales travel as an (n_blocks, 1) column: a 1-D scale block of 128 rows does
not match the layout XLA gives a 1-D f32 array longer than 128, and Mosaic
refuses it. The column is reshaped to (n_blocks,) outside the kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

ROWS_PER_TILE = 128


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]  # (ROWS, block) fp32
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    o_ref[...] = q * s_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quantize_blocks(x2d: jnp.ndarray, *, block: int = 256,
                    interpret: Optional[bool] = None):
    """x2d: (n_blocks, block) fp32 -> (q int8, scales fp32 (n_blocks,))."""
    n = x2d.shape[0]
    rows = min(ROWS_PER_TILE, n)
    pad = (-n) % rows
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    grid = (x2d.shape[0] // rows,)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, block), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, jnp.int8),
            jax.ShapeDtypeStruct((x2d.shape[0], 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(x2d)
    return q[:n], s[:n, 0]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dequantize_blocks(q: jnp.ndarray, scales: jnp.ndarray, *, block: int = 256,
                      interpret: Optional[bool] = None):
    n = q.shape[0]
    rows = min(ROWS_PER_TILE, n)
    pad = (-n) % rows
    scales = scales.reshape(n, 1)
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
        scales = jnp.pad(scales, ((0, pad), (0, 0)))
    grid = (q.shape[0] // rows,)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, block), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        interpret=interpret_mode(interpret),
    )(q, scales)
    return out[:n]
