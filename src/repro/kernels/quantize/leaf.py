"""Pallas TPU kernels: the int8 block format of one gradient leaf, in the
leaf's own layout.

The compressed gradient transport (``comm/collectives.compressed_leaf_sum``)
quantizes each leaf's local shard in blocks of ``block`` values along its
trailing axis and sums the copies the other pods send. Done in jnp, that
needs the leaf as ``(..., nblocks, block)``, which XLA lays out apart from
the leaf's own ``(rows, width)`` tiling and so copies both ways. These two
kernels read and write the leaf as it lies:

- ``quantize_leaf``: the f32 gradient and the error-feedback residual in,
  their sum quantized out: int8 codes ``(rows, width)``, one f32 scale a
  block ``(rows, width // block)`` and the new residual in the leaf's shape.
- ``dequantize_sum``: the ``n`` gathered copies of codes and scales in, their
  f32 sum out (divided by ``n`` for a mean), copy 0 first, as the jnp path
  adds.

The arithmetic is ``compress.quantize_blocks_int8``'s: scale ``amax / 127``
(1 where ``amax`` is 0), codes ``clip(round(x / scale), -127, 127)``.

Tiling: a grid over tiles of ``ROWS_PER_TILE`` rows by a few blocks
(``blocks_per_tile``, at most ``MAX_BLOCKS_PER_TILE``), so a kernel's body
is the same size whatever the leaf's. A tile's scales are columns of the
row tile's whole ``(rows, nblocks)`` block, which stays in VMEM while the
grid walks along the row: Mosaic takes no scale block narrower than that.
Each wrapper is jitted on the leaf's 2-D view ``(rows, width)``, so leaves
of one view share a lowering and a compiled kernel.

A leaf takes the kernels where ``takes_kernel`` says so, from its shape
alone: two axes or more and a trailing width of whole blocks of a multiple
of 128 lanes. Other leaves (a 1-D norm scale, the flat shard of the
hierarchical transport) keep the jnp path. The wire path's kernels
(``quantize.py``) are another layout: rows of one block each, uploaded.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

BLOCK = 256
ROWS_PER_TILE = 256
MAX_BLOCKS_PER_TILE = 4
LANES = 128


def takes_kernel(shape, block: int = BLOCK) -> bool:
    """Whether a leaf of ``shape`` is quantized by these kernels."""
    return (len(shape) >= 2 and block % LANES == 0 and shape[-1] % block == 0
            and math.prod(shape) > 0)


def blocks_per_tile(nblocks: int) -> int:
    """The widest tile, in blocks, that divides a row of ``nblocks``."""
    return max(c for c in range(1, MAX_BLOCKS_PER_TILE + 1) if nblocks % c == 0)


def _tiling(rows: int, width: int, block: int):
    nblocks = width // block
    per = blocks_per_tile(nblocks)
    tr = min(ROWS_PER_TILE, rows)
    return nblocks, per, tr, (pl.cdiv(rows, tr), nblocks // per)


def _quantize_kernel(*refs, block: int, per: int, feedback: bool):
    if feedback:
        x_ref, r_ref, q_ref, s_ref, res_ref = refs
    else:
        (x_ref, q_ref, s_ref, res_ref), r_ref = refs, None
    j = pl.program_id(1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    scales = s_ref[...]
    for k in range(per):
        cols = slice(k * block, (k + 1) * block)
        x = x_ref[:, cols].astype(jnp.float32)
        if feedback:
            x = x + r_ref[:, cols]
        amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
        q = jnp.clip(jnp.round(x / scale), -127, 127)
        q_ref[:, cols] = q.astype(jnp.int8)
        res_ref[:, cols] = x - q * scale
        # the block's scale into its column of the row tile's scales,
        # by a select: Mosaic stores no lane at a dynamic offset
        scales = jnp.where(lanes == j * per + k, scale, scales)
    s_ref[...] = scales


def _dequantize_sum_kernel(q_ref, s_ref, o_ref, *, block: int, per: int, mean: bool):
    j = pl.program_id(1)
    n = q_ref.shape[0]
    lanes = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape[1:], 1)
    scales = [s_ref[i] for i in range(n)]
    for k in range(per):
        cols = slice(k * block, (k + 1) * block)
        # a block's scale column by a select and a lane max (scales are
        # positive): Mosaic slices no lane at a dynamic offset
        pick = lanes == j * per + k
        total = None
        for i in range(n):
            scale = jnp.max(jnp.where(pick, scales[i], 0.0), axis=1, keepdims=True)
            part = q_ref[i, :, cols].astype(jnp.float32) * scale
            total = part if total is None else total + part
        if mean:
            total = total / n
        o_ref[:, cols] = total.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _quantize(x, residual, *, block: int, interpret: bool):
    rows, width = x.shape
    nblocks, per, tr, grid = _tiling(rows, width, block)
    feedback = residual is not None
    tile = pl.BlockSpec((tr, per * block), lambda i, j: (i, j))
    args = (x, residual) if feedback else (x,)
    return pl.pallas_call(
        functools.partial(_quantize_kernel, block=block, per=per, feedback=feedback),
        grid=grid,
        in_specs=[tile] * len(args),
        out_specs=[tile, pl.BlockSpec((tr, nblocks), lambda i, j: (i, 0)), tile],
        out_shape=[jax.ShapeDtypeStruct((rows, width), jnp.int8),
                   jax.ShapeDtypeStruct((rows, nblocks), jnp.float32),
                   jax.ShapeDtypeStruct((rows, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="leaf_quantize",
    )(*args)


@functools.partial(jax.jit, static_argnames=("dtype", "mean", "block", "interpret"))
def _dequantize_sum(codes, scales, *, dtype, mean: bool, block: int, interpret: bool):
    n, rows, width = codes.shape
    nblocks, per, tr, grid = _tiling(rows, width, block)
    return pl.pallas_call(
        functools.partial(_dequantize_sum_kernel, block=block, per=per, mean=mean),
        grid=grid,
        in_specs=[pl.BlockSpec((n, tr, per * block), lambda i, j: (0, i, j)),
                  pl.BlockSpec((n, tr, nblocks), lambda i, j: (0, i, 0))],
        out_specs=pl.BlockSpec((tr, per * block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, width), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="leaf_dequantize_sum",
    )(codes, scales)


def quantize_leaf(x: jnp.ndarray, residual: Optional[jnp.ndarray] = None, *,
                  block: int = BLOCK, interpret: Optional[bool] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``x`` (+ ``residual``, f32, where error feedback is on) quantized:
    (int8 codes ``(rows, width)``, f32 scales ``(rows, width // block)``,
    f32 residual ``x - dequantized`` in ``x``'s shape), where ``rows`` is
    the product of ``x``'s leading axes."""
    view = (math.prod(x.shape[:-1]), x.shape[-1])
    q, scales, new_residual = _quantize(
        x.reshape(view), None if residual is None else residual.reshape(view),
        block=block, interpret=interpret_mode(interpret))
    return q, scales, new_residual.reshape(x.shape)


def dequantize_sum(codes: jnp.ndarray, scales: jnp.ndarray, shape, dtype=jnp.float32, *,
                   mean: bool = False, block: int = BLOCK,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """The sum of ``n`` copies of codes ``(n, rows, width)`` and scales
    ``(n, rows, width // block)``, the mean where ``mean``, in ``shape``."""
    out = _dequantize_sum(codes, scales, dtype=jnp.dtype(dtype), mean=mean, block=block,
                          interpret=interpret_mode(interpret))
    return out.reshape(shape)
