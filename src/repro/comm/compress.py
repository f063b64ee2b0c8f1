"""Gradient wire formats: int8 block quantization (+ error feedback).

This is the 'serialization chunnel' analogue (exact-match capability — every
peer must speak the same wire format). The format is defined once, here:
``quantize_blocks_int8`` (scale ``amax / 127``, 1 for an all-zero block;
codes ``clip(round(x / scale), -127, 127)``) and ``dequantize_blocks_int8``
(``q * scale``). Every jnp path runs this pair; the Pallas kernels in
``repro.kernels.quantize`` (``quantize.py`` for the wire, ``leaf.py`` for the
gradient transport) compute the same arithmetic, and their tests compare
them against it.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp


def int8_wire_ratio(block: int = 256) -> float:
    """Wire bytes per f32 payload byte of the int8 block format: one int8 per
    4-byte float plus one f32 scale per block — the ``dcn_bytes_per_byte``
    cost-model term of every chunnel speaking this format."""
    return (1.0 + 4.0 / block) / 4.0


def int8_wire_bytes(shape, block: int = 256) -> int:
    """Bytes one leaf of ``shape`` takes on the wire when quantized in
    blocks along its trailing axis (``to_blocks``): one int8 per value of
    each padded block and one f32 scale per block."""
    width = shape[-1] if shape else 1
    blocks = -(-width // block)
    for s in shape[:-1]:
        blocks *= s
    return blocks * (block + 4)


def to_blocks(x: jnp.ndarray, block: int) -> jnp.ndarray:
    """x in f32 as (..., nblocks, block): blocks along its trailing axis, so
    the leading axes (and whatever sharding they carry) stay as they are. A
    trailing width that is not a multiple of ``block`` is zero-padded; a
    scalar is one block."""
    x = x.astype(jnp.float32).reshape(x.shape or (1,))
    pad = (-x.shape[-1]) % block
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(x.shape[:-1] + (x.shape[-1] // block, block))


def from_blocks(xb: jnp.ndarray, shape) -> jnp.ndarray:
    """Inverse of ``to_blocks``: drop the pad and restore ``shape``."""
    width = shape[-1] if shape else 1
    return xb.reshape(xb.shape[:-2] + (-1,))[..., :width].reshape(shape)


def quantize_blocks_int8(xb: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(..., nblocks, block) f32 -> (int8 codes of the same shape, one f32
    scale per block, (..., nblocks))."""
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scales = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(xb / scales[..., None]), -127, 127).astype(jnp.int8)
    return q, scales


def dequantize_blocks_int8(q: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``quantize_blocks_int8``: (..., nblocks, block) int8 codes
    and (..., nblocks) f32 scales -> f32 values of the codes' shape."""
    return q.astype(jnp.float32) * scales[..., None]


def quantize_int8(x: jnp.ndarray, *, block: int = 256) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (any shape), flattened -> (q int8 (nblocks, block), scales fp32
    (nblocks,))."""
    return quantize_blocks_int8(to_blocks(x.reshape(-1), block))


def dequantize_int8(q: jnp.ndarray, scales: jnp.ndarray, shape) -> jnp.ndarray:
    """Inverse of ``quantize_int8``: drop the pad and restore ``shape``."""
    flat = from_blocks(dequantize_blocks_int8(q, scales), (math.prod(shape),))
    return flat.reshape(shape)
