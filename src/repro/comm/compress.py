"""Gradient wire formats: int8 block quantization (+ error feedback).

This is the 'serialization chunnel' analogue (exact-match capability — every
peer must speak the same wire format). The jnp implementation here is the
oracle; the Pallas TPU kernel lives in kernels/quantize and is selected with
``use_kernel=True`` on real hardware (validated in interpret mode in tests).
"""
from __future__ import annotations

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


def _pad_to_block(x: jnp.ndarray, block: int) -> jnp.ndarray:
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    return jnp.pad(flat, (0, pad))


def quantize_int8(x: jnp.ndarray, *, block: int = 256,
                  use_kernel: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (any shape) -> (q int8 (nblocks, block), scales fp32 (nblocks,))."""
    if use_kernel:
        from repro.kernels.quantize import ops as qops

        return qops.quantize_int8(x, block=block)
    return quantize_blocks_int8(_pad_to_block(x, block).reshape(-1, block))


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


def dequantize_int8(q: jnp.ndarray, scales: jnp.ndarray, shape, *, block: int = 256,
                    use_kernel: bool = False) -> jnp.ndarray:
    if use_kernel:
        from repro.kernels.quantize import ops as qops

        return qops.dequantize_int8(q, scales, shape, block=block)
    n = 1
    for s in shape:
        n *= s
    flat = (q.astype(jnp.float32) * scales[:, None]).reshape(-1)[:n]
    return flat.reshape(shape)
