"""jit'd public wrappers matching repro.comm.compress's interface."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.quantize.quantize import dequantize_blocks, quantize_blocks


def quantize_int8(x: jnp.ndarray, *, block: int = 256):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    flat = jnp.pad(flat, (0, pad)).reshape(-1, block)
    return quantize_blocks(flat, block=block)


def dequantize_int8(q: jnp.ndarray, scales: jnp.ndarray, shape, *, block: int = 256):
    n = 1
    for s in shape:
        n *= s
    out = dequantize_blocks(q, scales, block=block)
    return out.reshape(-1)[:n].reshape(shape)
