"""jit'd public wrapper; see repro.kernels for where it runs."""
from __future__ import annotations

from repro.kernels.flash_attention.flash_attention import flash_attention as _fa


def flash_attention(q, k, v, *, causal=True, window=None, block_q=128, block_k=128):
    # block sizes shrink for tiny test shapes
    bq = min(block_q, q.shape[1]) if q.shape[1] >= 8 else q.shape[1]
    bk = min(block_k, k.shape[1]) if k.shape[1] >= 8 else k.shape[1]
    return _fa(q, k, v, causal=causal, window=window, block_q=bq, block_k=bk)
