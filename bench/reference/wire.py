"""Plain reference for the wire cells, in numpy.

The guarantee the configuration states: every value comes out of the
receiver within half a quantization step of the value sent, where a block's
step is its largest magnitude over `levels` (127 for int8). `error_in_steps`
reads how far a batch departs from it, in int8 steps: a sound wire reads at
most 0.5.

`quantize_dequantize(..., levels=7)` is the control: the same block format
with int4 codes, one precision below the int8 the configuration states.
"""
from __future__ import annotations

import numpy as np

INT8_LEVELS = 127
INT4_LEVELS = 7


def _blocks(flat: np.ndarray, block: int) -> np.ndarray:
    flat = np.asarray(flat, np.float32).reshape(-1)
    pad = (-flat.size) % block
    return np.pad(flat, (0, pad)).reshape(-1, block)


def steps(flat: np.ndarray, block: int, levels: int = INT8_LEVELS) -> np.ndarray:
    """Each block's quantization step (1 for an all-zero block)."""
    amax = np.abs(_blocks(flat, block)).max(axis=1).astype(np.float64)
    return np.where(amax > 0, amax / levels, 1.0)


def error_in_steps(sent: np.ndarray, got: np.ndarray, block: int) -> float:
    """Largest |got - sent| over the batch, in int8 steps of its block."""
    x = _blocks(sent, block).astype(np.float64)
    y = _blocks(got, block).astype(np.float64)
    if x.shape != y.shape:
        return float("inf")
    err = np.abs(y - x) / steps(sent, block)[:, None]
    return float(err.max()) if err.size else 0.0


def quantize_dequantize(flat: np.ndarray, block: int, levels: int) -> np.ndarray:
    """The block format with `levels` codes each side of zero, round to
    nearest, in float64."""
    x = _blocks(flat, block).astype(np.float64)
    s = steps(flat, block, levels)[:, None]
    q = np.clip(np.round(x / s), -levels, levels)
    return (q * s).reshape(-1)[:np.asarray(flat).size]
