"""The numbers that decide `correct` for the training cells, and the leaf
arithmetic behind them.

A leaf is one tensor of one layer: a stacked leaf (leading layer axis) is
split into `path[l]`. For a quantity q per leaf (a gradient's norm, a
parameter change's norm), the gap of a leaf is

    | q_program - q_reference | / max(q_reference, median over leaves of q_reference)

and a number is the worst leaf's gap: the gap of norms, not the norm of the
difference, measured against the larger of the leaf's own norm and the
median leaf's, since some gradients are all but zero.
"""
from __future__ import annotations

import statistics
from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Leaves whose reference gradient is under this share of the median leaf's
# move under Adam by round-off alone; they are left out of the change.
STILL_LEAF = 1e-3


def real_vocab(path: str, x, vocab: int):
    """The rows of the vocabulary that exist: the program pads the
    embedding and the head to a multiple of 256, and padded rows are never
    read."""
    if path == "embed/table":
        return x[:vocab]
    if path == "lm_head/w":
        return x[:, :vocab]
    return x


def leaf_norms(tree: Dict[str, jnp.ndarray], vocab: int, scale: float = 1.0,
               stacked: Iterable[str] = ()) -> Dict[str, float]:
    """f32 norm of every leaf, per layer for the stacked ones."""
    stacked = set(stacked)

    @jax.jit
    def norms(t):
        out = {}
        for p, x in t.items():
            x = real_vocab(p, x, vocab).astype(jnp.float32) * scale
            if p in stacked:
                out[p] = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
            else:
                out[p] = jnp.sqrt(jnp.sum(jnp.square(x)))
        return out

    return split_layers(jax.device_get(norms(tree)))


def split_layers(norms: Dict[str, np.ndarray]) -> Dict[str, float]:
    out = {}
    for p, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[p] = float(v)
        else:
            for i, x in enumerate(v):
                out[f"{p}[{i}]"] = float(x)
    return out


def worst_gap(prog: Dict[str, float], ref: Dict[str, float],
              leaves: Optional[Iterable[str]] = None) -> tuple:
    """(worst gap, its leaf) over `leaves` (all of `ref` by default)."""
    leaves = list(ref if leaves is None else leaves)
    floor = statistics.median(ref[k] for k in ref)
    worst, at = 0.0, ""
    for k in leaves:
        if k not in prog:
            return float("inf"), k
        gap = abs(prog[k] - ref[k]) / max(ref[k], floor)
        if not np.isfinite(gap) or gap > worst:
            worst, at = (gap if np.isfinite(gap) else float("inf")), k
    return worst, at


def moving_leaves(ref_grad: Dict[str, float]) -> list:
    """Leaves whose reference gradient is at least STILL_LEAF of the median
    leaf's."""
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= STILL_LEAF * med]
