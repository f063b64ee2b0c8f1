"""The compressed gradient transport's phases in a traced train step, and
the HBM bytes its quantize and dequantize work needs, from shapes alone.

The program opens `jax.named_scope("transport")` around the gradient
transport and, inside the int8 path, `quantize`, `exchange` (the
all-gathers over `pod`) and `dequantize`. A nested shard_map may put a
scope of its own between them (`transport/shard_map/quantize`), so a phase
is matched as path segments in order, not as one string. A trace of a
program that opens none of them reads nothing.

Bytes (`quantize_hbm_bytes`), per chip and step, for leaves quantized in
blocks of `BLOCK` along their trailing axis with one f32 scale a block,
sent to the other pods: the quantize reads the f32 gradient and the f32
error-feedback residual and writes the int8 codes, the scales and the new
residual; the dequantize reads the gathered copies of codes and scales, one
a pod, and writes the f32 sum. A chip holds its share of each leaf as the
configuration's deployment places it (`chip_leaves`); the pod count is that
of the configuration's mesh (`train.mesh`).
"""
from __future__ import annotations

import math
from typing import List, Optional, Tuple

from bench.reference import lm as ref_lm
from bench.trace import union

BLOCK = 256
POD_AXIS = {"pods": 2}  # a mesh's `pod` axis: pods is pod=2 x data=chips/2


def pods(cfg: dict) -> int:
    return POD_AXIS[cfg["train"]["mesh"]]


def chip_leaves(cfg: dict, chips: int) -> List[Tuple[int, int]]:
    """(values, blocks) of each gradient leaf as one chip of the
    configuration's mesh holds it: every matrix (two or more axes after the
    layer axis) split in `data = chips / pods` parts by FSDP, along an axis
    that keeps whole blocks of the trailing one at these widths; norm
    scales whole."""
    data = chips // pods(cfg)
    out = []
    for path, shape in ref_lm.layout(cfg):
        size = math.prod(shape)
        width = shape[-1]
        core = shape[1:] if path.startswith("layers/") else shape
        split = data if len(core) >= 2 else 1
        rows = size // width // split
        out.append((size // split, rows * -(-width // BLOCK)))
    return out


def quantize_hbm_bytes(cfg: dict, chips: int) -> int:
    """HBM bytes of one step's quantize and dequantize on one chip."""
    total, n = 0, pods(cfg)
    for values, blocks in chip_leaves(cfg, chips):
        padded = blocks * BLOCK
        quantize = 4 * values + 4 * values + padded + 4 * blocks + 4 * values
        dequantize = n * (padded + 4 * blocks) + 4 * values
        total += quantize + dequantize
    return total


def _in_scope(op_name: str, path: Tuple[str, ...]) -> bool:
    """The segments of `path` appear in `op_name`'s scope path, in order."""
    at = 0
    for seg in op_name.split("/"):
        if at < len(path) and seg == path[at]:
            at += 1
    return at == len(path)


def scope_s(view, dev: int, *paths: Tuple[str, ...]) -> float:
    """Device time on `dev` of the ops under any of `paths` (union of
    intervals, so nested or overlapping events count once)."""
    return sum(e - s for s, e in union(
        (s, e) for s, e, op in view.ops.get(dev, ())
        if any(_in_scope(op, p) for p in paths)))


def per_step_ms(r, *paths: Tuple[str, ...]) -> Optional[float]:
    """Mean over the cell's chips of `scope_s` per step, in ms; None where
    the trace holds no op under them."""
    from bench.stages import load

    view = load(r.out.trace_dir)
    steps = r.facts.get("steps")
    if view is None or not steps:
        return None
    times = [scope_s(view, d.id, *paths) for d in r.devices]
    if not any(times):
        return None
    return 1e3 * sum(times) / len(times) / steps

