"""The compressed transport's readers: its HBM bytes by hand, and the
phase arithmetic on a small trace made by hand in the form the program's
scopes take (`transport/shard_map/quantize` under a nested shard_map)."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, stages, transport
from bench.peaks import PEAKS
from bench.reference import lm

NEMO = json.loads((harness.ROOT / "bench/configs/mistral-nemo-12b-pods.json").read_text())


def test_quantize_bytes_by_hand():
    """A chip of pod=2 x data=2 holds half of each matrix and whole norm
    scales: 629,191,680 values in 2,457,780 blocks of 256, every trailing
    width a whole number of blocks. Quantize reads 8 B a value and writes
    5 B and a scale; dequantize reads two copies of codes and scales and
    writes 4 B: 19 B a value and 12 B a block."""
    leaves = transport.chip_leaves(NEMO, 4)
    values = sum(v for v, _ in leaves)
    blocks = sum(b for _, b in leaves)
    half = 4 * 5120 * 4096 // 2  # wq, wo; 16384 x 5120 // 2 for embed and head
    assert values == (4 * half + 2 * 4 * 5120 * 1024 // 2 + 3 * 4 * 5120 * 14336 // 2
                      + 5120 + 2 * 4 * 5120) == 629_191_680
    assert blocks == values // 256 == 2_457_780
    assert transport.quantize_hbm_bytes(NEMO, 4) == 19 * values + 12 * blocks
    least_ms = 1e3 * 11_984_135_280 / PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    assert least_ms == pytest.approx(14.6326, abs=1e-4)


def test_a_leaf_off_the_block_grid_pads_alone():
    cfg = dict(NEMO, hidden_size=80, intermediate_size=224, num_attention_heads=4,
               num_key_value_heads=1, head_dim=16, vocab_size=256, num_hidden_layers=2)
    by = dict(zip((p for p, _ in lm.layout(cfg)), transport.chip_leaves(cfg, 4)))
    assert by["layers/attn/wq/w"] == (2 * 80 * 64 // 2, 2 * 80 // 2)  # 64 wide: 1 block
    assert by["final_norm/scale"] == (80, 1)


def _trace(tmp_path, ops):
    path = Path(tmp_path) / "trace.json"
    path.write_text(json.dumps({"host": {}, "devices": ops}))
    return path


def _reading(path, cfg=NEMO, devices=(0, 1, 2, 3), **facts):
    run = SimpleNamespace(cell=SimpleNamespace(config=cfg))
    return SimpleNamespace(out=SimpleNamespace(trace_dir=path), facts=facts, run=run,
                           devices=[SimpleNamespace(id=d) for d in devices])


def _read(metric, r):
    return harness.load_reader(harness.ROOT, metric)(r)


T = "jit(step_fn)/shard_map/transport"
STEP = {
    "0": [[0.0, 0.30, "jit(step_fn)/shard_map/grads/dot_general"],
          [0.30, 0.31, f"{T}/quantize/add"],
          [0.31, 0.32, f"{T}/shard_map/quantize/abs"],
          [0.32, 0.33, f"{T}/shard_map/exchange/all_gather"],
          [0.325, 0.335, f"{T}/shard_map/exchange/all_gather"],
          [0.335, 0.345, f"{T}/shard_map/dequantize/mul"],
          [0.35, 0.38, "jit(step_fn)/shard_map/optimizer/add"]],
    "1": [[0.0, 0.30, "jit(step_fn)/shard_map/grads/dot_general"],
          [0.30, 0.34, f"{T}/shard_map/quantize/abs"],
          [0.34, 0.36, f"{T}/shard_map/exchange/all_gather"],
          [0.36, 0.37, f"{T}/shard_map/dequantize/mul"]],
}
STEP.update({"2": STEP["0"], "3": STEP["1"]})


def test_transport_readers_on_a_trace_by_hand(tmp_path):
    """One step on four chips, two alike: transport 45 and 70 ms, exchange
    15 and 20 (the overlapping gathers counted once), quantize and
    dequantize 30 and 50; the roofline is the least time of a chip of the
    four over their mean, 40 ms."""
    r = _reading(_trace(tmp_path, STEP), steps=1, peak=PEAKS["TPU v5 lite"])
    assert _read("train.transport_ms", r) == pytest.approx(57.5)
    assert _read("train.exchange_ms", r) == pytest.approx(17.5)
    assert _read("transport_quantize_roofline", r) == pytest.approx(
        100 * 14.6326 / 40.0, abs=1e-3)
    view = stages.load(r.out.trace_dir)
    assert transport.scope_s(view, 0, ("exchange",)) == pytest.approx(0.015)
    assert transport.scope_s(view, 0, ("transport", "dequantize")) == pytest.approx(0.01)


def test_a_program_without_the_scopes_reads_nothing(tmp_path):
    """The parent's compressed path names no quantize, exchange or
    dequantize, and the xla transport no transport at all."""
    flat = {"0": [[0.0, 0.3, "jit(step_fn)/shard_map/grads/dot_general"],
                  [0.3, 0.4, f"{T}/all_gather"]]}
    r = _reading(_trace(tmp_path, flat), devices=(0,), steps=1, peak=PEAKS["TPU v5 lite"])
    assert _read("train.transport_ms", r) == pytest.approx(100.0)
    assert _read("train.exchange_ms", r) is None
    assert _read("transport_quantize_roofline", r) is None
    none = _reading(None, steps=1, peak=PEAKS["TPU v5 lite"])
    assert all(_read(m, none) is None for m in
               ("train.transport_ms", "train.exchange_ms", "transport_quantize_roofline"))
