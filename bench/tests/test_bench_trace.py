"""The trace reduction on small recorded traces kept in `data/`."""
import json
from pathlib import Path

import pytest

from bench import flops, trace
from bench.peaks import PEAKS

DATA = Path(__file__).resolve().parent / "data"


def _view(name):
    return trace.TraceView.from_json(json.loads((DATA / name).read_text()))


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]


def test_busy_and_kernel_time_by_hand():
    """Two devices. Device 0: a fusion 0-2.5 ms overlapping a copy 1-3 ms,
    the quantize kernel 4-5 ms, the start (6 ms) and done (8.5 ms) of an
    all-gather, the dequantize kernel 10-10.5 ms; device 1 an all-reduce
    of 2 ms. Host spans name the gaps."""
    v = _view("trace_by_hand.json")
    ms = 1e-3
    assert v.busy(0) == pytest.approx((3 + 1 + 0.5 + 0.5 + 0.5) * ms)
    assert v.busy(1) == pytest.approx(2 * ms)
    assert v.busy_s() == pytest.approx((5.5 + 2) / 2 * ms)
    q = v.kernel_ops(0, r"^quantize_blocks")
    d = v.kernel_ops(0, r"^dequantize_blocks")
    assert [o.name for o in q] == ["quantize_blocks.1"] and q[0].dur == pytest.approx(1 * ms)
    assert [o.name for o in d] == ["dequantize_blocks.1"] and d[0].dur == pytest.approx(0.5 * ms)
    b = v.breakdown()
    assert b["device_ops"][0][0] == "fusion.1"
    assert "while.5" not in dict(b["device_ops"])  # a loop holds other ops
    assert b["idle_gaps"][0] == ["recv", pytest.approx(2 * ms)]
    assert len(b["idle_gaps"]) == 4


def test_a_chip_trace_excerpt():
    """24 ops of one 25 MiB batch from a v5e trace of wire-ddp-buckets:
    quantize 77.9 us, dequantize 22.6 us, no two ops overlapping, and the
    quantize call 41% of its HBM roofline (26.2 MB read in 77.9 us)."""
    v = _view("trace_wire_chip.json")
    ops = v.ops[0]
    assert len(ops) == 24
    assert v.busy(0) == pytest.approx(sum(o.dur for o in ops))
    assert v.busy(0) == pytest.approx(1553.8e-6, rel=1e-6)
    (q,) = v.kernel_ops(0, r"^quantize_blocks")
    (d,) = v.kernel_ops(0, r"^dequantize_blocks")
    assert q.dur == pytest.approx(77.932e-6, rel=1e-6)
    assert d.dur == pytest.approx(22.56e-6, rel=1e-6)
    share = flops.quantize_roofline_s(q.text, PEAKS["TPU v5 lite"])[0] / q.dur
    assert share == pytest.approx(26_214_400 / 819e9 / 77.932e-6)
    assert 0.40 < share < 0.42
    assert flops.hbm_bytes(d.text) == 0  # its codes, scales and output stay on chip
    assert v.host_during(q.start, q.end) == "send"


def test_breakdown_keeps_at_most_ten_of_each():
    """The result line's breakdown holds at most 10 device ops and 10 idle
    gaps, the longest first."""
    ops = [[f"fusion.{i}", i * 1e-3, i * 1e-3 + (i + 1) * 1e-5, ""] for i in range(30)]
    v = trace.TraceView.from_json({"devices": {"0": ops}})
    b = v.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0][0] == "fusion.29"
    gaps = [g for _, g in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
