"""`wire.encode_view_share`: its arithmetic on small written traces, nothing
on a trace without its stages, and a traced run of the tiny wire cell on the
CPU, where the program's stages land in the profile."""
import dataclasses
import json
from types import SimpleNamespace

import pytest

from bench import harness

METRIC = "wire.encode_view_share"
SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
TINY_ROOT = harness.ROOT / "bench" / "tests" / "data" / "root"


def _read_trace(tmp_path, host):
    path = tmp_path / "trace.json"
    path.write_text(json.dumps({"host": host, "devices": {"0": [[0.0, 1.0, ""]]}}))
    r = SimpleNamespace(out=SimpleNamespace(trace_dir=path), facts={"batches": 4})
    return harness.load_reader(harness.ROOT, METRIC)(r)


def test_share_counts_view_batches_over_all(tmp_path):
    host = {"wire.encode.pack": [[0.0, 0.1], [1.0, 1.1], [2.0, 2.1], [3.0, 3.1]],
            "wire.encode.views": [[0.01, 0.02], [1.01, 1.02], [3.01, 3.02]],
            "wire.encode.concat": [[2.01, 2.05]]}
    assert _read_trace(tmp_path, host) == 75.0


def test_nothing_without_the_two_stages(tmp_path):
    """The parent's trace: `wire.encode.pack` but neither nested stage."""
    assert _read_trace(tmp_path, {"wire.encode.pack": [[0.0, 0.1]]}) is None
    reader = harness.load_reader(harness.ROOT, METRIC)
    assert reader(SimpleNamespace(out=SimpleNamespace(trace_dir=None), facts={})) is None


def test_traced_tiny_wire_run_reads_the_share(tmp_path):
    """The tiny traffic's buckets (1000 and 810 floats) are no whole number
    of 256-float blocks, so every batch pads on the host and the share is 0;
    it is read all the same, beside the full-size cell's other metrics."""
    cell = harness.resolve("tiny-wire", TINY_ROOT)
    per_layer = [m for m in SPEC["per_layer"] if "wire-ddp-buckets" in m["workloads"]]
    assert METRIC in [m["name"] for m in per_layer]
    cell = dataclasses.replace(cell, per_layer=per_layer)
    line = harness.execute(cell, seed=2**31 + 11, seconds=0.5, trace=True,
                           require_tpu=False, cache=False, out_dir=tmp_path)
    assert line["correct"], line["checks"]
    share = line["metrics"][METRIC]
    assert share["unit"] == "%" and 0.0 <= share["value"] <= 100.0
    assert share["value"] == pytest.approx(0.0)
