"""The per-stage metrics: the readers' arithmetic on a small recorded trace
kept in `data/`, and traced runs of the tiny cells on the CPU, where the
program's stages land in the profile and the readers find them."""
import dataclasses
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, stages

DATA = Path(__file__).resolve().parent / "data"
ROOT = DATA / "root"
SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
WIRE = ("wire.host_copy_ms", "wire.transfer_ms", "wire.kernel_wait_ms", "wire.fabric_ms")
TRAIN = ("train.host_ms", "train.optimizer_ms")


def _reading(trace, **facts):
    return SimpleNamespace(out=SimpleNamespace(trace_dir=trace), facts=facts,
                           devices=[SimpleNamespace(id=0)])


def _read(metric, r):
    return harness.load_reader(harness.ROOT, metric)(r)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Each section of the recorded trace as a plain trace file of its own,
    and the section itself."""
    data = json.loads((DATA / "trace_stages.json").read_text())
    out = {}
    for name in ("wire", "train"):
        path = tmp_path_factory.mktemp("trace") / f"{name}.json"
        path.write_text(json.dumps(data[name]))
        out[name] = (path, data[name])
    return out


def test_wire_readers_on_a_recorded_trace(recorded):
    """Two 25 MiB batches from a v5e trace: each stage once a batch but the
    fabric reads (13 a batch), one 37 ms stall in `wire.encode.pack`, and the
    four metrics covering 98.8% of the benchmark's send and recv spans."""
    path, data = recorded["wire"]
    r = _reading(path, **data["facts"])
    got = {m: _read(m, r) for m in WIRE}
    assert got == pytest.approx({"wire.host_copy_ms": 25.05757, "wire.transfer_ms": 9.15214,
                                 "wire.kernel_wait_ms": 8.22946, "wire.fabric_ms": 2.52771},
                                abs=1e-5)
    view = stages.load(path)
    assert view.count("wire.recv.fabric") == 26
    assert all(view.count(n) == 2 for n in view.host if n != "wire.recv.fabric")
    around = sum(e - s for _, s, e in data["bench"]) * 1e3 / 2
    assert sum(got.values()) / around == pytest.approx(0.988, abs=0.001)
    (gap, name), *_ = view.idle_gaps(0)
    assert name == "wire.encode.pack" and gap == pytest.approx(0.0370793, abs=1e-7)


def test_train_readers_on_a_recorded_trace(recorded):
    """Two steps from a v5e trace: the host's stages 3.84 ms a step beside a
    345.6 ms wait, and the optimizer scope's 30 ops 37.15 ms of device time
    a step."""
    path, data = recorded["train"]
    r = _reading(path, **data["facts"])
    assert _read("train.host_ms", r) == pytest.approx(3.83911, abs=1e-5)
    assert _read("train.optimizer_ms", r) == pytest.approx(37.15315, abs=1e-5)
    view = stages.load(path)
    assert view.total_s(["train.wait"]) * 1e3 / 2 == pytest.approx(345.579, abs=1e-3)
    assert view.phase_s(0, "grads") > 0 and view.phase_s(0, "transport") == 0


def test_phase_time_counts_nested_ops_once():
    view = stages.Stages({}, {0: [(0.0, 2.0, "jit(step_fn)/optimizer/add:"),
                                  (0.5, 1.0, "jit(step_fn)/optimizer/mul:"),
                                  (3.0, 4.0, "jit(step_fn)/grads/dot:"),
                                  (5.0, 5.5, "jit(step_fn)/optimizer_state/x:")]})
    assert view.phase_s(0, "optimizer") == 2.0
    assert view.phase_s(0, "grads") == 1.0


def test_a_trace_without_stages_reads_nothing(tmp_path):
    """The traced run of a program that opens no stages: every reader
    reports nothing."""
    path = tmp_path / "bare.json"
    path.write_text(json.dumps({"host": {}, "devices": {"0": [[0.0, 1.0, ""]]}}))
    r = _reading(path, batches=3, steps=3)
    assert [_read(m, r) for m in WIRE + TRAIN] == [None] * 6
    assert _read("train.host_ms", _reading(None, steps=3)) is None


def _traced(cell_name, tmp_path, seconds):
    """A traced run of a tiny cell on the CPU, with every per-layer metric
    of the full-size cell that runs the same code."""
    cell = harness.resolve(cell_name, ROOT)
    real = "wire-ddp-buckets" if cell.config["driver"] == "wire" else "train-qwen2-7b-s4k"
    per_layer = [m for m in SPEC["per_layer"] if real in m["workloads"]]
    cell = dataclasses.replace(cell, per_layer=per_layer)
    return harness.execute(cell, seed=2**31 + 5, seconds=seconds, trace=True,
                           require_tpu=False, cache=False, out_dir=tmp_path)


def test_traced_wire_run_reports_the_four_stage_metrics(tmp_path):
    line = _traced("tiny-wire", tmp_path, 0.5)
    assert line["correct"], line["checks"]
    got = {k: line["metrics"][k]["value"] for k in WIRE}
    assert all(v > 0 for v in got.values()), got
    # the stages lie inside the benchmark's send and recv spans
    around = line["metrics"]["wire.send_ms"]["value"] + line["metrics"]["wire.recv_ms"]["value"]
    assert sum(got.values()) <= around


def test_traced_train_run_reports_host_time(tmp_path):
    line = _traced("tiny-train", tmp_path, 0.5)
    assert line["correct"], line["checks"]
    assert line["metrics"]["train.host_ms"]["value"] > 0
    assert "train.optimizer_ms" not in line["metrics"]  # the CPU has no device op line
