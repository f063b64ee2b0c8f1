"""The wire cells' comparison on the CPU at a tiny size (the Pallas kernels
interpreted): a sound run comes out correct; the int4 control, a value
altered where the receiver produces it, and a message that never comes
out, do not."""
from pathlib import Path

import numpy as np

from bench import harness
from bench.drivers import wire as wire_driver
from bench.reference import wire as ref_wire

ROOT = Path(__file__).resolve().parent / "data" / "root"
SEED = 2**31 + 99


def _run(tmp_path, control=None):
    cell = harness.resolve("tiny-wire", ROOT)
    return harness.execute(cell, seed=SEED, seconds=0.3, trace=False, control=control,
                           require_tpu=False, cache=False, out_dir=tmp_path)


def test_sound_run_is_correct(tmp_path):
    line = _run(tmp_path)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert 0.4 < line["checks"]["err_steps"]["value"] <= 0.5 + 1e-6


def test_int4_control_fails_the_limit(tmp_path):
    line = _run(tmp_path, control="int4")
    assert line["checks"]["int4.err_steps"]["value"] > line["checks"]["int4.err_steps"]["limit"]


def test_an_altered_value_is_not_correct(tmp_path):
    line = _run(tmp_path, control="fault:altered")
    assert not line["correct"]
    assert line["checks"]["err_steps"]["value"] > 1.0


def test_a_message_that_never_comes_is_failed(tmp_path, monkeypatch):
    monkeypatch.setattr(wire_driver, "RECV_TIMEOUT_S", 0.05)
    line = _run(tmp_path, control="fault:dropped")
    assert line["failed"] > 0 and not line["correct"]


def test_reference_error_in_steps():
    rng = np.random.default_rng(0)
    x = rng.standard_normal(1000).astype(np.float32)
    assert ref_wire.error_in_steps(x, x, 256) == 0.0
    q8 = ref_wire.quantize_dequantize(x, 256, ref_wire.INT8_LEVELS)
    assert 0.45 < ref_wire.error_in_steps(x, q8, 256) <= 0.5 + 1e-9
    q4 = ref_wire.quantize_dequantize(x, 256, ref_wire.INT4_LEVELS)
    assert ref_wire.error_in_steps(x, q4, 256) > 5.0
