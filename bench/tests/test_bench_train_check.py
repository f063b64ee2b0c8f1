"""The training cells' comparison on the CPU at a tiny size: a sound run
comes out correct; the control, and each fault planted under the timed path
(the harness's look for a chip skipped), come out not correct."""
import re
from pathlib import Path


from bench import harness
from bench.drivers import train as train_driver
from bench.reference import lm as ref_lm

ROOT = Path(__file__).resolve().parent / "data" / "root"
SEED = 2**31 + 77


def _run(tmp_path, control=None):
    cell = harness.resolve("tiny-train", ROOT)
    return harness.execute(cell, seed=SEED, seconds=0.5, trace=False, control=control,
                           require_tpu=False, cache=False, out_dir=tmp_path)


def test_sound_run_is_correct(tmp_path):
    line = _run(tmp_path)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line["checks"]) == ["loss_gap", "grad_norm_gap", "grad_gap", "change_gap"]


def test_only_numbers_with_a_limit_are_compared(tmp_path):
    cell = harness.resolve("tiny-train", ROOT)
    cell.checks = {"limits": {"grad_gap": 0.05}}
    line = harness.execute(cell, seed=SEED, seconds=0.3, trace=False,
                           require_tpu=False, cache=False, out_dir=tmp_path)
    assert list(line["checks"]) == ["grad_gap"]


def test_state_left_unchanged_is_not_correct(tmp_path):
    line = _run(tmp_path, control="fault:unchanged")
    assert not line["correct"]
    assert line["checks"]["change_gap"]["value"] > 0.9


def test_half_the_batch_left_out_is_not_correct(tmp_path):
    line = _run(tmp_path, control="fault:half_batch")
    assert not line["correct"], line["checks"]


def test_fp8_control_fails_a_limit():
    """The reference at fp8 in the program's place reads past a limit."""
    cell = harness.resolve("tiny-train", ROOT)
    cfg = cell.config
    stream = harness.import_generator("lm_stream").make(cell.traffic, SEED, cfg)
    batches = [stream.batch(i) for i in range(3)]
    ref = ref_lm.run_reference(cfg, cfg["optimizer"], SEED, batches)
    ctl = ref_lm.run_reference(cfg, cfg["optimizer"], SEED, batches, precision="fp8")
    gaps = train_driver.compare_to_reference(cfg, ctl, ref)
    limits = cell.checks["limits"]
    assert any(gaps[k] > limits[k] for k in limits), gaps
    same = train_driver.compare_to_reference(cfg, ref, ref)
    assert all(v == 0 for v in same.values())


def test_a_run_prints_its_step_times(tmp_path, capsys):
    """Each run names its slowest and fastest step on standard error, so a
    run that reads low shows whether one step stalled or every step slowed."""
    _run(tmp_path)
    err = capsys.readouterr().err
    assert re.search(r"bench: \d+ steps, step s min [0-9.]+ median [0-9.]+ max [0-9.]+", err)


def test_the_comparison_covers_every_leaf(tmp_path):
    """The first steps' readings name every leaf of every layer in the
    reference's layout, so no tensor escapes the comparison."""
    import jax

    cell = harness.resolve("tiny-train", ROOT)
    cfg, lay = cell.config, ref_lm.layout(cell.config)
    stream = harness.import_generator("lm_stream").make(cell.traffic, SEED, cfg)
    trainer = train_driver.build_trainer(cfg, cell.traffic)
    state = train_driver.make_state(trainer, lay, SEED)
    _, got = train_driver.first_steps(trainer, state, stream.batch, cfg, lay, SEED)
    layers = cfg["num_hidden_layers"]
    want = {f"{p}[{i}]" if p.startswith("layers/") else p
            for p, _ in lay for i in (range(layers) if p.startswith("layers/") else [0])}
    assert set(got["grad1"]) == set(got["change"]) == want
    assert len(got["losses"]) == train_driver.SETUP_STEPS
    del state, trainer
    jax.clear_caches()
