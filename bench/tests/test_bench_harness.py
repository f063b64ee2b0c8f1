"""The benchmark's harness on the CPU: every cell resolves, a cell added as
files is found, the yardstick's arithmetic, and the refusal to run without
a chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import flops, harness
from bench.generators import buckets, lm_stream
from bench.peaks import PEAKS

ROOT = harness.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = [m["name"] for m in SPEC["per_layer"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_workload_resolves(cell):
    c = harness.resolve(cell)
    assert (harness.BENCH / "drivers" / f"{c.config['driver']}.py").exists()
    assert (harness.BENCH / "generators" / f"{c.traffic['generator']}.py").exists()
    assert c.checks["limits"], "a cell without limits cannot decide correct"
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    assert c.chips in (1, 4)


@pytest.mark.parametrize("metric", METRICS)
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(harness.load_reader(ROOT, metric))


WIDTH = re.compile(r"^(hidden_size|intermediate_size|.*_dim|.*_rank|.*latent.*|.*state_size"
                   r"|.*proj.*_size|.*expan.*|num_experts_per_tok)$")


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_every_configuration_states_its_cuts(config):
    """The file lists each key cut from its source with the published value
    beside it, as `reduced` names them, and no width is cut."""
    entry = next(c for c in SPEC["configs"] if c["name"] == config)
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == config and cfg["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    for key, cut in cfg["reduced"].items():
        assert not WIDTH.search(key), key
        assert cfg[key] == cut["here"] != cut["published"] and cut["why"]


def test_benchmark_json_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).exists()
        assert c["file"].startswith("bench/")


def test_a_cell_added_as_files_is_found(tmp_path):
    """A new traffic mix and cell, given as data files and a BENCHMARK.json
    entry in a fresh checkout, resolve with no edit to any code."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench" / "configs", root / "bench" / "configs")
    shutil.copytree(ROOT / "bench" / "traffic", root / "bench" / "traffic")
    shutil.copytree(ROOT / "bench" / "workloads", root / "bench" / "workloads")
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "wire-mixed-sizes", "config": "wire-int8",
                              "traffic": "records-16x1MiB", "chips": 1, "why": "t"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "wire-ddp-buckets" in m.get("workloads", ()):
            m["workloads"].append("wire-mixed-sizes")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench" / "traffic" / "records-16x1MiB.json").write_text(json.dumps(
        {"generator": "buckets", "tensors": [["w", 1 << 18]] * 16,
         "bucket_floats": 16 << 18, "pool": 2}))
    (root / "bench" / "workloads" / "wire-mixed-sizes.json").write_text(json.dumps(
        {"limits": {"err_steps": 0.6}, "sample_batches": 4}))
    c = harness.resolve("wire-mixed-sizes", root)
    assert c.config["driver"] == "wire"
    assert {m["name"] for m in c.end_to_end} == {"setup_s", "wire_MB_per_s", "wire_p95_ms"}
    assert "wire.send_ms" in {m["name"] for m in c.per_layer}
    gen = harness.import_generator(c.traffic["generator"]).make(c.traffic, 5, c.config)
    assert [m.size for m in gen.batch(0)] == [1 << 18] * 16


# -- FLOPs and bytes, by hand --------------------------------------------------

def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_qwen2_flops_per_token():
    # per layer q 3584*3584, k and v 3584*512, o 3584*3584, MLP 3*3584*18944
    layer = 12_845_056 + 1_835_008 + 1_835_008 + 12_845_056 + 203_685_888
    n = 4 * layer + 3584 * 19008  # 1,000,308,736 with the head, without the embedding
    assert flops.matmul_params(_cfg("qwen2-7b-1chip")) == n == 1_000_308_736
    attn = 12 * 4 * 28 * 128 * 4096
    assert flops.model_flops_per_token(_cfg("qwen2-7b-1chip"), 4096) == 6 * n + attn
    assert 6 * n + attn == 6_706_495_488


def test_mistral_nemo_flops_per_token():
    cfg = {"hidden_size": 5120, "num_attention_heads": 32, "num_key_value_heads": 8,
           "head_dim": 128, "num_hidden_layers": 1, "intermediate_size": 14336,
           "vocab_size": 16384}
    # q 5120*4096, k and v 5120*1024, o 4096*5120 (H*hd != d), MLP 3*5120*14336
    n = 20_971_520 + 5_242_880 + 5_242_880 + 20_971_520 + 220_200_960 + 5120 * 16384
    assert flops.matmul_params(cfg) == n == 356_515_840
    assert flops.model_flops_per_token(cfg, 4096) == 2_340_421_632


def test_quantize_kernel_hbm_bytes_from_its_instruction():
    """As a v5e trace names the kernel: the f32 input in HBM, the int8 codes
    and scales on chip (`S(1)`); the layout constraints after the operands
    are not counted again."""
    text = ('%quantize_blocks.1 = (s8[25600,256]{1,0:T(8,128)(4,1)S(1)}, '
            'f32[25600,1]{1,0:T(8,128)S(1)}) custom-call(f32[25600,256]{1,0:T(8,128)} '
            '%x2d.1), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={f32[25600,256]{1,0}}')
    assert flops.hlo_buffers(text) == [("s8", 6_553_600, False), ("f32", 25_600, False),
                                       ("f32", 6_553_600, True)]
    assert flops.hbm_bytes(text) == 26_214_400
    t, bound = flops.quantize_roofline_s(text, PEAKS["TPU v5 lite"])
    assert bound == "hbm" and t == pytest.approx(26_214_400 / 819e9)
    on_chip = text.replace("{1,0:T(8,128)} %x2d", "{1,0:T(8,128)S(1)} %x2d")
    assert flops.hbm_bytes(on_chip) == 0
    assert flops.quantize_roofline_s(on_chip, PEAKS["TPU v5 lite"])[1] == "flops"


# -- traffic ---------------------------------------------------------------------

def test_ddp_buckets_cover_a_qwen2_layer():
    traffic = json.loads((ROOT / "bench/traffic/ddp-buckets-qwen2-7b.json").read_text())
    plan = buckets.bucket_plan([tuple(t) for t in traffic["tensors"]],
                               traffic["bucket_floats"])
    assert sum(map(sum, plan)) == 233_057_792
    assert traffic["bucket_floats"] == 25 * 2**20 // 4
    assert all(sum(b) == 6_553_600 for b in plan[:-1])
    assert len(plan) == 36 and sum(plan[-1]) == 3_681_792
    assert [sum(b) // 256 for b in plan[:1]] == [25_600]


def test_lm_stream_is_seeded():
    traffic = {"seq_len": 64, "batch": 2, "zipf_a": 1.2, "copy_prob": 0.5}
    a = lm_stream.LMStream(traffic, 2**31 + 7, 500)
    b = lm_stream.LMStream(traffic, 2**31 + 7, 500)
    c = lm_stream.LMStream(traffic, 2**31 + 8, 500)
    assert (a.batch(3)["tokens"] == b.batch(3)["tokens"]).all()
    assert not (a.batch(3)["tokens"] == c.batch(3)["tokens"]).all()
    assert not (a.batch(3)["tokens"] == a.batch(4)["tokens"]).all()
    assert a.batch(0)["tokens"].max() < 500
    assert (a.batch(1)["tokens"][:, 1:] == a.batch(1)["labels"][:, :-1]).all()


def test_wire_batches_are_seeded_with_fixed_sizes():
    traffic = {"tensors": [["a", 250]] * 8, "bucket_floats": 2000, "pool": 3}
    a, b, c = (buckets.Batches(traffic, s) for s in (11, 11, 12))
    assert all((x == y).all() for x, y in zip(a.batch(2), b.batch(2)))
    assert not (a.flat(2) == c.flat(2)).all()
    assert [m.size for m in a.batch(5)] == [m.size for m in c.batch(5)] == [250] * 8
    assert not (a.flat(0) == a.flat(1)).all()


# -- no chip, no result ------------------------------------------------------------

def test_command_on_the_cpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{") for line in p.stdout.splitlines())
