"""The four-chip training cell on four CPU devices: a tiny
Mistral-NeMo-shaped cell over compressed_int8 on the pods mesh, added as
files in a fresh checkout, comes out correct against the reference, and
with half the batch left out does not; a traced run reports the host
metrics of the full-size cell. The same tiny step compiled for a described
v5e 2x2 names the transport's phases as the device-trace readers match
them."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parent / "data" / "root"
SEED = 2**31 + 77

NEMO = "train-mistral-nemo-pods-int8"
FOUR_CHIP_RUN = """
import dataclasses, json, sys
from pathlib import Path
from bench import harness
root = Path(sys.argv[1])
cell = harness.resolve("tiny-nemo-pods", root)
for control in (None, "fault:half_batch"):
    line = harness.execute(cell, seed=int(sys.argv[2]), seconds=0.5, trace=False,
                           control=control, require_tpu=False, cache=False,
                           out_dir=root / "out")
    print(json.dumps({"control": control, "correct": line["correct"],
                      "checks": line["checks"], "chips": line["device"]["count"]}))
spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
traced = dataclasses.replace(cell, per_layer=[
    m for m in spec["per_layer"] if sys.argv[3] in m.get("workloads", ())])
line = harness.execute(traced, seed=int(sys.argv[2]) + 1, seconds=0.5, trace=True,
                       require_tpu=False, cache=False, out_dir=root / "traced")
print(json.dumps({"traced": line["correct"], "metrics": line["metrics"]}))
import jax
from bench.drivers.train import compare_to_reference
from bench.reference import lm, lm_rows
cfg = cell.config
stream = harness.import_generator("lm_stream").make(cell.traffic, int(sys.argv[2]), cfg)
batches = [stream.batch(i) for i in range(3)]
one = lm.run_reference(cfg, cfg["optimizer"], int(sys.argv[2]), batches)
rows = lm_rows.run_reference(cfg, cfg["optimizer"], int(sys.argv[2]), batches,
                             devices=jax.devices())
print(json.dumps({"rows_against_one": compare_to_reference(cfg, rows, one)}))
"""


def _nemo_pods_checkout(root: Path) -> Path:
    """A cell added as files in a fresh checkout: a tiny decoder of the
    Mistral-NeMo shape (4 heads of 16 against a hidden size of 80, the
    published 0.8; GQA 4:1) on the pods mesh over compressed_int8."""
    for d in ("configs", "traffic", "workloads"):
        (root / "bench" / d).mkdir(parents=True)
    cell = "tiny-nemo-pods"
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 1,
        "configs": [{"name": "tiny-nemo", "source": "tests", "reduced": [], "why": "tests",
                     "file": "bench/configs/tiny-nemo.json"}],
        "workloads": [{"name": cell, "config": "tiny-nemo", "traffic": "lm-4x64",
                       "chips": 4, "why": "tests"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
                        "source": "host_clock"},
                       {"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
                        "bound": 0.01, "source": "host_clock", "workloads": [cell]}],
        "per_layer": [{"name": "train.mfu", "unit": "%", "better": "higher",
                       "source": "host_clock", "layer": "train step",
                       "moves": "train_tokens_per_s", "workloads": [cell]}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cfg = json.loads((ROOT / "bench" / "configs" / "tiny-dense.json").read_text())
    cfg.update(name="tiny-nemo", source="tests", hidden_size=80, intermediate_size=224,
               num_attention_heads=4, num_key_value_heads=1, head_dim=16, vocab_size=256,
               rms_norm_eps=1e-05, qkv_bias=False, driver="train_rows",
               train={"mesh": "pods", "transport": "compressed_int8"})
    (root / "bench" / "configs" / "tiny-nemo.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "lm-4x64.json").write_text(json.dumps(
        {"generator": "lm_stream", "seq_len": 64, "batch": 4, "zipf_a": 1.2,
         "copy_prob": 0.5}))
    (root / "bench" / "workloads" / f"{cell}.json").write_text(json.dumps(
        {"limits": {"grad_gap": 0.02, "change_gap": 0.05}}))
    return root


def _env(**extra):
    return dict(os.environ, JAX_PLATFORMS="cpu", **extra,
                PYTHONPATH=os.pathsep.join([str(harness.ROOT), str(harness.ROOT / "src")]))


@pytest.fixture(scope="module")
def four_cpu_runs(tmp_path_factory):
    """The sound, half-batch and traced runs of the tiny cell and the two
    references, in one process on four CPU devices: its JSON lines."""
    root = _nemo_pods_checkout(tmp_path_factory.mktemp("pods") / "checkout")
    p = subprocess.run([sys.executable, "-c", FOUR_CHIP_RUN, str(root), str(SEED), NEMO],
                       capture_output=True, text=True, timeout=600, cwd=str(harness.ROOT),
                       env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert p.returncode == 0, p.stderr[-4000:]
    return [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]


def test_four_chip_compressed_cell_against_the_reference(four_cpu_runs):
    """On four CPU devices (pod=2 x data=2), the trainer over the negotiated
    int8 transport comes out correct against the plain reference over three
    steps from the seed's weights; with half the batch left out it does not.
    The reference a row a device (`lm_rows`) agrees with `lm`'s."""
    sound, half, _, rows = four_cpu_runs
    # the reference over four devices, a row each, is lm's to f32 rounding
    assert all(v < 1e-5 for v in rows["rows_against_one"].values()), rows
    assert sound["chips"] == 4
    assert sound["correct"], sound["checks"]
    assert not half["correct"], half["checks"]


def test_traced_four_chip_run_reports_the_host_metrics(four_cpu_runs):
    """A traced run of the tiny cell, with every per-layer metric that lists
    the full-size cell, is correct and reads the trainer's host loop; the
    CPU has no device op line, so the device-trace metrics read nothing
    here (their op names are checked against the compiled step below)."""
    traced = four_cpu_runs[2]
    assert traced["traced"]
    got = traced["metrics"]
    assert got["train.host_ms"]["value"] > 0 and got["train.input_ms"]["value"] > 0
    assert not {"train.transport_ms", "train.exchange_ms", "train.optimizer_ms",
                "transport_quantize_roofline"} & set(got)


# The tiny step compiled for a described v5e 2x2 (nothing runs). Each
# instruction of the compiled entry computation becomes one op on each of
# four chips, in the `bench.stages` form, carrying its HLO op_name (what the
# profiler's `tf_op` shows on the chip) and lasting the time its operand and
# result bytes take at the chip's HBM bandwidth. Those bytes count every
# operand whole, more than a fusion may read: an upper bound of the
# traffic, not a measurement.
TPU_REHEARSAL = """
import json, os, re, sys
from pathlib import Path
from types import SimpleNamespace
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh
from bench import harness
from bench.drivers import train
from bench.peaks import PEAKS
from repro.launch import train as launch
from repro.train import step as step_mod

jax.config.update("jax_enable_compilation_cache", False)
root, out = Path(sys.argv[1]), Path(sys.argv[2])
cell = harness.resolve("tiny-nemo-pods", root)
try:
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
except Exception as e:
    print(json.dumps({"no_topology": str(e)}))
    sys.exit(0)
launch.MESHES["pods"] = lambda: Mesh(np.array(topo.devices).reshape(2, 2), ("pod", "data"))
trainer = train.build_trainer(cell.config, cell.traffic)
state = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                     step_mod.state_shapes(trainer.model, trainer.chunnels), trainer.state_sh)
hlo = trainer.jitted.lower(state, trainer.model.batch_specs(trainer.shape)).compile().as_text()
entry = hlo[hlo.index("\\nENTRY "):]
entry = entry[:entry.index("\\n}")]
SHAPE = re.compile(r"\\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)\\[([\\d,]*)\\]")
SIZE = dict(pred=1, s8=1, u8=1, s16=2, u16=2, bf16=2, f16=2, s32=4, u32=4, f32=4,
            s64=8, u64=8, f64=8)
SKIP = re.compile(r"= \\S+ (bitcast|get-tuple-element|tuple|parameter|constant)\\(")
peak = PEAKS["TPU v5 lite"]
ops, t = [], 0.0
for line in entry.splitlines()[1:]:
    if " = " not in line or SKIP.search(line):
        continue
    name = re.search(r'op_name="([^"]*)"', line)
    body = re.split(r", (?:metadata|backend_config|frontend_attributes)=", line)[0]
    nbytes = sum(SIZE[d] * int(np.prod([int(x) for x in dims.split(",") if x]))
                 for d, dims in SHAPE.findall(body))
    ops.append([t, t + nbytes / peak["hbm_bytes_per_s"], name.group(1) if name else ""])
    t = ops[-1][1]
out.write_text(json.dumps({"host": {}, "devices": {str(d): ops for d in range(4)}}))
r = SimpleNamespace(out=SimpleNamespace(trace_dir=out), run=SimpleNamespace(cell=cell),
                    facts={"steps": 1, "peak": peak},
                    devices=[SimpleNamespace(id=d) for d in range(4)])
print(json.dumps({m: harness.load_reader(harness.ROOT, m)(r) for m in sys.argv[3:]}))
"""


def test_compiled_step_names_the_transport_phases(tmp_path):
    """Compiled for a v5e 2x2, the step's instructions carry op_names under
    which the device-trace metrics of the full-size cell find the quantize,
    the exchange, the dequantize and the optimizer; and the transport's
    least bytes (`bench.transport.quantize_hbm_bytes`) are no more than the
    bytes its compiled instructions touch, so the roofline share stays
    under 100% when each instruction moves all of its bytes."""
    root = _nemo_pods_checkout(tmp_path / "checkout")
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    device = [m["name"] for m in spec["per_layer"] if m["source"] == "device_trace"
              and NEMO in m.get("workloads", ()) and m["layer"] != "device"]
    assert sorted(device) == ["train.exchange_ms", "train.optimizer_ms",
                              "train.transport_ms", "transport_quantize_roofline"]
    p = subprocess.run([sys.executable, "-c", TPU_REHEARSAL, str(root),
                        str(tmp_path / "trace.json"), *device],
                       capture_output=True, text=True, timeout=600, cwd=str(harness.ROOT),
                       env=_env(ALLOW_MULTIPLE_LIBTPU_LOAD="1"))
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.splitlines()[-1])
    if "no_topology" in got:  # as tests/test_tpu_compile.py: no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {got['no_topology']}")
    assert all(got[m] is not None and got[m] > 0 for m in device), got
    assert got["train.exchange_ms"] < got["train.transport_ms"]
    assert got["transport_quantize_roofline"] <= 100.0
