"""The leaf kernels' share of the compressed transport's quantize and
dequantize time, on a small trace made by hand: the kernels' instructions
by name, the phases by op_name (`transport/shard_map/quantize` under a
nested shard_map), on two chips."""
import json
from pathlib import Path
from types import SimpleNamespace

from bench import harness
from bench.trace import TraceView

T = "jit(step_fn)/shard_map/transport/shard_map"


def _reading(tmp_path, ops):
    """`ops`: per chip, (instruction, start, end, op_name)."""
    path = Path(tmp_path) / "trace.json"
    path.write_text(json.dumps({"host": {}, "devices": {
        d: [[s, e, op] for _, s, e, op in evs] for d, evs in ops.items()}}))
    view = TraceView.from_json({"devices": {
        d: [[name, s, e] for name, s, e, _ in evs] for d, evs in ops.items()}})
    return SimpleNamespace(out=SimpleNamespace(trace_dir=path), trace=view,
                           devices=[SimpleNamespace(id=int(d)) for d in ops])


def _read(r):
    return harness.load_reader(harness.ROOT, "transport.kernel_share")(r)


def test_the_kernels_share_of_the_quantize_and_dequantize_time(tmp_path):
    chip = [
        ("leaf_quantize.1", 0.000, 0.008, f"{T}/quantize/jit(_quantize)/leaf_quantize/pallas_call"),
        ("copy.3", 0.008, 0.009, f"{T}/quantize/reshape"),
        ("all-gather.5", 0.009, 0.012, f"{T}/exchange/all_gather"),
        ("leaf_dequantize_sum.2", 0.012, 0.018, f"{T}/dequantize/jit(_dequantize_sum)/pallas_call"),
        ("fusion.7", 0.018, 0.019, f"{T}/dequantize/slice"),
        ("fusion.9", 0.019, 0.030, "jit(step_fn)/optimizer/mul"),
    ]
    r = _reading(tmp_path, {"0": chip, "1": chip})
    assert abs(_read(r) - 100.0 * 14 / 16) < 1e-9


def test_nothing_is_read_where_no_leaf_kernel_ran(tmp_path):
    chip = [("fusion.1", 0.0, 0.01, f"{T}/quantize/abs"),
            ("fusion.2", 0.01, 0.02, f"{T}/dequantize/convert")]
    assert _read(_reading(tmp_path, {"0": chip})) is None
    assert _read(_reading(tmp_path, {"0": []})) is None
