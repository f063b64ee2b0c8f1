"""One run of one cell: resolve it by name, check the chip, hand it to its
driver, reduce the trace, and print the result line.

The harness knows no cell, configuration, traffic mix or metric by name. It
reads `BENCHMARK.json`, loads `bench/configs/<config>.json`,
`bench/traffic/<traffic>.json` and `bench/workloads/<cell>.json`, imports the
driver that the configuration names from `bench/drivers/`, and, in a traced
run, each per-layer metric's reader from `bench/metrics/<metric>.py`.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import importlib.util
import json
import math
import os
import sys
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, Iterator, List, Optional

from bench import faults

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One entry of `workloads`, with everything it names loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    checks: dict  # bench/workloads/<cell>.json: the limits of `correct`
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path


def resolve(name: str, root: Path = ROOT) -> Cell:
    """Find the cell `name` in `<root>/BENCHMARK.json` and load its files."""
    spec = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(root / cfg_entry["file"]),
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        checks=load_json(root / "bench" / "workloads" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer, root=root)


def import_driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def import_generator(name: str):
    return importlib.import_module(f"bench.generators.{name}")


def load_reader(root: Path, metric: str) -> Callable:
    """`read` of `bench/metrics/<metric>.py` (metric names hold dots, so the
    file is loaded by its path)."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Check:
    """One number compared against its limit; `correct` needs value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back from one run."""

    setup_s: float
    metrics: Dict[str, float]  # end-to-end values, setup_s aside
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    window_s: float  # the measured (and, with --trace 1, traced) window
    trace_dir: Optional[Path] = None
    facts: Dict[str, Any] = field(default_factory=dict)  # for metric readers


class Run:
    """The context a driver runs in: the cell, the seed, the clock, the
    devices, host spans and where artefacts go."""

    def __init__(self, cell: Cell, *, seed: int, seconds: float, trace: bool,
                 control: Optional[str], t0: float, devices: list,
                 out_dir: Path):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.control = control
        self.t0 = t0
        self.devices = devices
        self.out_dir = out_dir
        self.spans: Dict[str, List[float]] = {}
        self._recording = False

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        """Host span `bench.<name>`: into the profiler's trace, and its
        duration into `spans[name]` while the window is open."""
        import jax

        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            t = time.perf_counter()
            try:
                yield
            finally:
                if self._recording:
                    self.spans.setdefault(name, []).append(time.perf_counter() - t)

    @contextlib.contextmanager
    def window(self) -> Iterator[None]:
        """The measured window: spans are recorded, and with --trace 1 the
        profiler runs for exactly its length."""
        import jax

        self.spans.clear()
        self._recording = True
        try:
            if self.trace:
                with jax.profiler.trace(str(self.trace_dir)):
                    yield
            else:
                yield
        finally:
            self._recording = False

    @property
    def trace_dir(self) -> Optional[Path]:
        return self.out_dir / "trace" if self.trace else None


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at `<checkout>/.jax_cache`, unless
    JAX_COMPILATION_CACHE_DIR names one; every program is cached, however
    quickly it compiled, so that only a cell's first run compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def chips_for(cell: Cell, require_tpu: bool) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"the first device is {devs[0].platform}, not a TPU")
    if len(devs) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips, JAX sees {len(devs)}")
    return devs[:cell.chips]


def execute(cell: Cell, *, seed: int, seconds: float, trace: bool,
            control: Optional[str] = None, require_tpu: bool = True,
            t0: Optional[float] = None, out_dir: Optional[Path] = None,
            cache: bool = True) -> dict:
    """Run `cell` once and return the result object (without printing)."""
    t0 = time.perf_counter() if t0 is None else t0
    if cache:
        enable_compile_cache(cell.root)
    devices = chips_for(cell, require_tpu)
    out_dir = out_dir or (cell.root / "results" / cell.name / f"seed{seed}-trace{int(trace)}")
    fault = control[len("fault:"):] if control and control.startswith("fault:") else None
    run = Run(cell, seed=seed, seconds=seconds, trace=trace,
              control=None if fault else control, t0=t0, devices=devices, out_dir=out_dir)
    driver = import_driver(cell.config["driver"])
    undo = faults.plant(fault) if fault else None
    try:
        out: Outcome = driver.run(run)
    finally:
        if undo:
            undo()
    return result_line(run, out)


def result_line(run: Run, out: Outcome) -> dict:
    cell = run.cell
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": int(out.memory_peak_bytes)}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics: Dict[str, dict] = {}
    breakdown = None
    if not run.trace:
        values = dict(out.metrics, setup_s=out.setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    else:
        from bench import trace as tr

        view = tr.load(out.trace_dir) if out.trace_dir is not None else None
        reading = Reading(run, out, view)
        for m in cell.per_layer:
            value = load_reader(cell.root, m["name"])(reading)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": units[m["name"]]}
        if view is not None:
            device["busy_s"] = view.busy_s(run.devices)
            device["window_s"] = out.window_s
            breakdown = view.breakdown(run.devices)
    correct = (out.attempted > 0 and out.failed == 0 and bool(out.checks)
               and all(c.ok for c in out.checks))
    line = {"correct": correct, "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


class Reading:
    """What a per-layer metric's reader sees: the run, the driver's outcome
    (its spans and facts) and the reduced trace."""

    def __init__(self, run: Run, out: Outcome, view):
        self.run = run
        self.out = out
        self.trace = view
        self.spans = run.spans
        self.facts = out.facts
        self.devices = run.devices


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    help="read the control in the program's place (fp8, int4), "
                         "or run with a fault planted (fault:<name>, see "
                         "bench/faults.py); never part of a measured run")
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None, t0: Optional[float] = None) -> int:
    args = parse_args(argv)
    try:
        cell = resolve(args.workload)
        line = execute(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), control=args.control, t0=t0)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    sys.stdout.flush()
    for name, c in line["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
