"""The program's own stages and the device ops' phases, from the profile of a
traced run.

The program opens `TRACER.stage("<layer>.<stage>")` (`repro.obs.trace`) once
a batch, fabric read or train step; each is a JAX profiler annotation, so
the traced window's `.xplane.pb` holds it on a host plane, on the device
ops' clock. `load(trace_dir)` reads, once per trace file:

- every host event named like a stage (`wire.encode.h2d`, `train.wait`;
  the benchmark's own `bench.*` spans are left to `bench.trace`), and JAX's
  compile annotations (`COMPILE`), by name;
- each device op of the op line, with the phase its HLO `op_name` carries
  (`jax.named_scope`: `grads`, `transport`, `optimizer`).

`Stages.from_json` builds the same from a plain object, the form in which a
small trace is kept for the tests. A trace of a program that opens no
stages gives empty readings, and the readers then report nothing.
"""
from __future__ import annotations

import functools
import glob
import json
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

from bench.trace import CONTAINER, DEVICE_PLANE, OPS_LINE, union

Interval = Tuple[float, float]  # seconds from the start of the trace

STAGE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
COMPILE = ("lower_sharding_computation", "backend_compile_and_load")
OP_NAME_STAT = "tf_op"  # the metadata stat that carries an op's HLO op_name


class Stages:
    def __init__(self, host: Dict[str, List[Interval]],
                 ops: Dict[int, List[Tuple[float, float, str]]]):
        self.host = {k: sorted(v) for k, v in host.items()}
        self.ops = {d: sorted(v) for d, v in ops.items()}  # (start, end, op_name)

    @classmethod
    def from_json(cls, data: dict) -> "Stages":
        return cls({k: [tuple(i) for i in v] for k, v in data["host"].items()},
                   {int(d): [tuple(o) for o in v] for d, v in data.get("devices", {}).items()})

    def total_s(self, names: Iterable[str]) -> float:
        return sum(e - s for n in names for s, e in self.host.get(n, ()))

    def count(self, name: str) -> int:
        return len(self.host.get(name, ()))

    def phase_s(self, dev: int, scope: str) -> float:
        """Device time of the ops that `jax.named_scope(scope)` holds: the
        union of their intervals, so nested events count once."""
        part = f"/{scope}/"
        return sum(e - s for s, e in union(
            (s, e) for s, e, op in self.ops.get(dev, ()) if part in f"/{op}/"))

    def idle_gaps(self, dev: int, top: int = 10) -> List[list]:
        """The `top` longest gaps between device ops, longest first, each as
        [seconds, the stage or compile annotation that overlaps it most]
        ("host" where none does)."""
        busy = union((s, e) for s, e, _ in self.ops.get(dev, ()))
        gaps = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])),
                      reverse=True)[:top]
        return [[g, self.host_during(s, e)] for g, s, e in gaps]

    def host_during(self, s: float, e: float) -> str:
        best, name = 0.0, "host"
        for n, spans in self.host.items():
            for a, b in spans:
                ov = min(e, b) - max(s, a)
                if ov > best:
                    best, name = ov, n
        return name


@functools.lru_cache(maxsize=1)
def _space_class():
    """The few messages of the profiler's XSpace proto that hold event
    metadata, built at run time (`jax.profiler.ProfileData` gives each
    event's own stats, but not those of its metadata, where `tf_op` is).
    A map field is read as the repeated key/value entries it is on the wire,
    and fields not named here (the lines and their events) are skipped."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    f = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto", package="bench_xplane")
    T = descriptor_pb2.FieldDescriptorProto
    spec = {
        "XSpace": [("planes", 1, T.TYPE_MESSAGE, "XPlane")],
        "XPlane": [("name", 2, T.TYPE_STRING, None),
                   ("event_metadata", 4, T.TYPE_MESSAGE, "EventMetadataEntry"),
                   ("stat_metadata", 5, T.TYPE_MESSAGE, "StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, T.TYPE_INT64, None),
                               ("value", 2, T.TYPE_MESSAGE, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, T.TYPE_INT64, None),
                              ("value", 2, T.TYPE_MESSAGE, "XStatMetadata")],
        "XEventMetadata": [("name", 2, T.TYPE_STRING, None),
                           ("stats", 5, T.TYPE_MESSAGE, "XStat")],
        "XStatMetadata": [("name", 2, T.TYPE_STRING, None)],
        "XStat": [("metadata_id", 1, T.TYPE_INT64, None),
                  ("str_value", 5, T.TYPE_STRING, None),
                  ("ref_value", 7, T.TYPE_UINT64, None)],
    }
    for msg, fields in spec.items():
        m = f.message_type.add(name=msg)
        for name, number, kind, ref in fields:
            repeated = kind == T.TYPE_MESSAGE and name != "value"
            m.field.add(name=name, number=number, type=kind,
                        label=T.LABEL_REPEATED if repeated else T.LABEL_OPTIONAL,
                        type_name=f".bench_xplane.{ref}" if ref else None)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _op_names(path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {op event name: its HLO op_name}}, from the `tf_op`
    stat of each op's event metadata."""
    space = _space_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        stat = {e.key: e.value.name for e in plane.stat_metadata}
        ids = {k for k, v in stat.items() if v == OP_NAME_STAT}
        names = out.setdefault(plane.name, {})
        for e in plane.event_metadata:
            for st in e.value.stats:
                if st.metadata_id in ids:
                    names[e.value.name] = st.str_value or stat.get(st.ref_value, "")
    return out


@functools.lru_cache(maxsize=4)
def _parse(path: str) -> Stages:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    op_names = _op_names(path)
    host: Dict[str, List[Interval]] = {}
    ops: Dict[int, List[Tuple[float, float, str]]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                dev = ops.setdefault(int(m.group(2)), [])
                named = op_names.get(plane.name, {})
                for e in line.events:
                    name = e.name.split(" = ", 1)[0].lstrip("%")
                    if not CONTAINER.match(name):
                        s = e.start_ns * 1e-9
                        dev.append((s, s + e.duration_ns * 1e-9, named.get(e.name, "")))
            elif not m:
                for e in line.events:
                    if ((STAGE.match(e.name) and not e.name.startswith("bench."))
                            or e.name in COMPILE):
                        s = e.start_ns * 1e-9
                        host.setdefault(e.name, []).append((s, s + e.duration_ns * 1e-9))
    return Stages(host, ops)


def load(trace_dir: Optional[Path]) -> Optional[Stages]:
    """The stages of the newest `.xplane.pb` under `trace_dir`, parsed once
    per file (or of `trace_dir` itself where it names a plain `.json`);
    None where there is no trace."""
    if trace_dir is None:
        return None
    if str(trace_dir).endswith(".json"):
        with open(trace_dir) as f:
            return Stages.from_json(json.load(f))
    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    return _parse(files[-1]) if files else None


def mean_ms(r, names: Iterable[str], unit: str) -> Optional[float]:
    """Summed time of the stages `names` over the run's `facts[unit]`
    (batches or steps), in ms; None where the trace holds none of them."""
    names = tuple(names)
    view = load(r.out.trace_dir)
    n = r.facts.get(unit)
    if view is None or not n or not any(view.count(x) for x in names):
        return None
    return 1e3 * view.total_s(names) / n
