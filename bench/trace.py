"""Reduction of a profiler trace to the numbers the per-layer metrics read.

`load(dir)` reads the `.xplane.pb` that `jax.profiler.trace` wrote, with
nothing but JAX: for each device plane (`/device:TPU:<n>`) the events of its
op line ("XLA Ops"), and from the host planes the benchmark's own spans
(`bench.*`). Device and host events are on the same
clock, counted from the start of the trace. An op event is named by its HLO
instruction (`quantize_blocks.1`); the full instruction is kept as its text.
`TraceView` then gives, per device:

- busy time: the union of the intervals in which an op ran;
- a kernel's time: the summed durations of the events that name it;
- the breakdown: ops by total time (loop and call containers left out), and
  the longest idle gaps, each named by the host span that overlaps it most.

`TraceView.from_json` builds the same view from a plain event list, the form
in which a small trace is kept for the tests.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]  # seconds

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINER = re.compile(r"^(while|conditional|call)\b")


class Op:
    __slots__ = ("name", "start", "end", "text")

    def __init__(self, name: str, start: float, end: float, text: str = ""):
        self.name, self.start, self.end, self.text = name, start, end, text

    @property
    def dur(self) -> float:
        return self.end - self.start


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class TraceView:
    def __init__(self, device_ops: Dict[int, List[Op]], host_spans: List[Op]):
        self.ops = {d: sorted(v, key=lambda o: o.start) for d, v in device_ops.items()}
        self.host = sorted(host_spans, key=lambda o: o.start)

    # -- construction ---------------------------------------------------------
    @classmethod
    def from_json(cls, data: dict) -> "TraceView":
        ops = {int(d): [Op(*e) for e in evs] for d, evs in data["devices"].items()}
        return cls(ops, [Op(*e) for e in data.get("host", [])])

    # -- readings ---------------------------------------------------------------
    def device_ids(self, devices=None) -> List[int]:
        if devices is None:
            return sorted(self.ops)
        return [d.id for d in devices if d.id in self.ops]

    def busy(self, dev: int) -> float:
        return sum(e - s for s, e in union((o.start, o.end) for o in self.ops[dev]))

    def busy_s(self, devices=None) -> float:
        ids = self.device_ids(devices)
        return sum(self.busy(d) for d in ids) / len(ids) if ids else 0.0

    def kernel_ops(self, dev: int, pattern: str) -> List[Op]:
        """Ops whose instruction name matches `pattern`."""
        rx = re.compile(pattern)
        return [o for o in self.ops[dev] if rx.search(o.name)]

    def breakdown(self, devices=None, top: int = 10) -> dict:
        ids = self.device_ids(devices)
        by_op: Dict[str, float] = defaultdict(float)
        for d in ids:
            for o in self.ops[d]:
                if not CONTAINER.match(o.name):
                    by_op[o.name] += o.dur / len(ids)
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        if ids:
            busy = union((o.start, o.end) for o in self.ops[ids[0]])
            for (s0, e0), (s1, _) in zip(busy, busy[1:]):
                gaps.append((s1 - e0, e0, s1))
        gaps.sort(reverse=True)
        named = [[self.host_during(s, e), g] for g, s, e in gaps[:top]]
        return {"device_ops": [[n, t] for n, t in ops], "idle_gaps": named}

    def host_during(self, s: float, e: float) -> str:
        """The host span that overlaps [s, e] most, by name ("host" where
        none does)."""
        best, name = 0.0, "host"
        for o in self.host:
            if o.start >= e:
                break
            ov = min(e, o.end) - max(s, o.start)
            if ov > 0 and ov >= best:  # ties go to the inner, later span
                best, name = ov, o.name
        return name


def _op(event) -> Op:
    """An op event: named by its HLO instruction, the full text kept."""
    text = event.name
    name = text.split(" = ", 1)[0].lstrip("%") if " = " in text else text
    s = event.start_ns * 1e-9
    return Op(name, s, s + event.duration_ns * 1e-9, text)


def load(trace_dir: Path) -> Optional[TraceView]:
    """The view of the newest `.xplane.pb` under `trace_dir`, or None."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        return None
    pd = ProfileData.from_file(files[-1])
    dev: Dict[int, List[Op]] = {}
    host: List[Op] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            d = int(m.group(2))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.setdefault(d, []).extend(_op(e) for e in line.events)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        s = e.start_ns * 1e-9
                        host.append(Op(e.name[len("bench."):], s, s + e.duration_ns * 1e-9))
    return TraceView(dev, host)

