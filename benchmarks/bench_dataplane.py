"""Data-plane throughput: msgs/s and bytes/s-per-core vs batch size.

Measures the batched hot path (docs/architecture.md §8) across three stacks —
default (raw fabric datapath), compressed (fused Pallas int8 wire), reliable
(windowed ReliableChannel) — at 1/8/64/512-message batches, against the
PR-6-era per-message baseline (global fabric lock, per-message RNG draw,
``queue.Queue`` inbox) replicated below and measured in the same run.

Writes ``benchmarks/out/dataplane.json``; the acceptance gate is
``speedup_batch64`` (batched default stack at batch=64 over the per-message
baseline) ≥ 10x. The driver is single-threaded, so msgs/s IS msgs/s-per-core.
"""
from __future__ import annotations

import json
import queue
import random
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmarks.common import emit
from repro.core.fabric import Fabric, LinkModel, ReliableChannel
from repro.core.runtime import FabricTransport

OUT = Path(__file__).parent / "out" / "dataplane.json"

BATCHES = (1, 8, 64, 512)
PAYLOAD = 64  # bytes per message on the default/reliable stacks


# ---------------------------------------------------------------------------
# Per-message baseline: a faithful replica of the pre-batching fabric
# (PR-6 era): one global lock + RNG draw + byte accounting per message, and a
# queue.Queue inbox delivering one (src, msg) tuple per put/get.
# ---------------------------------------------------------------------------


class _LegacyEndpoint:
    def __init__(self, addr: str, fabric: "_LegacyFabric"):
        self.addr = addr
        self.fabric = fabric
        self.inbox: "queue.Queue[Tuple[str, Any]]" = queue.Queue()

    def send(self, dst: str, msg: Any) -> None:
        self.fabric.send(self.addr, dst, msg)

    def recv(self, timeout: Optional[float] = None) -> Optional[Tuple[str, Any]]:
        try:
            return self.inbox.get(timeout=timeout)
        except queue.Empty:
            return None


class _LegacyFabric:
    def __init__(self, seed: int = 0):
        self._eps: Dict[str, _LegacyEndpoint] = {}
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        # internal tally emulating the legacy fabric's per-send accounting cost
        self.byte_count = 0
        self.msg_count = 0

    def register(self, addr: str) -> _LegacyEndpoint:
        ep = _LegacyEndpoint(addr, self)
        self._eps[addr] = ep
        return ep

    def send(self, src: str, dst: str, msg: Any) -> None:
        size = len(msg) if isinstance(msg, (bytes, str)) else 8
        with self._lock:
            self._rng.random()  # loss draw (loss=0 here, but the draw is paid)
            ep = self._eps.get(dst)
            self.msg_count += 1
            self.byte_count += size
        if ep is not None:
            ep.inbox.put((src, msg))


def bench_per_message_baseline(n_msgs: int) -> dict:
    fab = _LegacyFabric()
    a = fab.register("legacy-a")
    b = fab.register("legacy-b")
    payload = b"x" * PAYLOAD
    t0 = time.perf_counter()
    for _ in range(n_msgs):
        a.send("legacy-b", payload)
    while b.recv(timeout=0) is not None:
        pass
    dt = time.perf_counter() - t0
    return {"n_msgs": n_msgs, "msgs_per_s": n_msgs / dt,
            "bytes_per_s": n_msgs * PAYLOAD / dt}


# ---------------------------------------------------------------------------
# Batched stacks
# ---------------------------------------------------------------------------


def bench_default(batch: int, n_msgs: int) -> dict:
    """Raw fabric datapath: Endpoint.send_batch + recv_many."""
    fab = Fabric()
    a = fab.register("dflt-a")
    b = fab.register("dflt-b")
    msgs = [b"x" * PAYLOAD] * batch
    buf: List[Any] = [None] * max(batch, 64)
    iters = max(1, n_msgs // batch)
    t0 = time.perf_counter()
    for _ in range(iters):
        a.send_batch("dflt-b", msgs)
        got = 0
        while got < batch:
            n = b.recv_many(buf, timeout=0.1)
            if not n:
                break
            got += n
    dt = time.perf_counter() - t0
    total = iters * batch
    return {"batch": batch, "n_msgs": total, "msgs_per_s": total / dt,
            "bytes_per_s": total * PAYLOAD / dt}


def bench_compressed(batch: int, iters: int, *, msg_elems: int = 1024) -> dict:
    """Fused Pallas wire path: one device call per batch (quantize→pack on
    send, unpack→dequantize on recv), chunked over the fabric."""
    from repro.comm.wire import CompressChunnel

    fab = Fabric()
    a = fab.register("cmp-a")
    b = fab.register("cmp-b")
    tx = CompressChunnel(use_kernel=True).connect_wrap(
        FabricTransport(a, "cmp-b").connect_wrap(None))
    rx = CompressChunnel(use_kernel=True).connect_wrap(
        FabricTransport(b, "cmp-a").connect_wrap(None))
    rng = np.random.default_rng(0)
    msgs = [rng.standard_normal(msg_elems).astype(np.float32)
            for _ in range(batch)]
    buf: List[Any] = [None] * batch
    payload_bytes = batch * msg_elems * 4
    tx.send(msgs)  # warmup: jit compile both directions for this shape
    assert rx.recv(buf, timeout=2.0) == batch
    t0 = time.perf_counter()
    for _ in range(iters):
        tx.send(msgs)
        got = 0
        while got < batch:
            n = rx.recv(buf, timeout=2.0)
            if not n:
                break
            got += n
    dt = time.perf_counter() - t0
    total = iters * batch
    wire = fab.counters.sent_bytes
    return {"batch": batch, "n_msgs": total, "msgs_per_s": total / dt,
            "bytes_per_s": iters * payload_bytes / dt,
            "wire_ratio": wire / max(1, (iters + 1) * payload_bytes)}


def bench_reliable(batch: int, n_msgs: int, *, window: int = 32,
                   link_latency_s: float = 2e-4) -> dict:
    """Windowed ReliableChannel over a latency link vs stop-and-wait: up to W
    frames in flight instead of one RTT per frame."""
    fab = Fabric(default_link=LinkModel(latency_s=link_latency_s))
    cli = fab.register("rel-cli")
    srv = fab.register("rel-srv")
    server_chan = ReliableChannel(srv, peer="rel-cli")
    stop = threading.Event()

    def serve():
        while not stop.is_set():
            server_chan.serve_one(lambda src, m: {"ok": m["i"]}, timeout=0.02)

    th = threading.Thread(target=serve, daemon=True)
    th.start()
    chan = ReliableChannel(cli, peer="rel-srv", timeout=0.5, window=window)
    try:
        iters = max(1, n_msgs // batch)
        t0 = time.perf_counter()
        for _ in range(iters):
            replies = chan.request_window([{"i": i} for i in range(batch)])
            assert len(replies) == batch
        dt = time.perf_counter() - t0
    finally:
        stop.set()
        th.join(timeout=1.0)
    total = iters * batch
    return {"batch": batch, "n_msgs": total, "msgs_per_s": total / dt,
            "window": window}


def bench_reliable_stop_and_wait(n_msgs: int, *,
                                 link_latency_s: float = 2e-4) -> dict:
    fab = Fabric(default_link=LinkModel(latency_s=link_latency_s))
    cli = fab.register("saw-cli")
    srv = fab.register("saw-srv")
    server_chan = ReliableChannel(srv, peer="saw-cli")
    stop = threading.Event()

    def serve():
        while not stop.is_set():
            server_chan.serve_one(lambda src, m: {"ok": m["i"]}, timeout=0.02)

    th = threading.Thread(target=serve, daemon=True)
    th.start()
    chan = ReliableChannel(cli, peer="saw-srv", timeout=0.5)
    try:
        t0 = time.perf_counter()
        for i in range(n_msgs):
            chan.request({"i": i})
        dt = time.perf_counter() - t0
    finally:
        stop.set()
        th.join(timeout=1.0)
    return {"n_msgs": n_msgs, "msgs_per_s": n_msgs / dt}


# ---------------------------------------------------------------------------


def _best_of(fn, repeats: int = 3) -> dict:
    """Max-throughput of N repeats: robust to transient CPU contention (the
    gate below compares two measurements, so one depressed sample must not
    flip it)."""
    return max((fn() for _ in range(repeats)), key=lambda r: r["msgs_per_s"])


def run(smoke: bool = False) -> dict:
    scale = 8 if smoke else 1
    baseline = _best_of(lambda: bench_per_message_baseline(40_000 // scale))
    emit("dataplane_permsg_baseline", 1e6 / baseline["msgs_per_s"],
         f"msgs_per_s={baseline['msgs_per_s']:.0f}")

    default: Dict[str, dict] = {}
    for b in BATCHES:
        r = _best_of(lambda b=b: bench_default(b, 160_000 // scale))
        default[str(b)] = r
        emit(f"dataplane_default_b{b}", 1e6 / r["msgs_per_s"],
             f"msgs_per_s={r['msgs_per_s']:.0f};bytes_per_s={r['bytes_per_s']:.0f}")

    compressed: Dict[str, dict] = {}
    comp_batches = (1, 64) if smoke else BATCHES
    for b in comp_batches:
        r = bench_compressed(b, 3 if smoke else 10,
                             msg_elems=256 if smoke else 1024)
        compressed[str(b)] = r
        emit(f"dataplane_compressed_b{b}", 1e6 / r["msgs_per_s"],
             f"msgs_per_s={r['msgs_per_s']:.0f};wire_ratio={r['wire_ratio']:.3f}")

    saw = bench_reliable_stop_and_wait(100 // scale + 20)
    emit("dataplane_reliable_stopwait", 1e6 / saw["msgs_per_s"],
         f"msgs_per_s={saw['msgs_per_s']:.0f}")
    reliable: Dict[str, dict] = {"stop_and_wait": saw}
    rel_batches = (64,) if smoke else BATCHES
    for b in rel_batches:
        r = bench_reliable(b, 2000 // scale)
        reliable[str(b)] = r
        emit(f"dataplane_reliable_b{b}", 1e6 / r["msgs_per_s"],
             f"msgs_per_s={r['msgs_per_s']:.0f};window={r['window']}")

    speedup = default["64"]["msgs_per_s"] / baseline["msgs_per_s"]
    out = {
        "smoke": smoke,
        "per_message_baseline": baseline,
        "default": default,
        "compressed": compressed,
        "reliable": reliable,
        "speedup_batch64": speedup,
    }
    OUT.parent.mkdir(parents=True, exist_ok=True)
    OUT.write_text(json.dumps(out, indent=2))
    emit("dataplane_speedup_batch64", 0.0, f"speedup={speedup:.1f}x")
    assert speedup >= 10.0, (
        f"batched data plane only {speedup:.1f}x over per-message baseline")
    return out


def main() -> None:
    run(smoke=False)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="scaled-down pass for CI; still writes dataplane.json")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(smoke=args.smoke)
