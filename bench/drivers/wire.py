"""Wire cells: `CompressChunnel` (int8 block quantization on the chip, then
chunked frames) over `FabricTransport`, between two endpoints of one
in-process `Fabric`, as `chip_smoke.py` wires it.

Closed loop with one batch in flight: a batch is sent with one `send`, and
the next goes when every message of it has come out of the receiver's
`recv`. A batch's latency runs from the `send` call to its last message out.
Set-up warms every batch size of the traffic. After the window a sample of
the window's batches, drawn from the seed, is compared with the values sent
(`bench.reference.wire`): a message that never comes, or comes in the wrong
shape, is failed.

Configuration keys: `block`, `chunk_bytes`, `use_kernel`.
"""
from __future__ import annotations

import time

import numpy as np

from bench.harness import Check, Outcome, Run, import_generator
from bench.peaks import peaks
from bench.reference import wire as ref_wire

RECV_TIMEOUT_S = 60.0
WARM_BATCHES = 8


class Reservoir:
    """A uniform sample of `k` of the window's batches, drawn from the seed
    as they pass (algorithm R)."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5A3]))
        self.kept = []
        self.seen = 0

    def offer(self, item) -> None:
        if len(self.kept) < self.k:
            self.kept.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.kept[j] = item
        self.seen += 1


def connect(cfg: dict):
    from repro.comm.wire import CompressChunnel
    from repro.core.fabric import Fabric
    from repro.core.runtime import FabricTransport

    fab = Fabric()
    a, b = fab.register("bench-tx"), fab.register("bench-rx")

    def chunnel():
        return CompressChunnel(block=cfg["block"], use_kernel=cfg["use_kernel"],
                               chunk_bytes=cfg["chunk_bytes"])

    tx = chunnel().connect_wrap(FabricTransport(a, "bench-rx").connect_wrap(None))
    rx = chunnel().connect_wrap(FabricTransport(b, "bench-tx").connect_wrap(None))
    return tx, rx


def run(run: Run) -> Outcome:
    cell = run.cell
    cfg, traffic = cell.config, cell.traffic
    block = cfg["block"]
    gen = import_generator(traffic["generator"]).make(traffic, run.seed, cfg)
    tx, rx = connect(cfg)

    def one(i: int):
        msgs = gen.batch(i)
        t = time.perf_counter()
        with run.span("send"):
            tx.send(msgs)
        got = []
        with run.span("recv"):
            while len(got) < len(msgs):
                buf = [None] * (len(msgs) - len(got))
                n = rx.recv(buf, timeout=RECV_TIMEOUT_S)
                if n == 0:
                    break
                got += buf[:n]
        return msgs, got, time.perf_counter() - t

    i = 0
    for i in range(max(WARM_BATCHES, len(gen.plan))):  # every batch size
        one(i)
    i += 1
    setup_s = time.perf_counter() - run.t0

    sample = Reservoir(int(cell.checks["sample_batches"]), run.seed)
    lat = []
    attempted = failed = delivered_bytes = 0
    with run.window():
        t_start = time.perf_counter()
        while True:
            msgs, got, dt = one(i)
            lat.append(dt)
            n_ok = sum(g is not None and np.shape(g) == m.shape for m, g in zip(msgs, got))
            attempted += len(msgs)
            failed += len(msgs) - n_ok
            delivered_bytes += sum(m.nbytes for m, g in zip(msgs, got)
                                   if g is not None and np.shape(g) == m.shape)
            sample.offer((i, got))
            i += 1
            t_end = time.perf_counter()
            if t_end - t_start >= run.seconds:
                break
    window_s = t_end - t_start
    memory = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for d in run.devices)

    err = ctrl = 0.0
    for j, got in sample.kept:
        sent = gen.flat(j)
        flat = (np.concatenate([np.asarray(g, np.float32).reshape(-1) for g in got])
                if got else np.zeros(0, np.float32))
        err = max(err, ref_wire.error_in_steps(sent, flat, block))
        if run.control == "int4":
            ctrl = max(ctrl, ref_wire.error_in_steps(
                sent, ref_wire.quantize_dequantize(sent, block, ref_wire.INT4_LEVELS), block))
    limit = cell.checks["limits"]["err_steps"]
    checks = [Check("err_steps", err, limit)]
    if run.control == "int4":
        checks.append(Check("int4.err_steps", ctrl, limit))
    return Outcome(
        setup_s=setup_s,
        metrics={"wire_MB_per_s": delivered_bytes / 1e6 / window_s,
                 "wire_p95_ms": float(np.percentile(lat, 95)) * 1e3},
        attempted=attempted, failed=failed, checks=checks,
        memory_peak_bytes=memory, window_s=window_s, trace_dir=run.trace_dir,
        facts={"batches": len(lat),
               "peak": peaks(run.devices[0].device_kind) if run.devices[0].platform == "tpu" else None})
