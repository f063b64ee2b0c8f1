"""Observability plane: tracer, wire-level trace stitching, metrics
registry, flight recorder, exporters, and the traced KV-switch scenario.

The trace-context edge cases here are part of the PR's acceptance: spans
must survive CompressChunnel chunking/reassembly, WanLink retransmits must
reuse the original span id (tagged ``retry=n``), and dropped messages must
close their span/record with a ``drop_reason``.
"""
from __future__ import annotations

import json
import math
import threading
import time
from collections import deque

import numpy as np
import pytest

from repro.core.chunnel import Datapath
from repro.core.fabric import Fabric, LinkModel, ReliableChannel
from repro.core.telemetry import ConnTelemetry
from repro.obs import (
    NOOP_SPAN,
    RECORDER,
    TRACER,
    FlightRecorder,
    MetricsRegistry,
    parse_prometheus,
    phase_durations,
    render_timeline,
    stitched_trace_ids,
    to_chrome,
)


@pytest.fixture(autouse=True)
def clean_tracer():
    """Every test starts and ends with a disabled, empty tracer."""
    TRACER.disable()
    TRACER.reset()
    yield
    TRACER.disable()
    TRACER.reset()


def spans_named(records, name):
    return [r for r in records if r["kind"] == "span" and r["name"] == name]


def events_named(records, name):
    return [r for r in records if r["kind"] == "event" and r["name"] == name]


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------


class TestTracer:
    def test_disabled_is_noop(self):
        sp = TRACER.span("x")
        assert sp is NOOP_SPAN and not sp
        with sp:
            sp.set(a=1).event("e")
        assert TRACER.ctx() is None
        assert TRACER.collect() == []

    def test_span_nesting_and_trace_id(self):
        TRACER.enable()
        with TRACER.span("outer") as outer:
            with TRACER.span("inner") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
                assert TRACER.ctx() == (inner.trace_id, inner.span_id)
        recs = TRACER.collect()
        assert {r["name"] for r in recs} == {"outer", "inner"}
        by = {r["name"]: r for r in recs}
        assert by["inner"]["parent_id"] == by["outer"]["span_id"]
        assert by["outer"]["dur"] >= by["inner"]["dur"] >= 0

    def test_separate_roots_get_separate_traces(self):
        TRACER.enable()
        with TRACER.span("a"):
            pass
        with TRACER.span("b"):
            pass
        assert len(stitched_trace_ids(TRACER.collect())) == 2

    def test_exception_marks_error_status(self):
        TRACER.enable()
        with pytest.raises(RuntimeError):
            with TRACER.span("boom"):
                raise RuntimeError("nope")
        (rec,) = TRACER.collect()
        assert rec["status"] == "error"
        assert "RuntimeError" in rec["attrs"]["error"]

    def test_adopt_reparents_across_threads(self):
        TRACER.enable()
        got = {}

        def remote(tc):
            with TRACER.adopt(tc):
                with TRACER.span("remote.work") as sp:
                    got["trace"] = sp.trace_id

        with TRACER.span("local") as sp:
            t = threading.Thread(target=remote, args=(sp.ctx,))
            t.start()
            t.join()
        assert got["trace"] == sp.trace_id
        recs = TRACER.collect()
        assert len(stitched_trace_ids(recs)) == 1
        (remote_rec,) = spans_named(recs, "remote.work")
        assert remote_rec["parent_id"] == sp.span_id

    def test_ring_capacity_bounds_history(self):
        TRACER.enable(capacity=16)
        TRACER._tls.__dict__.clear()  # force a fresh ring at the new capacity
        for i in range(100):
            TRACER.record_batch("b", i, i)
        assert len(TRACER.collect()) == 16
        TRACER.enable(capacity=8192)
        TRACER._tls.__dict__.clear()

    def test_batch_record_normalization(self):
        TRACER.enable()
        TRACER.record_batch("fab", 8, 5, {"drop_reason": "loss"})
        (rec,) = TRACER.collect()
        assert rec["kind"] == "batch"
        assert rec["status"] == "partial"          # n_ok < n
        assert rec["attrs"] == {"n": 8, "n_ok": 5, "drop_reason": "loss"}

    def test_collect_clear(self):
        TRACER.enable()
        with TRACER.span("once"):
            pass
        assert len(TRACER.collect(clear=True)) == 1
        assert TRACER.collect() == []


# ---------------------------------------------------------------------------
# Wire-level stitching: ReliableChannel, Compress reassembly, WAN retransmit
# ---------------------------------------------------------------------------


class TestStage:
    def test_stage_lands_on_the_profilers_host_plane(self, tmp_path):
        """With the tracer off, a stage is still a profiler annotation: a
        `jax.profiler.trace` session records it by name, its attrs as stats,
        on a host plane."""
        import glob

        import jax

        with jax.profiler.trace(str(tmp_path)):
            with TRACER.stage("wire.encode.pack", n_blocks=3):
                time.sleep(0.002)
        (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
        found = [(p.name, e) for p in jax.profiler.ProfileData.from_file(path).planes
                 for line in p.lines for e in line.events
                 if e.name == "wire.encode.pack"]
        assert len(found) == 1
        plane, ev = found[0]
        assert plane.startswith("/host:")
        assert ev.duration_ns >= 2e6
        assert dict(ev.stats)["n_blocks"] == 3
        assert TRACER.collect() == []

    def test_stage_records_a_ring_span_only_when_enabled(self, monkeypatch):
        import repro.obs.trace as trace_mod

        def no_span(*a, **k):
            raise AssertionError("a disabled stage built a Span")

        with monkeypatch.context() as m:
            m.setattr(trace_mod, "Span", no_span)
            with TRACER.stage("train.wait"):
                pass
        assert TRACER.collect() == []
        TRACER.enable()
        with TRACER.span("step") as step:
            with TRACER.stage("train.wait", step=7) as st:
                assert TRACER.ctx() == step.ctx  # a stage is never a parent
        (rec,) = spans_named(TRACER.collect(), "train.wait")
        assert rec["parent_id"] == step.span_id and st.span_id == rec["span_id"]
        assert rec["attrs"] == {"step": 7} and rec["dur"] >= 0
        with pytest.raises(ValueError):
            with TRACER.stage("train.readback"):
                raise ValueError("nan")
        (bad,) = spans_named(TRACER.collect(), "train.readback")
        assert bad["status"] == "error"

    def test_one_wire_batch_opens_every_wire_stage_once_in_order(self):
        from repro.comm.wire import CompressChunnel
        from repro.core.runtime import FabricTransport

        fab = Fabric()
        a, b = fab.register("stage-tx"), fab.register("stage-rx")
        ch = CompressChunnel(use_kernel=False)
        tx = ch.connect_wrap(FabricTransport(a, "stage-rx").connect_wrap(None))
        rx = ch.connect_wrap(FabricTransport(b, "stage-tx").connect_wrap(None))
        msgs = [np.linspace(-1, 1, 1000, dtype=np.float32), np.ones((3, 7), np.float32)]
        TRACER.enable()
        tx.send(msgs)
        buf = [None] * 2
        assert rx.recv(buf, timeout=5.0) == 2
        assert np.allclose(buf[1], 1.0)
        names = [r["name"] for r in TRACER.collect()
                 if r["kind"] == "span" and r["name"].startswith("wire.")]
        assert names == [
            "wire.encode.pack", "wire.encode.concat", "wire.encode.h2d", "wire.encode.kernel",
            "wire.encode.d2h", "wire.encode.frame", "wire.send.fabric",
            "wire.recv.fabric", "wire.decode.join", "wire.decode.h2d",
            "wire.decode.kernel", "wire.decode.d2h", "wire.decode.split"]


class TestReliableChannelStitching:
    def test_request_stitches_one_trace_across_endpoints(self):
        TRACER.enable()
        fab = Fabric(default_link=LinkModel(), seed=0)
        cli, srv = fab.register("rc-cli"), fab.register("rc-srv")
        server_chan = ReliableChannel(srv, peer="*")
        stop = threading.Event()

        def handler(src, body):
            with TRACER.span("server.work", attrs={"src": src}):
                return {"type": "ok"}

        def serve():
            while not stop.is_set():
                server_chan.serve_one(handler, timeout=0.05)

        t = threading.Thread(target=serve)
        t.start()
        try:
            chan = ReliableChannel(cli, "rc-srv")
            with TRACER.span("client.call") as root:
                reply = chan.request({"type": "ping"})
            assert reply["type"] == "ok"
        finally:
            stop.set()
            t.join()
        recs = TRACER.collect()
        (work,) = spans_named(recs, "server.work")
        (rc,) = spans_named(recs, "rc.request")
        # the handler span (listener thread) and the rc span (client thread)
        # both live in the caller's trace — that's the over-the-wire stitch
        assert work["trace_id"] == rc["trace_id"] == root.trace_id
        assert rc["parent_id"] == root.span_id
        assert work["parent_id"] == rc["span_id"]
        assert work["thread"] != rc["thread"]

    def test_request_timeout_closes_span_with_drop_reason(self):
        TRACER.enable()
        fab = Fabric(default_link=LinkModel(loss=1.0), seed=0)
        cli = fab.register("to-cli")
        fab.register("to-srv")
        chan = ReliableChannel(cli, "to-srv", timeout=0.01, retries=2)
        with pytest.raises(TimeoutError):
            chan.request({"type": "ping"})
        (rc,) = spans_named(TRACER.collect(), "rc.request")
        assert rc["status"] == "timeout"
        assert rc["attrs"]["drop_reason"] == "no_reply"


class _LoopbackDP(Datapath):
    """In-memory datapath bridging a Compress send side to a recv side."""

    def __init__(self, q: deque):
        self.q = q

    def send(self, msgs):
        self.q.extend(msgs)

    def recv(self, buf, timeout=None):
        n = 0
        while n < len(buf) and self.q:
            buf[n] = self.q.popleft()
            n += 1
        return n


class TestCompressReassemblyCtx:
    def test_span_survives_chunking_and_reassembly(self):
        from repro.comm.wire import CompressChunnel

        TRACER.enable()
        q: deque = deque()
        ch = CompressChunnel(use_kernel=False, chunk_bytes=256)
        tx = ch.connect_wrap(_LoopbackDP(q))
        rx = ch.connect_wrap(_LoopbackDP(q))
        x = np.linspace(-1, 1, 2048, dtype=np.float32)
        with TRACER.span("blob.send") as root:
            tx.send([x])
        buf = [None]
        assert rx.recv(buf) == 1
        recs = TRACER.collect()
        (ev,) = events_named(recs, "wire.reassembled")
        # reassembly on the receive side is parented to the SENDER's span
        assert ev["trace_id"] == root.trace_id
        assert ev["parent_id"] == root.span_id
        assert ev["attrs"]["msgs"] == 1

    def test_eviction_closes_sender_story_with_drop_reason(self):
        from repro.comm.wire import Reassembler, chunk_payload

        TRACER.enable()
        reasm = Reassembler(max_partial=1)
        with TRACER.span("lost.blob") as root:
            frames = chunk_payload(b"x" * 512, {"kind": "t"}, chunk_bytes=128)
        assert frames[0]["hdr"]["tc"] == (root.trace_id, root.span_id)
        reasm.ingest(frames[0])               # partial blob #1 (incomplete)
        other = chunk_payload(b"y" * 512, {"kind": "t"}, chunk_bytes=128)
        reasm.ingest(other[0])                # evicts blob #1
        assert reasm.evicted == 1
        (ev,) = events_named(TRACER.collect(), "wire.evicted")
        assert ev["trace_id"] == root.trace_id
        assert ev["attrs"]["drop_reason"] == "reassembly_overflow"


class TestWanRetransmitSpans:
    def _pair(self, fab, **kw):
        from repro.comm.chunnels import WanLinkChunnel

        epa, epb = fab.register("wa"), fab.register("wb")
        kw.setdefault("use_kernel", False)
        return (WanLinkChunnel(epa, "wb", **kw).connect_wrap(None),
                WanLinkChunnel(epb, "wa", **kw).connect_wrap(None), epa)

    def test_retransmit_reuses_span_id_tagged_retry(self):
        TRACER.enable()
        fab = Fabric(default_link=LinkModel(), seed=13)
        lossy = LinkModel(loss=0.3)
        fab.set_link("wa", "wb", lossy)
        fab.set_link("wb", "wa", lossy)
        dpa, dpb, epa = self._pair(fab, timeout_s=0.02, retries=40)

        sent_tcs = []
        orig = epa.send_batch

        def spy(dst, msgs):
            sent_tcs.extend(m["_tc"] for m in msgs
                            if isinstance(m, dict) and "_tc" in m)
            return orig(dst, msgs)

        epa.send_batch = spy
        out: list = []
        done = threading.Event()

        def rx():
            # keep pumping past the last payload: a LOST final ack must be
            # re-served (re-acked) or the sender's window never completes
            buf = [None] * 4
            deadline = time.monotonic() + 10.0
            while not done.is_set() and time.monotonic() < deadline:
                got = dpb.recv(buf, timeout=0.05)
                out.extend(buf[:got])

        t = threading.Thread(target=rx)
        t.start()
        msgs = [{"i": i} for i in range(6)]
        try:
            for m in msgs:
                dpa.send([m])
        finally:
            done.set()
            t.join()
        assert out == msgs
        assert dpa.retransmits > 0, "loss never forced a retransmit"
        recs = TRACER.collect()
        windows = spans_named(recs, "rc.window")
        assert len(windows) == len(msgs)       # one window span per batch
        # a retransmitted frame keeps its ORIGINAL wire span id: every _tc
        # that went over the wire belongs to a recorded rc.window span
        window_ids = {(w["trace_id"], w["span_id"]) for w in windows}
        assert sent_tcs and set(sent_tcs) <= window_ids
        assert len(sent_tcs) > len(set(sent_tcs)), \
            "resends should repeat the same ctx, not mint new span ids"
        retries = [e for w in windows for e in w["events"]
                   if e["name"] == "retransmit"]
        assert retries and all(e["attrs"]["retry"] >= 1 for e in retries)
        wans = spans_named(recs, "wan.send")
        assert len(wans) == len(msgs) and all(w["status"] == "ok" for w in wans)

    def test_partitioned_send_drops_with_reason(self):
        TRACER.enable()
        fab = Fabric(default_link=LinkModel(), seed=0)
        dead = LinkModel(loss=1.0)
        fab.set_link("wa", "wb", dead)
        fab.set_link("wb", "wa", dead)
        dpa, _dpb, _ = self._pair(fab, timeout_s=0.01, retries=2)
        with pytest.raises(TimeoutError):
            dpa.send([{"i": 0}])
        assert dpa.failed_sends == 1
        recs = TRACER.collect()
        (wan,) = spans_named(recs, "wan.send")
        assert wan["status"] == "dropped"
        assert wan["attrs"]["drop_reason"] == "window_stalled"
        (win,) = spans_named(recs, "rc.window")
        assert win["status"] == "timeout"
        assert win["attrs"]["drop_reason"] == "window_stalled"


class TestFabricDropRecords:
    def test_unroutable_and_loss_record_drop_reason(self):
        TRACER.enable()
        fab = Fabric(default_link=LinkModel(loss=0.5), seed=3)
        a = fab.register("da")
        fab.register("db")
        a.send_batch("db", [b"x"] * 100)
        a.send_batch("ghost", [b"y"] * 4)
        recs = TRACER.collect()
        batches = [r for r in recs if r["kind"] == "batch"
                   and r["name"] == "fabric.send_batch"]
        reasons = {r["attrs"].get("drop_reason") for r in batches}
        assert "loss" in reasons and "unroutable" in reasons
        lossy = next(r for r in batches if r["attrs"].get("drop_reason") == "loss")
        assert lossy["status"] == "partial"
        assert lossy["attrs"]["n_ok"] < lossy["attrs"]["n"]


# ---------------------------------------------------------------------------
# Metrics registry + exporters
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_unifies_counter_families(self):
        fab = Fabric(seed=0)
        a = fab.register("m-a")
        fab.register("m-b")
        a.send_batch("m-b", [b"x" * 8] * 10)
        tel = ConnTelemetry()
        tel.record_send(4, 64, 0.001)
        reg = MetricsRegistry()
        reg.watch("fabric", fab.counters)
        reg.watch("conn", tel, instance="left")
        reg.register("custom", lambda: {"answer": 42})
        snap = reg.collect()
        assert snap["fabric"]["default"]["sent"] == 10
        assert snap["conn"]["left"]["ops"] == 1
        assert snap["custom"]["default"]["answer"] == 42

    def test_prometheus_round_trip(self):
        reg = MetricsRegistry()
        reg.register("fam", lambda: {"x": 1, "nested": {"a": 2.5, "b": 3},
                                     "skipped": "text"})
        text = reg.to_prometheus()
        samples = parse_prometheus(text)
        by = {(s["name"], s["labels"].get("key", "")): s["value"]
              for s in samples}
        assert by[("repro_fam_x", "")] == 1
        assert by[("repro_fam_nested", "a")] == 2.5
        assert by[("repro_fam_nested", "b")] == 3
        assert ("repro_fam_skipped", "") not in by   # non-numeric: JSON only
        assert json.loads(reg.to_json())["fam"]["default"]["skipped"] == "text"

    def test_parse_rejects_malformed(self):
        with pytest.raises(ValueError, match="unparseable"):
            parse_prometheus("this is { not metrics\n")

    def test_failing_source_isolated(self):
        reg = MetricsRegistry()
        reg.register("bad", lambda: 1 / 0)
        reg.register("good", lambda: {"v": 1})
        snap = reg.collect()
        assert "_error" in snap["bad"]["default"]
        assert snap["good"]["default"]["v"] == 1
        parse_prometheus(reg.to_prometheus())   # still emits valid text

    def test_watch_numeric_attr_fallback(self):
        class Bare:
            def __init__(self):
                self.retransmits = 3
                self.timeout = 0.1
                self._private = 9

        reg = MetricsRegistry()
        reg.watch("rc", Bare())
        m = reg.collect()["rc"]["default"]
        assert m == {"retransmits": 3, "timeout": 0.1}


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_dump_noop_when_disabled(self, tmp_path):
        rec = FlightRecorder(out_dir=str(tmp_path))
        assert rec.dump("why") is None
        assert list(tmp_path.iterdir()) == []

    def test_capture_dumps_on_assert_and_reraises(self, tmp_path):
        TRACER.enable()
        rec = FlightRecorder(out_dir=str(tmp_path))
        with TRACER.span("doomed"):
            pass
        with pytest.raises(AssertionError):
            with rec.capture("smoke"):
                assert False, "scenario shape broke"
        (path,) = tmp_path.iterdir()
        assert path.name == "flightrec_smoke_assert.json"
        doc = json.loads(path.read_text())
        # pytest's assertion rewriting appends the expression source; the
        # user-supplied message is the first line
        assert doc["extra"]["assertion"].splitlines()[0] == "scenario shape broke"
        assert any(r["name"] == "doomed" for r in doc["records"])

    def test_capture_passes_through_success(self, tmp_path):
        TRACER.enable()
        rec = FlightRecorder(out_dir=str(tmp_path))
        with rec.capture("smoke"):
            pass
        assert list(tmp_path.iterdir()) == []

    def test_dump_once_dedupes(self, tmp_path):
        TRACER.enable()
        rec = FlightRecorder(out_dir=str(tmp_path))
        assert rec.dump("strand_c1", once=True) is not None
        assert rec.dump("strand_c1", once=True) is None
        assert rec.dumps == 1

    def test_strand_alarm_records_event_and_dumps(self, tmp_path, monkeypatch):
        from repro.obs import flight

        TRACER.enable()
        monkeypatch.setattr(flight.RECORDER, "out_dir", str(tmp_path))
        monkeypatch.setattr(flight.RECORDER, "_dumped", set())
        path = flight.strand_alarm("conn9", "peer-x", 3)
        assert path and "strand_conn9" in path
        (ev,) = events_named(TRACER.collect(), "2pc.strand_alarm")
        assert ev["attrs"]["drop_reason"] == "resync_stalled"
        assert flight.strand_alarm("conn9", "peer-x", 3) is None  # deduped


# ---------------------------------------------------------------------------
# Telemetry window handoff (read-reset race regression)
# ---------------------------------------------------------------------------


class TestTelemetryWindowHandoff:
    def test_window_partitions_ops_exactly_under_concurrent_writes(self):
        """Regression: snapshot() used to read ``self.ops`` twice (once for
        the rate, once for the reset), so ops recorded between the two reads
        vanished from every window. The fix reads once; now consecutive
        snapshots partition the op stream exactly:
        ``round(ops_per_s * window_s) == ops_delta`` for every window."""
        tel = ConnTelemetry()
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                tel.record_send(1, 8, 1e-6)

        t = threading.Thread(target=hammer)
        t.start()
        try:
            prev = tel.snapshot()
            for _ in range(200):
                snap = tel.snapshot()
                window_ops = round(snap["ops_per_s"] * snap["window_s"])
                assert window_ops == snap["ops"] - prev["ops"], \
                    "ops recorded mid-snapshot leaked out of both windows"
                prev = snap
        finally:
            stop.set()
            t.join()

    def test_window_s_key_present_and_sane(self):
        tel = ConnTelemetry()
        tel.record_send(2, 16, 1e-6)
        time.sleep(0.01)
        snap = tel.snapshot()
        assert snap["window_s"] > 0
        assert round(snap["ops_per_s"] * snap["window_s"]) == 1


# ---------------------------------------------------------------------------
# Exporters + the end-to-end scenario
# ---------------------------------------------------------------------------


class TestExport:
    def _records(self):
        TRACER.enable()
        with TRACER.span("controller.tick", attrs={"rule": "r"}) as sp:
            sp.event("vote", peer="p")
            with TRACER.span("reconfig.swap"):
                pass
        TRACER.record_batch("fabric.send_batch", 4, 4)
        return TRACER.collect()

    def test_chrome_trace_shape(self):
        doc = to_chrome(self._records())
        assert doc["displayTimeUnit"] == "ms"
        phases = {e["ph"] for e in doc["traceEvents"]}
        assert {"X", "i", "M"} <= phases
        complete = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in complete} == {"controller.tick",
                                                "reconfig.swap"}
        assert all(e["dur"] > 0 for e in complete)
        instants = {e["name"] for e in doc["traceEvents"] if e["ph"] == "i"}
        assert "controller.tick:vote" in instants
        assert "fabric.send_batch" in instants
        json.dumps(doc)   # must be serializable as-is

    def test_timeline_and_phases(self):
        recs = self._records()
        pd = phase_durations(recs)
        assert "detect" in pd and "swap" in pd
        text = render_timeline(recs)
        assert "switch timeline" in text and "detect" in text

    def test_empty_timeline(self):
        assert render_timeline([]) == "(no phase spans recorded)"


class TestKvSwitchScenario:
    @pytest.mark.slow
    def test_one_stitched_trace_through_the_switch(self, tmp_path):
        from repro.obs.__main__ import check_records
        from repro.obs.scenario import run_kv_switch_scenario

        res = run_kv_switch_scenario(seed=7)
        assert res["committed"], res["decisions"]
        assert res["client_fp"] == res["server_fp"]
        assert "Compact" in res["client_fp"]
        summary = check_records(res["records"])
        assert summary["swaps"] >= 2            # both endpoints, one trace
        names = {r["name"] for r in res["records"] if r["kind"] == "span"}
        assert {"negotiate.client", "negotiate.offer", "negotiate.score",
                "2pc.prepare", "2pc.peer.prepare", "2pc.commit",
                "2pc.peer.commit", "scenario.drain"} <= names
        # the offer span carries the per-candidate negotiation scores
        (offer,) = spans_named(res["records"], "negotiate.offer")
        assert offer["attrs"]["candidates"], "scored offer lost its scores"
        # metrics plane sees every family the scenario touched
        samples = parse_prometheus(res["registry"].to_prometheus())
        families = {s["name"].split("_")[1] for s in samples}
        assert {"fabric", "conn", "controller"} <= families
        # scenario leaves the global tracer the way it found it
        assert not TRACER.enabled


# ---------------------------------------------------------------------------
# PR 10: flight-recorder rotation + Prometheus exposition edge cases
# ---------------------------------------------------------------------------


class TestFlightRecorderRotation:
    def test_oldest_dumps_rotated_out(self, tmp_path):
        TRACER.enable()
        rec = FlightRecorder(out_dir=str(tmp_path), max_dumps=3)
        for i in range(5):
            assert rec.dump(f"r{i}") is not None
        names = sorted(p.name for p in tmp_path.iterdir())
        assert names == ["flightrec_r2.json", "flightrec_r3.json",
                         "flightrec_r4.json"]

    def test_just_written_dump_survives_mtime_ties(self, tmp_path):
        # give every prior dump an identical (newer) mtime: (mtime, name)
        # ordering alone would then delete the newest file — the keep guard
        # must protect it
        import os as _os

        TRACER.enable()
        rec = FlightRecorder(out_dir=str(tmp_path), max_dumps=1)
        rec.dump("a")
        path = rec.dump("z_last")
        for p in tmp_path.iterdir():
            _os.utime(p, (2_000_000_000, 2_000_000_000))
        rec.dump("b")  # triggers rotation over the tied set
        assert (tmp_path / "flightrec_b.json").exists()

    def test_zero_disables_rotation(self, tmp_path):
        TRACER.enable()
        rec = FlightRecorder(out_dir=str(tmp_path), max_dumps=0)
        for i in range(6):
            rec.dump(f"r{i}")
        assert len(list(tmp_path.iterdir())) == 6

    def test_env_var_sets_default_cap(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_FLIGHTREC_KEEP", "2")
        TRACER.enable()
        rec = FlightRecorder(out_dir=str(tmp_path))
        assert rec.max_dumps == 2
        for i in range(4):
            rec.dump(f"r{i}")
        assert len(list(tmp_path.iterdir())) == 2


class TestPrometheusEdgeCases:
    def test_label_escaping_round_trips(self):
        from repro.obs.metrics import _unescape

        reg = MetricsRegistry()
        nasty = 'quote:" back:\\ newline:\n comma:, done'
        reg.register("conn", lambda: {"ops": 1.0}, instance=nasty)
        samples = parse_prometheus(reg.to_prometheus())
        assert samples[0]["labels"]["instance"] == nasty
        # the scanner is left-to-right: the four-char sequence \\n is an
        # escaped backslash then a literal n, NOT a newline
        assert _unescape("\\\\n") == "\\n"
        assert _unescape("\\n") == "\n"
        assert _unescape('\\"x\\"') == '"x"'

    def test_non_finite_values_round_trip(self):
        reg = MetricsRegistry()
        reg.register("conn", lambda: {"nan_v": float("nan"),
                                      "pinf": float("inf"),
                                      "ninf": float("-inf")}, instance="i")
        by_name = {s["name"].rsplit("_", 1)[-1]: s["value"]
                   for s in parse_prometheus(reg.to_prometheus())}
        assert math.isnan(by_name["v"])        # repro_conn_nan_v
        assert by_name["pinf"] == math.inf
        assert by_name["ninf"] == -math.inf

    def test_federated_multi_member_output_parses(self):
        from repro.core.rendezvous import KVStore
        from repro.obs.federate import MetricsFederator, MetricsPublisher

        store = KVStore()
        now = lambda: 5.0
        for name, ops in (("edge-1", 10.0), ('odd"member', 20.0)):
            reg = MetricsRegistry()
            reg.register("conn", lambda o=ops: {"ops_per_s": o},
                         instance=f"{name}/c")
            MetricsPublisher(store, "promfed", name, reg, now=now).publish()
        fed = MetricsFederator(store, "promfed", ttl_s=5.0, now=now)
        samples = parse_prometheus(fed.federated_registry().to_prometheus())
        insts = {s["labels"]["instance"] for s in samples}
        assert 'odd"member/odd"member/c' in insts
        assert "_fleet" in insts
