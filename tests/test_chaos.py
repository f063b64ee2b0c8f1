"""Chaos harness tier-1 suite (docs/architecture.md §9).

Covers the PR-8 satellites: LinkModel edge semantics on the batched data
path, ``recv_many`` timeout semantics, go-back-N retransmission + reply-cache
exactly-once under injected loss, the ``ChaosPlan``/``ChaosInjector``
schedule machinery on a virtual clock, WAN-link chunking/keepalives/bounded
reassembly, and the partition/churn regressions (coordinator crash
mid-commit converges via resync; fleet churn never blocks ``try_commit``).
"""
import threading
import time

import numpy as np
import pytest

from repro.chaos import (
    BLACKHOLE,
    ChaosInjector,
    ChaosPlan,
    VirtualClock,
    node_matches,
)
from repro.core import rendezvous
from repro.core.fabric import Fabric, LinkModel, ReliableChannel
from repro.core.rendezvous import KVStore


def counters_balance(fabric: Fabric) -> bool:
    """Every sent datagram is accounted exactly once (no in-flight timers
    with zero-latency links)."""
    c = fabric.counters
    return c.sent == (c.delivered + c.dropped_loss
                      + c.dropped_unroutable + c.dropped_overflow)


# ---------------------------------------------------------------------------
# LinkModel edges on the batched data path
# ---------------------------------------------------------------------------


class TestLinkModelEdges:
    @pytest.mark.parametrize("n", [0, 1, 7])
    def test_loss_zero_delivers_all(self, seeded_fabric, n):
        f = seeded_fabric(seed=1)
        a, b = f.register("a"), f.register("b")
        msgs = [f"m{i}" for i in range(n)]
        assert a.send_batch("b", msgs) == n
        assert b.pending() == n  # zero latency ⇒ synchronous delivery
        assert f.counters.sent == n and f.counters.delivered == n
        assert counters_balance(f)

    @pytest.mark.parametrize("n", [1, 7])
    def test_loss_one_drops_all(self, seeded_fabric, n):
        f = seeded_fabric(seed=1)
        a, b = f.register("a"), f.register("b")
        f.set_link("a", "b", LinkModel(loss=1.0))
        assert a.send_batch("b", [b"x"] * n) == 0
        assert b.pending() == 0
        assert f.counters.dropped_loss == n
        assert counters_balance(f)

    @pytest.mark.parametrize("n", [0, 1, 7])
    def test_loss_mask_deterministic_per_seed(self, seeded_fabric, n):
        """Same seed ⇒ identical Bernoulli mask at every batch size,
        including the empty and single-message batches."""
        accepted = []
        for _ in range(2):
            f = seeded_fabric(seed=42)
            a, b = f.register("a"), f.register("b")
            f.set_link("a", "b", LinkModel(loss=0.5))
            got = [a.send_batch("b", [f"m{i}" for i in range(n)])
                   for _ in range(8)]
            buf = [None] * 64
            drained = b.recv_many(buf, timeout=0.0)
            accepted.append((got, [m for _, m in buf[:drained]]))
            assert counters_balance(f)
        assert accepted[0] == accepted[1]

    def test_unroutable_counted(self, seeded_fabric):
        f = seeded_fabric()
        a = f.register("a")
        assert a.send_batch("ghost", ["x", "y"]) == 0
        assert f.counters.dropped_unroutable == 2
        assert counters_balance(f)

    def test_jitter_exceeding_latency_still_delivers(self, seeded_fabric):
        """delay = latency + U[0,1)·jitter stays non-negative and finite even
        when jitter dwarfs latency — messages arrive, just late."""
        f = seeded_fabric(seed=3)
        a, b = f.register("a"), f.register("b")
        f.set_link("a", "b", LinkModel(latency_s=0.001, jitter_s=0.02))
        assert a.send_batch("b", ["x", "y", "z"]) == 3
        buf = [None] * 3
        got = 0
        deadline = time.monotonic() + 2.0
        while got < 3 and time.monotonic() < deadline:
            got += b.recv_many(buf, timeout=0.05)
        assert got == 3
        assert f.counters.delivered == 3

    def test_zero_latency_synchronous(self, seeded_fabric):
        f = seeded_fabric()
        a, b = f.register("a"), f.register("b")
        f.set_link("a", "b", LinkModel(latency_s=0.0, jitter_s=0.0))
        a.send_batch("b", ["x"])
        assert b.pending() == 1  # no timer hop on the zero-delay path


# ---------------------------------------------------------------------------
# recv_many timeout semantics
# ---------------------------------------------------------------------------


class TestRecvMany:
    def test_zero_timeout_returns_immediately(self, seeded_fabric):
        f = seeded_fabric()
        b = f.register("b")
        t0 = time.monotonic()
        assert b.recv_many([None] * 4, timeout=0.0) == 0
        assert time.monotonic() - t0 < 0.1

    def test_timeout_expires_empty(self, seeded_fabric):
        f = seeded_fabric()
        b = f.register("b")
        t0 = time.monotonic()
        assert b.recv_many([None] * 4, timeout=0.05) == 0
        assert time.monotonic() - t0 >= 0.04

    def test_first_message_only_never_fills(self, seeded_fabric):
        """Blocks for the FIRST message only — an 8-slot buffer with one
        queued message returns 1, it does not wait for 8."""
        f = seeded_fabric()
        a, b = f.register("a"), f.register("b")
        a.send_batch("b", ["solo"])
        t0 = time.monotonic()
        buf = [None] * 8
        assert b.recv_many(buf, timeout=1.0) == 1
        assert time.monotonic() - t0 < 0.5
        assert buf[0] == ("a", "solo")

    def test_blocks_until_delayed_delivery(self, seeded_fabric):
        f = seeded_fabric()
        a, b = f.register("a"), f.register("b")
        t = threading.Timer(0.05, lambda: a.send_batch("b", ["late"]))
        t.start()
        try:
            assert b.recv_many([None] * 2, timeout=1.0) == 1
        finally:
            t.join()

    def test_max_n_caps_the_drain(self, seeded_fabric):
        f = seeded_fabric()
        a, b = f.register("a"), f.register("b")
        a.send_batch("b", [f"m{i}" for i in range(5)])
        buf = [None] * 8
        assert b.recv_many(buf, max_n=2, timeout=0.0) == 2
        assert [m for _, m in buf[:2]] == ["m0", "m1"]
        assert b.recv_many(buf, timeout=0.0) == 3  # the rest, in order


# ---------------------------------------------------------------------------
# ReliableChannel under injected loss
# ---------------------------------------------------------------------------


class TestReliableUnderLoss:
    def _serve(self, chan, handler, stop):
        while not stop.is_set():
            chan.serve_one(handler, timeout=0.02)

    @pytest.mark.slow
    def test_request_window_retransmits_exactly_once(self, seeded_fabric):
        """30% loss each way: go-back-N repairs every frame, replies come
        back in order, and the reply cache keeps the handler exactly-once."""
        f = seeded_fabric(seed=9)
        c, s = f.register("rc"), f.register("rs")
        lossy = LinkModel(loss=0.3)
        f.set_link("rc", "rs", lossy)
        f.set_link("rs", "rc", lossy)
        client = ReliableChannel(c, "rs", timeout=0.02, retries=40, window=8)
        server = ReliableChannel(s, "rs")
        seen: list = []
        stop = threading.Event()
        t = threading.Thread(target=self._serve, args=(
            server, lambda src, body: seen.append(body) or {"echo": body},
            stop))
        t.start()
        try:
            msgs = [{"i": i} for i in range(25)]
            replies = client.request_window(msgs)
        finally:
            stop.set()
            t.join()
        assert [r["echo"] for r in replies] == msgs       # ordered
        assert seen == msgs                               # exactly-once
        assert client.retransmits > 0                     # loss was repaired
        assert f.counters.dropped_loss > 0

    def test_request_retries_override_fails_fast(self, seeded_fabric):
        f = seeded_fabric()
        c = f.register("rc")
        f.register("dead")
        f.set_link("rc", "dead", BLACKHOLE)
        chan = ReliableChannel(c, "dead", timeout=0.01, retries=100)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            chan.request({"probe": 1}, retries=2)  # per-call budget wins
        assert time.monotonic() - t0 < 0.5
        assert chan.retransmits >= 1

    def test_reply_cache_answers_duplicate_without_handler(self, seeded_fabric):
        f = seeded_fabric()
        c, s = f.register("rc"), f.register("rs")
        server = ReliableChannel(s, "rs")
        calls = []
        frame = {"_seq": 7, "body": {"x": 1}}
        for _ in range(2):  # identical retransmission
            c.send_batch("rs", [frame])
            server.serve_one(lambda src, b: calls.append(b) or {"ok": 1},
                             timeout=0.2)
        assert len(calls) == 1           # handler ran once
        assert server.dup_replies == 1   # duplicate answered from the cache


# ---------------------------------------------------------------------------
# ChaosPlan / ChaosInjector on a virtual clock
# ---------------------------------------------------------------------------


class TestChaosInjector:
    def test_node_matches_prefix_only(self):
        assert node_matches("b", ["b"])
        assert node_matches("b/ctrl", ["b"])
        assert not node_matches("bx", ["b"])

    def test_exactly_one_of_at_or_on(self):
        plan = ChaosPlan()
        with pytest.raises(ValueError):
            plan.crash("n")                       # neither
        with pytest.raises(ValueError):
            plan.crash("n", at=1.0, on="trig")    # both

    def test_schedule_applies_and_autoheals(self, seeded_fabric,
                                            virtual_clock):
        f = seeded_fabric()
        f.register("a"), f.register("b")
        weather = LinkModel(latency_s=0.002, loss=0.4)
        plan = ChaosPlan()
        plan.degrade("a", "b", weather, at=1.0, for_s=2.0, label="w")
        inj = ChaosInjector(f, plan).start(now=virtual_clock())
        inj.poll(now=virtual_clock.advance(0.5))
        assert f.get_link("a", "b").loss == 0.0       # not due yet
        inj.poll(now=virtual_clock.advance(0.6))      # t=1.1: applied
        assert f.get_link("a", "b") == weather
        assert f.get_link("b", "a") == weather        # symmetric
        inj.poll(now=virtual_clock.advance(2.5))      # t=3.6: autohealed
        assert f.get_link("a", "b").loss == 0.0
        assert inj.active_labels() == []
        inj.stop()

    def test_heal_restores_previous_override(self, seeded_fabric,
                                             virtual_clock):
        """A partition layered on a degrade heals back to the DEGRADE, and
        healing the degrade restores the default — LIFO restore."""
        f = seeded_fabric()
        f.register("a"), f.register("b")
        weather = LinkModel(latency_s=0.001, loss=0.2)
        plan = ChaosPlan()
        plan.degrade("a", "b", weather, at=0.0, label="weather")
        plan.partition("a", "b", at=1.0, label="cut")
        plan.heal("cut", at=2.0)
        plan.heal("weather", at=3.0)
        inj = ChaosInjector(f, plan).start(now=virtual_clock())
        inj.poll(now=virtual_clock.advance(0.1))
        assert f.get_link("a", "b") == weather
        inj.poll(now=virtual_clock.advance(1.0))
        assert f.get_link("a", "b").loss == 1.0       # partitioned
        inj.poll(now=virtual_clock.advance(1.0))
        assert f.get_link("a", "b") == weather        # back to the degrade
        inj.poll(now=virtual_clock.advance(1.0))
        assert f.get_link("a", "b").loss == 0.0       # pristine
        inj.stop()

    def test_crash_covers_child_endpoints_and_new_registrations(
            self, seeded_fabric, virtual_clock):
        f = seeded_fabric()
        f.register("n"), f.register("n/ctrl"), f.register("other")
        plan = ChaosPlan()
        plan.crash("n", at=0.0, label="boom")
        inj = ChaosInjector(f, plan).start(now=virtual_clock())
        inj.poll(now=virtual_clock.advance(0.1))
        assert f.get_link("n/ctrl", "other").loss == 1.0
        assert f.get_link("other", "n", ).loss == 1.0
        # a fresh endpoint under the crashed prefix cannot escape the fault
        f.register("n/new")
        assert f.get_link("n/new", "other").loss == 1.0
        inj.stop()                                    # heals everything
        assert f.get_link("n/new", "other").loss == 0.0

    def test_stop_heals_lifo(self, seeded_fabric, virtual_clock):
        f = seeded_fabric()
        f.register("a"), f.register("b")
        weather = LinkModel(loss=0.1)
        plan = ChaosPlan()
        plan.degrade("a", "b", weather, at=0.0)
        plan.partition("a", "b", at=0.0)
        inj = ChaosInjector(f, plan).start(now=virtual_clock())
        inj.poll(now=virtual_clock.advance(0.1))
        inj.stop()
        assert f.get_link("a", "b").loss == 0.0       # fully restored

    def test_churn_is_seed_deterministic(self):
        def labels(seed):
            p = ChaosPlan(seed=seed)
            return p.churn(["m1", "m2", "m3"], start_s=0.0, period_s=1.0,
                           down_s=0.4, rounds=8)

        assert labels(5) == labels(5)
        with pytest.raises(ValueError):
            ChaosPlan().churn(["m1"], start_s=0, period_s=1.0, down_s=1.0,
                              rounds=1)

    def test_trigger_fires_once(self, seeded_fabric, virtual_clock):
        f = seeded_fabric()
        f.register("a"), f.register("b")
        plan = ChaosPlan()
        plan.partition("a", "b", on="go")
        inj = ChaosInjector(f, plan).start(now=virtual_clock())
        assert inj.fire("go") == 1
        assert f.get_link("a", "b").loss == 1.0
        assert inj.fire("go") == 0                    # consumed
        inj.stop()


# ---------------------------------------------------------------------------
# WAN link: chunking, exactly-once, keepalives, bounded reassembly
# ---------------------------------------------------------------------------


def _wan_pair(fabric, a="wa", b="wb", **kw):
    from repro.comm.chunnels import WanLinkChunnel

    epa, epb = fabric.register(a), fabric.register(b)
    kw.setdefault("use_kernel", False)
    dpa = WanLinkChunnel(epa, b, **kw).connect_wrap(None)
    dpb = WanLinkChunnel(epb, a, **kw).connect_wrap(None)
    return dpa, dpb


def _collect(dp, n, out, timeout_s=5.0):
    buf = [None] * n
    deadline = time.monotonic() + timeout_s
    while len(out) < n and time.monotonic() < deadline:
        got = dp.recv(buf, timeout=0.05)
        out.extend(buf[:got])


class TestWanLink:
    def test_mtu_chunking_roundtrip(self, seeded_fabric):
        """A tensor larger than the MTU is chunked, reassembled and decoded
        (int8 block quantization ⇒ bounded error); raw bytes and control
        objects ride the same window exactly."""
        f = seeded_fabric()
        dpa, dpb = _wan_pair(f, mtu_bytes=1024, block=64)
        tensor = np.linspace(-3.0, 3.0, 40 * 130,
                             dtype=np.float32).reshape(40, 130)
        raw = bytes(range(256)) * 9          # 2304 B > one MTU
        obj = {"kind": "ctrl", "i": 7}
        out: list = []
        rx = threading.Thread(target=_collect, args=(dpb, 3, out))
        rx.start()
        dpa.send([tensor, raw, obj])
        rx.join()
        assert len(out) == 3
        got_t, got_raw, got_obj = out
        assert got_t.shape == tensor.shape
        atol = float(np.abs(tensor).max()) / 127  # 2x the quantization step
        assert np.allclose(got_t, tensor, atol=atol)
        assert got_raw == raw                 # raw path is exact
        assert got_obj == obj
        assert dpa.frames_sent > 3            # really chunked

    @pytest.mark.slow
    def test_exactly_once_in_order_under_loss(self, seeded_fabric):
        f = seeded_fabric(seed=13)
        lossy = LinkModel(loss=0.25)
        f.set_link("wa", "wb", lossy)
        f.set_link("wb", "wa", lossy)
        dpa, dpb = _wan_pair(f, timeout_s=0.02, retries=40)
        msgs = [{"i": i} for i in range(12)]
        out: list = []
        rx = threading.Thread(target=_collect, args=(dpb, len(msgs), out))
        rx.start()
        for m in msgs:
            dpa.send([m])                     # delivery-confirmed send
        rx.join()
        assert out == msgs                    # exactly once, in order
        assert dpa.retransmits > 0            # loss really repaired
        assert dpa.failed_sends == 0

    def test_keepalive_detects_partition_and_heal(self, seeded_fabric,
                                                  virtual_clock):
        f = seeded_fabric()
        dpa, dpb = _wan_pair(f, timeout_s=0.01)
        plan = ChaosPlan()
        plan.partition("wa", "wb", at=0.0, label="cut")
        inj = ChaosInjector(f, plan).start(now=virtual_clock())

        served = threading.Event()

        def serve_pings():
            while not served.is_set():
                dpb.recv([None], timeout=0.02)  # pumps serve_one → pong

        t = threading.Thread(target=serve_pings)
        t.start()
        try:
            assert dpa.ping(retries=2)        # pre-fault: pong arrives
            inj.poll(now=virtual_clock.advance(0.1))
            assert not dpa.ping(retries=2)    # partitioned: fail-fast
            assert dpa.keepalive_failures == 1
            inj.stop()                        # heal
            assert dpa.ping(retries=4)
        finally:
            served.set()
            t.join()

    def test_reassembly_is_bounded(self):
        from repro.comm.wire import Reassembler, chunk_payload

        r = Reassembler(max_partial=2)
        heads = [chunk_payload(b"x" * 300, {"kind": "raw"}, chunk_bytes=100)[0]
                 for _ in range(4)]
        for h in heads:                       # 4 openers, bound of 2
            assert r.ingest(h) is None
        assert r.partial_count() <= 2
        assert r.evicted == 2                 # oldest partials dropped

    def test_chunk_payload_edges(self):
        from repro.comm.wire import Reassembler, chunk_payload

        assert len(chunk_payload(b"", {"k": 1}, chunk_bytes=10)) == 1
        assert len(chunk_payload(b"x" * 10, {"k": 1}, chunk_bytes=10)) == 1
        frames = chunk_payload(b"x" * 11, {"k": 1}, chunk_bytes=10)
        assert len(frames) == 2
        assert frames[0]["hdr"] == {"k": 1} and frames[1]["hdr"] is None
        r = Reassembler()
        assert r.ingest(frames[1]) is None    # out-of-order completion works
        payload, hdr = r.ingest(frames[0])
        assert payload == b"x" * 11 and hdr == {"k": 1}


class _Region:
    """One region's client of a ``WanGateway`` hub: the negotiated Select
    [FastWire | WanLink] under a ``wan_region_adaptive`` controller. Each
    tick feeds the controller the probe timeout ratio and the windowed WAN
    go-back-N retransmits per frame."""

    def __init__(self, fabric, name):
        from repro.comm.chunnels import WanLinkChunnel  # registers the policy
        from repro.core import (FabricTransport, LockedConn, Select,
                                conn_controller, make_stack)

        ep_fast = fabric.register(f"{name}/fastlink")
        ep_wan = fabric.register(f"{name}/wanlink")
        self.stack = make_stack(Select(
            FabricTransport(ep_fast, "hub/fast", label="FastWire"),
            WanLinkChunnel(ep_wan, "hub/wan", mtu_bytes=2048, window=8,
                           timeout_s=0.03, retries=8)))
        self.handle = LockedConn(self.stack.preferred())  # FastWire
        self.ctl = conn_controller(
            self.handle, self.stack, policy="wan_region_adaptive",
            policy_params={"breach_timeout_ratio": 0.05,
                           "recover_timeout_ratio": 0.01,
                           "recover_retransmit_ratio": 0.02, "hold": 2},
            cooldown_s=0.15)
        self.rid = self.timeouts = self.probes = 0
        self._prev = (None, 0, 0)  # (datapath id, retransmits, frames_sent)

    def on_wan(self) -> bool:
        return any(c.name == "WanLink" for c in self.handle.stack.chunnels)

    def probe(self, timeout: float = 0.08) -> None:
        self.rid += 1
        self.probes += 1
        if self.on_wan():  # delivery is confirmed by the window's acks
            try:
                self.handle.send([{"rid": self.rid}])
            except TimeoutError:
                self.timeouts += 1
            return
        self.handle.send([{"rid": self.rid}])  # the gateway echoes it
        buf = [None]
        deadline = time.monotonic() + timeout
        while True:
            t = deadline - time.monotonic()
            if t <= 0 or not self.handle.recv(buf, timeout=max(t, 0.0)):
                self.timeouts += 1
                return
            if isinstance(buf[0], dict) and buf[0].get("rid") == self.rid:
                return  # stale echoes of timed-out probes are skipped

    def rtx_ratio(self) -> float:
        if not self.on_wan():
            self._prev = (None, 0, 0)
            return 0.0
        s = self.handle.dp.stats()
        prev_id, prev_rtx, prev_fr = self._prev
        if prev_id != id(self.handle.dp):  # a fresh datapath after a swap
            prev_rtx = prev_fr = 0
        self._prev = (id(self.handle.dp), s["retransmits"], s["frames_sent"])
        return (s["retransmits"] - prev_rtx) / max(1, s["frames_sent"] - prev_fr)

    def tick(self):
        snap = self.handle.telemetry.snapshot()
        snap["link.timeout_ratio"] = self.timeouts / max(1, self.probes)
        snap["link.retransmit_ratio"] = self.rtx_ratio()
        self.timeouts = self.probes = 0
        return self.ctl.tick(snap)


class TestWanRegions:
    def test_lossy_region_moves_to_wanlink_clean_region_stays(self, seeded_fabric):
        """Two regions share one ``WanGateway`` hub. WAN weather (latency,
        jitter, 25% loss) on one region's links, then a short partition on
        top, moves that region to the compressed + reliable WAN option; the
        clean region keeps the fast path in the same run. The WAN wire
        carries chunked, quantized blobs, repairs loss and absorbs the
        partition (failed sends or keepalive misses) without wedging."""
        from repro.serving.gateway import WanGateway

        fabric = seeded_fabric(seed=7, default_link=LinkModel(latency_s=0.0002))
        gw = WanGateway(fabric, "hub")
        wan, dcn = _Region(fabric, "wan-cli"), _Region(fabric, "dcn-cli")
        plan = ChaosPlan(seed=7)
        plan.degrade("wan-cli", "hub", LinkModel(latency_s=0.004, jitter_s=0.002, loss=0.25),
                     at=0.0, label="wan-weather")
        # pulled one tick after the WAN region adopted the WAN stack
        plan.partition("wan-cli", "hub", on="storm", for_s=0.2, label="wan-storm")
        inj = ChaosInjector(fabric, plan).start()
        inj.poll()  # the weather is on before the first probe
        blob = np.arange(64 * 257, dtype=np.float32).reshape(64, 257) - 8000.0
        storm_at, post_storm = None, 0
        try:
            for tick in range(8):
                inj.poll()
                if storm_at == tick:
                    inj.fire("storm")
                for r in (wan, dcn):
                    if r.on_wan():
                        r.handle.dp.ping(retries=2)  # keepalive probe
                    for _ in range(4):
                        r.probe()
                        inj.poll()  # autoheal mid-window
                        time.sleep(0.004)
                    if r.on_wan() and tick % 2 == 0:
                        try:
                            r.handle.send([blob])  # chunked + quantized
                        except TimeoutError:
                            pass  # counted in failed_sends by the datapath
                for r in (wan, dcn):
                    d = r.tick()
                    if r is wan and storm_at is None and d.reason == "switched":
                        storm_at = tick + 1
                if storm_at is not None and tick > storm_at:
                    post_storm += 1
                if post_storm >= 2:
                    break
            link = wan.handle.dp.stats() if wan.on_wan() else {}
        finally:
            inj.stop()
            gw_stats = gw.stats()
            gw.close()

        switches = [d.rule for d in wan.ctl.switch_log()]
        assert switches and switches[0] == "lossy-wan->compressed-reliable"
        caps = [str(c) for ch in wan.handle.stack.chunnels for c in ch.capabilities()]
        assert "WanLink" in [c.name for c in wan.handle.stack.chunnels]
        assert any("wan-gbn" in c for c in caps) and any("q8b" in c for c in caps)
        assert not dcn.ctl.switch_log()
        assert "FastWire" in [c.name for c in dcn.handle.stack.chunnels]
        assert gw_stats["wan_blobs"] >= 1
        assert link.get("retransmits", 0) > 0
        assert link.get("failed_sends", 0) + link.get("keepalive_failures", 0) > 0


# ---------------------------------------------------------------------------
# Partition / churn regressions
# ---------------------------------------------------------------------------


class TestPartitionRegressions:
    @pytest.mark.slow
    def test_coordinator_crash_mid_commit_converges(self, seeded_fabric):
        """2PC coordinator crashes exactly at the commit point (before any
        phase-2 notification): the prepared peer's resync queries fail while
        the crash holds, then converge after the restart — zero stranded
        prepared peers, every survivor on the committed epoch."""
        from repro.core import (
            FabricTransport,
            FnChunnel,
            HostAgent,
            LockedConn,
            Select,
            make_stack,
        )

        f = seeded_fabric(default_link=LinkModel(latency_s=0.0002), seed=17)
        hA, hB = HostAgent(f, "cA"), HostAgent(f, "cB")
        conn = "reg-conn"

        def stack_for(tag):
            ep = f.register(f"{tag}/data")
            return make_stack(
                Select(FnChunnel(fn_name="Blue", on_send=lambda m: m),
                       FnChunnel(fn_name="Green", on_send=lambda m: m)),
                FabricTransport(ep, "hub"))

        stA, stB = stack_for("cA"), stack_for("cB")
        handleA = LockedConn(stA.preferred())
        target = stA.options()[1]
        hB.register_participant(conn, LockedConn(stB.preferred()), stB.find,
                                resync_after_s=0.08)

        plan = ChaosPlan()
        plan.crash("cA", on="mid_commit", label="boom")
        plan.restart("boom", at=0.3)
        inj = ChaosInjector(f, plan).start()
        record = hA.record_decision
        hA.record_decision = (lambda cid, epoch, fp:
                              (record(cid, epoch, fp),
                               inj.fire("mid_commit")) and None)
        try:
            ok = hA.reconfigure_multilateral(handleA, target, ["cB"], conn,
                                             timeout=0.03, retries=2)
            assert ok                         # presumed commit
            part = hB.participant(conn)
            assert part.prepared is not None  # stranded while A is down
            deadline = time.monotonic() + 4.0
            while time.monotonic() < deadline and part.prepared is not None:
                inj.poll()
                time.sleep(0.01)
            assert part.prepared is None      # zero stranded prepared peers
            assert part.resync_failures >= 1  # the crash really blocked it
            assert part.epoch == handleA.stats.switches == 1
            assert (part.handle.stack.fingerprint()
                    == handleA.stack.fingerprint()
                    == target.fingerprint())
        finally:
            inj.stop()
            hA.close()
            hB.close()

    def test_churn_during_aggregation_unblocks_try_commit(self):
        """A member crashing mid-aggregation-window stops heartbeating but
        still sits in the rendezvous membership map: ``try_commit`` pends on
        its ack until the aggregator's TTL expiry evicts it — never blocked
        past one aggregation pass (all on virtual time)."""
        from repro.core.telemetry import ConnTelemetry
        from repro.fleet import FleetAggregator, FleetPublisher
        from repro.fleet.publish import fleet_conn_id

        clk = VirtualClock(0.0)
        store = KVStore()
        conn = fleet_conn_id("f1")
        members = ("ma", "mb", "mc")
        for m in members:
            rendezvous.join(store, conn, m, ["fpX"], [["dX"]], lambda d: 0)
        pubs = {m: FleetPublisher(store, "f1", m, ConnTelemetry(), now=clk)
                for m in members}
        for p in pubs.values():
            p.publish(now=clk())
        agg = FleetAggregator(store, "f1", ttl_s=0.5, now=clk)

        # mc crashes (stops heartbeating); ma proposes, mb acks
        epoch = rendezvous.propose_transition(store, conn, "ma", "fpY", ["dY"])
        rendezvous.vote(store, conn, "mb", epoch, True)
        t0 = time.monotonic()
        assert rendezvous.try_commit(store, conn, epoch, 60.0, t0) is None

        # survivors keep heartbeating through the churn window
        clk.advance(0.6)
        for m in ("ma", "mb"):
            pubs[m].publish(now=clk())
        agg.aggregate(now=clk())              # TTL expiry evicts mc
        assert agg.expired_total == 1
        assert "mc" not in (store.get(f"{conn}/members") or {})
        assert rendezvous.try_commit(store, conn, epoch, 60.0, t0) is True
        assert store.get(f"{conn}/stack")["fp"] == "fpY"
