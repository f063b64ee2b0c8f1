"""PR 10 SLO plane: error-budget engine arithmetic on a fake clock, metrics
federation over the KV obs plane, trace-derived cost calibration, and the
``slo_guard`` policy — the federation → SLO → policy lifecycle of
docs/architecture.md §11, unit-sized.
"""
from __future__ import annotations

import pytest

from repro.core.controller import PolicyContext, Rule, policy_rules
from repro.core.cost import (
    Candidate,
    CostModel,
    chunnel_cost,
    measured_costs,
    reset_measured_costs,
)
from repro.core.rendezvous import KVStore
from repro.core.chunnel import FnChunnel
from repro.fleet.aggregate import FleetAggregator
from repro.fleet.publish import roster_key
from repro.obs import SLO, MetricsRegistry, TRACER, parse_prometheus
from repro.obs.calibrate import calibrate_from_traces
from repro.obs.federate import OBS_PLANE, MetricsFederator, MetricsPublisher
from repro.obs.slo import (
    SLOEngine,
    availability_slo_for,
    error_ratio_slo_for,
    latency_slo_for,
)


@pytest.fixture(autouse=True)
def clean_tracer():
    TRACER.disable()
    TRACER.reset()
    yield
    TRACER.disable()
    TRACER.reset()


class _FakeRecorder:
    """Captures SLOEngine breach dumps without touching the filesystem."""

    def __init__(self):
        self.dumps = []

    def dump(self, name, extra=None, once=False):
        self.dumps.append((name, extra, once))
        return name


def engine(slos, **kw):
    kw.setdefault("recorder", None)
    return SLOEngine(slos, **kw)


# ---------------------------------------------------------------------------
# SLO declaration + classification
# ---------------------------------------------------------------------------


class TestSLODeclaration:
    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown SLO kind"):
            SLO("x", "m", kind="throughput")

    def test_objective_must_be_sub_one(self):
        with pytest.raises(ValueError, match="objective"):
            SLO("x", "m", objective=1.0, threshold=1.0)

    def test_latency_needs_threshold(self):
        with pytest.raises(ValueError, match="threshold"):
            SLO("x", "m", kind="latency")

    def test_budget_is_one_minus_objective(self):
        assert SLO("x", "m", objective=0.95, threshold=1.0).budget == (
            pytest.approx(0.05))

    def test_helpers_build_each_kind(self):
        assert latency_slo_for("m", 0.005).kind == "latency"
        assert error_ratio_slo_for("m").kind == "error_ratio"
        assert availability_slo_for("m").kind == "availability"

    def test_latency_classification(self):
        s = latency_slo_for("rtt", 0.005, objective=0.95)
        assert s.bad_fraction({"rtt": 0.004}) == 0.0
        assert s.bad_fraction({"rtt": 0.006}) == 1.0

    def test_missing_nan_and_nonnumeric_are_no_data(self):
        s = latency_slo_for("rtt", 0.005)
        assert s.bad_fraction({}) is None
        assert s.bad_fraction({"rtt": float("nan")}) is None
        assert s.bad_fraction({"rtt": "broken"}) is None

    def test_error_ratio_clamps(self):
        s = error_ratio_slo_for("err")
        assert s.bad_fraction({"err": 0.02}) == pytest.approx(0.02)
        assert s.bad_fraction({"err": 7.0}) == 1.0
        assert s.bad_fraction({"err": -3.0}) == 0.0

    def test_availability_inverts(self):
        s = availability_slo_for("up")
        assert s.bad_fraction({"up": 1.0}) == 0.0
        assert s.bad_fraction({"up": 0.25}) == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# engine lifecycle on a fake clock
# ---------------------------------------------------------------------------

LAT = SLO("lat", "rtt_p95_s", objective=0.95, threshold=0.005)


class TestSLOEngine:
    def test_needs_slos_and_unique_names(self):
        with pytest.raises(ValueError, match="at least one"):
            engine([])
        with pytest.raises(ValueError, match="duplicate"):
            engine([LAT, SLO("lat", "x", threshold=1.0)])

    def test_healthy_run_burns_nothing(self):
        e = engine([LAT], fast_window_s=5.0, slow_window_s=60.0)
        for t in range(1, 61):
            sigs = e.observe({"rtt_p95_s": 0.001}, now=float(t))
        assert sigs["slo.lat.burn_fast"] == 0.0
        assert sigs["slo.lat.burn_slow"] == 0.0
        assert sigs["slo.lat.alarm"] == 0.0
        assert sigs["slo.lat.budget_remaining"] == 1.0
        assert e.events == []

    def test_short_spike_trips_fast_window_only(self):
        # multi-window point: 10 bad seconds after 100 good ones saturate the
        # fast window (burn 20 > 14.4) while the slow window stays diluted
        # (10/60 / 0.05 = 3.3 < 6.0) — no page
        e = engine([LAT], fast_window_s=5.0, slow_window_s=60.0)
        t = 0.0
        for _ in range(100):
            t += 1.0
            e.observe({"rtt_p95_s": 0.001}, now=t)
        for _ in range(10):
            t += 1.0
            sigs = e.observe({"rtt_p95_s": 0.02}, now=t)
        assert sigs["slo.lat.burn_fast"] > e.fast_burn
        assert sigs["slo.lat.burn_slow"] < e.slow_burn
        assert sigs["slo.lat.alarm"] == 0.0

    def test_sustained_badness_breaches_then_recovers(self):
        rec = _FakeRecorder()
        e = SLOEngine([LAT], fast_window_s=5.0, slow_window_s=60.0,
                      budget_window_s=3600.0, recorder=rec)
        TRACER.enable()
        t = 0.0
        for _ in range(60):
            t += 1.0
            e.observe({"rtt_p95_s": 0.001}, now=t)
        for _ in range(40):
            t += 1.0
            sigs = e.observe({"rtt_p95_s": 0.02}, now=t)
        assert sigs["slo.lat.alarm"] == 1.0
        assert sigs["slo.alarms"] == 1
        assert e.alarmed() == ["lat"]
        assert [ev["kind"] for ev in e.events] == ["breach"]
        # the breach tripped the recorder exactly once, with the event data
        assert len(rec.dumps) == 1
        name, extra, once = rec.dumps[0]
        assert name == "slo_breach_lat" and once and extra["slo"] == "lat"
        # ... and emitted a tracer instant
        kinds = [r["name"] for r in TRACER.collect()
                 if r.get("kind") == "event"]
        assert "slo.breach" in kinds

        for _ in range(10):
            t += 1.0
            sigs = e.observe({"rtt_p95_s": 0.001}, now=t)
        assert sigs["slo.lat.alarm"] == 0.0
        assert [ev["kind"] for ev in e.events] == ["breach", "recovery"]
        assert len(rec.dumps) == 1  # recovery does not dump

    def test_budget_spends_over_the_run(self):
        e = engine([LAT], budget_window_s=1000.0)
        t = 0.0
        for _ in range(25):
            t += 1.0
            sigs = e.observe({"rtt_p95_s": 0.02}, now=t)
        # 24 bad-held seconds / (0.05 budget * 1000s window) = 0.48
        assert sigs["slo.lat.budget_spent"] == pytest.approx(0.48)
        assert sigs["slo.lat.budget_remaining"] == pytest.approx(0.52)

    def test_missing_metric_leaves_state_untouched(self):
        e = engine([LAT])
        e.observe({"rtt_p95_s": 0.02}, now=1.0)
        before = e.report(now=2.0)[0]["samples"]
        e.observe({}, now=2.0)
        assert e.report(now=2.0)[0]["samples"] == before

    def test_report_row_shape(self):
        e = engine([LAT])
        e.observe({"rtt_p95_s": 0.001}, now=1.0)
        (row,) = e.report(now=2.0)
        assert row["slo"] == "lat" and row["objective"] == 0.95
        assert row["budget"] == pytest.approx(0.05)
        assert row["alarm"] is False and row["breaches"] == 0

    def test_view_fn_makes_it_a_signal_source(self):
        view = {"rtt_p95_s": 0.02}
        e = engine([LAT], view_fn=lambda: view)
        sigs = e.read(now=1.0)
        assert sigs["slo.lat.bad"] == 1.0
        # signals() peeks without re-sampling
        assert e.signals()["slo.lat.bad"] == 1.0

    def test_engine_feeds_fleet_aggregator(self):
        store = KVStore()
        agg = FleetAggregator(store, "f", now=lambda: 100.0)
        e = engine([LAT], view_fn=lambda: {"rtt_p95_s": 0.02})
        agg.add_source(e)
        snap = agg.aggregate(now=100.0)
        assert snap["slo.lat.bad"] == 1.0
        assert "slo.alarms" in snap


# ---------------------------------------------------------------------------
# federation over the KV obs plane
# ---------------------------------------------------------------------------


def _member(store, name, region, metrics, now):
    reg = MetricsRegistry()
    reg.register("conn", lambda m=metrics: dict(m), instance=f"{name}-c")
    pub = MetricsPublisher(store, "fed", name, reg, region=region, now=now)
    pub.publish()
    return pub


class TestFederation:
    M1 = {"ops_per_s": 100.0, "rtt_p50_s": 0.001, "rtt_p95_s": 0.005}
    M2 = {"ops_per_s": 300.0, "rtt_p50_s": 0.002, "rtt_p95_s": 0.003}

    def test_merge_modes(self):
        store = KVStore()
        now = lambda: 10.0
        _member(store, "m1", "edge", self.M1, now)
        _member(store, "m2", "core", self.M2, now)
        fed = MetricsFederator(store, "fed", ttl_s=5.0, now=now)
        conn = fed.merged()["conn"]
        assert conn["ops_per_s"] == pytest.approx(400.0)        # sum
        assert conn["rtt_p95_s"] == pytest.approx(0.005)        # max
        # load-weighted mean: (100*1ms + 300*2ms) / 400
        assert conn["rtt_p50_s"] == pytest.approx(0.00175)

    def test_view_has_flat_and_region_keys(self):
        store = KVStore()
        now = lambda: 10.0
        _member(store, "m1", "edge", self.M1, now)
        _member(store, "m2", "core", self.M2, now)
        fed = MetricsFederator(store, "fed", ttl_s=5.0, now=now)
        v = fed.view()
        assert v["obs.members"] == 2 and v["obs.stale_members"] == 0
        assert v["obs.availability"] == 1.0
        assert v["obs.conn.ops_per_s"] == pytest.approx(400.0)
        assert v["obs.region.edge.conn.rtt_p95_s"] == pytest.approx(0.005)
        assert v["obs.region.core.conn.rtt_p95_s"] == pytest.approx(0.003)
        assert v["obs.member_ops_per_s"] == {"m1": 100.0, "m2": 300.0}

    def test_obs_plane_keys_stay_off_the_fleet_plane(self):
        store = KVStore()
        now = lambda: 10.0
        _member(store, "m1", "edge", self.M1, now)
        assert store.get(roster_key("fed", OBS_PLANE)) is not None
        assert store.get(roster_key("fed")) is None  # coordination untouched

    def test_heartbeat_expiry_spares_rendezvous_membership(self):
        store = KVStore()
        t = [0.0]
        now = lambda: t[0]
        # a rendezvous membership map that obs-plane expiry must NOT evict
        store.transact(
            lambda txn: txn.put("fleet/fed/members", {"m2": "prepared"}))
        _member(store, "m2", "core", self.M2, now)
        t[0] = 10.0
        _member(store, "m1", "edge", self.M1, now)
        fed = MetricsFederator(store, "fed", ttl_s=5.0, now=now)
        fresh, stale = fed.members()
        assert set(fresh) == {"m1"} and stale == ["m2"]
        assert fed.expired_total == 1
        assert store.get("fleet/fed/members") == {"m2": "prepared"}

    def test_nonnumeric_and_private_keys_dropped_from_merge(self):
        store = KVStore()
        now = lambda: 10.0
        _member(store, "m1", "edge",
                {"ops_per_s": 10.0, "_err": "boom", "state": "ok",
                 "nested": {"x": 2.0}}, now)
        fed = MetricsFederator(store, "fed", ttl_s=5.0, now=now)
        conn = fed.merged()["conn"]
        assert conn == {"ops_per_s": 10.0, "nested.x": 2.0}

    def test_federated_registry_prometheus_round_trip(self):
        store = KVStore()
        now = lambda: 10.0
        _member(store, "m1", "edge", self.M1, now)
        _member(store, "m2", "core", self.M2, now)
        fed = MetricsFederator(store, "fed", ttl_s=5.0, now=now)
        text = fed.federated_registry().to_prometheus()
        samples = parse_prometheus(text)
        insts = {s["labels"]["instance"] for s in samples}
        assert {"m1/m1-c", "m2/m2-c", "_fleet"} <= insts
        fleet_ops = [s for s in samples
                     if s["labels"]["instance"] == "_fleet"
                     and s["name"].endswith("ops_per_s")]
        assert fleet_ops and fleet_ops[0]["value"] == pytest.approx(400.0)


# ---------------------------------------------------------------------------
# trace-derived calibration
# ---------------------------------------------------------------------------


def _batch(ch, dur, bi=0, bo=None):
    return {"name": "chunnel.send", "kind": "batch",
            "attrs": {"chunnel": ch, "dur": dur,
                      "bytes_in": bi, "bytes_out": bo}}


class TestCalibrateFromTraces:
    def test_median_latency_and_bytes_ratio(self):
        recs = [_batch("A", d, bi=100, bo=50)
                for d in (0.002, 0.003, 0.002, 0.9)]  # tail outlier ignored
        cal = calibrate_from_traces(recs, min_samples=3, apply=False)
        assert cal.chunnels["A"]["op_latency_s"] == pytest.approx(0.0025)
        assert cal.chunnels["A"]["dcn_bytes_per_byte"] == pytest.approx(0.5)
        assert cal.samples["A"] == 4

    def test_min_samples_gates_chunnels(self):
        cal = calibrate_from_traces([_batch("A", 0.002)] * 2,
                                    min_samples=3, apply=False)
        assert not cal
        assert cal.chunnels == {}

    def test_wan_span_records_count(self):
        recs = [{"name": "wan.send", "kind": "span", "dur": 0.004,
                 "attrs": {"chunnel": "W"}}] * 3
        cal = calibrate_from_traces(recs, apply=False)
        assert cal.chunnels["W"]["op_latency_s"] == pytest.approx(0.004)

    def test_swap_blip_applies_from_one_sample(self):
        recs = [{"name": "reconfig.swap", "kind": "span", "dur": 0.01,
                 "attrs": {"new": "fp1"}}]
        cal = calibrate_from_traces(recs, apply=False)
        assert cal.stack_blips == {"fp1": pytest.approx(0.01)}

    def test_traced_costs_flip_the_annotated_ranking(self):
        """Annotations that lie are corrected by traces: "Quick" claims
        0.1 ms a batch but sleeps 2 ms, "Steady" claims 5 ms but sleeps
        0.3 ms. Ranked on annotations Quick wins; after nine traced batches
        each and ``calibrate_from_traces(apply=True)``, Steady does."""
        import time

        from repro.core.cost import LATENCY_FIRST, rank

        def sleeper(s):
            def xf(msgs):
                time.sleep(s)
                return msgs
            return xf

        quick = FnChunnel("Quick", on_send_batch=sleeper(0.002),
                          cost=CostModel(op_latency_s=1e-4))
        steady = FnChunnel("Steady", on_send_batch=sleeper(0.0003),
                           cost=CostModel(op_latency_s=5e-3))

        def order():
            cands = [Candidate("quick-stack", chunnel_cost(quick), "Quick"),
                     Candidate("steady-stack", chunnel_cost(steady), "Steady")]
            return [c.label for _u, c in rank(cands, LATENCY_FIRST)]

        try:
            assert order() == ["Quick", "Steady"]
            TRACER.enable()
            for ch in (quick, steady):
                dp = ch.connect_wrap(None)
                for _ in range(9):
                    dp.send([b"x" * 128] * 8)
            calibrate_from_traces(TRACER.collect(clear=True), min_samples=3, apply=True)
            assert order() == ["Steady", "Quick"]
        finally:
            reset_measured_costs()

    def test_apply_installs_measured_override(self):
        ch = FnChunnel("CalTest", cost=CostModel(op_latency_s=1e-6))
        try:
            calibrate_from_traces([_batch("CalTest", 0.002)] * 3, apply=True)
            assert "CalTest" in measured_costs()[0]
            assert chunnel_cost(ch).op_latency_s == pytest.approx(0.002)
        finally:
            reset_measured_costs()
        assert chunnel_cost(ch).op_latency_s == pytest.approx(1e-6)


# ---------------------------------------------------------------------------
# slo_guard policy
# ---------------------------------------------------------------------------


class TestSLOGuardPolicy:
    def ctx(self, **params):
        cands = [Candidate("fast", CostModel(op_latency_s=1e-4), "Fast"),
                 Candidate("safe", CostModel(op_latency_s=2e-3), "Safe")]
        return PolicyContext(candidates=cands, default="fast",
                             params={"slo": "lat", **params})

    def test_burn_rule_arms_on_both_windows(self):
        rules = policy_rules("slo_guard", self.ctx(safe_names=("Safe",)))
        burn = next(r for r in rules if r.name == "slo_guard:lat:burn")
        assert burn.target == "safe"
        assert not burn.when({"slo.lat.burn_fast": 20.0,
                              "slo.lat.burn_slow": 1.0})
        assert not burn.when({"slo.lat.burn_fast": 1.0,
                              "slo.lat.burn_slow": 10.0})
        assert burn.when({"slo.lat.burn_fast": 20.0,
                          "slo.lat.burn_slow": 10.0})

    def test_recovery_rule_returns_to_default(self):
        rules = policy_rules("slo_guard", self.ctx(safe_names=("Safe",)))
        rec = next(r for r in rules if r.name == "slo_guard:lat:recovered")
        assert rec.target == "fast"
        assert rec.when({"slo.lat.alarm": 0.0})
        assert not rec.when({"slo.lat.alarm": 1.0})

    def test_no_default_no_recovery_rule(self):
        ctx = self.ctx(safe_names=("Safe",))
        ctx.default = None
        names = [r.name for r in policy_rules("slo_guard", ctx)]
        assert names == ["slo_guard:lat:burn"]

    def test_scored_target_without_safe_names(self):
        rules = policy_rules("slo_guard", self.ctx())
        burn = next(r for r in rules if r.name == "slo_guard:lat:burn")
        # a ScoredTarget re-ranks candidates at fire time
        assert hasattr(burn.target, "resolve") or burn.target not in (
            "fast", "safe")

    def test_guard_switches_before_the_raw_threshold(self):
        """End to end on a virtual clock: an edge connection's RTT degrades
        (WAN weather: 4-6 ms and one probe in five timing out at 40 ms).
        Its telemetry is published, federated with a healthy core member,
        and judged by an SLO whose bound (3.5 ms) sits far below a raw
        emergency threshold (12 ms, ``hold=2``). On the same snapshots the
        ``slo_guard`` burn rule switches the connection to the safe option
        strictly before the raw rule would fire, the breach is an event and
        trips the recorder, and the regions stay apart."""
        import numpy as np

        from repro.chaos import VirtualClock
        from repro.core import (LockedConn, ReconfigController, Select, above,
                                conn_controller, make_stack)
        from repro.core.telemetry import ConnTelemetry

        clock = VirtualClock(0.0)
        stack = make_stack(Select(FnChunnel("FastWire"), FnChunnel("WanLink")))
        handle = LockedConn(stack.preferred())
        handle.telemetry = ConnTelemetry(now=clock)
        store = KVStore()
        edge = MetricsRegistry()
        # peek, do not reset: the controller's snapshot owns the rate window
        edge.register("conn", lambda: handle.telemetry.snapshot(reset_window=False),
                      instance="edge-conn")
        pub = MetricsPublisher(store, "fed", "edge-1", edge, region="edge", now=clock)
        _member(store, "core-1", "core", {"ops_per_s": 40.0, "rtt_p95_s": 0.0004}, clock)
        fed = MetricsFederator(store, "fed", ttl_s=5.0, now=clock)
        recorder = _FakeRecorder()
        slo_engine = SLOEngine(
            [SLO("region_latency", "obs.region.edge.conn.rtt_p95_s",
                 objective=0.95, threshold=0.0035)],
            fast_window_s=0.15, slow_window_s=0.6, budget_window_s=60.0,
            recorder=recorder, now=clock)
        guard = conn_controller(handle, stack, policy="slo_guard",
                                policy_params={"slo": "region_latency",
                                               "safe_names": ("WanLink",), "hold": 1},
                                cooldown_s=0.0, now=clock)
        raw = ReconfigController(
            rules=[Rule("raw-threshold", above("rtt_p95_s", 0.012), "WanLink", hold=2)],
            switch=lambda t: True, current=lambda: "FastWire", cooldown_s=0.0, now=clock)
        rng = np.random.default_rng(13)
        guard_tick = raw_tick = None
        for tick in range(45):
            clock.advance(0.05)
            for _ in range(4):
                if tick < 3:  # a clean baseline: the budget starts intact
                    rtt = 0.0015
                elif rng.random() < 0.2:
                    rtt = 0.04
                else:
                    rtt = 0.004 + 0.002 * rng.random()
                handle.telemetry.record_rtt(rtt)
            pub.publish()
            view = fed.view()
            sigs = slo_engine.observe(view)
            snap = handle.telemetry.snapshot()
            snap.update(sigs)
            if guard.tick(snap).reason == "switched" and guard_tick is None:
                guard_tick = tick
            if raw.tick(dict(snap)).fired and raw_tick is None:
                raw_tick = tick
            if raw_tick is not None:
                break
        assert guard_tick is not None and raw_tick is not None
        assert guard_tick < raw_tick
        assert [d.rule for d in guard.switch_log()] == ["slo_guard:region_latency:burn"]
        assert handle.stack.chunnels[0].name == "WanLink"
        assert [e["kind"] for e in slo_engine.events][:1] == ["breach"]
        assert recorder.dumps and recorder.dumps[0][0] == "slo_breach_region_latency"
        assert view["obs.members"] == 2
        assert (view["obs.region.edge.conn.rtt_p95_s"]
                > view["obs.region.core.conn.rtt_p95_s"])

    def test_custom_burn_thresholds(self):
        rules = policy_rules("slo_guard", self.ctx(
            safe_names=("Safe",), fast_burn=2.0, slow_burn=1.0))
        burn = next(r for r in rules if r.name == "slo_guard:lat:burn")
        assert burn.when({"slo.lat.burn_fast": 3.0,
                          "slo.lat.burn_slow": 1.5})
