"""Closed-loop reconfiguration controller tests: telemetry estimators, policy
damping (hysteresis / cooldown / no-flap), conn-level integration (unilateral
and multilateral 2PC switches from live telemetry), and the trainer plane."""
import os
import random
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import pytest

from repro.core import (
    CapabilitySet,
    ConnTelemetry,
    EwmaQuantile,
    Fabric,
    FabricTransport,
    FnChunnel,
    HostAgent,
    LockedConn,
    ReconfigController,
    Rule,
    Select,
    WireType,
    above,
    below,
    conn_controller,
    make_stack,
    option_named,
)
from repro.core.reconfigure import ReconfigParticipant, ReconfigStats


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def T(name, upper="obj", lower="unit", caps=None, multilateral=False):
    return FnChunnel(fn_name=name, upper=WireType.of(upper),
                     lower=WireType.of(lower), caps=caps,
                     multilateral_=multilateral)


class TestEwmaQuantile:
    def test_quantile_ordering_on_uniform(self):
        rng = random.Random(0)
        p50, p95 = EwmaQuantile(0.50), EwmaQuantile(0.95)
        for _ in range(5000):
            x = rng.uniform(0.0, 1.0)
            p50.update(x)
            p95.update(x)
        assert 0.3 < p50.value < 0.7
        assert p95.value > p50.value
        assert p95.value > 0.75

    def test_tracks_level_shift(self):
        q = EwmaQuantile(0.5)
        for _ in range(300):
            q.update(1.0)
        low = q.value
        for _ in range(600):
            q.update(10.0)
        assert q.value > low + 1.0


class TestTelemetry:
    def test_counters_and_windowed_rates(self):
        clock = FakeClock()
        t = ConnTelemetry(now=clock)
        for _ in range(10):
            t.record_send(2, 100, 0.001)
        clock.advance(2.0)
        s = t.snapshot()
        assert s["ops"] == 10 and s["msgs_out"] == 20 and s["bytes_out"] == 1000
        assert s["ops_per_s"] == pytest.approx(5.0)
        assert s["bytes_per_s"] == pytest.approx(500.0)
        clock.advance(1.0)  # nothing new in this window
        assert t.snapshot()["ops_per_s"] == 0.0

    def test_straggler_ratio_needs_two_pods(self):
        t = ConnTelemetry()
        for _ in range(30):
            t.record_step({"a": 0.1})
        assert t.straggler_ratio() == 1.0
        for _ in range(30):
            t.record_step({"b": 0.1, "c": 0.3})
        assert t.straggler_ratio() == pytest.approx(3.0, rel=0.2)

    def test_straggler_excluded_from_its_own_baseline(self):
        # with the straggler inside the denominator a 2-pod job could never
        # read above 2.0 (3x straggler -> exactly 1.5), capping thresholds
        t = ConnTelemetry()
        for _ in range(30):
            t.record_step({"a": 0.1, "b": 0.3})
        assert t.straggler_ratio() == pytest.approx(3.0, rel=0.2)

    def test_steps_counted_once_per_step_not_per_pod(self):
        t = ConnTelemetry()
        for _ in range(10):
            t.record_step({"a": 0.1, "b": 0.1, "c": 0.1})
        s = t.snapshot()
        assert s["steps"] == 10 and s["ops"] == 10  # not inflated by n_pods

    def test_reconfig_stats_folded_into_snapshot(self):
        t = ConnTelemetry()
        st = ReconfigStats()
        t.bind_reconfig(st)
        st.switches, st.last_switch_s = 2, 0.5
        s = t.snapshot()
        assert s["switches"] == 2 and s["last_switch_s"] == 0.5

    def test_batch_shape_counters(self):
        # PR 7: the data plane is batched, so telemetry tracks msgs/op shape
        t = ConnTelemetry()
        for n in (1, 1, 3, 8, 64, 0):
            t.record_send(n, 10 * n, 0.001)
        s = t.snapshot()
        assert s["batch_hist"] == {"1": 2, "2-3": 1, "8-15": 1, "64-127": 1,
                                   "0": 1}
        assert s["msgs_per_op"] == pytest.approx((1 + 1 + 3 + 8 + 64) / 6)
        assert s["batch_p50"] <= s["batch_p95"]

    def test_batch_quantiles_track_batch_size(self):
        t = ConnTelemetry()
        for _ in range(200):
            t.record_send(64, 64, 0.001)
        s = t.snapshot()
        assert s["batch_p50"] == pytest.approx(64, rel=0.2)
        assert s["batch_p95"] == pytest.approx(64, rel=0.2)


class TestControllerPolicy:
    def mk(self, rules, *, clock=None, cooldown=0.0, refuse=False, start="A"):
        committed = []
        cur = {"v": start}

        def switch(target):
            if refuse:
                return False
            committed.append(target)
            cur["v"] = target
            return True

        ctl = ReconfigController(rules, switch, lambda: cur["v"],
                                 cooldown_s=cooldown,
                                 now=clock if clock is not None else time.monotonic)
        return ctl, committed

    def test_hysteresis_requires_consecutive_ticks(self):
        ctl, committed = self.mk([Rule("hot", above("x", 1.0), "B", hold=3)])
        for snap in ({"x": 2}, {"x": 2}, {"x": 0}, {"x": 2}, {"x": 2}):
            d = ctl.tick(snap)
            assert not d.fired
        assert committed == []
        d = ctl.tick({"x": 2})  # third consecutive tick above threshold
        assert d.fired and d.committed and committed == ["B"]

    def test_no_flap_under_oscillating_telemetry(self):
        rules = [Rule("hot", above("x", 1.0), "B", hold=2, priority=1),
                 Rule("cold", below("x", 1.0), "A", hold=2)]
        ctl, committed = self.mk(rules)
        for i in range(60):
            ctl.tick({"x": 2.0 if i % 2 == 0 else 0.0})
        assert committed == []  # neither predicate ever holds twice in a row

    def test_cooldown_blocks_then_releases(self):
        clock = FakeClock()
        rules = [Rule("hot", above("x", 1.0), "B", hold=1, priority=1),
                 Rule("cold", below("x", 1.0), "A", hold=1)]
        ctl, committed = self.mk(rules, clock=clock, cooldown=10.0)
        assert ctl.tick({"x": 2.0}).committed  # A -> B
        clock.advance(1.0)
        d = ctl.tick({"x": 0.0})  # cold armed but inside cooldown
        assert not d.fired and d.reason == "cooldown"
        clock.advance(20.0)
        d = ctl.tick({"x": 0.0})
        assert d.committed and committed == ["B", "A"]

    def test_current_target_never_reselected(self):
        ctl, committed = self.mk([Rule("same", above("x", 1.0), "A", hold=1)])
        for _ in range(5):
            d = ctl.tick({"x": 2.0})
            assert d.reason == "idle"
        assert committed == []

    def test_priority_breaks_same_tick_ties(self):
        rules = [Rule("lo", above("x", 1.0), "B", hold=1, priority=0),
                 Rule("hi", above("x", 1.0), "C", hold=1, priority=5)]
        ctl, committed = self.mk(rules)
        ctl.tick({"x": 2.0})
        assert committed == ["C"]

    def test_refused_switch_reported_and_no_cooldown(self):
        clock = FakeClock()
        ctl, committed = self.mk([Rule("hot", above("x", 1.0), "B", hold=1)],
                                 clock=clock, cooldown=10.0, refuse=True)
        d = ctl.tick({"x": 2.0})
        assert d.fired and not d.committed and d.reason == "refused"
        d = ctl.tick({"x": 2.0})  # refusal must not start the cooldown timer
        assert d.fired and d.reason == "refused"
        assert committed == []

    def test_missing_metric_does_not_arm(self):
        ctl, committed = self.mk([Rule("hot", above("x", 1.0), "B", hold=1)])
        d = ctl.tick({"y": 5.0})
        assert d.reason == "idle" and committed == []

    def test_satisfied_high_priority_rule_suppresses_lower(self):
        # two persistently-armed rules with different targets (straggler=>B,
        # budget=>C) must not ping-pong: once B is active the satisfied
        # high-priority rule claims every tick and the budget rule stays quiet
        rules = [Rule("strag", above("x", 1.0), "B", hold=1, priority=2),
                 Rule("budget", above("y", 1.0), "C", hold=1, priority=1)]
        ctl, committed = self.mk(rules)
        for _ in range(10):
            ctl.tick({"x": 2.0, "y": 2.0})
        assert committed == ["B"]

    def test_duplicate_rule_names_rejected(self):
        with pytest.raises(ValueError):
            ReconfigController(
                [Rule("r", above("x", 1.0), "B"), Rule("r", below("y", 1.0), "C")],
                lambda t: True, lambda: "A")

    def test_decision_log_is_bounded(self):
        # max_decisions is the legacy alias of max_history — both must bound
        ctl = ReconfigController([Rule("hot", above("x", 1.0), "B", hold=99)],
                                 lambda t: True, lambda: "A", max_decisions=10)
        for _ in range(50):
            ctl.tick({"x": 0.0})
        assert len(ctl.decisions) == 10

    def test_counts_survive_history_eviction(self):
        # every tick fires (target B never becomes current): with only 5
        # retained decisions, the lifetime totals must still count all 20
        ctl = ReconfigController([Rule("hot", above("x", 1.0), "B", hold=1)],
                                 lambda t: True, lambda: "A",
                                 max_history=5, cooldown_s=0.0)
        for _ in range(20):
            ctl.tick({"x": 2.0})
        assert len(ctl.decisions) == 5
        assert len(ctl.switch_log()) == 5          # windowed view
        c = ctl.counts()                           # lifetime view
        assert c == {"ticks": 20, "fired": 20, "committed": 20,
                     "by_rule": {"hot": 20}}

    def test_counts_track_refused_switches(self):
        ctl = ReconfigController([Rule("hot", above("x", 1.0), "B", hold=1)],
                                 lambda t: False, lambda: "A",
                                 max_history=4, cooldown_s=0.0)
        for _ in range(9):
            ctl.tick({"x": 2.0})
        c = ctl.counts()
        assert c["fired"] == 9 and c["committed"] == 0
        assert not ctl.switch_log()


class TestConnControllerIntegration:
    def test_unilateral_switch_from_live_telemetry(self):
        fabric = Fabric()
        ep = fabric.register("ctl-uni")
        stack = make_stack(Select(T("A", "bytes", "bytes"), T("B", "bytes", "bytes")),
                           FabricTransport(ep, "sink"))
        handle = LockedConn(stack.preferred())
        ctl = conn_controller(
            handle, stack,
            [Rule("busy", above("ops_per_s", 10.0),
                  option_named(stack, "B"), hold=2)],
            cooldown_s=0.0)
        for _ in range(100):
            handle.send([b"x"])
        assert not ctl.tick(handle.telemetry.snapshot()).fired  # hold=2
        for _ in range(100):
            handle.send([b"x"])
        d = ctl.tick(handle.telemetry.snapshot())
        assert d.fired and d.committed
        assert handle.stack.chunnels[0].name == "B"
        assert handle.telemetry.snapshot()["switches"] == 1  # blip folded in

    def test_multilateral_switch_runs_2pc(self):
        fabric = Fabric()
        srv = HostAgent(fabric, "ctl-srv")
        cli = HostAgent(fabric, "ctl-cli")
        caps = CapabilitySet.exact("x")
        stack = make_stack(Select(T("A", caps=caps, multilateral=True),
                                  T("B", caps=caps, multilateral=True)))
        srv.listen(stack)
        conn = cli.connect("ctl-srv", stack)
        assert conn.stack.chunnels[0].name == "A"
        srv_handle = LockedConn(srv.accept_stack("ctl-cli"))
        srv.register_participant("c1", srv_handle, stack.find)
        ctl = conn_controller(
            conn, stack,
            [Rule("go", above("ops", -1.0), option_named(stack, "B"), hold=1)],
            agent=cli, peers=["ctl-srv"], conn_id="c1", cooldown_s=0.0)
        d = ctl.tick(conn.telemetry.snapshot())
        assert d.committed
        assert conn.stack.chunnels[0].name == "B"      # client swapped
        assert srv_handle.stack.chunnels[0].name == "B"  # peer swapped via 2PC
        srv.close(); cli.close()

    def test_multilateral_target_without_agent_refused(self):
        stack = make_stack(Select(T("A", multilateral=True),
                                  T("B", multilateral=True)))
        handle = LockedConn(stack.preferred())
        with pytest.raises(ValueError, match="multilateral"):
            conn_controller(
                handle, stack,
                [Rule("go", above("ops", -1.0), option_named(stack, "B"), hold=1)])


class TestPreparedPeerResync:
    """A 2PC peer that misses the commit notification must resync eagerly via
    the epoch query instead of waiting for its next prepare (presumed-commit
    fix, ROADMAP)."""

    def _stack(self):
        caps = CapabilitySet.exact("x")
        return make_stack(Select(T("A", caps=caps, multilateral=True),
                                 T("B", caps=caps, multilateral=True)))

    def test_missed_commit_applied_from_epoch_query(self):
        clock = FakeClock()
        stack = self._stack()
        handle = LockedConn(stack.preferred())
        part = ReconfigParticipant(handle, stack.find,
                                   resync_after_s=1.0, now=clock)
        target = option_named(stack, "B")
        r = part.handle_msg("coord", {"type": "reconfig_prepare",
                                      "fp": target.fingerprint()})
        assert r["type"] == "reconfig_ready"
        # commit notification lost; not yet overdue
        assert part.needs_resync() is None
        clock.advance(2.0)
        assert part.needs_resync() == "coord"  # query the prepare's sender
        # coordinator swapped (its epoch advanced): peer adopts the commit
        applied = part.apply_state({"type": "reconfig_state", "epoch": 1,
                                    "fp": target.fingerprint()})
        assert applied and handle.stack.chunnels[0].name == "B"
        assert part.epoch == 1 and part.needs_resync() is None

    def test_aborted_proposal_clears_prepared_state(self):
        clock = FakeClock()
        stack = self._stack()
        handle = LockedConn(stack.preferred())
        part = ReconfigParticipant(handle, stack.find,
                                   resync_after_s=1.0, now=clock)
        target = option_named(stack, "B")
        part.handle_msg("coord", {"type": "reconfig_prepare",
                                  "fp": target.fingerprint()})
        clock.advance(2.0)
        # coordinator reports no new epoch (proposal aborted elsewhere)
        applied = part.apply_state({"type": "reconfig_state", "epoch": 0,
                                    "fp": stack.preferred().fingerprint()})
        assert not applied and handle.stack.chunnels[0].name == "A"
        assert part.needs_resync() is None  # stale prepared state cleared

    def test_pending_reply_defers_instead_of_clearing(self):
        # during phase 1 nothing is decided: a resync landing then must keep
        # the peer prepared (re-query next window), not misread the unchanged
        # epoch as an abort and later refuse the real commit
        clock = FakeClock()
        stack = self._stack()
        handle = LockedConn(stack.preferred())
        part = ReconfigParticipant(handle, stack.find,
                                   resync_after_s=1.0, now=clock)
        target = option_named(stack, "B")
        part.handle_msg("coord", {"type": "reconfig_prepare",
                                  "fp": target.fingerprint()})
        clock.advance(2.0)
        assert part.needs_resync() == "coord"
        applied = part.apply_state({"type": "reconfig_state", "epoch": 0,
                                    "fp": stack.preferred().fingerprint(),
                                    "pending": True})
        assert not applied
        assert part.needs_resync() is None  # deferred, but still prepared...
        clock.advance(2.0)
        assert part.needs_resync() == "coord"  # ...so the next window re-asks
        # and the eventually-arriving commit still lands normally
        r = part.handle_msg("coord", {"type": "reconfig_commit",
                                      "fp": target.fingerprint(), "epoch": 1})
        assert r["type"] == "reconfig_done"
        assert handle.stack.chunnels[0].name == "B" and part.epoch == 1

    def test_refuse_reply_clears_prepared_state(self):
        clock = FakeClock()
        stack = self._stack()
        handle = LockedConn(stack.preferred())
        part = ReconfigParticipant(handle, stack.find,
                                   resync_after_s=1.0, now=clock)
        part.handle_msg("coord", {"type": "reconfig_prepare",
                                  "fp": option_named(stack, "B").fingerprint()})
        clock.advance(2.0)
        assert not part.apply_state({"type": "reconfig_refuse"})
        assert part.needs_resync() is None
        assert handle.stack.chunnels[0].name == "A"

    def test_in_flight_commit_query_answers_with_decided_epoch(self):
        # phase-2 notifications can block for seconds on an unreachable peer
        # while the coordinator's local swap has not applied yet; a query in
        # that window must see the commit DECISION, or a merely-delayed peer
        # reads "aborted", clears prepared, and refuses the real commit
        fabric = Fabric()
        coord = HostAgent(fabric, "rs-dec")
        querier = HostAgent(fabric, "rs-q")
        stack = self._stack()
        handle = LockedConn(stack.preferred())
        target = option_named(stack, "B")
        try:
            coord.coordinate("c1", handle)
            # what two_phase_commit's on_decide hook records at commit point
            coord.record_decision("c1", handle.stats.switches + 1,
                                  target.fingerprint())
            r = querier.request("rs-dec", {"type": "reconfig_query",
                                           "conn": "c1"})
            assert r["type"] == "reconfig_state"
            assert r["epoch"] == 1 and r["fp"] == target.fingerprint()
            # once the local swap lands, live state and decision agree
            handle.reconfigure(target)
            r = querier.request("rs-dec", {"type": "reconfig_query",
                                           "conn": "c1"})
            assert r["epoch"] == 1 and r["fp"] == target.fingerprint()
        finally:
            coord.close(); querier.close()

    def test_agent_loop_resyncs_prepared_peer_end_to_end(self):
        fabric = Fabric()
        coord = HostAgent(fabric, "rs-coord")
        peer = HostAgent(fabric, "rs-peer")
        stack = self._stack()
        peer_handle = LockedConn(stack.preferred())
        peer.register_participant("c1", peer_handle, stack.find,
                                  resync_after_s=0.2)
        coord_handle = LockedConn(stack.preferred())
        target = option_named(stack, "B")
        try:
            # phase 1 reaches the peer...
            r = coord.request("rs-peer", {"type": "reconfig_prepare",
                                          "fp": target.fingerprint(),
                                          "conn": "c1"})
            assert r["type"] == "reconfig_ready"
            # ...then the commit notification is "lost": the coordinator
            # swaps locally and only answers queries
            coord.coordinate("c1", coord_handle)
            coord_handle.reconfigure(target)
            deadline = time.monotonic() + 3.0
            while (time.monotonic() < deadline
                   and peer_handle.stack.chunnels[0].name != "B"):
                time.sleep(0.02)
            assert peer_handle.stack.chunnels[0].name == "B"
        finally:
            coord.close(); peer.close()


class TestTrainerControllerPlane:
    def test_trainer_controller_initiates_mitigation(self):
        import jax
        from repro import compat
        from repro.configs import get_smoke_config
        from repro.configs.base import ShapeConfig, TrainConfig
        from repro.data.synthetic import batches_for
        from repro.launch.mesh import make_test_mesh
        from repro.train.trainer import HostSpec, ReconfigurableTrainer

        cfg = get_smoke_config("llama3.2-1b")
        shape = ShapeConfig("ctl-test", 64, 4, "train")
        mesh = make_test_mesh((2, 1), ("pod", "model"))
        offers = ["xla", "localsgd"]

        def pod_times(step_idx, dt):
            # host1's heartbeat reports a persistent 3x straggler from step 3
            return {"host0": dt, "host1": dt * (3.0 if step_idx >= 3 else 1.0)}

        # use_mesh, not set_mesh: the ambient mesh must not leak into test
        # modules that run later (compat.set_mesh is deliberately persistent)
        with compat.use_mesh(mesh):
            tr = ReconfigurableTrainer(
                cfg, shape, mesh,
                tcfg=TrainConfig(learning_rate=1e-3, warmup_steps=2, total_steps=32),
                transport="xla",
                hosts=[HostSpec(0, list(offers)), HostSpec(1, list(offers))],
            )
            ctl = tr.make_controller(straggler_threshold=1.3, hold=2, cooldown_s=0.0)
            state = tr.init_state(jax.random.PRNGKey(0))
            gen = batches_for(cfg, shape)
            state, hist = tr.run(state, gen, 12, controller=ctl, pod_times=pod_times)
        assert tr.transport_name == "localsgd"
        last = tr.reconfig_log[-1]
        assert last["committed"] and last["from"] == "xla" and last["to"] == "localsgd"
        fired = [d for d in ctl.decisions if d.fired and d.committed]
        assert fired and fired[0].rule == "straggler->mitigation"
        assert all(l == l for l in (float(m["loss"]) for m in hist))  # finite

    def test_attached_trainer_publishes_its_steps_to_the_fleet(self):
        """``attach_fleet`` puts the trainer on the fleet signal plane: after
        two steps a ``FleetAggregator`` over its store sees one member, and
        the member's straggler view (host1 reported 3x slow)."""
        import jax
        from repro import compat
        from repro.configs import get_smoke_config
        from repro.configs.base import ShapeConfig
        from repro.data.synthetic import batches_for
        from repro.fleet import FleetAggregator
        from repro.launch.mesh import make_test_mesh
        from repro.train.trainer import HostSpec, ReconfigurableTrainer

        cfg = get_smoke_config("llama3.2-1b")
        shape = ShapeConfig("fleet-test", 64, 4, "train")
        mesh = make_test_mesh((2, 1), ("pod", "model"))
        with compat.use_mesh(mesh):
            tr = ReconfigurableTrainer(cfg, shape, mesh, transport="xla",
                                       hosts=[HostSpec(0, ["xla"]), HostSpec(1, ["xla"])])
            tr.attach_fleet(fleet_id="trainfleet", period_s=0.0)
            agg = FleetAggregator(tr.store, "trainfleet", ttl_s=600.0)
            state = tr.init_state(jax.random.PRNGKey(0))
            tr.run(state, batches_for(cfg, shape), 2,
                   pod_times=lambda i, dt: {"host0": dt, "host1": 3.0 * dt})
        view = agg.aggregate()
        assert view["fleet.members"] == 1
        assert view["fleet.straggler_ratio"] > 2.0

    def test_policy_cannot_override_peer_negotiation(self):
        # a transition target outside a PEER's offer set must abort at the
        # rendezvous vote (the proposer consents by proposing; peers veto)
        from repro import compat
        from repro.configs import get_smoke_config
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_test_mesh
        from repro.train.trainer import HostSpec, ReconfigurableTrainer

        cfg = get_smoke_config("llama3.2-1b")
        mesh = make_test_mesh((2, 1), ("pod", "model"))
        with compat.use_mesh(mesh):
            tr = ReconfigurableTrainer(
                cfg, ShapeConfig("veto", 64, 4, "train"), mesh,
                transport="xla",
                hosts=[HostSpec(0, ["xla", "localsgd"]), HostSpec(1, ["xla"])],
            )
            tr.reconfigure(None, "localsgd")  # host1 never offered localsgd
        assert tr.reconfig_log[-1]["committed"] is False
        assert tr.transport_name == "xla"
