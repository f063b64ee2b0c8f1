"""Multi-objective scorer + policy plugin registry tests: cost-model folding,
argmax-vs-first-compatible negotiation, degenerate/weight-zero objectives,
ScoredTarget resolution inside a controller, and registry semantics."""
import pytest

from repro.core import (
    BYTES_FIRST,
    CapabilitySet,
    Candidate,
    CostModel,
    FnChunnel,
    LATENCY_FIRST,
    LockedConn,
    Objective,
    PolicyContext,
    ReconfigController,
    Rule,
    ScoredTarget,
    Select,
    WireType,
    available_policies,
    conn_controller,
    get_policy,
    make_stack,
    pick_compatible,
    policy_rules,
    register_policy,
    score_stack,
    stack_cost,
    utility,
)
from repro.core.controller import _POLICIES


CAPS = CapabilitySet.exact("wire:obj")


def impl(name, lat=0.0, ratio=1.0, blip=0.0, caps=CAPS):
    return FnChunnel(fn_name=name, caps=caps,
                     cost=CostModel(op_latency_s=lat, dcn_bytes_per_byte=ratio,
                                    switch_blip_s=blip))


class TestCostModel:
    def test_stack_cost_folds_latency_sum_ratio_product(self):
        st = make_stack(impl("A", lat=1e-3, ratio=0.5, blip=0.1),
                        impl("B", lat=2e-3, ratio=0.5, blip=0.2)).preferred()
        c = stack_cost(st)
        assert c.op_latency_s == pytest.approx(3e-3)
        assert c.dcn_bytes_per_byte == pytest.approx(0.25)
        assert c.switch_blip_s == pytest.approx(0.3)

    def test_unannotated_chunnel_is_neutral(self):
        st = make_stack(FnChunnel(fn_name="Plain")).preferred()
        c = stack_cost(st)
        assert (c.op_latency_s, c.dcn_bytes_per_byte, c.switch_blip_s) == (0.0, 1.0, 0.0)

    def test_utility_scales_with_telemetry(self):
        c = CostModel(op_latency_s=1e-3, dcn_bytes_per_byte=1.0)
        quiet = utility(c, snapshot={"ops_per_s": 1.0, "bytes_per_s": 0.0})
        busy = utility(c, snapshot={"ops_per_s": 1000.0, "bytes_per_s": 0.0})
        assert busy < quiet  # same stack costs more under more load

    def test_no_snapshot_keeps_byte_annotations_in_play(self):
        # BYTES_FIRST with no telemetry must still prefer the low-byte option
        # (nominal workload), not silently degrade to latency-only scoring
        fat = CostModel(op_latency_s=3e-3, dcn_bytes_per_byte=1.0)
        lean = CostModel(op_latency_s=5e-3, dcn_bytes_per_byte=0.25)
        assert utility(lean, BYTES_FIRST) > utility(fat, BYTES_FIRST)

    def test_weight_zero_objective_ignores_that_dimension(self):
        slow_cheap = CostModel(op_latency_s=10.0, dcn_bytes_per_byte=0.1)
        fast_fat = CostModel(op_latency_s=1e-6, dcn_bytes_per_byte=1.0)
        snap = {"ops_per_s": 100.0, "bytes_per_s": 1e6}
        bytes_only = Objective(w_latency=0.0, w_bytes=1.0)
        assert utility(slow_cheap, bytes_only, snap) > utility(fast_fat, bytes_only, snap)
        lat_only = Objective(w_latency=1.0, w_bytes=0.0)
        assert utility(fast_fat, lat_only, snap) > utility(slow_cheap, lat_only, snap)


class TestScoredNegotiation:
    def _stacks(self):
        # distinct exact caps: each server option pairs 1:1 with the client
        # option speaking the same wire format
        def mk(name, lat, ratio):
            return impl(name, lat=lat, ratio=ratio,
                        caps=CapabilitySet.exact(f"wire:{name}"))

        server = make_stack(Select(mk("Legacy", 5e-3, 1.0),
                                   mk("ZipWire", 3e-3, 0.25),
                                   mk("FastPath", 4e-4, 1.0)))
        client = make_stack(Select(mk("Legacy", 5e-3, 1.0),
                                   mk("ZipWire", 3e-3, 0.25),
                                   mk("FastPath", 4e-4, 1.0)))
        return server, client.offer()

    def test_argmax_beats_first_compatible_on_crafted_costs(self):
        server, offer = self._stacks()
        first, _ = pick_compatible(server, offer, mode="first")
        assert first.chunnels[0].name == "Legacy"  # server preference
        chatty = {"ops_per_s": 2000.0, "bytes_per_s": 5e4}
        scored, idx = pick_compatible(server, offer, snapshot=chatty,
                                      objective=LATENCY_FIRST)
        assert scored.chunnels[0].name == "FastPath"
        assert offer[idx][0]["name"] == "FastPath"  # client idx tracks the pick
        bulk = {"ops_per_s": 5.0, "bytes_per_s": 5e7}
        scored, _ = pick_compatible(server, offer, snapshot=bulk,
                                    objective=BYTES_FIRST)
        assert scored.chunnels[0].name == "ZipWire"

    def test_neutral_costs_preserve_preference_order(self):
        a = FnChunnel(fn_name="A", caps=CAPS)
        b = FnChunnel(fn_name="B", caps=CAPS)
        server = make_stack(Select(a, b))
        offer = make_stack(Select(b, a)).offer()
        picked, _ = pick_compatible(server, offer,
                                    snapshot={"ops_per_s": 1e4, "bytes_per_s": 1e7})
        assert picked.chunnels[0].name == "A"  # ties break to server preference

    def test_degenerate_single_option_set(self):
        only = impl("Only", lat=1.0, ratio=2.0, blip=3.0)
        server = make_stack(only)
        offer = make_stack(only).offer()
        picked = pick_compatible(server, offer,
                                 snapshot={"ops_per_s": 1e6, "bytes_per_s": 1e9})
        assert picked is not None and picked[0].chunnels[0].name == "Only"

    def test_no_compatible_option_returns_none(self):
        server = make_stack(impl("A", caps=CapabilitySet.exact("fmt:a")))
        offer = make_stack(impl("B", caps=CapabilitySet.exact("fmt:b"))).offer()
        assert pick_compatible(server, offer) is None
        assert pick_compatible(server, offer, mode="first") is None


class TestScoredTarget:
    def test_resolves_argmax_under_live_snapshot(self):
        cands = [Candidate("fat", CostModel(dcn_bytes_per_byte=1.0), "fat"),
                 Candidate("lean", CostModel(dcn_bytes_per_byte=0.1), "lean")]
        st = ScoredTarget(cands, BYTES_FIRST)
        assert st.resolve({"bytes_per_s": 1e7}, "fat") == "lean"

    def test_margin_keeps_current_on_small_gains(self):
        cands = [Candidate("a", CostModel(op_latency_s=1.00e-3), "a"),
                 Candidate("b", CostModel(op_latency_s=0.99e-3), "b")]
        st = ScoredTarget(cands, LATENCY_FIRST, margin=0.5)
        # b is 1% better: inside the 50% margin, stay on a
        assert st.resolve({"ops_per_s": 100.0}, current_label="a") == "a"
        # but from nowhere (no current), pick the argmax
        assert st.resolve({"ops_per_s": 100.0}) == "b"

    def test_empty_candidates_rejected(self):
        with pytest.raises(ValueError):
            ScoredTarget([])

    def test_controller_switches_to_resolved_target(self):
        cands = [Candidate("A", CostModel(op_latency_s=5e-3), "A"),
                 Candidate("B", CostModel(op_latency_s=1e-4), "B")]
        committed = []
        cur = {"v": "A"}

        def switch(t):
            committed.append(t)
            cur["v"] = t
            return True

        ctl = ReconfigController(
            [Rule("lat", lambda s: True, ScoredTarget(cands, LATENCY_FIRST), hold=1)],
            switch, lambda: cur["v"], cooldown_s=0.0)
        d = ctl.tick({"ops_per_s": 1000.0})
        assert d.committed and committed == ["B"] and d.target == "B"
        # once B is active the same rule resolves to B -> idle, no flap
        d = ctl.tick({"ops_per_s": 1000.0})
        assert d.reason == "idle" and committed == ["B"]


class TestPolicyRegistry:
    def test_builtins_registered(self):
        for name in ("cost_aware", "latency_slo", "byte_budget"):
            assert name in available_policies()

    def test_duplicate_registration_rejected(self):
        @register_policy("test_dup_policy")
        def p1(ctx):
            return []

        try:
            with pytest.raises(ValueError, match="already registered"):
                @register_policy("test_dup_policy")
                def p2(ctx):
                    return []

            # explicit override is allowed
            @register_policy("test_dup_policy", override=True)
            def p3(ctx):
                return [Rule("r", lambda s: True, "X")]

            assert len(policy_rules("test_dup_policy", PolicyContext())) == 1
        finally:
            _POLICIES.pop("test_dup_policy", None)

    def test_unknown_policy_name_raises_with_listing(self):
        with pytest.raises(KeyError, match="cost_aware"):
            get_policy("no_such_policy")

    def test_cost_aware_policy_rules(self):
        ctx = PolicyContext(candidates=[Candidate("a"), Candidate("b")])
        rules = policy_rules("cost_aware", ctx)
        assert len(rules) == 1 and isinstance(rules[0].target, ScoredTarget)

    def test_latency_slo_requires_slo_param(self):
        ctx = PolicyContext(candidates=[Candidate("a")])
        with pytest.raises(KeyError):
            policy_rules("latency_slo", ctx)
        ctx.params["slo_s"] = 0.1
        ctx.default = "a"
        rules = policy_rules("latency_slo", ctx)
        assert {r.name for r in rules} == {"latency_slo:breach", "latency_slo:recovered"}

    def test_byte_budget_drives_controller_to_lean_option(self):
        ctx = PolicyContext(
            candidates=[Candidate("fat", CostModel(dcn_bytes_per_byte=1.0), "fat"),
                        Candidate("lean", CostModel(dcn_bytes_per_byte=0.1), "lean")],
            default="fat", params={"bytes_per_s": 1000.0, "hold": 1})
        rules = policy_rules("byte_budget", ctx)
        committed = []
        cur = {"v": "fat"}

        def switch(t):
            committed.append(t)
            cur["v"] = t
            return True

        ctl = ReconfigController(rules, switch, lambda: cur["v"], cooldown_s=0.0)
        ctl.tick({"bytes_per_s": 5000.0})
        assert committed == ["lean"]
        for _ in range(2):  # recovery (hold=2) brings it back to the default
            ctl.tick({"bytes_per_s": 10.0})
        assert committed == ["lean", "fat"]


class TestTrainerDefaultPolicy:
    def test_scored_budget_target_excludes_mitigation(self):
        # localsgd wins BOTH communication dimensions (it simply skips syncs)
        # but changes training semantics — only the straggler rule may pick
        # it; the scored byte-budget argmax must land on a sync transport
        from repro.train.trainer import trainer_default_policy

        cands = [Candidate("xla", CostModel(3e-3, 1.0, 2.0), "xla"),
                 Candidate("compressed_int8", CostModel(2.5e-3, 0.254, 2.0),
                           "compressed_int8"),
                 Candidate("localsgd", CostModel(1e-3, 0.25, 2.0), "localsgd")]
        ctx = PolicyContext(candidates=cands, default="xla",
                            params={"dcn_budget_bytes_per_s": 1000.0,
                                    "budget_target": None, "hold": 1})
        rules = trainer_default_policy(ctx)
        budget_rule = next(r for r in rules if r.name == "dcn-budget->compressed")
        # 1 GB/s of DCN gradients: the byte savings dwarf the re-jit blip
        resolved = budget_rule.target.resolve(
            {"ops_per_s": 10.0, "bytes_per_s": 1e9}, "xla")
        assert resolved == "compressed_int8"
        # at a low byte rate the amortized re-jit blip wins: stay put
        assert budget_rule.target.resolve(
            {"ops_per_s": 10.0, "bytes_per_s": 1e6}, "xla") == "xla"
        straggler_rule = next(r for r in rules if r.name == "straggler->mitigation")
        assert straggler_rule.target == "localsgd"  # mitigation stays reachable

    def test_transport_candidates_exclude_staleness_trades_by_default(self):
        # any scoring policy fed transport_candidates (cost_aware included)
        # must not see localsgd: it wins the comm-cost contest by changing
        # training semantics, so only an explicit mitigation rule names it
        from types import SimpleNamespace

        from repro.train.trainer import HostSpec, ReconfigurableTrainer

        offers = ["xla", "localsgd", "compressed_int8"]
        shim = SimpleNamespace(hosts=[HostSpec(0, offers), HostSpec(1, offers)])
        cands = ReconfigurableTrainer.transport_candidates(shim)
        assert [c.label for c in cands] == ["xla", "compressed_int8"]
        with_mit = ReconfigurableTrainer.transport_candidates(
            shim, include_mitigations=True)
        assert [c.label for c in with_mit] == offers


class TestConnControllerPolicyPath:
    def _stack(self):
        from repro.core import Fabric, FabricTransport

        fabric = Fabric()
        ep = fabric.register("pol-ep")
        fastpath = FnChunnel(fn_name="FastPath", upper=WireType.of("bytes"),
                             lower=WireType.of("bytes"),
                             cost=CostModel(op_latency_s=1e-4))
        slowpath = FnChunnel(fn_name="SlowPath", upper=WireType.of("bytes"),
                             lower=WireType.of("bytes"),
                             cost=CostModel(op_latency_s=5e-3))
        return make_stack(Select(slowpath, fastpath), FabricTransport(ep, "sink"))

    def test_policy_by_name_replaces_flat_rule_list(self):
        stack = self._stack()
        handle = LockedConn(stack.preferred())
        ctl = conn_controller(handle, stack, policy="cost_aware",
                              policy_params={"hold": 1, "margin": 0.0},
                              cooldown_s=0.0)
        for _ in range(300):
            handle.send([b"x"])
        d = ctl.tick(handle.telemetry.snapshot())
        assert d.committed
        assert handle.stack.chunnels[0].name == "FastPath"

    def test_rules_and_policy_are_mutually_exclusive(self):
        stack = self._stack()
        handle = LockedConn(stack.preferred())
        with pytest.raises(ValueError, match="exactly one"):
            conn_controller(handle, stack)
        with pytest.raises(ValueError, match="exactly one"):
            conn_controller(handle, stack, [Rule("r", lambda s: True, "X")],
                            policy="cost_aware")


class TestKvLoadAdaptive:
    def test_offered_load_moves_the_client_to_client_shard_and_back(
            self, seeded_fabric, virtual_clock):
        """The §7.3 serving policy, by name from the registry, over a live
        KV routing stack: offered load past ``high_ops_per_s`` moves the
        client from ServerRouter to ClientShard, and the drain below
        ``low_ops_per_s`` moves it back. Rates are on the virtual clock;
        the requests are real, through backends and the router."""
        from repro.core.telemetry import ConnTelemetry
        from repro.serving.router import KVBackend, KVClient, Router, routing_stack

        fabric = seeded_fabric()
        backends = [KVBackend(fabric, f"kv{i}") for i in range(4)]
        router = Router(fabric, "router", [b.addr for b in backends])
        ep = fabric.register("cli")
        stack = routing_stack(ep, [b.addr for b in backends], router_addr="router",
                              prefer="server")
        handle = LockedConn(stack.preferred())  # ServerRouter: the low-load default
        handle.telemetry = ConnTelemetry(now=virtual_clock)
        handle.telemetry.bind_reconfig(handle.stats)
        client = KVClient(fabric, ep, handle)
        ctl = conn_controller(
            handle, stack, policy="kv_load_adaptive",
            policy_params={"high_ops_per_s": 150.0, "low_ops_per_s": 120.0, "hold": 2},
            cooldown_s=0.2, now=virtual_clock)
        buf = [None]
        after = []
        try:
            # (offered requests/s, requests, open loop): the high phase sends
            # fire-and-forget with a blocking request every 25th
            for rate, n_req, open_loop in ((70.0, 40, False), (450.0, 250, True),
                                           (55.0, 60, False)):
                for i in range(n_req):
                    virtual_clock.advance(1.0 / rate)
                    if open_loop and i % 25:
                        handle.send([{"op": "get", "key": f"k{i % 37}", "rid": -1,
                                      "reply_to": ep.addr}])
                    else:
                        client.request("put" if i % 3 == 0 else "get", f"k{i % 37}",
                                       val=i, timeout=1.0)
                    if (i + 1) % 10 == 0:
                        ctl.tick(handle.telemetry.snapshot())
                while handle.recv(buf, timeout=0.001):
                    pass  # no stale replies into the next phase
                after.append(handle.stack.chunnels[0].name)
        finally:
            for b in backends:
                b.close()
            router.close()
        assert after == ["ServerRouter", "ClientShard", "ServerRouter"]
        assert [d.rule for d in ctl.switch_log()] == ["high-load->client-shard",
                                                      "low-load->server-router"]
        assert handle.stats.switches == 2


class TestScorerInNegotiator:
    def test_negotiator_scores_with_telemetry_without_resetting_window(self):
        from repro.core import ConnTelemetry, ServerNegotiator

        legacy = impl("Legacy", lat=5e-3)
        fast = impl("FastPath", lat=4e-4)
        server_stack = make_stack(Select(legacy, fast))
        tel = ConnTelemetry()
        for _ in range(50):
            tel.record_send(1, 100, 0.001)
        neg = ServerNegotiator(server_stack, objective=LATENCY_FIRST, telemetry=tel)
        client = make_stack(Select(legacy, fast))
        reply = neg.handle("cli", {
            "type": "offer", "options": client.offer(),
            "fps": [o.fingerprint() for o in client.options()],
        })
        assert reply["type"] == "accept"
        assert neg.negotiated["cli"].chunnels[0].name == "FastPath"
        # the negotiator peeked: the controller's rate window is undisturbed
        assert tel.snapshot()["ops_per_s"] > 0.0

    def test_bare_negotiator_honors_preference_over_annotations(self):
        # evidence-gated scoring: with no telemetry and no objective, static
        # cost annotations must not override the operator's declared Select
        # order (the routing_stack prefer="server" contract)
        from repro.core import ServerNegotiator

        slow_default = impl("SlowDefault", lat=2.4e-3)  # deliberately first
        fast = impl("FastAlt", lat=1.6e-3)
        neg = ServerNegotiator(make_stack(Select(slow_default, fast)))
        client = make_stack(Select(slow_default, fast))
        reply = neg.handle("cli", {
            "type": "offer", "options": client.offer(),
            "fps": [o.fingerprint() for o in client.options()],
        })
        assert reply["type"] == "accept"
        assert neg.negotiated["cli"].chunnels[0].name == "SlowDefault"
