"""Unit + integration tests for the Bertha core (stacks, negotiation,
reconfiguration, rendezvous)."""
import threading
import time

import pytest

from repro.core import (
    ANY,
    BarrierConn,
    Capability,
    CapabilitySet,
    Chunnel,
    Datapath,
    Fabric,
    FabricTransport,
    FnChunnel,
    HostAgent,
    KVStore,
    LinkModel,
    LockedConn,
    NegotiationError,
    Select,
    ServerNegotiator,
    Stack,
    StackTypeError,
    WireType,
    make_stack,
)
from repro.core import rendezvous
from repro.core.reconfigure import two_phase_commit


def T(name, upper, lower, caps=None, multilateral=False):
    return FnChunnel(
        fn_name=name,
        upper=WireType.of(upper),
        lower=WireType.of(lower),
        caps=caps,
        multilateral_=multilateral,
    )


class TestStackTyping:
    def test_compose_ok(self):
        s = make_stack(T("Ser", "obj", "bytes"), T("Udp", "bytes", "unit"))
        assert len(s.preferred()) == 2

    def test_type_mismatch_rejected_at_assembly(self):
        with pytest.raises(StackTypeError):
            make_stack(T("Ser", "obj", "bytes"), T("Tcp", "string", "unit"))

    def test_select_filters_ill_typed_branches(self):
        s = make_stack(
            T("Ser", "obj", "bytes"),
            Select(T("Bad", "string", "unit"), T("Udp", "bytes", "unit")),
        )
        opts = s.options()
        assert len(opts) == 1 and opts[0].chunnels[1].name == "Udp"

    def test_select_preference_order(self):
        s = make_stack(Select(T("A", "bytes", "unit"), T("B", "bytes", "unit")))
        assert [o.chunnels[0].name for o in s.options()] == ["A", "B"]

    def test_nested_select(self):
        s = make_stack(
            Select(
                T("PSP", "bytes", "unit"),
                Select(T("QUIC", "bytes", "unit"),
                       (T("TLS", "bytes", "bytes"), T("TCP", "bytes", "unit"))),
            )
        )
        names = [" ".join(c.name for c in o) for o in s.options()]
        assert names == ["PSP", "QUIC", "TLS TCP"]  # paper §7.1 example

    def test_composition_not_commutative(self):
        a, b = T("A", "x", "x"), T("B", "x", "x")
        assert make_stack(a, b).preferred().fingerprint() != make_stack(
            b, a).preferred().fingerprint()


class TestCapabilities:
    def test_exact_must_match_both(self):
        a = CapabilitySet.exact("ser:protobuf")
        b = CapabilitySet.exact("ser:protobuf")
        c = CapabilitySet.exact("ser:capnproto")
        assert a.compatible_with(b)
        assert not a.compatible_with(c)

    def test_compose_one_side_suffices(self):
        a = CapabilitySet.exact("ser:pb").union_(CapabilitySet.compose("shard"))
        b = CapabilitySet.exact("ser:pb")
        assert a.compatible_with(b) and b.compatible_with(a)

    def test_relative_compat_reuse_label(self):
        # ProtoACC reuses the protobuf capability label (paper §5.2)
        sw = CapabilitySet.exact("ser:protobuf")
        hw = CapabilitySet.exact("ser:protobuf")  # different impl, same label
        assert sw.compatible_with(hw)


def _mk_pair(fabric, caps_client=None, caps_server=None, server_first=True):
    server = HostAgent(fabric, "srv")
    client = HostAgent(fabric, "cli")
    return server, client


class TestNegotiation:
    def test_one_rtt_negotiation(self):
        fabric = Fabric()
        server, client = _mk_pair(fabric)
        sstack = make_stack(
            Select(
                T("Kafka", "obj", "unit", CapabilitySet.exact("pubsub:kafka")),
                T("SQS", "obj", "unit", CapabilitySet.exact("pubsub:sqs")),
            )
        )
        cstack = make_stack(T("SQS", "obj", "unit", CapabilitySet.exact("pubsub:sqs")))
        server.listen(sstack)
        conn = client.connect("srv", cstack)
        assert conn.stack.chunnels[0].name == "SQS"
        assert server.accept_stack("cli").chunnels[0].name == "SQS"
        server.close(); client.close()

    def test_incompatible_rejected(self):
        fabric = Fabric()
        server, client = _mk_pair(fabric)
        server.listen(make_stack(T("A", "obj", "unit", CapabilitySet.exact("fmt:a"))))
        with pytest.raises(NegotiationError):
            client.connect("srv", make_stack(T("B", "obj", "unit",
                                               CapabilitySet.exact("fmt:b"))))
        server.close(); client.close()

    def test_negotiation_over_lossy_base_connection(self):
        fabric = Fabric(default_link=LinkModel(latency_s=0.001, loss=0.3), seed=7)
        server, client = _mk_pair(fabric)
        st = make_stack(T("X", "obj", "unit", CapabilitySet.exact("x")))
        server.listen(st)
        conn = client.connect("srv", st)  # reliability layer must recover
        assert conn.stack.chunnels[0].name == "X"
        server.close(); client.close()

    def test_zero_rtt_resumption(self):
        fabric = Fabric()
        server, client = _mk_pair(fabric)
        st = make_stack(T("X", "obj", "unit", CapabilitySet.exact("x")))
        server.listen(st)
        c1 = client.connect("srv", st, use_zero_rtt=True)
        assert not c1.was_zero_rtt  # first connection pays the RTT
        c2 = client.connect("srv", st, use_zero_rtt=True)
        assert c2.was_zero_rtt
        assert c2.stack.fingerprint() == c1.stack.fingerprint()
        server.close(); client.close()

    def test_zero_rtt_nonce_matches_original_negotiation(self):
        # The nonce encodes the agreed select branches (§7.3 uses it to let
        # backends accept a client's requests) — resuming the SAME stack via
        # 0-RTT must therefore mint the SAME nonce as the 1-RTT negotiation.
        fabric = Fabric()
        server, client = _mk_pair(fabric)
        st = make_stack(T("X", "obj", "unit", CapabilitySet.exact("x")))
        server.listen(st)
        c1 = client.connect("srv", st, use_zero_rtt=True)
        c2 = client.connect("srv", st, use_zero_rtt=True)
        assert not c1.was_zero_rtt and c2.was_zero_rtt
        assert c2.nonce == c1.nonce
        server.close(); client.close()

    def test_zero_rtt_claim_validated_against_cache(self):
        st = make_stack(T("X", "obj", "unit", CapabilitySet.exact("x")))
        neg = ServerNegotiator(st)
        # no prior negotiation with this peer: claim must be rejected
        r = neg.handle("stranger", {"type": "zero_rtt", "fp": "anything"})
        assert r["type"] == "negotiate_failed"
        # negotiate, then claim a DIFFERENT fingerprint: must be rejected too
        accept = neg.handle("cli", {
            "type": "offer", "options": st.offer(),
            "fps": [o.fingerprint() for o in st.options()],
        })
        assert accept["type"] == "accept"
        r = neg.handle("cli", {"type": "zero_rtt", "fp": "not-what-we-agreed"})
        assert r["type"] == "negotiate_failed"
        # the real fingerprint resumes, and with the original nonce
        good = neg.handle("cli", {"type": "zero_rtt",
                                  "fp": st.preferred().fingerprint()})
        assert good["type"] == "zero_rtt_ok"
        assert good["nonce"] == accept["nonce"]

    def test_server_preference_wins(self):
        fabric = Fabric()
        server, client = _mk_pair(fabric)
        ka = T("Kafka", "obj", "unit", CapabilitySet.exact("pubsub:kafka"))
        sq = T("SQS", "obj", "unit", CapabilitySet.exact("pubsub:sqs"))
        server.listen(make_stack(Select(ka, sq)))
        conn = client.connect("srv", make_stack(Select(sq, ka)))
        # server prefers kafka; client offered both; server preference rules
        assert conn.stack.chunnels[0].name == "Kafka"
        server.close(); client.close()


class _CountingChunnel(FnChunnel):
    pass


def _counting(name):
    calls = {"n": 0}

    def on_send(m):
        calls["n"] += 1
        return m

    ch = FnChunnel(fn_name=name, on_send=on_send)
    return ch, calls


class TestReconfiguration:
    def _echo_stack(self, fabric, name="A"):
        ep = fabric.register(f"ep-{name}-{time.monotonic_ns()}")
        ch, calls = _counting(name)
        st = make_stack(ch, FabricTransport(ep, "nowhere"))
        return st, calls

    @pytest.mark.parametrize("cls", [LockedConn, BarrierConn])
    def test_unilateral_swap_preserves_service(self, cls):
        fabric = Fabric()
        st_a, calls_a = self._echo_stack(fabric, "A")
        st_b, calls_b = self._echo_stack(fabric, "B")
        handle = cls(st_a.preferred()) if cls is LockedConn else cls(
            st_a.preferred(), n_threads=1)

        stop = threading.Event()
        sent = {"n": 0}

        def pump():
            while not stop.is_set():
                handle.send([b"x"])
                sent["n"] += 1

        t = threading.Thread(target=pump)
        t.start()
        time.sleep(0.05)
        ok = handle.reconfigure(st_b.preferred())
        time.sleep(0.05)
        stop.set(); t.join()
        assert ok
        assert calls_a["n"] > 0 and calls_b["n"] > 0  # traffic on both impls
        assert handle.stats.switches == 1
        assert sent["n"] == calls_a["n"] + calls_b["n"]  # nothing lost/duplicated

    @pytest.mark.parametrize("cls", [LockedConn, BarrierConn])
    def test_swap_under_three_pumping_threads(self, cls):
        """Three threads send without pause while the handle swaps: the
        swap commits once, whatever the mechanism, and both stacks carry
        traffic (BarrierConn's stop-the-world gathers all three)."""
        fabric = Fabric()
        st_a, calls_a = self._echo_stack(fabric, "A")
        st_b, calls_b = self._echo_stack(fabric, "B")
        handle = (LockedConn(st_a.preferred()) if cls is LockedConn
                  else BarrierConn(st_a.preferred(), n_threads=3))
        stop = threading.Event()

        def pump():
            while not stop.is_set():
                handle.send([b"x"])

        threads = [threading.Thread(target=pump) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        ok = handle.reconfigure(st_b.preferred())
        time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join()
        assert ok and handle.stats.switches == 1
        assert calls_a["n"] > 0 and calls_b["n"] > 0
        assert handle.stack.chunnels[0].name == "B"

    def test_controller_switches_barrier_conn_mid_traffic(self):
        """The closed loop on the lock-free mechanism: three threads pump a
        BarrierConn whose SlowPath takes 2 ms a send; the ``latency_slo``
        policy over live op latency (bound 1 ms) switches it to FastPath
        itself, once, while the threads keep sending."""
        from repro.core import CostModel, conn_controller

        caps = CapabilitySet.exact("wire:obj")

        def slow_send(m):
            time.sleep(2e-3)
            return m

        slow = FnChunnel(fn_name="SlowPath", caps=caps, on_send=slow_send,
                         cost=CostModel(op_latency_s=2e-3, switch_blip_s=1e-4))
        fast = FnChunnel(fn_name="FastPath", caps=caps, on_send=lambda m: m,
                         cost=CostModel(op_latency_s=1e-4, switch_blip_s=1e-4))
        ep = Fabric().register("barrier-ctl")
        stack = make_stack(Select(slow, fast), FabricTransport(ep, "sink"))
        handle = BarrierConn(stack.preferred(), n_threads=3)
        ctl = conn_controller(handle, stack, policy="latency_slo",
                              policy_params={"slo_s": 1e-3, "metric": "op_p95_s",
                                             "hold": 2},
                              cooldown_s=0.1)
        sends = {"SlowPath": 0, "FastPath": 0}
        stop = threading.Event()

        def pump():
            while not stop.is_set():
                name = handle.stack.chunnels[0].name
                handle.send([b"x"])
                sends[name] += 1

        threads = [threading.Thread(target=pump) for _ in range(3)]
        for t in threads:
            t.start()
        t_end = time.monotonic() + 0.6
        while time.monotonic() < t_end:
            time.sleep(0.03)
            ctl.tick(handle.telemetry.snapshot())
        stop.set()
        for t in threads:
            t.join()
        assert handle.stats.switches == 1
        assert handle.stack.chunnels[0].name == "FastPath"
        assert sends["SlowPath"] and sends["FastPath"]

    def test_coordinate_false_aborts(self):
        fabric = Fabric()
        st_a, _ = self._echo_stack(fabric, "A")
        st_b, _ = self._echo_stack(fabric, "B")
        handle = LockedConn(st_a.preferred())
        assert handle.reconfigure(st_b.preferred(), coordinate=lambda: False) is False
        assert handle.stack.chunnels[0].name == "A"


class _PassDP(Datapath):
    def __init__(self, inner):
        self.inner = inner

    def send(self, msgs):
        if self.inner is not None:
            self.inner.send(msgs)

    def recv(self, buf, timeout=None):
        return self.inner.recv(buf, timeout) if self.inner else 0


class _MigCh(Chunnel):
    """Pass-through chunnel that logs every migrate_state call."""

    upper_type = ANY
    lower_type = ANY

    def __init__(self, name, log):
        self._name = name
        self.log = log

    @property
    def name(self):
        return self._name

    def connect_wrap(self, inner):
        return _PassDP(inner)

    def migrate_state(self, old):
        self.log.append(self._name)
        return {f"from_{self._name}": 1}


class _MigChV2(_MigCh):
    """Same name as a _MigCh, different implementation class."""


class TestStateMigrationAlignment:
    def test_new_trailing_layer_migrates_on_depth_mismatch(self):
        # old [A] -> new [A, C]: a positional zip pairs only (A, A) and C
        # never gets to extract state; name alignment must call C.
        log = []
        handle = LockedConn(make_stack(_MigCh("A", log)).preferred())
        new = make_stack(_MigCh("A", log), _MigCh("C", log)).preferred()
        log.clear()
        assert handle.reconfigure(new)
        assert log == ["C"]

    def test_shorter_stack_changed_head_still_migrates(self):
        # old [A, B, C] -> new [D, C]: zip pairs (A,D),(B,C) and the kept C is
        # compared against B (spurious) while D's pairing is right by luck;
        # name alignment: D (new) migrates, C (unchanged, just moved) does not.
        log = []
        handle = LockedConn(
            make_stack(_MigCh("A", log), _MigCh("B", log), _MigCh("C", log)).preferred())
        new = make_stack(_MigCh("D", log), _MigCh("C", log)).preferred()
        log.clear()
        assert handle.reconfigure(new)
        assert log == ["D"]

    def test_reordered_unchanged_layers_do_not_spuriously_migrate(self):
        log = []
        handle = LockedConn(make_stack(_MigCh("A", log), _MigCh("C", log)).preferred())
        new = make_stack(_MigCh("C", log), _MigCh("A", log)).preferred()
        log.clear()
        assert handle.reconfigure(new)
        assert log == []

    def test_same_name_different_impl_migrates(self):
        # relative-compatibility: a different implementation reusing the name
        # still needs the state translated.
        log = []
        handle = LockedConn(make_stack(_MigCh("M", log)).preferred())
        new = make_stack(_MigChV2("M", log)).preferred()
        log.clear()
        assert handle.reconfigure(new)
        assert log == ["M"]


class TestTwoPhaseCommitAbortSafety:
    def _chan(self, sent, *, commit_timeout_for=(), refuse=(), abort_timeout_for=()):
        def chan_request(p, m):
            t = m["type"]
            sent.append((p, t))
            if t == "reconfig_prepare":
                if p in refuse:
                    return {"type": "reconfig_refuse"}
                return {"type": "reconfig_ready"}
            if t == "reconfig_commit" and p in commit_timeout_for:
                raise TimeoutError(p)
            if t == "reconfig_abort" and p in abort_timeout_for:
                raise TimeoutError(p)
            return {"type": "reconfig_done"}
        return chan_request

    def test_commit_phase_timeout_does_not_escape(self):
        # Once all peers are prepared the decision is commit: a delivery
        # failure to p2 must neither raise nor stop p3 from being notified.
        sent = []
        ok = two_phase_commit(self._chan(sent, commit_timeout_for={"p2"}),
                              ["p1", "p2", "p3"], "fp-new")
        assert ok is True
        commits = [p for p, t in sent if t == "reconfig_commit"]
        assert commits == ["p1", "p2", "p3"]

    def test_refusal_aborts_and_abort_timeout_swallowed(self):
        sent = []
        ok = two_phase_commit(
            self._chan(sent, refuse={"p3"}, abort_timeout_for={"p1"}),
            ["p1", "p2", "p3"], "fp-new")
        assert ok is False
        aborts = [p for p, t in sent if t == "reconfig_abort"]
        assert aborts == ["p1", "p2"]  # p1's timeout didn't stop p2's abort
        assert not [p for p, t in sent if t == "reconfig_commit"]


class TestDispatchConnIsolation:
    def test_unknown_conn_refused_and_correct_conn_swaps(self):
        fabric = Fabric()
        srv = HostAgent(fabric, "iso-srv")
        cli = HostAgent(fabric, "iso-cli")
        stack = make_stack(Select(T("A", "obj", "unit"), T("B", "obj", "unit")))
        handle = LockedConn(stack.options()[0])
        srv.register_participant("connA", handle, stack.find)
        fp_b = stack.options()[1].fingerprint()
        # a prepare/commit for an unknown conn must be refused, not routed to
        # an arbitrary participant (it would swap conn A's stack)
        r = cli.request("iso-srv", {"type": "reconfig_prepare", "fp": fp_b,
                                    "conn": "connB"})
        assert r["type"] == "reconfig_refuse"
        r = cli.request("iso-srv", {"type": "reconfig_commit", "fp": fp_b,
                                    "conn": "connB"})
        assert r["type"] == "reconfig_refuse"
        assert handle.stack.fingerprint() == stack.options()[0].fingerprint()
        assert handle.stats.switches == 0
        # the registered conn id still works end-to-end
        r = cli.request("iso-srv", {"type": "reconfig_prepare", "fp": fp_b,
                                    "conn": "connA"})
        assert r["type"] == "reconfig_ready"
        r = cli.request("iso-srv", {"type": "reconfig_commit", "fp": fp_b,
                                    "conn": "connA"})
        assert r["type"] == "reconfig_done"
        assert handle.stack.fingerprint() == fp_b
        srv.close(); cli.close()


class TestRendezvous:
    def test_first_proposer_wins_cas(self):
        store = KVStore()
        r1 = rendezvous.join(store, "conn", "m1", ["fpA"], [[{"name": "A", "caps": []}]],
                             lambda desc: 0)
        assert r1.proposed and r1.stack_fp == "fpA"
        r2 = rendezvous.join(store, "conn", "m2", ["fpB", "fpA"],
                             [[{"name": "B", "caps": []}], [{"name": "A", "caps": []}]],
                             lambda desc: 1)
        assert not r2.proposed and r2.stack_fp == "fpA" and r2.participants == 2

    def test_incompatible_joiner_raises(self):
        store = KVStore()
        rendezvous.join(store, "conn", "m1", ["fpA"], [[{"name": "A", "caps": []}]],
                        lambda desc: 0)
        with pytest.raises(ValueError):
            rendezvous.join(store, "conn", "m2", ["fpB"], [[{"name": "B", "caps": []}]],
                            lambda desc: None)

    def test_late_joiner_recovers_stack(self):
        store = KVStore()
        rendezvous.join(store, "conn", "m1", ["fpA"], [[{"name": "A", "caps": []}]],
                        lambda desc: 0)
        cur = rendezvous.current_stack(store, "conn")
        assert cur["fp"] == "fpA" and cur["epoch"] == 1

    def test_transition_commits_when_all_ack(self):
        store = KVStore()
        for m in ("m1", "m2", "m3"):
            rendezvous.join(store, "conn", m, ["fpA"], [[{"name": "A", "caps": []}]],
                            lambda desc: 0)
        epoch = rendezvous.propose_transition(store, "conn", "m1", "fpB",
                                              [{"name": "B", "caps": []}])
        assert rendezvous.try_commit(store, "conn", epoch, 5.0) is None  # pending
        rendezvous.vote(store, "conn", "m2", epoch, True)
        rendezvous.vote(store, "conn", "m3", epoch, True)
        assert rendezvous.try_commit(store, "conn", epoch, 5.0) is True
        assert rendezvous.current_stack(store, "conn")["fp"] == "fpB"

    def test_any_refusal_aborts(self):
        store = KVStore()
        for m in ("m1", "m2"):
            rendezvous.join(store, "conn", m, ["fpA"], [[{"name": "A", "caps": []}]],
                            lambda desc: 0)
        epoch = rendezvous.propose_transition(store, "conn", "m1", "fpB", [])
        rendezvous.vote(store, "conn", "m2", epoch, False)
        assert rendezvous.try_commit(store, "conn", epoch, 5.0) is False
        assert rendezvous.current_stack(store, "conn")["fp"] == "fpA"

    def test_timeout_aborts(self):
        store = KVStore()
        for m in ("m1", "m2"):
            rendezvous.join(store, "conn", m, ["fpA"], [[{"name": "A", "caps": []}]],
                            lambda desc: 0)
        epoch = rendezvous.propose_transition(store, "conn", "m1", "fpB", [])
        t0 = time.monotonic() - 10.0
        assert rendezvous.try_commit(store, "conn", epoch, 5.0, t0) is False


class TestBatchedFabric:
    """PR 7 data plane: vectorized delivery, split counters, bulk drain."""

    def test_recv_many_order_and_drain(self):
        fabric = Fabric()
        a = fabric.register("bf-a")
        b = fabric.register("bf-b")
        msgs = [f"m{i}".encode() for i in range(100)]
        a.send_batch("bf-b", msgs)
        buf = [None] * 100
        got = []
        deadline = time.monotonic() + 2.0
        while len(got) < 100 and time.monotonic() < deadline:
            n = b.recv_many(buf, timeout=0.1)
            got.extend((src, m) for src, m in buf[:n])
        assert [m for _, m in got] == msgs
        assert all(src == "bf-a" for src, _ in got)
        # drained: an immediate follow-up sees nothing
        assert b.recv_many(buf, timeout=0.0) == 0

    def test_recv_many_respects_max_n(self):
        fabric = Fabric()
        a = fabric.register("mx-a")
        b = fabric.register("mx-b")
        a.send_batch("mx-b", [b"x"] * 10)
        buf = [None] * 10
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            if b.recv_many(buf, max_n=3, timeout=0.1) == 3:
                break
        n2 = b.recv_many(buf, max_n=100, timeout=0.5)
        assert 1 <= n2 <= 7

    def test_split_counters_loss_and_unroutable(self):
        fabric = Fabric(default_link=LinkModel(loss=0.5), seed=3)
        a = fabric.register("sc-a")
        fabric.register("sc-b")
        a.send_batch("sc-b", [b"p" * 8] * 200)
        a.send_batch("ghost", [b"q" * 8] * 10)
        c = fabric.counters.snapshot()
        assert c["sent"] == 210
        assert c["dropped_unroutable"] == 10
        assert 0 < c["dropped_loss"] < 200
        assert c["delivered"] == 200 - c["dropped_loss"]
        assert c["sent_bytes"] == 200 * 8 + 10 * 8

    def test_batch_loss_is_per_message(self):
        """One RNG draw per message within the batch mask — a lossy link
        drops some of a batch, not all-or-nothing."""
        fabric = Fabric(default_link=LinkModel(loss=0.3), seed=11)
        a = fabric.register("pm-a")
        b = fabric.register("pm-b")
        a.send_batch("pm-b", [bytes([i]) for i in range(200)])
        buf = [None] * 200
        got = 0
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            n = b.recv_many(buf, timeout=0.1)
            if n == 0 and got:
                break
            got += n
        assert 0 < got < 200


class TestWindowedReliable:
    """request_window pipelines W frames with cumulative acks (go-back-N)."""

    def _pair(self, loss=0.0, seed=0):
        from repro.core.fabric import ReliableChannel

        fabric = Fabric(default_link=LinkModel(loss=loss), seed=seed)
        c = fabric.register("wr-c")
        s = fabric.register("wr-s")
        cli = ReliableChannel(c, "wr-s", timeout=0.05, retries=60, window=4)
        srv = ReliableChannel(s, "wr-c", timeout=0.05)
        return cli, srv

    def _serve(self, srv, handler, stop):
        while not stop.is_set():
            srv.serve_one(handler, timeout=0.05)

    def test_replies_in_order_over_lossy_link(self):
        cli, srv = self._pair(loss=0.25, seed=5)
        calls = []

        def handler(src, body):
            calls.append(body)
            return body * 10

        stop = threading.Event()
        t = threading.Thread(target=self._serve, args=(srv, handler, stop),
                             daemon=True)
        t.start()
        try:
            replies = cli.request_window(list(range(20)))
        finally:
            stop.set()
            t.join(timeout=2)
        assert replies == [i * 10 for i in range(20)]
        # exactly-once despite retransmissions over a 25%-loss link
        assert sorted(calls) == list(range(20))

    def test_empty_window(self):
        cli, _ = self._pair()
        assert cli.request_window([]) == []

    def test_window_timeout_when_unserved(self):
        from repro.core.fabric import ReliableChannel

        fabric = Fabric()
        c = fabric.register("to-c")
        fabric.register("to-s")
        cli = ReliableChannel(c, "to-s", timeout=0.01, retries=3)
        with pytest.raises(TimeoutError):
            cli.request_window([1, 2, 3])

    def test_reply_cache_bounded(self):
        cli, srv = self._pair()
        srv_small = srv
        srv_small.reply_cache_size = 8
        stop = threading.Event()
        t = threading.Thread(target=self._serve,
                             args=(srv_small, lambda s, b: b, stop), daemon=True)
        t.start()
        try:
            for i in range(50):
                assert cli.request(i) == i
        finally:
            stop.set()
            t.join(timeout=2)
        assert len(srv_small._reply_cache) <= 8
        assert sum(len(d) for d in srv_small._reply_order.values()) <= 8
