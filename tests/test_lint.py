"""Tests for repro.lint: every rule gets a good/bad fixture pair, the runtime
stack verifier is proven clean on the repo's real stacks and loud on seeded-bad
ones, and the CI contract (``--strict`` clean over src/repro) is itself a test.
"""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core import CapabilitySet, FnChunnel, Select, WireType, make_stack
from repro.lint import (
    RULES,
    builtin_stacks,
    lint_paths,
    lint_sources,
    verify_stack,
)
from repro.lint.findings import apply_baseline, load_baseline, write_baseline

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src" / "repro"

# fixture paths: hygiene/concurrency rules scope on path fragments, so bad
# snippets are "located" inside the control plane
CORE = "src/repro/core/fixture.py"


def rules_of(findings):
    return {f.rule for f in findings}


def T(name, upper, lower, caps=None, multilateral=False):
    return FnChunnel(
        fn_name=name,
        upper=WireType.of(upper),
        lower=WireType.of(lower),
        caps=caps,
        multilateral_=multilateral,
    )


# ---------------------------------------------------------------------------
# stack verifier: static (AST) half
# ---------------------------------------------------------------------------


class TestMigrateSignature:
    def test_bad_arity_flagged(self):
        src = (
            "class C:\n"
            "    def migrate_state(self):\n"
            "        return {}\n"
            "    def apply_state(self, state, extra):\n"
            "        pass\n"
        )
        fs = lint_sources({CORE: src})
        assert [f.rule for f in fs] == ["stack-migrate-signature"] * 2

    def test_good_arity_clean(self):
        src = (
            "class C:\n"
            "    def migrate_state(self, old):\n"
            "        return {}\n"
            "    def apply_state(self, state):\n"
            "        pass\n"
            "    def restore_state(self, state):\n"
            "        pass\n"
        )
        assert lint_sources({CORE: src}) == []

    def test_star_args_flagged(self):
        src = "class C:\n    def migrate_state(self, *a):\n        pass\n"
        assert rules_of(lint_sources({CORE: src})) == {"stack-migrate-signature"}


# ---------------------------------------------------------------------------
# stack verifier: runtime half
# ---------------------------------------------------------------------------


class TestVerifyStack:
    def test_shipped_stacks_clean(self):
        # the satellite guarantee: zero false positives on the real router
        # Select and the trainer transport Select (imports jax)
        for name, stack in builtin_stacks().items():
            assert verify_stack(stack, name) == [], name

    def test_dead_option_detected(self):
        # B's lower type clashes with the transport: that Select arm is dead
        st = make_stack(
            Select(T("A", "obj", "bytes"), T("B", "obj", "string")),
            T("Udp", "bytes", "unit"),
        )
        fs = verify_stack(st, "seeded")
        assert rules_of(fs) == {"stack-dead-option"}
        assert "B" in fs[0].message

    def test_capability_closure_violation(self):
        # exact wire capabilities differ across options on NON-multilateral
        # chunnels: a unilateral swap would break the wire contract
        st = make_stack(Select(
            T("Json", "obj", "unit", CapabilitySet.exact("fmt:json")),
            T("Proto", "obj", "unit", CapabilitySet.exact("fmt:proto")),
        ))
        assert rules_of(verify_stack(st, "seeded")) == {"stack-capability-closure"}

    def test_capability_closure_ok_when_multilateral(self):
        st = make_stack(Select(
            T("Json", "obj", "unit", CapabilitySet.exact("fmt:json"),
              multilateral=True),
            T("Proto", "obj", "unit", CapabilitySet.exact("fmt:proto"),
              multilateral=True),
        ))
        assert verify_stack(st, "ok") == []

    def test_compose_capabilities_never_block(self):
        st = make_stack(Select(
            T("A", "obj", "unit", CapabilitySet.compose("route:a")),
            T("B", "obj", "unit", CapabilitySet.compose("route:b")),
        ))
        assert verify_stack(st, "ok") == []

    def test_swap_alignment_name_reuse_across_classes(self):
        class Other(FnChunnel):
            pass

        st = make_stack(Select(
            T("Same", "obj", "unit"),
            Other(fn_name="Same", upper=WireType.of("obj"),
                  lower=WireType.of("unit")),
        ))
        assert rules_of(verify_stack(st, "seeded")) == {"stack-swap-alignment"}

    def test_swap_alignment_duplicate_in_one_option(self):
        st = make_stack(T("Dup", "obj", "obj"), T("Dup", "obj", "unit"))
        assert rules_of(verify_stack(st, "seeded")) == {"stack-swap-alignment"}

    def test_semantic_order(self):
        comp = T("Lz", "obj", "obj", CapabilitySet.exact("compression:lz"),
                 multilateral=True)
        rel = T("Ack", "obj", "obj", CapabilitySet.exact("reliability:ack"),
                multilateral=True)
        udp = T("Udp", "obj", "unit")
        good = make_stack(comp, rel, udp)
        assert verify_stack(good, "good") == []
        bad = make_stack(rel, comp, udp)
        fs = verify_stack(bad, "seeded")
        assert rules_of(fs) == {"stack-semantic-order"}
        assert "reliability" in fs[0].message


# ---------------------------------------------------------------------------
# concurrency analyzer
# ---------------------------------------------------------------------------


LOCK_PREAMBLE = (
    "import threading\n"
    "import time\n"
    "import queue\n"
    "class C:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._other = threading.Lock()\n"
    "        self._q = queue.Queue()\n"
    "        self.x = 0\n"
)


class TestLockOrder:
    def test_inversion_detected(self):
        src = LOCK_PREAMBLE + (
            "    def a(self):\n"
            "        with self._lock:\n"
            "            with self._other:\n"
            "                pass\n"
            "    def b(self):\n"
            "        with self._other:\n"
            "            with self._lock:\n"
            "                pass\n"
        )
        fs = lint_sources({CORE: src})
        assert rules_of(fs) == {"lock-order"}
        assert "opposite orders" in fs[0].message

    def test_consistent_order_clean(self):
        src = LOCK_PREAMBLE + (
            "    def a(self):\n"
            "        with self._lock:\n"
            "            with self._other:\n"
            "                pass\n"
            "    def b(self):\n"
            "        with self._lock:\n"
            "            with self._other:\n"
            "                pass\n"
        )
        assert lint_sources({CORE: src}) == []

    def test_reacquire_nonreentrant(self):
        src = LOCK_PREAMBLE + (
            "    def a(self):\n"
            "        with self._lock:\n"
            "            with self._lock:\n"
            "                pass\n"
        )
        fs = lint_sources({CORE: src})
        assert rules_of(fs) == {"lock-order"}
        assert "re-acquired" in fs[0].message

    def test_rlock_reentry_allowed(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "    def a(self):\n"
            "        with self._lock:\n"
            "            with self._lock:\n"
            "                pass\n"
        )
        assert lint_sources({CORE: src}) == []


class TestBlockingUnderLock:
    def test_sleep_under_lock(self):
        src = LOCK_PREAMBLE + (
            "    def a(self):\n"
            "        with self._lock:\n"
            "            time.sleep(0.1)\n"
        )
        assert rules_of(lint_sources({CORE: src})) == {"blocking-under-lock"}

    def test_sleep_outside_lock_clean(self):
        src = LOCK_PREAMBLE + (
            "    def a(self):\n"
            "        with self._lock:\n"
            "            self.x = 1\n"
            "        time.sleep(0.1)\n"
        )
        assert lint_sources({CORE: src}) == []

    def test_queue_get_under_lock(self):
        src = LOCK_PREAMBLE + (
            "    def a(self):\n"
            "        with self._lock:\n"
            "            return self._q.get(timeout=1.0)\n"
        )
        assert rules_of(lint_sources({CORE: src})) == {"blocking-under-lock"}

    def test_kv_transact_under_lock(self):
        src = LOCK_PREAMBLE + (
            "    def a(self, store):\n"
            "        with self._lock:\n"
            "            store.transact_retry(lambda t: None)\n"
        )
        assert rules_of(lint_sources({CORE: src})) == {"blocking-under-lock"}

    def test_caller_supplied_callable_under_lock(self):
        src = LOCK_PREAMBLE + (
            "    def a(self, fn):\n"
            "        with self._lock:\n"
            "            return fn()\n"
        )
        fs = lint_sources({CORE: src})
        assert rules_of(fs) == {"blocking-under-lock"}
        assert "caller-supplied" in fs[0].message

    def test_txn_closure_analyzed_as_locked(self):
        # fn passed to a PESSIMISTIC .transact runs with the store lock held
        src = (
            "import time\n"
            "def hot(store):\n"
            "    def _fn(txn):\n"
            "        time.sleep(1.0)\n"
            "    return store.transact(_fn)\n"
        )
        fs = lint_sources({CORE: src})
        assert rules_of(fs) == {"blocking-under-lock"}
        assert "pessimistic" in fs[0].message

    def test_condition_wait_on_held_condition_allowed(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._cv = threading.Condition()\n"
            "    def a(self):\n"
            "        with self._cv:\n"
            "            self._cv.wait(timeout=0.1)\n"
        )
        assert lint_sources({CORE: src}) == []

    def test_event_wait_under_lock_flagged(self):
        src = LOCK_PREAMBLE + (
            "    def a(self, ev):\n"
            "        with self._lock:\n"
            "            ev.wait()\n"
        )
        assert rules_of(lint_sources({CORE: src})) == {"blocking-under-lock"}


class TestUnguardedAttr:
    def test_unguarded_write_flagged(self):
        src = LOCK_PREAMBLE + (
            "    def a(self):\n"
            "        self.x = 1\n"
            "    def b(self):\n"
            "        with self._lock:\n"
            "            return self.x\n"
        )
        fs = lint_sources({CORE: src})
        assert rules_of(fs) == {"unguarded-attr"}
        assert "self.x" in fs[0].message

    def test_guarded_write_clean(self):
        src = LOCK_PREAMBLE + (
            "    def a(self):\n"
            "        with self._lock:\n"
            "            self.x = 1\n"
            "    def b(self):\n"
            "        with self._lock:\n"
            "            return self.x\n"
        )
        assert lint_sources({CORE: src}) == []

    def test_private_to_one_method_clean(self):
        # written without the lock but no OTHER method touches it
        src = LOCK_PREAMBLE + (
            "    def a(self):\n"
            "        self.only_here = 1\n"
            "        return self.only_here\n"
        )
        assert lint_sources({CORE: src}) == []

    def test_thread_target_write_flagged(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self.n = 0\n"
            "        threading.Thread(target=self._loop).start()\n"
            "    def _loop(self):\n"
            "        self.n = self.n + 1\n"
            "    def snapshot(self):\n"
            "        return self.n\n"
        )
        fs = lint_sources({CORE: src})
        assert rules_of(fs) == {"unguarded-attr"}
        assert "spawned thread" in fs[0].message


# ---------------------------------------------------------------------------
# compat boundary
# ---------------------------------------------------------------------------


class TestCompatBoundary:
    def test_direct_gated_attribute(self):
        src = "import jax\njax.shard_map(lambda x: x)\n"
        assert rules_of(lint_sources({"src/repro/comm/x.py": src})) == \
            {"compat-boundary"}

    def test_from_import_gated(self):
        src = "from jax.experimental.shard_map import shard_map\n"
        assert rules_of(lint_sources({"src/repro/comm/x.py": src})) == \
            {"compat-boundary"}

    def test_aliased_module_chain(self):
        src = ("import jax.experimental.shard_map\n"
               "f = jax.experimental.shard_map.shard_map\n")
        assert rules_of(lint_sources({"src/repro/comm/x.py": src})) == \
            {"compat-boundary"}

    def test_axis_type_and_mesh_api(self):
        src = ("from jax.sharding import AxisType\n"
               "import jax\n"
               "jax.sharding.set_mesh(None)\n")
        fs = lint_sources({"src/repro/models/x.py": src})
        assert [f.rule for f in fs] == ["compat-boundary"] * 2

    def test_make_mesh_axis_types_kwarg_only(self):
        bad = "import jax\njax.make_mesh((1,), ('x',), axis_types=None)\n"
        good = "import jax\njax.make_mesh((1,), ('x',))\n"
        assert rules_of(lint_sources({"src/repro/models/x.py": bad})) == \
            {"compat-boundary"}
        assert lint_sources({"src/repro/models/x.py": good}) == []

    def test_cost_analysis_outside_compat(self):
        bad = "def f(compiled):\n    return compiled.cost_analysis()\n"
        good = ("from repro import compat\n"
                "def f(compiled):\n    return compat.cost_analysis(compiled)\n")
        assert rules_of(lint_sources({"src/repro/launch/x.py": bad})) == \
            {"compat-boundary"}
        assert lint_sources({"src/repro/launch/x.py": good}) == []

    def test_compat_package_exempt(self):
        src = "import jax\njax.shard_map(lambda x: x)\n"
        assert lint_sources({"src/repro/compat/x.py": src}) == []

    def test_sanctioned_wrapper_clean(self):
        src = ("from repro import compat\n"
               "mesh = compat.make_mesh((1,), ('x',))\n"
               "compat.set_mesh(mesh)\n")
        assert lint_sources({"src/repro/train/x.py": src}) == []


# ---------------------------------------------------------------------------
# hygiene
# ---------------------------------------------------------------------------


class TestHygiene:
    def test_silent_except_flagged(self):
        src = "try:\n    f()\nexcept Exception:\n    pass\n"
        assert rules_of(lint_sources({CORE: src})) == {"silent-except"}

    def test_bare_except_flagged(self):
        src = "try:\n    f()\nexcept:\n    pass\n"
        assert rules_of(lint_sources({CORE: src})) == {"silent-except"}

    def test_typed_except_pass_ok(self):
        # swallowing a SPECIFIC exception is a statement, not an accident
        src = "try:\n    f()\nexcept TimeoutError:\n    pass\n"
        assert lint_sources({CORE: src}) == []

    def test_handled_broad_except_ok(self):
        src = ("import logging\n"
               "try:\n    f()\n"
               "except Exception as e:\n    logging.debug('%s', e)\n")
        assert lint_sources({CORE: src}) == []

    def test_out_of_scope_not_flagged(self):
        src = "try:\n    f()\nexcept Exception:\n    pass\n"
        assert lint_sources({"src/repro/compat/x.py": src}) == []
        assert lint_sources({"src/repro/models/x.py": src}) == []

    def test_mutable_default(self):
        bad = "def f(x, acc=[]):\n    return acc\n"
        good = "def f(x, acc=None):\n    return acc or []\n"
        assert rules_of(lint_sources({CORE: bad})) == {"mutable-default"}
        assert lint_sources({CORE: good}) == []


# ---------------------------------------------------------------------------
# pragmas + baseline
# ---------------------------------------------------------------------------


class TestPragmas:
    BAD = "def f(x, acc=[]):  # lint: allow[mutable-default] fixture justification\n    return acc\n"

    def test_inline_pragma_suppresses(self):
        assert lint_sources({CORE: self.BAD}) == []

    def test_pragma_on_line_above(self):
        src = ("# lint: allow[mutable-default] fixture justification\n"
               "def f(x, acc=[]):\n    return acc\n")
        assert lint_sources({CORE: src}) == []

    def test_pragma_needs_reason(self):
        src = "def f(x, acc=[]):  # lint: allow[mutable-default]\n    return acc\n"
        assert rules_of(lint_sources({CORE: src})) == {"pragma-missing-reason"}

    def test_unknown_rule_flagged(self):
        src = "x = 1  # lint: allow[no-such-rule] because\n"
        assert rules_of(lint_sources({CORE: src})) == {"pragma-unknown-rule"}

    def test_wrong_rule_does_not_suppress(self):
        src = "def f(x, acc=[]):  # lint: allow[silent-except] wrong rule\n    return acc\n"
        assert rules_of(lint_sources({CORE: src})) == {"mutable-default"}

    def test_def_line_pragma_covers_function(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.x = self.y = 0\n"
            "    def apply(self):  # lint: allow[unguarded-attr] callers hold the lock\n"
            "        self.x = 1\n"
            "        self.y = 2\n"
            "    def read(self):\n"
            "        with self._lock:\n"
            "            return self.x + self.y\n"
        )
        assert lint_sources({CORE: src}) == []

    def test_pragma_in_docstring_inert(self):
        src = '"""Docs mention # lint: allow[nope] syntax."""\nx = 1\n'
        assert lint_sources({CORE: src}) == []


def scoped(tmp_path, name="legacy.py"):
    # hygiene rules scope on the "repro/core/" path fragment
    d = tmp_path / "repro" / "core"
    d.mkdir(parents=True, exist_ok=True)
    return d / name


class TestBaseline:
    def test_roundtrip(self, tmp_path):
        f = scoped(tmp_path)
        f.write_text("def f(x, acc=[]):\n    return acc\n")
        findings, lines = lint_paths([str(f)])
        assert rules_of(findings) == {"mutable-default"}
        bl = tmp_path / "baseline.json"
        write_baseline(bl, findings, lines)
        left = apply_baseline(findings, load_baseline(bl), lines)
        assert left == []

    def test_baseline_resurfaces_on_change(self, tmp_path):
        f = scoped(tmp_path)
        f.write_text("def f(x, acc=[]):\n    return acc\n")
        findings, lines = lint_paths([str(f)])
        bl = tmp_path / "baseline.json"
        write_baseline(bl, findings, lines)
        # the flagged line CHANGES: its fingerprint no longer matches
        f.write_text("def f(y, acc=[]):\n    return acc\n")
        findings2, lines2 = lint_paths([str(f)])
        left = apply_baseline(findings2, load_baseline(bl), lines2)
        assert rules_of(left) == {"mutable-default"}


# ---------------------------------------------------------------------------
# the CI contract
# ---------------------------------------------------------------------------


class TestRepoIsClean:
    def test_src_repro_lints_clean(self):
        # what --strict enforces in CI, as a test: every suppression in the
        # tree is justified and nothing else fires
        findings, _ = lint_paths([str(SRC)], root=REPO)
        assert findings == [], [f.format() for f in findings]

    def test_every_rule_documented(self):
        for rule, doc in RULES.items():
            assert doc and len(doc) > 10, rule


class TestCLI:
    def run(self, *args, cwd=None):
        env_src = str(REPO / "src")
        return subprocess.run(
            [sys.executable, "-m", "repro.lint", *args],
            capture_output=True, text=True, cwd=cwd or REPO,
            env={"PYTHONPATH": env_src, "PATH": "/usr/bin:/bin:/usr/local/bin"},
        )

    def test_strict_fails_on_violation(self, tmp_path):
        bad = scoped(tmp_path, "bad.py")
        bad.write_text("def f(x, acc=[]):\n    return acc\n")
        r = self.run("--strict", str(bad))
        assert r.returncode == 1
        assert "mutable-default" in r.stdout

    def test_strict_clean_exits_zero(self, tmp_path):
        good = tmp_path / "good.py"
        good.write_text("def f(x, acc=None):\n    return acc\n")
        r = self.run("--strict", str(good))
        assert r.returncode == 0, r.stdout + r.stderr

    def test_json_report(self, tmp_path):
        bad = scoped(tmp_path, "bad.py")
        bad.write_text("def f(x, acc=[]):\n    return acc\n")
        out = tmp_path / "report.json"
        r = self.run(str(bad), "--json", str(out))
        assert r.returncode == 0  # not strict: report, don't fail
        rep = json.loads(out.read_text())
        assert rep["n_findings"] == 1
        assert rep["findings"][0]["rule"] == "mutable-default"

    def test_list_rules(self):
        r = self.run("--list-rules")
        assert r.returncode == 0
        for rule in ("lock-order", "compat-boundary", "stack-dead-option"):
            assert rule in r.stdout


class TestPerMessageHotPath:
    BAD_DP = (
        "class ShimDP:\n"
        "    def send(self, msgs):\n"
        "        for m in msgs:\n"
        "            self.inner.send([m])\n"
    )
    BAD_FABRIC = (
        "class Fabric:\n"
        "    def send_batch(self, src, dst, msgs):\n"
        "        for m in msgs:\n"
        "            self._eps[dst].inbox.put((src, m))\n"
    )
    GOOD_BATCH = (
        "class ShimDP:\n"
        "    def send(self, msgs):\n"
        "        out = [self.fn(m) for m in msgs]\n"
        "        self.inner.send(out)\n"
    )
    GOOD_GROUPING = (
        "class RouteDP:\n"
        "    def send(self, msgs):\n"
        "        by_dst = {}\n"
        "        for m in msgs:\n"
        "            by_dst.setdefault(m['dst'], []).append(m)\n"
        "        for dst, batch in by_dst.items():\n"
        "            self.ep.send_batch(dst, batch)\n"
    )

    def test_singleton_send_loop_flagged(self):
        assert rules_of(lint_sources({CORE: self.BAD_DP})) == {
            "per-message-hot-path"}

    def test_per_message_queue_put_flagged(self):
        assert rules_of(lint_sources({CORE: self.BAD_FABRIC})) == {
            "per-message-hot-path"}

    def test_comprehension_delivery_flagged(self):
        src = ("class PushDP:\n"
               "    def send(self, msgs):\n"
               "        [self.broker.publish(t, m) for t, m in msgs]\n")
        assert rules_of(lint_sources({CORE: src})) == {"per-message-hot-path"}

    def test_batched_send_ok(self):
        assert lint_sources({CORE: self.GOOD_BATCH}) == []

    def test_per_destination_send_batch_ok(self):
        # grouping loops that forward whole sub-batches stay legal
        assert lint_sources({CORE: self.GOOD_GROUPING}) == []

    def test_inherited_datapath_base_is_hot(self):
        src = ("class Shim(Datapath):\n"
               "    def recv(self, buf, timeout=None):\n"
               "        while True:\n"
               "            buf.append(self.inner.request(1))\n")
        assert rules_of(lint_sources({CORE: src})) == {"per-message-hot-path"}

    def test_cold_class_not_flagged(self):
        src = ("class Planner:\n"
               "    def send(self, msgs):\n"
               "        for m in msgs:\n"
               "            self.inner.send([m])\n")
        assert lint_sources({CORE: src}) == []

    def test_cold_method_not_flagged(self):
        src = ("class ShimDP:\n"
               "    def close(self):\n"
               "        for c in self.children:\n"
               "            c.send(b'bye')\n")
        assert lint_sources({CORE: src}) == []

    def test_pragma_suppresses(self):
        src = ("class ShimDP:\n"
               "    def send(self, msgs):\n"
               "        for m in msgs:\n"
               "            # lint: allow[per-message-hot-path] fixture justification\n"
               "            self.inner.send([m])\n")
        assert lint_sources({CORE: src}) == []


class TestSpanInHotLoop:
    BAD_SPAN_LOOP = (
        "class ShimDP:\n"
        "    def send(self, msgs):\n"
        "        for m in msgs:\n"
        "            with TRACER.span('msg'):\n"
        "                pass\n"
        "        self.inner.send(msgs)\n"
    )
    BAD_BEGIN_SPAN_WHILE = (
        "class Fabric:\n"
        "    def recv_many(self, buf, timeout=None):\n"
        "        while True:\n"
        "            sp = TRACER.begin_span('frame')\n"
        "            sp.end()\n"
    )
    GOOD_BATCH_SPAN = (
        "class ShimDP:\n"
        "    def send(self, msgs):\n"
        "        with TRACER.span('batch'):\n"
        "            self.inner.send(msgs)\n"
    )
    GOOD_RECORD_BATCH = (
        "class ShimDP:\n"
        "    def send(self, msgs):\n"
        "        for dst, batch in msgs.items():\n"
        "            TRACER.record_batch('chunnel.send', len(batch), len(batch))\n"
        "            self.ep.send_batch(dst, batch)\n"
    )

    def test_span_per_message_flagged(self):
        assert rules_of(lint_sources({CORE: self.BAD_SPAN_LOOP})) == {
            "span-in-hot-loop"}

    def test_begin_span_in_while_flagged(self):
        assert rules_of(lint_sources({CORE: self.BAD_BEGIN_SPAN_WHILE})) == {
            "span-in-hot-loop"}

    def test_batch_level_span_ok(self):
        assert lint_sources({CORE: self.GOOD_BATCH_SPAN}) == []

    def test_stage_per_frame_flagged(self):
        src = ("class ShimDP:\n"
               "    def recv(self, buf, timeout=None):\n"
               "        for k, frame in enumerate(self.frames):\n"
               "            with TRACER.stage('wire.decode.join'):\n"
               "                buf[k] = frame\n"
               "        with TRACER.stage('wire.recv.fabric'):\n"
               "            return self.inner.recv(buf, timeout)\n")
        findings = lint_sources({CORE: src})
        assert rules_of(findings) == {"span-in-hot-loop"}
        assert [f.line for f in findings] == [4]  # the batch-level stage is legal

    def test_record_batch_in_loop_ok(self):
        # record_batch is the sanctioned per-batch instrument — legal even
        # inside a per-destination grouping loop
        assert lint_sources({CORE: self.GOOD_RECORD_BATCH}) == []

    def test_cold_class_span_loop_ok(self):
        src = ("class Planner:\n"
               "    def send(self, msgs):\n"
               "        for m in msgs:\n"
               "            with TRACER.span('plan'):\n"
               "                pass\n")
        assert lint_sources({CORE: src}) == []

    def test_pragma_suppresses(self):
        src = ("class ShimDP:\n"
               "    def send(self, msgs):\n"
               "        for m in msgs:\n"
               "            # lint: allow[span-in-hot-loop] fixture justification\n"
               "            sp = TRACER.span('m')\n")
        assert lint_sources({CORE: src}) == []


class TestObsHotClasses:
    """PR 10: the observability aggregation classes are hot — their per-tick
    methods run over every member/SLO, so the data-plane rules apply, and
    the SLO engine's lock discipline (compute locked, I/O after release) is
    checkable as blocking-under-lock."""

    OBS = "src/repro/obs/fixture.py"

    def test_span_per_member_in_federator_view_flagged(self):
        src = ("class MetricsFederator:\n"
               "    def view(self, now=None):\n"
               "        out = {}\n"
               "        for m, rec in self.members().items():\n"
               "            with TRACER.span('member'):\n"
               "                out[m] = rec\n"
               "        return out\n")
        assert rules_of(lint_sources({self.OBS: src})) == {"span-in-hot-loop"}

    def test_per_member_publish_loop_flagged(self):
        src = ("class MetricsPublisher:\n"
               "    def publish(self):\n"
               "        for key, rec in self.records():\n"
               "            self.store.put(key, rec)\n")
        assert rules_of(lint_sources({self.OBS: src})) == {
            "per-message-hot-path"}

    def test_kv_transact_under_engine_lock_flagged(self):
        # the SLO engine must never touch the KV plane while holding its
        # lock: the view is sampled before, side effects fire after release
        src = ("import threading\n"
               "class SLOEngine:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "    def observe(self, store):\n"
               "        with self._lock:\n"
               "            store.transact_retry(lambda t: None)\n")
        assert rules_of(lint_sources({self.OBS: src})) == {
            "blocking-under-lock"}

    def test_compute_locked_io_after_release_clean(self):
        # the shipped SLOEngine.observe shape: fold under the lock, fire
        # recorder/tracer work on the collected list afterwards
        src = ("import threading\n"
               "class SLOEngine:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "        self.fired = []\n"
               "    def observe(self, view, recorder):\n"
               "        with self._lock:\n"
               "            fired = list(self.fired)\n"
               "        for ev in fired:\n"
               "            recorder.dump(ev)\n")
        assert lint_sources({self.OBS: src}) == []

    def test_shipped_obs_modules_clean_under_extended_rules(self):
        fs, _src = lint_paths([SRC / "obs" / "federate.py",
                               SRC / "obs" / "slo.py"])
        assert fs == [], [str(f) for f in fs]
