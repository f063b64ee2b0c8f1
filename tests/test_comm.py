"""Comm-chunnel tests: collective transports agree with psum; flash-decode
combine agrees with the local oracle; compression round-trips."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.comm import chunnels, compress, kvshard
from repro.comm import collectives as C


@pytest.fixture(scope="module")
def mesh():
    return compat.make_mesh((2, 4), ("pod", "data"),
                            axis_types=(compat.AUTO,) * 2)


def tree_of(key, sizes=((17,), (3, 5), (64,))):
    ks = jax.random.split(key, len(sizes))
    return {f"p{i}": jax.random.normal(k, s) for i, (k, s) in enumerate(zip(ks, sizes))}


def run_manual(mesh, axes, fn, *args):
    # partial-manual shard_map composes with the auto partitioner, so it must
    # run under jit (as it always does in the real step functions)
    f = compat.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False, axis_names=set(axes))
    return jax.jit(f)(*args)


class TestCollectives:
    def test_ring_equals_psum(self, mesh):
        t = tree_of(jax.random.PRNGKey(0))
        ref = run_manual(mesh, {"pod"}, lambda x: C.psum_tree(x, "pod"), t)
        out = run_manual(mesh, {"pod"}, lambda x: C.ring_tree(x, "pod"), t)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5), ref, out)

    def test_ring_lowers_to_collective_permute(self, mesh):
        t = tree_of(jax.random.PRNGKey(0))
        f = jax.jit(lambda x: run_manual(mesh, {"pod"}, lambda y: C.ring_tree(y, "pod"), x))
        txt = f.lower(t).compile().as_text()
        assert "collective-permute" in txt
        assert txt.count("all-reduce") == 0  # truly manual schedule

    def test_hierarchical_equals_psum(self, mesh):
        t = tree_of(jax.random.PRNGKey(1))
        ref = run_manual(mesh, {"pod", "data"},
                         lambda x: C.psum_tree(C.psum_tree(x, "pod"), "data"), t)
        out = run_manual(mesh, {"pod", "data"},
                         lambda x: C.hierarchical_tree(x, "data", "pod"), t)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5), ref, out)

    def test_hierarchical_schedule_ops(self, mesh):
        t = tree_of(jax.random.PRNGKey(1))
        f = jax.jit(lambda x: run_manual(
            mesh, {"pod", "data"}, lambda y: C.hierarchical_tree(y, "data", "pod"), x))
        txt = f.lower(t).compile().as_text()
        assert "reduce-scatter" in txt and "all-gather" in txt

    def test_compressed_close_to_psum(self, mesh):
        t = tree_of(jax.random.PRNGKey(2))
        ref = run_manual(mesh, {"pod"}, lambda x: C.psum_tree(x, "pod"), t)
        out = run_manual(mesh, {"pod"}, lambda x: C.compressed_tree(x, "pod", block=32), t)
        # int8 wire: 1/127 relative error per element bound
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=2 * 4 / 127 * np.abs(a).max()),
            ref, out)

    def test_hier_compressed_close_to_psum(self, mesh):
        t = tree_of(jax.random.PRNGKey(3))
        ref = run_manual(mesh, {"pod", "data"},
                         lambda x: C.psum_tree(C.psum_tree(x, "pod"), "data"), t)
        out = run_manual(mesh, {"pod", "data"},
                         lambda x: C.hierarchical_compressed_tree(x, "data", "pod", block=32), t)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=4e-1 * max(1.0, np.abs(a).max())),
            ref, out)


class TestCompression:
    @pytest.mark.parametrize("shape", [(100,), (17, 3), (256,), (1, 1)])
    @pytest.mark.parametrize("block", [16, 256])
    def test_roundtrip_error_bound(self, shape, block):
        x = jax.random.normal(jax.random.PRNGKey(0), shape) * 3.0
        q, s = compress.quantize_int8(x, block=block)
        y = compress.dequantize_int8(q, s, shape, block=block)
        per_block_max = np.abs(np.asarray(x)).max()
        assert np.abs(np.asarray(x - y)).max() <= per_block_max / 127.0 + 1e-6

    def test_error_feedback_reduces_bias(self):
        # with EF, the *accumulated* transmitted signal tracks the true signal
        x = jax.random.normal(jax.random.PRNGKey(1), (512,)) * 0.01
        resid = jnp.zeros_like(x)
        sent_sum = jnp.zeros_like(x)
        for _ in range(20):
            g = x + resid
            q, s = compress.quantize_int8(g, block=64)
            dq = compress.dequantize_int8(q, s, g.shape, block=64)
            sent_sum = sent_sum + dq
            resid = g - dq
        drift = np.abs(np.asarray(sent_sum - 20 * x)).max()
        assert drift <= np.abs(np.asarray(x)).max() + 1e-5  # bounded by one quantum


class TestGradChunnels:
    def test_transports_numerically_equivalent(self, mesh):
        t = tree_of(jax.random.PRNGKey(4))
        ctx = {"mesh": mesh}
        ref = None
        for name in ("psum", "ring", "hierarchical"):
            ch = chunnels.make_transport(
                name, **({"fast_axis": "data", "slow_axis": "pod"}
                         if name == "hierarchical" else {"axis": "pod"}))
            st = ch.init_state(jax.eval_shape(lambda: t))
            out, _ = run_manual(mesh, set(ch.manual_axes) or {"pod"},
                                lambda x: ch.apply(x, st, ctx), t)
            if name == "hierarchical":
                # hierarchical normalizes by pod*data; compare against double pmean
                ref2 = run_manual(mesh, {"pod", "data"},
                                  lambda x: C.pmean_tree(C.pmean_tree(x, "pod"), "data"), t)
                jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5),
                             ref2, out)
                continue
            if ref is None:
                ref = out
            else:
                jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5),
                             ref, out)

    def test_localsgd_syncs_on_schedule(self, mesh):
        ch = chunnels.GradLocalSGD(axis="pod", sync_every=2)
        t = tree_of(jax.random.PRNGKey(5))
        ctx = {"mesh": mesh}
        st = ch.init_state(None)
        out1, st = run_manual(mesh, {"pod"}, lambda x: ch.apply(x, st, ctx), t)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b), t, out1)  # no sync
        out2, st = run_manual(mesh, {"pod"}, lambda x: ch.apply(x, st, ctx), t)
        ref = run_manual(mesh, {"pod"}, lambda x: C.pmean_tree(x, "pod"), t)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5), ref, out2)


class TestFlashDecode:
    @pytest.mark.parametrize("kv_heads,S,B,H", [(2, 64, 2, 4), (1, 32, 3, 5)])
    def test_seq_sharded_matches_local(self, mesh, kv_heads, S, B, H):
        from repro.models.attention import decode_attention_local

        hd = 16
        rng = jax.random.PRNGKey(0)
        ks = jax.random.split(rng, 4)
        q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
        kc = jax.random.normal(ks[1], (B, S, kv_heads, hd), jnp.float32)
        vc = jax.random.normal(ks[2], (B, S, kv_heads, hd), jnp.float32)
        kv_len = S - 3

        ref = decode_attention_local(q, kc, vc, kv_len)
        # shard sequence over the 4-way 'data' axis of the test mesh
        attn_fn = kvshard.make_seq_sharded_decode(mesh, axis="data")
        out = jax.jit(lambda *a: attn_fn(*a))(q, kc, vc, kv_len, None)
        np.testing.assert_allclose(np.asarray(ref, np.float32),
                                   np.asarray(out, np.float32), atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# Batched datapath contract + fused compressed wire path (docs §8)
# ---------------------------------------------------------------------------


def _fabric_pair(fabric=None, chunnel=None, chunnel_rx=None):
    """A connected (tx, rx) datapath pair over a fresh loopback fabric,
    optionally wrapped by a chunnel on each side."""
    from repro.core.fabric import Fabric
    from repro.core.runtime import FabricTransport

    fab = fabric or Fabric()
    a = fab.register("pair-a")
    b = fab.register("pair-b")
    tx = FabricTransport(a, "pair-b").connect_wrap(None)
    rx = FabricTransport(b, "pair-a").connect_wrap(None)
    if chunnel is not None:
        tx = chunnel.connect_wrap(tx)
        rx = (chunnel_rx or chunnel).connect_wrap(rx)
    return tx, rx


def _drain(rx, n_expected, timeout=2.0):
    import time as _t

    buf = [None] * max(n_expected, 1)
    got = []
    deadline = _t.monotonic() + timeout
    while len(got) < n_expected and _t.monotonic() < deadline:
        n = rx.recv(buf, timeout=0.1)
        got.extend(buf[:n])
    return got


class TestBatchedDatapathContract:
    """Every shipped host chunnel's send(msgs)/recv(buf) preserves order,
    count, and content for batch sizes 0/1/odd/large."""

    BATCHES = [0, 1, 3, 7, 64, 257]

    @pytest.mark.parametrize("n", BATCHES)
    def test_fabric_transport(self, n):
        tx, rx = _fabric_pair()
        msgs = [f"m{i}".encode() for i in range(n)]
        tx.send(msgs)
        got = _drain(rx, n)
        assert got == msgs

    @pytest.mark.parametrize("n", BATCHES)
    def test_fn_chunnel_per_message_adapter(self, n):
        from repro.core.chunnel import FnChunnel

        ch = FnChunnel(fn_name="Rev",
                       on_send=lambda m: m[::-1], on_recv=lambda m: m[::-1])
        tx, rx = _fabric_pair(chunnel=ch)
        msgs = [f"msg-{i}".encode() for i in range(n)]
        tx.send(msgs)
        got = _drain(rx, n)
        assert got == msgs

    @pytest.mark.parametrize("n", BATCHES)
    def test_fn_chunnel_batch_transform(self, n):
        from repro.core.chunnel import FnChunnel

        seen_batches = []

        def send_batch(msgs):
            seen_batches.append(len(msgs))
            return [m + b"!" for m in msgs]

        ch = FnChunnel(fn_name="Batch", on_send_batch=send_batch,
                       on_recv_batch=lambda msgs: [m[:-1] for m in msgs])
        tx, rx = _fabric_pair(chunnel=ch)
        msgs = [f"b{i}".encode() for i in range(n)]
        tx.send(msgs)
        got = _drain(rx, n)
        assert got == msgs
        # the whole batch went through ONE transform call
        assert seen_batches == [n]

    @pytest.mark.parametrize("n", [0, 1, 3, 64])
    def test_compress_wire_chunnel(self, n):
        from repro.comm.wire import CompressChunnel

        ch = CompressChunnel(block=64, use_kernel=True, chunk_bytes=256)
        tx, rx = _fabric_pair(chunnel=ch)
        rng = np.random.default_rng(n)
        msgs = [rng.standard_normal(17 + i).astype(np.float32) for i in range(n)]
        tx.send(msgs)
        got = _drain(rx, n)
        assert len(got) == n
        for a, b in zip(msgs, got):
            assert a.shape == b.shape
            amax = np.abs(a).max(initial=0.0)
            np.testing.assert_allclose(a, b, atol=amax / 100.0 + 1e-6)

    @pytest.mark.parametrize("n", [1, 7, 64])
    def test_routed_batch_is_one_inner_send(self, n):
        from repro.core.chunnel import Datapath
        from repro.serving.router import ClientShardChunnel

        calls = []

        class Sink(Datapath):
            def send(self, msgs):
                calls.append(list(msgs))

            def recv(self, buf, timeout=None):
                return 0

        ch = ClientShardChunnel(backends=("s0", "s1", "s2"))
        dp = ch.connect_wrap(Sink())
        dp.send([{"key": f"k{i}"} for i in range(n)])
        assert len(calls) == 1 and len(calls[0]) == n
        assert all("_route_to" in m for m in calls[0])


class TestFusedWire:
    """The fused Pallas wire path (use_kernel=True) is byte- and
    numerically-equal to the jnp oracle in interpret mode."""

    @pytest.mark.parametrize("block", [64, 256])
    def test_kernel_oracle_byte_equality(self, block):
        from repro.comm import wire

        rng = np.random.default_rng(0)
        msgs = [rng.standard_normal(s).astype(np.float32) * 3.0
                for s in [(33,), (8, 9), (301,)]]
        fk = wire.encode_batch(msgs, block=block, use_kernel=True)
        fo = wire.encode_batch(msgs, block=block, use_kernel=False)
        assert b"".join(f["data"] for f in fk) == b"".join(f["data"] for f in fo)

    def test_kernel_oracle_decode_equality(self):
        from repro.comm import wire

        rng = np.random.default_rng(1)
        msgs = [rng.standard_normal(129).astype(np.float32)]
        frames = wire.encode_batch(msgs, block=64, use_kernel=True)
        payload = b"".join(f["data"] for f in frames)
        hdr = frames[0]["hdr"]
        via_kernel = wire.decode_blob(payload, hdr, use_kernel=True)
        via_oracle = wire.decode_blob(payload, hdr, use_kernel=False)
        for a, b in zip(via_kernel, via_oracle):
            np.testing.assert_array_equal(a, b)

    def test_one_device_call_per_batch(self):
        from repro.comm import wire

        rng = np.random.default_rng(2)
        msgs = [rng.standard_normal(64).astype(np.float32) for _ in range(32)]
        frames = wire.encode_batch(msgs, block=64, use_kernel=False)
        # one blob for the whole batch (chunked only by size), one header
        ids = {f["_wire"][0] for f in frames}
        assert len(ids) == 1
        assert sum(f["hdr"] is not None for f in frames) == 1

    def test_chunked_reassembly_over_fabric(self):
        from repro.comm.wire import CompressChunnel

        ch = CompressChunnel(block=64, chunk_bytes=128)  # force many chunks
        tx, rx = _fabric_pair(chunnel=ch)
        rng = np.random.default_rng(3)
        msgs = [rng.standard_normal(500).astype(np.float32)]
        tx.send(msgs)
        got = _drain(rx, 1)
        assert len(got) == 1 and got[0].shape == (500,)

    @staticmethod
    def _batch(case):
        """Batches as the wire path meets them, at block 256; ``big`` floats
        is the smallest message that goes up as a view of its own."""
        from repro.comm import wire

        big = wire.VIEW_MIN_BYTES // 4
        rng = np.random.default_rng(7)

        def f32(*shape):
            return rng.standard_normal(shape).astype(np.float32) * 3.0

        return {
            "one_large": lambda: [f32(big)],
            # the 7-message DDP bucket's shape: biases between weights
            "large_small_large": lambda: [f32(big + 256), f32(512), f32(big), f32(512),
                                          f32(big), f32(3584), f32(big)],
            "two_small_adjacent": lambda: [f32(big), f32(256), f32(512), f32(big)],
            "misaligned": lambda: [f32(33), f32(8, 9), f32(301)],
            "large_after_misaligned": lambda: [f32(33), f32(big), f32(256)],
            "non_contiguous": lambda: [f32(big // 128, 256)[:, ::2]],
            "float64": lambda: [rng.standard_normal(big) * 3.0],
            "empty": lambda: [],
        }[case]()

    CASES = {"one_large": "views", "large_small_large": "views",
             "two_small_adjacent": "concat", "misaligned": "concat",
             "large_after_misaligned": "concat", "non_contiguous": "concat",
             "float64": "concat", "empty": "views"}

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_pieces_encode_to_the_host_concat_bytes(self, case):
        """Whatever pieces a batch goes up in, its wire bytes are those of
        one host concat, zero pad and encode of the whole batch."""
        from repro.comm import wire

        msgs = self._batch(case)
        flat = np.concatenate([np.asarray(m, np.float32).reshape(-1) for m in msgs]
                              + [np.zeros(0, np.float32)])
        flat = np.pad(flat, (0, (-flat.size) % 256))
        want = (np.asarray(wire._fused_encode(jnp.asarray(flat.reshape(-1, 256)),
                                              block=256, use_kernel=False)).tobytes()
                if flat.size else b"")
        frames = wire.encode_batch(msgs, block=256, use_kernel=False)
        assert b"".join(f["data"] for f in frames) == want
        hdr = frames[0]["hdr"]
        assert hdr["n_blocks"] == flat.size // 256
        assert hdr["shapes"] == [np.shape(m) for m in msgs]

    def test_pieces_encode_to_the_kernel_bytes_too(self):
        """The Pallas path stacks the same pieces: equal to the oracle's bytes
        on a batch of four pieces, the last padded."""
        from repro.comm import wire

        big = wire.VIEW_MIN_BYTES // 4
        rng = np.random.default_rng(8)
        msgs = [rng.standard_normal(n).astype(np.float32) for n in (big, 512, big, 7)]
        assert len(wire.plan_pieces([m.size for m in msgs], block=256)) == 4
        fk = wire.encode_batch(msgs, block=256, use_kernel=True)
        fo = wire.encode_batch(msgs, block=256, use_kernel=False)
        assert b"".join(f["data"] for f in fk) == b"".join(f["data"] for f in fo)

    @pytest.mark.parametrize("sizes, min_floats, want", [
        ([1024, 256, 2048], 1024, [[0], [1], [2]]),  # a lone whole-block run
        ([1024, 256, 256, 2048], 1024, [[0], [1, 2], [3]]),
        ([33, 1024, 256], 1024, [[0, 1, 2]]),  # the large one starts off a boundary
        ([1024, 1000], 1024, [[0], [1]]),  # the tail keeps its pad
        ([300, 724, 1024], 1024, [[0, 1], [2]]),  # a run ends on a boundary
        ([1024, 0, 1024], 1024, [[0], [1], [2]]),
        ([], 1024, []),
    ])
    def test_plan_pieces(self, sizes, min_floats, want):
        from repro.comm import wire

        got = wire.plan_pieces(sizes, block=256, min_floats=min_floats)
        assert [list(r) for r in got] == want

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_batch_opens_views_or_concat_inside_pack(self, case):
        """A batch that copies no float on the host opens `wire.encode.views`
        inside `wire.encode.pack`; one that joins, pads or converts a message
        opens `wire.encode.concat`."""
        from repro.comm import wire
        from repro.obs.trace import TRACER

        TRACER.reset()
        TRACER.enable()
        try:
            wire.encode_batch(self._batch(case), block=256, use_kernel=False)
            spans = [r for r in TRACER.collect() if r["kind"] == "span"]
        finally:
            TRACER.disable()
            TRACER.reset()
        names = [r["name"] for r in spans]
        assert names[:2] == ["wire.encode.pack", f"wire.encode.{self.CASES[case]}"]
        pack, inner = spans[0], spans[1]
        assert pack["ts"] <= inner["ts"] and inner["ts"] + inner["dur"] <= pack["ts"] + pack["dur"]
