"""Comm-chunnel tests: collective transports agree with psum; flash-decode
combine agrees with the local oracle; compression round-trips."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

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


def _half_steps(x, block):
    """Half a quantization step of each value's block, blocks of ``block``
    along the trailing axis (numpy)."""
    x = np.asarray(x, np.float32).reshape(np.shape(x) or (1,))
    w = x.shape[-1]
    xb = np.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, (-w) % block)])
    xb = xb.reshape(x.shape[:-1] + (-1, block))
    amax = np.abs(xb).max(axis=-1, keepdims=True)
    scale = np.where(amax > 0, amax / 127.0, 1.0)
    half = np.broadcast_to(scale / 2, xb.shape).reshape(x.shape[:-1] + (-1,))[..., :w]
    dq = (np.clip(np.round(xb / scale), -127, 127) * scale).reshape(x.shape[:-1] + (-1,))[..., :w]
    return half, dq


def _pod_scaled(x):
    """Each pod contributes a different tree: pod p sends (p + 1) x."""
    p = jax.lax.axis_index("pod").astype(jnp.float32)
    return jax.tree.map(lambda g: g * (1.0 + p), x)


def _replica_group_sizes(hlo: str, op: str):
    """Group size of each ``op`` in compiled HLO text."""
    sizes = []
    for line in hlo.splitlines():
        if f" {op}(" not in line or "replica_groups=" not in line:
            continue
        groups = line.split("replica_groups=", 1)[1]
        if groups.startswith("{{"):
            sizes.append(len(groups[2:groups.index("}")].split(",")))
        else:  # iota form [groups,size]<=[...]
            sizes.append(int(groups[1:groups.index("]")].split(",")[1]))
    return sizes


class TestPerLeafCompressed:
    """compressed_tree works one leaf at a time: blocks along the trailing
    axis, each leaf's shape and sharding kept, no joined f32 vector."""

    SIZES = ((17,), (3, 5), (64,), (4, 96), (2, 3, 40))

    def test_agrees_with_psum_within_half_a_step_per_block(self, mesh):
        t = tree_of(jax.random.PRNGKey(6), self.SIZES)
        ref = run_manual(mesh, {"pod"}, lambda x: C.psum_tree(_pod_scaled(x), "pod"), t)
        out = run_manual(mesh, {"pod"},
                         lambda x: C.compressed_tree(_pod_scaled(x), "pod", block=32), t)
        for k in t:
            assert out[k].shape == t[k].shape and out[k].dtype == t[k].dtype
            bound = sum(_half_steps(np.asarray(t[k]) * (1 + p), 32)[0] for p in range(2))
            err = np.abs(np.asarray(out[k]) - np.asarray(ref[k]))
            assert (err <= bound * (1 + 1e-5) + 1e-7).all(), k

    def test_keeps_each_leaf_shape_and_data_sharding(self, mesh):
        from jax.sharding import NamedSharding

        specs = {"a": P("data", None), "b": P(None, "data"), "c": P()}
        shapes = {"a": (8, 512), "b": (3, 1024), "c": (17,)}
        sh = {k: NamedSharding(mesh, s) for k, s in specs.items()}
        t = {k: jax.device_put(jax.random.normal(jax.random.PRNGKey(i), shapes[k]), sh[k])
             for i, k in enumerate(sorted(shapes))}
        f = compat.shard_map(
            lambda x: C.compressed_tree_ef(x, "pod", specs=specs), mesh=mesh,
            in_specs=P(), out_specs=P(), check_vma=False, axis_names={"pod"})
        jf = jax.jit(f, in_shardings=(sh,))
        out, resid = jf(t)
        for k in t:
            for got in (out[k], resid[k]):
                assert got.shape == shapes[k]
                assert got.sharding.is_equivalent_to(sh[k], len(shapes[k])), k
        # each chip sends its own shard: every all-gather spans the 2 pods only
        hlo = jf.lower(t).compile().as_text()
        sizes = _replica_group_sizes(hlo, "all-gather")
        assert sizes and set(sizes) == {2}

    def test_no_joined_tree_and_no_n_fold_f32_dequantize(self, mesh):
        import re

        t = tree_of(jax.random.PRNGKey(7), self.SIZES)
        f = compat.shard_map(lambda x: C.compressed_tree(x, "pod", block=32), mesh=mesh,
                             in_specs=P(), out_specs=P(), check_vma=False,
                             axis_names={"pod"})
        txt = jax.jit(f).lower(t).as_text()
        assert "concatenate" not in txt
        largest = max(-(-s[-1] // 32) * 32 * int(np.prod(s[:-1])) for s in self.SIZES)
        f32 = [int(np.prod([int(d) for d in m.split("x")]))
               for m in re.findall(r"tensor<([0-9x]+)xf32>", txt)]
        assert f32 and max(f32) <= largest

    def test_error_feedback_residual_is_from_the_codes_sent(self, mesh):
        t = tree_of(jax.random.PRNGKey(8), self.SIZES)
        ch = chunnels.GradCompressed(axis="pod", block=32)
        ctx = {"mesh": mesh}
        st = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), t)

        def body(x):
            x = _pod_scaled(x)
            out, resid = ch.apply(x, st, ctx)
            sent = C.pmean_tree(jax.tree.map(lambda g, r: g - r, x, resid), "pod")
            return out, resid, sent

        out, resid, sent = run_manual(mesh, {"pod"}, body, t)
        for k in t:
            _, dq = _half_steps(t[k], 32)  # pod 0 sends x itself
            np.testing.assert_allclose(resid[k], np.asarray(t[k]) - dq, atol=1e-6)
            # what the transport summed is exactly what the residuals left out
            np.testing.assert_allclose(out[k], sent[k], rtol=1e-6, atol=1e-6)


STEP_LOWERING = """
import hashlib, json
import jax
import numpy as np
from jax.sharding import Mesh
from repro import compat
from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig
from repro.kernels.quantize import leaf
from repro.train import step as step_mod
from repro.train.trainer import HostSpec, ReconfigurableTrainer

leaf.interpret_mode = lambda interpret=None: False  # Mosaic kernels, lowered for TPU
mesh = compat.set_mesh(Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("pod", "data")))
cfg = get_smoke_config("mistral-nemo-12b").replace(
    d_model=512, num_heads=4, num_kv_heads=4, head_dim=128, d_ff=1024, vocab_size=512)
tr = ReconfigurableTrainer(cfg, ShapeConfig("t", 64, 4, "train"), mesh,
                           transport="compressed_int8", hosts=[HostSpec(0, ["compressed_int8"])])
state = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                     step_mod.state_shapes(tr.model, tr.chunnels), tr.state_sh)
text = tr.jitted.trace(state, tr.model.batch_specs(tr.shape)).lower(
    lowering_platforms=("tpu",)).as_text()
print(json.dumps({"sha1": hashlib.sha1(text.encode()).hexdigest(),
                  "kernels": text.count("tpu_custom_call")}))
"""


def test_the_lowered_pods_step_is_the_same_in_every_process():
    """The persistent compilation cache keys a program on its lowered text:
    two fresh processes, with different string hashes, lower the tiny pods
    step over compressed_int8 for TPU, with its Mosaic leaf kernels, to the
    same text (on four CPU devices: lowering needs no TPU library)."""
    src = str(Path(__file__).resolve().parents[1] / "src")
    runs = []
    for hash_seed in ("1", "2"):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src, PYTHONHASHSEED=hash_seed,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        p = subprocess.run([sys.executable, "-c", STEP_LOWERING], capture_output=True,
                           text=True, timeout=300, env=env)
        assert p.returncode == 0, p.stderr[-3000:]
        runs.append(json.loads(p.stdout.splitlines()[-1]))
    assert runs[0]["kernels"] > 0
    assert runs[0] == runs[1]


class TestLeafKernelPath:
    """A leaf whose shape the Pallas kernels take is quantized and summed by
    them (``kernels/quantize/leaf``), with the same sums and residuals as
    the jnp blocks; a 1-D leaf or a trailing width of part blocks keeps the
    jnp path. Shape alone decides."""

    # the mistral smoke config's leaves on the pods mesh (d 80, 4 x 16
    # query and 2 x 16 KV heads, d_ff 128, vocabulary 256: only the head's
    # trailing width is whole blocks), a stacked leaf of whole blocks, a
    # layer norm stack and a width of part blocks
    SHAPES = {"embed": (256, 80), "wq": (2, 80, 64), "wk": (2, 80, 32), "wo": (2, 64, 80),
              "down": (2, 128, 80), "ln1": (2, 80), "final_norm": (80,),
              "lm_head": (80, 256), "stacked": (2, 16, 512), "norms": (2, 512),
              "wide": (8, 768), "part": (4, 300)}

    @staticmethod
    def _transport(mesh, t, r):
        f = compat.shard_map(
            lambda x, e: C.compressed_tree_ef(_pod_scaled(x), "pod", mean=True, residual=e),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False, axis_names={"pod"})
        return jax.jit(f)(t, r)

    @pytest.mark.parametrize("pods", [2, 4])
    def test_kernel_and_jnp_paths_agree(self, pods, monkeypatch):
        mesh = compat.make_mesh((pods, 8 // pods), ("pod", "data"),
                                axis_types=(compat.AUTO,) * 2)
        t = tree_of(jax.random.PRNGKey(11), tuple(self.SHAPES.values()))
        r = jax.tree.map(lambda g: 0.01 * jnp.ones_like(g), t)
        out, resid = self._transport(mesh, t, r)
        monkeypatch.setattr(C.leaf, "takes_kernel", lambda shape, block=256: False)
        jax.clear_caches()
        ref_out, ref_resid = self._transport(mesh, t, r)
        for k in t:
            np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref_out[k]), k)
            np.testing.assert_array_equal(np.asarray(resid[k]), np.asarray(ref_resid[k]), k)
            assert out[k].dtype == jnp.float32 and out[k].shape == t[k].shape

    @pytest.mark.parametrize("shape,kernels", [((5120,), 0), ((4, 80), 0),
                                               ((4, 512), 2), ((2, 8, 512), 2)])
    def test_only_leaves_of_whole_blocks_take_the_kernels(self, mesh, shape, kernels):
        f = compat.shard_map(lambda x: C.compressed_leaf_sum(x, "pod")[0], mesh=mesh,
                             in_specs=P(), out_specs=P(), check_vma=False,
                             axis_names={"pod", "data"})
        jaxpr = str(jax.make_jaxpr(f)(jnp.zeros(shape)))
        assert jaxpr.count("pallas_call[") == kernels

    @pytest.mark.parametrize("shape", [(4, 512), (2, 8, 768), (4, 80), (300,)])
    def test_the_wire_carries_int8_wire_bytes(self, mesh, shape):
        """What crosses ``pod`` (the all-gathers' operands) is one int8 a
        value of each padded block and one f32 scale a block, on either path."""
        f = compat.shard_map(lambda x: C.compressed_leaf_sum(x, "pod")[0], mesh=mesh,
                             in_specs=P(), out_specs=P(), check_vma=False,
                             axis_names={"pod", "data"})
        jaxpr = jax.make_jaxpr(f)(jnp.zeros(shape)).jaxpr.eqns[0].params["jaxpr"]
        sent = [v.aval for e in jaxpr.eqns if e.primitive.name == "all_gather"
                for v in e.invars]
        assert sum(a.size * a.dtype.itemsize for a in sent) == compress.int8_wire_bytes(shape)

    def test_leaves_of_one_shape_share_a_lowering(self, mesh):
        shapes = ((2, 8, 512), (2, 8, 512), (4, 512), (4, 512), (512,))
        t = tree_of(jax.random.PRNGKey(12), shapes)
        f = compat.shard_map(lambda x: C.compressed_tree_ef(x, "pod", residual=x),
                             mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
                             axis_names={"pod"})
        txt = jax.jit(f).lower(t).as_text()
        # one function for each distinct shape, called once for each leaf
        for kernel in ("_quantize", "_dequantize_sum"):
            assert len(re.findall(rf"func\.func private @{kernel}(_\d+)?\(", txt)) == 2
            assert len(re.findall(rf"call @{kernel}(_\d+)?\(", txt)) == 4


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
