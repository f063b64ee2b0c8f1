"""Pallas kernel validation (interpret mode): shape/dtype sweeps vs the pure
jnp oracles + hypothesis property tests on the invariants.

hypothesis is an optional dev dependency (requirements-dev.txt): without it
the property-test methods are skipped while the parametrized oracle sweeps
still run."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:

    def _skip_without_hypothesis(*_args, **_kwargs):
        def deco(fn):
            def stub(*args, **kwargs):
                pytest.skip("hypothesis not installed (see requirements-dev.txt)")

            stub.__name__ = fn.__name__
            stub.__doc__ = fn.__doc__
            return stub

        return deco

    given = settings = _skip_without_hypothesis

    class st:  # noqa: N801 - stands in for hypothesis.strategies
        integers = staticmethod(lambda *a, **k: None)
        floats = staticmethod(lambda *a, **k: None)

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.comm import compress
from repro.kernels.quantize import leaf
from repro.kernels.quantize import ops as q_ops
from repro.kernels.quantize.ref import dequantize_blocks_ref, quantize_blocks_ref
from repro.kernels.quantize.quantize import dequantize_blocks, quantize_blocks
from repro.kernels.ssm_scan import ops as ssm_ops
from repro.kernels.ssm_scan.ref import ssm_scan_chunk_ref


class TestInterpretMode:
    @pytest.mark.parametrize("platform,expect", [("cpu", True), ("tpu", False)])
    def test_decided_by_platform(self, monkeypatch, platform, expect):
        from repro.kernels import interpret_mode

        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        assert interpret_mode() is expect
        assert interpret_mode(not expect) is (not expect)  # explicit wins

    def test_other_platforms_raise(self, monkeypatch):
        from repro.kernels import interpret_mode

        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="gpu"):
            interpret_mode()


class TestQuantizeKernel:
    @pytest.mark.parametrize("n_blocks", [1, 7, 128, 300])
    @pytest.mark.parametrize("block", [64, 256])
    def test_matches_ref_sweep(self, n_blocks, block):
        x = jax.random.normal(jax.random.PRNGKey(n_blocks), (n_blocks, block)) * 5.0
        q_k, s_k = quantize_blocks(x, block=block, interpret=True)
        q_r, s_r = quantize_blocks_ref(x, block=block)
        np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-6)
        y_k = dequantize_blocks(q_k, s_k, block=block, interpret=True)
        y_r = dequantize_blocks_ref(q_r, s_r, block=block)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-6)

    @pytest.mark.parametrize("shape", [(1000,), (3, 5, 7), (256, 256)])
    def test_ops_roundtrip_shapes(self, shape):
        x = jax.random.normal(jax.random.PRNGKey(0), shape) * 2.0
        q, s = q_ops.quantize_int8(x, block=128)
        y = q_ops.dequantize_int8(q, s, shape, block=128)
        assert y.shape == shape
        err = np.abs(np.asarray(x) - np.asarray(y))
        assert err.max() <= np.abs(np.asarray(x)).max() / 127.0 + 1e-6

    @settings(max_examples=25, deadline=None)
    @given(
        n=st.integers(1, 64),
        scale=st.floats(1e-3, 1e3),
        seed=st.integers(0, 2**16),
    )
    def test_property_error_bound(self, n, scale, seed):
        """|x - dq(q(x))| <= block_amax/127 elementwise, any scale."""
        x = jax.random.normal(jax.random.PRNGKey(seed), (n, 64)) * scale
        q, s = quantize_blocks(x, block=64, interpret=True)
        y = dequantize_blocks(q, s, block=64, interpret=True)
        amax = np.abs(np.asarray(x)).max(axis=1, keepdims=True)
        assert (np.abs(np.asarray(x - y)) <= amax / 127.0 + 1e-6).all()

    def test_zero_block_is_exact(self):
        x = jnp.zeros((4, 64))
        q, s = quantize_blocks(x, block=64, interpret=True)
        y = dequantize_blocks(q, s, block=64, interpret=True)
        np.testing.assert_array_equal(np.asarray(y), 0.0)


class TestLeafQuantizeKernel:
    """The gradient transport's kernels (``kernels/quantize/leaf``) against
    the jnp arithmetic they replace (``compress.to_blocks`` and
    ``quantize_blocks_int8``, jitted as the step runs it): codes, scales and
    residual bit for bit, and the n-copy sum."""

    # the tiny pods step's head shard, a stacked (L, r, c) leaf, a layer
    # norm stack, 5 and 3 blocks a row (tiles of 1 and 3 blocks), and a row
    # count that leaves a part tile
    SHAPES = [(40, 256), (2, 24, 512), (2, 1024), (4, 1280), (300, 768)]

    @staticmethod
    @functools.partial(jax.jit, static_argnames="feedback")
    def _jnp_quantize(g, r, feedback=True):
        x = g.astype(jnp.float32) + r if feedback else g
        xb = compress.to_blocks(x, 256)
        q, s = compress.quantize_blocks_int8(xb)
        res = compress.from_blocks(xb - q.astype(jnp.float32) * s[..., None], x.shape)
        rows = int(np.prod(x.shape[:-1]))
        return q.reshape(rows, -1), s.reshape(rows, -1), res

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("shape", "mean"))
    def _jnp_sum(q_all, s_all, shape, mean):
        n = q_all.shape[0]
        qb = q_all.reshape((n,) + shape[:-1] + (-1, 256))
        sb = s_all.reshape((n,) + shape[:-1] + (-1,))
        total = qb[0].astype(jnp.float32) * sb[0][..., None]
        for i in range(1, n):
            total = total + qb[i].astype(jnp.float32) * sb[i][..., None]
        if mean:
            total = total / n
        return compress.from_blocks(total, shape)

    @staticmethod
    def _leaf(key, shape):
        g = jax.random.normal(key, shape) * 3.0
        return g.at[..., :256].set(0.0)  # a whole zero block: scale 1, codes 0

    @pytest.mark.parametrize("feedback", [True, False])
    @pytest.mark.parametrize("shape", SHAPES)
    def test_quantize_is_the_jnp_path_bit_for_bit(self, shape, feedback):
        k1, k2 = jax.random.split(jax.random.PRNGKey(len(shape) + shape[-1]))
        g = self._leaf(k1, shape)
        r = 0.01 * jax.random.normal(k2, shape)
        got = leaf.quantize_leaf(g, r if feedback else None)
        want = self._jnp_quantize(g, r, feedback=feedback)
        assert got[0].dtype == jnp.int8 and got[1].dtype == got[2].dtype == jnp.float32
        assert got[2].shape == shape
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("mean", [True, False])
    @pytest.mark.parametrize("n", [2, 4])
    @pytest.mark.parametrize("shape", SHAPES)
    def test_dequantize_sum_is_the_jnp_path(self, shape, n, mean):
        keys = jax.random.split(jax.random.PRNGKey(n), n)
        copies = [leaf.quantize_leaf(self._leaf(k, shape) * (i + 1))
                  for i, k in enumerate(keys)]
        q_all = jnp.stack([c[0] for c in copies])
        s_all = jnp.stack([c[1] for c in copies])
        got = leaf.dequantize_sum(q_all, s_all, shape, mean=mean)
        assert got.shape == shape and got.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(self._jnp_sum(q_all, s_all, shape, mean)))

    @pytest.mark.parametrize("shape,takes", [
        ((5120,), False),      # a final norm: rank 1
        ((4, 80), False),      # a trailing width of part blocks
        ((2, 80, 256), True),  # a stacked leaf of whole blocks
        ((4, 5120), True),     # a layer norm stack
        ((0, 256), False),     # nothing to send
    ])
    def test_shape_alone_picks_the_kernel(self, shape, takes):
        assert leaf.takes_kernel(shape) is takes
        assert not leaf.takes_kernel(shape, block=64)  # a block narrower than the lanes

    @pytest.mark.parametrize("nblocks,per", [(1, 1), (4, 4), (10, 2), (56, 4), (5, 1), (3, 3)])
    def test_tile_width_divides_the_row(self, nblocks, per):
        assert leaf.blocks_per_tile(nblocks) == per


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("B,S,H,KH,hd", [
        (1, 128, 4, 4, 64),   # MHA
        (2, 256, 8, 2, 32),   # GQA 4:1
        (1, 384, 6, 1, 64),   # MQA
        (2, 96, 4, 2, 16),    # ragged block boundary (S % block != 0)
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref_sweep(self, B, S, H, KH, hd, dtype):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
        k = jax.random.normal(ks[1], (B, S, KH, hd), dtype)
        v = jax.random.normal(ks[2], (B, S, KH, hd), dtype)
        out = fa_ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        ref = flash_attention_ref(q, k, v, causal=True)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=tol, rtol=tol)

    @pytest.mark.parametrize("window", [16, 64])
    def test_sliding_window(self, window):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 128, 2, 32))
        k = jax.random.normal(ks[1], (1, 128, 2, 32))
        v = jax.random.normal(ks[2], (1, 128, 2, 32))
        out = fa_ops.flash_attention(q, k, v, causal=True, window=window,
                                     block_q=32, block_k=32)
        ref = flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=3e-3, rtol=3e-3)

    def test_non_causal(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (2, 64, 2, 16))
        k = jax.random.normal(ks[1], (2, 64, 2, 16))
        v = jax.random.normal(ks[2], (2, 64, 2, 16))
        out = fa_ops.flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
        ref = flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=3e-3, rtol=3e-3)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**16), s_pow=st.integers(5, 8))
    def test_property_softmax_convexity(self, seed, s_pow):
        """Attention output rows lie inside the convex hull of V rows: the
        per-dim output is bounded by V's min/max over valid positions."""
        S = 2**s_pow
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (1, S, 2, 16))
        k = jax.random.normal(ks[1], (1, S, 2, 16))
        v = jax.random.normal(ks[2], (1, S, 2, 16))
        out = np.asarray(fa_ops.flash_attention(q, k, v, causal=False,
                                                block_q=32, block_k=32), np.float32)
        vmin = np.asarray(v, np.float32).min(axis=1, keepdims=True)
        vmax = np.asarray(v, np.float32).max(axis=1, keepdims=True)
        assert (out >= vmin - 1e-3).all() and (out <= vmax + 1e-3).all()


class TestSsmScanKernel:
    @pytest.mark.parametrize("B,C,d,N", [
        (1, 16, 32, 4), (2, 64, 256, 16), (3, 8, 300, 16),  # incl. d % tile != 0
    ])
    def test_matches_ref_sweep(self, B, C, d, N):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, C, d, N)))  # decay in (0,1)
        bx = jax.random.normal(ks[1], (B, C, d, N)) * 0.1
        h0 = jax.random.normal(ks[2], (B, d, N)) * 0.1
        h_seq, h_last = ssm_ops.ssm_scan_chunk(a, bx, h0)
        r_seq, r_last = ssm_scan_chunk_ref(a, bx, h0)
        np.testing.assert_allclose(np.asarray(h_seq), np.asarray(r_seq),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(h_last), np.asarray(r_last),
                                   atol=1e-5, rtol=1e-5)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**16), C=st.integers(2, 32))
    def test_property_composition(self, seed, C):
        """Scanning a chunk equals scanning its two halves sequentially."""
        B, d, N = 1, 16, 4
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        C = 2 * C
        a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, C, d, N)))
        bx = jax.random.normal(ks[1], (B, C, d, N)) * 0.1
        h0 = jnp.zeros((B, d, N))
        _, h_full = ssm_ops.ssm_scan_chunk(a, bx, h0)
        _, h_half = ssm_ops.ssm_scan_chunk(a[:, : C // 2], bx[:, : C // 2], h0)
        _, h_two = ssm_ops.ssm_scan_chunk(a[:, C // 2 :], bx[:, C // 2 :], h_half)
        np.testing.assert_allclose(np.asarray(h_full), np.asarray(h_two),
                                   atol=1e-5, rtol=1e-5)

    def test_identity_decay_accumulates(self):
        """a=1 => h_last = h0 + sum_t bx_t."""
        B, C, d, N = 1, 8, 8, 4
        a = jnp.ones((B, C, d, N))
        bx = jax.random.normal(jax.random.PRNGKey(3), (B, C, d, N))
        h0 = jax.random.normal(jax.random.PRNGKey(4), (B, d, N))
        _, h_last = ssm_ops.ssm_scan_chunk(a, bx, h0)
        np.testing.assert_allclose(np.asarray(h_last),
                                   np.asarray(h0 + bx.sum(axis=1)), atol=1e-5)


class TestFusedWirePath:
    """comm.wire fuses quantize -> pack-to-bytes -> chunk into one jitted
    device call; in interpret mode the Pallas kernel path must be
    byte-identical to the jnp oracle (tier-1 acceptance for ISSUE 7)."""

    @pytest.mark.parametrize("block", [64, 128, 256])
    @pytest.mark.parametrize("n_blocks", [1, 3, 8])
    def test_encode_kernel_equals_oracle(self, block, n_blocks):
        from repro.comm import wire

        x = jax.random.normal(jax.random.PRNGKey(block + n_blocks),
                              (n_blocks, block)).astype(jnp.float32) * 5.0
        pk = np.asarray(wire._fused_encode(x, block=block, use_kernel=True))
        po = np.asarray(wire._fused_encode(x, block=block, use_kernel=False))
        np.testing.assert_array_equal(pk, po)

    @pytest.mark.parametrize("block", [64, 256])
    def test_decode_kernel_equals_oracle(self, block):
        from repro.comm import wire

        n_blocks = 4
        x = jax.random.normal(jax.random.PRNGKey(9),
                              (n_blocks, block)).astype(jnp.float32)
        packed = wire._fused_encode(x, block=block, use_kernel=False)
        dk = np.asarray(wire._fused_decode(packed, n_blocks=n_blocks,
                                           block=block, use_kernel=True))
        do = np.asarray(wire._fused_decode(packed, n_blocks=n_blocks,
                                           block=block, use_kernel=False))
        np.testing.assert_array_equal(dk, do)

    def test_roundtrip_error_bound(self):
        """Wire roundtrip matches the standalone block-quantization error:
        per-block max abs error <= scale/2 = amax/254."""
        from repro.comm import wire

        block = 128
        x = jax.random.normal(jax.random.PRNGKey(5), (4, block)) * 3.0
        x = x.astype(jnp.float32)
        packed = wire._fused_encode(x, block=block, use_kernel=True)
        y = np.asarray(wire._fused_decode(packed, n_blocks=4, block=block,
                                          use_kernel=True))
        xb = np.asarray(x).reshape(4, block)
        amax = np.abs(xb).max(axis=1)
        err = np.abs(xb - y.reshape(4, block)).max(axis=1)
        assert np.all(err <= amax / 254.0 + 1e-7)

    def test_packed_layout(self):
        """Packed blob = int8 codes then float32 scales as raw bytes."""
        from repro.comm import wire

        block, n_blocks = 64, 2
        x = jnp.ones((n_blocks, block), jnp.float32)
        packed = np.asarray(wire._fused_encode(x, block=block, use_kernel=True))
        assert packed.dtype == np.uint8
        assert packed.shape == (n_blocks * block + 4 * n_blocks,)
        codes = packed[: n_blocks * block].view(np.int8)
        scales = packed[n_blocks * block :].view(np.float32)
        np.testing.assert_array_equal(codes, np.full(n_blocks * block, 127, np.int8))
        np.testing.assert_allclose(scales, np.full(n_blocks, 1.0 / 127.0), rtol=1e-6)
