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
        q_r, s_r = compress.quantize_blocks_int8(x)
        np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-6)
        y_k = dequantize_blocks(q_k, s_k, block=block, interpret=True)
        y_r = compress.dequantize_blocks_int8(q_r, s_r)
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-6)

    @pytest.mark.parametrize("shape", [(1000,), (3, 5, 7), (256, 256)])
    def test_ops_roundtrip_shapes(self, shape):
        x = jax.random.normal(jax.random.PRNGKey(0), shape) * 2.0
        x2d = compress.to_blocks(x.reshape(-1), 128)
        q, s = quantize_blocks(x2d, block=128, interpret=True)
        y = dequantize_blocks(q, s, block=128, interpret=True)
        y = compress.from_blocks(y, (x.size,)).reshape(shape)
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
        res = compress.from_blocks(xb - compress.dequantize_blocks_int8(q, s), x.shape)
        rows = int(np.prod(x.shape[:-1]))
        return q.reshape(rows, -1), s.reshape(rows, -1), res

    @staticmethod
    @functools.partial(jax.jit, static_argnames=("shape", "mean"))
    def _jnp_sum(q_all, s_all, shape, mean):
        n = q_all.shape[0]
        qb = q_all.reshape((n,) + shape[:-1] + (-1, 256))
        sb = s_all.reshape((n,) + shape[:-1] + (-1,))
        total = compress.dequantize_blocks_int8(qb[0], sb[0])
        for i in range(1, n):
            total = total + compress.dequantize_blocks_int8(qb[i], sb[i])
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


def _wire_payload(x, block, use_kernel, packed):
    """The payload bytes of ``comm.wire._fused_encode``'s parts, joined as
    ``encode_batch`` joins them."""
    from repro.comm import wire

    parts = wire._fused_encode(x, block=block, use_kernel=use_kernel, packed=packed)
    assert len(parts) == (1 if packed else 2)
    return b"".join([np.asarray(a) for a in parts])


def _wire_decode(payload, n_blocks, block, use_kernel, packed):
    """``comm.wire._fused_decode`` of a payload, uploaded as ``decode_blob``
    uploads it in either form."""
    from repro.comm import wire

    parts = ((np.frombuffer(payload, np.uint8),) if packed
             else wire.split_payload(payload, n_blocks, block))
    return np.asarray(wire._fused_decode(tuple(map(jnp.asarray, parts)), n_blocks=n_blocks,
                                         block=block, use_kernel=use_kernel))


class TestFusedWirePath:
    """comm.wire quantizes a batch in one jitted device program whose
    outputs are the payload's parts: the int8 codes and the f32 scales as
    two arrays, or, below ``SPLIT_MIN_BLOCKS``, one byte vector packed on
    the device. In interpret mode the Pallas kernel path must be
    byte-identical to the jnp oracle, in both forms."""

    @pytest.mark.parametrize("block", [64, 128, 256])
    @pytest.mark.parametrize("n_blocks", [1, 3, 8])
    def test_encode_kernel_equals_oracle(self, block, n_blocks):
        from repro.comm import wire

        x = jax.random.normal(jax.random.PRNGKey(block + n_blocks),
                              (n_blocks, block)).astype(jnp.float32) * 5.0
        for packed in (False, True):
            pk = wire._fused_encode(x, block=block, use_kernel=True, packed=packed)
            po = wire._fused_encode(x, block=block, use_kernel=False, packed=packed)
            for a, b in zip(pk, po, strict=True):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert (_wire_payload(x, block, True, packed=False)
                == _wire_payload(x, block, True, packed=True))

    @pytest.mark.parametrize("block", [64, 256])
    def test_decode_kernel_equals_oracle(self, block):
        n_blocks = 4
        x = jax.random.normal(jax.random.PRNGKey(9),
                              (n_blocks, block)).astype(jnp.float32)
        payload = _wire_payload(x, block, False, packed=False)
        for packed in (False, True):
            dk = _wire_decode(payload, n_blocks, block, True, packed)
            do = _wire_decode(payload, n_blocks, block, False, packed)
            np.testing.assert_array_equal(dk, do)

    def test_roundtrip_error_bound(self):
        """Wire roundtrip matches the standalone block-quantization error:
        per-block max abs error <= scale/2 = amax/254, in either form."""
        block = 128
        x = jax.random.normal(jax.random.PRNGKey(5), (4, block)) * 3.0
        x = x.astype(jnp.float32)
        xb = np.asarray(x).reshape(4, block)
        amax = np.abs(xb).max(axis=1)
        for packed in (False, True):
            payload = _wire_payload(x, block, True, packed)
            y = _wire_decode(payload, 4, block, True, packed)
            err = np.abs(xb - y.reshape(4, block)).max(axis=1)
            assert np.all(err <= amax / 254.0 + 1e-7)

    def test_packed_layout(self):
        """Payload = int8 codes then float32 scales as raw bytes: the two
        arrays as the kernel leaves them, or one uint8 vector of both."""
        from repro.comm import wire

        block, n_blocks = 64, 2
        x = jnp.ones((n_blocks, block), jnp.float32)
        codes, scales = map(np.asarray, wire._fused_encode(x, block=block, use_kernel=True,
                                                           packed=False))
        assert codes.dtype == np.int8 and codes.shape == (n_blocks * block,)
        assert scales.dtype == np.float32 and scales.shape == (n_blocks,)
        np.testing.assert_array_equal(codes, np.full(n_blocks * block, 127, np.int8))
        np.testing.assert_allclose(scales, np.full(n_blocks, 1.0 / 127.0), rtol=1e-6)
        (packed,) = wire._fused_encode(x, block=block, use_kernel=True, packed=True)
        packed = np.asarray(packed)
        assert packed.dtype == np.uint8
        assert packed.shape == (n_blocks * block + 4 * n_blocks,)
        assert packed.tobytes() == codes.tobytes() + scales.tobytes()

    @staticmethod
    def _batch(case):
        """Batches as the wire path meets them, at block 256."""
        from repro.comm import wire

        big = wire.VIEW_MIN_BYTES // 4
        rng = np.random.default_rng(17)
        sizes = {
            "one_block": [256],
            # a qwen2-7b layer's norm and bias gradients: 46 blocks
            "small_tensors": [3584, 512, 512, 3584, 3584],
            "tail_padded": [33, 72, 301],
            # six pieces stacked on the device, past SPLIT_MIN_BLOCKS
            "stacked_pieces": [big, 512, big, big, big, 7],
        }[case]
        return [rng.standard_normal(n).astype(np.float32) * 3.0 for n in sizes]

    @pytest.mark.parametrize("use_kernel", [True, False])
    @pytest.mark.parametrize("case", ["one_block", "small_tensors", "tail_padded",
                                      "stacked_pieces"])
    def test_payload_is_the_format_golden(self, case, use_kernel):
        """The joined payload of ``encode_batch`` is the codes' bytes then
        the scales' little-endian bytes of ``compress.quantize_blocks_int8``
        over the zero-padded batch, whichever form moved it; ``decode_blob``
        gives every value back within half a step of its block. The format
        is compiled, as the wire compiles it: XLA may turn ``amax / 127`` into
        a product, one ulp off the eager quotient."""
        from repro.comm import wire
        from repro.comm.compress import quantize_blocks_int8

        block = 256
        msgs = self._batch(case)
        flat = np.concatenate(msgs)
        x2d = np.pad(flat, (0, (-flat.size) % block)).reshape(-1, block)
        codes, scales = map(np.asarray, jax.jit(quantize_blocks_int8)(jnp.asarray(x2d)))
        frames = wire.encode_batch(msgs, block=block, use_kernel=use_kernel)
        payload = b"".join(f["data"] for f in frames)
        assert payload == codes.tobytes() + scales.astype("<f4").tobytes()
        hdr = frames[0]["hdr"]
        assert hdr["n_blocks"] == x2d.shape[0]
        if case == "stacked_pieces":
            assert not wire.packed_form(hdr["n_blocks"])
            assert len(wire.plan_pieces([m.size for m in msgs], block=block)) == 6
        got = wire.decode_blob(payload, hdr, use_kernel=use_kernel)
        y = np.pad(np.concatenate(got), (0, x2d.size - flat.size)).reshape(x2d.shape)
        steps = np.abs(y - x2d) / scales[:, None]
        assert steps.max() <= 0.5 + 2.0 ** -14
