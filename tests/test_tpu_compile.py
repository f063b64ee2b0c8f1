"""The Pallas kernels of the main path compile for a TPU v5e at real sizes.

Nothing runs: each case lowers and compiles against a v5e:2x2 topology that is
described, not attached, and checks that the compiled program holds the Mosaic
kernel (``tpu_custom_call``) rather than an interpreted one. Interpret mode
(the rest of the suite) accepts block shapes that Mosaic refuses, such as the
1-D scale blocks the quantize kernels had past 128 blocks.

The topology is described inside a fixture only: loading the TPU library at
import time would make the test workers collect different tests.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro import compat
from repro.comm import wire
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.quantize import leaf
from repro.kernels.quantize.quantize import dequantize_blocks, quantize_blocks

BLOCK = 256
WIRE_BLOCKS = (4 << 20) // 4 // BLOCK  # a 4 MiB batch of floats


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _quantize(n_blocks):
    def lower(sh):
        return jax.jit(lambda x: quantize_blocks(x, block=BLOCK, interpret=False)).lower(
            _spec((n_blocks, BLOCK), jnp.float32, sh))
    return lower


def _dequantize(n_blocks):
    def lower(sh):
        return jax.jit(lambda q, s: dequantize_blocks(q, s, block=BLOCK, interpret=False)).lower(
            _spec((n_blocks, BLOCK), jnp.int8, sh), _spec((n_blocks,), jnp.float32, sh))
    return lower


def _wire_parts(n_blocks, sh):
    """The decode program's operands, in the form the path takes at
    ``n_blocks``."""
    if wire.packed_form(n_blocks):
        return (_spec((n_blocks * BLOCK + 4 * n_blocks,), jnp.uint8, sh),)
    return _spec((n_blocks, BLOCK), jnp.int8, sh), _spec((n_blocks,), jnp.float32, sh)


def _wire_encode(sh, n_blocks=WIRE_BLOCKS):
    return wire._fused_encode.lower(_spec((n_blocks, BLOCK), jnp.float32, sh), block=BLOCK,
                                    use_kernel=True, packed=wire.packed_form(n_blocks),
                                    interpret=False)


def _wire_decode(sh, n_blocks=WIRE_BLOCKS):
    return wire._fused_decode.lower(_wire_parts(n_blocks, sh), n_blocks=n_blocks,
                                    block=BLOCK, use_kernel=True, interpret=False)


def _flash_llama(sh):
    # llama3.2-1b: 32 query heads over 8 kv heads of 64, at 2048 tokens
    q = _spec((1, 2048, 32, 64), jnp.bfloat16, sh)
    kv = _spec((1, 2048, 8, 64), jnp.bfloat16, sh)
    return jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                   interpret=False)).lower(q, kv, kv)


# Mistral-NeMo-12B's gradient leaves as one chip of the pods mesh holds them
# (FSDP over data=2; 4 layers, vocabulary 16384): the leaf kernels' shapes
NEMO_SHARDS = {"gate_up": (4, 2560, 14336), "q": (4, 2560, 4096), "o": (4, 4096, 2560),
               "kv": (4, 2560, 1024), "down": (4, 14336, 2560), "embed": (16384, 2560),
               "head": (2560, 16384)}


def _leaf_quantize(shape):
    def lower(sh):
        return jax.jit(lambda g, r: leaf.quantize_leaf(g, r, interpret=False)).lower(
            _spec(shape, jnp.float32, sh), _spec(shape, jnp.float32, sh))
    return lower


def _leaf_dequantize_sum(shape, pods=2):
    rows = math.prod(shape[:-1])
    def lower(sh):
        return jax.jit(lambda q, s: leaf.dequantize_sum(q, s, shape, mean=True,
                                                        interpret=False)).lower(
            _spec((pods, rows, shape[-1]), jnp.int8, sh),
            _spec((pods, rows, shape[-1] // BLOCK), jnp.float32, sh))
    return lower


CASES = {
    **{f"leaf_quantize-{k}": _leaf_quantize(v) for k, v in NEMO_SHARDS.items()},
    **{f"leaf_dequantize_sum-{k}": _leaf_dequantize_sum(v) for k, v in NEMO_SHARDS.items()},
    **{f"quantize_blocks-{n}": _quantize(n) for n in (7, 128, 129, 4096)},
    **{f"dequantize_blocks-{n}": _dequantize(n) for n in (7, 128, 129, 4096)},
    "wire_encode-4MiB": _wire_encode,
    "wire_decode-4MiB": _wire_decode,
    "flash_attention-llama3.2-1b": _flash_llama,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    compiled = CASES[case](one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


DDP_BLOCKS = 25600  # a 25 MiB DDP bucket of floats in blocks of 256


def test_wire_programs_hold_codes_and_scales_not_the_payload(one_chip, no_persistent_cache):
    """At a DDP bucket's 25,600 blocks the wire's device programs hold the
    codes and the scales as two arrays: the decode program reshapes no 8-bit
    array into more than ``n_blocks`` rows of 4 bytes, neither program holds
    an array of the whole payload's length, and the encode program joins no
    byte vectors."""
    n = DDP_BLOCKS
    payload_len = n * BLOCK + 4 * n
    enc_lowered = _wire_encode(one_chip, n)
    enc = enc_lowered.compile().as_text()
    dec = _wire_decode(one_chip, n).compile().as_text()
    assert "tpu_custom_call" in enc and "tpu_custom_call" in dec
    rows_of_4 = [int(r) for r in re.findall(r"\b[su]8\[(\d+),4\]", dec)]
    assert all(r <= n for r in rows_of_4), rows_of_4
    for text in (enc, dec):
        assert not re.search(rf"\b[su]8\[{payload_len}\]", text)
    assert "concatenate" not in enc_lowered.as_text()


def test_pods_step_lowers_one_kernel_pair_per_leaf_shape(topo, no_persistent_cache, monkeypatch):
    """The tiny pods step over compressed_int8, lowered for a described v5e
    2x2 (pod=2 x data=2), holds one quantize and one dequantize-sum Mosaic
    kernel for each distinct shard shape that takes them, however many
    leaves share it. The mistral smoke config's widths are under a block,
    so its widths here are whole blocks: q, k and v shards share one shape,
    gate and up another, and the two layer norms a third."""
    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.train import step as step_mod
    from repro.train.trainer import HostSpec, ReconfigurableTrainer

    monkeypatch.setattr(leaf, "interpret_mode", lambda interpret=None: False)
    cfg = get_smoke_config("mistral-nemo-12b").replace(
        d_model=512, num_heads=4, num_kv_heads=4, head_dim=128, d_ff=1024, vocab_size=512)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("pod", "data"))
    with compat.use_mesh(mesh):
        tr = ReconfigurableTrainer(cfg, ShapeConfig("t", 64, 4, "train"), mesh,
                                   transport="compressed_int8",
                                   hosts=[HostSpec(0, ["compressed_int8"])])
        state = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                             step_mod.state_shapes(tr.model, tr.chunnels), tr.state_sh)
        lowered = tr.jitted.lower(state, tr.model.batch_specs(tr.shape))
        compiled = lowered.compile()
    # each gradient shard as a chip holds it, placed as its parameter is
    shards = {sh.shard_shape(p.shape) for sh, p in zip(jax.tree.leaves(tr.state_sh.params),
                                                        jax.tree.leaves(tr.model.param_shapes()))}
    shards = {s for s in shards if leaf.takes_kernel(s)}
    views = {(math.prod(s[:-1]), s[-1]) for s in shards}  # the kernels' 2-D operands
    kernels = lowered.as_text().count("tpu_custom_call")
    assert len(shards) >= 5
    assert kernels == 2 * len(views) <= 2 * len(shards)
    assert "tpu_custom_call" in compiled.as_text()
