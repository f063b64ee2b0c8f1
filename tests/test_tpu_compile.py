"""The Pallas kernels of the main path compile for a TPU v5e at real sizes.

Nothing runs: each case lowers and compiles against a v5e:2x2 topology that is
described, not attached, and checks that the compiled program holds the Mosaic
kernel (``tpu_custom_call``) rather than an interpreted one. Interpret mode
(the rest of the suite) accepts block shapes that Mosaic refuses, such as the
1-D scale blocks the quantize kernels had past 128 blocks.

The topology is described inside a fixture only: loading the TPU library at
import time would make the test workers collect different tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.comm import wire
from repro.kernels.flash_attention.flash_attention import flash_attention
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


def _wire_encode(sh):
    return wire._fused_encode.lower(_spec((WIRE_BLOCKS, BLOCK), jnp.float32, sh),
                                    block=BLOCK, use_kernel=True, interpret=False)


def _wire_decode(sh):
    packed = WIRE_BLOCKS * BLOCK + 4 * WIRE_BLOCKS
    return wire._fused_decode.lower(_spec((packed,), jnp.uint8, sh),
                                    n_blocks=WIRE_BLOCKS, block=BLOCK,
                                    use_kernel=True, interpret=False)


def _flash_llama(sh):
    # llama3.2-1b: 32 query heads over 8 kv heads of 64, at 2048 tokens
    q = _spec((1, 2048, 32, 64), jnp.bfloat16, sh)
    kv = _spec((1, 2048, 8, 64), jnp.bfloat16, sh)
    return jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                   interpret=False)).lower(q, kv, kv)


CASES = {
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
