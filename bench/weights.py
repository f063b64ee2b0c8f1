"""Seeded weights for the model cells, made by the benchmark and not by the
program: the program is handed them, and the plain reference makes the same
ones again from the seed.

Each leaf is addressed by its path (`layers/attn/wq/w`) and drawn from a key
folded from the seed and the path, so a leaf can be made alone or with all
the others and comes out the same. Layer stacks carry a leading layer axis,
as the program keeps them.
"""
from __future__ import annotations

import zlib
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

Layout = Tuple[Tuple[str, Tuple[int, ...]], ...]


def base_key(seed: int):
    """A key from a seed of any size (the driver's seeds pass 2**31)."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def dense_layout(cfg: dict, vocab_rows: int) -> Layout:
    """Leaves of a dense GQA decoder with a gated MLP, in the program's
    naming. `vocab_rows` is the embedding's row count as held (the program
    pads the vocabulary to a multiple of 256; padded rows are never read)."""
    d = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    f = cfg["intermediate_size"]
    bias = cfg.get("qkv_bias", False)
    out = [("embed/table", (vocab_rows, d))]
    per = [("ln1/scale", (d,)),
           ("attn/wq/w", (d, h * hd)), ("attn/wk/w", (d, kh * hd)),
           ("attn/wv/w", (d, kh * hd)), ("attn/wo/w", (h * hd, d)),
           ("ln2/scale", (d,)),
           ("mlp/gate/w", (d, f)), ("mlp/up/w", (d, f)), ("mlp/down/w", (f, d))]
    if bias:
        per += [("attn/wq/b", (h * hd,)), ("attn/wk/b", (kh * hd,)),
                ("attn/wv/b", (kh * hd,))]
    out += [(f"layers/{p}", (layers,) + s) for p, s in per]
    out += [("final_norm/scale", (d,)), ("lm_head/w", (d, vocab_rows))]
    return tuple(sorted(out))


def make_leaf(key, path: str, shape: Sequence[int]) -> jnp.ndarray:
    """Norm scales near 1, biases and embeddings small, matrices scaled by
    their fan-in (the second-last axis), all in f32."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    z = jax.random.normal(k, tuple(shape), jnp.float32)
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "scale":
        return 1.0 + 0.1 * z
    if leaf == "b" or leaf == "table":
        return 0.02 * z
    return z * (shape[-2] ** -0.5)


def make_params(seed: int, layout: Layout, shardings=None) -> Dict[str, jnp.ndarray]:
    """Every leaf of `layout`, made on the device in one jitted call."""

    def make(key):
        return {p: make_leaf(key, p, s) for p, s in layout}

    fn = jax.jit(make, out_shardings=shardings)
    return fn(base_key(seed))
