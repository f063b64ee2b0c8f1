"""Serving launcher: prefill + batched decode with the KV-partition chunnel.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \\
      --batch 4 --prompt-len 64 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from repro import compat

from repro.configs import get_config, get_smoke_config
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models import build


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_test_mesh((1, 1))
    compat.set_mesh(mesh)
    model = build(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng)

    B, S = args.batch, args.prompt_len
    batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        f = cfg.frontend
        batch["patches"] = jax.random.normal(rng, (B, f.num_positions, f.embed_dim),
                                             jnp.bfloat16)
    if cfg.family == "audio":
        src = max(1, S // cfg.encdec.src_ratio)
        batch["frames"] = jax.random.normal(rng, (B, src, cfg.frontend.embed_dim),
                                            jnp.bfloat16)

    t0 = time.time()
    cache, logits = jax.jit(model.prefill)(params, batch)
    jax.block_until_ready(logits)
    t_pre = time.time() - t0

    # grow caches for generation
    def grow(leaf):
        if hasattr(leaf, "ndim") and leaf.ndim >= 4:
            pad = [(0, 0)] * leaf.ndim
            pad[-3] = (0, args.gen + 1)
            return jnp.pad(leaf, pad)
        return leaf

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        cache = jax.tree.map(grow, cache)
    if cfg.family == "hybrid":
        for i in cfg.global_layers:
            for n in ("k", "v"):
                cache["layers"][i][n] = jnp.pad(
                    cache["layers"][i][n], ((0, 0), (0, args.gen + 1), (0, 0), (0, 0)))

    decode = jax.jit(model.decode)
    toks = jnp.argmax(logits, -1)[:, None]
    out = [toks]
    t0 = time.time()
    for _ in range(args.gen):
        cache, logits = decode(params, cache, {"tokens": toks})
        toks = jnp.argmax(logits, -1)[:, None]
        out.append(toks)
    jax.block_until_ready(logits)
    t_dec = time.time() - t0
    gen = jnp.concatenate(out, axis=1)
    assert bool(jnp.all(jnp.isfinite(logits)))
    print(f"arch={cfg.name} prefill({B}x{S})={t_pre*1e3:.0f}ms "
          f"decode={t_dec/args.gen*1e3:.1f}ms/tok "
          f"first row: {np.asarray(gen[0])[:10]}")


if __name__ == "__main__":
    main()
