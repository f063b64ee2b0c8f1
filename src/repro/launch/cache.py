"""Where JAX's persistent compilation cache lives: one helper for every entry
point (``chip_smoke.py`` and the launchers), called before the first compile."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/cache.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing. Otherwise the cache is ``<checkout>/.jax_cache``: a fixed
    path, since the directory is part of what a later run looks up.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
