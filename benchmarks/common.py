"""Shared benchmark helpers; each bench prints ``name,us_per_call,derived``."""
from __future__ import annotations

import time

import numpy as np


def bench_mesh(shape=(2, 4), axes=("pod", "data")):
    """Benchmark meshes share the test-mesh builder (Auto axes, through
    repro.compat) so they cannot diverge from the test tier."""
    from repro.launch.mesh import make_test_mesh

    return make_test_mesh(shape, axes)


def smoke_check() -> None:
    """Tiny end-to-end sanity used by ``run.py --smoke``: build a compat mesh,
    run one jitted shard_map psum on it, and emit a CSV row. Catches
    version-compat regressions in the mesh/shard_map path without paying for
    a full benchmark sweep."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro import compat

    n = jax.device_count()
    mesh = bench_mesh((n,), ("data",))
    f = compat.shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                         in_specs=P(), out_specs=P(), check_vma=False,
                         axis_names={"data"})
    out = jax.jit(f)(jnp.ones((4,)))
    assert float(np.asarray(out)[0]) == float(n), out
    t = timeit(lambda: jax.block_until_ready(jax.jit(f)(jnp.ones((4,)))))
    emit("smoke_psum", t * 1e6, f"devices={n}")


def timeit(fn, *, warmup: int = 3, iters: int = 20) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def pct(xs, p):
    return float(np.percentile(np.asarray(xs), p))


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.2f},{derived}")
