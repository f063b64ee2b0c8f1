"""Tests for repro.compat, the one module that touches JAX's mesh,
axis-type and shard_map API: round-trips, ambient-mesh scoping, the Auto
default and the report."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat


class TestMakeMesh:
    def test_roundtrips_axis_names_and_shape(self):
        mesh = compat.make_mesh((2, 4), ("data", "model"),
                                axis_types=(compat.AUTO,) * 2)
        assert tuple(mesh.axis_names) == ("data", "model")
        assert mesh.devices.shape == (2, 4)
        assert compat.axis_size(mesh, "data") == 2
        assert compat.axis_size(mesh, "model") == 4

    def test_axis_types_are_queryable_without_private_attrs(self):
        mesh = compat.make_mesh((2, 4), ("data", "model"),
                                axis_types=(compat.EXPLICIT, compat.AUTO))
        assert not compat.axis_is_auto(mesh, "data")
        assert compat.axis_is_auto(mesh, "model")

    def test_default_axis_types_are_auto(self):
        mesh = compat.make_mesh((8,), ("data",))
        assert compat.axis_is_auto(mesh, "data")
        # unknown axis names default to Auto rather than raising
        assert compat.axis_is_auto(mesh, "nonexistent")
        assert compat.axis_is_auto(None, "data")

    def test_agrees_with_native_axis_types(self):
        """compat must report exactly what the native mesh says."""
        mesh = compat.make_mesh((2, 4), ("data", "model"),
                                axis_types=(compat.EXPLICIT, compat.AUTO))
        native = dict(zip(mesh.axis_names, mesh.axis_types))
        for name in mesh.axis_names:
            assert compat.axis_is_auto(mesh, name) == (
                native[name] == jax.sharding.AxisType.Auto)


class TestMeshContext:
    def test_use_mesh_scopes_the_ambient_mesh(self):
        # compat.set_mesh is deliberately persistent, and other test modules
        # in the same process may have called it — assert restoration to
        # whatever was ambient before, not to None.
        before = compat.current_mesh()
        mesh = compat.make_mesh((2, 4), ("data", "model"),
                                axis_types=(compat.AUTO,) * 2)
        with compat.use_mesh(mesh):
            seen = compat.current_mesh()
            assert seen is not None
            assert tuple(seen.axis_names) == ("data", "model")
            assert compat.axis_size(seen, "model") == 4
        after = compat.current_mesh()
        assert (after is before) or (after == before)

    def test_use_mesh_restores_after_an_error(self):
        before = compat.current_mesh()
        mesh = compat.make_mesh((8,), ("data",))
        try:
            with compat.use_mesh(mesh):
                raise RuntimeError("boom")
        except RuntimeError:
            pass
        after = compat.current_mesh()
        assert (after is before) or (after == before)

    def test_sharding_constraint_works_under_use_mesh(self):
        """The property the whole stack depends on: bare-PartitionSpec
        with_sharding_constraint composes with jit inside the mesh context."""
        mesh = compat.make_mesh((2, 4), ("data", "model"),
                                axis_types=(compat.AUTO,) * 2)
        with compat.use_mesh(mesh):
            f = jax.jit(
                lambda x: jax.lax.with_sharding_constraint(x, P("data", None)))
            out = f(jnp.ones((4, 8)))
            np.testing.assert_array_equal(np.asarray(out), 1.0)


class TestShardMap:
    def test_psum_matches_tree_sum(self):
        mesh = compat.make_mesh((2, 4), ("pod", "data"),
                                axis_types=(compat.AUTO,) * 2)
        f = compat.shard_map(lambda x: jax.lax.psum(x, "pod"), mesh=mesh,
                             in_specs=P(), out_specs=P(), check_vma=False,
                             axis_names={"pod"})
        out = jax.jit(f)(jnp.arange(6.0))
        np.testing.assert_allclose(np.asarray(out), 2 * np.arange(6.0))

    def test_all_axes_manual_without_axis_names(self):
        mesh = compat.make_mesh((2, 4), ("pod", "data"))
        f = compat.shard_map(
            lambda x: jax.lax.psum(x, ("pod", "data")), mesh=mesh,
            in_specs=P(), out_specs=P(), check_vma=False)
        out = jax.jit(f)(jnp.ones(3))
        np.testing.assert_allclose(np.asarray(out), 8.0)

    def test_named_axis_size_is_static(self):
        mesh = compat.make_mesh((2, 4), ("pod", "data"),
                                axis_types=(compat.AUTO,) * 2)

        def fn(x):
            n = compat.named_axis_size("pod")
            # must be usable as a Python int (loop bounds in the ring
            # collectives) — a tracer would throw here
            assert int(n) == 2
            return x

        f = compat.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False, axis_names={"pod"})
        jax.jit(f)(jnp.arange(2.0))

    def test_manual_axes_reported_not_auto(self):
        """Inside shard_map, manual axes must stop reporting as Auto so the
        pshard constraint helpers skip them (the abstract mesh says Manual)."""
        mesh = compat.make_mesh((2, 4), ("pod", "data"),
                                axis_types=(compat.AUTO,) * 2)
        seen = {}

        def fn(x):
            m = compat.current_mesh()
            seen["pod_auto"] = compat.axis_is_auto(m, "pod")
            return x

        f = compat.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False, axis_names={"pod"})
        with compat.use_mesh(mesh):
            jax.jit(f)(jnp.arange(2.0))
        assert seen["pod_auto"] is False


class TestCostAnalysis:
    def test_returns_flat_dict(self):
        c = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile()
        cost = compat.cost_analysis(c)
        assert hasattr(cost, "keys") and "flops" in cost
        assert float(cost["flops"]) > 0


class TestReport:
    def test_report_names_active_code_path(self):
        r = compat.report()
        assert f"JAX {jax.__version__}" in r
        assert f"backend={jax.default_backend()}" in r
        assert jax.devices()[0].device_kind in r

    def test_tree_map(self):
        out = compat.tree_map(lambda a: a + 1, {"x": jnp.zeros(2)})
        np.testing.assert_array_equal(np.asarray(out["x"]), 1.0)
