"""The one module that touches JAX's mesh, axis-type and shard_map API.

The stack targets the installed JAX, 0.9.0. Everything mesh-, axis-type- or
shard_map-shaped goes through here, so the next change of that API touches
this file only (``python -m repro.lint --strict`` enforces the boundary):

    from repro import compat
    mesh = compat.make_mesh((2, 4), ("data", "model"))   # Auto axes
    compat.set_mesh(mesh)
    f = compat.shard_map(fn, mesh=mesh, in_specs=..., out_specs=...,
                         check_vma=False, axis_names={"data"})

``python -m repro.compat`` prints the JAX version and the devices it sees.
"""
from __future__ import annotations

from typing import Mapping, Optional, Sequence

import jax

__all__ = [
    "AUTO",
    "EXPLICIT",
    "MANUAL",
    "AxisType",
    "axis_is_auto",
    "axis_size",
    "cost_analysis",
    "current_mesh",
    "make_mesh",
    "named_axis_size",
    "report",
    "set_mesh",
    "shard_map",
    "tree_map",
    "use_mesh",
]

AxisType = jax.sharding.AxisType
AUTO = AxisType.Auto
EXPLICIT = AxisType.Explicit
MANUAL = AxisType.Manual

tree_map = jax.tree.map


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              axis_types: Optional[Sequence] = None, devices=None):
    """``jax.make_mesh`` whose axes are Auto unless ``axis_types`` says
    otherwise (JAX 0.9 defaults to Explicit)."""
    if axis_types is None:
        axis_types = (AUTO,) * len(axis_names)
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=tuple(axis_types), devices=devices)


def set_mesh(mesh):
    """Make ``mesh`` the ambient mesh of the calling thread; returns it so
    launchers can write ``mesh = compat.set_mesh(m)``."""
    jax.set_mesh(mesh)
    return mesh


def use_mesh(mesh):
    """Scoped :func:`set_mesh`: ``with compat.use_mesh(mesh): ...``."""
    return jax.set_mesh(mesh)


def current_mesh():
    """The ambient (abstract) mesh, or None when no mesh is set."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


def axis_is_auto(mesh, name: str) -> bool:
    """True when ``mesh``'s axis ``name`` is auto-partitioned. An axis the
    mesh does not have, or no mesh, counts as Auto; axes under shard_map
    report Manual through the ambient abstract mesh."""
    if mesh is None:
        return True
    types = dict(zip(mesh.axis_names, mesh.axis_types))
    return types.get(name, AUTO) == AUTO


def axis_size(mesh, name: str) -> int:
    """Size of a named axis of a Mesh or an AbstractMesh."""
    return int(mesh.shape[name])


def named_axis_size(name: str) -> int:
    """Static size of a manual axis, for code running inside shard_map."""
    return jax.lax.axis_size(name)


def cost_analysis(compiled) -> Mapping:
    """XLA's cost analysis of a ``Compiled`` as a flat dict."""
    return compiled.cost_analysis()


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True,
              axis_names=None):
    """``jax.shard_map``; ``axis_names`` is the set of axes under manual
    control (None = all of them), the others stay auto-partitioned."""
    kwargs = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_vma, **kwargs)


def report() -> str:
    """JAX version, backend and device count, for logs and the CLI."""
    devs = jax.devices()
    return (f"repro.compat: JAX {jax.__version__} backend={jax.default_backend()} "
            f"devices={len(devs)} kind={devs[0].device_kind}")
