"""Axis-name sharding annotations that degrade to no-ops off-mesh.

Models annotate intermediates with logical axis names::

    x = constrain(x, "batch", None, "model")   # one name per array dim

``"batch"`` is a logical alias for the data-parallel axes of the active
mesh (``("pod", "data")`` when a pod axis exists, else ``("data",)``);
other names are physical mesh axes and are dropped when the mesh lacks
them.  With no active mesh — unit tests, single-host CPU runs — every
call returns its input unchanged, so the zoo stays runnable anywhere.

The active mesh is either an explicit :func:`constraint_mesh` scope or
the mesh of the innermost ``jax.set_mesh`` scope, which JAX carries into
traced functions as an abstract mesh.
"""
from __future__ import annotations

import contextlib

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_MESH_STACK: list = []


@contextlib.contextmanager
def constraint_mesh(mesh):
    """Explicitly scope the mesh :func:`constrain` resolves against."""
    _MESH_STACK.append(mesh)
    try:
        yield mesh
    finally:
        _MESH_STACK.pop()


def current_mesh():
    """The mesh constrain() resolves against, or None."""
    if _MESH_STACK:
        return _MESH_STACK[-1]
    mesh = jax.sharding.get_abstract_mesh()  # `jax.set_mesh` scope
    return None if mesh.empty else mesh


def _resolve(axis, mesh_axes):
    if axis is None:
        return None
    if axis == "batch":
        present = tuple(a for a in ("pod", "data") if a in mesh_axes)
        return present if present else None
    if isinstance(axis, (tuple, list)):
        kept = tuple(a for a in axis if a in mesh_axes)
        return kept if kept else None
    return axis if axis in mesh_axes else None


def constrain(x, *axes):
    """``with_sharding_constraint`` by logical axis names; no-op off-mesh."""
    mesh = current_mesh()
    if mesh is None or mesh.size <= 1 or len(axes) != x.ndim:
        return x
    names = set(mesh.axis_names)
    spec = P(*(_resolve(a, names) for a in axes))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
