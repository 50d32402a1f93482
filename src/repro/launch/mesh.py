"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model); the pod axis is
the DCN (inter-pod) dimension — pure data parallelism across pods, FSDP
within a pod over 'data', tensor/expert parallelism over 'model'.

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over the locally available devices (tests / smoke runs)."""
    n = len(jax.devices())
    data = max(1, n // model_axis)
    return jax.make_mesh((data, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def dp_axes(mesh) -> tuple:
    """Axes that shard the batch dimension."""
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))
