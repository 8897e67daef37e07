"""Production meshes.

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, and smoke tests must keep seeing 1 device.

Mesh shapes (TPU v5e):
  single-pod  (16, 16)     axes ("data", "model")   — 256 chips
  multi-pod   (2, 16, 16)  axes ("pod", "data", "model") — 512 chips

The ``pod`` axis is an outer data-parallel axis: gradient all-reduce
crosses pods once per step (DCN-friendly); weights/optimizer shard over
(data × model) *within* a pod so no parameter collective crosses the DCN.
"""
from __future__ import annotations

from typing import Dict

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules constrain
    activations through ``with_sharding_constraint``, which refuses the
    Explicit axes ``jax.make_mesh`` gives by default."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def mesh_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def make_host_mesh():
    """1-device mesh for CPU smoke paths (axes exist, sizes 1)."""
    return auto_mesh((1, 1), ("data", "model"))
