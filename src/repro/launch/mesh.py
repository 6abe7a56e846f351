"""Mesh builders.

`make_production_mesh` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 in its `main()` before
first jax init, while smoke tests and benches see 1 device.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType

from repro.configs.base import MeshConfig


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None):
    """`jax.make_mesh` with Auto axes. The sharding rules place arrays
    through NamedSharding and sharding constraints and leave the rest to
    the partitioner; `jax.make_mesh` alone would make every axis
    Explicit."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_config(mesh) -> MeshConfig:
    return MeshConfig(tuple(mesh.shape[a] for a in mesh.axis_names),
                      tuple(mesh.axis_names))
