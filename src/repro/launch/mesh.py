"""Production meshes (single-pod 16x16, multi-pod 2x16x16).

`make_production_mesh` is a FUNCTION so importing this module never touches
jax device state; the dry-run entry point sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / small-scale runs), every axis Auto."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def data_axis_size(mesh) -> int:
    n = 1
    for name in ("pod", "data"):
        if name in mesh.axis_names:
            n *= mesh.shape[name]
    return n


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)
