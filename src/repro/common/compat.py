"""The one place that builds meshes.

``jax.make_mesh`` defaults to ``Explicit`` axis types, and under those
``with_sharding_constraint`` refuses the PartitionSpecs the sharding
rules emit ("can only refer to Auto axes of the mesh").  Every mesh in
the code and the tests is built here, with ``Auto`` axis types.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Mesh of ``shape`` over all devices of the process."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_from_devices(devices, axes: Tuple[str, ...]) -> Mesh:
    """Mesh over an explicit [*shape]-shaped device array."""
    return Mesh(devices, axes, axis_types=(AxisType.Auto,) * len(axes))
