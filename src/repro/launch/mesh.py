"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run sets
``xla_force_host_platform_device_count=512`` before first jax init, while
smoke tests must see the 1 real CPU device.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "dp_axes", "tp_axis"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model code constrains shardings with bare
    # PartitionSpecs (``models.layers.shard_hint``), which Explicit axes refuse
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> Tuple[str, ...]:
    """Data-parallel axes: batch (and FSDP/ZeRO param+state sharding)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh) -> str:
    return "model"
