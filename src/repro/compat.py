"""Mesh and ``shard_map`` spellings the benchmark scripts import.

Library code calls ``jax.shard_map`` and ``repro.launch.mesh`` directly.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["shard_map", "make_mesh", "auto_axis_types"]

shard_map = jax.shard_map
make_mesh = jax.make_mesh


def auto_axis_types(num_axes: int):
    return (AxisType.Auto,) * num_axes
