"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_local_mesh(shape, axes)


def make_local_mesh(shape=None, axes=None):
    """Mesh over whatever devices exist, every axis ``AxisType.Auto``
    (GSPMD places what ``shard_map`` does not)."""
    n = len(jax.devices())
    if shape is None:
        shape = (n,) if n == 1 else (2, n // 2)
    if axes is None:
        axes = ("data",) if len(shape) == 1 else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
