"""What every exchange kernel shares: the compiled-or-interpreted switch,
the VMEM budget, and the item layout Mosaic can address.

An *item* is one row of an array ``(n, *feat)``: a scalar when ``feat`` is
empty, a feature row otherwise.  Mosaic slices VMEM dynamically only along
the sublane (second-to-last) axis, so items live in a 2-D view:

* scalar items are packed lane-dense, ``L`` to a row (``L = 128``, or the
  whole array when it is shorter): item ``i`` is lane ``i % L`` of row
  ``i // L``.  A load masks the lane out and max-reduces the row to a
  ``(1, 1)`` vector (exact for every value, ``-0.0`` and NaN included);
  a store selects it into its lane.
* feature items are rows of ``(n, F)`` with ``F = prod(feat)``.

Kernels compute on 32-bit items.  Narrower dtypes are widened exactly
before the kernel and narrowed back after it; the accumulate kernels round
after every combine, so a bf16 sum rounds where the jnp scatter-add
rounds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["LANES", "VMEM_LIMIT_BYTES", "VMEM_BUDGET_BYTES",
           "VmemBudgetError", "interpret_mode", "compiler_params",
           "item_bytes", "check_resident", "lane_width", "to_items",
           "from_items", "load_item", "store_item", "combine_item"]

LANES = 128

# v5e has 128 MiB of VMEM per core and its compiler accepts a scoped limit
# of all of it (its default is 16 MiB); compiles at 127 MiB of resident
# arrays pass at this limit and fail at the default.
VMEM_LIMIT_BYTES = 128 * 2**20
# resident arrays may take this much; the rest holds the pipelined index,
# value and output blocks and Mosaic's own scratch
VMEM_BUDGET_BYTES = 112 * 2**20


class VmemBudgetError(ValueError):
    """An array a kernel keeps whole in VMEM does not fit the budget."""


def interpret_mode() -> bool:
    """Kernels compile to Mosaic on a TPU backend and run through the
    Pallas interpreter everywhere else — the one place that decides."""
    return jax.default_backend() != "tpu"


def compiler_params(*semantics: str):
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES,
                                dimension_semantics=semantics or None)


def lane_width(n: int) -> int:
    """Lanes per row of a lane-dense array of ``n`` scalar items."""
    return LANES if n > LANES else max(n, 1)


def item_bytes(n: int, feat=(), itemsize: int = 4) -> int:
    """VMEM bytes of ``n`` items in the kernel layout (tiles of 8 x 128
    32-bit words; narrower dtypes are widened to 32 bits)."""
    f = int(np.prod(feat, dtype=np.int64))
    width = max(itemsize, 4)
    if f <= 1:
        rows, lanes = -(-max(n, 1) // LANES), LANES
    else:
        rows, lanes = n, -(-f // LANES) * LANES
    return (-(-rows // 8) * 8) * lanes * width


def check_resident(kernel: str, *arrays) -> None:
    """Raise unless the arrays ``kernel`` keeps whole in VMEM fit the
    budget.  ``arrays`` are ``(n_items, feat, itemsize)`` triples."""
    total = sum(item_bytes(n, feat, size) for n, feat, size in arrays)
    if total > VMEM_BUDGET_BYTES:
        raise VmemBudgetError(
            f"{kernel}: {total} bytes must stay resident in VMEM, over the "
            f"{VMEM_BUDGET_BYTES}-byte budget (VMEM limit "
            f"{VMEM_LIMIT_BYTES} bytes); shard the operand over more "
            f"devices or take the jnp path (use_kernel=False)")


def to_items(a: jax.Array) -> jax.Array:
    """``(n, *feat)`` -> the 2-D kernel view: lane-dense ``(rows, L)`` for
    scalar items, ``(n, F)`` for feature items."""
    n = a.shape[0]
    if a.ndim == 1 or int(np.prod(a.shape[1:])) == 1:
        lanes = lane_width(n)
        flat = a.reshape(n)
        pad = -n % lanes
        if pad:
            flat = jnp.pad(flat, (0, pad))
        return flat.reshape(-1, lanes)
    return a.reshape(n, -1)


def from_items(v: jax.Array, n: int, feat) -> jax.Array:
    """Inverse of ``to_items`` (drops lane padding)."""
    return v.reshape(-1)[: n * int(np.prod(feat, dtype=np.int64))].reshape(
        (n,) + tuple(feat))


def _lanes_of(ref):
    return jax.lax.broadcasted_iota(jnp.int32, (1, ref.shape[-1]), 1)


def _lowest(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).min, dtype)


def load_item(ref, i, scalar: bool):
    """Item ``i`` of a 2-D kernel view: ``(1, 1)`` for scalar items,
    ``(1, F)`` for feature items."""
    if not scalar:
        return ref[pl.ds(i, 1), :]
    lanes = ref.shape[-1]
    row = ref[pl.ds(i // lanes, 1), :]
    hit = _lanes_of(ref) == i % lanes
    return jnp.max(jnp.where(hit, row, _lowest(row.dtype)), axis=1,
                   keepdims=True)


def store_item(ref, k, v, scalar: bool):
    if not scalar:
        ref[pl.ds(k, 1), :] = v.astype(ref.dtype)
        return
    lanes = ref.shape[-1]
    r = k // lanes
    row = ref[pl.ds(r, 1), :]
    ref[pl.ds(r, 1), :] = jnp.where(_lanes_of(ref) == k % lanes,
                                    v.astype(ref.dtype), row)


def combine_item(ref, k, v, scalar: bool, reduce: str, round_to=None):
    """``ref[k] = ref[k] (+|max) v``; ``round_to`` rounds the result
    through a narrower dtype, as that dtype's own scatter would."""
    lanes = ref.shape[-1] if scalar else None
    r = k // lanes if scalar else k
    row = ref[pl.ds(r, 1), :]
    new = jnp.maximum(row, v) if reduce == "max" else row + v
    if round_to is not None:
        new = new.astype(round_to).astype(ref.dtype)
    if scalar:
        new = jnp.where(_lanes_of(ref) == k % lanes, new, row)
    ref[pl.ds(r, 1), :] = new
