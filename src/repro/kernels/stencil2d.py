"""Pallas TPU kernel for the 5-point Jacobi stencil (paper §8, Listing 8).

Row-band decomposition: the grid walks row tiles of height ``tile_rows``; the
kernel reads three bands (previous / current / next, selected by clamped
index maps — BlockSpecs cannot overlap, so halo rows come from the adjacent
bands) and writes one band of the updated field.  Column halos are handled
in-register by shifting; the global boundary is preserved via masking with
the band's global row offset.

VMEM per step: 4 bands × tile_rows × N × 4 B.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import interpret_mode

__all__ = ["stencil2d"]


def _kernel(prev_ref, cur_ref, next_ref, out_ref, *, coef: float,
            tile_rows: int, total_rows: int):
    i = pl.program_id(0)
    cur = cur_ref[...].astype(jnp.float32)                  # (T, N)
    prev_last = prev_ref[tile_rows - 1:tile_rows, :].astype(jnp.float32)
    next_first = next_ref[0:1, :].astype(jnp.float32)
    up = jnp.concatenate([prev_last, cur[:-1, :]], axis=0)
    down = jnp.concatenate([cur[1:, :], next_first], axis=0)
    left = jnp.concatenate([cur[:, :1], cur[:, :-1]], axis=1)
    right = jnp.concatenate([cur[:, 1:], cur[:, -1:]], axis=1)

    lap = up + down + left + right - 4.0 * cur
    updated = cur + jnp.float32(coef) * lap

    t, n = cur.shape
    grow = i * tile_rows + jax.lax.broadcasted_iota(jnp.int32, (t, n), 0)
    gcol = jax.lax.broadcasted_iota(jnp.int32, (t, n), 1)
    interior = (
        (grow > 0) & (grow < total_rows - 1) & (gcol > 0) & (gcol < n - 1)
    )
    out_ref[...] = jnp.where(interior, updated, cur).astype(out_ref.dtype)


def stencil2d(
    x: jax.Array,          # (M, N) local field including halo/boundary rows
    *,
    coef: float,
    tile_rows: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    m, n = x.shape
    assert m % tile_rows == 0, "pad rows to a tile multiple"
    nblk = m // tile_rows
    kern = functools.partial(
        _kernel, coef=coef, tile_rows=tile_rows, total_rows=m
    )
    spec = lambda f: pl.BlockSpec((tile_rows, n), f)  # noqa: E731
    return pl.pallas_call(
        kern,
        grid=(nblk,),
        in_specs=[
            spec(lambda i: (jnp.maximum(i - 1, 0), 0)),
            spec(lambda i: (i, 0)),
            spec(lambda i: (jnp.minimum(i + 1, nblk - 1), 0)),
        ],
        out_specs=spec(lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret_mode() if interpret is None else interpret,
    )(x, x, x)
