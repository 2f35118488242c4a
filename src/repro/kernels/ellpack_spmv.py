"""Pallas TPU kernel for modified-EllPack SpMV — the paper's compute hot-spot.

TPU adaptation of the paper's insight: the GPU/CPU version of this kernel
gathers ``x[J[i,j]]`` straight from main memory.  On TPU we apply the
paper's *blockwise* idea one level down the memory hierarchy — at the
HBM→VMEM boundary:

  * rows are processed in blocks of ``rows_per_block``;
  * for each row block, the one-time plan computes the (quantized) column
    *window* that covers every index the block touches (meshes reordered for
    locality make this window small — paper §3.1/§6.1);
  * the window is DMA'd into VMEM as two adjacent tiles selected by a
    scalar-prefetched per-block window index (``win_blk``), so the
    irregular gather happens VMEM-locally on relative indices.

This is "message condensing at VMEM granularity": bulk, planned,
latency-amortizing transfers instead of fine-grained irregular access.

Grid: ``(n_row_blocks,)``.  Per step the block's column indices, values,
diagonal and own-index stream through SMEM; each nonzero reads one scalar
item of the window (``kernels.layout``).  The two window tiles are single
buffered: 2·W·4 B of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import (
    LANES, check_resident, compiler_params, interpret_mode, load_item,
    store_item,
)

__all__ = ["ellpack_spmv_windowed"]


def _kernel(win_ref, diag_ref, vals_ref, cols_ref, own_ref, x_lo_ref,
            x_hi_ref, y_ref, *, r_nz, window):
    """Row-block kernel; ``own_rel`` carries the row's own x index relative
    to the window (so the diagonal term is also a window gather)."""
    del win_ref

    def x_at(c):
        lo = load_item(x_lo_ref, jnp.minimum(c, window - 1), True)
        hi = load_item(x_hi_ref, jnp.maximum(c - window, 0), True)
        return jnp.where(c < window, lo, hi)

    def row(r, carry):
        acc = jnp.zeros((1, 1), jnp.float32)
        for j in range(r_nz):
            k = r * r_nz + j
            acc = acc + vals_ref[0, k] * x_at(cols_ref[0, k])
        store_item(y_ref, r, diag_ref[0, r] * x_at(own_ref[0, r]) + acc,
                   True)
        return carry

    jax.lax.fori_loop(0, diag_ref.shape[1], row, 0)


def ellpack_spmv_windowed(
    diag: jax.Array,       # (n,)
    vals: jax.Array,       # (n, r_nz)
    cols_rel: jax.Array,   # (n, r_nz) int32, relative to win_blk*window
    own_rel: jax.Array,    # (n,)      int32, row's own x idx relative to window
    win_blk: jax.Array,    # (n_blocks,) int32 scalar-prefetch window indices
    x: jax.Array,          # (>= (max(win_blk)+2)*window,) padded vector
    *,
    window: int,
    rows_per_block: int,
    interpret: bool | None = None,
) -> jax.Array:
    """y of shape (n,).  All blocking/padding is prepared by kernels.ops."""
    n, r_nz = vals.shape
    assert n % rows_per_block == 0 and window % LANES == 0
    assert x.shape[0] % window == 0
    n_blocks = n // rows_per_block
    check_resident("ellpack_spmv_windowed", (2 * window, (), 4))
    y_lanes = LANES if rows_per_block % LANES == 0 else rows_per_block
    y_rows = rows_per_block // y_lanes
    f32 = jnp.float32

    def smem(a, per_row):
        return a.astype(a.dtype if a.dtype == jnp.int32 else f32).reshape(
            n_blocks, 1, rows_per_block * per_row)

    def smem_spec(per_row):
        return pl.BlockSpec((None, 1, rows_per_block * per_row),
                            lambda i, w: (i, 0, 0), memory_space=pltpu.SMEM)

    def window_spec(shift):
        return pl.BlockSpec((None, window // LANES, LANES),
                            lambda i, w: (w[i] + shift, 0, 0),
                            pipeline_mode=pl.Buffered(1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks,),
        in_specs=[smem_spec(1), smem_spec(r_nz), smem_spec(r_nz),
                  smem_spec(1), window_spec(0), window_spec(1)],
        out_specs=pl.BlockSpec((None, y_rows, y_lanes),
                               lambda i, w: (i, 0, 0)),
    )
    x_tiles = x.astype(f32).reshape(-1, window // LANES, LANES)
    y = pl.pallas_call(
        functools.partial(_kernel, r_nz=r_nz, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, y_rows, y_lanes), f32),
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret_mode() if interpret is None else interpret,
    )(win_blk, smem(diag, 1), smem(vals, r_nz), smem(cols_rel, r_nz),
      smem(own_rel, 1), x_tiles, x_tiles)
    return y.reshape(n).astype(diag.dtype)
