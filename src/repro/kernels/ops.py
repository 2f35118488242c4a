"""Public entry points of the Pallas kernels.

The SpMV and stencil wrappers own the blocking/padding/window planning
their kernels need; the exchange kernels (pack / unpack / accumulate) are
re-exported as they are.  No wrapper falls back to the jnp reference: an
operand too large for its kernel raises ``VmemBudgetError``.  Every kernel
compiles to Mosaic on a TPU backend and runs through the Pallas
interpreter elsewhere (``kernels.layout.interpret_mode``), so the whole
framework runs, and is tested, on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ellpack_spmv import ellpack_spmv_windowed
from repro.kernels.layout import VmemBudgetError
from repro.kernels.pack_gather import (
    accumulate_into, accumulate_segments, pack_gather, unpack_dest,
    unpack_scatter_set,
)
from repro.kernels.stencil2d import stencil2d as _stencil2d_kernel

__all__ = [
    "VmemBudgetError", "plan_spmv_windows", "ellpack_spmv",
    "make_spmv_on_copy_sharded", "make_spmv_overlap_sharded",
    "pack_gather", "unpack_dest", "unpack_scatter_set",
    "accumulate_segments", "accumulate_into", "stencil2d",
    "decode_attention", "selective_scan",
]


# --------------------------------------------------------------------------
# EllPack SpMV
# --------------------------------------------------------------------------

def plan_spmv_windows(
    cols: np.ndarray, *, rows_per_block: int = 256, lane: int = 128
):
    """Host-side one-time window planning (DESIGN.md: VMEM-level blockwise).

    Returns (window, win_blk, cols_rel, own_rel); ``window`` is the static
    tile width (multiple of ``lane``) covering every row block's column span.
    """
    n, _ = cols.shape
    assert n % rows_per_block == 0, "pad rows first"
    nblk = n // rows_per_block
    own = np.arange(n, dtype=np.int64)
    # own row index participates in the span (diagonal term gathers x[i])
    lo = np.minimum(
        cols.reshape(nblk, -1).min(axis=1),
        own.reshape(nblk, rows_per_block).min(axis=1),
    )
    hi = np.maximum(
        cols.reshape(nblk, -1).max(axis=1),
        own.reshape(nblk, rows_per_block).max(axis=1),
    )
    span = int((hi - lo + 1).max())
    window = max(lane, int(np.ceil(span / lane)) * lane)
    win_blk = (lo // window).astype(np.int32)           # (nblk,)
    base = (win_blk.astype(np.int64) * window)          # window start
    cols_rel = (
        cols - np.repeat(base, rows_per_block)[:, None]
    ).astype(np.int32)
    own_rel = (own - np.repeat(base, rows_per_block)).astype(np.int32)
    assert cols_rel.min() >= 0 and cols_rel.max() < 2 * window
    return window, win_blk, cols_rel, own_rel


@functools.partial(
    jax.jit, static_argnames=("window", "rows_per_block", "interpret")
)
def _spmv_call(diag, vals, cols_rel, own_rel, win_blk, x_padded, *, window,
               rows_per_block, interpret):
    return ellpack_spmv_windowed(
        diag, vals, cols_rel, own_rel, win_blk, x_padded,
        window=window, rows_per_block=rows_per_block, interpret=interpret,
    )


def ellpack_spmv(
    diag, vals, cols, x, *, rows_per_block: int = 256, interpret=None,
    plan=None,
):
    """y = diag*x + EllPack(vals, cols) @ x via the windowed Pallas kernel.

    ``plan``: optional precomputed ``plan_spmv_windows`` output (amortize the
    one-time prep, exactly like the paper's preparation step).
    """
    n, _ = np.shape(vals)
    if plan is None:
        plan = plan_spmv_windows(np.asarray(cols), rows_per_block=rows_per_block)
    window, win_blk, cols_rel, own_rel = plan
    need = (int(win_blk.max()) + 2) * window
    x_padded = jnp.pad(x, (0, max(0, need - x.shape[0])))
    return _spmv_call(
        diag, vals, jnp.asarray(cols_rel), jnp.asarray(own_rel),
        jnp.asarray(win_blk), x_padded,
        window=window, rows_per_block=rows_per_block, interpret=interpret,
    )


def make_spmv_on_copy_sharded(
    cols: np.ndarray, p: int, *, rows_per_block: int = 256, interpret=None
):
    """Per-shard window plans with one common static window, for use inside
    the DistributedSpMV shard_map (each device computes its own rows against
    its private x_copy).

    Returns (local_fn, plan_args) where ``plan_args`` are host arrays shaped
    (P, ...) to be passed through shard_map with in_specs P(axis) and
    ``local_fn(diag_l, vals_l, x_copy, win_blk_l, cols_rel_l, own_rel_l)``.
    """
    n, r_nz = cols.shape
    shard = n // p
    rows_per_block = min(rows_per_block, shard)
    # plan per shard, then unify the static window across shards
    plans = [
        plan_spmv_windows(cols[q * shard:(q + 1) * shard],
                          rows_per_block=rows_per_block)
        for q in range(p)
    ]
    window = max(pl[0] for pl in plans)
    nblk = shard // rows_per_block
    win_blk = np.zeros((p, nblk), np.int32)
    cols_rel = np.zeros((p, shard, r_nz), np.int32)
    own_rel = np.zeros((p, shard), np.int32)
    for q in range(p):
        sub = cols[q * shard:(q + 1) * shard]
        own = np.arange(q * shard, (q + 1) * shard, dtype=np.int64)
        lo = np.minimum(
            sub.reshape(nblk, -1).min(axis=1),
            own.reshape(nblk, rows_per_block).min(axis=1),
        )
        wb = (lo // window).astype(np.int32)
        base = np.repeat(wb.astype(np.int64) * window, rows_per_block)
        win_blk[q] = wb
        cols_rel[q] = (sub - base[:, None]).astype(np.int32)
        own_rel[q] = (own - base).astype(np.int32)
        assert cols_rel[q].min() >= 0 and cols_rel[q].max() < 2 * window
    need_global = (int(win_blk.max()) + 2) * window

    def local_fn(diag_l, vals_l, x_copy, win_blk_l, cols_rel_l, own_rel_l):
        ln = x_copy.shape[0]
        if ln < need_global:
            xp = jnp.pad(x_copy, (0, need_global - ln))
        else:
            xp = x_copy[:need_global]
        return _spmv_call(
            diag_l, vals_l, cols_rel_l[0], own_rel_l[0], win_blk_l[0], xp,
            window=window, rows_per_block=rows_per_block, interpret=interpret,
        )

    return local_fn, (win_blk, cols_rel, own_rel)


def make_spmv_overlap_sharded(plan, vals: np.ndarray, *,
                              rows_per_block: int = 256, interpret=None):
    """Split-kernel on-copy variant of the ``overlap`` rung.

    The overlap strategy splits the local SpMV into an own-shard partial
    (reads only ``x_local``, runs while the condensed all_to_all is in
    flight) and a foreign partial (reads the landed ``x_copy``).  This
    builds BOTH partials as windowed Pallas kernels from the plan's
    own/foreign column split:

      * own kernel: columns are the plan's shard-local ``loc_cols`` (padding
        -> the zero slot at ``shard_size``), x is ``x_local`` + 1 pad slot;
      * foreign kernel: columns are ``rem_cols`` with padding redirected to
        an in-window fallback whose value is zeroed out of ``vals`` (the
        jnp path instead relies on x_copy's zero slot at n+1, which would
        blow the kernel's window up to the whole vector), diag = 0.

    Returns ``(own_fn, rem_fn, kargs)``: ``kargs`` are 8 host arrays shaped
    (P, ...) to pass through shard_map with in_specs P(axis);
    ``own_fn(diag_l, x_ext, *kargs[:4])`` and ``rem_fn(x_copy, *kargs[4:])``
    are the two shard-local partials.
    """
    p, n, shard = plan.p, plan.n, plan.shard_size
    rows_per_block = min(rows_per_block, shard)
    assert shard % rows_per_block == 0
    nblk_rows = shard // rows_per_block
    lane = 128

    # ---- own half: local indices in [0, shard]; one static window covers
    # the whole extended shard, so win_blk is identically zero ----
    loc_vals = np.take_along_axis(vals, plan.loc_src, axis=1)
    window_own = max(lane, int(np.ceil((shard + 1) / lane)) * lane)
    loc_vals_s = loc_vals.reshape(p, shard, -1)
    loc_cols_s = plan.loc_cols.reshape(p, shard, -1)
    own_win = np.zeros((p, nblk_rows), np.int32)
    own_rel = np.tile(np.arange(shard, dtype=np.int32), (p, 1))

    # ---- foreign half: global indices; padding (n + 1) must not join the
    # window span, so redirect padded slots to the block's lowest valid
    # column and zero their vals ----
    rem_vals = np.take_along_axis(vals, plan.rem_src, axis=1)
    valid = plan.rem_cols != (n + 1)
    rem_vals = np.where(valid, rem_vals, 0).astype(vals.dtype)
    r_rem = plan.rem_cols.shape[1]
    cols_v = np.where(valid, plan.rem_cols, np.iinfo(np.int32).max)
    cols_blk = cols_v.reshape(p, nblk_rows, rows_per_block * r_rem)
    lo = cols_blk.min(axis=2)
    lo = np.where(lo == np.iinfo(np.int32).max, 0, lo)      # all-pad block
    hi_blk = np.where(valid, plan.rem_cols, 0).reshape(
        p, nblk_rows, rows_per_block * r_rem)
    hi = np.maximum(hi_blk.max(axis=2), lo)
    span = int((hi - lo + 1).max())
    window_rem = max(lane, int(np.ceil(span / lane)) * lane)
    rem_win = (lo // window_rem).astype(np.int32)            # (P, nblk)
    base = np.repeat(rem_win.astype(np.int64) * window_rem,
                     rows_per_block, axis=1)                 # (P, shard)
    lo_rows = np.repeat(lo.astype(np.int64), rows_per_block, axis=1)
    rem_cols_rel = (
        np.where(valid.reshape(p, shard, r_rem),
                 plan.rem_cols.reshape(p, shard, r_rem),
                 lo_rows[:, :, None]) - base[:, :, None]
    ).astype(np.int32)
    rem_own_rel = (lo_rows - base).astype(np.int32)          # diag=0: any
    assert rem_cols_rel.min() >= 0 and rem_cols_rel.max() < 2 * window_rem
    need_rem = (int(rem_win.max()) + 2) * window_rem

    def own_fn(diag_l, x_ext, loc_vals_l, loc_cols_l, own_win_l, own_rel_l):
        xp = jnp.pad(x_ext, (0, 2 * window_own - x_ext.shape[0]))
        return _spmv_call(
            diag_l, loc_vals_l[0], loc_cols_l[0], own_rel_l[0],
            own_win_l[0], xp,
            window=window_own, rows_per_block=rows_per_block,
            interpret=interpret,
        )

    def rem_fn(x_copy, rem_vals_l, rem_cols_l, rem_own_l, rem_win_l):
        ln = x_copy.shape[0]
        if ln < need_rem:
            xp = jnp.pad(x_copy, (0, need_rem - ln))
        else:
            xp = x_copy[:need_rem]
        zero_diag = jnp.zeros((shard,), x_copy.dtype)
        return _spmv_call(
            zero_diag, rem_vals_l[0], rem_cols_l[0], rem_own_l[0],
            rem_win_l[0], xp,
            window=window_rem, rows_per_block=rows_per_block,
            interpret=interpret,
        )

    kargs = (loc_vals_s, loc_cols_s, own_win, own_rel,
             rem_vals.reshape(p, shard, r_rem), rem_cols_rel,
             rem_own_rel.reshape(p, shard), rem_win)
    return own_fn, rem_fn, kargs


# --------------------------------------------------------------------------
# 2D stencil
# --------------------------------------------------------------------------

def stencil2d(x, *, coef: float, tile_rows: int = 8, interpret=None):
    """One Jacobi step; pads rows to a tile multiple and slices back."""
    m, n = x.shape
    mp = int(np.ceil(m / tile_rows)) * tile_rows
    if mp != m:
        x_p = jnp.pad(x, ((0, mp - m), (0, 0)), mode="edge")
    else:
        x_p = x
    # padded rows replicate the last row; masking keys on the *unpadded*
    # boundary, so run the kernel with total_rows = m semantics by slicing.
    out = _stencil2d_kernel(x_p, coef=coef, tile_rows=tile_rows,
                            interpret=interpret)
    if mp != m:
        # rows >= m are padding; recompute the last true row as boundary copy
        out = out[:m, :]
        out = out.at[m - 1, :].set(x[m - 1, :])
    return out


# --------------------------------------------------------------------------
# Decode attention (flash-decoding)
# --------------------------------------------------------------------------

def decode_attention(q, k, v, lengths, *, kv_chunk: int = 512,
                     interpret=None):
    """Single-token GQA attention over a KV cache; see
    kernels/decode_attention.py."""
    from repro.kernels.decode_attention import decode_attention as _da
    return _da(q, k, v, lengths, kv_chunk=kv_chunk, interpret=interpret)


# --------------------------------------------------------------------------
# Fused selective scan (mamba-1 recurrence)
# --------------------------------------------------------------------------

def selective_scan(x, dt, bmat, cmat, a, *, tile_di: int = 128,
                   chunk_l: int = 256, interpret=None):
    """HBM-minimal SSM recurrence; see kernels/selective_scan.py."""
    from repro.kernels.selective_scan import selective_scan as _ss
    return _ss(x, dt, bmat, cmat, a, tile_di=tile_di, chunk_l=chunk_l,
               interpret=interpret)
