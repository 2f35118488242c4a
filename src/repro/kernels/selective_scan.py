"""Pallas TPU kernel for the fused mamba-1 selective scan.

The pure-JAX chunked scan (models/ssm.py) materializes the (B, C, d_inner,
state) decay/update tensors in HBM every chunk — the dominant memory-roofline
term for SSM architectures at long sequence (EXPERIMENTS.md §Perf cell C).
This kernel keeps the recurrence state in VMEM across the whole sequence:
HBM traffic drops to the inputs (x, dt, B, C) and output y only —
O(L·(d_inner + 2·state)) instead of O(L·d_inner·state).

Grid: (batch, d_inner tiles, seq chunks), seq innermost (sequential on TPU)
so the (tile, state) VMEM scratch carries h across chunks.

Same per-(channel,state) recurrence as the oracle:
    h[t] = exp(dt[t]·A) ⊙ h[t-1] + (dt[t]·x[t]) ⊗ B[t]
    y[t] = h[t] · C[t]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import interpret_mode

__all__ = ["selective_scan"]


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, h_scr,
            *, chunk_l: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...].astype(jnp.float32)               # (tile, st)

    def step(t, h):
        xt = x_ref[0, t, :].astype(jnp.float32)       # (tile,)
        dtt = dt_ref[0, t, :].astype(jnp.float32)     # (tile,)
        bt = b_ref[0, t, :].astype(jnp.float32)       # (st,)
        ct = c_ref[0, t, :].astype(jnp.float32)       # (st,)
        da = jnp.exp(dtt[:, None] * a)                # (tile, st)
        h = da * h + (dtt * xt)[:, None] * bt[None, :]
        y_ref[0, t, :] = (h @ ct).astype(y_ref.dtype)  # (tile,)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk_l, step, h_scr[...])


def selective_scan(
    x: jax.Array,    # (B, L, di)
    dt: jax.Array,   # (B, L, di)  (already softplus'd)
    bmat: jax.Array, # (B, L, st)
    cmat: jax.Array, # (B, L, st)
    a: jax.Array,    # (di, st)    (negative decay rates)
    *,
    tile_di: int = 128,
    chunk_l: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns y (B, L, di) = the recurrence output (no gate/skip)."""
    b, l, di = x.shape
    st = bmat.shape[-1]
    tile_di = min(tile_di, di)
    chunk_l = min(chunk_l, l)
    assert di % tile_di == 0 and l % chunk_l == 0
    grid = (b, di // tile_di, l // chunk_l)

    return pl.pallas_call(
        functools.partial(_kernel, chunk_l=chunk_l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk_l, tile_di), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, chunk_l, tile_di), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, chunk_l, st), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, chunk_l, st), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((tile_di, st), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk_l, tile_di),
                               lambda i, j, k: (i, k, j)),
        out_shape=jax.ShapeDtypeStruct((b, l, di), x.dtype),
        scratch_shapes=[pltpu.VMEM((tile_di, st), jnp.float32)],
        interpret=interpret_mode() if interpret is None else interpret,
    )(x, dt, bmat, cmat, a)
