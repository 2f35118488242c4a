"""The SpMV yardstick: the test matrix, the plain float64 reference, and the
bfloat16 control.

The matrix has the paper's test-problem shape (modified EllPack, r_nz
off-diagonal nonzeros per row drawn from a band around the diagonal, as a
reordered tetrahedral mesh gives, with a share of long-range columns drawn
uniformly).  It is generated here, on the device, from the configuration's
matrix seed, so the program under test receives data it did not make.
"""
from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np

from bench.common import key32

BLOCK_ROWS = 1 << 18


def make_matrix(cfg: dict):
    """(diag (n,), vals (n, r_nz), cols (n, r_nz) int32) as numpy arrays.

    Row i's columns come from the band [i - w, i + w] (w = the
    configuration's ``locality_window``, by default max(64, n // 256)), with
    offset 0 moved to +1 (the diagonal is stored apart) and the band clipped
    at the matrix edge; a ``long_range_frac`` share is redrawn uniformly over
    [0, n).  Values are normal / r_nz; the diagonal is the row's absolute
    sum plus 1, as in a diffusion matrix.  Drawn slot-major on the device:
    an (n, 16) table would pad its minor dimension to 128 lanes in HBM.
    """
    import jax
    import jax.numpy as jnp

    n, r = int(cfg["n"]), int(cfg["r_nz"])
    w = int(cfg.get("locality_window") or max(64, n // 256))
    frac = float(cfg["long_range_frac"])

    @jax.jit
    def draw(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        off = jax.random.randint(k1, (r, n), -w, w + 1, jnp.int32)
        off = jnp.where(off == 0, 1, off)
        cols = jnp.clip(jnp.arange(n, dtype=jnp.int32)[None] + off, 0, n - 1)
        far = jax.random.uniform(k2, (r, n)) < frac
        cols = jnp.where(far, jax.random.randint(k3, (r, n), 0, n,
                                                 jnp.int32), cols)
        vals = jax.random.normal(k4, (r, n), jnp.float32) / r
        diag = jnp.abs(vals).sum(axis=0) + 1.0
        return diag, vals, cols

    diag, vals, cols = draw(jax.random.PRNGKey(
        key32(int(cfg["matrix_seed"]), "matrix")))
    diag = np.asarray(diag)
    vals = np.ascontiguousarray(np.asarray(vals).T)
    cols = np.ascontiguousarray(np.asarray(cols).T)
    return diag, vals, cols


def _blocks(n: int):
    return [(lo, min(n, lo + BLOCK_ROWS)) for lo in range(0, n, BLOCK_ROWS)]


def reference(diag, vals, cols, x, *, threads: int | None = None):
    """y = D x + A x in float64, in blocks of rows over up to 16 threads
    (numpy's take and multiply release the interpreter lock)."""
    threads = threads or min(16, os.cpu_count() or 1)
    x64 = np.asarray(x, np.float64)
    y = np.empty(len(diag), np.float64)

    def block(lo_hi):
        lo, hi = lo_hi
        g = np.take(x64, cols[lo:hi])
        g *= vals[lo:hi]
        y[lo:hi] = diag[lo:hi] * x64[lo:hi] + g.sum(axis=1)

    with cf.ThreadPoolExecutor(threads) as pool:
        list(pool.map(block, _blocks(len(diag))))
    return y


def rel_err(got, want) -> float:
    """max |got - want| over max |want| (NaN and inf fail)."""
    got = np.asarray(got, np.float64)
    err = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                 1e-300))
    return err if np.isfinite(err) else float("inf")


def control_bf16(diag, vals, cols, x):
    """The reference in the precision below the configuration's float32:
    matrix and vector rounded to bfloat16, products in bfloat16, sums in
    float32.  On the default device, in blocks of rows."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(d, v, c, xb, xl):
        prod = v.astype(jnp.bfloat16) * xb[c]
        return (d.astype(jnp.bfloat16) * xl).astype(jnp.float32) + \
            prod.astype(jnp.float32).sum(axis=1)

    xb = jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
    out = []
    for lo, hi in _blocks(len(diag)):
        out.append(np.asarray(block(diag[lo:hi], vals[lo:hi], cols[lo:hi],
                                    xb, xb[lo:hi])))
    return np.concatenate(out)
