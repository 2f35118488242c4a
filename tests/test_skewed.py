"""Power-law-skewed pattern generator (repro.data.skewed) and the
eq.-11 BLOCKSIZE sweep it stresses (repro.comm.select.blocksize_sweep).

The uniform mesh-like generator flatters the blockwise model: every
block is roughly equally popular, so any blocksize looks fine.  The
zipf-hub generator concentrates remote traffic on a few columns, which
is where ``choose_blocksize`` has to actually earn its keep — and where
the sweep's curve stops being flat.
"""
import numpy as np
import pytest

from repro.comm.select import blocksize_sweep, choose_blocksize
from repro.comm.perfmodel import ABEL
from repro.comm.plan import Topology
from repro.data.skewed import (make_powerlaw_matrix, skew_summary,
                               zipf_column_weights)


def test_zipf_weights_normalized_and_skewed():
    w = zipf_column_weights(1024, alpha=1.1, seed=0)
    assert w.shape == (1024,)
    assert np.all(w > 0)
    np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-12)
    # top 1% of columns carries far more than 1% of the mass
    assert np.sort(w)[-10:].sum() > 0.1


def test_powerlaw_matrix_is_valid_ellpack():
    n, r_nz = 512, 8
    m = make_powerlaw_matrix(n, r_nz, alpha=1.1, seed=1)
    assert m.n == n
    assert m.cols.shape == (n, r_nz) and m.cols.dtype == np.int32
    assert m.cols.min() >= 0 and m.cols.max() < n
    assert m.vals.shape == (n, r_nz)
    assert np.all(np.isfinite(m.diag)) and np.all(np.isfinite(m.vals))
    # diagonal dominance (the CG-friendly construction)
    assert np.all(np.abs(m.diag) >= np.abs(m.vals).sum(axis=1))


def test_powerlaw_matrix_concentrates_traffic():
    n, r_nz, p = 2048, 8, 8
    skewed = make_powerlaw_matrix(n, r_nz, alpha=1.1, seed=2)
    flat = make_powerlaw_matrix(n, r_nz, alpha=0.0, seed=2)
    s, f = skew_summary(skewed.cols, n, p), skew_summary(flat.cols, n, p)
    assert set(s) == {"top1pct_frac", "shard_imbalance"}
    assert s["top1pct_frac"] > 3 * f["top1pct_frac"]
    assert s["shard_imbalance"] >= f["shard_imbalance"] * 0.9


def test_blocksize_sweep_and_argmin():
    n, r_nz, p = 1024, 8, 8
    m = make_powerlaw_matrix(n, r_nz, alpha=1.1, seed=3)
    topo = Topology(p, 4)
    sweep = blocksize_sweep(m.cols, n, p, topology=topo, hw=ABEL)
    assert len(sweep) >= 2
    bss = [bs for bs, _ in sweep]
    assert bss == sorted(bss)                 # candidate order kept
    assert all(n // p % bs == 0 for bs in bss)
    assert all(t > 0 for _, t in sweep)
    best = choose_blocksize(m.cols, n, p, topology=topo, hw=ABEL)
    assert best == min(sweep, key=lambda kv: kv[1])[0]


def test_blocksize_sweep_skew_changes_the_curve():
    # the skewed pattern's sweep must differ from the uniform one — the
    # hub columns change which blocks are needed remotely
    n, r_nz, p = 2048, 8, 8
    topo = Topology(p, 4)
    sk = make_powerlaw_matrix(n, r_nz, alpha=1.3, seed=4)
    un = make_powerlaw_matrix(n, r_nz, alpha=0.0, seed=4)
    t_sk = dict(blocksize_sweep(sk.cols, n, p, topology=topo, hw=ABEL))
    t_un = dict(blocksize_sweep(un.cols, n, p, topology=topo, hw=ABEL))
    assert t_sk.keys() == t_un.keys()
    assert any(abs(t_sk[bs] - t_un[bs]) / t_un[bs] > 0.05 for bs in t_sk)


def test_blocksize_sweep_respects_candidates():
    n, p = 512, 8
    m = make_powerlaw_matrix(n, 4, alpha=1.1, seed=5)
    sweep = blocksize_sweep(m.cols, n, p, topology=Topology(p, 4), hw=ABEL,
                            candidates=[16, 30, 64])
    assert [bs for bs, _ in sweep] == [16, 64]   # 30 doesn't divide 64
