"""The workload-agnostic ``repro.comm`` front door: ``AccessPattern`` /
``SharedVector`` / ``IrregularGather`` / ``OverlapHandle``.

Every gather is checked against the NumPy ground truth (x_copy must equal x
at every index the pattern's shard accesses), for every ladder rung, for
m != n accessor patterns, and for vectors with trailing feature dims.  Runs
on whatever devices the pytest process has (1 locally, 8 under the CI
gate's XLA_FLAGS).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (AccessPattern, IrregularGather, SharedVector,
                        STRATEGIES, Topology, select)
from repro.comm import perfmodel as pm


def _mesh():
    ndev = len(jax.devices())
    return jax.make_mesh((ndev,), ("data",)), ndev


def _check_gather(g, pattern, x, ndev):
    """Every index accessed by shard q's pattern rows must be delivered."""
    xc = np.asarray(g(g.shard_vector(x)))
    rows = pattern.m // ndev
    for q in range(ndev):
        needed = np.unique(pattern.indices[q * rows:(q + 1) * rows])
        np.testing.assert_array_equal(xc[q][needed], np.asarray(x)[needed])


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_gather_matches_numpy_reference(strategy):
    mesh, ndev = _mesh()
    n = 64 * ndev
    rng = np.random.default_rng(0)
    idx = rng.integers(0, n, size=(n, 5)).astype(np.int32)
    pattern = AccessPattern.from_indices(idx, n=n)
    g = IrregularGather(pattern, mesh, strategy=strategy, blocksize=16)
    x = rng.standard_normal(n).astype(np.float32)
    _check_gather(g, pattern, x, ndev)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_gather_with_feature_dims(strategy):
    mesh, ndev = _mesh()
    n, d = 32 * ndev, 7
    rng = np.random.default_rng(1)
    idx = rng.integers(0, n, size=(n, 3)).astype(np.int32)
    pattern = AccessPattern.from_indices(idx, n=n)
    g = IrregularGather(pattern, mesh, strategy=strategy, blocksize=8)
    x = rng.standard_normal((n, d)).astype(np.float32)
    _check_gather(g, pattern, x, ndev)


def test_gather_m_not_equal_n():
    """Accessor count decoupled from vector length (the MoE-dispatch shape)."""
    mesh, ndev = _mesh()
    n, m = 64 * ndev, 16 * ndev
    rng = np.random.default_rng(2)
    idx = rng.integers(0, n, size=(m, 2)).astype(np.int32)
    pattern = AccessPattern.from_indices(idx, n=n)
    assert pattern.m == m and pattern.n == n
    for strategy in STRATEGIES:
        g = IrregularGather(pattern, mesh, strategy=strategy, blocksize=16)
        assert g.plan.m == m and g.plan.rows_per_shard == m // ndev
        x = rng.standard_normal(n).astype(np.float32)
        _check_gather(g, pattern, x, ndev)


def test_auto_strategy_resolves_and_delivers():
    mesh, ndev = _mesh()
    n = 64 * ndev
    rng = np.random.default_rng(3)
    idx = rng.integers(0, n, size=(n, 4)).astype(np.int32)
    pattern = AccessPattern.from_indices(idx, n=n)
    g = IrregularGather(pattern, mesh, strategy="auto", blocksize=16,
                        hw=pm.ABEL)
    assert g.requested_strategy == "auto"
    assert g.strategy in STRATEGIES
    assert set(g.predicted_times) == set(STRATEGIES)
    x = rng.standard_normal(n).astype(np.float32)
    _check_gather(g, pattern, x, ndev)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_overlap_handle_zero_slots(strategy):
    """finish(extra_slots=k) must guarantee x_copy[n+1 .. n+k] == 0 for
    every strategy — consumers point their padding indices there."""
    mesh, ndev = _mesh()
    n = 32 * ndev
    rng = np.random.default_rng(4)
    idx = rng.integers(0, n, size=(n, 3)).astype(np.int32)
    pattern = AccessPattern.from_indices(idx, n=n)
    g = IrregularGather(pattern, mesh, strategy=strategy, blocksize=8)
    from jax.sharding import PartitionSpec as P

    def local(x_local, *args):
        h = g.start_local(x_local, *args)
        return h.finish(extra_slots=2)[None]

    f = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("data"),) + g.in_specs,
        out_specs=P("data"), check_vma=False))
    x = rng.standard_normal(n).astype(np.float32) + 10.0  # no accidental 0s
    xc = np.asarray(f(g.shard_vector(x), *g.plan_args))
    rows = pattern.m // ndev
    for q in range(ndev):
        assert xc[q].shape[0] >= n + 3
        np.testing.assert_array_equal(xc[q][n + 1:n + 3], 0.0)
        needed = np.unique(pattern.indices[q * rows:(q + 1) * rows])
        np.testing.assert_array_equal(xc[q][needed], x[needed])


def test_shared_vector_ownership():
    mesh, ndev = _mesh()
    sv = SharedVector(mesh, n=16 * ndev)
    assert sv.p == ndev and sv.shard_size == 16
    assert sv.owner_of(0) == 0
    assert sv.owner_of(16 * ndev - 1) == ndev - 1
    x = np.arange(16 * ndev, dtype=np.float32)
    xs = sv.put(x)
    np.testing.assert_array_equal(np.asarray(xs), x)
    # IrregularGather accepts the SharedVector as the placement spec
    idx = np.arange(16 * ndev, dtype=np.int32)[:, None]
    g = IrregularGather(AccessPattern.from_indices(idx, n=sv.n), sv,
                        strategy="condensed")
    _check_gather(g, g.pattern, x, ndev)


def test_pattern_validation():
    with pytest.raises(AssertionError):
        AccessPattern.from_indices(np.array([[0, 5]]), n=4)  # out of range
    pat = AccessPattern.from_indices(np.array([3, 1, 2, 0]))  # 1-D ok
    assert pat.indices.shape == (4, 1) and pat.n == 4


def test_choose_blocksize_minimizes_eq11():
    from repro.comm.plan import blockwise_block_counts
    from repro.core.matrix import make_mesh_like_matrix

    n, p = 1 << 12, 8
    topo = Topology(p, 4)
    m = make_mesh_like_matrix(n, 8, locality_window=n // 16,
                              long_range_frac=0.05, seed=7)
    bs = select.choose_blocksize(m.cols, n, p, topology=topo, hw=pm.ABEL)
    shard = n // p
    assert shard % bs == 0
    # exhaustively verify the sweep's argmin against direct eq.-11 evals
    preds = {}
    for cand in select.blocksize_candidates(shard):
        bl, br = blockwise_block_counts(m.cols, n, p, cand, topo)
        zeros = np.zeros(p, np.int64)
        counts = pm.GatherCounts(
            c_local_indv=zeros, c_remote_indv=zeros, b_local=bl, b_remote=br,
            blocksize=cand, s_local_out=zeros, s_remote_out=zeros,
            s_local_in=zeros, s_remote_in=zeros, c_remote_out=zeros,
            padded_condensed_per_shard=0, padded_blockwise_per_shard=0)
        w = pm.SpmvWorkload(n=n, r_nz=8, p=p, blocksize=cand, topology=topo,
                            counts=counts)
        preds[cand] = pm.predict_v2(w, pm.ABEL)
    assert bs == min(preds, key=preds.get)


def test_blocksize_auto_on_engine():
    from repro.core.matrix import make_mesh_like_matrix, spmv_ref_np
    from repro.core.spmv import DistributedSpMV

    mesh, ndev = _mesh()
    n = 128 * ndev
    m = make_mesh_like_matrix(n, 4, locality_window=n // 8,
                              long_range_frac=0.1, seed=8)
    eng = DistributedSpMV(m, mesh, strategy="blockwise", blocksize="auto",
                          hw=pm.ABEL)
    assert (n // ndev) % eng.blocksize == 0
    x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
    np.testing.assert_allclose(np.asarray(eng(eng.shard_vector(x))),
                               spmv_ref_np(m, x), rtol=2e-4, atol=2e-4)


def test_moe_dispatch_matches_reference_all_rungs():
    from repro.models.moe import (MoEDispatchGather, moe_dispatch_pattern,
                                  moe_dispatch_ref)

    mesh, ndev = _mesh()
    n_tok, k, d = 64 * ndev, 2, 6
    e_total, cap = 2 * ndev, 12
    rng = np.random.default_rng(5)
    top_e = rng.integers(0, e_total, size=(n_tok, k))
    x = rng.standard_normal((n_tok, d)).astype(np.float32)
    idx, valid = moe_dispatch_pattern(top_e, n_tok, e_total, cap, ndev)
    ref = moe_dispatch_ref(x, idx, valid, e_total, cap)
    for strategy in STRATEGIES + ("auto",):
        g = MoEDispatchGather(top_e, n_tok, e_total, cap, mesh,
                              strategy=strategy, blocksize=16, hw=pm.ABEL)
        buf = np.asarray(g(g.shard_tokens(x)))
        np.testing.assert_array_equal(buf, ref)


def test_moe_dispatch_pattern_capacity_truncation():
    from repro.models.moe import moe_dispatch_pattern

    # all tokens route to expert 0 -> capacity keeps the first C tokens
    top_e = np.zeros((16, 1), np.int64)
    idx, valid = moe_dispatch_pattern(top_e, 16, 2, 4, p=1)
    idx = idx.reshape(2, 4)
    valid = valid.reshape(2, 4)
    np.testing.assert_array_equal(idx[0], [0, 1, 2, 3])
    assert valid[0].all() and not valid[1].any()
