"""The push-direction front door: ``IrregularScatter`` / ``ScatterHandle``
over transpose-derived plans, plus the two scatter consumers.

Every rung is checked bit-identically against the NumPy ground truth.
Contributions are integer-valued floats (and combine weights powers of
two), so every float sum is exact and bit-identical regardless of the
accumulation order each rung/backend picks — the duplicate handling itself,
not float associativity, is what is under test.  Runs on whatever devices
the pytest process has (1 locally, 8 under the CI gate's XLA_FLAGS).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (AccessPattern, IrregularScatter, STRATEGIES,
                        plan_cache)
from repro.comm import perfmodel as pm


def _mesh():
    ndev = len(jax.devices())
    return jax.make_mesh((ndev,), ("data",)), ndev


def _case(n, m, r, seed=0, lo=-4, hi=5):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n, size=(m, r)).astype(np.int32)
    vals = rng.integers(lo, hi, size=(m, r)).astype(np.float32)
    return AccessPattern.from_indices(idx, n=n), idx, vals


def _ref(idx, vals, n, reduce):
    feat = vals.shape[2:]
    if reduce == "add":
        y = np.zeros((n,) + feat, vals.dtype)
        np.add.at(y, idx.ravel(), vals.reshape((-1,) + feat))
        return y
    if reduce == "max":
        y = np.full((n,) + feat, -np.inf, vals.dtype)
        np.maximum.at(y, idx.ravel(), vals.reshape((-1,) + feat))
        return np.where(np.isneginf(y), 0.0, y).astype(vals.dtype)
    y = np.zeros((n,) + feat, vals.dtype)   # "set": last writer wins
    for i, v in zip(idx.ravel(), vals.reshape((-1,) + feat)):
        y[i] = v
    return y


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("reduce", ("add", "set", "max"))
def test_scatter_matches_numpy_reference(strategy, reduce):
    """All four rungs, all three reduce semantics, duplicate targets
    included (r random draws per row collide constantly)."""
    mesh, ndev = _mesh()
    n = 64 * ndev
    pattern, idx, vals = _case(n, n, 5)
    s = IrregularScatter(pattern, mesh, strategy=strategy, blocksize=16,
                         reduce=reduce)
    y = np.asarray(s(s.shard_values(vals)))
    np.testing.assert_array_equal(y, _ref(idx, vals, n, reduce))


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_scatter_with_feature_dims(strategy):
    mesh, ndev = _mesh()
    n, d = 32 * ndev, 7
    rng = np.random.default_rng(1)
    idx = rng.integers(0, n, size=(n, 3)).astype(np.int32)
    vals = rng.integers(-3, 4, size=(n, 3, d)).astype(np.float32)
    pattern = AccessPattern.from_indices(idx, n=n)
    s = IrregularScatter(pattern, mesh, strategy=strategy, blocksize=8)
    y = np.asarray(s(s.shard_values(vals)))
    np.testing.assert_array_equal(y, _ref(idx, vals, n, "add"))


def test_scatter_m_not_equal_n():
    """Accessor count decoupled from vector length (the MoE-combine
    shape: expert-capacity slots push into the token vector)."""
    mesh, ndev = _mesh()
    n, m = 64 * ndev, 16 * ndev
    pattern, idx, vals = _case(n, m, 2, seed=2)
    for strategy in STRATEGIES:
        s = IrregularScatter(pattern, mesh, strategy=strategy, blocksize=16)
        assert s.plan.m == m and s.splan.m == m
        y = np.asarray(s(s.shard_values(vals)))
        np.testing.assert_array_equal(y, _ref(idx, vals, n, "add"))


def test_scatter_handle_overlap_protocol():
    """start_local issues the exchange; finish combines own + landed —
    composable inside a consumer's own shard_map."""
    from jax.sharding import PartitionSpec as P

    mesh, ndev = _mesh()
    n = 32 * ndev
    pattern, idx, vals = _case(n, n, 3, seed=3)
    s = IrregularScatter(pattern, mesh, strategy="overlap", blocksize=8)

    def step(vals_local, *args):
        h = s.start_local(vals_local, *args)
        own_window = vals_local.sum() * 0.0  # any x_local-only compute
        return h.finish() + own_window

    f = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P("data"),) + s.in_specs,
        out_specs=P("data"), check_vma=False))
    y = np.asarray(f(s.shard_values(vals), *s.plan_args))
    np.testing.assert_array_equal(y, _ref(idx, vals, n, "add"))


def test_transpose_round_trips():
    """transpose() is an involution onto the shared base plan, and the
    derived tables are exactly reconstructible from the plan alone."""
    from repro.comm.plan import build_comm_plan, pattern_cols

    n, p, r = 256, 4, 5
    pattern, idx, _ = _case(n, n, r, seed=4)
    plan = build_comm_plan(idx, n, p, blocksize=16)
    splan = plan.transpose()
    assert splan.transpose() is plan
    np.testing.assert_array_equal(pattern_cols(plan), idx)
    np.testing.assert_array_equal(splan.tgt_global, idx)
    # put-direction counts: outgoing <-> incoming volumes swap
    np.testing.assert_array_equal(
        splan.counts.s_local_out + splan.counts.s_remote_out,
        plan.counts.s_local_in + plan.counts.s_remote_in)
    np.testing.assert_array_equal(
        splan.counts.s_local_in + splan.counts.s_remote_in,
        plan.counts.s_local_out + plan.counts.s_remote_out)


def test_auto_strategy_uses_put_models():
    mesh, ndev = _mesh()
    n = 64 * ndev
    pattern, idx, vals = _case(n, n, 4, seed=5)
    s = IrregularScatter(pattern, mesh, strategy="auto", blocksize=16,
                         hw=pm.ABEL)
    assert s.requested_strategy == "auto"
    assert s.strategy in STRATEGIES
    assert set(s.predicted_times) == set(STRATEGIES)
    # the resolved pick is the put-model argmin (acceptance criterion)
    assert s.strategy == min(s.predicted_times, key=s.predicted_times.get)
    # and it matches an explicit put-direction ranking of the same plan
    from repro.comm import select
    ranked = select.rank_strategies(s.splan, pattern.r, pm.ABEL,
                                    direction="put")
    assert s.strategy == ranked[0][0]
    y = np.asarray(s(s.shard_values(vals)))
    np.testing.assert_array_equal(y, _ref(idx, vals, n, "add"))


def test_scatter_invalid_args_rejected():
    mesh, ndev = _mesh()
    pattern, _, _ = _case(16 * ndev, 16 * ndev, 2, seed=6)
    with pytest.raises(ValueError, match="reduce"):
        IrregularScatter(pattern, mesh, reduce="mean")
    with pytest.raises(ValueError, match="strategy"):
        IrregularScatter(pattern, mesh, strategy="bogus")


def test_hw_measurement_memoized_per_mesh(monkeypatch):
    """Constructing several exchanges on one mesh must run the §5.4
    microbenchmark at most once (module-level memo in comm.exchange)."""
    from repro.comm import exchange

    calls = []

    def fake_measure(mesh, axis_name, elem_bytes):
        calls.append((axis_name,))
        return pm.ABEL

    monkeypatch.setattr(exchange, "_probe", fake_measure)
    exchange.clear_hw_memo()
    mesh, ndev = _mesh()
    n = 16 * ndev
    pattern, idx, vals = _case(n, n, 2, seed=7)
    g1 = IrregularScatter(pattern, mesh, strategy="auto", blocksize=8)
    from repro.comm import IrregularGather
    g2 = IrregularGather(pattern, mesh, strategy="auto", blocksize=8)
    g3 = IrregularScatter(pattern, mesh, strategy="auto", blocksize=8)
    assert len(calls) == 1, calls
    exchange.clear_hw_memo()
    assert g1.hw is g2.hw is g3.hw


def test_moe_combine_matches_reference_all_rungs():
    from repro.models.moe import (MoECombineScatter, moe_combine_ref,
                                  moe_combine_weights, moe_dispatch_pattern)

    mesh, ndev = _mesh()
    n_tok, k, d = 64 * ndev, 2, 6
    e_total, cap = 2 * ndev, 12
    rng = np.random.default_rng(8)
    top_e = rng.integers(0, e_total, size=(n_tok, k))
    # power-of-two weights keep every product/sum exact in float32
    top_w = np.where(rng.random((n_tok, k)) < 0.5, 0.5, 0.25).astype(
        np.float32)
    buf = rng.integers(-3, 4, (e_total, cap, d)).astype(np.float32)
    idx, valid = moe_dispatch_pattern(top_e, n_tok, e_total, cap, ndev)
    w_slot = moe_combine_weights(top_e, top_w, n_tok, e_total, cap)
    ref = moe_combine_ref(buf, idx, valid, w_slot, n_tok)
    for strategy in STRATEGIES + ("auto",):
        g = MoECombineScatter(top_e, top_w, n_tok, e_total, cap, mesh,
                              strategy=strategy, blocksize=16, hw=pm.ABEL)
        y = np.asarray(g(g.shard_expert_buf(buf)))
        np.testing.assert_array_equal(y, ref)


def test_moe_dispatch_combine_round_trip():
    """Dispatch → (identity experts) → combine equals the local-only
    combine_one reference: each token recovers the weighted sum of its
    kept expert copies."""
    from repro.models.moe import (MoECombineScatter, MoEDispatchGather,
                                  moe_combine_ref, moe_combine_weights,
                                  moe_dispatch_pattern)

    mesh, ndev = _mesh()
    n_tok, k, d = 32 * ndev, 2, 4
    e_total, cap = 2 * ndev, 8
    rng = np.random.default_rng(9)
    top_e = rng.integers(0, e_total, size=(n_tok, k))
    top_w = np.where(rng.random((n_tok, k)) < 0.5, 0.5, 0.25).astype(
        np.float32)
    x = rng.integers(-3, 4, (n_tok, d)).astype(np.float32)

    disp = MoEDispatchGather(top_e, n_tok, e_total, cap, mesh,
                             strategy="condensed", blocksize=8)
    comb = MoECombineScatter(top_e, top_w, n_tok, e_total, cap, mesh,
                             strategy="condensed", blocksize=8)
    ebuf = np.asarray(disp(disp.shard_tokens(x)))
    y = np.asarray(comb(comb.shard_expert_buf(ebuf)))

    idx, valid = moe_dispatch_pattern(top_e, n_tok, e_total, cap, ndev)
    w_slot = moe_combine_weights(top_e, top_w, n_tok, e_total, cap)
    np.testing.assert_array_equal(
        y, moe_combine_ref(ebuf, idx, valid, w_slot, n_tok))


def test_spmv_transpose_matches_reference_all_rungs():
    from repro.core.matrix import (EllpackMatrix, make_mesh_like_matrix,
                                   spmv_t_ref_np)
    from repro.core.spmv import DistributedSpMV

    mesh, ndev = _mesh()
    n = 64 * ndev
    m0 = make_mesh_like_matrix(n, 4, locality_window=n // 8,
                               long_range_frac=0.1, seed=10)
    rng = np.random.default_rng(10)
    m = EllpackMatrix(
        n=n, r_nz=m0.r_nz,
        diag=rng.integers(-3, 4, n).astype(np.float32),
        vals=rng.integers(-3, 4, (n, m0.r_nz)).astype(np.float32),
        cols=m0.cols)
    x = rng.integers(-3, 4, n).astype(np.float32)
    ref = spmv_t_ref_np(m, x)
    for strategy in STRATEGIES + ("auto",):
        eng = DistributedSpMV(m, mesh, strategy=strategy, blocksize=16,
                              transpose=True, hw=pm.ABEL)
        assert eng.transpose and eng.gather is None
        y = np.asarray(eng(eng.shard_vector(x)))
        np.testing.assert_array_equal(y, ref)


def test_spmv_forward_and_transpose_share_base_plan(tmp_path, monkeypatch):
    """The transpose is a cached O(m*r) delta of the forward plan: one
    O(nnz) preparation step covers both directions."""
    from repro.core.matrix import make_mesh_like_matrix
    from repro.core.spmv import DistributedSpMV

    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path / "plans"))
    plan_cache.clear_memory_cache()
    plan_cache.stats.reset()
    mesh, ndev = _mesh()
    n = 64 * ndev
    m = make_mesh_like_matrix(n, 4, locality_window=n // 8,
                              long_range_frac=0.1, seed=11)
    fwd = DistributedSpMV(m, mesh, strategy="condensed", blocksize=16,
                          materialize="full")
    t = DistributedSpMV(m, mesh, strategy="condensed", blocksize=16,
                        transpose=True)
    assert plan_cache.stats.misses == 1      # one O(nnz) build total
    assert plan_cache.stats.derives == 1     # one O(m*r) transpose delta
    assert t.splan.transpose() is t.plan

    # the transposed engine's counts are the put-direction volumes
    np.testing.assert_array_equal(
        t.counts.s_local_out + t.counts.s_remote_out,
        fwd.counts.s_local_in + fwd.counts.s_remote_in)
