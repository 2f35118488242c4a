"""DynamicPattern: device-derived executor tables vs the host ``CommPlan``.

The dynamic tier's whole contract is bit-identity: the in-jit derivation
(``repro.comm.dynamic``) must reproduce the host planner's tables exactly —
same sort order, same dump slots, same envelope padding — across routing
shapes, in BOTH directions.  Property-tested with hypothesis where the
extra is installed; a seeded grid sweep covers the same space otherwise
(the repo's degraded-import pattern).
"""
import numpy as np
import pytest

from repro.comm import dynamic as dyn
from repro.comm import plan_cache
from repro.comm.pattern import AccessPattern
from repro.comm.plan import build_comm_plan, derive_scatter_plan
from repro.models.moe import moe_dispatch_pattern, random_router

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # degraded: the seeded sweep below covers the grid
    HAVE_HYPOTHESIS = False


@pytest.fixture(autouse=True)
def isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path / "plans"))
    monkeypatch.delenv("REPRO_PLAN_CACHE", raising=False)
    plan_cache.clear_memory_cache()
    plan_cache.stats.reset()
    yield
    plan_cache.clear_memory_cache()


def _routing_cols(num_experts, capacity, k, seed, n_tok=128, p=4):
    """A realistic irregular index set: the MoE slot→token table."""
    top_e, _ = random_router(seed, n_tok, num_experts, k)
    idx, _ = moe_dispatch_pattern(top_e, n_tok, num_experts, capacity, p)
    return idx.reshape(-1, 1), n_tok, p


def _assert_tables_match(cols, n, p, s_max):
    """Both directions, all seven executor tables, bit-exact."""
    plan = build_comm_plan(cols, n, p, s_max=s_max)
    assert plan.s_max == s_max
    g = dyn.derive_gather_tables(cols, n, p, s_max)
    np.testing.assert_array_equal(np.asarray(g.send_local_idx),
                                  plan.send_local_idx)
    np.testing.assert_array_equal(np.asarray(g.recv_global_idx),
                                  plan.recv_global_idx)
    np.testing.assert_array_equal(np.asarray(g.send_counts),
                                  plan.send_counts)
    splan = derive_scatter_plan(plan)
    s = dyn.derive_scatter_tables(cols, n, p, s_max, gather=g)
    np.testing.assert_array_equal(np.asarray(s.cond_msg_idx),
                                  splan.cond_msg_idx)
    np.testing.assert_array_equal(np.asarray(s.own_tgt_idx),
                                  splan.own_tgt_idx)
    np.testing.assert_array_equal(np.asarray(s.win_mask), splan.win_mask)
    np.testing.assert_array_equal(np.asarray(s.touched), splan.touched)


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(num_experts=st.sampled_from([8, 16, 32]),
           capacity=st.sampled_from([4, 8, 16]),
           k=st.integers(1, 4),
           seed=st.integers(0, 2 ** 16),
           widen=st.integers(0, 3))
    def test_dynamic_tables_bit_identical(num_experts, capacity, k, seed,
                                          widen):
        cols, n, p = _routing_cols(num_experts, capacity, k, seed)
        s_max = dyn.envelope_s_max(cols.shape[0], 1, n, p)
        _assert_tables_match(cols, n, p, s_max + widen)

else:

    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("num_experts,capacity,k",
                             [(e, c, k) for e in (8, 16, 32)
                              for c in (4, 16) for k in (1, 2, 4)])
    def test_dynamic_tables_bit_identical(num_experts, capacity, k, seed):
        cols, n, p = _routing_cols(num_experts, capacity, k, seed)
        s_max = dyn.envelope_s_max(cols.shape[0], 1, n, p)
        _assert_tables_match(cols, n, p, s_max + seed)


def test_envelope_padding_is_widening_only():
    """The natural s_max, the envelope bound, and anything wider all give
    bit-identical tables (extra slots are pure dump padding); narrowing
    below the natural maximum is refused by the host build."""
    cols, n, p = _routing_cols(8, 8, 2, 0)
    natural = build_comm_plan(cols, n, p).s_max
    env = dyn.envelope_s_max(cols.shape[0], 1, n, p)
    assert natural <= env
    for s_max in (natural, env, env + 5):
        _assert_tables_match(cols, n, p, s_max)
    if natural > 1:
        with pytest.raises(AssertionError, match="widening-only"):
            build_comm_plan(cols, n, p, s_max=natural - 1)


def test_multi_r_patterns_match():
    """r > 1 rows (SpMV-like) derive identically too — the tier is not
    MoE-specific."""
    rng = np.random.default_rng(5)
    n, p = 256, 4
    for r in (2, 3):
        cols = rng.integers(0, n, size=(64, r)).astype(np.int32)
        s_max = dyn.envelope_s_max(64, r, n, p)
        _assert_tables_match(cols, n, p, s_max)


# ---------------------------------------------------------------------------
# Front-door surface: the DynamicPattern duck-type through the real doors
# ---------------------------------------------------------------------------


def _mesh():
    import jax
    ndev = len(jax.devices())
    return jax.make_mesh((ndev,), ("data",)), ndev


def _dyn_case(p, seed=0, rows_per_shard=32, r=2, shard=64):
    rng = np.random.default_rng(seed)
    n = shard * p
    cols = rng.integers(0, n, size=(rows_per_shard * p, r)).astype(np.int32)
    template = AccessPattern.from_indices(cols, n=n)
    return template, dyn.DynamicPattern.from_template(template, p), n


def test_front_doors_accept_dynamic_pattern():
    """Gather and scatter take a DynamicPattern wherever they take an
    AccessPattern; auto restricts candidates to the dynamic rungs; results
    match a statically host-planned exchange of the same pattern."""
    from repro.comm.gather import IrregularGather
    from repro.comm.scatter import IrregularScatter
    from repro.comm import perfmodel as pm

    mesh, p = _mesh()
    template, dp, n = _dyn_case(p)
    rng = np.random.default_rng(1)

    gather = IrregularGather(dp, mesh, strategy="auto", hw=pm.ABEL)
    assert gather.strategy in dyn.DYNAMIC_STRATEGIES
    assert set(gather.predicted_times) == set(dyn.DYNAMIC_STRATEGIES)
    static_g = IrregularGather(template, mesh, strategy=gather.strategy,
                               hw=pm.ABEL)
    x = rng.standard_normal(n).astype(np.float32)
    # compare the n real entries only: the trailing dump slot collects
    # padded sends and legitimately differs between the natural-s_max
    # static plan and the envelope-s_max dynamic one
    np.testing.assert_array_equal(
        np.asarray(gather(gather.shard_vector(x)))[:, :n],
        np.asarray(static_g(static_g.shard_vector(x)))[:, :n])

    scatter = IrregularScatter(dp, mesh, strategy="auto", reduce="add",
                               hw=pm.ABEL)
    assert scatter.strategy in dyn.DYNAMIC_STRATEGIES
    static_s = IrregularScatter(template, mesh, strategy=scatter.strategy,
                                reduce="add", hw=pm.ABEL)
    vals = rng.standard_normal(template.indices.shape).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(scatter(scatter.shard_values(vals))),
        np.asarray(static_s(static_s.shard_values(vals))))


def test_dynamic_pattern_rejects_underivable_configs():
    """Rungs outside DYNAMIC_STRATEGIES, auto candidates naming them, and
    host-precomputed Destination descriptors are all refused loudly."""
    from repro.comm.gather import IrregularGather
    from repro.comm.pattern import Destination
    from repro.comm.scatter import IrregularScatter
    from repro.comm import perfmodel as pm

    mesh, p = _mesh()
    _, dp, n = _dyn_case(p)
    with pytest.raises(ValueError, match="DynamicPattern"):
        IrregularGather(dp, mesh, strategy="blockwise", hw=pm.ABEL)
    with pytest.raises(ValueError, match="DynamicPattern"):
        IrregularScatter(dp, mesh, strategy="replicate", reduce="add",
                         hw=pm.ABEL)
    with pytest.raises(ValueError, match="candidates"):
        IrregularGather(dp, mesh, strategy="auto",
                        candidates=("blockwise", "condensed"), hw=pm.ABEL)
    slots = np.zeros((p, 4), np.int64)
    with pytest.raises(ValueError, match="Destination"):
        IrregularGather(dp, mesh, strategy="condensed",
                        destination=Destination.from_slots(s=slots),
                        hw=pm.ABEL)


def test_derive_plan_args_guard_rails():
    """derive_plan_args serves only the dynamic rungs."""
    from repro.comm.gather import IrregularGather
    from repro.comm import perfmodel as pm

    mesh, p = _mesh()
    template, dp, n = _dyn_case(p)
    g = IrregularGather(template, mesh, strategy="blockwise", hw=pm.ABEL)
    with pytest.raises(ValueError, match="derive_plan_args"):
        g.derive_plan_args(template.indices)


def test_envelope_s_max_bounds():
    """The envelope is the tight worst case: no per-(reader, owner) pair
    can need more slots than its shard holds or than the reader reads."""
    assert dyn.envelope_s_max(64, 1, 1024, 8) == 8        # rows bound
    assert dyn.envelope_s_max(4096, 2, 64, 8) == 8        # shard bound
    assert dyn.envelope_s_max(8, 1, 8, 8) == 1            # floor
    cols, n, p = _routing_cols(16, 8, 2, 3)
    natural = build_comm_plan(cols, n, p).s_max
    assert natural <= dyn.envelope_s_max(cols.shape[0], 1, n, p)


def test_dynamic_moe_layer_matches_static_layer():
    """The proving consumer: one routed step through DynamicMoELayer ==
    the statically host-planned MoELayer for the same routing."""
    import jax
    from repro.comm import perfmodel as pm
    from repro.models.moe import DynamicMoELayer, MoELayer

    mesh, p = _mesh()
    n_tok, d, f, k, e_total, cap = 128, 4, 8, 2, 8, 16
    rng = np.random.default_rng(0)
    params = {
        "w1": (rng.standard_normal((e_total, d, f)) * 0.1).astype(np.float32),
        "w2": (rng.standard_normal((e_total, f, d)) * 0.1).astype(np.float32),
    }
    te, tw = random_router(1, n_tok, e_total, k)
    x_host = rng.standard_normal((n_tok, d)).astype(np.float32)

    layer = DynamicMoELayer(params, te, n_tok, e_total, cap, mesh,
                            strategy="auto", hw=pm.ABEL)
    y_dyn = np.asarray(layer(layer.shard_tokens(x_host), te, tw))
    base = MoELayer(params, te, tw, n_tok, e_total, cap, mesh,
                    strategy="condensed", hw=pm.ABEL)
    y_ref = np.asarray(base(base.shard_tokens(x_host)))
    np.testing.assert_allclose(y_dyn, y_ref, rtol=2e-5, atol=2e-5)
    # a second, different routing through the SAME layer still matches
    te2, tw2 = random_router(2, n_tok, e_total, k)
    y_dyn2 = np.asarray(layer(layer.shard_tokens(x_host), te2, tw2))
    base2 = MoELayer(params, te2, tw2, n_tok, e_total, cap, mesh,
                     strategy="condensed", hw=pm.ABEL)
    y_ref2 = np.asarray(base2(base2.shard_tokens(x_host)))
    np.testing.assert_allclose(y_dyn2, y_ref2, rtol=2e-5, atol=2e-5)
