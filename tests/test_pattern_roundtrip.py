"""Round-trip properties of the AccessPattern constructors and the plan
transpose involution.

The planner's whole edifice rests on two losslessness claims:

* every constructor (``from_indices`` / ``from_ellpack`` /
  ``from_stencil5``) captures EXACTLY the index set it was given —
  promotion, n-inference and padding included — and a built plan can
  reconstruct that set bit-for-bit (``pattern_cols``);
* ``CommPlan.transpose()`` is an involution: the push-direction plan's
  ``transpose()`` returns the original gather plan *object*, so the two
  directions can never drift apart.

Property-tested with hypothesis where the extra is installed; a seeded
grid sweep covers the same space otherwise (the repo's degraded-import
pattern).  Shapes deliberately include duplicate targets inside one row
and m != n accessor sets — the historical corner cases.
"""
import itertools

import numpy as np
import pytest

from repro.comm.pattern import AccessPattern
from repro.comm.plan import build_comm_plan, pattern_cols
from repro.core.matrix import make_mesh_like_matrix

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # degraded: the seeded sweep below covers the grid
    HAVE_HYPOTHESIS = False


def _random_cols(n, m, r, seed, dup):
    rng = np.random.default_rng(seed)
    cols = rng.integers(0, n, size=(m, r))
    if dup and r > 1:
        cols[:, -1] = cols[:, 0]   # duplicate target inside one row
    return cols


# --------------------------------------------------------------------------
# from_indices: promotion, inference, exact capture
# --------------------------------------------------------------------------

def _check_from_indices(n, m, r, seed, dup):
    cols = _random_cols(n, m, r, seed, dup)
    pat = AccessPattern.from_indices(cols, n=n)
    assert (pat.m, pat.r, pat.n) == (m, r, n)
    assert pat.indices.dtype == np.int32
    np.testing.assert_array_equal(pat.indices, cols)
    # inferred n is exactly max+1, never more
    inferred = AccessPattern.from_indices(cols)
    assert inferred.n == int(cols.max()) + 1


def test_from_indices_1d_promotion():
    pat = AccessPattern.from_indices(np.array([3, 0, 2]))
    assert pat.indices.shape == (3, 1)       # (m,) promotes to (m, 1)
    assert (pat.m, pat.r, pat.n) == (3, 1, 4)
    np.testing.assert_array_equal(pat.indices[:, 0], [3, 0, 2])


def test_from_indices_rejects_out_of_bounds():
    with pytest.raises(AssertionError):
        AccessPattern.from_indices(np.array([[0, 5]]), n=4)
    with pytest.raises(AssertionError):
        AccessPattern.from_indices(np.array([[-1, 0]]), n=4)


def test_from_ellpack_equals_from_indices():
    m = make_mesh_like_matrix(64, 4, locality_window=16, seed=0)
    a = AccessPattern.from_ellpack(m)
    b = AccessPattern.from_indices(m.cols, n=m.n)
    assert a.n == b.n == m.n
    np.testing.assert_array_equal(a.indices, b.indices)


# --------------------------------------------------------------------------
# from_stencil5: shape, bounds, boundary padding, edge symmetry
# --------------------------------------------------------------------------

def _check_stencil5(big_m, big_n, mprocs, nprocs):
    pat = AccessPattern.from_stencil5(big_m, big_n, mprocs, nprocs)
    n = big_m * big_n
    assert (pat.m, pat.r, pat.n) == (n, 4, n)
    idx = pat.indices
    assert idx.min() >= 0 and idx.max() < n
    # row g is the accessor of element g, so own-id padding shows up as
    # idx[g, s] == g; exactly one pad per out-of-domain neighbor
    pads = int((idx == np.arange(n)[:, None]).sum())
    assert pads == 2 * big_m + 2 * big_n
    # the 5-point neighborhood is symmetric: every real edge a->b has b->a
    a = np.repeat(np.arange(n), 4)
    b = idx.ravel()
    real = a != b
    edges = set(zip(a[real].tolist(), b[real].tolist()))
    assert all((y, x) in edges for x, y in edges)


STENCILS = [(4, 4, 2, 2), (4, 8, 2, 2), (8, 4, 2, 4), (6, 6, 3, 2),
            (8, 8, 1, 4), (4, 12, 2, 6)]


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.sampled_from(STENCILS))
    def test_stencil5_properties(case):
        _check_stencil5(*case)
else:
    @pytest.mark.parametrize("case", STENCILS)
    def test_stencil5_properties(case):
        _check_stencil5(*case)


@pytest.mark.parametrize("case", STENCILS + [(2, 6, 2, 2), (6, 2, 3, 1)])
def test_stencil5_edge_only_plans_the_same_exchange(case):
    """The tile edge rings carry every foreign access: their plan moves
    the same per-pair messages, blocks and foreign counts as the plan of
    every cell."""
    big_m, big_n, mprocs, nprocs = case
    full = AccessPattern.from_stencil5(big_m, big_n, mprocs, nprocs)
    edge = AccessPattern.from_stencil5(big_m, big_n, mprocs, nprocs,
                                       edge_only=True)
    p, n = mprocs * nprocs, big_m * big_n
    m_loc, n_loc = big_m // mprocs, big_n // nprocs
    ring = m_loc * n_loc - max(m_loc - 2, 0) * max(n_loc - 2, 0)
    assert (edge.m, edge.r, edge.n) == (p * ring, 4, n)
    # every edge row is one of the full pattern's rows
    full_rows = {tuple(r) for r in full.indices.tolist()}
    assert all(tuple(r) in full_rows for r in edge.indices.tolist())
    a = build_comm_plan(full.indices, n, p, blocksize=1)
    b = build_comm_plan(edge.indices, n, p, blocksize=1)
    for name in ("send_counts", "send_local_idx", "recv_global_idx",
                 "send_block_counts", "send_local_blk", "recv_global_blk"):
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name),
                                      err_msg=name)
    for name in ("c_local_indv", "c_remote_indv", "b_local", "b_remote",
                 "s_local_out", "s_remote_out", "s_local_in", "s_remote_in",
                 "c_remote_out"):
        np.testing.assert_array_equal(getattr(a.counts, name),
                                      getattr(b.counts, name), err_msg=name)


# --------------------------------------------------------------------------
# CommPlan: lossless cols reconstruction + transpose involution
# --------------------------------------------------------------------------

def _check_plan_roundtrip(p, shard, rows, r, seed, dup):
    n, m = p * shard, p * rows
    cols = _random_cols(n, m, r, seed, dup)
    plan = build_comm_plan(cols, n, p)
    assert (plan.m, plan.n, plan.p) == (m, n, p)
    # the overlap-split arrays are a lossless compaction of cols
    np.testing.assert_array_equal(pattern_cols(plan), cols)
    sp = plan.transpose()
    assert sp.transpose() is plan            # involution, same object
    # a re-derived scatter plan prices the same put-direction volumes
    sp2 = plan.transpose()
    np.testing.assert_array_equal(np.asarray(sp2.counts.s_local_out),
                                  np.asarray(sp.counts.s_local_out))
    np.testing.assert_array_equal(np.asarray(sp2.counts.s_remote_out),
                                  np.asarray(sp.counts.s_remote_out))


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(p=st.sampled_from([2, 4]), shard=st.sampled_from([4, 8]),
           rows=st.sampled_from([2, 4, 8]), r=st.integers(1, 4),
           seed=st.integers(0, 999), dup=st.booleans())
    def test_plan_roundtrip(p, shard, rows, r, seed, dup):
        _check_plan_roundtrip(p, shard, rows, r, seed, dup)

    @settings(max_examples=30, deadline=None)
    @given(p=st.sampled_from([2, 4]), shard=st.sampled_from([4, 8, 16]),
           rows=st.sampled_from([2, 4]), r=st.integers(1, 4),
           seed=st.integers(0, 999), dup=st.booleans())
    def test_from_indices_roundtrip(p, shard, rows, r, seed, dup):
        _check_from_indices(p * shard, p * rows, r, seed, dup)
else:
    GRID = list(itertools.product([2, 4], [4, 8], [2, 4, 8], [1, 2, 4],
                                  [0, 7], [False, True]))[::3]

    @pytest.mark.parametrize("p,shard,rows,r,seed,dup", GRID)
    def test_plan_roundtrip(p, shard, rows, r, seed, dup):
        _check_plan_roundtrip(p, shard, rows, r, seed, dup)

    @pytest.mark.parametrize("p,shard,rows,r,seed,dup", GRID)
    def test_from_indices_roundtrip(p, shard, rows, r, seed, dup):
        _check_from_indices(p * shard, p * rows, r, seed, dup)
