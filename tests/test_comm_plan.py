"""Property-based tests for the communication planner (paper §4.3.1).

Invariants:
  * delivery: after the condensed exchange, every index a shard's rows access
    is present in its x_copy (verified numerically in the multi-device test;
    here structurally);
  * conservation: Σ send == Σ recv, per pair;
  * condensing: per-pair message contents are unique and sorted;
  * volume ordering (paper Fig. 2): condensed <= blockwise <= replicate;
  * counts consistency between the plan arrays and the perf-model counts.
"""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property-based planner tests need the 'hypothesis' extra")
from hypothesis import given, settings, strategies as st

from repro.core.matrix import make_mesh_like_matrix
from repro.comm.plan import Topology, build_comm_plan


@st.composite
def plan_case(draw):
    p = draw(st.sampled_from([2, 4, 8]))
    shard = draw(st.sampled_from([16, 32, 64]))
    r_nz = draw(st.integers(2, 8))
    n = p * shard
    seed = draw(st.integers(0, 2**16))
    window = draw(st.integers(4, n))
    long_frac = draw(st.sampled_from([0.0, 0.05, 0.3]))
    spn = draw(st.sampled_from([1, 2]))
    if p % spn:
        spn = 1
    m = make_mesh_like_matrix(n, r_nz, locality_window=window,
                              long_range_frac=long_frac, seed=seed)
    bs = draw(st.sampled_from([s for s in (4, 8, 16, shard)
                               if shard % s == 0]))
    return m, n, p, bs, Topology(p, p // spn if p % (p // spn) == 0 else p)


@settings(max_examples=25, deadline=None)
@given(plan_case())
def test_plan_invariants(case):
    m, n, p, bs, topo = case
    plan = build_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    shard = n // p

    # conservation + condensing + correct ownership
    for s in range(p):
        for q in range(p):
            k = int(plan.send_counts[s, q])
            if s == q:
                assert k == 0
                continue
            sent_local = plan.send_local_idx[s, q, :k]
            recv_glob = plan.recv_global_idx[q, s, :k]
            # sender's local indices + shard offset == receiver's globals
            np.testing.assert_array_equal(sent_local + s * shard, recv_glob)
            # condensed: unique and sorted
            assert len(np.unique(recv_glob)) == k
            assert (np.diff(recv_glob) > 0).all()
            # padding is the dump slot
            assert (plan.recv_global_idx[q, s, k:] == n).all()

    # delivery: every foreign index needed by q appears in some message to q
    for q in range(p):
        rows = slice(q * shard, (q + 1) * shard)
        needed = np.unique(m.cols[rows])
        foreign = needed[(needed // shard) != q]
        got = np.concatenate([
            plan.recv_global_idx[q, s, :plan.send_counts[s, q]]
            for s in range(p)]) if p > 1 else np.zeros(0, int)
        assert np.isin(foreign, got).all()

    # volume ordering (paper Fig. 2): condensed <= blockwise-foreign <= n-shard
    c = plan.counts
    cond = c.total_condensed_volume()
    blockw_foreign = (c.total_blockwise_volume()
                      - p * shard)  # minus own-shard copies
    assert cond <= blockw_foreign <= p * (n - shard)

    # counts consistency
    assert cond == int(plan.send_counts.sum())
    assert (c.s_local_out + c.s_remote_out).sum() == cond


@settings(max_examples=10, deadline=None)
@given(plan_case())
def test_blockwise_covers_condensed(case):
    """Every condensed index must live inside some transferred block."""
    m, n, p, bs, topo = case
    plan = build_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    for q in range(p):
        for s in range(p):
            k = int(plan.send_counts[s, q])
            if not k:
                continue
            vals = plan.recv_global_idx[q, s, :k]
            kb = int(plan.send_block_counts[s, q])
            blocks = plan.recv_global_blk[q, s, :kb]
            assert np.isin(vals // bs, blocks).all()


def test_tau_counts_split_by_node():
    m = make_mesh_like_matrix(256, 4, locality_window=256,
                              long_range_frac=0.5, seed=1)
    topo = Topology(8, 4)  # 2 nodes
    plan = build_comm_plan(m.cols, 256, 8, blocksize=8, topology=topo)
    c = plan.counts
    # with heavy long-range traffic both intra and inter node occur
    assert c.c_local_indv.sum() > 0 and c.c_remote_indv.sum() > 0
    # every occurrence classified exactly once
    total_foreign = sum(
        ((m.cols[q * 32:(q + 1) * 32] // 32) != q).sum() for q in range(8))
    assert c.c_local_indv.sum() + c.c_remote_indv.sum() == total_foreign
