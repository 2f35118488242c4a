"""The fused Pallas exchange path (``use_kernel=True``) against the jnp
strategy ladder — the bit-identity contract, both directions.

Every kernelized rung must return the SAME BITS as its jnp sibling: the
kernels move items exactly and combine in index order, the order XLA's
CPU scatter uses, so any divergence is a routing bug, not rounding.  The
jaxpr regressions pin the kernel count per rung (the fused paths must not
silently fall back to jnp, nor grow extra passes).  Runs on whatever
devices the pytest process has (1 locally, 8 under the CI gate's
XLA_FLAGS).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (AccessPattern, IrregularGather, IrregularScatter,
                        STRATEGIES)
from repro.kernels import ops as kops
from repro.kernels import ref as kref


def _mesh():
    ndev = len(jax.devices())
    return jax.make_mesh((ndev,), ("data",)), ndev


def _gather_case(n, m, r, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n, size=(m, r)).astype(np.int32)
    return AccessPattern.from_indices(idx, n=n), idx


def _scatter_vals(rng, shape, dtype):
    # integer-valued floats: every combine is exact in f32 AND bf16, so
    # kernel-vs-jnp equality failures can only come from routing
    return rng.integers(-4, 5, size=shape).astype(np.float32).astype(dtype)


# --------------------------------------------------------------------------
# Kernel layer vs its jnp oracles (padding, feature dims, dtypes, edges)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("feat", [(), (3,)])
@pytest.mark.parametrize("block", [None, 16])
def test_pack_gather_matches_ref(feat, block):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((40,) + feat).astype(np.float32)
    idx = rng.integers(0, 40, size=37).astype(np.int32)   # 37 % 16 != 0
    got = kops.pack_gather(x, idx, block=block)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(kref.pack_gather_ref(x, idx)))


@pytest.mark.parametrize("feat", [(), (2,)])
@pytest.mark.parametrize("block", [None, 16])
def test_unpack_dest_matches_ref(feat, block):
    rng = np.random.default_rng(1)
    L, R, shard = 53, 21, 16
    recv = rng.standard_normal((R,) + feat).astype(np.float32)
    x = rng.standard_normal((shard,) + feat).astype(np.float32)
    src = rng.integers(0, R, size=L).astype(np.int32)
    own = rng.integers(0, shard, size=L).astype(np.int32)
    own_m = (rng.random(L) < 0.4).astype(np.int8)
    rem_m = ((rng.random(L) < 0.5) & (own_m == 0)).astype(np.int8)
    got = kops.unpack_dest(recv, x, src, own, own_m, rem_m, block=block)
    want = kref.unpack_dest_ref(recv, x, src, own, own_m, rem_m)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("copy_own", [True, False])
def test_unpack_scatter_set_matches_ref(copy_own):
    rng = np.random.default_rng(2)
    recv = rng.standard_normal((19, 2)).astype(np.float32)
    idx = rng.integers(0, 33, size=19).astype(np.int32)
    x_own = rng.standard_normal((8, 2)).astype(np.float32)
    got = kops.unpack_scatter_set(recv, idx, x_own, 16, out_len=33,
                                  copy_own=copy_own)
    want = kref.unpack_scatter_set_ref(recv, idx, x_own, 16, out_len=33,
                                       copy_own=copy_own)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("reduce", ["add", "set", "max"])
def test_accumulate_kernels_match_ref(reduce):
    rng = np.random.default_rng(3)
    vals = _scatter_vals(rng, (29, 2), np.float32)
    idx = rng.integers(0, 11, size=29).astype(np.int32)
    got = kops.accumulate_segments(vals, idx, out_len=11, reduce=reduce)
    want = kref.accumulate_segments_ref(vals, idx, out_len=11, reduce=reduce)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    init = jnp.asarray(np.asarray(want))
    more = _scatter_vals(rng, (13, 2), np.float32)
    midx = rng.integers(0, 11, size=13).astype(np.int32)
    got2 = kops.accumulate_into(init, more, midx, reduce=reduce)
    want2 = kref.accumulate_into_ref(init, more, midx, reduce=reduce)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(want2))


def test_pack_gather_empty_message_set():
    x = np.ones((8, 3), np.float32)
    out = kops.pack_gather(x, np.zeros((0,), np.int32))
    assert out.shape == (0, 3)


@pytest.mark.parametrize("kernel", ["pack_gather", "unpack_scatter_set",
                                    "accumulate_segments"])
def test_shard_over_vmem_budget_raises(kernel):
    """No silent jnp fallback: a resident operand past the budget is an
    error naming its bytes and the limit (checked at trace time)."""
    from repro.kernels.layout import VMEM_BUDGET_BYTES

    n = VMEM_BUDGET_BYTES // 4 + 4096
    big = jax.ShapeDtypeStruct((n,), jnp.float32)
    idx = jax.ShapeDtypeStruct((16,), jnp.int32)
    vals = jax.ShapeDtypeStruct((16,), jnp.float32)
    call = {
        "pack_gather": lambda b, i, v: kops.pack_gather(b, i),
        "unpack_scatter_set": lambda b, i, v: kops.unpack_scatter_set(
            v, i, b, 0, out_len=n),
        "accumulate_segments": lambda b, i, v: kops.accumulate_segments(
            v, i, out_len=n),
    }[kernel]
    with pytest.raises(kops.VmemBudgetError,
                       match=rf"{kernel}: \d+ bytes .* {VMEM_BUDGET_BYTES}"):
        jax.eval_shape(call, big, idx, vals)


# --------------------------------------------------------------------------
# Gather direction: every rung, kernel vs jnp, bit-identical
# --------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("feat", [(), (3,)])
def test_gather_kernel_bit_identical(strategy, dtype, feat):
    mesh, ndev = _mesh()
    n = 32 * ndev
    pattern, _ = _gather_case(n, n, 4, seed=5)
    x = np.random.default_rng(5).standard_normal((n,) + feat)
    x = jnp.asarray(x).astype(dtype)
    outs = {}
    for uk in (False, True):
        g = IrregularGather(pattern, mesh, strategy=strategy, blocksize=8,
                            use_kernel=uk, use_plan_cache=False)
        outs[uk] = np.asarray(g(g.shard_vector(x)).astype(jnp.float32))
    np.testing.assert_array_equal(outs[True], outs[False])


# --------------------------------------------------------------------------
# Scatter direction: rungs x reduces x dtypes, kernel vs jnp, bit-identical
# --------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("reduce", ["add", "set", "max"])
def test_scatter_kernel_bit_identical(strategy, reduce):
    mesh, ndev = _mesh()
    n = 32 * ndev
    pattern, idx = _gather_case(n, n, 5, seed=6)
    vals = _scatter_vals(np.random.default_rng(6), idx.shape, np.float32)
    outs = {}
    for uk in (False, True):
        s = IrregularScatter(pattern, mesh, strategy=strategy, blocksize=8,
                             reduce=reduce, use_kernel=uk,
                             use_plan_cache=False)
        outs[uk] = np.asarray(s(s.shard_values(vals)))
    np.testing.assert_array_equal(outs[True], outs[False])


@pytest.mark.parametrize("strategy", ["condensed", "overlap"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16])
@pytest.mark.parametrize("feat", [(), (2,)])
def test_scatter_kernel_bit_identical_bf16_feat(strategy, dtype, feat):
    mesh, ndev = _mesh()
    n = 32 * ndev
    pattern, idx = _gather_case(n, n, 4, seed=7)
    vals = _scatter_vals(np.random.default_rng(7), idx.shape + feat,
                         np.float32)
    vals = jnp.asarray(vals).astype(dtype)
    outs = {}
    for uk in (False, True):
        s = IrregularScatter(pattern, mesh, strategy=strategy, blocksize=8,
                             reduce="add", use_kernel=uk,
                             use_plan_cache=False)
        outs[uk] = np.asarray(s(s.shard_values(vals)).astype(jnp.float32))
    np.testing.assert_array_equal(outs[True], outs[False])


# --------------------------------------------------------------------------
# DistributedSpMV: transpose + use_kernel on every rung; dest + use_kernel
# (the formerly-rejected combination) routes to the dest-unpack kernel
# --------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_spmv_transpose_kernel_all_rungs(strategy):
    from repro.core.matrix import make_mesh_like_matrix
    from repro.core.spmv import DistributedSpMV

    mesh, ndev = _mesh()
    n = 32 * ndev
    m = make_mesh_like_matrix(n, 4, locality_window=n // 4, seed=8)
    x = np.random.default_rng(8).standard_normal(n).astype(np.float32)
    ys = {}
    for uk in (False, True):
        eng = DistributedSpMV(m, mesh, strategy=strategy, transpose=True,
                              use_kernel=uk, use_plan_cache=False)
        ys[uk] = np.asarray(eng(eng.shard_vector(x)))
    np.testing.assert_array_equal(ys[True], ys[False])


@pytest.mark.parametrize("strategy", ["replicate", "condensed", "overlap"])
def test_spmv_dest_kernel_routes_and_matches(strategy):
    """materialize="dest" + use_kernel=True used to raise; it now routes
    the exchange through the fused dest-unpack kernel, bit-identical to
    the jnp dest path (the local slot compute is shared)."""
    from repro.core.matrix import make_mesh_like_matrix
    from repro.core.spmv import DistributedSpMV

    mesh, ndev = _mesh()
    n = 32 * ndev
    m = make_mesh_like_matrix(n, 4, locality_window=n // 4, seed=9)
    x = np.random.default_rng(9).standard_normal(n).astype(np.float32)
    ys = {}
    for uk in (False, True):
        eng = DistributedSpMV(m, mesh, strategy=strategy,
                              materialize="dest", use_kernel=uk,
                              use_plan_cache=False)
        assert eng.materialize == "dest"
        ys[uk] = np.asarray(eng(eng.shard_vector(x)))
    np.testing.assert_array_equal(ys[True], ys[False])


# --------------------------------------------------------------------------
# Jaxpr regression: the kernelized rungs run exactly the expected number
# of pallas_call equations (no silent jnp fallback, no extra passes)
# --------------------------------------------------------------------------

def _count_pallas(jaxpr) -> int:
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            count += 1
        for val in eqn.params.values():
            for sub in _jaxprs_of(val):
                count += _count_pallas(sub)
    return count


def _jaxprs_of(val):
    if hasattr(val, "jaxpr"):           # ClosedJaxpr
        yield val.jaxpr
    elif hasattr(val, "eqns"):          # raw Jaxpr
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _jaxprs_of(v)


@pytest.mark.parametrize("use_kernel,expected", [(False, 0), (True, 2)])
def test_gather_condensed_pallas_count(use_kernel, expected):
    # kernelized condensed gather = pack + fused full unpack
    mesh, ndev = _mesh()
    n = 32 * ndev
    pattern, _ = _gather_case(n, n, 4, seed=10)
    g = IrregularGather(pattern, mesh, strategy="condensed", blocksize=8,
                        use_kernel=use_kernel, use_plan_cache=False)
    x = g.shard_vector(np.zeros(n, np.float32))
    jaxpr = jax.make_jaxpr(lambda xx: g._gather_all(xx, *g.plan_args))(x)
    assert _count_pallas(jaxpr.jaxpr) == expected


@pytest.mark.parametrize("use_kernel,expected", [(False, 0), (True, 3)])
def test_scatter_condensed_pallas_count(use_kernel, expected):
    # kernelized condensed scatter = pack-accumulate + own-accumulate
    # (issued while the collective flies) + landed-accumulate
    mesh, ndev = _mesh()
    n = 32 * ndev
    pattern, idx = _gather_case(n, n, 4, seed=11)
    s = IrregularScatter(pattern, mesh, strategy="condensed", blocksize=8,
                         reduce="add", use_kernel=use_kernel,
                         use_plan_cache=False)
    vals = s.shard_values(np.zeros(idx.shape, np.float32))
    jaxpr = jax.make_jaxpr(
        lambda vv: s._scatter_all(vv, *s.plan_args))(vals)
    assert _count_pallas(jaxpr.jaxpr) == expected


# --------------------------------------------------------------------------
# Schedule threading: schedule-wide default + per-stage override
# --------------------------------------------------------------------------

def test_schedule_use_kernel_default_and_override():
    from repro.comm.schedule import Schedule

    mesh, ndev = _mesh()
    n = 32 * ndev
    pattern, idx = _gather_case(n, n, 4, seed=12)
    rng = np.random.default_rng(12)
    vals = rng.standard_normal(idx.shape).astype(np.float32)
    x_host = rng.standard_normal(n).astype(np.float32)

    def build(**kw):
        sched = Schedule()
        x = sched.input("x")
        vl = sched.constant(vals, name="vals")
        cl = sched.constant(idx, name="cols")
        g = sched.gather(pattern, src=x, name="exchange",
                         use_kernel=kw.pop("stage_use_kernel", None))
        sched.compute(lambda xc, v_, c_: (v_ * xc[c_]).sum(-1), g, vl, cl,
                      name="spmv")
        return sched.compile(mesh, axis_name="data", strategy="condensed",
                             blocksize=8, **kw)

    base = build(use_kernel=False)
    kern = build(use_kernel=True)                     # schedule-wide default
    over = build(stage_use_kernel=True)               # per-stage override
    xs = base.shard_input(x_host)
    y0 = np.asarray(base(xs))
    np.testing.assert_array_equal(np.asarray(kern(kern.shard_input(x_host))),
                                  y0)
    np.testing.assert_array_equal(np.asarray(over(over.shard_input(x_host))),
                                  y0)
    # the kernel really engaged: the per-stage-override window holds
    # pallas_call equations, the jnp window none
    j_base = jax.make_jaxpr(base.mapped)(xs, *base.step_args)
    j_over = jax.make_jaxpr(over.mapped)(xs, *over.step_args)
    assert _count_pallas(j_base.jaxpr) == 0
    assert _count_pallas(j_over.jaxpr) == 2
