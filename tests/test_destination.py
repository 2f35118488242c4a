"""Consumer-targeted unpack (``Destination``): every strategy rung must
deliver values straight into named consumer slots, bit-identically to the
assembled-x_copy path, and the Heat2D step must do O(halo) unpack work —
no full-length intermediate (the regression the ROADMAP asked for).

Runs on whatever devices the pytest process has (1 locally, 8 under the CI
gate's XLA_FLAGS).
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers.check_compact_overlap import CASES, run
from repro.comm import (AccessPattern, Destination, IrregularGather,
                        STRATEGIES, Topology)
from repro.comm.plan import build_comm_plan
from repro.comm.strategies import dest_gather_local, dest_slot_kinds
from repro.comm import perfmodel as pm
from jax.sharding import PartitionSpec as P


def _mesh():
    ndev = len(jax.devices())
    return jax.make_mesh((ndev,), ("data",)), ndev


# ---------------------------------------------------------------------------
# Destination descriptor basics
# ---------------------------------------------------------------------------

def test_destination_from_slots_and_split():
    d = Destination.from_slots(
        up=np.array([[4, 5], [0, 1]]),
        left=np.array([[6], [-1]]))
    assert d.names == ("up", "left")
    assert d.p == 2 and d.num_slots == 3
    out = d.split_local(np.array([10.0, 11.0, 12.0]))
    np.testing.assert_array_equal(out["up"], [10.0, 11.0])
    np.testing.assert_array_equal(out["left"], [12.0])
    # feature dims flow through the split
    out = d.split_local(np.zeros((3, 5)))
    assert out["up"].shape == (2, 5) and out["left"].shape == (1, 5)


def test_destination_rejects_unplanned_foreign_index():
    """A foreign destination id outside the AccessPattern never arrives —
    the planner must refuse instead of delivering garbage."""
    mesh, ndev = _mesh()
    if ndev == 1:
        pytest.skip("needs a foreign shard")
    n = 16 * ndev
    idx = np.zeros((n, 1), np.int32)        # pattern only gathers element 0
    pattern = AccessPattern.from_indices(idx, n=n)
    # shard 0 asks for element n-1 (owned by the last shard, never exchanged)
    slots = np.zeros((ndev, 1), np.int64)
    slots[0, 0] = n - 1
    with pytest.raises(ValueError, match="never"):
        IrregularGather(pattern, mesh, strategy="condensed", blocksize=8,
                        destination=Destination.from_slots(s=slots),
                        use_plan_cache=False)


# ---------------------------------------------------------------------------
# gather-level: targeted delivery equals the reference for every rung
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_targeted_unpack_matches_reference(strategy):
    mesh, ndev = _mesh()
    n, d = 64 * ndev, 3
    rng = np.random.default_rng(0)
    idx = rng.integers(0, n, size=(n, 5)).astype(np.int32)
    pattern = AccessPattern.from_indices(idx, n=n)
    # slots: a mix of pattern reads and forced-zero sentinels
    slots = idx.reshape(ndev, -1, 5)[:, :16].reshape(ndev, -1).astype(
        np.int64).copy()
    slots[:, -3:] = Destination.ZERO
    dest = Destination.from_slots(rows=slots)
    g = IrregularGather(pattern, mesh, strategy=strategy, blocksize=16,
                        destination=dest)
    x = rng.standard_normal((n, d)).astype(np.float32)

    def local(x_local, *args):
        return g.local(x_local, *args)["rows"][None]

    f = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("data"),) + g.in_specs,
        out_specs=P("data"), check_vma=False))
    out = np.asarray(f(g.shard_vector(x), *g.plan_args))
    want = np.where((slots >= 0)[..., None], x[np.clip(slots, 0, None)], 0.0)
    np.testing.assert_array_equal(out, want)
    # the full materialization stays available on the same gather
    xc = np.asarray(g(g.shard_vector(x)))
    rows = pattern.m // ndev
    for q in range(ndev):
        needed = np.unique(pattern.indices[q * rows:(q + 1) * rows])
        np.testing.assert_array_equal(xc[q][needed], x[needed])


# ---------------------------------------------------------------------------
# consumer equivalence: materialize="dest" == materialize="full", bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_spmv_dest_equals_full_bitwise(strategy):
    from repro.core.matrix import make_mesh_like_matrix, spmv_ref_np
    from repro.core.spmv import DistributedSpMV

    mesh, ndev = _mesh()
    n = 128 * ndev
    m = make_mesh_like_matrix(n, 8, locality_window=n // 8,
                              long_range_frac=0.1, seed=11)
    x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
    ed = DistributedSpMV(m, mesh, strategy=strategy, blocksize=32)
    assert ed.materialize == "dest"
    ef = DistributedSpMV(m, mesh, strategy=strategy, blocksize=32,
                         materialize="full")
    yd = np.asarray(ed(ed.shard_vector(x)))
    np.testing.assert_array_equal(
        yd, np.asarray(ef(ef.shard_vector(x))),
        err_msg=f"strategy={strategy}: targeted unpack changed the result")
    np.testing.assert_allclose(yd, spmv_ref_np(m, x), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_heat2d_dest_equals_full_bitwise(strategy):
    from repro.core.heat2d import Heat2D

    mesh, ndev = _mesh()
    shape = (2, ndev // 2) if ndev % 2 == 0 and ndev > 1 else (1, ndev)
    mesh = jax.make_mesh(shape, ("data", "model"))
    kw = dict(coef=0.1, strategy=strategy)
    if strategy == "blockwise":
        kw["blocksize"] = 8
    hd = Heat2D(mesh, shape[0] * 16, shape[1] * 16, **kw)
    hf = Heat2D(mesh, shape[0] * 16, shape[1] * 16, materialize="full", **kw)
    phi = hd.init_field(3)
    got = np.asarray(hd.run(phi, 5))
    np.testing.assert_array_equal(
        got, np.asarray(hf.run(phi, 5)),
        err_msg=f"strategy={strategy}: targeted unpack changed the result")
    np.testing.assert_allclose(got, hd.reference(np.asarray(phi), 5),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_moe_dispatch_dest_equals_full_bitwise(strategy):
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
    gd = MoEDispatchGather(top_e, n_tok, e_total, cap, mesh,
                           strategy=strategy, blocksize=16, hw=pm.ABEL)
    gf = MoEDispatchGather(top_e, n_tok, e_total, cap, mesh,
                           strategy=strategy, blocksize=16, hw=pm.ABEL,
                           materialize="full")
    bd = np.asarray(gd(gd.shard_tokens(x)))
    np.testing.assert_array_equal(bd, np.asarray(gf(gf.shard_tokens(x))))
    np.testing.assert_array_equal(bd, ref)


# ---------------------------------------------------------------------------
# the regression the ROADMAP asked for: Heat2D unpack work is O(halo)
# ---------------------------------------------------------------------------

def _max_rank1_intermediate(jaxpr) -> int:
    """Largest rank-1 array produced by any equation, recursing into
    sub-jaxprs (pjit / scan / shard_map bodies)."""
    try:
        from jax.extend import core as jcore  # noqa: F401
    except ImportError:
        pass
    best = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", None)
            if shape is not None and len(shape) == 1:
                best = max(best, int(shape[0]))
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                best = max(best, _max_rank1_intermediate(sub))
    return best


def _sub_jaxprs(val):
    if hasattr(val, "jaxpr") and hasattr(val, "eqns") is False:
        # ClosedJaxpr wraps a Jaxpr
        yield val.jaxpr
    elif hasattr(val, "eqns"):
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _sub_jaxprs(v)


def _shard_map_bodies(jaxpr):
    """Inner jaxprs of every shard_map equation (the per-device programs)."""
    for eqn in jaxpr.eqns:
        is_shmap = "shard_map" in str(eqn.primitive)
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                if is_shmap:
                    yield sub
                yield from _shard_map_bodies(sub)


def test_heat2d_step_has_no_full_length_intermediate():
    """The targeted unpack must not materialize any O(n)=O(big_m*big_n)
    buffer: every rank-1 intermediate in the step (x_local, recv buffers,
    halo strips) is O(shard + halo).  The full materialization, by
    construction, assembles the (n+2,) x_copy — the detector must see it."""
    from repro.core.heat2d import Heat2D

    ndev = len(jax.devices())
    shape = (2, ndev // 2) if ndev % 2 == 0 and ndev > 1 else (1, ndev)
    mesh = jax.make_mesh(shape, ("data", "model"))
    big_m, big_n = shape[0] * 16, shape[1] * 16
    n = big_m * big_n
    shard = n // (shape[0] * shape[1])

    hd = Heat2D(mesh, big_m, big_n, coef=0.1)
    jaxpr_dest = jax.make_jaxpr(lambda p: hd.run(p, 1))(hd.init_field(0))
    dest_max = _max_rank1_intermediate(jaxpr_dest.jaxpr)
    # O(shard + halo): the biggest 1-D buffer is the flattened local tile
    # (shard elements) plus at most the padded recv buffer — far below n
    halo = 2 * (big_m // shape[0] + big_n // shape[1])
    assert dest_max <= shard + hd.gather.plan.p * hd.gather.plan.s_max, (
        f"targeted unpack materialized a {dest_max}-element 1-D buffer "
        f"(shard={shard}, halo={halo}, n={n})")
    # on a single device shard == n, so the O(n)-vs-O(shard) distinction
    # only exists multi-device (the CI gate runs with 8)
    assert dest_max < n or hd.gather.p == 1

    # sanity: the detector is not blind — the full path DOES build x_copy
    hf = Heat2D(mesh, big_m, big_n, coef=0.1, materialize="full")
    jaxpr_full = jax.make_jaxpr(lambda p: hf.run(p, 1))(hf.init_field(0))
    assert _max_rank1_intermediate(jaxpr_full.jaxpr) >= n


def test_spmv_dest_scatter_operands_are_o_slots():
    """SpMV targeted unpack: inside the per-device program, no rank-1
    intermediate beyond shard + recv + slots (the sharded global output y
    is legitimately n-sized, so only the shard_map body is inspected)."""
    from repro.core.matrix import make_mesh_like_matrix
    from repro.core.spmv import DistributedSpMV

    mesh, ndev = _mesh()
    n = 128 * ndev
    m = make_mesh_like_matrix(n, 4, locality_window=n // 8,
                              long_range_frac=0.1, seed=3)
    x_host = np.random.default_rng(0).standard_normal(n).astype(np.float32)

    def body_max(eng):
        jaxpr = jax.make_jaxpr(eng._step)(eng.shard_vector(x_host),
                                          *eng._args)
        bodies = list(_shard_map_bodies(jaxpr.jaxpr))
        assert bodies, "step contains no shard_map body"
        return max(_max_rank1_intermediate(b) for b in bodies)

    eng = DistributedSpMV(m, mesh, strategy="condensed", blocksize=32)
    mx = body_max(eng)
    shard = n // ndev
    recv = eng.plan.p * eng.plan.s_max
    assert mx <= max(shard + 1, recv, eng.plan.dest_len), (mx, shard, recv)
    assert mx < n or ndev == 1
    # sanity: the full path's per-device program does build the (>=n) copy
    engf = DistributedSpMV(m, mesh, strategy="condensed", blocksize=32,
                           materialize="full")
    assert body_max(engf) >= n


# ---------------------------------------------------------------------------
# §5 pricing of the two unpack modes
# ---------------------------------------------------------------------------

def test_model_prices_dest_unpack_below_full_assembly():
    """For a sparse-access consumer (halo-sized destination, big n) the
    targeted unpack must be predicted cheaper than full assembly, for every
    runnable rung — that's what lets strategy="auto" pick per consumer."""
    from repro.comm import select
    from repro.comm.plan import build_comm_plan

    n, p = 1 << 14, 8
    rng = np.random.default_rng(0)
    idx = rng.integers(0, n, size=(n, 4)).astype(np.int32)
    slots = idx[::64, :2].reshape(p, -1).astype(np.int64)  # sparse consumer
    dest = Destination.from_slots(s=slots)
    plan = build_comm_plan(idx, n, p, blocksize=64, topology=Topology(p, 4),
                           destination=dest)
    full = dict(select.rank_strategies(plan, 4, pm.ABEL, materialize="full"))
    tgt = dict(select.rank_strategies(plan, 4, pm.ABEL, materialize="dest"))
    for name in ("condensed", "blockwise", "overlap"):
        assert tgt[name] < full[name], name
    # paper-mode pricing (materialize=None) is untouched by the extension
    base = dict(select.rank_strategies(plan, 4, pm.ABEL))
    w = select.workload_from_plan(plan, 4)
    assert base["condensed"] == pytest.approx(pm.predict_v3(w, pm.ABEL))


# ---------------------------------------------------------------------------
# compact destinations: the overlap SpMV's foreign slot list (4 host
# devices, one subprocess), and the gathers dest_gather_local compiles
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def compact():
    return run("destination")


@pytest.mark.parametrize("case", list(CASES))
def test_compact_destination_delivers_foreign_slots(compact, case):
    """Every slot of the overlap rung's destination is foreign or padding;
    real slots read x at their column, padding reads exactly 0, and the
    rows the foreign partial sums into stay sorted."""
    got = compact[case]
    assert got["none_owned"] and got["rows_sorted"], got
    assert got["real_read_x"] and got["padding_zero"], got


def test_compact_destination_cache_round_trip(compact):
    """Built and stored, then loaded from disk: same destination, same
    product, no host build the second time."""
    got = compact["cache_round_trip"]
    assert got["host_builds"] == 0 and got["disk_hits"] == 2, got
    assert got["entries"] == 2, got
    assert got["same_destination"] and got["same_product"], got


def _parent_dest_gather(recv_flat, x_local, src_idx, own_idx, own_mask,
                        rem_mask):
    """``dest_gather_local`` as it was before it took static slot kinds."""
    def take(a, idx):
        return a.at[idx].get(mode="promise_in_bounds")

    with jax.named_scope("comm.unpack"):
        zero = jnp.zeros((), x_local.dtype)
        return jnp.where(rem_mask != 0, take(recv_flat, src_idx),
                         jnp.where(own_mask != 0, take(x_local, own_idx),
                                   zero))


def _lowered(fn, L=16):
    args = (jnp.zeros(40), jnp.zeros(32), jnp.zeros(L, jnp.int32),
            jnp.zeros(L, jnp.int32), jnp.zeros(L, jnp.int8),
            jnp.zeros(L, jnp.int8))
    text = jax.jit(fn).lower(*args).as_text()
    return text.split("\n", 1)[1]          # past the module's name


@pytest.mark.parametrize("kinds, gathers, selects", [
    (dict(has_own=True, has_foreign=True, has_zero=True), 2, 2),
    (dict(has_own=True, has_foreign=True, has_zero=False), 2, 2),
    (dict(has_own=False, has_foreign=True, has_zero=False), 1, 0),
    (dict(has_own=False, has_foreign=True, has_zero=True), 1, 1),
    (dict(has_own=True, has_foreign=False, has_zero=False), 1, 0),
    (dict(has_own=False, has_foreign=False, has_zero=True), 0, 0),
])
def test_dest_gather_local_compiles_only_needed_gathers(kinds, gathers,
                                                        selects):
    """Value gathers and value selects in the lowered unpack.  With owned
    and foreign slots both present the program is the parent's, op for
    op; without owned slots nothing reads ``x_local``."""
    text = _lowered(functools.partial(dest_gather_local, **kinds))
    assert len(re.findall(r'"stablehlo\.gather"\(', text)) == gathers
    assert len(re.findall(r"stablehlo\.select .*xf32>$", text,
                          re.M)) == selects
    if kinds["has_own"] and kinds["has_foreign"]:
        assert text == _lowered(_parent_dest_gather)


def test_dest_slot_kinds_reads_the_plan():
    n, p = 64, 4
    idx = ((np.arange(n)[:, None] + np.array([0, 20])) % n).astype(np.int32)
    mixed = np.array([[0, 20, -1], [16, 36, -1], [32, 52, -1],
                      [48, 4, -1]])
    foreign = mixed[:, 1:2]
    kinds = {}
    for name, slots in (("mixed", mixed), ("foreign", foreign)):
        plan = build_comm_plan(idx, n, p, blocksize=8,
                               destination=Destination.from_slots(s=slots))
        kinds[name] = dest_slot_kinds(plan)
    assert kinds["mixed"] == dict(has_own=True, has_foreign=True,
                                  has_zero=True)
    assert kinds["foreign"] == dict(has_own=False, has_foreign=True,
                                    has_zero=False)
