"""The ExchangeSchedule front door: fused multi-exchange windows.

Covers the tentpole guarantees of ``repro.comm.schedule``:

* a single-stage schedule is bit-identical to the one-shot front door it
  wraps (``IrregularGather`` / ``IrregularScatter`` stay the stage
  executors — the shim tests);
* the fused MoE dispatch → expert → combine layer is bit-identical to the
  composed three-window path on every ladder rung, and issues its stages
  inside ONE ``shard_map``;
* ``normal_equations_step`` (z = MᵀM x) matches the NumPy ground truth on
  every rung and shares one base plan between its two directions;
* the §5 composition model (``perfmodel.predict_schedule``) and the
  Heat2D full-window refinement (edge-ring term) behave;
* the ``measure_hw`` memo keys (tuple axes, factorization, clearing).

Integer-valued data keeps every float sum exact, so bit-identity tests
the scheduling/unpacking machinery, not float associativity.  Runs on
whatever devices the pytest process has (1 locally, 8 under the CI
gate's XLA_FLAGS).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import (AccessPattern, IrregularGather, IrregularScatter,
                        Schedule, STRATEGIES, plan_cache)
from repro.comm import perfmodel as pm
from repro.comm.plan import Topology


def _mesh():
    ndev = len(jax.devices())
    return jax.make_mesh((ndev,), ("data",)), ndev


def _case(n, m, r, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n, size=(m, r)).astype(np.int32)
    vals = rng.integers(-4, 5, size=(m, r)).astype(np.float32)
    return AccessPattern.from_indices(idx, n=n), idx, vals


def _inner_jaxprs(param_value):
    vals = param_value if isinstance(param_value, (list, tuple)) \
        else [param_value]
    out = []
    for v in vals:
        if hasattr(v, "jaxpr"):       # ClosedJaxpr
            out.append(v.jaxpr)
        elif hasattr(v, "eqns"):      # Jaxpr
            out.append(v)
    return out


def _count_shard_maps(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        if "shard_map" in str(eqn.primitive):
            total += 1
        for v in eqn.params.values():
            for sub in _inner_jaxprs(v):
                total += _count_shard_maps(sub)
    return total


# --------------------------------------------------------------------------
# shim tests: one-stage schedules == the one-shot front doors, bitwise
# --------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_single_stage_gather_schedule_is_the_front_door(strategy):
    mesh, ndev = _mesh()
    n = 32 * ndev
    pattern, idx, _ = _case(n, n, 3, seed=0)
    rng = np.random.default_rng(0)
    x = rng.integers(-4, 5, size=n).astype(np.float32)

    g = IrregularGather(pattern, mesh, strategy=strategy, blocksize=8)
    sched = Schedule()
    x_ref = sched.input("x")
    gr = sched.gather(pattern, src=x_ref, strategy=strategy)
    sched.compute(lambda xc: xc[None], gr, name="stack")
    step = sched.compile(mesh, strategy=strategy, blocksize=8)
    np.testing.assert_array_equal(
        np.asarray(step(step.shard_input(x))),
        np.asarray(g(g.shard_vector(x))),
        err_msg=f"strategy={strategy}: schedule shim diverged")


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_single_stage_scatter_schedule_is_the_front_door(strategy):
    mesh, ndev = _mesh()
    n = 32 * ndev
    pattern, idx, vals = _case(n, n, 3, seed=1)
    s = IrregularScatter(pattern, mesh, strategy=strategy, blocksize=8)
    sched = Schedule()
    v_ref = sched.input("vals")
    sched.scatter(pattern, v_ref, reduce="add", strategy=strategy)
    step = sched.compile(mesh, blocksize=8)
    np.testing.assert_array_equal(
        np.asarray(step(step.shard_input(vals))),
        np.asarray(s(s.shard_values(vals))),
        err_msg=f"strategy={strategy}: schedule shim diverged")


# --------------------------------------------------------------------------
# the fused MoE layer (acceptance criterion): bit-identical to the
# composed dispatch + expert MLP + combine path on every rung, one
# shard_map for the whole chain
# --------------------------------------------------------------------------

def _moe_case(ndev, seed=2):
    n_tok, k, d, f = 32 * ndev, 2, 4, 8
    e_total, cap = 2 * ndev, 12
    rng = np.random.default_rng(seed)
    top_e = rng.integers(0, e_total, size=(n_tok, k))
    # power-of-two weights keep every product/sum exact in float32
    top_w = np.where(rng.random((n_tok, k)) < 0.5, 0.5, 0.25).astype(
        np.float32)
    x = rng.integers(-3, 4, (n_tok, d)).astype(np.float32)
    params = {
        "w1": rng.integers(-2, 3, (e_total, d, f)).astype(np.float32) * 0.25,
        "w2": rng.integers(-2, 3, (e_total, f, d)).astype(np.float32) * 0.25,
    }
    return n_tok, d, e_total, cap, top_e, top_w, x, params


def _composed_moe(params, top_e, top_w, n_tok, e_total, cap, mesh,
                  strategies, blocksize):
    """The back-to-back baseline: three windows, same rungs, the same
    local expert math (``moe_expert_local`` on both paths)."""
    from repro.models.moe import (MoECombineScatter, MoEDispatchGather,
                                  moe_expert_local)

    disp = MoEDispatchGather(top_e, n_tok, e_total, cap, mesh,
                             strategy=strategies["dispatch"],
                             blocksize=blocksize, hw=pm.ABEL)
    comb = MoECombineScatter(top_e, top_w, n_tok, e_total, cap, mesh,
                             strategy=strategies["combine"],
                             blocksize=blocksize, hw=pm.ABEL)
    shard = NamedSharding(mesh, P("data"))
    w1 = jax.device_put(params["w1"], shard)
    w2 = jax.device_put(params["w2"], shard)
    expert = jax.jit(jax.shard_map(
        lambda b, a, c: moe_expert_local(b, a, c),
        mesh=mesh, in_specs=(P("data"),) * 3, out_specs=P("data"),
        check_vma=False))
    return disp, lambda x: comb(expert(disp(x), w1, w2))


@pytest.mark.parametrize("strategy", STRATEGIES + ("auto",))
def test_moe_layer_bit_identical_to_composed_path(strategy):
    from repro.models.moe import MoELayer

    mesh, ndev = _mesh()
    n_tok, d, e_total, cap, top_e, top_w, x, params = _moe_case(ndev)
    layer = MoELayer(params, top_e, top_w, n_tok, e_total, cap, mesh,
                     strategy=strategy, blocksize=8, hw=pm.ABEL)
    assert set(layer.strategies) == {"dispatch", "combine"}
    disp, baseline = _composed_moe(params, top_e, top_w, n_tok, e_total,
                                   cap, mesh, layer.strategies, blocksize=8)
    xs = layer.shard_tokens(x)
    np.testing.assert_array_equal(
        np.asarray(layer(xs)), np.asarray(baseline(xs)),
        err_msg=f"strategy={strategy}: fused layer diverged from the "
                "composed dispatch+expert+combine path")


def test_moe_layer_single_shard_map_and_shared_plan(tmp_path, monkeypatch):
    """The fused step is ONE shard_map; the combine's executor tables are
    a transpose-derived delta of the dispatch's base plan (one O(nnz)
    preparation step for the whole chain); the fused window is priced."""
    from repro.models.moe import MoELayer

    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path / "plans"))
    plan_cache.clear_memory_cache()
    plan_cache.stats.reset()
    mesh, ndev = _mesh()
    n_tok, d, e_total, cap, top_e, top_w, x, params = _moe_case(ndev, seed=3)
    layer = MoELayer(params, top_e, top_w, n_tok, e_total, cap, mesh,
                     strategy="condensed", blocksize=8, hw=pm.ABEL)
    assert plan_cache.stats.misses == 1      # one O(nnz) build total
    assert plan_cache.stats.derives == 1     # one O(m*r) transpose delta
    assert layer.scatter.splan.transpose() is layer.scatter.plan

    jaxpr = jax.make_jaxpr(lambda v: layer.schedule(v))(
        layer.shard_tokens(x))
    assert _count_shard_maps(jaxpr.jaxpr) == 1, (
        "the fused step must issue all stages inside one shard_map")

    win = layer.predicted_window
    assert win is not None and win["total"] > 0
    assert win["total"] <= win["sum_standalone"]
    assert len(win["stages"]) == 2
    assert {s[1] for s in win["stages"]} == {"get", "put"}


# --------------------------------------------------------------------------
# normal equations: z = MᵀM x through one schedule
# --------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES + ("auto",))
def test_normal_equations_step_matches_reference(strategy):
    from repro.core.matrix import (EllpackMatrix, make_mesh_like_matrix,
                                   spmv_ref_np, spmv_t_ref_np)
    from repro.core.spmv import normal_equations_step

    mesh, ndev = _mesh()
    n = 64 * ndev
    m0 = make_mesh_like_matrix(n, 4, locality_window=n // 8,
                               long_range_frac=0.1, seed=4)
    rng = np.random.default_rng(4)
    m = EllpackMatrix(
        n=n, r_nz=m0.r_nz,
        diag=rng.integers(-3, 4, n).astype(np.float32),
        vals=rng.integers(-3, 4, (n, m0.r_nz)).astype(np.float32),
        cols=m0.cols)
    x = rng.integers(-3, 4, n).astype(np.float32)
    ref = spmv_t_ref_np(m, spmv_ref_np(m, x))
    step = normal_equations_step(m, mesh, strategy=strategy, blocksize=16,
                                 hw=pm.ABEL)
    z = np.asarray(step(step.shard_vector(x)))
    np.testing.assert_array_equal(z, ref)
    assert set(step.strategies) == {"gather_x", "scatter_t"}


def test_normal_equations_shares_one_base_plan(tmp_path, monkeypatch):
    from repro.core.matrix import make_mesh_like_matrix
    from repro.core.spmv import normal_equations_step

    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path / "plans"))
    plan_cache.clear_memory_cache()
    plan_cache.stats.reset()
    mesh, ndev = _mesh()
    n = 64 * ndev
    m = make_mesh_like_matrix(n, 4, locality_window=n // 8,
                              long_range_frac=0.1, seed=5)
    step = normal_equations_step(m, mesh, strategy="condensed",
                                 blocksize=16)
    assert plan_cache.stats.misses == 1
    assert plan_cache.stats.derives == 1
    assert step.predicted_window is None  # no hw in scope, fixed rungs


# --------------------------------------------------------------------------
# builder semantics
# --------------------------------------------------------------------------

def test_schedule_per_stage_strategy_override_and_pipelined_chain():
    """gather → compute → scatter in one window, with a per-stage rung
    override beating the schedule default, against the NumPy reference."""
    mesh, ndev = _mesh()
    n = 32 * ndev
    pattern, idx, vals = _case(n, n, 3, seed=6)
    rng = np.random.default_rng(6)
    x = rng.integers(-3, 4, n).astype(np.float32)

    sched = Schedule()
    x_ref = sched.input("x")
    rows = sched.constant(idx)
    v = sched.constant(vals)
    g = sched.gather(pattern, src=x_ref, strategy="replicate", name="g")
    c = sched.compute(lambda xc, r, vv: vv * xc[r], g, rows, v)
    s = sched.scatter(pattern, c, reduce="add", name="s")
    # the schedule default applies to stages without an override
    step = sched.compile(mesh, strategy="condensed", blocksize=8, output=s)
    assert step.strategies == {"g": "replicate", "s": "condensed"}
    out = np.asarray(step(step.shard_input(x)))
    ref = np.zeros(n, np.float32)
    np.add.at(ref, idx.ravel(), (vals * x[idx]).ravel())
    np.testing.assert_array_equal(out, ref)


def test_schedule_validation_errors():
    mesh, ndev = _mesh()
    n = 16 * ndev
    pattern, idx, _ = _case(n, n, 2, seed=7)
    from repro.comm import Destination
    slots = idx[:, :1].reshape(ndev, -1).astype(np.int64)
    dest = Destination.from_slots(rows=slots)

    sched = Schedule()
    x = sched.input("x")
    g = sched.gather(pattern, src=x, destination=dest)
    with pytest.raises(ValueError, match="Destination"):
        sched.scatter(pattern, g)          # dict-valued src rejected
    with pytest.raises(ValueError, match="Destination"):
        sched.compile(mesh, strategy="condensed", output=g)

    s2 = Schedule()
    vin = s2.input("v")
    with pytest.raises(ValueError, match="reduce"):
        s2.scatter(pattern, vin, reduce="mean")

    empty = Schedule()
    empty.input("x")
    with pytest.raises(AssertionError, match="at least one exchange"):
        empty.compile(mesh)


# --------------------------------------------------------------------------
# the §5 composition model (eq. 23)
# --------------------------------------------------------------------------

def test_predict_schedule_composition():
    n, p = 1 << 12, 8
    rng = np.random.default_rng(8)
    cols = rng.integers(0, n, size=(n, 4)).astype(np.int32)
    from repro.comm.plan import build_comm_plan
    from repro.comm import select
    plan = build_comm_plan(cols, n, p, blocksize=64,
                           topology=Topology(p, 4))
    wg = select.workload_from_plan(plan, 4)
    wp = select.workload_from_plan(plan.transpose(), 4)

    out = pm.predict_schedule(
        [("g", "get", wg, None), ("s", "put", wp, None)], pm.ABEL)
    times = [t for (_, _, _, t) in out["stages"]]
    # the fused window saves setup but can never beat its slowest stage
    assert out["total"] <= out["sum_standalone"]
    assert out["total"] >= max(times)
    assert out["setup_saved"] == pm.window_setup_time(wg.topology, pm.ABEL)
    # per-stage auto picks match the per-direction §5 argmins
    get_pick = min(pm.STRATEGY_PREDICTORS,
                   key=lambda s: pm.STRATEGY_PREDICTORS[s](wg, pm.ABEL))
    put_pick = min(pm.PUT_STRATEGY_PREDICTORS,
                   key=lambda s: pm.PUT_STRATEGY_PREDICTORS[s](wp, pm.ABEL))
    assert out["stages"][0][2] == get_pick
    assert out["stages"][1][2] == put_pick
    # pinning a rung prices exactly that rung
    pinned = pm.predict_schedule([("g", "get", wg, "condensed")], pm.ABEL)
    assert pinned["stages"][0][3] == pm.predict_v3(wg, pm.ABEL)
    assert pinned["setup_saved"] == 0.0   # K=1: nothing to consolidate


# --------------------------------------------------------------------------
# Heat2D full-window refinement (the ROADMAP edge-ring term), table5-style
# --------------------------------------------------------------------------

def test_heat2d_window_model_edge_ring_term():
    topo = Topology(8, 8)
    hw = pm.ABEL.replace(tau=0.0)     # isolate the compute terms
    small = pm.Heat2DWorkload(big_m=8, big_n=16, mprocs=2, nprocs=4,
                              topology=topo)
    big = pm.Heat2DWorkload(big_m=512, big_n=1024, mprocs=2, nprocs=4,
                            topology=topo)
    # skinny tiles: the four 3-wide strips recompute more than the whole
    # tile costs — overlap must NOT be predicted cheaper (the mispick the
    # ring term fixes)
    ws = pm.predict_heat2d_window(small, hw)
    assert ws["overlap"] > ws["condensed"]
    # big tiles + expensive communication: hiding the exchange behind the
    # interior wins despite the ring overhead
    wb = pm.predict_heat2d_window(big, pm.ABEL.replace(tau=1e-3))
    assert wb["overlap"] < wb["condensed"]
    # the ring term is exactly the overlap surcharge at zero comm cost
    free = pm.ABEL.replace(tau=0.0, w_remote=1e30, w_private=1e30)
    wf = pm.predict_heat2d_window(big, free)
    assert wf["overlap"] == pytest.approx(0.0, abs=1e-18)


def test_heat2d_auto_ranks_on_full_window_cost():
    """table5-style predicted-vs-measured smoke: strategy="auto" must
    carry the window-refined overlap/condensed entries, pick their argmin,
    and still match the sequential reference."""
    from repro.core.heat2d import Heat2D

    ndev = len(jax.devices())
    shape = (2, ndev // 2) if ndev % 2 == 0 and ndev > 1 else (1, ndev)
    mesh = jax.make_mesh(shape, ("data", "model"))
    big_m, big_n = shape[0] * 16, shape[1] * 16
    h = Heat2D(mesh, big_m, big_n, strategy="auto", hw=pm.ABEL)

    w2d = pm.Heat2DWorkload(big_m=big_m, big_n=big_n, mprocs=shape[0],
                            nprocs=shape[1],
                            topology=Topology(ndev, ndev))
    win = pm.predict_heat2d_window(w2d, pm.ABEL)
    assert h.predicted_times["condensed"] == win["condensed"]
    assert h.predicted_times["overlap"] == win["overlap"]
    assert h.strategy == min(h.predicted_times, key=h.predicted_times.get)
    assert h.overlap == (h.strategy == "overlap")
    assert all(np.isfinite(t) and t > 0
               for t in h.predicted_times.values())

    phi = h.init_field(6)
    got = np.asarray(h.run(phi, 3))
    np.testing.assert_allclose(got, h.reference(np.asarray(phi), 3),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# measure_hw memo keys (exchange core)
# --------------------------------------------------------------------------

class _Dev:
    def __init__(self, i):
        self.id = i


def _fake_mesh(shape, names):
    """A mesh-shaped stub: enough surface for the memo key (devices,
    axis_names, shape) without needing that many real devices."""
    import types
    m = types.SimpleNamespace()
    n = int(np.prod(shape))
    m.devices = np.array([_Dev(i) for i in range(n)],
                         dtype=object).reshape(shape)
    m.axis_names = tuple(names)
    m.shape = dict(zip(names, shape))
    return m


def test_hw_memo_keys_and_clearing(monkeypatch):
    from repro.comm import exchange

    calls = []
    monkeypatch.setattr(
        exchange, "_probe",
        lambda *a, **k: (calls.append(a), pm.ABEL)[1])
    exchange.clear_hw_memo()
    m24 = _fake_mesh((2, 4), ("a", "b"))
    m42 = _fake_mesh((4, 2), ("a", "b"))

    # multi-axis tuple key: calibrates once, then memo-hits
    h1 = exchange.measure_hw(m24, ("a", "b"))
    h2 = exchange.measure_hw(m24, ("a", "b"))
    assert len(calls) == 1 and h1 is h2
    # tuple-axis calibration describes the whole device set, so the two
    # factorizations of the SAME 8 devices share one entry
    h3 = exchange.measure_hw(m42, ("a", "b"))
    assert len(calls) == 1 and h3 is h1

    # single-axis keys: (2,4) vs (4,2) give axis "a" different ring
    # lengths on the same devices — distinct entries, one probe each
    exchange.measure_hw(m24, "a")
    exchange.measure_hw(m42, "a")
    assert len(calls) == 3
    exchange.measure_hw(m24, "a")     # memo hit
    exchange.measure_hw(m42, "a")     # memo hit
    assert len(calls) == 3

    # clear_hw_memo forces recalibration
    exchange.clear_hw_memo()
    exchange.measure_hw(m24, ("a", "b"))
    assert len(calls) == 4
    exchange.clear_hw_memo()


# --------------------------------------------------------------------------
# transpose + use_kernel: the push-side split kernels (formerly rejected)
# --------------------------------------------------------------------------

def test_spmv_transpose_kernel_matches_jnp():
    from repro.core.matrix import make_mesh_like_matrix
    from repro.core.spmv import DistributedSpMV

    mesh, ndev = _mesh()
    n = 16 * ndev
    m = make_mesh_like_matrix(n, 2, locality_window=n // 4, seed=9)
    x = np.random.default_rng(9).standard_normal(n).astype(np.float32)
    ys = {}
    for uk in (False, True):
        eng = DistributedSpMV(m, mesh, transpose=True, use_kernel=uk,
                              use_plan_cache=False)
        ys[uk] = np.asarray(eng(eng.shard_vector(x)))
    np.testing.assert_array_equal(ys[True], ys[False])
