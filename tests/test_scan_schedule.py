"""Scan-level schedules: persistent exchange windows across time loops.

Covers the ``Schedule.scan`` / ``ScanSchedule`` tentpole:

* an n-step scan is bit-identical to re-dispatching the compiled one-shot
  window from a Python loop (single carry, multiple carries, and the
  double-buffered feed path);
* the whole loop is ONE ``shard_map`` for any ``n_steps``, and the
  scanned ``Heat2D.run`` resolves its plans exactly once (one plan-cache
  miss, one ``measure_hw`` memo entry — no per-step O(nnz) host work);
* the scanned double-buffered Heat2D overlap loop matches the sequential
  stencil reference, like every other rung;
* ``ConjugateGradient`` converges to the ``numpy.linalg`` reference on
  every rung including ``strategy="auto"``;
* the eq.-23′ steady-state model behaves (amortization, credit floor,
  ``rank_strategies(scan_steps=...)`` re-pricing);
* builder misuse fails loudly (``compile()`` on a double-buffered graph,
  ``feed`` on a non-db gather, double feed, exchange-tainted prime,
  carry/input mismatches).

Integer-valued data keeps float sums exact, so bit-identity tests the
scheduling machinery, not float associativity.  Runs on whatever devices
the pytest process has (1 locally, 8 under the CI gate's XLA_FLAGS).
"""
import jax
import numpy as np
import pytest

from repro.comm import AccessPattern, Schedule, plan_cache
from repro.comm import exchange as exchange_mod
from repro.comm import select
from repro.comm.exchange import clear_hw_memo
from repro.comm import perfmodel as pm
from repro.core.heat2d import Heat2D
from repro.core.matrix import make_mesh_like_matrix
from repro.comm.plan import Topology
from repro.core.solvers import ConjugateGradient


def _mesh():
    ndev = len(jax.devices())
    return jax.make_mesh((ndev,), ("data",)), ndev


def _case(n, r=3, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n, size=(n, r)).astype(np.int32)
    return AccessPattern.from_indices(idx, n=n), idx


def _inner_jaxprs(param_value):
    vals = param_value if isinstance(param_value, (list, tuple)) \
        else [param_value]
    return [getattr(v, "jaxpr", v) for v in vals if hasattr(v, "jaxpr")
            or hasattr(v, "eqns")]


def _count_shard_maps(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        if "shard_map" in str(eqn.primitive):
            total += 1
        for v in eqn.params.values():
            for sub in _inner_jaxprs(v):
                total += _count_shard_maps(sub)
    return total


def _int_body(sched, pattern, idx):
    """x <- round-trip stage graph with exact integer arithmetic."""
    x = sched.input("x")
    rows = sched.constant(idx)
    g = sched.gather(pattern, src=x)
    y = sched.compute(lambda xc, r, xl: xc[r].sum(-1) - 2 * xl,
                      g, rows, x)
    return x, y


# --------------------------------------------------------------------------
# scan == python loop over the compiled one-shot window, bitwise
# --------------------------------------------------------------------------

def test_scan_matches_python_loop_bitwise():
    mesh, ndev = _mesh()
    n = 16 * ndev
    pattern, idx = _case(n)
    rng = np.random.default_rng(1)
    xv = rng.integers(-3, 4, size=n).astype(np.float32)

    sched = Schedule()
    _, y = _int_body(sched, pattern, idx)
    step = sched.compile(mesh, strategy="condensed", blocksize=8)
    ref = step.shard_input(xv)
    for _ in range(5):
        ref = step(ref)

    sched2 = Schedule()
    x2, y2 = _int_body(sched2, pattern, idx)
    loop = sched2.scan(mesh, carry=x2, output=y2,
                       strategy="condensed", blocksize=8)
    got = loop(loop.shard_input(xv), n_steps=5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # n_steps=0 is the identity
    np.testing.assert_array_equal(
        np.asarray(loop(loop.shard_input(xv), n_steps=0)), xv)


def test_multi_carry_scan_matches_numpy():
    mesh, ndev = _mesh()
    n = 16 * ndev
    pattern, idx = _case(n, seed=2)
    rng = np.random.default_rng(3)
    av = rng.integers(-3, 4, size=n).astype(np.float32)
    bv = rng.integers(-3, 4, size=n).astype(np.float32)

    sched = Schedule()
    a = sched.input("a")
    b = sched.input("b")
    rows = sched.constant(idx)
    g = sched.gather(pattern, src=a)
    a2 = sched.compute(lambda xc, r, bl: xc[r].sum(-1) + bl, g, rows, b)
    b2 = sched.compute(lambda bl: bl * 2.0, b)
    loop = sched.scan(mesh, carry=(a, b), output=(a2, b2),
                      strategy="condensed", blocksize=8)

    ra, rb = av.copy(), bv.copy()
    for _ in range(3):
        ra, rb = ra[idx].sum(-1) + rb, rb * 2.0
    fa, fb = loop(loop.shard_input(av, 0), loop.shard_input(bv, 1),
                  n_steps=3)
    np.testing.assert_array_equal(np.asarray(fa), ra)
    np.testing.assert_array_equal(np.asarray(fb), rb)


def test_double_buffer_feed_matches_in_body_gather():
    # feeding the refreshed carry is bit-identical to gathering it in-body
    # next iteration: the db value of iteration k IS gather(output k-1)
    mesh, ndev = _mesh()
    n = 16 * ndev
    pattern, idx = _case(n)
    rng = np.random.default_rng(1)
    xv = rng.integers(-3, 4, size=n).astype(np.float32)

    sched = Schedule()
    x, y = _int_body(sched, pattern, idx)
    loop = sched.scan(mesh, carry=x, output=y,
                      strategy="condensed", blocksize=8)
    want = np.asarray(loop(loop.shard_input(xv), n_steps=4))

    db = Schedule()
    xd = db.input("x")
    rows = db.constant(idx)
    gd = db.gather(pattern, double_buffer=True, prime=xd)
    yd = db.compute(lambda xc, r, xl: xc[r].sum(-1) - 2 * xl,
                    gd, rows, xd)
    db.feed(gd, yd)
    dloop = db.scan(mesh, carry=xd, output=yd,
                    strategy="condensed", blocksize=8)
    got = np.asarray(dloop(dloop.shard_input(xv), n_steps=4))
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------
# one window, one plan resolution — the no-per-step-host-work regression
# --------------------------------------------------------------------------

def test_scan_is_one_shard_map_for_any_n_steps():
    mesh, ndev = _mesh()
    n = 16 * ndev
    pattern, idx = _case(n)
    sched = Schedule()
    x, y = _int_body(sched, pattern, idx)
    loop = sched.scan(mesh, carry=x, output=y,
                      strategy="condensed", blocksize=8)
    v = loop.shard_input(np.zeros(n, np.float32))
    for steps in (1, 37):
        jaxpr = jax.make_jaxpr(
            lambda c: loop._run(steps, (c,), loop.step_args))(v)
        assert _count_shard_maps(jaxpr.jaxpr) == 1, (
            f"{steps}-step scan must trace to ONE shard_map, got "
            f"{_count_shard_maps(jaxpr.jaxpr)}")


def test_heat2d_scan_resolves_plans_and_hw_once(monkeypatch, tmp_path):
    # isolate the persistent disk cache so the count below really is the
    # number of O(nnz) plan builds this construction pays
    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path / "plans"))
    mesh2 = jax.make_mesh((1, len(jax.devices())), ("data", "model"))
    plan_cache.stats.reset()
    clear_hw_memo()
    h = Heat2D(mesh2, 8, 8 * len(jax.devices()), coef=0.1,
               strategy="auto", n_steps_hint=16)
    # TWO schedules were built (the one-shot window and the scan window)
    # over ONE O(nnz) plan build and ONE hardware calibration
    assert plan_cache.stats.misses == 1, plan_cache.stats
    assert len(exchange_mod._HW_MEMO) == 1
    phi = h.init_field(0)
    jaxpr = jax.make_jaxpr(lambda p_: h.run(p_, 16))(phi)
    assert _count_shard_maps(jaxpr.jaxpr) == 1
    # and the loop still computes the right thing on the resolved rung
    got = np.asarray(h.run(phi, 4))
    want = h.reference(np.asarray(phi), 4)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_heat2d_scan_overlap_matches_reference():
    ndev = len(jax.devices())
    shape = (2, ndev // 2) if ndev % 2 == 0 and ndev > 1 else (1, ndev)
    mesh2 = jax.make_mesh(shape, ("data", "model"))
    big_m, big_n = shape[0] * 16, shape[1] * 16
    h_ovl = Heat2D(mesh2, big_m, big_n, coef=0.07, overlap=True)
    h_cond = Heat2D(mesh2, big_m, big_n, coef=0.07, strategy="condensed")
    phi = h_ovl.init_field(3)
    want = h_ovl.reference(np.asarray(phi), 7, coef=0.07)
    np.testing.assert_allclose(np.asarray(h_ovl.run(phi, 7)), want,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_cond.run(phi, 7)), want,
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# the CG solver: convergence on every rung vs numpy.linalg
# --------------------------------------------------------------------------

def _dense(m):
    n = m.n
    a = np.zeros((n, n), np.float64)
    rows = np.repeat(np.arange(n), m.cols.shape[1]).reshape(m.cols.shape)
    np.add.at(a, (rows, m.cols), m.vals.astype(np.float64))
    a[np.arange(n), np.arange(n)] += m.diag.astype(np.float64)
    return a


@pytest.mark.parametrize("strategy", ["replicate", "blockwise", "condensed",
                                      "overlap", "auto"])
def test_cg_converges_to_linalg_reference(strategy):
    mesh, ndev = _mesh()
    m = make_mesh_like_matrix(16 * ndev, 4, seed=3)
    a = _dense(m)
    rng = np.random.default_rng(0)
    b = rng.standard_normal(m.n).astype(np.float32)
    x_ref = np.linalg.solve(a.T @ a, b.astype(np.float64))

    cg = ConjugateGradient(m, mesh, strategy=strategy, blocksize=8,
                           n_steps_hint=50)
    x = np.asarray(cg.solve(b, n_steps=50))
    rel = np.abs(x - x_ref).max() / np.abs(x_ref).max()
    assert rel < 1e-3, (strategy, rel)
    # the iterate satisfies the normal equations, not just the ref
    resid = (a.T @ a) @ x.astype(np.float64) - b
    assert np.abs(resid).max() < 1e-3 * np.abs(b).max()


# --------------------------------------------------------------------------
# the eq.-23' steady-state model
# --------------------------------------------------------------------------

def test_scan_loop_cost_properties():
    setup, t_call = 5e-4, 2e-3
    # setup paid once: n-step loop beats n re-dispatches whenever setup > 0
    for n in (2, 10, 100):
        assert pm.scan_loop_cost(t_call, setup, n) < n * t_call
    # monotone in n, linear steady state
    t10 = pm.scan_loop_cost(t_call, setup, 10)
    t20 = pm.scan_loop_cost(t_call, setup, 20)
    assert abs((t20 - t10) - 10 * (t_call - setup)) < 1e-12
    # the credit floor: an iteration can never finish before the work the
    # in-flight exchange is hiding
    credit = 1.8e-3
    t = pm.scan_loop_cost(t_call, setup, 10, overlap_credit=credit)
    assert abs(t - (setup + 10 * credit)) < 1e-12
    # degenerate: per-iter never negative
    assert pm.scan_loop_cost(1e-5, 1e-3, 10) == 1e-3


def test_predict_scan_schedule_consistency():
    n, p = 1 << 10, 8
    rng = np.random.default_rng(0)
    cols = rng.integers(0, n, size=(n, 4)).astype(np.int32)
    from repro.comm.plan import build_comm_plan
    plan = build_comm_plan(cols, n, p, blocksize=32,
                           topology=Topology(p, 4))
    w = select.workload_from_plan(plan, 4)
    stages = [("g", "get", w, None), ("s", "put",
              select.workload_from_plan(plan.transpose(), 4), None)]
    loop = pm.predict_scan_schedule(stages, pm.ABEL, 50)
    from helpers.model_error import assert_model_error
    assert loop["total"] <= loop["sum_redispatch"]
    assert_model_error(loop["total"], loop["setup"] + 50 * loop["per_iter"],
                       budget=1e-9, label="scan total = setup + n*per_iter")
    assert loop["per_call"] == pm.predict_schedule(stages, pm.ABEL)["total"]

    # rank_strategies(scan_steps=...) is exactly the per-rung re-pricing
    base = dict(select.rank_strategies(plan, 4, pm.ABEL))
    setup = pm.window_setup_time(w.topology, pm.ABEL)
    looped = dict(select.rank_strategies(plan, 4, pm.ABEL, scan_steps=50))
    assert set(looped) == set(base)
    for name, t in base.items():
        assert_model_error(looped[name], pm.scan_loop_cost(t, setup, 50),
                           budget=1e-9, label=f"scan re-pricing [{name}]")


def test_predict_heat2d_scan_amortizes():
    w = pm.Heat2DWorkload(big_m=256, big_n=512, mprocs=2, nprocs=4,
                          topology=Topology(8, 1))
    hw = pm.ABEL.replace(tau=1e-4)
    scn = pm.predict_heat2d_scan(w, hw, 100)
    assert scn["condensed"] <= scn["redispatch"]["condensed"]
    assert scn["overlap"] <= scn["redispatch"]["overlap"]
    assert scn["setup"] > 0
    for rung, per in scn["per_iter"].items():
        assert per > 0, rung


# --------------------------------------------------------------------------
# builder misuse fails loudly
# --------------------------------------------------------------------------

def test_builder_misuse_errors():
    mesh, ndev = _mesh()
    n = 16 * ndev
    pattern, idx = _case(n)

    # compile() refuses a double-buffered graph
    sched = Schedule()
    x = sched.input("x")
    g = sched.gather(pattern, double_buffer=True, prime=x)
    sched.feed(g, x)
    with pytest.raises(ValueError, match="scan"):
        sched.compile(mesh, strategy="condensed", blocksize=8)

    # feed targets only db gathers; one feed per gather; prime required
    sched = Schedule()
    x = sched.input("x")
    g_plain = sched.gather(pattern, src=x)
    with pytest.raises(ValueError, match="double_buffer"):
        sched.feed(g_plain, x)
    with pytest.raises(ValueError, match="prime"):
        sched.gather(pattern, double_buffer=True)
    with pytest.raises(ValueError, match="src"):
        sched.gather(pattern, double_buffer=True, prime=x, src=x)

    sched = Schedule()
    x = sched.input("x")
    g = sched.gather(pattern, double_buffer=True, prime=x)
    sched.feed(g, x)
    with pytest.raises(ValueError, match="feed"):
        sched.feed(g, x)

    # a prime whose ancestry contains an exchange cannot seed the prologue
    sched = Schedule()
    x = sched.input("x")
    g0 = sched.gather(pattern, src=x)
    tainted = sched.compute(lambda xc: xc[:n], g0, name="tainted")
    g1 = sched.gather(pattern, double_buffer=True, prime=tainted)
    y = sched.compute(lambda xc: xc[:n], g1)
    sched.feed(g1, y)
    with pytest.raises(ValueError, match="exchange"):
        sched.scan(mesh, carry=x, output=y,
                   strategy="condensed", blocksize=8)

    # carries must cover every input exactly once
    sched = Schedule()
    a = sched.input("a")
    b = sched.input("b")
    ga = sched.gather(pattern, src=a)
    a2 = sched.compute(lambda xc, bl: xc[:n] + bl, ga, b)
    with pytest.raises(ValueError):
        sched.scan(mesh, carry=a, output=a2,
                   strategy="condensed", blocksize=8)
