"""Model-driven strategy autotuner (comm.select and the one hardware
calibration, comm.exchange.measure_hw): ranking is a faithful sort of
the §5 predictions, ``strategy="auto"`` resolves to a runnable rung that
matches the reference, and (subprocess, 8 devices) the predicted ranking
tracks the measured one."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.comm import perfmodel as pm
from repro.comm import select
from repro.comm.exchange import measure_hw
from repro.comm.plan import Topology, build_comm_plan
from repro.comm.strategies import STRATEGIES
from repro.core.matrix import make_mesh_like_matrix, spmv_ref_np

ABEL = pm.ABEL


def _plan(p=16, shard=4096, r_nz=16, nodes=4, long_frac=0.05, bs=256,
          window_div=64):
    n = p * shard
    m = make_mesh_like_matrix(n, r_nz, locality_window=n // window_div,
                              long_range_frac=long_frac, seed=1)
    topo = Topology(p, p // nodes)
    return build_comm_plan(m.cols, n, p, blocksize=bs, topology=topo), r_nz


def test_rank_is_sorted_and_complete():
    plan, r_nz = _plan()
    ranked = select.rank_strategies(plan, r_nz, ABEL)
    names = [s for s, _ in ranked]
    times = [t for _, t in ranked]
    assert sorted(names) == sorted(STRATEGIES)
    assert times == sorted(times)
    assert all(t > 0 and np.isfinite(t) for t in times)


def test_rank_matches_predictors_exactly():
    plan, r_nz = _plan()
    w = select.workload_from_plan(plan, r_nz)
    ranked = dict(select.rank_strategies(plan, r_nz, ABEL))
    from helpers.model_error import assert_model_error
    for rung, direct in (("condensed", pm.predict_v3(w, ABEL)),
                         ("blockwise", pm.predict_v2(w, ABEL)),
                         ("replicate", pm.predict_replicate(w, ABEL)),
                         ("overlap", pm.predict_overlap(w, ABEL))):
        assert_model_error(ranked[rung], direct, budget=1e-6,
                           label=f"rank_strategies vs predictor [{rung}]")


def test_overlap_never_predicted_slower_than_condensed():
    """Overlap hides the memput phase behind own compute and drops the
    eq.-14 copy, so the model must never rank it behind condensed."""
    for kwargs in (dict(), dict(long_frac=0.3), dict(nodes=1),
                   dict(p=8, shard=512, nodes=2, bs=64)):
        plan, r_nz = _plan(**kwargs)
        w = select.workload_from_plan(plan, r_nz)
        assert pm.predict_overlap(w, ABEL) <= pm.predict_v3(w, ABEL) * (1 + 1e-9)


def test_condensed_family_wins_at_paper_scale():
    """Paper Table 3 regime: multi-node, large shards, mostly-local pattern
    -> the condensed family (condensed/overlap) must be the model's pick,
    and blockwise must rank last (whole-block volume tax)."""
    plan, r_nz = _plan(p=16, shard=16384, long_frac=0.002, bs=256,
                       window_div=256)
    ranked = select.rank_strategies(plan, r_nz, ABEL)
    assert ranked[0][0] in ("condensed", "overlap")
    assert ranked[-1][0] == "blockwise"


def test_choose_respects_candidates():
    plan, r_nz = _plan()
    assert select.choose_strategy(
        plan, r_nz, hw=ABEL,
        candidates=("replicate", "blockwise")) in ("replicate", "blockwise")


def test_hw_calibration_memoized_and_sane():
    hw1 = measure_hw()
    hw2 = measure_hw()
    assert hw1 is hw2  # per-process memoization: one calibration per mesh
    assert hw1.w_private > 0 and hw1.w_remote > 0 and hw1.tau > 0
    assert 16 <= hw1.cacheline <= 4096


def test_one_calibration_per_key(monkeypatch):
    """An ``auto`` gather, then ``choose_strategy`` and ``blocksize_sweep``
    with no hardware given, on the same mesh and axis, run the §5.4 probes
    once; another element size or ``force=True`` runs them again."""
    import jax
    from repro.comm import AccessPattern, IrregularGather, exchange

    probes = []

    def fake_probe(mesh, axis, elem_bytes):
        probes.append((axis, elem_bytes))
        return pm.ABEL.replace(elem=elem_bytes)

    monkeypatch.setattr(exchange, "_probe", fake_probe)
    exchange.clear_hw_memo()
    ndev = len(jax.devices())
    mesh = jax.make_mesh((ndev,), ("data",))
    n = 64 * ndev
    m = make_mesh_like_matrix(n, 4, locality_window=n // 8,
                              long_range_frac=0.1, seed=3)
    g = IrregularGather(AccessPattern.from_ellpack(m), mesh,
                        strategy="auto", blocksize="auto")
    assert select.choose_strategy(g.plan, 4, mesh=mesh,
                                  axis_name="data") in STRATEGIES
    assert select.blocksize_sweep(m.cols, n, ndev)
    assert probes == [("data", 4)]
    assert measure_hw(mesh, "data") is g.hw
    assert measure_hw(mesh, "data", elem_bytes=8).elem == 8
    assert len(probes) == 2
    measure_hw(mesh, "data", force=True)
    assert probes == [("data", 4), ("data", 8), ("data", 4)]
    exchange.clear_hw_memo()


def test_auto_engine_matches_reference():
    import jax
    from repro.core.spmv import DistributedSpMV

    ndev = len(jax.devices())
    mesh = jax.make_mesh((ndev,), ("data",))
    n = 128 * ndev
    m = make_mesh_like_matrix(n, 8, locality_window=n // 8,
                              long_range_frac=0.05, seed=2)
    eng = DistributedSpMV(m, mesh, strategy="auto", blocksize=32)
    assert eng.requested_strategy == "auto"
    assert eng.strategy in STRATEGIES
    assert set(eng.predicted_times) == set(STRATEGIES)
    x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
    np.testing.assert_allclose(np.asarray(eng(eng.shard_vector(x))),
                               spmv_ref_np(m, x), rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_predicted_ranking_tracks_measured_8dev():
    helpers = os.path.join(os.path.dirname(__file__), "helpers")
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(helpers, "check_autotune.py")],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, (
        f"check_autotune failed:\nSTDOUT:{proc.stdout[-3000:]}\n"
        f"STDERR:{proc.stderr[-3000:]}")
    assert "AUTOTUNE_OK" in proc.stdout
