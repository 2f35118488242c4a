"""Config-driven benchmark matrix + the standing model-error gate.

One YAML (``benchmarks/matrix.yaml``) declares four axes — mesh shapes x
strategy rungs x workloads x dtypes — and this module runs their full
cartesian product through ONE generic cell runner: build the workload's
``Schedule`` on the requested mesh at the requested rung, verify against
the numpy ground truth, measure, price with the §5 models, and score the
relative model error (``perfmodel.model_error``) against the cell's
tolerance (``perfmodel.error_budget``).  ``BENCH_matrix.json`` carries the
uniform per-cell records (measured, predicted, error, budget, plan-source
telemetry) and ``matrix_bench`` returns the budget violations so
``benchmarks.run`` can exit non-zero — the paper's central claim, that the
formulas *predict* measured exchange cost, gated on every push.

The per-rung ladder machinery the bespoke ``benchmarks/tables.py`` loops
used to duplicate lives here too (``measured_ladder`` / ``ladder_volume``)
and tables.py now rides it.
"""
from __future__ import annotations

import itertools
import json
import math
import os

import numpy as np

from benchmarks.common import csv_row, drain_rows, timeit

try:  # the matrix config is YAML; everything else degrades without it
    import yaml
except ImportError:  # pragma: no cover - pyyaml ships with the image
    yaml = None

RUNGS = ("replicate", "blockwise", "condensed", "overlap")
DTYPE_BYTES = {"float32": 4, "bfloat16": 2}
DEFAULT_CONFIG = os.path.join(os.path.dirname(__file__), "matrix.yaml")
_AXIS_NAMES = ("data", "model", "ax2", "ax3")


# --------------------------------------------------------------------------
# Generic per-rung ladder (shared with benchmarks/tables.py)
# --------------------------------------------------------------------------

def ladder_volume(counts, strategy: str, p: int, n: int) -> int:
    """The per-strategy moved-element count every ladder row reports."""
    return {"replicate": p * n,
            "blockwise": counts.total_blockwise_volume()}.get(
                strategy, counts.total_condensed_volume())


def measured_ladder(prefix: str, build, *, iters: int, preds,
                    vol_of=None) -> dict:
    """Run one strategy ladder (four rungs + auto) and emit its rows.

    ``build(strategy)`` returns ``(fn, args, engine)`` with correctness
    already verified; ``preds(engine)`` prices the rungs once (a
    ``{strategy: seconds}`` mapping, evaluated on the first engine built);
    ``vol_of(engine, strategy)`` optionally reports moved elements.

    Fixed rungs emit ``{prefix}.{strategy}`` rows with the §5 prediction
    and the ``accuracy = min/max`` column; the ``auto`` row reports the
    resolved rung, the full predicted ordering, whether the pick agrees
    with the measured-best fixed rung's model ranking, and the measured
    ratio to the best fixed rung.  Returns ``{strategy: seconds}``.
    """
    results: dict[str, float] = {}
    preds_d = None
    for strategy in RUNGS + ("auto",):
        fn, args, eng = build(strategy)
        if preds_d is None:
            preds_d = dict(preds(eng))
        t = timeit(fn, *args, iters=iters)
        results[strategy] = t
        if strategy == "auto":
            best_fixed = min(v for s, v in results.items() if s != "auto")
            order = ">".join(s for s, _ in sorted(preds_d.items(),
                                                  key=lambda kv: kv[1]))
            resolved = getattr(eng, "strategy", None)
            agree = resolved == min(preds_d, key=preds_d.get)
            csv_row(f"{prefix}.auto", t * 1e6,
                    f"resolved={resolved} predicted_order={order} "
                    f"pick_agrees_with_model={agree} "
                    f"vs_best_fixed={t/best_fixed:.2f}x")
        else:
            t_pred = preds_d[strategy]
            acc = min(t, t_pred) / max(t, t_pred)
            vol = f" vol_elems={vol_of(eng, strategy)}" if vol_of else ""
            csv_row(f"{prefix}.{strategy}", t * 1e6,
                    f"predicted_us={t_pred*1e6:.1f} accuracy={acc:.2f}{vol}")
    return results


# --------------------------------------------------------------------------
# Config loading
# --------------------------------------------------------------------------

def load_matrix_config(path: str | None = None) -> dict:
    """Load + structurally validate a matrix YAML (see matrix.yaml header)."""
    if yaml is None:
        raise RuntimeError(
            "benchmarks.matrix needs pyyaml for its config; install it or "
            "pass a pre-parsed dict to run_matrix")
    with open(path or DEFAULT_CONFIG) as f:
        cfg = yaml.safe_load(f)
    for key in ("matrix", "run", "workloads"):
        if key not in cfg:
            raise ValueError(f"matrix config missing top-level {key!r}")
    axes = cfg["matrix"]
    for axis in ("mesh", "rung", "workload", "dtype"):
        if not isinstance(axes.get(axis), list) or not axes[axis]:
            raise ValueError(f"matrix.{axis} must be a non-empty list")
    for d in axes["dtype"]:
        if d not in DTYPE_BYTES:
            raise ValueError(f"unknown dtype {d!r} (have {set(DTYPE_BYTES)})")
    for w in axes["workload"]:
        if w not in cfg["workloads"]:
            raise ValueError(f"workload {w!r} has no workloads: entry")
        if w not in _BUILDERS:
            raise ValueError(f"workload {w!r} has no registered builder "
                             f"(have {sorted(_BUILDERS)})")
    return cfg


def _smoke_merge(params: dict, smoke: bool) -> dict:
    out = {k: v for k, v in params.items() if k != "smoke"}
    if smoke:
        out.update(params.get("smoke") or {})
    return out


def iter_cells(cfg: dict, smoke: bool = False):
    """The full (workload x mesh x dtype x rung) product, rungs innermost
    so consecutive cells share the pattern's cached base plan."""
    axes = cfg["matrix"]
    run = _smoke_merge(cfg["run"], smoke)
    for workload, mesh, dtype, rung in itertools.product(
            axes["workload"], axes["mesh"], axes["dtype"], axes["rung"]):
        yield {
            "workload": workload,
            "mesh": [int(x) for x in mesh],
            "dtype": dtype,
            "rung": rung,
            "params": _smoke_merge(cfg["workloads"][workload], smoke),
            "iters": int(run.get("iters", 10)),
            "warmup": int(run.get("warmup", 3)),
        }


# --------------------------------------------------------------------------
# Cell building: one adapter per workload axis entry
# --------------------------------------------------------------------------

def _cast(arr, dtype: str):
    """Round a host array to the cell dtype (bfloat16 via jnp/ml_dtypes)."""
    if dtype == "float32":
        return np.asarray(arr, np.float32)
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(np.asarray(arr)).astype(jnp.bfloat16))


def _f32(arr):
    return np.asarray(arr).astype(np.float32)


def _verify_tol(dtype: str) -> dict:
    # bf16 accumulates ~2^-8 relative error per term; the check only needs
    # to catch wrong *routing* (O(1) wrong values), not rounding
    return (dict(rtol=2e-4, atol=2e-4) if dtype == "float32"
            else dict(rtol=0.2, atol=0.2))


def _build_spmv(cell, mesh, axis_name, hw, *, skewed: bool,
                use_kernel: bool = False):
    from repro.comm.pattern import AccessPattern
    from repro.comm.schedule import Schedule
    from repro.core.matrix import make_mesh_like_matrix, spmv_ref_np

    prm, dtype = cell["params"], cell["dtype"]
    n, r_nz, seed = int(prm["n"]), int(prm["r_nz"]), int(prm.get("seed", 1))
    if skewed:
        from repro.data.skewed import make_powerlaw_matrix
        m = make_powerlaw_matrix(n, r_nz, alpha=float(prm.get("alpha", 1.1)),
                                 seed=seed)
    else:
        m = make_mesh_like_matrix(n, r_nz, locality_window=n // 64,
                                  long_range_frac=0.02, seed=seed)
    diag, vals = _cast(m.diag, dtype), _cast(m.vals, dtype)
    x_host = _cast(np.random.default_rng(seed).standard_normal(n)
                   .astype(np.float32), dtype)
    # ground truth on the dtype-rounded operands, computed in f32
    ref = spmv_ref_np(
        type(m)(n=n, r_nz=r_nz, diag=_f32(diag), vals=_f32(vals),
                cols=m.cols), _f32(x_host))

    p = math.prod(cell["mesh"])
    sched = Schedule()
    x = sched.input("x")
    dg = sched.constant(diag, name="diag")
    vl = sched.constant(vals, name="vals")
    cl = sched.constant(m.cols, name="cols")
    g = sched.gather(AccessPattern.from_ellpack(m), src=x, name="exchange")
    sched.compute(lambda xc, d_, v_, c_, xl: d_ * xl + (v_ * xc[c_]).sum(-1),
                  g, dg, vl, cl, x, name="spmv")
    step = sched.compile(mesh, axis_name=axis_name, strategy=cell["rung"],
                         blocksize=max(8, n // p // 16), hw=hw,
                         use_kernel=use_kernel)
    xs = step.shard_input(x_host)
    np.testing.assert_allclose(_f32(step(xs)), ref, **_verify_tol(dtype))
    return step, (xs,), step.strategies["exchange"]


def _build_moe_dispatch(cell, mesh, axis_name, hw):
    from repro.comm.pattern import AccessPattern
    from repro.comm.schedule import Schedule
    from repro.models.moe import (moe_dispatch_pattern, moe_dispatch_ref,
                                  random_router)

    prm, dtype = cell["params"], cell["dtype"]
    n_tok, d = int(prm["n_tok"]), int(prm["d"])
    k, e_total = int(prm.get("k", 2)), int(prm.get("e_total", 32))
    seed = int(prm.get("seed", 3))
    p = math.prod(cell["mesh"])
    cap = int(1.25 * n_tok * k / e_total)
    top_e, _ = random_router(seed, n_tok, e_total, k)
    idx, valid = moe_dispatch_pattern(top_e, n_tok, e_total, cap, p)
    x_host = _cast(np.random.default_rng(seed)
                   .standard_normal((n_tok, d)).astype(np.float32), dtype)
    ref = moe_dispatch_ref(_f32(x_host), idx, valid,
                           e_total, cap).reshape(-1, d)

    sched = Schedule()
    x = sched.input("x")
    sl = sched.constant(idx, name="slots")
    vm = sched.constant(_cast(valid.astype(np.float32), dtype), name="valid")
    g = sched.gather(AccessPattern.from_indices(idx, n=n_tok), src=x,
                     name="exchange")
    sched.compute(lambda xc, s_, v_: xc[s_] * v_[:, None], g, sl, vm,
                  name="dispatch")
    step = sched.compile(mesh, axis_name=axis_name, strategy=cell["rung"],
                         blocksize=max(8, n_tok // p // 16), hw=hw)
    xs = step.shard_input(x_host)
    # dispatch is pure data movement: bf16 values move bit-exactly
    np.testing.assert_allclose(_f32(step(xs)), ref, rtol=1e-6, atol=1e-6)
    return step, (xs,), step.strategies["exchange"]


def _build_gnn(cell, mesh, axis_name, hw):
    from repro.models.gnn import (GNNNeighborAggregate, gnn_ref_np,
                                  random_neighbors)

    prm, dtype = cell["params"], cell["dtype"]
    n, r, d = int(prm["n"]), int(prm["r"]), int(prm["d"])
    seed = int(prm.get("seed", 4))
    p = math.prod(cell["mesh"])
    nbrs = random_neighbors(n, r, alpha=float(prm.get("alpha", 0.0)),
                            seed=seed)
    h_host = _cast(np.random.default_rng(seed)
                   .standard_normal((n, d)).astype(np.float32), dtype)
    layer = GNNNeighborAggregate(nbrs, n, mesh, axis_name=axis_name,
                                 strategy=cell["rung"],
                                 blocksize=max(8, n // p // 16), hw=hw)
    hs = layer.shard_features(h_host)
    np.testing.assert_allclose(_f32(layer(hs)),
                               gnn_ref_np(_f32(h_host), nbrs),
                               **_verify_tol(dtype))
    resolved = "+".join(layer.strategies[s] for s in ("gather_nbrs",
                                                      "scatter_upd"))
    return layer, (hs,), resolved


def _elem_bytes(cell) -> int:
    """hw.elem for the cell: dtype width, feature width folded in (every
    moved element of the token/feature workloads is one d-wide row)."""
    width = DTYPE_BYTES[cell["dtype"]]
    d = cell["params"].get("d")
    return width * int(d) if d else width


_BUILDERS = {
    "spmv": lambda cell, mesh, ax, hw: _build_spmv(cell, mesh, ax, hw,
                                                   skewed=False),
    "spmv_skewed": lambda cell, mesh, ax, hw: _build_spmv(cell, mesh, ax, hw,
                                                          skewed=True),
    # the same exchange driven through the fused Pallas pack/unpack kernels
    # (use_kernel=True), priced by the kernel-variant §5 compute terms
    "spmv_kernel": lambda cell, mesh, ax, hw: _build_spmv(
        cell, mesh, ax, hw, skewed=False, use_kernel=True),
    "moe_dispatch": _build_moe_dispatch,
    "gnn": _build_gnn,
}


# --------------------------------------------------------------------------
# The runner + the model-error gate
# --------------------------------------------------------------------------

def _get_mesh(shape: tuple[int, ...], cache: dict):
    import jax
    from repro import compat

    if shape not in cache:
        ndev = len(jax.devices())
        if math.prod(shape) > ndev:
            raise RuntimeError(
                f"mesh {list(shape)} needs {math.prod(shape)} devices, have "
                f"{ndev} (run via benchmarks.run, which forces 8)")
        names = _AXIS_NAMES[:len(shape)]
        mesh = compat.make_mesh(shape, names,
                                axis_types=compat.auto_axis_types(len(shape)))
        cache[shape] = (mesh, names[0] if len(shape) == 1 else names)
    return cache[shape]


def run_cell(cell: dict, mesh, axis_name, predict_scale: float = 1.0) -> dict:
    """Build, verify, measure and score ONE matrix cell."""
    from repro.comm import telemetry
    from repro.comm.exchange import measure_hw
    from repro.comm import perfmodel as pm

    hw = measure_hw(mesh, axis_name).replace(elem=_elem_bytes(cell))
    snap = telemetry.stats.snapshot()
    step, args, resolved = _BUILDERS[cell["workload"]](cell, mesh, axis_name,
                                                       hw)
    tel = telemetry.stats.since(snap)
    source = max(pm.PLAN_SOURCES, key=lambda s: tel.get(s, 0))
    if tel.get(source, 0) == 0:
        source = "host-build"   # no acquisition recorded: price the worst

    measured = timeit(step, *args, iters=cell["iters"],
                      warmup=cell["warmup"])
    predicted = float(step.predicted_window["total"]) * float(predict_scale)
    err = round(pm.model_error(measured, predicted), 4)
    budget = pm.error_budget(cell)
    return {
        "workload": cell["workload"],
        "mesh": cell["mesh"],
        "rung": cell["rung"],
        "dtype": cell["dtype"],
        "resolved": resolved,
        "measured_us": round(measured * 1e6, 1),
        "predicted_us": round(predicted * 1e6, 1),
        "model_error": err,
        "budget": budget,
        "within_budget": bool(err <= budget),
        "plan_source": source,
        "plan_acquisitions": {s: int(tel[s]) for s in
                              telemetry.PLAN_SOURCES + telemetry.TICK_KINDS},
    }


def run_matrix(cfg: dict, smoke: bool = False) -> tuple[list, list]:
    """Run every cell; returns ``(cells, violations)`` and emits one
    ``matrix.<workload>.<mesh>.<rung>.<dtype>`` csv row per cell."""
    scales = cfg.get("predict_scale") or {}
    mesh_cache: dict = {}
    cells, violations = [], []
    for cell in iter_cells(cfg, smoke):
        mesh, axis_name = _get_mesh(tuple(cell["mesh"]), mesh_cache)
        res = run_cell(cell, mesh, axis_name,
                       predict_scale=scales.get(cell["workload"], 1.0))
        cells.append(res)
        tag = "x".join(map(str, res["mesh"]))
        name = (f"matrix.{res['workload']}.{tag}.{res['rung']}"
                f".{res['dtype']}")
        csv_row(name, res["measured_us"],
                f"predicted_us={res['predicted_us']} "
                f"model_error={res['model_error']} "
                f"budget={res['budget']:g} "
                f"within_budget={res['within_budget']} "
                f"resolved={res['resolved']} "
                f"plan_source={res['plan_source']}")
        if not res["within_budget"]:
            violations.append(
                f"{name}: model_error {res['model_error']} exceeds budget "
                f"{res['budget']:g} (measured={res['measured_us']}us "
                f"predicted={res['predicted_us']}us)")
    return cells, violations


def write_matrix_json(cells: list, rows: list, smoke: bool,
                      path: str = "BENCH_matrix.json") -> None:
    from repro.comm import telemetry

    payload = {"bench": "matrix", "smoke": smoke, "rows": rows,
               "cells": cells, "telemetry": telemetry.stats.snapshot()}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {path} ({len(cells)} cells)")


def matrix_bench(smoke: bool = False, config: str | None = None,
                 out_path: str = "BENCH_matrix.json") -> list:
    """The ``benchmarks.run matrix`` entry point.

    Runs the configured matrix, writes ``BENCH_matrix.json`` (rows +
    per-cell records + plan telemetry) and returns the list of model-error
    budget violations — the caller exits non-zero on any.
    """
    cfg = load_matrix_config(config)
    n_cells = len(list(iter_cells(cfg, smoke)))
    print(f"# matrix: {n_cells} cells "
          f"(mesh x rung x workload x dtype from "
          f"{config or DEFAULT_CONFIG}); model-error gate armed")
    drain_rows()   # cell rows only in the artifact, wherever we ran from
    cells, violations = run_matrix(cfg, smoke)
    write_matrix_json(cells, drain_rows(), smoke, path=out_path)
    worst = max(cells, key=lambda c: c["model_error"] / c["budget"])
    print(f"# matrix: worst cell {worst['workload']}.{worst['rung']}"
          f".{worst['dtype']} model_error={worst['model_error']} "
          f"(budget {worst['budget']:g}); violations={len(violations)}")
    return violations
