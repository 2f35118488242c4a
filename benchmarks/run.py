"""Benchmark harness entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Multi-device benchmarks need 8
host devices, so this module RE-EXECS itself with the XLA flag when invoked
with a single device (keeping plain ``python -m benchmarks.run`` working).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run table3 table5
  python -m benchmarks.run table3 --smoke            # CI-sized quick pass
  python -m benchmarks.run matrix --smoke            # config-driven matrix

``matrix`` runs the declarative mesh x rung x workload x dtype product
from ``benchmarks/matrix.yaml`` (override with ``--config=PATH``), writes
``BENCH_matrix.json`` itself, and makes the process exit non-zero when any
cell's predicted-vs-measured drift exceeds its ``perfmodel.error_budget``
— the standing model-error regression gate.
"""
from __future__ import annotations

import inspect
import os
import sys


def _ensure_devices():
    if "--no-reexec" in sys.argv:
        sys.argv.remove("--no-reexec")
        return
    if os.environ.get("XLA_FLAGS", "").find("device_count") < 0:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
        os.execvpe(sys.executable,
                   [sys.executable, "-m", "benchmarks.run", "--no-reexec"]
                   + sys.argv[1:], env)


def _write_bench_json(name: str, rows, smoke: bool) -> None:
    """Machine-readable per-PR perf trajectory (BENCH_<name>.json at the
    repo root, next to the CSV the CI job tees) — every csv_row of the
    bench, schedule + scatter rows included.  table3 additionally carries
    the plan-acquisition telemetry of the whole bench run (where every
    executor table came from: memory/disk/bucket/device/host — the §5
    T_plan closure; see repro.comm.telemetry)."""
    import json

    payload = {"bench": name, "smoke": smoke, "rows": rows}
    if name == "table3":
        from repro.comm import telemetry

        payload["telemetry"] = telemetry.stats.snapshot()
    path = f"BENCH_{name}.json"
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {path} ({len(rows)} rows)")


def main() -> None:
    _ensure_devices()
    from benchmarks import common, tables

    smoke = "--smoke" in sys.argv[1:]
    config = None
    for arg in sys.argv[1:]:
        if arg.startswith("--config="):
            config = arg.split("=", 1)[1]
    which = [a for a in sys.argv[1:] if not a.startswith("-")]
    all_benches = {
        "table2": tables.table2_privatization,
        "table3": tables.table3_strategies,
        "table4": tables.table4_model_validation,
        "fig2": tables.fig2_volumes,
        "table5": tables.table5_heat2d,
        "serve": tables.table_serve,
        "matrix": None,  # dispatched below: writes its own JSON + gates
    }
    if not which:
        which = list(all_benches)
    print("name,us_per_call,derived")
    violations: list[str] = []
    for name in which:
        if name == "matrix":
            from benchmarks import matrix

            violations.extend(matrix.matrix_bench(smoke=smoke,
                                                  config=config))
            continue
        fn = all_benches[name]
        common.drain_rows()
        if smoke and "smoke" in inspect.signature(fn).parameters:
            fn(smoke=True)
        else:
            fn()
        if name in ("table3", "table5", "serve") and smoke:
            _write_bench_json(name, common.drain_rows(), smoke)
    if violations:
        print(f"# FAIL: {len(violations)} matrix cell(s) exceed their "
              "model-error budget", file=sys.stderr)
        for v in violations:
            print(f"#   {v}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
