"""Shared benchmark utilities: timing, host calibration, CSV output."""
from __future__ import annotations

import time

import jax
import numpy as np

__all__ = ["timeit", "csv_row", "drain_rows", "calibrate_host"]

# every csv_row also lands here so the runner can persist a machine-
# readable copy (BENCH_table3.json) next to the human-readable CSV
_rows: list[dict] = []


def timeit(fn, *args, iters: int = 10, warmup: int = 3) -> float:
    """Median wall seconds per call (blocking on the result)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def csv_row(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.1f},{derived}")
    _rows.append({"name": name, "us_per_call": round(float(us_per_call), 1),
                  "derived": derived})


def drain_rows() -> list[dict]:
    """All rows emitted since the last drain (for the JSON artifact)."""
    rows = list(_rows)
    _rows.clear()
    return rows


def calibrate_host(elem_bytes: int = 4):
    """Measure the paper's four hardware parameters on THIS host (§6.2).

    Delegates to ``repro.comm.exchange.measure_hw`` — the one memoized
    calibration the ``strategy="auto"`` engine uses — so benchmarks and the
    autotuner always see identical numbers.  Host devices are one-core XLA
    threads, so each device is modeled as its own "node" during validation:
    every inter-device message pays tau, exactly like the paper's
    inter-node accesses."""
    from repro.comm.exchange import measure_hw

    return measure_hw(elem_bytes=elem_bytes)
