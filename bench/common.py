"""Pieces every system driver and metric reader shares: host spans, seeds,
percentiles, the device description and the run context a reader sees.

Nothing here imports JAX at module level, so the trace reducer and the work
arithmetic stay importable without an accelerator.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import sys
import time
from typing import Any

import numpy as np


def say(msg: str) -> None:
    """One diagnostic line on standard error (standard output carries only
    the result line)."""
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def key32(seed: int, tag: str) -> int:
    """A 32-bit key for ``jax.random`` from any whole-number seed and a tag
    naming what it draws (so weights, data and traffic draw independently).
    ``--seed`` may exceed what 32 signed bits hold."""
    ss = np.random.SeedSequence([abs(int(seed)), int(seed < 0)]
                                + [ord(c) for c in tag])
    return int(ss.generate_state(1, np.uint32)[0])


def rng(seed: int, tag: str) -> np.random.Generator:
    """A numpy generator for ``seed`` and ``tag`` (see ``key32``)."""
    return np.random.default_rng(
        np.random.SeedSequence([abs(int(seed)), int(seed < 0)]
                               + [ord(c) for c in tag]))


def percentile(values, q: float) -> float:
    """The ``q``-th percentile of every sample, linear interpolation
    (numpy's default).  No tail is ever taken from medians of chunks."""
    if len(values) == 0:
        return math.nan
    return float(np.percentile(np.asarray(values, np.float64), q))


class Spans:
    """Host spans the harness records around its own calls into the program.

    Each span is kept as (name, start, end) on ``time.perf_counter`` and, in a
    traced run, also written into the profiler's trace as a
    ``TraceAnnotation`` named ``bench:<name>``, so the trace reducer can say
    what the host was doing in each idle gap of the device.
    """

    PREFIX = "bench:"

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(self.PREFIX + name)
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def total(self, name: str) -> float:
        return sum(b - a for n, a, b in self.spans if n == name)

    def first(self, name: str) -> tuple[float, float] | None:
        for n, a, b in self.spans:
            if n == name:
                return a, b
        return None


@dataclasses.dataclass
class Measured:
    """What a system driver hands back after its window and its check.

    ``end_to_end`` maps end-to-end metric names to values; ``counters`` holds
    what the per-layer readers read (counts, work, step numbers);
    ``compared`` lists (name, value, limit) for every number the check
    compared, and ``correct`` is their verdict."""

    end_to_end: dict[str, float]
    counters: dict[str, Any]
    compared: list[tuple[str, float, float]]
    correct: bool
    attempted: int
    failed: int
    memory_peak_bytes: int


@dataclasses.dataclass
class ReadContext:
    """What a per-layer metric reader sees."""

    cell: dict
    config: dict
    traffic: dict
    measured: Measured
    spans: Spans
    trace: Any            # bench.trace.TraceSummary, or None untraced
    peaks: dict
    chips: int


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    """Peak bytes in use on the fullest of the devices the cell uses."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
