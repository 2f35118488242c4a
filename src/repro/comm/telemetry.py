"""Plan-source telemetry — where did each exchange's executor tables come from?

The paper's "one-time preparation step" (§4.3.1) stops being one-time the
moment the access pattern changes per batch: at traffic rates the question
"did this exchange pay a host plan build?" is the difference between a hot
path and a stall.  This module counts, per process, how every plan was
obtained:

* ``memory-hit``    — exact plan served from the in-process LRU;
* ``disk-hit``      — exact plan loaded from the persistent cache;
* ``bucket-reuse``  — a compatible cached *envelope* plan reused after the
  pattern's quantized stats matched (``plan_cache.get_envelope_plan``);
* ``device-derive`` — executor tables computed in-jit from the batch's
  routing (``comm.dynamic``), no host round-trip at all;
* ``host-build``    — the full O(nnz) host preparation step ran.

Build latency is accumulated per source so the §5 ``T_plan`` model
(``perfmodel.plan_build_time``) can be validated against what actually
happened.  The counters are surfaced as the ``telemetry`` block of
``BENCH_table3.json`` and asserted by the dynamic-MoE acceptance test
("N distinct routings, zero host builds after warmup").

Thread-safe like ``plan_cache.CacheStats`` (bump under a lock); tests use
``isolated()`` instead of mutating the module-global ``stats``.

Beyond the plan sources, a second counter group ticks the *serving loop*
(``TICK_KINDS``): the continuous-batching engine (``repro.serve``) bumps
``decode_steps`` once per jitted decode tick and ``prefill_chunks`` once
per prefill chunk, so "zero host plan-builds during steady-state decode"
is an assertable interval fact: snapshot, run N ticks, check
``since(snap)`` shows ``decode_steps >= N`` and ``host-build == 0``
(``decode_host_free`` packages exactly that).

>>> from repro.comm import telemetry
>>> with telemetry.isolated() as t:
...     telemetry.record("host-build", seconds=0.25)   # warmup
...     snap = t.snapshot()
...     telemetry.record("device-derive")
...     telemetry.record("device-derive")
...     telemetry.record_tick("decode_steps")
>>> t.snapshot()["sources"]["device-derive"], t.snapshot()["sources"]["host-build"]
(2, 1)
>>> t.snapshot()["build_seconds"]["host-build"]
0.25
>>> t.host_free(warmup=1)   # after the 1-record warmup, no host builds
True
>>> delta = t.since(snap)
>>> delta["host-build"], delta["decode_steps"]
(0, 1)
>>> t.decode_host_free(snap)   # >=1 decode tick, 0 host builds since snap
True

A third group times the host: ``span(name)`` accumulates seconds and a
count per name on the active object (``snapshot()["spans"]``) and, while a
``jax.profiler`` trace runs, lies in it as ``repro.<name>`` on the clock
of the device's ops.  The library opens these spans, none inside another:

* ``plan.key`` (content hashes), ``plan.load`` (disk read, inflate and
  deserialise), ``plan.build`` (the O(nnz) host build or scatter
  derivation: what ``build_seconds["host-build"]`` accumulates),
  ``plan.store`` (disk write), ``plan.destination`` (a consumer's
  ``Destination`` slot tables and attaching them to a plan) — in
  ``plan_cache`` and ``IrregularGather``;
* ``serve.prefill`` (one admitted prompt's chunked prefill) — in
  ``repro.serve``;
* ``comm.measure_hw`` (the §5.4 calibration) and ``comm.rank`` (the auto
  ranking) — in the exchange front doors;
* ``spmv.split`` (the vals splits), ``spmv.place`` (the engine's
  ``device_put`` of matrix and extra plan tables), ``spmv.call`` (each
  product) — in ``core.spmv``.

A ``jax.monitoring`` listener adds ``compiles`` and ``compile_s``: XLA
compilations and persistent compile-cache loads (``cache_loads`` of
them), and their seconds.

``record_dest_slots`` counts compact destinations: ``dest_compact`` (how
many were built), ``dest_slots`` (the slots per device each delivers) and
``dest_slots_dense`` (the dense per-device table each replaced).  The
overlap SpMV records one per engine.

>>> with telemetry.isolated() as t:
...     telemetry.record_dest_slots(delivered=3, dense=60)
>>> d = t.since({})
>>> d["dest_compact"], d["dest_slots"], d["dest_slots_dense"]
(1, 3, 60)

``moe_dropped`` counts MoE token-to-expert assignments that a capacity
bound dropped; the serving engine records it from the device's running
count (``ServeEngine.report``).  ``record_mla_decode`` counts the decode
steps that ran the latent-attention kernel (``mla_kernel_steps``) and the
latent rows it read per layer (``mla_rows_read``, whole blocks); the
engine records both at each finished tick from the lanes' positions.

>>> with telemetry.isolated() as t:
...     with telemetry.span("plan.key"):
...         pass
>>> t.snapshot()["spans"]["plan.key"]["count"]
1
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

__all__ = ["PLAN_SOURCES", "TICK_KINDS", "PlanTelemetry", "stats",
           "record", "record_tick", "record_dest_slots", "record_moe_dropped",
           "record_mla_decode",
           "span", "isolated",
           "watch_compiles"]

# Ordered from cheapest to most expensive way of obtaining a plan.
PLAN_SOURCES = ("memory-hit", "disk-hit", "bucket-reuse", "device-derive",
                "host-build")

# Serving-loop tick counters (repro.serve): one bump per jitted decode
# tick / per prefill chunk — the denominator for "zero host builds while
# the loop was actually decoding".
TICK_KINDS = ("decode_steps", "prefill_chunks")

SPAN_PREFIX = "repro."

# jax.monitoring events: one backend-compile duration per XLA compilation
# or persistent-cache load (the load happens inside it), and one
# cache-hit event per load
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# integer counters diffed by ``since``: compiles, and compact destinations
_COUNTERS = ("compiles", "cache_loads", "dest_compact", "dest_slots",
             "dest_slots_dense", "moe_dropped", "mla_kernel_steps",
             "mla_rows_read")


class PlanTelemetry:
    """Per-exchange plan-source counters + accumulated build latency."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with getattr(self, "_lock", threading.Lock()):
            self.sources = {s: 0 for s in PLAN_SOURCES}
            self.build_seconds = {s: 0.0 for s in PLAN_SOURCES}
            self.ticks = {k: 0 for k in TICK_KINDS}
            # ordinals of the host-build records, all ``host_free`` needs
            # (a serving process records a device-derive every tick)
            self._host_builds: list[int] = []
            self.spans: dict[str, list] = {}     # name -> [seconds, count]
            self.compiles = 0
            self.cache_loads = 0
            self.compile_s = 0.0
            self.dest_compact = 0
            self.dest_slots = 0
            self.dest_slots_dense = 0
            self.moe_dropped = 0
            self.mla_kernel_steps = 0
            self.mla_rows_read = 0

    def record(self, source: str, seconds: float = 0.0) -> None:
        if source not in PLAN_SOURCES:
            raise ValueError(
                f"unknown plan source {source!r}; expected one of "
                f"{PLAN_SOURCES}")
        with self._lock:
            if source == "host-build":
                self._host_builds.append(sum(self.sources.values()))
            self.sources[source] += 1
            self.build_seconds[source] += float(seconds)

    def add_span(self, name: str, seconds: float) -> None:
        """Accumulate one closed span (``span`` calls this)."""
        with self._lock:
            acc = self.spans.setdefault(name, [0.0, 0])
            acc[0] += float(seconds)
            acc[1] += 1

    def add_compile(self, seconds: float) -> None:
        with self._lock:
            self.compiles += 1
            self.compile_s += float(seconds)

    def add_cache_load(self) -> None:
        with self._lock:
            self.cache_loads += 1

    def record_dest_slots(self, delivered: int, dense: int) -> None:
        """One compact ``Destination``: ``delivered`` slots per device in
        place of a ``dense`` per-device table."""
        with self._lock:
            self.dest_compact += 1
            self.dest_slots += int(delivered)
            self.dest_slots_dense += int(dense)

    def record_moe_dropped(self, n: int) -> None:
        """Count ``n`` MoE token-to-expert assignments dropped past an
        expert's capacity."""
        with self._lock:
            self.moe_dropped += int(n)

    def record_mla_decode(self, steps: int, rows: int) -> None:
        """Count ``steps`` decode steps through the latent-attention kernel
        that read ``rows`` latent rows a layer between them."""
        with self._lock:
            self.mla_kernel_steps += int(steps)
            self.mla_rows_read += int(rows)

    def record_tick(self, kind: str, n: int = 1) -> None:
        """Bump a serving-loop counter (a ``TICK_KINDS`` name) by ``n``."""
        if kind not in TICK_KINDS:
            raise ValueError(
                f"unknown tick kind {kind!r}; expected one of {TICK_KINDS}")
        with self._lock:
            self.ticks[kind] += int(n)

    @property
    def total(self) -> int:
        return sum(self.sources.values())

    def snapshot(self) -> dict:
        """A deep, detached copy — safe to compare across later records."""
        with self._lock:
            return {
                "sources": dict(self.sources),
                "build_seconds": dict(self.build_seconds),
                "ticks": dict(self.ticks),
                "total": sum(self.sources.values()),
                "spans": {k: {"seconds": s, "count": c}
                          for k, (s, c) in self.spans.items()},
                "compile_s": self.compile_s,
                **{k: getattr(self, k) for k in _COUNTERS},
            }

    def since(self, snap: dict) -> dict:
        """Per-source (and per-tick-kind) deltas between ``snap`` (a
        ``snapshot()``) and now, the compile and compact-destination
        counters' deltas, and under ``"spans"`` the seconds and count of
        each span closed since.  Older snapshots are accepted — missing
        keys count from 0."""
        cur = self.snapshot()
        out = {s: cur["sources"][s] - snap.get("sources", {}).get(s, 0)
               for s in PLAN_SOURCES}
        prev_ticks = snap.get("ticks", {})
        out.update({k: cur["ticks"][k] - prev_ticks.get(k, 0)
                    for k in TICK_KINDS})
        for k in _COUNTERS + ("compile_s",):
            out[k] = cur[k] - snap.get(k, 0)
        prev = snap.get("spans", {})
        out["spans"] = {}
        for name, v in cur["spans"].items():
            p = prev.get(name, {"seconds": 0.0, "count": 0})
            if v["count"] > p["count"]:
                out["spans"][name] = {"seconds": v["seconds"] - p["seconds"],
                                      "count": v["count"] - p["count"]}
        return out

    def decode_host_free(self, snap: dict) -> bool:
        """The serving acceptance criterion: since ``snap``, at least one
        decode tick ran and NO plan came from the host O(nnz) build."""
        delta = self.since(snap)
        return delta["decode_steps"] > 0 and delta["host-build"] == 0

    def host_free(self, warmup: int = 0) -> bool:
        """True when no record after the first ``warmup`` came from
        ``host-build`` (every later plan came from a hot-path source) —
        the dynamic-MoE acceptance criterion."""
        with self._lock:
            return all(i < warmup for i in self._host_builds)


# Module-global telemetry; swap it out with ``isolated()`` in tests.
stats = PlanTelemetry()


def record(source: str, seconds: float = 0.0) -> None:
    """Record one plan acquisition on the active telemetry object."""
    stats.record(source, seconds)


def record_tick(kind: str, n: int = 1) -> None:
    """Bump a serving-loop tick counter on the active telemetry object."""
    stats.record_tick(kind, n)


def record_dest_slots(delivered: int, dense: int) -> None:
    """Count one compact ``Destination`` on the active telemetry object."""
    stats.record_dest_slots(delivered, dense)


def record_moe_dropped(n: int) -> None:
    """Count dropped MoE assignments on the active telemetry object."""
    stats.record_moe_dropped(n)


def record_mla_decode(steps: int, rows: int) -> None:
    """Count latent-attention kernel steps and the rows they read a layer
    on the active telemetry object."""
    stats.record_mla_decode(steps, rows)


class _Timer:
    seconds = 0.0


@contextlib.contextmanager
def span(name: str):
    """Time a host phase: seconds and a count accumulate under ``name`` on
    the telemetry object active at entry, and a running ``jax.profiler``
    trace holds it as ``repro.<name>``.  Yields an object whose
    ``seconds`` holds the span's length once it has closed."""
    tel = stats
    timer = _Timer()
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        t0 = time.perf_counter()
        try:
            yield timer
        finally:
            timer.seconds = time.perf_counter() - t0
            tel.add_span(name, timer.seconds)


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        stats.add_compile(duration)


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        stats.add_cache_load()


def watch_compiles() -> None:
    """Count compilations on the active telemetry object from now on.
    Registers the ``jax.monitoring`` listeners once per process; calling
    it again (or reloading this module, which keeps its namespace) adds
    none."""
    if globals().get("_watching"):
        return
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    globals()["_watching"] = True


@contextlib.contextmanager
def isolated():
    """Capture-safe scope: a fresh ``PlanTelemetry`` becomes the module
    global for the duration, the previous one is restored after — tests
    never mutate (or race on) the process-wide counters."""
    global stats
    prev = stats
    stats = PlanTelemetry()
    try:
        yield stats
    finally:
        stats = prev


watch_compiles()
