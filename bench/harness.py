"""The benchmark harness: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name.  The cell is an entry of ``BENCHMARK.json``'s
``workloads``; its configuration file is named there; its traffic mix is
``bench/traffic/<traffic>.json``; the configuration's ``system`` names the
driver ``bench/systems/<system>.py``; each per-layer metric is read by
``bench/metrics/<metric name>.py``.  A later change adds a cell, a
configuration, a mix or a metric by adding files and entries.

Each run loads, warms up, measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints one JSON object as
the last line of standard output.  Diagnostics go to standard error, which
ends with each number compared beside its limit.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

from bench.common import ReadContext, Spans, device_info, say
from bench.peaks import peaks_for

PACKAGE = Path(__file__).resolve().parent
CACHE = ".cache"          # under bench/, listed in bench/.gitignore


def find_file(root: Path, kind: str, name: str, suffix: str) -> Path:
    """``<root>/bench/<kind>/<name><suffix>``, else the package's own."""
    for base in (root / "bench" / kind, PACKAGE / kind):
        path = base / f"{name}{suffix}"
        if path.is_file():
            return path
    raise FileNotFoundError(f"no {kind} file for {name!r}")


def load_module(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(root: Path, workload: str) -> dict:
    """The cell, its configuration, its traffic mix and the metric lists
    that apply to it."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(find_file(root, "traffic", cell["traffic"],
                                   ".json").read_text())

    def applies(m, reported=None):
        if "workloads" in m:
            return workload in m["workloads"]
        return reported is None or m["moves"] in reported

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if applies(m, names)]
    return {"cell": cell, "config": cfg, "traffic": traffic, "e2e": e2e,
            "per_layer": per_layer}


def set_caches(root: Path) -> None:
    """Compile and plan caches at fixed paths inside the checkout, so a
    cell's later runs there find what its first run built, and two
    checkouts share nothing."""
    cache = root / "bench" / CACHE
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache / "jax")
    os.environ["REPRO_PLAN_CACHE_DIR"] = str(cache / "plans")
    os.environ["REPRO_PLAN_CACHE_MAX_BYTES"] = str(16 << 30)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


class Tracer:
    """Profiler around the measured window of a ``--trace 1`` run; the
    trace goes to a temporary directory that is read and then removed."""

    def __init__(self, enabled: bool, spans: Spans):
        self.enabled, self.spans = enabled, spans
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if enabled else ""

    def start(self):
        if self.enabled:
            import jax
            jax.profiler.start_trace(self.dir)
            self.spans.tracing = True

    def stop(self):
        if self.enabled and self.spans.tracing:
            import jax
            self.spans.tracing = False
            jax.profiler.stop_trace()

    def summary(self):
        from bench import trace
        try:
            return trace.TraceSummary(trace.load_xplane(
                trace.find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: Path | None = None, allow_cpu: bool = False,
         t_start: float | None = None) -> int:
    """Run the cell; 0 with a result line, 2 without an accelerator."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    root = Path(root or PACKAGE.parent).resolve()
    found = resolve(root, args.workload)
    cell, cfg = found["cell"], found["config"]
    chips = int(cell["chips"])
    set_caches(root)

    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    dev = device_info(chips)
    peaks = None if allow_cpu and dev["platform"] != "tpu" else \
        peaks_for(dev["kind"])

    spans = Spans()
    tracer = Tracer(bool(args.trace), spans)
    system = load_module(find_file(root, "systems", cfg["system"], ".py"),
                         "bench_system")
    say(f"{args.workload}: config {cell['config']}, traffic "
        f"{cell['traffic']}, {chips} chip(s) of {dev['kind']}, seed "
        f"{args.seed}, {args.seconds} s, trace {args.trace}")
    measured = system.run(cfg, found["traffic"], args.seed, args.seconds,
                          spans, chips, tracer)
    win = spans.first("window")
    e2e = dict(measured.end_to_end, setup_s=win[0] - t_start)
    for name, a, b in spans.spans:
        if name.startswith("setup."):
            say(f"span {name}: {b - a:.6f} s")

    line = {"correct": bool(measured.correct),
            "attempted": int(measured.attempted),
            "failed": int(measured.failed)}
    device = dict(dev, memory_peak_bytes=int(measured.memory_peak_bytes))
    metrics = {}
    if args.trace:
        summary = tracer.summary()
        device.update(busy_s=summary.mean_busy_s(),
                      window_s=summary.window_s)
        ctx = ReadContext(cell=cell, config=cfg, traffic=found["traffic"],
                          measured=measured, spans=spans, trace=summary,
                          peaks=peaks, chips=chips)
        for m in found["per_layer"]:
            reader = load_module(find_file(root, "metrics", m["name"], ".py"),
                                 "bench_metric")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        line["breakdown"] = summary.breakdown()
    else:
        for m in found["e2e"]:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit in measured.compared}
    for name, value, limit in measured.compared:
        print(f"[bench] check {name}: {value!r} limit {limit!r} "
              f"{'ok' if value <= limit else 'FAILED'}", file=sys.stderr,
              flush=True)
    print(json.dumps(line), flush=True)
    return 0
