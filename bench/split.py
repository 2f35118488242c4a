"""Where a cell's set-up and step go, by the program's own spans, counters
and scopes: one traced run of an SpMV cell on the chip.

    python3 bench/split.py --workload <cell> --seed <n> --seconds <s> \
        [--trace-out PATH]

It builds and warms up the cell exactly as the system's ``run`` does
(``bench/systems/<system>.py``: ``build``, the warm-up, ``window``), traces
the window, and prints on standard error:

* the set-up split: the ``repro.comm.telemetry`` spans closed by the end of
  the warm-up (``plan.*``, ``comm.*``, ``spmv.*``), their sum beside the
  harness's ``setup.engine`` span (the metric ``spmv.plan_s``), and the
  compile counters of set-up;
* the compiles in the window (the warm-up compiles every program the window
  runs, so this reads 0);
* the step's device split per execution of ``step_local``: ``comm.pack``,
  ``comm.exchange`` (its non-collective ops), ``comm.unpack``,
  ``spmv.local`` (and its ``own`` / ``foreign`` parts), unscoped, their sum
  and ``TraceSummary.module_compute_s``, and the collectives.

The last line of standard output is the same as one JSON object.
``--trace-out`` writes the reduced trace (``bench.scopes.load_xplane``'s
structure, op_names joined) there.  The benchmark's own runs never run
this script.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import harness, scopes, trace  # noqa: E402
from bench.common import Spans, rng, say  # noqa: E402

MODULE = "step_local"
SETUP_PREFIXES = ("plan.", "comm.")
SETUP_SPANS = ("spmv.split", "spmv.place")


def setup_split(spans_delta: dict) -> dict[str, float]:
    """Seconds of the set-up spans that tile ``DistributedSpMV(...)``."""
    return {k: v["seconds"] for k, v in sorted(spans_delta.items())
            if k.startswith(SETUP_PREFIXES) or k in SETUP_SPANS}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-out", default="")
    ap.add_argument("--root", default=str(REPO),
                    help="checkout whose BENCHMARK.json names the cell")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run on CPU devices (a rehearsal at test size; "
                    "a CPU trace has no device ops to split)")
    args = ap.parse_args(argv)
    root = Path(args.root).resolve()
    found = harness.resolve(root, args.workload)
    cfg, chips = found["config"], int(found["cell"]["chips"])
    harness.set_caches(root)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(root / "bench" / harness.CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if len(jax.devices()) < chips or (jax.devices()[0].platform != "tpu"
                                      and not args.allow_cpu):
        print("split: needs the cell's TPU chips", file=sys.stderr)
        return 2
    from repro.comm import telemetry
    system = harness.load_module(harness.find_file(
        root, "systems", cfg["system"], ".py"), "bench_system")

    spans = Spans()
    tracer = harness.Tracer(True, spans)
    st = system.build(cfg, spans, chips)
    eng, rescale = st["eng"], st["rescale"]
    n = len(st["diag"])
    with spans.span("setup.x"):
        x = eng.shard_vector(rng(args.seed, "x").standard_normal(
            n, dtype=np.float32))
    with spans.span("setup.warmup"):
        for _ in range(2):
            x_w = rescale(eng(x))
        jax.block_until_ready(x_w)
        del x_w
    setup = telemetry.stats.snapshot()
    res = system.window(eng, rescale, x, args.seed, args.seconds, spans,
                        tracer, samples=1)
    in_window = telemetry.stats.since(setup)
    setup_s = spans.first("window")[0] - T_START

    split = setup_split(setup.get("spans", {}))
    plan_s = spans.total("setup.engine")
    out = {"strategy": eng.strategy, "steps": res["steps"],
           "step_ms": res["elapsed"] / res["steps"] * 1e3,
           "setup_s": setup_s, "plan_s": plan_s, "setup_spans": split,
           "setup_spans_sum": sum(split.values()),
           "setup_compiles": setup.get("compiles"),
           "setup_cache_loads": setup.get("cache_loads"),
           "setup_compile_s": setup.get("compile_s"),
           "compiles_in_window": in_window.get("compiles")}
    say(f"set-up {setup_s:.6f} s; spmv.plan_s {plan_s:.6f} s; spans "
        f"{json.dumps(split)}; sum {out['setup_spans_sum']:.6f} s "
        f"({100 * out['setup_spans_sum'] / plan_s:.3f}% of spmv.plan_s); "
        f"compiles {out['setup_compiles']} ({out['setup_cache_loads']} "
        f"from the cache) {out['setup_compile_s']!r} s")
    say(f"compiles in the window: {out['compiles_in_window']}")

    path = trace.find_xplane(tracer.dir)
    try:
        data = scopes.load_xplane(path)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    if not data["devices"]:
        say("the trace holds no device ops: no step split")
        print(json.dumps(out), flush=True)
        return 0
    named = scopes.join_hlo(data, eng.lower(x).compile().as_text(), MODULE)
    say(f"{named} ops named from the compiled module's HLO text")
    summary = scopes.ScopedSummary(data)
    if args.trace_out:
        trace.dump(data, args.trace_out)
        say(f"trace written to {args.trace_out}")
    per_exec = {k: v * 1e3 for k, v in summary.scope_split(MODULE).items()}
    compute_s, execs = summary.module_compute_s(MODULE)
    compute_ms = compute_s / execs * 1e3 if execs else 0.0
    coll_s, exposed_s = summary.collective_s()
    main3 = sum(v for k, v in per_exec.items()
                if k.split("/")[0] in ("comm.pack", "comm.unpack",
                                       "spmv.local"))
    out.update(device_ms=per_exec, module_compute_ms=compute_ms,
               executions=execs, scoped_share=(main3 / compute_ms
                                               if compute_ms else None),
               collective_ms=coll_s * 1e3 / res["steps"],
               collective_exposed_ms=exposed_s * 1e3 / res["steps"],
               breakdown=summary.breakdown())
    say(f"step device split (ms per {MODULE} execution, {execs} "
        f"executions): {json.dumps(per_exec)}; pack + unpack + local "
        f"{main3:.6f} of {compute_ms:.6f} = "
        f"{100 * (out['scoped_share'] or 0):.4f}%; unscoped "
        f"{per_exec.get(scopes.UNSCOPED, 0.0):.6f}; collectives "
        f"{out['collective_ms']:.6f} ms a step "
        f"({out['collective_exposed_ms']:.6f} exposed)")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
