"""Readings that set a cell's correctness limit, in one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9 --seconds 12 [--trace-out PATH] [--probe]

The configuration's ``system`` names the driver
``bench/systems/<system>.py``, found by name as the harness finds it; its
``calibrate(found, args, chips, record_trace)`` gives the readings through
the same check a run makes.  For each seed the program runs the cell's
window (at the cell's size and load, for ``--seconds``) and the check gives
its reading: the lower reading of the limit is the largest of these.  For
each control seed the same check is read off the control, the reference
computed in the precision below the configuration's (SpMV: bfloat16 for
float32; Mixtral: float8 weights for bfloat16): the upper reading is the
smallest of these.  The benchmark's own runs never run the control.

``--trace-out`` also records a short traced window of the first seed and
writes its reduced trace (``bench.trace.dump``) there; ``--probe`` prints
the planes and lines of that raw trace.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402
from bench.common import Spans, say  # noqa: E402


def record_trace(args, run_window):
    """Trace a short window and keep its reduction; a trace that does not
    reduce is reported, and the readings go on."""
    from bench import trace
    spans = Spans()
    tracer = harness.Tracer(True, spans)
    run_window(spans, tracer)
    try:
        path = trace.find_xplane(tracer.dir)
        if args.probe:
            probe(path)
        data = trace.load_xplane(path)
        trace.dump(data, args.trace_out)
        s = trace.TraceSummary(data)
        say(f"trace written to {args.trace_out}: devices {s.devices}, "
            f"window {s.window_s:.6f} s, busy {s.mean_busy_s():.6f} s, "
            f"collectives {s.collective_s()}, breakdown "
            f"{json.dumps(s.breakdown())}")
    except (OSError, ValueError, KeyError) as e:
        say(f"trace not reduced: {e!r}")


def probe(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})[:8]
            t = (evs[0].start_ns, evs[-1].start_ns) if evs else ()
            lines.append(f"{line.name!r} n={len(evs)} t={t} names={names}")
        say(f"plane {plane.name!r}: " + " | ".join(lines))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(v) for v in s.split(",")])
    ap.add_argument("--control-seeds", default=[],
                    type=lambda s: [int(v) for v in s.split(",") if v])
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--trace-out", default="")
    ap.add_argument("--probe", action="store_true")
    args = ap.parse_args(argv)
    root = harness.PACKAGE.parent
    found = harness.resolve(root, args.workload)
    chips = int(found["cell"]["chips"])
    harness.set_caches(root)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(root / "bench" / harness.CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < chips:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    system = harness.load_module(harness.find_file(
        root, "systems", found["config"]["system"], ".py"), "bench_system")
    out = system.calibrate(found, args, chips, functools.partial(
        record_trace, args))

    def reading(side, pick):
        vals = list(out[side].values())
        if not vals or any(v is None for v in vals):
            return None          # a seed with no reading sets no limit
        if isinstance(vals[0], dict):
            return {k: pick(v[k] for v in vals) for k in vals[0]}
        return pick(vals)

    out["lower"] = reading("program", max)
    out["upper"] = reading("control", min)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
