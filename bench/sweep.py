"""Knee sweep of a serving cell: the highest arrival rate the engine
sustains, found once on the chip and written into the cell's traffic file.

    python3 bench/sweep.py --workload serve_mixtral.steady --seed 1 \
        --seconds 30 --rates 4,5,6,7,8,9,10

One process, one set-up; each rate runs the cell's open loop for
``--seconds`` (same generator, other rate) and then drains.  Per rate it
prints the offered and completed tokens/s, TTFT p50/p95, the queue at the
close and whether TTFT grew from the window's first third to its last (a
growing backlog).  The knee is the highest rate without a growing backlog.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402
from bench.common import Spans, percentile, say  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(v) for v in s.split(",")])
    args = ap.parse_args(argv)
    root = harness.PACKAGE.parent
    found = harness.resolve(root, args.workload)
    harness.set_caches(root)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(root / "bench" / harness.CACHE / "jax"))
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from bench.systems import serve as S
    from bench.traffic import open_loop
    from repro.comm import telemetry

    cfg, traffic = found["config"], found["traffic"]
    spans = Spans()
    st = S.build(cfg, args.seed, spans)
    engine = st["engine"]
    S.warm_up(engine, cfg, spans)
    rows = []
    for rate in args.rates:
        arr = open_loop(traffic, args.seed, args.seconds, cfg["vocab_size"],
                        rate)
        res = S.window(engine, arr, args.seconds, Spans(),
                       harness.Tracer(False, Spans()),
                       int(cfg["program"]["prefill_chunk"]), telemetry)
        book = res["book"]
        ttft = [book.first[a.id] - (book.t0 + a.due) for a in arr]
        third = max(1, len(arr) // 3)
        offered = sum(a.max_new_tokens for a in arr) / args.seconds
        row = {"rate": rate, "requests": len(arr),
               "offered_tokens_per_s": offered,
               "tokens_per_s": book.tokens_in_window / args.seconds,
               "ttft_p50_ms": percentile(ttft, 50) * 1e3,
               "ttft_p95_ms": percentile(ttft, 95) * 1e3,
               "itl_p95_ms": percentile(book.itl, 95) * 1e3,
               "ttft_first_third_ms": float(np.median(ttft[:third])) * 1e3,
               "ttft_last_third_ms": float(np.median(ttft[-third:])) * 1e3,
               "lateness_max_ms": max(res["lateness"]) * 1e3}
        rows.append(row)
        say(json.dumps(row))
    knee = max((r["rate"] for r in rows if sustained(r)), default=None)
    print(json.dumps({"rows": rows, "knee": knee}), flush=True)
    return 0


def sustained(row) -> bool:
    """No growing backlog: the median TTFT of the window's last third stays
    within 1.6x its first third's.  (Tokens completed inside a window fall
    short of those offered by requests still running at its close, so they
    do not tell a backlog apart.)"""
    return row["ttft_last_third_ms"] <= 1.6 * row["ttft_first_third_ms"]


if __name__ == "__main__":
    sys.exit(main())
