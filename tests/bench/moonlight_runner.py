"""Drive the closed-batch decode cell (``bench/systems/serve_decode.py``) on
the CPU for the tests, optionally with a fault planted in the timed path.

    python tests/bench/moonlight_runner.py ROOT WORKLOAD SECONDS SEED \
        FAULT[,FAULT...]

For each fault (``none`` for the unbroken program) the harness runs the
cell once with that fault planted and one line ``RESULT <fault> <json>``
is printed with the run's last line.  The fault ``control`` runs the window
unbroken and prints the readings of the float8 control instead.
"""
from __future__ import annotations

import contextlib
import io
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def faults(root: Path, workload: str, seed: int) -> dict:
    """{fault: (object, attribute, replacement)}."""
    import jax.numpy as jnp
    from bench import harness
    from bench.systems import serve_decode
    from repro.models import moe
    from repro.models.transformer import Model

    found = harness.resolve(root, workload)
    prompts = serve_decode.sessions(found["config"], found["traffic"], seed)
    longest = max(range(len(prompts)), key=lambda i: len(prompts[i]))
    decode, route = Model.decode_step, moe.route

    def latent_zeroed(self, params, cache, tokens):
        # the first MoE layer's latent rows lost before every tick
        layers = dict(cache["layers"], ckv=cache["layers"]["ckv"].at[1].set(0))
        return decode(self, params, dict(cache, layers=layers), tokens)

    def routing_forced(p_router, x, cfg):
        top_w, top_e = route(p_router, x, cfg)
        return top_w, jnp.broadcast_to(
            jnp.arange(cfg.experts_per_token, dtype=jnp.int32), top_e.shape)

    def lane_altered(self, params, cache, tokens):
        logits, new = decode(self, params, cache, tokens)
        row = logits[longest, 0].astype(jnp.float32)
        return logits.at[longest, 0, 5].add(
            (0.5 * jnp.std(row)).astype(logits.dtype)), new

    return {"latent_zeroed": (Model, "decode_step", latent_zeroed),
            "routing_forced": (moe, "route", routing_forced),
            "lane_altered": (Model, "decode_step", lane_altered)}


def control(root: Path, workload: str, seconds: float, seed: int) -> dict:
    from bench import harness
    from bench.common import Spans
    from bench.systems import serve_decode

    found = harness.resolve(root, workload)
    harness.set_caches(root)
    spans = Spans()
    st = serve_decode.served_window(found["config"], found["traffic"], seed,
                                    seconds, spans,
                                    harness.Tracer(False, spans))
    serve_decode.free_engine(st)
    return {"program": serve_decode.readings(found["config"], st),
            "control": serve_decode.readings(found["config"], st,
                                             control=True)}


def main() -> int:
    root, workload, seconds, seed, names = sys.argv[1:6]
    root, seed = Path(root), int(seed)
    from bench.harness import main as bench_main

    table = faults(root, workload, seed)
    for fault in names.split(","):
        if fault == "control":
            out = json.dumps(control(root, workload, float(seconds), seed))
            print(f"RESULT control {json.dumps({'rc': 0, 'line': out})}",
                  flush=True)
            continue
        patch = table.get(fault)
        if patch:
            obj, attr, new = patch
            old = getattr(obj, attr)
            setattr(obj, attr, new)
        out = io.StringIO()
        try:
            with contextlib.redirect_stdout(out):
                rc = bench_main(["--workload", workload, "--seed", str(seed),
                                 "--seconds", seconds, "--trace", "0"],
                                root=root, allow_cpu=True)
        finally:
            if patch:
                setattr(obj, attr, old)
        last = out.getvalue().strip().splitlines()[-1] if rc == 0 else "{}"
        print(f"RESULT {fault} {json.dumps({'rc': rc, 'line': last})}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
