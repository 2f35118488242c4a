"""The SpMV exchange window: repeated forward products through
``DistributedSpMV`` (the comm library's front door for the paper's workload)
on a one-dimensional mesh.

Each step's input is the previous step's output rescaled to max |x| = 1, as
in a power or Krylov iteration, so no step can be skipped or overlapped with
the next.  ``step_ms`` is the whole window over the steps completed in it.

The check compares the products of a sample of the window's steps, drawn
from the seed (a reservoir over every step, plus the last), with the float64
reference on the host, at the timed size and through the timed call.
"""
from __future__ import annotations

import time

import numpy as np

from bench.common import Measured, Spans, memory_peak, rng, say
from bench.refs import spmv_ref


def build(cfg: dict, spans, chips: int):
    """Matrix and engine: everything that set-up pays for."""
    import jax
    import jax.numpy as jnp
    from repro.comm import telemetry
    from repro.core.matrix import EllpackMatrix
    from repro.core.spmv import DistributedSpMV
    from repro.launch.mesh import make_local_mesh

    p = int(cfg["mesh"][0])
    if p != chips:
        raise ValueError(f"the configuration's mesh {cfg['mesh']} does not "
                         f"match the cell's {chips} chips")
    mesh = make_local_mesh((p,), ("data",))
    with spans.span("setup.matrix"):
        diag, vals, cols = spmv_ref.make_matrix(cfg)
    n, r = len(diag), vals.shape[1]
    snap = telemetry.stats.snapshot()
    with spans.span("setup.engine"):
        eng = DistributedSpMV(EllpackMatrix(n=n, r_nz=r, diag=diag,
                                            vals=vals, cols=cols),
                              mesh, strategy=cfg["strategy"],
                              blocksize=cfg.get("blocksize"))
    sources = {k: v for k, v in telemetry.stats.since(snap).items() if v}
    rescale = jax.jit(lambda y: y / jnp.max(jnp.abs(y)))
    return {"eng": eng, "rescale": rescale, "diag": diag, "vals": vals,
            "cols": cols, "plan_sources": sources}


def run(cfg: dict, traffic: dict, seed: int, seconds: float, spans,
        chips: int, tracer) -> Measured:
    import jax

    st = build(cfg, spans, chips)
    eng, rescale = st["eng"], st["rescale"]
    n = len(st["diag"])
    with spans.span("setup.x"):
        x0 = rng(seed, "x").standard_normal(n, dtype=np.float32)
        x = eng.shard_vector(x0)
    with spans.span("setup.warmup"):
        for _ in range(2):
            x_w = rescale(eng(x))
        jax.block_until_ready(x_w)
        del x_w
    say(f"spmv n={n} r_nz={st['vals'].shape[1]} p={eng.p}: requested "
        f"{eng.requested_strategy!r}, resolved {eng.strategy!r}, blocksize "
        f"{eng.blocksize}; plan sources {st['plan_sources']}")

    res = window(eng, rescale, x, seed, seconds, spans, tracer,
                 samples=int(traffic["check_samples"]))
    peak = memory_peak(chips)
    step_s = res["elapsed"] / res["steps"]
    predicted = (eng.predicted_times or {}).get(eng.strategy)
    say(f"window {res['elapsed']:.6f} s, {res['steps']} steps, step "
        f"{step_s * 1e3:.6f} ms; §5 prediction for {eng.strategy}: "
        f"{'none' if predicted is None else f'{predicted * 1e3:.6f} ms'}; "
        f"all predictions {eng.predicted_times}")
    devices = len(res["last_y"].sharding.device_set)

    compared, failed = check(st, res["samples"], cfg["check"])
    if devices != chips:
        compared.append(("output_devices", float(devices), float(chips)))
    correct = failed == 0 and devices == chips
    counters = {"steps": res["steps"], "n": n, "r_nz": st["vals"].shape[1],
                "p": eng.p, "strategy": eng.strategy,
                "plan_s": spans.total("setup.engine")}
    return Measured(
        end_to_end={"step_ms": step_s * 1e3},
        counters=counters, compared=compared, correct=correct,
        attempted=res["steps"], failed=failed, memory_peak_bytes=peak)


def window(eng, rescale, x, seed, seconds, spans, tracer, *, samples: int):
    """Steps until ``seconds`` have passed; keeps (step, input, output) of
    a reservoir of ``samples`` steps drawn from the seed, and the last."""
    import jax

    pick = rng(seed, "check-steps")
    reservoir: list[tuple[int, object, object]] = []
    steps = 0
    y = None
    tracer.start()
    t0 = time.perf_counter()
    with spans.span("window"):
        while True:
            with spans.span("step"):
                y = eng(x)
                x_next = rescale(y)
                jax.block_until_ready(x_next)
            if len(reservoir) < samples:
                reservoir.append((steps, x, y))
            else:
                j = int(pick.integers(0, steps + 1))
                if j < samples:
                    reservoir[j] = (steps, x, y)
            steps += 1
            last = (steps - 1, x, y)
            x = x_next
            if time.perf_counter() - t0 >= seconds:
                break
    elapsed = time.perf_counter() - t0
    tracer.stop()
    if all(k != last[0] for k, _, _ in reservoir):
        reservoir.append(last)
    return {"steps": steps, "elapsed": elapsed, "samples": reservoir,
            "last_y": y}


def check(st, samples, limits):
    """(compared, failed): the widest relative error of each sampled step
    against the float64 reference."""
    worst, failed = 0.0, 0
    for k, x_k, y_k in sorted(samples, key=lambda s: s[0]):
        want = spmv_ref.reference(st["diag"], st["vals"], st["cols"],
                                  np.asarray(x_k))
        err = spmv_ref.rel_err(np.asarray(y_k), want)
        say(f"check step {k}: max rel err {err:.6e}")
        worst = max(worst, err)
        failed += int(not err <= limits["max_rel_err"])
    return [("max_rel_err", worst, float(limits["max_rel_err"]))], failed


def calibrate(found: dict, args, chips: int, record_trace) -> dict:
    """Readings for the limit, in one process: for each of ``args.seeds``
    the check of its window (program); for each of ``args.control_seeds``
    the same check of the bfloat16 control on that window's inputs."""
    from bench.harness import Tracer

    cfg = found["config"]
    st = build(cfg, Spans(), chips)
    eng, rescale = st["eng"], st["rescale"]
    n = len(st["diag"])
    samples = int(found["traffic"]["check_samples"])
    say(f"resolved {eng.strategy}; plan sources {st['plan_sources']}")

    def run_window(seed, seconds, spans, tracer, k=samples):
        x = eng.shard_vector(rng(seed, "x").standard_normal(
            n, dtype=np.float32))
        return window(eng, rescale, x, seed, seconds, spans, tracer,
                      samples=k)

    out = {"program": {}, "control": {}, "strategy": eng.strategy}
    for seed in args.seeds + args.control_seeds:
        res = run_window(seed, args.seconds, Spans(), Tracer(False, Spans()))
        if seed in args.seeds:
            compared, _ = check(st, res["samples"], cfg["check"])
            out["program"][seed] = compared[0][1]
        if seed in args.control_seeds:
            ctl = [(k, x_k, spmv_ref.control_bf16(
                st["diag"], st["vals"], st["cols"], np.asarray(x_k)))
                for k, x_k, _ in res["samples"]]
            compared, _ = check(st, ctl, cfg["check"])
            out["control"][seed] = compared[0][1]
        say(f"seed {seed}: {res['steps']} steps at "
            f"{res['elapsed'] / res['steps'] * 1e3:.3f} ms, program "
            f"{out['program'].get(seed, '-')}, control "
            f"{out['control'].get(seed, '-')}")
        del res
    if args.trace_out:
        record_trace(lambda spans, tracer: run_window(
            args.seeds[0], 2.0, spans, tracer, k=1))
    return out
