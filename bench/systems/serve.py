"""Serving under open-loop traffic: ``ServeEngine`` (continuous batching,
chunked prefill, MoE decode through ``DynamicMoELayer``) driven by the
harness at the traffic file's arrival rate.

Requests are submitted when they fall due on the host clock; each
``engine.step()`` is one admit-decode-bookkeep tick.  A token's time is the
return of the step that carries it.  TTFT runs from a request's due time to
its first token; after the window no new request is sent, and those already
due are followed until each has its first token.  ``tokens_per_s`` counts
the output tokens emitted inside the window, over the window.

The check runs the float32 reference (``bench.refs.mixtral_ref``) over a
sample of the finished requests drawn from the seed, the longest among
them, on each prompt with its served tokens, and compares the widest gap
by which a served token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time

import numpy as np

from bench.common import Measured, Spans, memory_peak, percentile, rng, say
from bench.refs import mixtral_ref
from bench.traffic import check_traffic, open_loop

DRAIN_LIMIT_S = 60.0
# program fields that must equal the configuration's published sizes
_WIDTHS = {"d_model": "hidden_size", "num_heads": "num_attention_heads",
           "num_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
           "vocab_size": "vocab_size", "num_experts": "num_local_experts",
           "experts_per_token": "num_experts_per_tok",
           "num_layers": "num_hidden_layers", "head_dim": "head_dim"}


def program_config(cfg: dict):
    """The program's ``ArchConfig`` for this configuration, checked field
    by field against the configuration's sizes."""
    from repro.configs.registry import get_config

    prog = cfg["program"]
    arch = dataclasses.replace(
        get_config(prog["arch"]), num_layers=cfg["num_hidden_layers"],
        capacity_factor=float(prog["capacity_factor"]),
        **prog.get("overrides", {}))
    for field, key in _WIDTHS.items():
        want = cfg.get(key) or (cfg["hidden_size"]
                                // cfg["num_attention_heads"])
        if getattr(arch, field) != want:
            raise ValueError(f"program {field}={getattr(arch, field)} but "
                             f"the configuration's {key} is {want}")
    if float(arch.rope_theta) != float(cfg["rope_theta"]):
        raise ValueError("rope_theta differs from the configuration")
    if arch.swa_window and arch.swa_window < int(prog["cache_len"]):
        raise ValueError("the program's attention window would bind")
    return arch


def build(cfg: dict, seed: int, spans):
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import build_moe_layer
    from repro.models.transformer import Model, RunCtx
    from repro.serve import ServeEngine

    prog = cfg["program"]
    arch = program_config(cfg)
    model = Model(arch, RunCtx(remat="none", act_dtype=jnp.bfloat16))
    with spans.span("setup.weights"):
        abstract = jax.eval_shape(
            functools.partial(model.init_params, dtype=jnp.bfloat16),
            jax.random.PRNGKey(0))
        params = mixtral_ref.make_params(abstract, seed)
        jax.block_until_ready(params)
    with spans.span("setup.engine"):
        mesh = make_local_mesh((1,), ("data",))
        layer = build_moe_layer(model, params, int(prog["num_slots"]), mesh)
        engine = ServeEngine(model, params, num_slots=int(prog["num_slots"]),
                             cache_len=int(prog["cache_len"]),
                             prefill_chunk=int(prog["prefill_chunk"]),
                             moe_layer=layer, cache_dtype=jnp.bfloat16)
    return {"engine": engine, "params": params, "layer": layer,
            "arch": arch}


def warm_up(engine, cfg: dict, spans) -> None:
    """Compile prefill, insert and decode, the only programs the window
    runs: a first request, one tick, then a second admitted beside it.
    An insert into a cache that a decode wrote takes other argument
    shardings than one into the fresh cache, so both orders are run."""
    from repro.serve import Request

    chunk = int(cfg["program"]["prefill_chunk"])
    with spans.span("setup.warmup"):
        for r in range(2):
            engine.submit(Request(id=f"warmup{r}a", prompt=[1] * chunk,
                                  max_new_tokens=4))
            engine.step()
            engine.submit(Request(id=f"warmup{r}b", prompt=[2] * chunk,
                                  max_new_tokens=2))
            engine.run()


class Bookkeeper:
    """Token times per request, read from the engine's slots after every
    step (a slot's ``generated`` counts the tokens its request got)."""

    def __init__(self, arrivals, t0: float, end: float, chunk: int):
        self.by_id = {a.id: a for a in arrivals}
        self.t0, self.end, self.chunk = t0, end, chunk
        self.seen: dict[int, int] = {}         # id -> tokens seen so far
        self.running: set = set()
        self.first: dict[int, float] = {}
        self.last: dict[int, float] = {}
        self.itl: list[float] = []
        self.tokens_in_window = 0
        self.work = {"tokens": 0, "attended": 0, "logit_rows": 0}

    def observe(self, slots, t: float) -> None:
        active = {s.request_id: s.generated for s in slots.active()}
        # requests that were running before this step, and those it admitted
        for rid in set(self.running) | set(active):
            a = self.by_id.get(rid)
            if a is None:
                continue
            before = self.seen.get(rid, 0)
            now = active.get(rid, a.max_new_tokens)   # gone: it finished
            for j in range(before, now):
                self._token(a, j, t)
            self.seen[rid] = now
        self.running = set(active)

    def _token(self, a, j: int, t: float) -> None:
        rid, plen = a.id, len(a.prompt)
        if j == 0:
            self.first[rid] = t
        else:
            if t <= self.end:
                self.itl.append(t - self.last[rid])
        self.last[rid] = t
        if t > self.end:
            return
        self.tokens_in_window += 1
        w = self.work
        if j == 0:                       # the prompt's chunked prefill
            w["tokens"] += plen
            w["attended"] += plen * (plen + 1) // 2
            w["logit_rows"] += -(-plen // self.chunk)
        else:                            # decode of token j at plen + j - 1
            w["tokens"] += 1
            w["attended"] += plen + j
            w["logit_rows"] += 1


def run(cfg: dict, traffic: dict, seed: int, seconds: float, spans,
        chips: int, tracer) -> Measured:
    from repro.comm import telemetry

    prog = cfg["program"]
    check_traffic(traffic, int(prog["cache_len"]),
                  int(prog["prefill_chunk"]))
    st = build(cfg, seed, spans)
    engine = st["engine"]
    warm_up(engine, cfg, spans)
    say(f"serve {cfg['num_hidden_layers']} layers, {prog['num_slots']} "
        f"slots, cache {prog['cache_len']}, chunk {prog['prefill_chunk']}; "
        f"MoE decode {st['layer'].strategies}")
    arrivals = open_loop(traffic, seed, seconds, cfg["vocab_size"])
    res = window(engine, arrivals, seconds, spans, tracer,
                 int(prog["prefill_chunk"]), telemetry)
    peak = memory_peak(chips)
    book = res["book"]
    report = engine.report()
    due = arrivals
    ttft = [book.first[a.id] - (book.t0 + a.due) for a in due
            if a.id in book.first]
    failed = len(due) - len(ttft)
    tps = book.tokens_in_window / seconds
    say(f"window {seconds} s: {len(due)} requests due, {len(ttft)} with a "
        f"first token, {report_finished(report, arrivals)} finished; "
        f"{book.tokens_in_window} tokens in the window; {len(book.itl)} "
        f"inter-token gaps; generator lateness p50 "
        f"{np.median(res['lateness']) * 1e3:.3f} ms max "
        f"{max(res['lateness']) * 1e3:.3f} ms; compiles in window "
        f"{res['compiles']}; plan sources {res['plan_sources']}")

    del engine, st["engine"], st["layer"]
    gc.collect()
    compared, n_bad = check(cfg, st["params"], report, arrivals, seed)
    del st
    gc.collect()
    counters = {"work": dict(book.work), "requests_due": len(due),
                "tokens_in_window": book.tokens_in_window,
                "itl_samples": len(book.itl), "ttft_samples": len(ttft)}
    return Measured(
        end_to_end={"tokens_per_s": tps,
                    "ttft_p95_ms": percentile(ttft, 95) * 1e3,
                    "itl_p95_ms": percentile(book.itl, 95) * 1e3},
        counters=counters, compared=compared,
        correct=(n_bad == 0 and failed == 0), attempted=len(due),
        failed=failed, memory_peak_bytes=peak)


def report_finished(report, arrivals) -> int:
    want = {a.id: a.max_new_tokens for a in arrivals}
    return sum(1 for rid, toks in report.outputs.items()
               if rid in want and len(toks) == want[rid])


def window(engine, arrivals, seconds, spans, tracer, chunk, telemetry):
    """Drive the engine: submit what is due, step, read the slots; after
    the window, stop sending and step until every due request has its first
    token (at most ``DRAIN_LIMIT_S``)."""
    import jax
    from repro.serve import Request

    compiles = [0]

    def on_event(event, *_a, **_k):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    snap = telemetry.stats.snapshot()
    tracer.start()
    t0 = time.perf_counter()
    end = t0 + seconds
    book = Bookkeeper(arrivals, t0, end, chunk)
    lateness = []
    i = 0
    n = len(arrivals)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        with spans.span("window"):
            while True:
                now = time.perf_counter()
                if now >= end:
                    break
                while i < n and t0 + arrivals[i].due <= now:
                    a = arrivals[i]
                    engine.submit(Request(id=a.id, prompt=a.prompt,
                                          max_new_tokens=a.max_new_tokens,
                                          arrival_time=engine.now))
                    lateness.append(now - (t0 + a.due))
                    i += 1
                if not engine.slots.active() and not len(engine.queue):
                    nxt = t0 + arrivals[i].due if i < n else end
                    with spans.span("idle"):
                        time.sleep(max(0.0, min(nxt, end) - now))
                    continue
                with spans.span("engine.step"):
                    engine.step()
                book.observe(engine.slots, time.perf_counter())
    finally:
        tracer.stop()
        jax.monitoring.unregister_event_duration_listener(on_event)
    n_compiles = compiles[0]
    # every arrival fell due inside the window; send any the last step held
    for a in arrivals[i:]:
        engine.submit(Request(id=a.id, prompt=a.prompt,
                              max_new_tokens=a.max_new_tokens,
                              arrival_time=engine.now))
        lateness.append(time.perf_counter() - (t0 + a.due))
    due_ids = {a.id for a in arrivals}
    limit = time.perf_counter() + DRAIN_LIMIT_S
    with spans.span("drain"):
        while (any(r not in book.first for r in due_ids)
               and time.perf_counter() < limit
               and (engine.slots.active() or len(engine.queue))):
            engine.step()
            book.observe(engine.slots, time.perf_counter())
        # finish what is running, so the check has whole requests to read
        while engine.slots.active() and time.perf_counter() < limit:
            engine.step()
            book.observe(engine.slots, time.perf_counter())
    sources = {k: v for k, v in telemetry.stats.since(snap).items() if v}
    return {"book": book, "lateness": lateness or [0.0],
            "compiles": n_compiles, "plan_sources": sources}


def sample_requests(report, arrivals, seed: int, min_tokens: int,
                    max_requests: int):
    """Finished requests for the check: the longest, then others drawn from
    the seed, until ``min_tokens`` served tokens or ``max_requests``."""
    by_id = {a.id: a for a in arrivals}
    done = [rid for rid, toks in report.outputs.items()
            if rid in by_id and len(toks) == by_id[rid].max_new_tokens]
    if not done:
        return []
    done.sort(key=lambda r: -(len(by_id[r].prompt) + len(report.outputs[r])))
    pick = [done[0]]
    rest = list(rng(seed, "check-requests").permutation(done[1:]))
    while rest and len(pick) < max_requests and sum(
            len(report.outputs[r]) for r in pick) < min_tokens:
        pick.append(int(rest.pop()))
    return [(by_id[r], report.outputs[r]) for r in pick]


def reference_gaps(cfg: dict, params, picked, *, quantize=False):
    """Per picked request, the reference's gaps of its served tokens; with
    ``quantize``, the gaps of the tokens the float8 control puts first."""
    import jax.numpy as jnp

    cache_len = int(cfg["program"]["cache_len"])
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, str))))
    ref = mixtral_ref.compiled_forward(key, False)
    ctl = mixtral_ref.compiled_forward(key, True) if quantize else None
    out = []
    for a, served in picked:
        seq = np.zeros(cache_len, np.int32)
        body = np.concatenate([a.prompt, np.asarray(served[:-1], np.int32)])
        seq[:len(body)] = body
        logits = np.asarray(ref(params, jnp.asarray(seq)))
        if ctl is None:
            out.append(mixtral_ref.served_gaps(logits, len(a.prompt), served))
        else:
            first = np.asarray(ctl(params, jnp.asarray(seq)))[
                len(a.prompt) - 1:len(a.prompt) - 1 + len(served)].argmax(1)
            out.append(mixtral_ref.served_gaps(logits, len(a.prompt), first))
    return out


def readings(cfg: dict, params, report, arrivals, seed: int, *,
             control: bool = False):
    """The numbers the check compares, over finished requests sampled from
    the seed; with ``control``, of the tokens the float8 control puts first
    instead of the served ones.  None when no request finished."""
    lim = cfg["check"]
    picked = sample_requests(report, arrivals, seed,
                             int(lim["sample_tokens"]),
                             int(lim["sample_requests"]))
    if not picked:
        return None
    gaps = reference_gaps(cfg, params, picked, quantize=control)
    numbers = gap_numbers(gaps)
    say(f"check{' (control)' if control else ''} {len(picked)} requests, "
        f"{sum(len(g) for g in gaps)} served tokens: {numbers} (widest per "
        f"request {[round(float(g.max()), 6) for g in gaps]})")
    return numbers


def check(cfg: dict, params, report, arrivals, seed: int):
    lim = cfg["check"]
    numbers = readings(cfg, params, report, arrivals, seed)
    if numbers is None:
        return [("finished_requests", 0.0, 1.0)], 1
    compared = [(name, numbers[name], float(lim[name])) for name in numbers
                if lim.get(name) is not None]
    if not compared:
        raise ValueError("the configuration sets no limit for any number "
                         "the check compares")
    bad = sum(not (np.isfinite(v) and v <= limit)
              for _, v, limit in compared)
    return compared, int(bad > 0)


def gap_numbers(gaps) -> dict[str, float]:
    """The numbers the check can compare: the widest gap of any served
    token, and the mean gap over all served tokens."""
    flat = np.concatenate(gaps)
    return {"max_logit_gap": float(flat.max()),
            "mean_logit_gap": float(flat.mean())}


def calibrate(found: dict, args, chips: int, record_trace) -> dict:
    """Readings for the limits, in one process: for each of ``args.seeds``
    the check's numbers over its window (program); for each of
    ``args.control_seeds`` the same numbers of the float8 control."""
    from bench.harness import Tracer
    from repro.comm import telemetry

    cfg, traffic = found["config"], found["traffic"]
    chunk = int(cfg["program"]["prefill_chunk"])
    out = {"program": {}, "control": {}}
    for seed in args.seeds + args.control_seeds:
        spans = Spans()
        st = build(cfg, seed, spans)
        engine = st["engine"]
        warm_up(engine, cfg, spans)
        arrivals = open_loop(traffic, seed, args.seconds, cfg["vocab_size"])
        res = window(engine, arrivals, args.seconds, spans,
                     Tracer(False, spans), chunk, telemetry)
        report = engine.report()
        if args.trace_out and seed == args.seeds[0]:
            record_trace(lambda sp, tr: window(
                engine, open_loop(traffic, seed + 1, 1.5, cfg["vocab_size"]),
                1.5, sp, tr, chunk, telemetry))
        del engine, st["engine"], st["layer"]
        gc.collect()
        for side, control in (("program", False), ("control", True)):
            if seed in (args.control_seeds if control else args.seeds):
                out[side][seed] = readings(cfg, st["params"], report,
                                           arrivals, seed, control=control)
        say(f"seed {seed}: program {out['program'].get(seed, '-')}, control "
            f"{out['control'].get(seed, '-')}, compiles in window "
            f"{res['compiles']}")
        del st
        gc.collect()
    return out
