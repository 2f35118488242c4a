"""Closed-batch decode: ``ServeEngine`` with every lane holding one cached
session, each engine tick decoding one greedy token on every lane.

Set-up admits one session per lane (chunked prefill into the cache, then
the insert), which is most of ``setup_s``, and warms up the decode tick.
In the window no lane finishes and none is admitted (each session may
generate up to the cache's end), so every tick does the same work:
``step_ms`` is the window over the ticks completed in it.

The check reads what the timed ticks produced.  At 3 ticks drawn from the
seed (a reservoir over the window) and the last, the recorder keeps the
logits rows of 8 lanes drawn from the seed (the longest session among
them) and every MoE layer's top-k choices; they stay on the device until
the window has closed.  The plain reference (``bench.refs.moonlight_ref``)
then runs each sampled lane's prompt and served tokens; at the checked rows
its routing is pinned to the program's choices where each of them scores
within the configuration's ``margin`` of the reference's k-th best.  It
compares (a) the largest |logit - reference| over the reference row's
standard deviation, and the largest root mean square of the same over a
row, (b) the share of (row, MoE layer) choices outside the margin and (c)
the engine's ``moe_dropped`` counter.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time

import numpy as np

from bench.common import Measured, memory_peak, rng, say
from bench.refs import moonlight_ref
from bench.traffic import _lognormal_quantiles

# program fields that must equal the configuration's published values
_SIZES = {"d_model": "hidden_size", "num_heads": "num_attention_heads",
          "d_ff": "moe_intermediate_size", "dense_d_ff": "intermediate_size",
          "vocab_size": "vocab_size", "num_experts": "n_routed_experts",
          "experts_per_token": "num_experts_per_tok",
          "num_layers": "num_hidden_layers", "kv_lora_rank": "kv_lora_rank",
          "qk_nope_head_dim": "qk_nope_head_dim",
          "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
          "first_dense_layers": "first_k_dense_replace",
          "router_scale": "routed_scaling_factor",
          "router_score": "scoring_func", "rope_theta": "rope_theta"}
WARMUP_TICKS = 2


def program_config(cfg: dict):
    """The program's ``ArchConfig`` for this configuration, checked value by
    value against the configuration's."""
    from repro.configs.registry import get_config

    prog = cfg["program"]
    arch = dataclasses.replace(
        get_config(prog["arch"]), num_layers=cfg["num_hidden_layers"],
        capacity_factor=float(prog["capacity_factor"]),
        **prog.get("overrides", {}))
    want = {f: cfg[k] for f, k in _SIZES.items()}
    want["residual_d_ff"] = cfg["n_shared_experts"] * cfg[
        "moe_intermediate_size"]
    for field, value in want.items():
        if getattr(arch, field) != value:
            raise ValueError(f"program {field}={getattr(arch, field)!r} but "
                             f"the configuration gives {value!r}")
    if (cfg["q_lora_rank"] is not None or cfg["n_group"] != 1
            or cfg["topk_group"] != 1 or cfg["topk_method"] != "noaux_tc"
            or not cfg["norm_topk_prob"] or cfg["tie_word_embeddings"]):
        raise ValueError("the program serves noaux_tc routing in one group, "
                         "renormalised, no query compression and an untied "
                         "head")
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
        params = moonlight_ref.make_params(abstract, seed)
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


def sessions(cfg: dict, traffic: dict, seed: int) -> list[np.ndarray]:
    """One prompt a lane.  Every seed gets the same multiset of lengths
    (the log-normal's quantiles, a multiple of the prefill chunk), in an
    order and with token ids drawn from the seed."""
    prog = cfg["program"]
    lanes = int(traffic["lanes"])
    spec = traffic["prompt_tokens"]
    if lanes != int(prog["num_slots"]):
        raise ValueError(f"{lanes} sessions for {prog['num_slots']} lanes")
    if int(spec["multiple"]) % int(prog["prefill_chunk"]) or \
            int(spec["min"]) % int(prog["prefill_chunk"]):
        raise ValueError("prompt lengths must be multiples of the prefill "
                         "chunk")
    if int(spec["max"]) + WARMUP_TICKS + 1 >= int(prog["cache_len"]):
        raise ValueError("the longest prompt must leave cache for the "
                         "window's ticks")
    lengths = rng(seed, "traffic").permutation(
        _lognormal_quantiles(spec, lanes))
    tok = rng(seed, "tokens")
    return [tok.integers(0, cfg["vocab_size"], int(n), dtype=np.int32)
            for n in lengths]


def admit(engine, cfg: dict, prompts, spans) -> None:
    """Every session into its lane (lane i holds prompt i), then the warm-up
    ticks: the first tick after the admissions compiles the decode step."""
    from repro.serve import Request

    cache_len = int(cfg["program"]["cache_len"])
    for i, p in enumerate(prompts):
        engine.submit(Request(id=i, prompt=p,
                              max_new_tokens=cache_len - len(p)))
    with spans.span("setup.prefill"):
        engine.step()
    with spans.span("setup.warmup"):
        for _ in range(WARMUP_TICKS):
            engine.step()
    if [s.request_id for s in engine.slots.active()] != list(
            range(len(prompts))):
        raise RuntimeError("every lane must hold the session of its index")


class Recorder:
    """The logits rows of the sampled lanes and all lanes' MoE choices at
    ticks sampled from the seed: a reservoir of ``ticks`` over the window,
    and the last tick.  Slices are taken on the device when a tick is kept
    and fetched only by ``fetch``."""

    def __init__(self, engine, lanes, ticks: int, seed: int):
        import jax.numpy as jnp
        self.engine, self.k = engine, ticks
        self.lanes = jnp.asarray(np.asarray(lanes, np.int32))
        self.r = rng(seed, "check-ticks")
        self.kept: list = []
        self.seen = 0

    def take(self, tick: int):
        e = self.engine
        return tick, e.last_logits[self.lanes, 0], e.last_experts

    def offer(self, tick: int) -> None:
        self.seen += 1
        if len(self.kept) < self.k:
            self.kept.append(self.take(tick))
        else:
            j = int(self.r.integers(0, self.seen))
            if j < self.k:
                self.kept[j] = self.take(tick)

    def fetch(self, last_tick: int) -> dict:
        """{tick: (logits (lanes, V) f32, experts (MoE layers, B, k))} of
        the kept ticks and the last."""
        out = {t: (np.asarray(lg, np.float32), np.asarray(ex))
               for t, lg, ex in self.kept}
        if last_tick not in out:
            _, lg, ex = self.take(last_tick)
            out[last_tick] = (np.asarray(lg, np.float32), np.asarray(ex))
        return dict(sorted(out.items()))


def window(engine, seconds: float, spans, tracer, recorder, first_tick):
    """Decode ticks for ``seconds``; returns (elapsed s, ticks)."""
    tracer.start()
    ticks = 0
    try:
        with spans.span("window"):
            t0 = time.perf_counter()
            end = t0 + seconds
            while time.perf_counter() < end:
                engine.step()
                recorder.offer(first_tick + ticks)
                ticks += 1
            elapsed = time.perf_counter() - t0
    finally:
        tracer.stop()
    return elapsed, ticks


def sample_lanes(prompts, seed: int, n: int) -> list[int]:
    """``n`` lanes: the longest session, then others drawn from the seed."""
    longest = int(np.argmax([len(p) for p in prompts]))
    rest = [i for i in rng(seed, "check-lanes").permutation(len(prompts))
            if i != longest]
    return [longest] + [int(i) for i in rest[:n - 1]]


def served_window(cfg, traffic, seed, seconds, spans, tracer):
    """Build, admit, warm up and run the window: what ``run`` and
    ``calibrate`` share."""
    from repro.comm import telemetry

    snap = telemetry.stats.snapshot()
    st = build(cfg, seed, spans)
    engine = st["engine"]
    prompts = sessions(cfg, traffic, seed)
    admit(engine, cfg, prompts, spans)
    lanes = sample_lanes(prompts, seed, int(traffic["check"]["lanes"]))
    recorder = Recorder(engine, lanes, int(traffic["check"]["ticks"]), seed)
    first = WARMUP_TICKS + 1
    recorder.take(first - 1)        # compiles the slicing before the window
    # as a server does after warm-up: set-up's objects leave the collector's
    # generations, so a full collection in the window walks what the window
    # allocated, not the whole heap of JAX's compiled objects
    gc.collect()
    gc.freeze()
    try:
        elapsed, ticks = window(engine, seconds, spans, tracer, recorder,
                                first)
    finally:
        gc.unfreeze()
    st.update(prompts=prompts, lanes=lanes, elapsed=elapsed, ticks=ticks,
              first_tick=first,
              samples=recorder.fetch(first + ticks - 1),
              still_active=len(engine.slots.active()))
    if tracer.enabled:
        from bench.scopes import hlo_op_names
        st["op_names"] = hlo_op_names(engine._decode_fn.lower(
            st["params"], engine.cache, engine._tokens).compile().as_text())
    st["outputs"] = engine.report().outputs
    st["moe_dropped"] = telemetry.stats.since(snap)["moe_dropped"]
    st["plan_sources"] = {k: v for k, v in telemetry.stats.since(snap)
                          .items() if v and not isinstance(v, dict)}
    return st


def free_engine(st) -> None:
    for k in ("engine", "layer"):
        st.pop(k, None)
    gc.collect()


def run(cfg: dict, traffic: dict, seed: int, seconds: float, spans,
        chips: int, tracer) -> Measured:
    prog = cfg["program"]
    st = served_window(cfg, traffic, seed, seconds, spans, tracer)
    peak = memory_peak(chips)
    ticks, lanes = st["ticks"], len(st["prompts"])
    step_s = st["elapsed"] / max(ticks, 1)
    say(f"moonlight {cfg['num_hidden_layers']} layers, {lanes} lanes, cache "
        f"{prog['cache_len']}, chunk {prog['prefill_chunk']}; MoE decode "
        f"{st['layer'].strategies}; window {st['elapsed']:.6f} s, {ticks} "
        f"ticks, step {step_s * 1e3:.6f} ms; lanes active after "
        f"{st['still_active']}; peak {peak} B; plan sources "
        f"{st['plan_sources']}")
    plens = np.asarray([len(p) for p in st["prompts"]], np.int64)
    # decode tick j attends positions [0, plen + j] of each lane
    mid = st["first_tick"] + (ticks - 1) / 2.0
    samples = st["samples"]
    counters = {
        "ticks": ticks, "lanes": lanes,
        "attended_rows": float(plens.sum() + lanes * (mid + 1)),
        "experts_hit": float(np.mean([
            sum(len(np.unique(ex[li])) for li in range(ex.shape[0]))
            for _, ex in samples.values()])),
        "moe_dropped": st["moe_dropped"],
    }
    if "op_names" in st:
        counters["decode_op_names"] = st["op_names"]
    free_engine(st)
    if st["still_active"] != lanes or ticks == 0:
        # a lane reached the cache's end inside the window: the traffic no
        # longer fits the cell, nothing to check
        compared = [("lanes_finished_in_window",
                     float(lanes - st["still_active"]), 0.0)]
        ok = False
    else:
        compared, ok = check(cfg, st)
    return Measured(end_to_end={"step_ms": step_s * 1e3}, counters=counters,
                    compared=compared, correct=ok, attempted=ticks * lanes,
                    failed=0 if ok else ticks * lanes,
                    memory_peak_bytes=peak)


def _sequence(st, lane: int, last_tick: int):
    """The lane's prompt and the served tokens decode read up to
    ``last_tick``, padded for the reference."""
    toks = np.concatenate([st["prompts"][lane], np.asarray(
        st["outputs"][lane][:last_tick + 1], np.int32)])
    seq = np.zeros(moonlight_ref.padded_length(len(toks)), np.int32)
    seq[:len(toks)] = toks
    return seq


def readings(cfg: dict, st, *, control: bool = False) -> dict:
    """The numbers the check compares, over the sampled lanes and ticks;
    with ``control``, of the float8 control's logits and choices in place
    of the program's."""
    import jax.numpy as jnp

    margin = float(cfg["check"]["margin"])
    key = moonlight_ref.config_key(cfg)
    ref = moonlight_ref.compiled_forward(key, margin, False)
    ctl = moonlight_ref.compiled_forward(key, margin, True) if control \
        else None
    ticks = sorted(st["samples"])
    gaps, rms, outside, pairs = [], [], 0, 0
    for n, lane in enumerate(st["lanes"]):
        plen = len(st["prompts"][lane])
        rows = jnp.asarray([plen + t for t in ticks], jnp.int32)
        seq = jnp.asarray(_sequence(st, lane, ticks[-1]))
        if ctl is None:
            got = np.stack([st["samples"][t][0][n] for t in ticks])
            pin = np.stack([st["samples"][t][1][:, lane] for t in ticks])
        else:
            got, pin, _ = ctl(st["params"], seq, rows, None)
            got, pin = np.asarray(got), np.asarray(pin)
        want, _, within = ref(st["params"], seq, rows, jnp.asarray(pin))
        want = np.asarray(want, np.float64)
        err = np.abs(got - want) / want.std(-1, keepdims=True)
        gaps.append(float(err.max()))
        rms.append(float(np.sqrt((err ** 2).mean(-1)).max()))
        within = np.asarray(within)
        outside += int((~within).sum())
        pairs += within.size
    numbers = {"max_logit_gap": max(gaps), "rms_logit_gap": max(rms),
               "outside_margin_share": outside / pairs}
    say(f"check{' (control)' if control else ''}: lanes {st['lanes']}, "
        f"ticks {ticks}: {numbers}; gap per lane "
        f"{[round(g, 6) for g in gaps]}")
    return numbers


def check(cfg: dict, st):
    lim = cfg["check"]
    numbers = dict(readings(cfg, st), moe_dropped=float(st["moe_dropped"]))
    compared = [(name, numbers[name], float(lim[name])) for name in numbers
                if lim.get(name) is not None]
    if len(compared) != len(numbers):
        raise ValueError("the configuration sets no limit for "
                         f"{sorted(set(numbers) - {c[0] for c in compared})}")
    ok = all(np.isfinite(v) and v <= limit for _, v, limit in compared)
    return compared, ok


def calibrate(found: dict, args, chips: int, record_trace) -> dict:
    """Readings for the limits, in one process: for each of ``args.seeds``
    the check's numbers over its window (program); for each of
    ``args.control_seeds`` the same numbers of the float8 control."""
    import json

    from bench.common import Spans
    from bench.harness import Tracer

    cfg, traffic = found["config"], found["traffic"]
    out = {"program": {}, "control": {}}
    for seed in args.seeds + args.control_seeds:
        spans = Spans()
        tracing = bool(args.trace_out) and seed == args.seeds[0]
        st = {}

        def go(sp, tr):
            st.update(served_window(cfg, traffic, seed, args.seconds, sp,
                                    tr))

        if tracing:
            record_trace(go)
            with open(args.trace_out + ".ops.json", "w") as f:
                json.dump(st.get("op_names", {}), f)
        else:
            go(spans, Tracer(False, spans))
        free_engine(st)
        for side, control in (("program", False), ("control", True)):
            if seed in (args.control_seeds if control else args.seeds):
                out[side][seed] = readings(cfg, st, control=control)
        say(f"seed {seed}: {st['ticks']} ticks in {st['elapsed']:.3f} s, "
            f"moe_dropped {st['moe_dropped']}, program "
            f"{out['program'].get(seed, '-')}, control "
            f"{out['control'].get(seed, '-')}")
        del st
        gc.collect()
    return out
