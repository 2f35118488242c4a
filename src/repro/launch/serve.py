"""Serving driver: a continuous-batching engine over a request queue.

CPU example (8 forced host devices for the MoE comm path):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x22b \
      --reduced --requests 16 --slots 8 --prompt-len 24 --gen 16 --moe-comm

``repro.serve`` supplies the loop (queue → slots → engine, docs/serving.md);
this driver builds the model, fabricates a Poisson-ish arrival trace, and
prints the throughput/latency report.  Families without a per-slot cache
(ssm / hybrid / encdec / vlm) fall back to the original batched demo loop.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.train import make_mesh, preset_lm100m
from repro.models.transformer import Model, RunCtx

log = logging.getLogger("repro.serve")


def prefill_into_cache(model, params, cache, tokens):
    """Sequential prefill through decode_step — the ORACLE for the fused
    path (``Model.prefill``), which the engine uses in production.  Kept small
    and obviously-correct; tests/test_serve.py holds fused to this within
    f32 rounding."""
    def body(cache, tok):
        logits, cache = model.decode_step(params, cache, tok[:, None])
        return cache, logits
    cache, logits = jax.lax.scan(body, cache, tokens.T)
    return cache, logits[-1]


def build_moe_layer(model, params, num_slots, mesh, *, axis_name="data",
                    strategy="auto"):
    """A ``DynamicMoELayer`` sized for the engine's decode batch: one
    instance (template shapes of one layer's expert weights) serves every
    scanned layer via ``DynamicMoELayer.apply``, which takes that layer's
    weights per call — the layer holds no weights of its own."""
    from repro.models import moe as M

    cfg = model.cfg
    p = int(mesh.shape[axis_name])
    if cfg.num_experts % p or num_slots % p:
        raise ValueError(
            f"MoE comm path needs num_experts ({cfg.num_experts}) and "
            f"--slots ({num_slots}) divisible by the mesh axis ({p})")
    cap = M.moe_capacity(num_slots, cfg)
    tmpl_e, _ = M.random_router(0, num_slots, cfg.num_experts,
                                cfg.experts_per_token)
    weights = {name: jax.ShapeDtypeStruct(w.shape[1:], w.dtype)
               for name, w in params["layers"]["moe"].items()
               if name in ("w1", "w2", "w3")}
    return M.DynamicMoELayer(weights, tmpl_e, num_slots, cfg.num_experts,
                             cap, mesh, axis_name=axis_name, act=cfg.act,
                             strategy=strategy, decode=True)


def _serve_main(cfg, ctx, args):
    from repro.serve import Request, ServeEngine

    model = Model(cfg, ctx)
    # serving weights live once, in the activation dtype; one jitted init
    # writes them straight into it (no f32 copy of the stack)
    params = jax.jit(functools.partial(model.init_params,
                                       dtype=ctx.act_dtype))(
        jax.random.PRNGKey(args.seed))
    cache_len = args.prompt_len + args.gen

    moe_layer = None
    if args.moe_comm:
        if cfg.family != "moe":
            raise SystemExit("--moe-comm needs a MoE architecture")
        mesh = (make_mesh(args.mesh) if args.mesh != "local"
                else make_local_mesh((len(jax.devices()),), ("data",)))
        moe_layer = build_moe_layer(model, params, args.slots, mesh)
        log.info("MoE decode comm: strategies=%s plan_time=%.2fus",
                 moe_layer.strategies, moe_layer.plan_time * 1e6)

    engine = ServeEngine(model, params, num_slots=args.slots,
                         cache_len=cache_len,
                         prefill_chunk=args.prefill_chunk,
                         moe_layer=moe_layer, cache_dtype=ctx.act_dtype)

    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        plen = int(rng.integers(args.prompt_len // 2 + 1,
                                args.prompt_len + 1))
        engine.submit(Request(
            id=f"req{i}",
            prompt=rng.integers(0, cfg.vocab_size, (plen,)).tolist(),
            max_new_tokens=args.gen,
            # staggered arrivals in tick units: ~2 new requests per tick
            arrival_time=float(i // 2)))

    t0 = time.time()
    report = engine.run()
    wall = time.time() - t0
    log.info("%d requests, %d ticks, %.2fs wall", args.requests,
             report.ticks, wall)
    log.info("decode: %.1f tok/s, p50 %.0fus, p99 %.0fus per token",
             report.tokens_per_s, report.p50_us(), report.p99_us())
    log.info("telemetry: %s", report.telemetry)
    print("completed:", len(report.completed), "of", args.requests,
          "| total tokens:", report.total_tokens)
    return report


def _batch_demo_main(cfg, ctx, args):
    """Legacy batched demo for families without a per-slot cache."""
    model = Model(cfg, ctx)
    params = model.init_params(jax.random.PRNGKey(args.seed))

    cache_len = args.prompt_len + args.gen
    cross_len = cfg.encoder_seq or cfg.num_image_tokens or 0
    cache = model.init_cache(args.batch, cache_len, cross_len=cross_len,
                             dtype=ctx.act_dtype)
    rng = np.random.default_rng(args.seed)
    if cross_len:
        context = jnp.asarray(rng.standard_normal(
            (args.batch, cross_len, cfg.d_model)), ctx.act_dtype)
        cache = model.prefill_cross(params, cache, context)

    prompt = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)

    decode = jax.jit(model.decode_step, donate_argnums=(1,))

    t0 = time.time()
    cache, last_logits = jax.jit(
        lambda p, c, t: prefill_into_cache(model, p, c, t))(
            params, cache, prompt)
    last = jnp.argmax(last_logits[:, -1], axis=-1).astype(jnp.int32)  # (B,)
    jax.block_until_ready(last)
    t_prefill = time.time() - t0

    out_tokens = [last]
    t0 = time.time()
    for _ in range(args.gen):
        logits, cache = decode(params, cache, out_tokens[-1][:, None])
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out_tokens.append(nxt)
    jax.block_until_ready(out_tokens[-1])
    t_decode = time.time() - t0

    toks = args.gen * args.batch
    log.info("prefill %.3fs (%d tokens); decode %.3fs "
             "(%.1f tok/s aggregate)", t_prefill,
             args.batch * args.prompt_len, t_decode, toks / t_decode)
    seq = jnp.stack(out_tokens[1:], axis=1)
    print("generated shape:", seq.shape)
    return seq


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--preset", default=None, choices=[None, "lm100m"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)     # legacy demo path
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--prefill-chunk", type=int, default=None)
    ap.add_argument("--moe-comm", action="store_true",
                    help="route decode MoE through DynamicMoELayer")
    ap.add_argument("--experts", type=int, default=None,
                    help="override num_experts (e.g. to match the mesh)")
    ap.add_argument("--mesh", default="local")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    logging.basicConfig(level=logging.INFO)

    cfg = (preset_lm100m() if args.preset == "lm100m"
           else get_config(args.arch, reduced=args.reduced))
    if args.experts:
        cfg = dataclasses.replace(cfg, num_experts=args.experts)
    ctx = RunCtx(remat="none",
                 act_dtype=jnp.float32 if jax.default_backend() == "cpu"
                 else jnp.bfloat16)
    if cfg.family in ("dense", "moe"):
        return _serve_main(cfg, ctx, args)
    return _batch_demo_main(cfg, ctx, args)


if __name__ == "__main__":
    main()
