"""End-to-end training driver with checkpoint/restart, straggler watch and
elastic resume.

Examples (CPU):
  PYTHONPATH=src python -m repro.launch.train --preset lm100m --steps 200
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --steps 20 --batch 8 --seq 128

On a pod, the same driver runs under the production mesh: --mesh 16x16.
XLA's latency-hiding scheduler overlaps the FSDP all-gathers with compute;
enable via:
  XLA_FLAGS="--xla_tpu_enable_latency_hiding_scheduler=true"  (TPU only)
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ArchConfig
from repro.configs.registry import get_config
from repro.data.pipeline import DataState, SyntheticLM
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models.transformer import Model, RunCtx
from repro.optim.adamw import AdamW, cosine_schedule
from repro.runtime import sharding as sh
from repro.runtime.fault import StepTimer, StragglerWatch, retrying
from repro.runtime.steps import build_train_step

log = logging.getLogger("repro.train")


def preset_lm100m() -> ArchConfig:
    """~100M-param dense LM for the end-to-end CPU example."""
    return ArchConfig(
        name="lm100m", family="dense", num_layers=12, d_model=768,
        num_heads=12, num_kv_heads=4, d_ff=3072, vocab_size=32768,
        head_dim=64,
    )


def make_mesh(spec: str):
    if spec == "local":
        return make_local_mesh()
    if spec in ("16x16", "pod"):
        return make_production_mesh()
    if spec in ("2x16x16", "multipod"):
        return make_production_mesh(multi_pod=True)
    dims = tuple(int(d) for d in spec.split("x"))
    axes = ("data", "model")[: len(dims)]
    return make_local_mesh(dims, axes)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--preset", default=None, choices=[None, "lm100m"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--mesh", default="local")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    if args.preset == "lm100m":
        cfg = preset_lm100m()
    elif args.arch:
        cfg = get_config(args.arch, reduced=args.reduced)
    else:
        raise SystemExit("pass --arch or --preset")

    mesh = make_mesh(args.mesh)
    has_model_axis = "model" in mesh.shape and mesh.shape["model"] > 1
    fsdp = "data" if mesh.shape.get("data", 1) > 1 else None
    rules = sh.ShardingRules(
        mesh=mesh, fsdp_axes=fsdp,
        ep_mode=cfg.is_moe and cfg.num_experts >= mesh.shape.get("model", 1),
    ) if (has_model_axis or fsdp) else None

    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    ctx = RunCtx(
        moe_groups=max(1, min(dp, args.batch)),
        remat="full",
        constrain=sh.make_constrain(rules) if rules else None,
        act_dtype=jnp.float32 if jax.default_backend() == "cpu"
        else jnp.bfloat16,
        vocab_shards=mesh.shape.get("model", 1),
    )
    model = Model(cfg, ctx)
    opt = AdamW(lr=cosine_schedule(args.lr, args.warmup, args.steps),
                weight_decay=0.01)

    # ---- init or resume ----
    params = model.init_params(jax.random.PRNGKey(args.seed))
    opt_state = opt.init(params)
    if rules is not None:
        pshard = sh.param_shardings(rules, jax.eval_shape(lambda: params))
        oshard = sh.param_shardings(rules, jax.eval_shape(lambda: opt_state))
        params = jax.tree.map(jax.device_put, params, pshard)
        opt_state = jax.tree.map(jax.device_put, opt_state, oshard)
    else:
        pshard = None

    data = SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    dstate = DataState()

    mgr = None
    start_step = 0
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, save_every=args.save_every)
        got = mgr.restore_latest(
            {"params": params, "opt": opt_state},
            shardings={"params": pshard, "opt": oshard} if rules else None)
        if got[0] is not None:
            start_step, tree, extra_state = got
            params, opt_state = tree["params"], tree["opt"]
            dstate = DataState.from_json(extra_state.get("data", {"step": 0}))
            log.info("resumed from step %d", start_step)

    step_fn = jax.jit(
        build_train_step(
            model, opt, accum_steps=args.accum,
            grad_shardings=pshard),
        donate_argnums=(0, 1))

    watch = StragglerWatch()
    extra = None
    if cfg.is_encdec:
        extra = {"frames": jnp.zeros(
            (args.batch, cfg.encoder_seq, cfg.d_model), ctx.act_dtype)}
    if cfg.is_vlm:
        extra = {"image_embeds": jnp.zeros(
            (args.batch, cfg.num_image_tokens, cfg.d_model), ctx.act_dtype)}

    metrics_hist = []

    def one_step(params, opt_state, batch):
        return step_fn(params, opt_state, batch, extra)

    safe_step = retrying(one_step, retries=1)

    t_start = time.time()
    for step in range(start_step, args.steps):
        tokens, labels = data.batch_at(dstate.step)
        if rules is not None:
            bshard = sh.batch_sharding(rules, tokens.shape)
            tokens = jax.device_put(tokens, bshard)
            labels = jax.device_put(labels, bshard)
        with StepTimer() as t:
            params, opt_state, metrics = safe_step(
                params, opt_state, (jnp.asarray(tokens), jnp.asarray(labels)))
            loss = float(metrics["loss"])
        dstate.step += 1
        watch.observe(t.dt)
        if watch.persistent:
            log.warning("persistent straggler detected; checkpoint + "
                        "re-slice advised")
        if step % args.log_every == 0 or step == args.steps - 1:
            log.info("step %d loss %.4f gnorm %.3f %.2fs/step",
                     step, loss, float(metrics["grad_norm"]), t.dt)
        metrics_hist.append(
            {"step": step, "loss": loss, "sec": t.dt})
        if mgr is not None:
            mgr.maybe_save(step + 1, {"params": params, "opt": opt_state},
                           extra={"data": dstate.to_json()})

    if mgr is not None:
        mgr.maybe_save(args.steps, {"params": params, "opt": opt_state},
                       extra={"data": dstate.to_json()}, force=True)
        mgr.wait()
    wall = time.time() - t_start
    log.info("done: %d steps in %.1fs (%.2fs/step)",
             args.steps - start_step, wall,
             wall / max(1, args.steps - start_step))
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(metrics_hist, f)
    return metrics_hist


if __name__ == "__main__":
    main()
