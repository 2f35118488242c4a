"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces a JSON artifact with:
  * memory_analysis (proves per-device residency fits),
  * cost_analysis FLOPs/bytes,
  * the parsed collective schedule (per-op bytes, ICI vs DCI),
  * the three roofline terms + dominant bottleneck + MODEL_FLOPS ratio.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out experiments/dryrun
"""
import argparse
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ARCH_NAMES, get_config
from repro.configs.shapes import SHAPES, skip_reason
from repro.core import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models.transformer import Model, RunCtx
from repro.optim.adamw import AdamW
from repro.runtime import sharding as sh
from repro.runtime.steps import (build_decode_step, build_prefill,
                                 build_train_step, model_flops)

SERVE_RESIDENCY_LIMIT = 12e9  # bytes/device of weights before ZeRO-serving


def _sds(tree_shapes, shardings):
    return jax.tree.map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d),
        tree_shapes, shardings)


def _cast_tree(tree_shapes, dtype):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype if jnp.issubdtype(s.dtype, jnp.floating)
            else s.dtype),
        tree_shapes)


def make_rules(cfg, mesh, mode):
    multi = "pod" in mesh.shape
    ep = cfg.is_moe and cfg.num_experts >= mesh.shape["model"]
    if mode == "train":
        if multi:
            # hierarchical ZeRO (EXPERIMENTS.md §Perf cell B): bf16 compute
            # params gather pod-locally over "data"; the f32 optimizer state
            # spreads over ("pod","data") — weight gathers never cross DCI
            return sh.ShardingRules(
                mesh=mesh, fsdp_axes="data",
                opt_fsdp_axes=("pod", "data"), ep_mode=ep)
        return sh.ShardingRules(mesh=mesh, fsdp_axes="data", ep_mode=ep)
    # serve: weights over model axis only, unless they would not fit
    fsdp = ("pod", "data") if multi else "data"
    pshapes = jax.eval_shape(
        Model(cfg, RunCtx()).init_params, jax.random.PRNGKey(0))
    pbytes = sum(int(np.prod(s.shape)) * 2  # bf16 serving weights
                 for s in jax.tree.leaves(pshapes))
    if pbytes / mesh.shape["model"] <= SERVE_RESIDENCY_LIMIT:
        fsdp = None  # fits with pure TP: replicate over data for latency
    return sh.ShardingRules(mesh=mesh, fsdp_axes=fsdp, ep_mode=ep)


def input_specs(cfg, shape, mesh, *, mode: str, rules=None,
                remat_groups: int = 1):
    """ShapeDtypeStruct stand-ins for every input of the lowered step."""
    rules = rules or make_rules(cfg, mesh, mode)
    dp = int(np.prod([mesh.shape[a] for a in
                      (("pod", "data") if "pod" in mesh.shape
                       else ("data",))]))
    ctx = RunCtx(moe_groups=max(1, min(dp, shape.global_batch)),
                 remat="full" if mode == "train" else "none",
                 constrain=sh.make_constrain(rules),
                 vocab_shards=mesh.shape["model"],
                 remat_groups=remat_groups if mode == "train" else 1)
    model = Model(cfg, ctx)

    pshapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    # compute params are bf16 in BOTH train and serve: train uses true mixed
    # precision (f32 masters live in the optimizer state), so every weight
    # collective moves 2-byte payloads
    pshapes = _cast_tree(pshapes, jnp.bfloat16)
    pspecs = _sds(pshapes, sh.param_shardings(rules, pshapes))

    b = shape.global_batch
    tok = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        (b, s), jnp.int32, sharding=sh.batch_sharding(rules, (b, s)))

    extra = None
    if cfg.is_encdec:
        eshape = (b, cfg.encoder_seq, cfg.d_model)
        extra = {"frames": jax.ShapeDtypeStruct(
            eshape, jnp.bfloat16, sharding=sh.batch_sharding(rules, eshape))}
    if cfg.is_vlm:
        ishape = (b, cfg.num_image_tokens, cfg.d_model)
        extra = {"image_embeds": jax.ShapeDtypeStruct(
            ishape, jnp.bfloat16, sharding=sh.batch_sharding(rules, ishape))}

    if mode == "train":
        opt = AdamW(lr=1e-4, mixed_precision=True)
        oshapes = jax.eval_shape(opt.init, pshapes)
        ospecs = _sds(oshapes, sh.param_shardings(rules, oshapes))
        return model, ctx, {
            "params": pspecs, "opt": ospecs,
            "batch": (tok(shape.seq_len), tok(shape.seq_len)),
            "extra": extra,
        }
    if mode == "prefill":
        return model, ctx, {"params": pspecs, "tokens": tok(shape.seq_len),
                            "extra": extra}
    # decode
    cross_len = cfg.encoder_seq or cfg.num_image_tokens or 0
    cshapes = jax.eval_shape(
        lambda: model.init_cache(b, shape.seq_len, cross_len=cross_len,
                                 dtype=jnp.bfloat16))
    cspecs = _sds(cshapes, sh.cache_shardings(rules, cshapes))
    return model, ctx, {"params": pspecs, "cache": cspecs,
                        "tokens": tok(1)}


DEFAULT_ACCUM = 8  # microbatched grad accumulation for train cells


def lower_cell(cfg, shape, mesh, *, mode: str, accum_steps: int | None = None,
               remat_groups: int = 1):
    if accum_steps is None:
        accum_steps = DEFAULT_ACCUM if mode == "train" else 1
    rules = make_rules(cfg, mesh, mode)
    model, ctx, specs = input_specs(cfg, shape, mesh, mode=mode, rules=rules,
                                    remat_groups=remat_groups)
    if mode == "train":
        opt = AdamW(lr=1e-4, mixed_precision=True)
        gshard = jax.tree.map(lambda s: s.sharding, specs["params"])
        step = build_train_step(model, opt, grad_shardings=gshard,
                                accum_steps=accum_steps)
        oshard = jax.tree.map(lambda s: s.sharding, specs["opt"])
        fn = jax.jit(step, donate_argnums=(0, 1),
                     out_shardings=(gshard, oshard, None))
        args = (specs["params"], specs["opt"], specs["batch"],
                specs["extra"])
    elif mode == "prefill":
        step = build_prefill(model)
        fn = jax.jit(step)
        args = (specs["params"], specs["tokens"], specs["extra"])
    else:
        step = build_decode_step(model)
        fn = jax.jit(step, donate_argnums=(1,))
        args = (specs["params"], specs["cache"], specs["tokens"])
    with mesh:
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    return compiled


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str | None = None, verbose: bool = True):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_tag = "multipod_2x16x16" if multi_pod else "pod_16x16"
    name = f"{arch}__{shape_name}__{mesh_tag}"

    reason = skip_reason(cfg, shape)
    if reason:
        art = {"name": name, "skipped": True, "reason": reason}
        _write(out_dir, name, art)
        if verbose:
            print(f"SKIP {name}: {reason}")
        return art

    mesh = make_production_mesh(multi_pod=multi_pod)
    ndev = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    compiled = lower_cell(cfg, shape, mesh, mode=shape.mode)
    compile_s = time.time() - t0

    report = rl.analyze_compiled(
        compiled, name=name, num_devices=ndev,
        devices_per_pod=256 if multi_pod else ndev,
        model_flops=model_flops(cfg, mode=shape.mode,
                                batch=shape.global_batch,
                                seq=shape.seq_len),
        bf16_program=True,  # models are authored bf16; see hlo_cost docs
    )
    ma = compiled.memory_analysis()
    art = report.to_json()
    art.update({
        "skipped": False,
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "compile_seconds": compile_s,
        "memory_analysis": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes_per_device": int(
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
        },
    })
    _write(out_dir, name, art)
    if verbose:
        mb = art["memory_analysis"]["peak_bytes_per_device"] / 2**30
        print(f"OK {name}: compile={compile_s:.1f}s "
              f"peak={mb:.2f}GiB/dev dominant={art['dominant']} "
              f"terms(c/m/coll)=({art['compute_term_s']:.2e},"
              f"{art['memory_term_s']:.2e},{art['collective_term_s']:.2e})s "
              f"useful={art['useful_flops_ratio']:.2f}")
    return art


def _write(out_dir, name, art):
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, name + ".json"), "w") as f:
            json.dump(art, f, indent=2)


def main():
    # the production meshes need 512 host devices; jax reads the flag when
    # its backend first initializes, which nothing before this line does
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count=512"]))
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ARCH_NAMES if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                try:
                    run_cell(arch, shape, multi_pod=multi, out_dir=args.out)
                except Exception:
                    failures.append((arch, shape, multi))
                    print(f"FAIL {arch} {shape} multi={multi}")
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: {failures}")
    print("DRYRUN_ALL_OK")


if __name__ == "__main__":
    main()
