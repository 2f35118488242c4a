#!/usr/bin/env python3
"""Bring-up smoke test: drive the system's main paths once on a TPU.

Run from the checkout root on a machine with a TPU:

    python chip_smoke.py              # one chip: phases ladder, heat2d,
                                      # kernels, serve
    python chip_smoke.py --chips 4    # four chips: ladder4, heat2d4, moe4
    python chip_smoke.py --phase serve          # one phase of the set

Every phase checks its output against an independent reference and prints
one line: its sizes, its maximum error next to the stated tolerance, and
its set-up, compile and run seconds.  Those seconds are smoke output, not
benchmark numbers.  Any failure exits non-zero; the last line of a passing
run is ``{"ok": true, "device": {...}}``.  Without a TPU the script exits
non-zero before doing anything else.

Phases (sizes are the constants below):

* ladder  — ``DistributedSpMV`` on every rung (replicate, blockwise,
  condensed, overlap, auto), forward and transposed, jnp path, on a
  mesh-like EllPack matrix with 5% long-range columns.
* heat2d  — ``Heat2D.run`` against a numpy Jacobi reference.
* kernels — the ladder again with ``use_kernel=True`` at the largest
  matrix whose resident arrays fit the kernels' VMEM budget; each compiled
  step must hold a Mosaic kernel (``tpu_custom_call``).  The jnp path's
  transposed product on the same matrix is printed beside each kernel
  transposed step.
* serve   — ``ServeEngine`` on mixtral-8x22b at its published widths, depth
  cut to what one chip holds, bf16 weights, MoE decode through
  ``DynamicMoELayer``; prefill logits, and the logits of one decode tick
  through the engine's ``DynamicMoELayer`` hook, checked against the same
  weights in f32 at highest matmul precision.
* ladder4 / heat2d4 / moe4 — the paths users run across chips: the SpMV
  ladder on mesh [4] (jnp and kernel, one matrix), Heat2D on mesh [2, 2],
  and one ``DynamicMoELayer`` at mixtral widths with two experts per chip;
  each output must span all four devices and each compiled step must hold
  its collective.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

RUNGS = ("replicate", "blockwise", "condensed", "overlap", "auto")
R_NZ = 16
LONG_RANGE = 0.05
# one chip: the jnp steps hold their EllPack tables slot-major (r_nz,
# rows), unpadded in HBM; the worst step (replicate forward) needs 9.8 GB
# of v5e's 15.75 GB at 2^24 rows (compiled for a described v5e)
LADDER_ROWS = 2**24
HEAT_GRID = 16384              # field of HEAT_GRID**2 f32
# four chips: one matrix serves the jnp and the kernel ladder.  Host plan
# building is O(n) on one host thread and holds all four chips while it
# runs, so the four-chip phases are sized for about three minutes in all:
# 2^19 rows and a 4096^2 tile per chip, against 2^24 rows and 16384^2 on
# one chip
LADDER4_ROWS = 2**21
HEAT4_GRID = 8192
HEAT_STEPS = 10
SPMV_RTOL = 1e-5               # f32 vs an f64 reference, relative to max|y|
# the transposed product sums ~w*r_nz/4 contributions into each of
# columns 0 and n-1 (the band clipped at the matrix edge, w = n/256): 249 k
# at 2^24 rows.  A sequential f32 sum (numpy's np.add.at in f32) reads
# 1.069e-05 in row-major contribution order and 2.571e-06 in slot-major
# order at 2^24 rows (seed 0); 9.091e-06 and 2.129e-06 at 2^23 (seed 1).
# The tolerance is 4x the largest of these, rounded up
SPMV_T_RTOL = 5e-5
HEAT_RTOL = 1e-5
SERVE_LAYERS = 2
SERVE_SLOTS, SERVE_REQUESTS, SERVE_GEN = 8, 16, 64
SERVE_PROMPTS = (128, 256, 384, 512)
SERVE_CHUNK = 128
SERVE_CHECK_PROMPT = 16
SERVE_RTOL = 5e-2              # bf16 engine vs f32 logits, over max|logit|
MOE4_TOKENS = 32
MOE4_RTOL = 5e-2               # bf16 layer vs the f32 reference

# the collective each rung's compiled step must hold across chips
COLLECTIVES = {"replicate": ("all-gather", "all-reduce", "reduce-scatter"),
               "blockwise": ("all-to-all",), "condensed": ("all-to-all",),
               "overlap": ("all-to-all",)}


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(name: str, sizes: str, err: float, tol: float, *, setup: float,
          compile_s: float, run_s: float, extra: str = "") -> None:
    ok = err == err and err <= tol          # NaN fails
    say(f"{name} | {sizes} | max rel err {err:.3e} {'<=' if ok else '>'} "
        f"tol {tol:.0e} | setup {setup:.2f}s compile {compile_s:.2f}s "
        f"run {run_s:.4f}s (smoke timing){extra}")
    if not ok:
        raise AssertionError(f"{name}: error {err:.3e} over tolerance {tol}")


def rel_err(got, want, scale=None) -> float:
    """max|got - want| over max|scale| (``scale`` defaults to ``want``)."""
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    # f64 where either side is; two f32 grids of 2^28 cells stay f32
    dtype = np.promote_types(np.promote_types(got.dtype, want.dtype),
                             np.float32)
    got, want = got.astype(dtype, copy=False), want.astype(dtype, copy=False)
    scale = want if scale is None else np.asarray(scale)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(scale)),
                                                  1e-30))


def timed(fn, *args):
    import jax
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


def compile_text(lowered):
    """Compile a ``jax.stages.Lowered``; returns (HLO text, seconds).  The
    executable lands in JAX's compile cache, so the next call of the same
    step reuses it."""
    t = time.perf_counter()
    text = lowered.compile().as_text()
    return text, time.perf_counter() - t


# ---------------------------------------------------------------------------
# SpMV ladder
# ---------------------------------------------------------------------------

def spmv_problem(n: int, seed: int = 0):
    """Matrix, x, and f64 references for both directions."""
    import numpy as np
    from repro.core.matrix import make_mesh_like_matrix

    m = make_mesh_like_matrix(n, R_NZ, long_range_frac=LONG_RANGE,
                              seed=seed)
    x = np.random.default_rng(seed).standard_normal(n).astype(np.float32)
    x64 = x.astype(np.float64)
    diag64 = m.diag.astype(np.float64)
    y_fwd = diag64 * x64 + np.einsum("ij,ij->i", m.vals.astype(np.float64),
                                     x64[m.cols])
    y_t = diag64 * x64 + np.bincount(
        m.cols.ravel(), weights=(m.vals * x[:, None]).ravel().astype(
            np.float64), minlength=n)
    return m, x, {False: y_fwd, True: y_t}


def run_ladder(name, m, x, refs, mesh, *, use_kernel: bool, chips: int,
               blocksize=None, against=None):
    """Every rung in both directions; returns the host outputs keyed by
    (rung, transpose).  ``against`` maps a direction to another path's
    output on the same matrix, printed beside each step's error."""
    import numpy as np
    from repro.core.spmv import DistributedSpMV

    outs = {}
    for rung in RUNGS:
        for transpose in (False, True):
            t = time.perf_counter()
            eng = DistributedSpMV(m, mesh, strategy=rung,
                                  transpose=transpose, use_kernel=use_kernel,
                                  blocksize=blocksize)
            xs = eng.shard_vector(x)
            setup = time.perf_counter() - t
            text, t_compile = compile_text(eng.lower(xs))
            y, t_run = timed(eng, xs)
            extra = f" | resolved {eng.strategy}" if rung == "auto" else ""
            if use_kernel:
                if "tpu_custom_call" not in text:
                    raise AssertionError(
                        f"{name} {rung}: no Mosaic kernel in the compiled "
                        "step")
                extra += " | tpu_custom_call present"
            if chips > 1:
                devices = len(y.sharding.device_set)
                if devices != chips:
                    raise AssertionError(
                        f"{name} {rung}: output spans {devices} devices")
                want = COLLECTIVES[eng.strategy]
                if not any(c in text for c in want):
                    raise AssertionError(
                        f"{name} {rung}: compiled step holds none of {want}")
                held = "/".join(c for c in want if c in text)
                extra += f" | spans {devices} devices, holds {held}"
            y = np.asarray(y)
            if against is not None and transpose in against:
                other = against[transpose]
                extra += (f" | jnp path on this matrix: err "
                          f"{rel_err(other, refs[transpose]):.3e}, "
                          f"max|kernel-jnp| {rel_err(y, other, refs[transpose]):.3e}")
            check(f"{name} rung={rung} "
                  f"dir={'transpose' if transpose else 'forward'}",
                  f"n={m.n} r_nz={R_NZ} p={eng.p}",
                  rel_err(y, refs[transpose]),
                  SPMV_T_RTOL if transpose else SPMV_RTOL, setup=setup,
                  compile_s=t_compile, run_s=t_run, extra=extra)
            outs[rung, transpose] = y
            del eng, xs
            gc.collect()
    return outs


def kernel_rows() -> int:
    """Largest power-of-two row count whose kernel path fits VMEM: the
    full unpack and the SpMV window each keep two arrays of ~n items."""
    from repro.kernels.layout import VMEM_BUDGET_BYTES, item_bytes

    n = 2**10
    while 2 * item_bytes(2 * n + 2) <= VMEM_BUDGET_BYTES and n < 2**24:
        n *= 2
    return n


# blockwise blocks of 128 fill whole lanes; a narrower block would pad
# each resident row to 128 lanes in VMEM and shrink the admitted matrix
KERNEL_BLOCK = 128


def phase_ladder(mesh, rows: int):
    m, x, refs = spmv_problem(rows)
    run_ladder("ladder", m, x, refs, mesh, use_kernel=False, chips=1)


def say_kernel_size(name: str, n: int) -> None:
    from repro.kernels.layout import (VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES,
                                      item_bytes)
    say(f"{name}: largest admitted matrix {kernel_rows()} rows, running "
        f"n={n} (x_copy and the SpMV window {2 * item_bytes(n + 2)} bytes "
        f"resident of a {VMEM_BUDGET_BYTES}-byte budget, VMEM limit "
        f"{VMEM_LIMIT_BYTES})")


def phase_kernels(mesh, n: int):
    import numpy as np
    from repro.core.spmv import DistributedSpMV

    say_kernel_size("kernels", n)
    m, x, refs = spmv_problem(n, seed=1)
    # the jnp path's transposed product on the same matrix and plan, for
    # comparison with each kernel transposed step
    eng = DistributedSpMV(m, mesh, strategy="replicate", transpose=True,
                          blocksize=KERNEL_BLOCK)
    y_jnp = np.asarray(timed(eng, eng.shard_vector(x))[0])
    del eng
    run_ladder("kernels", m, x, refs, mesh, use_kernel=True, chips=1,
               blocksize=KERNEL_BLOCK, against={True: y_jnp})


def phase_ladder4(mesh, n: int):
    """jnp and kernel ladders on mesh [4], one matrix and one plan."""
    assert n <= kernel_rows(), (n, kernel_rows())
    say_kernel_size("kernels4", n)
    m, x, refs = spmv_problem(n, seed=1)
    ys = run_ladder("ladder4", m, x, refs, mesh, use_kernel=False, chips=4,
                    blocksize=KERNEL_BLOCK)
    run_ladder("kernels4", m, x, refs, mesh, use_kernel=True, chips=4,
               blocksize=KERNEL_BLOCK,
               against={t: ys["replicate", t] for t in (False, True)})


# ---------------------------------------------------------------------------
# Heat2D
# ---------------------------------------------------------------------------

def heat_reference(phi, steps: int, coef: float = 0.1):
    """Numpy Jacobi steps: the interior gets mid + coef * (up + down +
    left + right - 4 * mid), in that order of f32 operations, the boundary
    keeps its values.  In place over two buffers: the grid is 1 GiB."""
    import numpy as np
    x, nxt = phi.copy(), phi.copy()
    lap = np.empty_like(phi[1:-1, 1:-1])
    tmp = np.empty_like(lap)
    for _ in range(steps):
        mid = x[1:-1, 1:-1]
        np.add(x[:-2, 1:-1], x[2:, 1:-1], out=lap)
        lap += x[1:-1, :-2]
        lap += x[1:-1, 2:]
        lap -= np.multiply(mid, np.float32(4), out=tmp)
        lap *= np.float32(coef)
        np.add(mid, lap, out=nxt[1:-1, 1:-1])
        x, nxt = nxt, x
    return x


def phase_heat2d(mesh, grid: int, *, name="heat2d", chips=1):
    import numpy as np
    from repro.core.heat2d import Heat2D

    t = time.perf_counter()
    h = Heat2D(mesh, grid, grid)
    phi = h.init_field(0)
    phi_host = np.asarray(phi)
    setup = time.perf_counter() - t
    text, t_compile = compile_text(
        h.scan_schedule.lower(phi, n_steps=HEAT_STEPS))
    out, t_run = timed(lambda v: h.run(v, HEAT_STEPS), phi)
    extra = ""
    if chips > 1:
        devices = len(out.sharding.device_set)
        if devices != chips or not any(
                c in text for c in ("all-to-all", "collective-permute")):
            raise AssertionError(f"{name}: spans {devices} devices or holds "
                                 "no halo collective")
        extra = f" | spans {devices} devices, holds the halo collective"
    check(name, f"grid {grid}x{grid} f32 steps={HEAT_STEPS} "
          f"mesh={dict(mesh.shape)} rung={h.strategy}",
          rel_err(out, heat_reference(phi_host, HEAT_STEPS)), HEAT_RTOL,
          setup=setup, compile_s=t_compile, run_s=t_run, extra=extra)


# ---------------------------------------------------------------------------
# Serving: mixtral-8x22b at published widths
# ---------------------------------------------------------------------------

def mixtral_config(layers: int):
    import dataclasses
    from repro.configs.registry import get_config
    return dataclasses.replace(get_config("mixtral-8x22b"),
                               num_layers=layers)


def phase_serve(mesh):
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import build_moe_layer
    from repro.models.transformer import Model, RunCtx
    from repro.serve import Request, ServeEngine

    cfg = mixtral_config(SERVE_LAYERS)
    cache_len = max(SERVE_PROMPTS) + SERVE_GEN
    assert cache_len <= cfg.swa_window
    t = time.perf_counter()
    model = Model(cfg, RunCtx(remat="none", act_dtype=jnp.bfloat16))
    params = jax.jit(functools.partial(model.init_params,
                                       dtype=jnp.bfloat16))(
        jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    n_params = sum(a.size for a in jax.tree.leaves(params))
    layer = build_moe_layer(model, params, SERVE_SLOTS, mesh)
    engine = ServeEngine(model, params, num_slots=SERVE_SLOTS,
                         cache_len=cache_len, prefill_chunk=SERVE_CHUNK,
                         moe_layer=layer, cache_dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    for i in range(SERVE_REQUESTS):
        plen = int(rng.choice(SERVE_PROMPTS))
        engine.submit(Request(
            id=f"req{i}",
            prompt=rng.integers(0, cfg.vocab_size, (plen,)).tolist(),
            max_new_tokens=SERVE_GEN, arrival_time=float(i // 2)))
    setup = time.perf_counter() - t
    t = time.perf_counter()
    report = engine.run()
    wall = time.perf_counter() - t
    short = [r for r, toks in report.outputs.items()
             if len(toks) != SERVE_GEN]
    if len(report.completed) != SERVE_REQUESTS or short:
        raise AssertionError(
            f"serve: {len(report.completed)} of {SERVE_REQUESTS} requests "
            f"completed; short outputs: {short}")
    ticks = sorted(report.tick_seconds)
    say(f"serve engine | mixtral-8x22b widths d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim={cfg.head_dim} "
        f"d_ff={cfg.d_ff} experts={cfg.num_experts} top{cfg.experts_per_token}"
        f" vocab={cfg.vocab_size} layers={cfg.num_layers} bf16 params="
        f"{n_params} | {SERVE_REQUESTS} requests x {SERVE_GEN} tokens all "
        f"complete, {SERVE_SLOTS} slots, prompts {SERVE_PROMPTS}, cache_len="
        f"{cache_len} <= SWA window {cfg.swa_window} (window does not bind) "
        f"| MoE decode {layer.strategies} | setup {setup:.2f}s run "
        f"{wall:.2f}s, first tick {report.tick_seconds[0]:.3f}s (compile), "
        f"median tick {ticks[len(ticks) // 2]:.4f}s (smoke timing)")

    # the references compute in f32 at highest precision from the same
    # bf16 weights; XLA widens them inside each dot, so they need under
    # 1 GB beside them on the chip (widened on the host CPU the stack needs
    # more than the host's 40 GiB).  Their MoE FFN is moe_fwd's in-jit
    # dispatch, with no DynamicMoELayer hook.
    ref_model = Model(cfg, RunCtx(remat="none", act_dtype=jnp.float32))

    def reference(fn, *args):
        t = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            out, _ = jax.jit(fn)(params, *args)
        return np.asarray(out), time.perf_counter() - t

    # prefill logits of one short prompt vs the same weights in f32
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                    (1, SERVE_CHECK_PROMPT)), jnp.int32)
    prefix = engine.model.init_cache(1, cache_len, per_slot=True,
                                     dtype=jnp.bfloat16)
    prefill = jax.jit(engine.model.prefill)
    _, t_compile = compile_text(prefill.lower(params, prefix, toks))
    (got, _), t_run = timed(prefill, params, prefix, toks)
    want, t_ref = reference(
        ref_model.prefill, ref_model.init_cache(
            1, cache_len, per_slot=True, dtype=jnp.float32), toks)
    check("serve prefill logits", f"prompt {SERVE_CHECK_PROMPT} tokens, bf16 "
          "engine vs the same weights in f32 at highest precision",
          rel_err(got.astype(jnp.float32), want), SERVE_RTOL, setup=t_ref,
          compile_s=t_compile, run_s=t_run,
          extra=" (setup: compile+run of the f32 reference)")

    # one decode tick of all slots through the engine's model, whose MoE
    # FFN runs the DynamicMoELayer hook, from a cache the engine's prefill
    # filled; the reference decodes the same tokens from the same cache
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                    (SERVE_SLOTS, SERVE_CHECK_PROMPT)),
                       jnp.int32)
    cache = engine.model.init_cache(SERVE_SLOTS, cache_len, per_slot=True,
                                    dtype=jnp.bfloat16)
    logits, cache = prefill(params, cache, toks)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # (slots, 1)
    hook, traced = engine.model.ctx.moe_step, []

    def counted_hook(p_moe, h):
        traced.append(h.shape)
        return hook(p_moe, h)

    hooked = Model(cfg, dataclasses.replace(engine.model.ctx,
                                            moe_step=counted_hook))
    decode = jax.jit(hooked.decode_step)
    _, t_compile = compile_text(decode.lower(params, cache, nxt))
    (got, _), t_run = timed(decode, params, cache, nxt)
    if not traced:
        raise AssertionError("serve decode: the tick never reached the "
                             "DynamicMoELayer hook")
    cache32 = jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, cache)
    want, t_ref = reference(ref_model.decode_step, cache32, nxt)
    check("serve decode tick logits", f"{SERVE_SLOTS} slots at position "
          f"{SERVE_CHECK_PROMPT}, bf16 engine model with the DynamicMoELayer "
          "hook vs the same weights in f32 at highest precision (moe_fwd)",
          rel_err(got.astype(jnp.float32), want), SERVE_RTOL, setup=t_ref,
          compile_s=t_compile, run_s=t_run,
          extra=" (setup: compile+run of the f32 reference)")
    del engine, layer, prefill, decode, cache, cache32
    gc.collect()


# ---------------------------------------------------------------------------
# MoE decode exchange across four chips
# ---------------------------------------------------------------------------

def phase_moe4(mesh, chips: int):
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import moe as M

    cfg = mixtral_config(1)
    e, d, f, k = cfg.num_experts, cfg.d_model, cfg.d_ff, cfg.experts_per_token
    t = time.perf_counter()
    shard = NamedSharding(mesh, P("data"))

    @functools.partial(jax.jit, static_argnums=(1, 2), out_shardings=shard)
    def weight(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(jnp.bfloat16)

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    w = {"w1": weight(keys[0], (e, d, f), d ** -0.5),
         "w3": weight(keys[1], (e, d, f), d ** -0.5),
         "w2": weight(keys[2], (e, f, d), f ** -0.5)}
    top_e, top_w = M.random_router(0, MOE4_TOKENS, e, k)
    cap = M.moe_capacity(MOE4_TOKENS, cfg)
    x = (np.random.default_rng(0).standard_normal((MOE4_TOKENS, d))
         .astype(np.float32))
    layer = M.DynamicMoELayer(w, top_e, MOE4_TOKENS, e, cap, mesh,
                              act=cfg.act, decode=True)
    xs = layer.shard_tokens(jnp.asarray(x, jnp.bfloat16))
    setup = time.perf_counter() - t
    text, t_compile = compile_text(layer.lower(xs, top_e, top_w))
    y, t_run = timed(layer, xs, top_e, top_w)
    devices = len(y.sharding.device_set)
    if devices != chips or "all-to-all" not in text:
        raise AssertionError(f"moe4: spans {devices} devices or holds no "
                             "all-to-all")

    # f32 dispatch -> expert -> combine reference on the host CPU, from
    # the same bf16 numbers the layer holds
    cpu = jax.devices("cpu")[0]
    xb = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))
    idx, valid = M.moe_dispatch_pattern(top_e, MOE4_TOKENS, e, cap, chips)
    w_slot = M.moe_combine_weights(top_e, top_w, MOE4_TOKENS, e, cap)
    buf = M.moe_dispatch_ref(xb, idx, valid, e, cap)
    with jax.default_matmul_precision("highest"):
        w1, w2, w3 = (jax.device_put(w[n], cpu).astype(jnp.float32)
                      for n in ("w1", "w2", "w3"))
        b = jax.device_put(buf, cpu)
        h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", b, w1))
             * jnp.einsum("ecd,edf->ecf", b, w3))
        out = np.asarray(jnp.einsum("ecf,efd->ecd", h, w2))
    want = M.moe_combine_ref(out, idx, valid, w_slot, MOE4_TOKENS)
    check("moe4 DynamicMoELayer decode",
          f"mixtral widths d_model={d} d_ff={f} experts={e} top{k} "
          f"({e // chips} per chip) tokens={MOE4_TOKENS} capacity={cap} "
          f"bf16, strategies {layer.strategies}",
          rel_err(np.asarray(y.astype(jnp.float32)), want), MOE4_RTOL,
          setup=setup, compile_s=t_compile, run_s=t_run,
          extra=f" | spans {devices} devices, holds all-to-all")


# ---------------------------------------------------------------------------

ONE_CHIP = ("ladder", "heat2d", "kernels", "serve")
FOUR_CHIPS = ("ladder4", "heat2d4", "moe4")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--phase", action="append",
                    choices=ONE_CHIP + FOUR_CHIPS,
                    help="run only these phases (repeatable)")
    args = ap.parse_args(argv)

    import jax

    # moe4's f32 reference runs on the host CPU: keep its backend available
    # next to the accelerator even where JAX_PLATFORMS names only the TPU
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r}",
              file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_local_mesh

    # plans of this size are rebuilt, never written under $HOME
    os.environ.setdefault("REPRO_PLAN_CACHE_MAX_BYTES", "0")
    say(f"compile cache: {enable_compile_cache()}")
    say(f"devices: {len(devices)} x {devices[0].device_kind}")
    phases = args.phase or (ONE_CHIP if args.chips == 1 else FOUR_CHIPS)
    t0 = time.perf_counter()
    for phase in phases:
        t = time.perf_counter()
        if phase == "ladder":
            phase_ladder(make_local_mesh((1,), ("data",)), LADDER_ROWS)
        elif phase == "kernels":
            phase_kernels(make_local_mesh((1,), ("data",)), kernel_rows())
        elif phase == "heat2d":
            phase_heat2d(make_local_mesh((1, 1), ("data", "model")),
                         HEAT_GRID)
        elif phase == "serve":
            phase_serve(make_local_mesh((1,), ("data",)))
        elif phase == "ladder4":
            phase_ladder4(make_local_mesh((4,), ("data",)), LADDER4_ROWS)
        elif phase == "heat2d4":
            phase_heat2d(make_local_mesh((2, 2), ("data", "model")),
                         HEAT4_GRID, name="heat2d4", chips=4)
        elif phase == "moe4":
            phase_moe4(make_local_mesh((4,), ("data",)), 4)
        say(f"phase {phase} done in {time.perf_counter() - t:.1f}s")
    say(f"all phases done in {time.perf_counter() - t0:.1f}s")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
