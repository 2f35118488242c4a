"""One benchmark per paper table/figure (DESIGN.md §9).

All multi-device measurements run inside THIS process only when it was
launched with 8 forced host devices (benchmarks.run spawns itself that way);
single-device benchmarks run anywhere.

Output format: ``name,us_per_call,derived`` CSV rows on stdout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import calibrate_host, csv_row, timeit
from benchmarks.matrix import ladder_volume, measured_ladder
from repro import compat
from repro.comm import perfmodel as pm
from repro.comm.exchange import measure_hw
from repro.comm.plan import Topology
from repro.comm.plan_cache import get_comm_plan
from repro.core.heat2d import Heat2D
from repro.core.matrix import make_mesh_like_matrix, spmv_ref_np
from repro.core.spmv import DistributedSpMV
from repro.kernels import ops as kops


def _mesh8():
    assert len(jax.devices()) >= 8, "run via benchmarks.run (8 host devices)"
    return compat.make_mesh((8,), ("data",),
                            axis_types=compat.auto_axis_types(1))


# --------------------------------------------------------------------------
# Table 2: naive vs thread-privatized (UPCv1) — single "node" scaling
# --------------------------------------------------------------------------

def table2_privatization(n=1 << 18, r_nz=16):
    """Paper Table 2: the per-access overhead tax.  UPC's pointer-to-shared
    pays owner/phase/address bookkeeping on EVERY access; privatization
    removes it.  The measurable host analogue of that per-access tax is a
    guarded gather (bounds-check + fill select) vs a trusted local gather
    (promise_in_bounds).  The Pallas windowed kernel is validated for
    correctness here; its wall-time on CPU is interpret-mode Python and is
    deliberately NOT compared (TPU is the target; see §Roofline)."""
    print("# table2: guarded (naive shared-access) vs privatized gather SpMV"
          f" (n={n}, r_nz={r_nz}; seconds per 1000 iters)")
    m = make_mesh_like_matrix(n, r_nz, locality_window=n // 256, seed=0)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(n), jnp.float32)
    diag, vals, cols = (jnp.asarray(m.diag), jnp.asarray(m.vals),
                        jnp.asarray(m.cols))

    naive = jax.jit(lambda d, v, c, xx: d * xx + (
        v * jnp.take(xx, c, mode="fill", fill_value=0.0)).sum(-1))
    t_naive = timeit(naive, diag, vals, cols, x)

    # trusted local gather: clamp-only indexing (x[c]), no fill-select
    priv = jax.jit(lambda d, v, c, xx: d * xx + (v * xx[c]).sum(-1))
    t_priv = timeit(priv, diag, vals, cols, x)

    y_ref = np.asarray(priv(diag, vals, cols, x))
    plan = kops.plan_spmv_windows(m.cols, rows_per_block=256)
    y_kern = np.asarray(kops.ellpack_spmv(diag, vals, m.cols, x, plan=plan))
    np.testing.assert_allclose(y_kern, y_ref, rtol=3e-5, atol=3e-5)

    csv_row("table2.naive_guarded", t_naive * 1e6,
            f"per_1000={t_naive*1e3:.2f}s")
    csv_row("table2.privatized", t_priv * 1e6,
            f"per_1000={t_priv*1e3:.2f}s speedup={t_naive/t_priv:.2f}x "
            f"pallas_kernel=validated(interpret)")


# --------------------------------------------------------------------------
# Table 3: the three strategies, measured on 8 host devices + modeled at
# paper scale (16..1024 threads, Abel parameters)
# --------------------------------------------------------------------------

def table3_strategies(n=1 << 17, r_nz=16, iters=50, smoke=False):
    if smoke:  # CI trajectory capture: small but same shape of output
        n, iters = 1 << 14, 5
    print(f"# table3: strategies measured on 8 host devices (n={n}) + "
          "modeled at Abel scale")
    mesh = _mesh8()
    m = make_mesh_like_matrix(n, r_nz, locality_window=n // 64,
                              long_range_frac=0.02, seed=1)
    x_host = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    y_ref = spmv_ref_np(m, x_host)

    from repro.comm import select
    hw = measure_hw(mesh, "data")

    def build(strategy):
        eng = DistributedSpMV(m, mesh, strategy=strategy,
                              blocksize=n // 8 // 16, shards_per_node=4)
        x = eng.shard_vector(x_host)
        np.testing.assert_allclose(np.asarray(eng(x)), y_ref, rtol=2e-4,
                                   atol=2e-4)
        return eng, (x,), eng

    results = measured_ladder(
        "table3.measured", build, iters=iters,
        preds=lambda eng: select.rank_strategies(eng.plan, r_nz, hw),
        vol_of=lambda eng, s: ladder_volume(eng.counts, s, 8, n))

    # modeled at paper scale with Abel parameters (prediction deliverable)
    print("# table3 model: Abel params, threads=16..1024 (seconds/1000 iters)")
    for threads in (16, 32, 64, 128, 256, 512, 1024):
        if threads > n // 64:
            continue
        topo = Topology(threads, 16)
        mm = make_mesh_like_matrix(n, r_nz, locality_window=n // 64,
                                   long_range_frac=0.02, seed=1)
        plan = get_comm_plan(mm.cols, n, threads,
                               blocksize=max(64, n // threads // 8),
                               topology=topo)
        w = pm.SpmvWorkload(n=n, r_nz=r_nz, p=threads,
                            blocksize=max(64, n // threads // 8),
                            topology=topo, counts=plan.counts)
        t = pm.predict_all(w, pm.ABEL)
        csv_row(f"table3.model.{threads}threads",
                t["v3_condensed"] * 1e6 * 1000,
                f"v1={t['v1_finegrained']*1000:.2f}s "
                f"v2={t['v2_blockwise']*1000:.2f}s "
                f"v3={t['v3_condensed']*1000:.2f}s "
                f"overlap={t['overlap']*1000:.2f}s per-1000")

    table3_unpack_modes(n=n, r_nz=r_nz, iters=iters, mesh=mesh, m=m,
                        x_host=x_host, y_ref=y_ref)
    table3_kernel(n=n, r_nz=r_nz, iters=iters, mesh=mesh, m=m,
                  x_host=x_host, y_ref=y_ref)
    table3_moe_dispatch(smoke=smoke, iters=iters)
    table3_scatter(smoke=smoke, iters=iters)
    table3_schedule(smoke=smoke, iters=iters)
    table3_dynamic(smoke=smoke, iters=iters)
    return results


# --------------------------------------------------------------------------
# Table 3c: the two unpack modes on the condensed rung — the paper's
# assemble-x_copy layout vs the Destination-targeted delivery, each priced
# by its own §5 term (docs/perf_model.md eqs. 14'/15')
# --------------------------------------------------------------------------

def table3_unpack_modes(*, n, r_nz, iters, mesh, m, x_host, y_ref):
    from repro.comm import select

    print("# table3 unpack: condensed rung, full x_copy assembly vs "
          "Destination-targeted delivery (per-mode §5 prediction)")
    hw = measure_hw(mesh, "data")
    for mode in ("full", "dest"):
        eng = DistributedSpMV(m, mesh, strategy="condensed",
                              blocksize=n // 8 // 16, shards_per_node=1,
                              materialize=mode)
        x = eng.shard_vector(x_host)
        np.testing.assert_allclose(np.asarray(eng(x)), y_ref, rtol=2e-4,
                                   atol=2e-4)
        t = timeit(eng, x, iters=iters)
        t_pred = dict(select.rank_strategies(
            eng.plan, r_nz, hw, materialize=mode))["condensed"]
        acc = min(t, t_pred) / max(t, t_pred)
        csv_row(f"table3.unpack.{mode}", t * 1e6,
                f"predicted_us={t_pred*1e6:.1f} accuracy={acc:.2f} "
                f"dest_slots={eng.plan.dest_len}")


# --------------------------------------------------------------------------
# Table 3g: the fused Pallas exchange path (use_kernel=True) on the
# condensed/overlap rungs, both directions, against the bit-identical jnp
# reference — priced by the kernel-variant §5 compute terms (eqs. 14ᵏ/15ᵏ,
# 14ᵀᵏ/15ᵀᵏ; docs/perf_model.md)
# --------------------------------------------------------------------------

def table3_kernel(*, n, r_nz, iters, mesh, m, x_host, y_ref):
    from repro.comm import select
    from repro.core.matrix import spmv_t_ref_np

    print("# table3 kernel: fused pack/unpack exchange kernels vs the jnp "
          "path (bit-identical), per-variant §5 prediction")
    hw = measure_hw(mesh, "data")
    yt_ref = spmv_t_ref_np(m, x_host)
    bs = n // 8 // 16
    for direction in ("gather", "scatter"):
        transpose = direction == "scatter"
        ref = yt_ref if transpose else y_ref
        # hold the local compute constant (dest-mode slot compute for the
        # gather, scatter-accumulate for the put) so the pair differs ONLY
        # in the exchange path — that is the bit-identity contract
        mat = None if transpose else "dest"
        for strategy in ("condensed", "overlap"):
            t, y = {}, {}
            for uk in (False, True):
                eng = DistributedSpMV(m, mesh, strategy=strategy,
                                      blocksize=bs, shards_per_node=1,
                                      transpose=transpose, use_kernel=uk,
                                      materialize=mat, hw=hw)
                x = eng.shard_vector(x_host)
                y[uk] = np.asarray(eng(x))
                np.testing.assert_allclose(y[uk], ref, rtol=2e-4, atol=2e-4)
                t[uk] = timeit(eng, x, iters=iters)
                if uk:
                    plan = eng.splan if transpose else eng.plan
                    t_pred = dict(select.rank_strategies(
                        plan, r_nz, hw, use_kernel=True, materialize=mat,
                        dest_slots=None if transpose else plan.dest_len,
                        direction="put" if transpose else "get"))[strategy]
            np.testing.assert_array_equal(y[True], y[False])
            acc = min(t[True], t_pred) / max(t[True], t_pred)
            csv_row(f"table3.kernel.{direction}.{strategy}", t[True] * 1e6,
                    f"predicted_us={t_pred*1e6:.1f} accuracy={acc:.2f} "
                    f"vs_jnp={t[True]/t[False]:.2f}x jnp_us={t[False]*1e6:.1f}"
                    " bit_identical=verified")


# --------------------------------------------------------------------------
# Table 3b: the MoE-dispatch consumer — the same ladder on the token→expert
# gather, measured on 8 host devices with §5 predicted-vs-measured
# --------------------------------------------------------------------------

def table3_moe_dispatch(n_tok=1 << 14, d=32, smoke=False, iters=50):
    from repro.comm import select
    from repro.models.moe import (MoEDispatchGather, moe_dispatch_pattern,
                                  moe_dispatch_ref, random_router)

    if smoke:
        n_tok, d, iters = 1 << 12, 8, 5
    k, e_total = 2, 32
    cap = int(1.25 * n_tok * k / e_total)
    print(f"# table3 moe_dispatch: token->expert gather ladder "
          f"(tokens={n_tok}, d={d}, experts={e_total}, capacity={cap})")
    mesh = _mesh8()
    rng = np.random.default_rng(3)
    # zipf-skewed routing: experts differ in load, like trained routers
    top_e, _ = random_router(3, n_tok, e_total, k)
    x_host = rng.standard_normal((n_tok, d)).astype(np.float32)
    idx, valid = moe_dispatch_pattern(top_e, n_tok, e_total, cap, 8)
    ref = moe_dispatch_ref(x_host, idx, valid, e_total, cap)

    # price with the host's measured parameters, feature width folded into
    # the element size (every moved "element" is one d-wide token vector)
    hw = measure_hw(mesh, "data").replace(elem=4 * d)

    def build(strategy):
        g = MoEDispatchGather(top_e, n_tok, e_total, cap, mesh,
                              strategy=strategy, blocksize=n_tok // 8 // 16,
                              shards_per_node=1, hw=hw)
        x = g.shard_tokens(x_host)
        np.testing.assert_array_equal(np.asarray(g(x)), ref)
        return g, (x,), g

    return measured_ladder(
        "table3.moe_dispatch", build, iters=iters,
        preds=lambda g: select.rank_strategies(g.plan, 1, hw),
        vol_of=lambda g, s: ladder_volume(g.counts, s, 8, n_tok))


# --------------------------------------------------------------------------
# Table 3d: the push direction — MoE expert→token combine and transposed
# SpMV on the scatter ladder, measured on 8 host devices with the §5
# put-model predictions (docs/perf_model.md eqs. 12ᵀ–15ᵀ) per rung
# --------------------------------------------------------------------------

def table3_scatter(n=1 << 17, r_nz=16, smoke=False, iters=50):
    from repro.comm import select
    from repro.core.matrix import spmv_t_ref_np
    from repro.models.moe import (MoECombineScatter, moe_combine_ref,
                                  moe_combine_weights, moe_dispatch_pattern,
                                  random_router)

    if smoke:
        n, iters = 1 << 14, 5
    mesh = _mesh8()

    # -- spmv_transpose: y = (D + A)ᵀ x via scatter-accumulate --
    print(f"# table3 scatter: transposed SpMV (n={n}) + MoE combine on the "
          "put ladder, predicted (§5ᵀ) vs measured")
    m = make_mesh_like_matrix(n, r_nz, locality_window=n // 64,
                              long_range_frac=0.02, seed=1)
    x_host = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    y_ref = spmv_t_ref_np(m, x_host)
    hw = measure_hw(mesh, "data")

    def build_t(strategy):
        eng = DistributedSpMV(m, mesh, strategy=strategy,
                              blocksize=n // 8 // 16, shards_per_node=1,
                              transpose=True, hw=hw)
        x = eng.shard_vector(x_host)
        np.testing.assert_allclose(np.asarray(eng(x)), y_ref, rtol=2e-4,
                                   atol=2e-4)
        return eng, (x,), eng

    measured_ladder(
        "table3.scatter.spmv_transpose", build_t, iters=iters,
        preds=lambda eng: select.rank_strategies(eng.splan, r_nz, hw,
                                                 direction="put"),
        vol_of=lambda eng, s: ladder_volume(eng.counts, s, 8, n))

    # -- moe_combine: weighted expert→token return --
    n_tok, d = (1 << 12, 8) if smoke else (1 << 14, 32)
    k, e_total = 2, 32
    cap = int(1.25 * n_tok * k / e_total)
    rng = np.random.default_rng(3)
    top_e, top_w = random_router(3, n_tok, e_total, k)
    buf = rng.standard_normal((e_total, cap, d)).astype(np.float32)
    idx, valid = moe_dispatch_pattern(top_e, n_tok, e_total, cap, 8)
    w_slot = moe_combine_weights(top_e, top_w, n_tok, e_total, cap)
    ref = moe_combine_ref(buf, idx, valid, w_slot, n_tok)
    hw_tok = hw.replace(elem=4 * d)  # every moved element is a d-wide row

    def build_c(strategy):
        g = MoECombineScatter(top_e, top_w, n_tok, e_total, cap, mesh,
                              strategy=strategy, blocksize=n_tok // 8 // 16,
                              shards_per_node=1, hw=hw_tok)
        b = g.shard_expert_buf(buf)
        np.testing.assert_allclose(np.asarray(g(b)), ref, rtol=2e-4,
                                   atol=2e-4)
        return g, (b,), g

    return measured_ladder(
        "table3.scatter.moe_combine", build_c, iters=iters,
        preds=lambda g: select.rank_strategies(g.splan, 1, hw_tok,
                                               direction="put"),
        vol_of=lambda g, s: ladder_volume(g.counts, s, 8, n_tok))


# --------------------------------------------------------------------------
# Table 3e: the fused multi-exchange window — ExchangeSchedule chains vs
# their back-to-back one-shot baselines, with the §5 composition model
# (perfmodel.predict_schedule, eq. 23) predicted-vs-measured
# --------------------------------------------------------------------------

def table3_schedule(smoke=False, iters=50):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.matrix import spmv_ref_np, spmv_t_ref_np
    from repro.core.spmv import normal_equations_step
    from repro.models.moe import (MoECombineScatter, MoEDispatchGather,
                                  MoELayer, moe_expert_local, random_router)

    mesh = _mesh8()
    print("# table3 schedule: fused ExchangeSchedule windows vs back-to-back"
          " one-shot exchanges, predicted (eq. 23) vs measured")

    # -- moe_layer: dispatch → expert MLP → combine in ONE window --
    n_tok, d = (1 << 12, 8) if smoke else (1 << 14, 32)
    f, k, e_total = 2 * d, 2, 32
    cap = int(1.25 * n_tok * k / e_total)
    rng = np.random.default_rng(7)
    top_e, top_w = random_router(7, n_tok, e_total, k)
    x_host = rng.standard_normal((n_tok, d)).astype(np.float32)
    params = {
        "w1": (rng.standard_normal((e_total, d, f)) * 0.1).astype(np.float32),
        "w2": (rng.standard_normal((e_total, f, d)) * 0.1).astype(np.float32),
    }
    hw_tok = measure_hw(mesh, "data").replace(elem=4 * d)

    layer = MoELayer(params, top_e, top_w, n_tok, e_total, cap, mesh,
                     strategy="condensed", blocksize=n_tok // 8 // 16,
                     shards_per_node=1, hw=hw_tok)
    x = layer.shard_tokens(x_host)
    t_fused = timeit(layer, x, iters=iters)
    t_pred = layer.predicted_window["total"]

    # back-to-back one-shot baseline: three windows, same rungs, the
    # identical local expert math (moe_expert_local on both paths)
    disp = MoEDispatchGather(top_e, n_tok, e_total, cap, mesh,
                             strategy="condensed",
                             blocksize=n_tok // 8 // 16,
                             shards_per_node=1, hw=hw_tok)
    comb = MoECombineScatter(top_e, top_w, n_tok, e_total, cap, mesh,
                             strategy="condensed",
                             blocksize=n_tok // 8 // 16,
                             shards_per_node=1, hw=hw_tok)
    shard = NamedSharding(mesh, P("data"))
    w1 = jax.device_put(params["w1"], shard)
    w2 = jax.device_put(params["w2"], shard)
    expert = jax.jit(compat.shard_map(
        lambda b, a, c: moe_expert_local(b, a, c),
        mesh=mesh, in_specs=(P("data"),) * 3, out_specs=P("data"),
        check_vma=False))

    def baseline(xx):
        return comb(expert(disp(xx), w1, w2))

    np.testing.assert_array_equal(np.asarray(layer(x)),
                                  np.asarray(baseline(x)))
    t_base = timeit(baseline, x, iters=iters)
    acc = min(t_fused, t_pred) / max(t_fused, t_pred)
    csv_row("table3.schedule.moe_layer.fused", t_fused * 1e6,
            f"predicted_us={t_pred*1e6:.1f} accuracy={acc:.2f} "
            f"vs_baseline={t_fused/t_base:.2f}x "
            f"setup_saved_us={layer.predicted_window['setup_saved']*1e6:.1f}")
    csv_row("table3.schedule.moe_layer.baseline", t_base * 1e6,
            "back_to_back=dispatch+expert+combine (3 windows) "
            f"predicted_sum_us="
            f"{layer.predicted_window['sum_standalone']*1e6:.1f}")

    # -- normal_eq: z = MᵀM x (forward gather + transposed scatter) --
    n, r_nz = (1 << 14, 16) if smoke else (1 << 17, 16)
    m = make_mesh_like_matrix(n, r_nz, locality_window=n // 64,
                              long_range_frac=0.02, seed=1)
    x_host = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    z_ref = spmv_t_ref_np(m, spmv_ref_np(m, x_host))
    hw = measure_hw(mesh, "data")
    step = normal_equations_step(m, mesh, strategy="condensed",
                                 blocksize=n // 8 // 16, shards_per_node=1,
                                 hw=hw)
    x = step.shard_vector(x_host)
    np.testing.assert_allclose(np.asarray(step(x)), z_ref, rtol=2e-3,
                               atol=2e-3)
    t_fused = timeit(step, x, iters=iters)
    t_pred = step.predicted_window["total"]

    fwd = DistributedSpMV(m, mesh, strategy="condensed",
                          blocksize=n // 8 // 16, shards_per_node=1, hw=hw)
    bwd = DistributedSpMV(m, mesh, strategy="condensed",
                          blocksize=n // 8 // 16, shards_per_node=1,
                          transpose=True, hw=hw)

    def ne_baseline(xx):
        return bwd(fwd(xx))

    t_base = timeit(ne_baseline, x, iters=iters)
    acc = min(t_fused, t_pred) / max(t_fused, t_pred)
    csv_row("table3.schedule.normal_eq.fused", t_fused * 1e6,
            f"predicted_us={t_pred*1e6:.1f} accuracy={acc:.2f} "
            f"vs_baseline={t_fused/t_base:.2f}x")
    csv_row("table3.schedule.normal_eq.baseline", t_base * 1e6,
            "back_to_back=forward+transpose (2 windows)")


# --------------------------------------------------------------------------
# Table 3f: per-batch routing — the DynamicPattern tier (repro.comm.dynamic)
# vs the rebuild-every-batch baseline, with the T_plan-inclusive §5 pricing
# (perfmodel.plan_build_time threaded through rank_strategies(plan_cost=))
# --------------------------------------------------------------------------

def table3_dynamic(smoke=False, iters=50):
    import time as _time

    from repro.comm import telemetry
    from repro.models.moe import DynamicMoELayer, MoELayer, random_router

    n_tok, d = (1 << 12, 8) if smoke else (1 << 14, 32)
    f, k, e_total = 2 * d, 2, 32
    cap = int(1.25 * n_tok * k / e_total)
    n_batches = 4 if smoke else 8
    print(f"# table3 dynamic: per-batch routed MoE — device-derived tables "
          f"vs rebuild-every-batch (tokens={n_tok}, d={d}, "
          f"batches={n_batches})")
    mesh = _mesh8()
    rng = np.random.default_rng(9)
    params = {
        "w1": (rng.standard_normal((e_total, d, f)) * 0.1).astype(np.float32),
        "w2": (rng.standard_normal((e_total, f, d)) * 0.1).astype(np.float32),
    }
    routings = [random_router(100 + i, n_tok, e_total, k)
                for i in range(n_batches)]
    x_host = rng.standard_normal((n_tok, d)).astype(np.float32)
    hw_tok = measure_hw(mesh, "data").replace(elem=4 * d)
    bs = n_tok // 8 // 16

    # -- dynamic: one envelope plan, per-batch in-jit table derivation --
    layer = DynamicMoELayer(params, routings[0][0], n_tok, e_total, cap,
                            mesh, strategy="auto", blocksize=bs,
                            shards_per_node=1, hw=hw_tok)
    x = layer.shard_tokens(x_host)
    jax.block_until_ready(layer(x, *routings[0]))   # warmup: trace once
    snap = telemetry.stats.snapshot()

    def run_all():
        out = None
        for te, tw in routings:
            out = layer(x, te, tw)
        return out

    t_dyn = timeit(run_all, iters=max(3, iters // 10), warmup=1) / n_batches
    tel = telemetry.stats.since(snap)
    assert tel["host-build"] == 0, (
        f"dynamic path must be host-free after warmup, saw {tel}")
    gs, ss = layer.strategies["dispatch"], layer.strategies["combine"]
    # each rung prediction already carries plan_cost (the device-derive
    # T_plan); ONE derivation serves both directions, so count it once
    pred_dyn = (layer.predicted_times["dispatch"][gs]
                + layer.predicted_times["combine"][ss] - layer.plan_time)
    acc = min(t_dyn, pred_dyn) / max(t_dyn, pred_dyn)
    csv_row("table3.dynamic.per_batch", t_dyn * 1e6,
            f"strategies={gs}+{ss} predicted_us={pred_dyn*1e6:.1f} "
            f"accuracy={acc:.2f} t_plan_us={layer.plan_time*1e6:.2f} "
            f"telemetry=" + "/".join(f"{s}:{c}" for s, c in tel.items()))

    # -- baseline: honest host rebuild (plan + trace + compile) per batch --
    t_host_plan = pm.plan_build_time(e_total * cap, 1, hw_tok,
                                     source="host-build")
    y_dyn0 = np.asarray(layer(x, *routings[0]))
    rebuild_times = []
    for te, tw in routings[:min(n_batches, 3)]:
        t0 = _time.perf_counter()
        base = MoELayer(params, te, tw, n_tok, e_total, cap, mesh,
                        strategy="condensed", blocksize=bs,
                        shards_per_node=1, hw=hw_tok, use_plan_cache=False)
        y = jax.block_until_ready(base(base.shard_tokens(x_host)))
        rebuild_times.append(_time.perf_counter() - t0)
        if (te, tw) is routings[0]:
            np.testing.assert_allclose(y_dyn0, np.asarray(y), rtol=2e-4,
                                       atol=2e-4)
    t_rebuild = float(np.median(rebuild_times))
    # static per-step cost once a fresh host plan exists (no T_plan term),
    # and the rebuild's one-time cost on top — the break-even question:
    # after how many reuses of ONE routing does a host rebuild beat the
    # per-batch derivation?  (perfmodel.replan_break_even_steps)
    pred_static = (layer.predicted_times["dispatch"][gs]
                   + layer.predicted_times["combine"][ss]
                   - 2 * layer.plan_time)
    pred_rebuild = pred_static + t_host_plan
    be = pm.replan_break_even_steps(t_host_plan, t_dyn, pred_static)
    csv_row("table3.dynamic.rebuild_baseline", t_rebuild * 1e6,
            f"predicted_us={pred_rebuild*1e6:.1f} (excl. trace+compile) "
            f"t_plan_host_us={t_host_plan*1e6:.2f} "
            f"vs_dynamic={t_rebuild/t_dyn:.1f}x "
            f"break_even_steps={be:.0f}")
    assert t_dyn < t_rebuild, (
        f"per-batch dynamic ({t_dyn:.4f}s) must beat rebuild-every-batch "
        f"({t_rebuild:.4f}s)")
    return {"dynamic": t_dyn, "rebuild": t_rebuild}


# --------------------------------------------------------------------------
# Table serve: the continuous-batching engine (repro.serve) + §5-priced MoE
# decode exchanges.  Engine rows report tokens/s and p50/p99 per-token
# latency with the steady-state zero-host-build telemetry assertion; the
# decode_step rows compare a measured DynamicMoELayer step against
# perfmodel.predict_decode_step (the eqs. 12δ–15δ latency floors) at decode
# batch sizes {1, 8, 32}, each gated by perfmodel.error_budget.
# --------------------------------------------------------------------------

def table_serve(smoke=False, iters=30):
    import dataclasses as _dc

    from repro.comm import select
    from repro.configs.registry import get_config
    from repro.models import moe as M
    from repro.models.transformer import Model, RunCtx
    from repro.serve import Request, ServeEngine

    mesh = _mesh8()
    slots = 8
    cfg = get_config("mixtral-8x22b", reduced=True)
    # serving shape: experts divide the 8-way mesh, full attention (SWA
    # would clamp the ring cache), no-drop capacity so the engine matches
    # the batch-loop baseline bit-exactly (tests/test_serve.py)
    cfg = _dc.replace(cfg, num_experts=8, swa_window=0,
                      capacity_factor=8.0 / cfg.experts_per_token)
    ctx = RunCtx(remat="none", act_dtype=jnp.float32)
    model = Model(cfg, ctx)
    params = model.init_params(jax.random.PRNGKey(0))
    print(f"# table_serve: continuous batching on {cfg.name} reduced "
          f"(slots={slots}, experts={cfg.num_experts}, "
          f"layers={cfg.num_layers})")

    d = cfg.d_model
    hw_tok = measure_hw(mesh, "data").replace(elem=4 * d)
    cap = M.moe_capacity(slots, cfg)
    moe_p = params["layers"]["moe"]
    weights = {"w1": np.asarray(moe_p["w1"][0]),
               "w2": np.asarray(moe_p["w2"][0])}
    if "w3" in moe_p:
        weights["w3"] = np.asarray(moe_p["w3"][0])
    tmpl_e, _ = M.random_router(0, slots, cfg.num_experts,
                                cfg.experts_per_token)
    layer = M.DynamicMoELayer(weights, tmpl_e, slots, cfg.num_experts, cap,
                              mesh, act=cfg.act, strategy="auto",
                              shards_per_node=1, hw=hw_tok, decode=True)

    engine = ServeEngine(model, params, num_slots=slots, cache_len=48,
                         prefill_chunk=8, moe_layer=layer,
                         cache_dtype=jnp.float32)
    rng = np.random.default_rng(0)

    def submit(n, tag, gen):
        for i in range(n):
            engine.submit(Request(
                id=f"{tag}{i}",
                prompt=rng.integers(0, cfg.vocab_size, (16,)).tolist(),
                max_new_tokens=gen, arrival_time=float(i // 4)))

    submit(slots, "warm", 4)        # warmup: prefill/insert/decode traces
    engine.run()
    rep0 = engine.report()          # warmup watermark (compile ticks)
    snap = engine.snapshot()
    n_req, gen = (8, 6) if smoke else (24, 16)
    submit(n_req, "req", gen)
    rep = engine.run()
    # acceptance: zero host plan builds across the steady-state run
    delta = engine.assert_steady_state(snap)
    # steady-state slices: everything after the warmup watermark, so the
    # latency percentiles describe serving, not tracing/compilation
    tick_ss = rep.tick_seconds[rep0.ticks:]
    tok_ss = rep.token_seconds[len(rep0.token_seconds):]
    csv_row("table_serve.engine.decode", float(np.mean(tick_ss)) * 1e6,
            f"tokens_per_s={len(tok_ss)/sum(tick_ss):.1f} "
            f"p50_us={np.percentile(tok_ss, 50)*1e6:.0f} "
            f"p99_us={np.percentile(tok_ss, 99)*1e6:.0f} "
            f"requests={n_req} ticks={len(tick_ss)} "
            "telemetry=" + "/".join(f"{k}:{v}" for k, v in delta.items()))
    ttft = sorted(t for rid, t in rep.ttft_seconds.items()
                  if rid.startswith("req"))
    csv_row("table_serve.engine.prefill", float(np.mean(ttft)) * 1e6,
            f"ttft_p50_us={np.median(ttft)*1e6:.0f} requests={len(ttft)} "
            f"chunks={delta['prefill_chunks']}")

    # -- per-decode-step §5 pricing at decode batch sizes {1, 8, 32} --
    for b in (1, 8, 32):
        lanes = max(b, 8)           # DynamicMoELayer needs lanes % 8 == 0
        cap_b = M.moe_capacity(lanes, cfg)
        te, tw = M.random_router(b, lanes, cfg.num_experts,
                                 cfg.experts_per_token)
        lb = M.DynamicMoELayer(weights, te, lanes, cfg.num_experts, cap_b,
                               mesh, act=cfg.act, strategy="auto",
                               shards_per_node=1, hw=hw_tok, decode=True)
        x = lb.shard_tokens(
            rng.standard_normal((lanes, d)).astype(np.float32))
        jax.block_until_ready(lb(x, te, tw))
        t_meas = timeit(lb, x, te, tw, iters=(5 if smoke else iters))
        gs, ss = lb.strategies["dispatch"], lb.strategies["combine"]
        w_g = select.workload_from_plan(lb.gather.plan, 1,
                                        materialize="full")
        w_s = select.workload_from_plan(lb.scatter.splan, 1)
        pred = pm.predict_decode_step(
            [("dispatch", "get", w_g, gs), ("combine", "put", w_s, ss)],
            hw_tok)
        t_pred = pred["total"] + lb.plan_time
        err = pm.model_error(t_meas, t_pred)
        budget = pm.error_budget({"rung": gs, "workload": "moe_decode",
                                  "dtype": "float32", "mesh": [8]})
        ok = err <= budget
        pad = "" if b == lanes else f" (b={b} padded to {lanes} lanes)"
        csv_row(f"table_serve.decode_step.b{b}", t_meas * 1e6,
                f"lanes={lanes}{pad} strategies={gs}+{ss} "
                f"predicted_us={t_pred*1e6:.1f} model_error={err:.3f} "
                f"budget={budget:.0f} within_budget={ok} latency_bound="
                + (",".join(pred["latency_bound"]) or "none"))
        assert ok, (f"decode-step model error {err:.2f} exceeds budget "
                    f"{budget:.0f} at b={b}")


# --------------------------------------------------------------------------
# Table 4: measured vs predicted with calibrated host parameters
# --------------------------------------------------------------------------

def table4_model_validation(n=1 << 17, r_nz=16):
    print("# table4: measured vs predicted (calibrated host params)")
    hw = calibrate_host()
    csv_row("table4.calib.w_private", 0,
            f"{hw.w_private/1e9:.2f}GB/s")
    csv_row("table4.calib.w_remote", 0, f"{hw.w_remote/1e9:.2f}GB/s")
    csv_row("table4.calib.tau", hw.tau * 1e6, "us")

    mesh = _mesh8()
    m = make_mesh_like_matrix(n, r_nz, locality_window=n // 64,
                              long_range_frac=0.02, seed=1)
    x_host = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    # each host device is its own "node": every inter-device message pays
    # tau (calibration note in benchmarks.common.calibrate_host)
    topo = Topology(8, 1)
    bs = n // 8 // 16
    plan = get_comm_plan(m.cols, n, 8, blocksize=bs, topology=topo)
    w = pm.SpmvWorkload(n=n, r_nz=r_nz, p=8, blocksize=bs, topology=topo,
                        counts=plan.counts)
    preds = pm.predict_all(w, hw)
    name_map = {"replicate": "replicate", "blockwise": "v2_blockwise",
                "condensed": "v3_condensed", "overlap": "overlap"}
    for strategy in ("replicate", "blockwise", "condensed", "overlap"):
        eng = DistributedSpMV(m, mesh, strategy=strategy, blocksize=bs,
                              shards_per_node=1)
        x = eng.shard_vector(x_host)
        t_meas = timeit(eng, x, iters=30)
        t_pred = preds[name_map[strategy]]
        acc = min(t_meas, t_pred) / max(t_meas, t_pred)
        csv_row(f"table4.{strategy}", t_meas * 1e6,
                f"predicted_us={t_pred*1e6:.1f} accuracy={acc:.2f}")


# --------------------------------------------------------------------------
# Fig 2: per-shard communication volumes per strategy and BLOCKSIZE sweep
# --------------------------------------------------------------------------

def fig2_volumes(n=1 << 16, r_nz=16, p=8):
    print("# fig2: per-shard comm volumes (elements) + BLOCKSIZE sweep; "
          "blockwise volume excludes own-shard copies for comparability")
    m = make_mesh_like_matrix(n, r_nz, locality_window=n // 128,
                              long_range_frac=0.002, seed=2)
    shard = n // p
    for bs in (shard // 64, shard // 16, shard // 4, shard):
        plan = get_comm_plan(m.cols, n, p, blocksize=bs,
                               topology=Topology(p, 4))
        c = plan.counts
        per_shard_cond = (c.s_local_in + c.s_remote_in)
        blockwise_foreign = c.total_blockwise_volume() - p * shard
        csv_row(f"fig2.blocksize_{bs}", 0,
                f"condensed_total={c.total_condensed_volume()} "
                f"blockwise_foreign={blockwise_foreign} "
                f"replicate_total={p*(n-shard)} "
                f"cond_max_shard={int(per_shard_cond.max())} "
                f"cond_min_shard={int(per_shard_cond.min())} "
                f"padded_condensed={c.padded_condensed_per_shard*p}")


# --------------------------------------------------------------------------
# Table 5: heat2d measured vs predicted
# --------------------------------------------------------------------------

def table5_heat2d(big_m=512, big_n=1024, steps=100, smoke=False):
    if smoke:
        big_m, big_n, steps = 128, 256, 20
    print(f"# table5: heat2d {big_m}x{big_n}, {steps} steps, 2x4 device grid")
    hw = calibrate_host(elem_bytes=4)
    mesh = compat.make_mesh((2, 4), ("data", "model"),
                            axis_types=compat.auto_axis_types(2))

    # each host device modeled as its own node (see table4 note): every
    # halo message pays the calibrated per-message tau
    w = pm.Heat2DWorkload(big_m=big_m, big_n=big_n, mprocs=2, nprocs=4,
                          topology=Topology(8, 1))

    # the eqs.(19)-(21) halo model prices the paper's in-place O(halo)
    # unpack — exactly what materialize="dest" runs; the "full" mode
    # additionally assembles the length-n x_copy each step (docs/
    # perf_model.md eq. 15'), priced by the model's materialize knob
    t_base = None
    for mode in ("dest", "full"):
        h = Heat2D(mesh, big_m, big_n, coef=0.1, materialize=mode)
        phi = h.init_field(0)
        t = timeit(lambda p: h.run(p, steps), phi, iters=3, warmup=1)
        pred_mode = pm.predict_heat2d(w, hw, steps=steps, materialize=mode)
        t_pred = pred_mode["halo"] + pred_mode["comp"]
        acc = min(t, t_pred) / max(t, t_pred)
        name = "table5.heat2d" if mode == "dest" else "table5.heat2d_full"
        csv_row(name, t * 1e6,
                f"unpack={mode} predicted_us={t_pred*1e6:.0f} "
                f"(halo={pred_mode['halo']*1e6:.0f} "
                f"comp={pred_mode['comp']*1e6:.0f}) "
                f"accuracy={acc:.2f}")
        if mode == "dest":
            t_base = t

    h = Heat2D(mesh, big_m, big_n, coef=0.1, overlap=True)
    phi = h.init_field(0)
    t = timeit(lambda p: h.run(p, steps), phi, iters=3, warmup=1)
    # the full-window overlap prediction incl. the edge-ring recompute term
    # (the refinement strategy="auto" ranks overlap vs condensed with)
    win = pm.predict_heat2d_window(w, hw, steps=steps)
    acc = min(t, win["overlap"]) / max(t, win["overlap"])
    csv_row("table5.heat2d_overlap", t * 1e6,
            f"predicted_us={win['overlap']*1e6:.0f} accuracy={acc:.2f} "
            f"vs_base={t/t_base:.2f}x "
            "(interior/edge split so halo exchange can overlap)")

    table5_scan(smoke=smoke)


def table5_scan(smoke=False):
    """Per-iteration scan-window rows (eq. 23′): the scanned ``Heat2D.run``
    loop and the CG solver, each against the per-step re-dispatch baseline
    over the same single-step window and against the steady-state model."""
    big_m, big_n, steps = (128, 256, 20) if smoke else (512, 1024, 100)
    print(f"# table5.scan: persistent windows, {steps}-step loops")
    hw = calibrate_host(elem_bytes=4)
    mesh = compat.make_mesh((2, 4), ("data", "model"),
                            axis_types=compat.auto_axis_types(2))
    w = pm.Heat2DWorkload(big_m=big_m, big_n=big_n, mprocs=2, nprocs=4,
                          topology=Topology(8, 1))

    # the scanned double-buffered overlap loop: Heat2D.run is ONE window
    # around lax.scan; the baseline re-dispatches the identical one-step
    # window (h.schedule) from a Python loop — same plan, same rung, the
    # only difference is where the loop runs
    h = Heat2D(mesh, big_m, big_n, coef=0.1, overlap=True, hw=hw,
               n_steps_hint=steps)
    phi = h.init_field(0)
    t_scan = timeit(lambda p_: h.run(p_, steps), phi, iters=3, warmup=1)

    def redispatch(p_):
        x = p_
        for _ in range(steps):
            x = h.schedule(x)
        return x

    t_loop = timeit(redispatch, phi, iters=3, warmup=1)
    scn = pm.predict_heat2d_scan(w, hw, steps)
    pred_iter = scn["per_iter"]["overlap"]
    meas_iter = t_scan / steps
    acc = min(meas_iter, pred_iter) / max(meas_iter, pred_iter)
    csv_row("table5.scan.heat2d", meas_iter * 1e6,
            f"per_iter steps={steps} predicted_us={pred_iter*1e6:.0f} "
            f"accuracy={acc:.2f} vs_redispatch={t_loop/t_scan:.2f}x "
            "(double-buffered halos, one persistent window)")

    # CG on the fused z = MtM p window: the scan carries (x, r, p); the
    # baseline drives the same fused product window per iteration with the
    # recurrence on the host
    from repro.core.solvers import ConjugateGradient
    from repro.core.spmv import normal_equations_step

    mesh1d = _mesh8()
    n, r_nz = (1 << 12, 8) if smoke else (1 << 14, 16)
    k = 20 if smoke else 50
    m = make_mesh_like_matrix(n, r_nz, seed=5)
    rng = np.random.default_rng(0)
    b = rng.standard_normal(n).astype(np.float32)
    cg = ConjugateGradient(m, mesh1d, strategy="condensed", hw=hw,
                           n_steps_hint=k)
    carries = cg.carries(b)
    t_scan = timeit(lambda *c: cg.schedule(*c, n_steps=k), *carries,
                    iters=3, warmup=1)

    step = normal_equations_step(m, mesh1d, strategy="condensed", hw=hw)

    def cg_redispatch(x, r, pv):
        for _ in range(k):
            z = step(pv)
            rs, pz = jnp.vdot(r, r), jnp.vdot(pv, z)
            alpha = jnp.where(pz != 0, rs / jnp.where(pz != 0, pz, 1), 0)
            x = x + alpha * pv
            r2 = r - alpha * z
            beta = jnp.where(rs != 0,
                             jnp.vdot(r2, r2) / jnp.where(rs != 0, rs, 1), 0)
            pv, r = r2 + beta * pv, r2
        return x

    t_loop = timeit(cg_redispatch, *carries, iters=3, warmup=1)
    pred = cg.predicted_loop(k)
    meas_iter = t_scan / k
    pred_iter = pred["per_iter"] if pred is not None else meas_iter
    acc = min(meas_iter, pred_iter) / max(meas_iter, pred_iter)
    csv_row("table5.scan.cg", meas_iter * 1e6,
            f"per_iter iters={k} n={n} predicted_us={pred_iter*1e6:.0f} "
            f"accuracy={acc:.2f} vs_redispatch={t_loop/t_scan:.2f}x "
            "(CGNR, one fused MtM window per iteration)")
