"""Moonlight-16B-A3B (the DeepSeek-V3 block) against its plain float32
reference, on a reduced config (1 dense + 2 MoE layers, 8 experts top-2,
MLA at rank 32) with seeded random weights on the CPU.

Tolerance: both sides compute in float32 here.  They differ only in the
order of sums and in the rotary form (the program turns adjacent pairs in
place, the reference permutes them to halves first, as published), which
moves a logit by about 1e-6 of its row's spread.  ``TOL`` = 1e-4 of the
row's standard deviation leaves room for that and lies far below what a
lower precision does: float8 expert weights move logits by more than
1e-2 of the spread (``test_float8_control_fails``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels import mla_decode as K
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import build_moe_layer
from repro.models import mla as A
from repro.models import moe as M
from repro.models import reference_deepseek_v3 as R
from repro.models.transformer import Model, RunCtx, _ffn_fwd
from repro.serve import Request, ServeEngine, moe_decode_hook

CFG = get_config("moonlight-16b-a3b", reduced=True)
TOL = 1e-4


def _model(seed=0):
    model = Model(CFG, RunCtx(remat="none", act_dtype=jnp.float32))
    params = model.init_params(jax.random.PRNGKey(seed))
    # a selection bias that changes a visible share of choices
    bias = params["layers"]["moe"]["router"]["bias"]
    params["layers"]["moe"]["router"]["bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), bias.shape)
    return model, params


def _gap(got, ref):
    """max |got - ref| / std(ref) over rows."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float((np.abs(got - ref).max(-1) / ref.std(-1)).max())


def _tokens(n, seed=0):
    return jax.random.randint(jax.random.PRNGKey(100 + seed), (n,), 0,
                              CFG.vocab_size)


def test_reduced_config_keeps_the_block():
    full = get_config("moonlight-16b-a3b")
    assert (full.kv_lora_rank, full.qk_nope_head_dim, full.qk_rope_head_dim,
            full.v_head_dim) == (512, 128, 64, 128)
    assert (full.num_experts, full.experts_per_token, full.d_ff,
            full.residual_d_ff, full.dense_d_ff) == (64, 6, 1408, 2816, 11264)
    assert (full.router_score, full.router_scale, full.first_dense_layers,
            full.rope_theta) == ("sigmoid", 2.446, 1, 50000.0)
    # dropless: capacity holds every token of a batch
    assert M.moe_capacity(128, full) == 128
    assert CFG.is_mla and CFG.first_dense_layers == 1 and CFG.num_layers == 3


def test_param_count_full_config_exact():
    cfg = get_config("moonlight-16b-a3b")
    shapes = jax.eval_shape(Model(cfg, RunCtx()).init_params,
                            jax.random.PRNGKey(0))
    actual = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    total, active = cfg.param_count()
    assert actual == total == 15_960_110_208
    assert 2.8e9 < active < 3.0e9           # "A3B": ~3B activated


def test_forward_matches_reference():
    model, params = _model()
    tokens = _tokens(24)
    got = model.forward(params, tokens[None])[0]
    ref, _ = R.forward(CFG, params, tokens)
    assert _gap(got, ref) < TOL


def test_float8_control_fails():
    """The reference with float8 e4m3 expert weights (one scale per output
    channel) fails ``TOL`` by orders of magnitude."""
    _, params = _model()
    tokens = _tokens(24)
    ref, _ = R.forward(CFG, params, tokens)

    def fp8(w):
        s = jnp.maximum(jnp.abs(w).max(-2, keepdims=True), 1e-30) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    q = jax.tree.map(lambda a: a, params)
    for n in ("w1", "w2", "w3"):
        q["layers"]["moe"][n] = fp8(params["layers"]["moe"][n])
    ctl, _ = R.forward(CFG, q, tokens)
    assert _gap(ctl, ref) > 100 * TOL


def test_router_bias_chooses_scores_weigh():
    _, params = _model()
    pr = jax.tree.map(lambda a: a[0], params["layers"]["moe"]["router"])
    h = jax.random.normal(jax.random.PRNGKey(7), (16, CFG.d_model))
    scores = jax.nn.sigmoid(h @ pr["w"])
    w0, e0 = M.route({**pr, "bias": jnp.zeros_like(pr["bias"])}, h, CFG)
    # a bias on the least-scored expert of token 0 makes it chosen, and its
    # weight comes from its unbiased score
    low = int(jnp.argmin(scores[0]))
    w1, e1 = M.route({**pr, "bias": jnp.zeros_like(pr["bias"]).at[low]
                      .set(10.0)}, h, CFG)
    assert low not in np.asarray(e0[0]) and low in np.asarray(e1[0])
    chosen = np.asarray(scores[0][e1[0]])
    np.testing.assert_allclose(np.asarray(w1[0]),
                               chosen / chosen.sum() * 2.446, rtol=1e-6)
    # renormalised over the k, then scaled by routed_scaling_factor
    np.testing.assert_allclose(np.asarray(w0.sum(-1)), 2.446, rtol=1e-6)
    # softmax routers (mixtral) renormalise to 1
    mix = get_config("mixtral-8x22b", reduced=True)
    pm = {"w": jax.random.normal(jax.random.PRNGKey(3),
                                 (CFG.d_model, mix.num_experts))}
    wm, _ = M.route(pm, h, mix)
    np.testing.assert_allclose(np.asarray(wm.sum(-1)), 1.0, rtol=1e-6)


def test_prefill_and_decode_route_alike():
    """The decode hook (DynamicMoELayer) and the prefill path (moe_fwd)
    choose the same experts and give the same output for the same
    hidden states."""
    model, params = _model()
    p = jax.tree.map(lambda a: a[1], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 1, CFG.d_model))
    mesh = make_local_mesh((1,), ("data",))
    layer = build_moe_layer(model, params, 4, mesh)
    ctx_dec = dataclasses.replace(model.ctx,
                                  moe_step=moe_decode_hook(CFG, layer))
    st_dec, st_pre = {}, {}
    y_dec = _ffn_fwd(p, x, CFG, ctx_dec, kind="moe", stats=st_dec)
    y_pre = _ffn_fwd(p, x.reshape(1, 4, -1), CFG, model.ctx, kind="moe",
                     stats=st_pre)
    np.testing.assert_array_equal(np.asarray(st_dec["experts"]),
                                  np.asarray(st_pre["experts"]))
    assert int(st_dec["dropped"]) == int(st_pre["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(y_dec).reshape(4, -1),
                               np.asarray(y_pre).reshape(4, -1),
                               rtol=1e-5, atol=1e-6)


def test_absorbed_cached_mla_matches_naive():
    """Token-by-token absorbed attention over the latent cache equals the
    naive (lifted k/v) causal attention over the whole sequence."""
    _, params = _model()
    p = jax.tree.map(lambda a: a[0], params["lead_layers"]["attn"])
    s = 12
    x = jax.random.normal(jax.random.PRNGKey(9), (2, s, CFG.d_model))
    naive = A.mla_fwd(p, x, CFG)
    cache = jax.tree.map(lambda a: a[None],
                         A.init_mla_cache(CFG, 2, 16, jnp.float32))
    step = jax.jit(lambda p, x, c, i: A.mla_cached(p, x, CFG, c, 0, i))
    outs = []
    for i in range(s):
        y, cache = step(p, x[:, i:i + 1], cache, jnp.asarray(i))
        outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(naive), rtol=1e-5, atol=1e-5)
    # a whole chunk at once writes and attends the same
    cache2 = jax.tree.map(lambda a: a[None],
                          A.init_mla_cache(CFG, 2, 16, jnp.float32))
    y2, cache2 = A.mla_cached(p, x, CFG, cache2, 0, jnp.asarray(0))
    np.testing.assert_allclose(np.asarray(y2), np.asarray(naive),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache2["ckv"]),
                               np.asarray(cache["ckv"]), rtol=1e-6)


def _ring(cache_len, sessions):
    """slot_pos of one lane's ring after ``sessions`` (lengths, oldest
    first) wrote positions 0 .. n - 1 each at slot position % cache_len."""
    sp = np.full(cache_len, -1, np.int32)
    for n in sessions:
        for p in range(n):
            sp[p % cache_len] = p
    return sp


# (cache_len, each lane's sessions: the last one's final position is the
# lane's query position); blocks of 512 rows
KERNEL_CASES = {
    "ragged": (1024, [[1], [17], [600], [1024]]),
    "wrapped": (1024, [[5], [2100], [1100], [1024]]),
    "stale": (1024, [[900, 3], [1024, 520], [2100, 16], [9]]),
    "partial_block": (600, [[1], [530], [600], [1300, 590]]),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_mla_decode_kernel_matches_einsum(case):
    """The decode kernel (block walk by lane position, the einsum form's
    mask inside each visited block) gives the einsum form's latent output
    on layer 1 of a stacked cache: ragged positions (position 0 too), a
    ring that has wrapped, stale rows of a reused lane past its position,
    and a ring the block does not divide."""
    cache_len, lanes = KERNEL_CASES[case]
    h, r, dr = CFG.num_heads, CFG.kv_lora_rank, CFG.qk_rope_head_dim
    b, layers = len(lanes), 2
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    cache = {
        "ckv": jax.random.normal(ks[0], (layers, b, cache_len, r)),
        "kpe": jax.random.normal(ks[1], (layers, b, cache_len, dr)),
        "slot_pos": jnp.asarray(np.stack(
            [np.stack([_ring(cache_len, s) for s in lanes])] * layers)),
    }
    qpos = jnp.asarray([s[-1] - 1 for s in lanes], jnp.int32)
    q_lat = jax.random.normal(ks[2], (b, h, r))
    q_pe = jax.random.normal(ks[3], (b, h, dr))
    scale = A._scale(CFG)
    got = K.mla_decode_attention(q_lat, q_pe, cache["ckv"],
                                 jnp.swapaxes(cache["kpe"], 2, 3),
                                 cache["slot_pos"], jnp.int32(1), qpos,
                                 scale=scale)
    want = A._attend(q_lat[:, None], q_pe[:, None], cache, 1, qpos[:, None],
                     scale)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the rows of the blocks each lane visits
    n = np.minimum(np.asarray(qpos) + 1, cache_len)
    assert K.rows_read(qpos, cache_len) == int((-(-n // 512)).sum() * 512)


@pytest.mark.parametrize("chunk", [4, 8])
def test_engine_prefill_and_decode_match_reference(chunk):
    """ServeEngine (chunked prefill into the latent cache, insert, decode
    ticks with the MoE through DynamicMoELayer) against the reference's
    full forward pass over each prompt and its served tokens."""
    model, params = _model()
    mesh = make_local_mesh((1,), ("data",))
    layer = build_moe_layer(model, params, 2, mesh)
    eng = ServeEngine(model, params, num_slots=2, cache_len=32,
                      prefill_chunk=chunk, moe_layer=layer,
                      cache_dtype=jnp.float32)
    prompts = {"a": np.asarray(_tokens(16, 1)), "b": np.asarray(_tokens(8, 2))}
    for rid, pr in prompts.items():
        eng.submit(Request(id=rid, prompt=pr, max_new_tokens=6))
    ticks = []
    while eng.slots.active() or len(eng.queue):
        eng.step()
        ticks.append(np.asarray(eng.last_logits[:, 0]))
    rep = eng.report()
    assert rep.telemetry["moe_dropped"] == 0
    # every decode step ran the latent-attention kernel
    assert rep.telemetry["mla_kernel_steps"] == rep.telemetry[
        "decode_steps"] > 0
    assert rep.telemetry["mla_rows_read"] > 0
    for rid, pr in prompts.items():
        lane, served = rep.slot_of[rid], rep.outputs[rid]
        ref, _ = R.forward(CFG, params, jnp.asarray(
            np.concatenate([pr, served[:-1]]).astype(np.int32)))
        # decode tick j reads served token j at position len(prompt) + j
        rows = ref[len(pr):len(pr) + len(served) - 1]
        got = np.stack([t[lane] for t in ticks[:len(served) - 1]])
        assert _gap(got, rows) < TOL
        # and the served tokens are the reference's greedy choices
        assert list(np.asarray(rows.argmax(-1))) == served[1:]


def test_engine_records_decode_routing():
    """The engine exposes the last finished tick's routing of every MoE
    layer (what the benchmark's check reads), and it is the reference's."""
    model, params = _model()
    mesh = make_local_mesh((1,), ("data",))
    layer = build_moe_layer(model, params, 2, mesh)
    eng = ServeEngine(model, params, num_slots=2, cache_len=32,
                      prefill_chunk=8, moe_layer=layer,
                      cache_dtype=jnp.float32)
    pr = np.asarray(_tokens(8, 3))
    eng.submit(Request(id="r", prompt=pr, max_new_tokens=4))
    eng.step()
    eng.step()
    experts = np.asarray(eng.last_experts)                # (L_moe, B, k)
    assert experts.shape == (CFG.num_layers - 1, 2, CFG.experts_per_token)
    served = eng.report().outputs["r"]
    _, routing = R.forward(CFG, params, jnp.asarray(
        np.concatenate([pr, served[:2]]).astype(np.int32)))
    lane = eng.report().slot_of["r"]
    np.testing.assert_array_equal(
        np.sort(experts[:, lane], -1),
        np.sort(np.asarray(routing[:, len(pr) + 1]), -1))
