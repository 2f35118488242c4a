"""The Moonlight-16B-A3B yardstick: seeded weights, the plain float32
reference forward pass of the DeepSeek-V3 decoder, and the float8 control.

The weights are made here, on the device, in one jitted call from the seed,
in bfloat16 (the type they are served in), with the shapes of the program's
parameter tree.  The reference reads them by name and follows the published
decoder (HF ``modeling_deepseek.py``; arXiv:2412.19437): RMSNorm (eps
``rms_norm_eps``; 1e-6 on c_kv, the default ``kv_a_layernorm`` is built
with); MLA without query compression, k and v lifted from the normed latent
c_kv, the rotary part permuted from adjacent pairs to halves and rotated as
published (theta ``rope_theta``, no YaRN), scale (qk_nope + qk_rope) ** -0.5;
``first_k_dense_replace`` dense SwiGLU layers, then MoE layers: sigmoid
scores of float32 router logits, the top-k of score + bias chosen, the
chosen unbiased scores renormalised and times ``routed_scaling_factor``,
plus the shared experts as one SwiGLU of ``n_shared_experts`` x the expert
width; a final RMSNorm and an untied LM head.  It runs in float32 at the
highest matmul precision over one whole sequence at a time, with no cache,
batching or capacity.

Departures, none of which changes a number: routed experts run densely over
every position, weighted by the gate (zero where unchosen); queries attend
in blocks of ``Q_BLOCK`` rows so that a 7k-token sequence fits; the LM head
runs only on the rows asked for; at those rows the routing may be pinned
(``forward``'s ``pin``).
"""
from __future__ import annotations

import functools

import numpy as np

from bench.common import key32

FP8_MAX = 448.0            # largest finite float8_e4m3fn
BIAS_STD = 0.05            # the seeded e_score_correction_bias
Q_BLOCK = 1024


def make_params(abstract, seed: int):
    """Weights with the structure and shapes of ``abstract`` (a pytree of
    ``ShapeDtypeStruct``), in bfloat16: norm scales are ones, the router's
    selection bias normal with std ``BIAS_STD``, the embedding standard
    normal, every other matrix normal / sqrt(fan-in) with the fan-in its
    second-to-last axis.  Stacked leaves are drawn one slab at a time."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def draw(key, path, leaf):
        names = [getattr(k, "key", str(k)) for k in path]
        shape, dtype = leaf.shape, jnp.bfloat16
        if names[-1] == "scale":
            return jnp.ones(shape, dtype)
        if names[-1] == "bias":
            return (jax.random.normal(key, shape, jnp.float32)
                    * BIAS_STD).astype(dtype)
        std = 1.0 if "embed" in names else shape[-2] ** -0.5
        lead, slab = shape[:-2], shape[-2:]
        count = int(np.prod(lead)) if lead else 1
        keys = jax.random.split(key, count)
        out = jax.lax.map(
            lambda k: (jax.random.normal(k, slab, jnp.float32)
                       * std).astype(dtype), keys)
        return out.reshape(shape)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten(
            [draw(k, path, leaf) for k, (path, leaf) in zip(keys, leaves)])

    return make(jax.random.PRNGKey(key32(seed, "weights")))


def fp8(w, axis=-2):
    """float8 e4m3 with one scale per slice along ``axis`` (weights: per
    output channel; cache rows: per token)."""
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True),
                    1e-30) / FP8_MAX
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (T, [H,] D) as published: adjacent pairs permuted to halves, then
    the halves rotated."""
    import jax.numpy as jnp
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2)
    x = jnp.concatenate([x[..., 0], x[..., 1]], axis=-1)
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    ang = jnp.concatenate([ang, ang], -1)
    if x.ndim == 3:
        ang = ang[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def forward(cfg: dict, params, tokens, rows, pin, *, margin: float,
            quantize: bool = False):
    """One sequence ``tokens`` (T,) through the reference; T is a multiple
    of ``Q_BLOCK`` or below it (``padded_length``).

    ``rows`` (R,): the positions whose logits are returned.  ``pin``
    (R, MoE layers, k) or None: experts chosen elsewhere at those rows; in
    each MoE layer a row's routing is pinned to them where each of them
    scores (score + bias) within ``margin`` of the reference's k-th best.
    With ``quantize`` the expert weights (routed and shared) and the cached
    latent rows (c_kv, k_pe) go through float8 e4m3 first: the control.

    Returns (logits (R, V), own routing at the rows (R, MoE layers, k),
    within (R, MoE layers) bool: the pin held)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32 = jnp.float32
    hi = lax.Precision.HIGHEST
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    k_top, n_exp = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    theta, eps = float(cfg["rope_theta"]), float(cfg["rms_norm_eps"])
    lead = cfg["first_k_dense_replace"]
    t = tokens.shape[0]
    pos = jnp.arange(t)
    wx = fp8 if quantize else (lambda w: w.astype(f32))
    cached = (lambda a: fp8(a, -1)) if quantize else (lambda a: a)

    def mm(a, w):
        return jnp.matmul(a, w.astype(f32), precision=hi)

    def swiglu(p, h, w=lambda a: a.astype(f32)):
        return jnp.matmul(jax.nn.silu(jnp.matmul(h, w(p["w1"]),
                                                 precision=hi))
                          * jnp.matmul(h, w(p["w3"]), precision=hi),
                          w(p["w2"]), precision=hi)

    def mla(p, h):
        q = mm(h, p["wq"]["w"]).reshape(t, nh, dn + dr)
        q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], pos, theta)
        kva = mm(h, p["wkv_a"]["w"])
        ckv = cached(_rms(kva[:, :r], p["kv_norm"]["scale"], 1e-6))
        k_pe = cached(_rope(kva[:, r:], pos, theta))
        kv = mm(ckv, p["wkv_b"]["w"]).reshape(t, nh, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]

        qb = min(Q_BLOCK, t)

        def block(i):
            qn = lax.dynamic_slice_in_dim(q_nope, i * qb, qb)
            qp = lax.dynamic_slice_in_dim(q_pe, i * qb, qb)
            s = (jnp.einsum("thn,shn->hts", qn, k_nope, precision=hi)
                 + jnp.einsum("thr,sr->hts", qp, k_pe, precision=hi)) \
                * (dn + dr) ** -0.5
            qpos = i * qb + jnp.arange(qb)
            s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
            return jnp.einsum("hts,shv->thv", jax.nn.softmax(s, -1), v,
                              precision=hi)

        o = lax.map(block, jnp.arange(t // qb))
        return mm(o.reshape(t, nh * dv), p["wo"]["w"])

    def moe(li, h):
        m = params["layers"]["moe"]
        scores = jax.nn.sigmoid(mm(h, m["router"]["w"][li]))
        choice = scores + m["router"]["bias"][li].astype(f32)
        _, top_e = lax.top_k(choice, k_top)
        kth = jnp.sort(choice[rows], -1)[:, -k_top]            # (R,)
        own = top_e[rows]
        within = jnp.zeros(rows.shape, bool)
        if pin is not None:
            want = pin[:, li]
            within = jnp.all(jnp.take_along_axis(choice[rows], want, -1)
                             >= kth[:, None] - margin, -1)
            top_e = top_e.at[rows].set(jnp.where(within[:, None], want,
                                                 own))
        top_w = jnp.take_along_axis(scores, top_e, -1)
        top_w = top_w / top_w.sum(-1, keepdims=True) \
            * cfg["routed_scaling_factor"]
        gate = jnp.zeros_like(scores).at[jnp.arange(t)[:, None],
                                         top_e].set(top_w)

        def expert(acc, e):
            p = {n: m[n][li, e] for n in ("w1", "w2", "w3")}
            return acc + gate[:, e][:, None] * swiglu(p, h, wx), None

        out, _ = lax.scan(expert, jnp.zeros_like(h), jnp.arange(n_exp))
        shared = jax.tree.map(lambda a: a[li], params["layers"]["res_mlp"])
        shared = {n: shared[n]["w"] for n in ("w1", "w2", "w3")}
        return out + swiglu(shared, h, wx), own, within

    x = params["embed"]["w"][tokens].astype(f32)
    owns, withins = [], []
    for i in range(cfg["num_hidden_layers"]):
        stack, j = ("lead_layers", i) if i < lead else ("layers", i - lead)
        lay = params[stack]
        p = jax.tree.map(lambda a: a[j], {k: v for k, v in lay.items()
                                          if k not in ("moe", "res_mlp")})
        x = x + mla(p["attn"], _rms(x, p["ln1"]["scale"], eps))
        h = _rms(x, p["ln2"]["scale"], eps)
        if i < lead:
            x = x + swiglu({n: p["mlp"][n]["w"] for n in ("w1", "w2", "w3")},
                           h)
        else:
            y, own, within = moe(i - lead, h)
            x = x + y
            owns.append(own)
            withins.append(within)
    x = _rms(x[rows], params["final_norm"]["scale"], eps)
    return (mm(x, params["lm_head"]["w"]), jnp.stack(owns, 1),
            jnp.stack(withins, 1))


def padded_length(n: int) -> int:
    """The length a sequence of ``n`` tokens is padded to (at the end:
    causal attention leaves the earlier rows as they are), so that the
    reference compiles for few lengths."""
    step = Q_BLOCK if n > Q_BLOCK else 64
    return -(-n // step) * step


@functools.lru_cache(maxsize=4)
def compiled_forward(cfg_items: tuple, margin: float, quantize: bool):
    import jax
    cfg = dict(cfg_items)
    return jax.jit(functools.partial(forward, cfg, margin=margin,
                                     quantize=quantize))


def config_key(cfg: dict) -> tuple:
    """The configuration's scalar keys, hashable (``compiled_forward``)."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))
