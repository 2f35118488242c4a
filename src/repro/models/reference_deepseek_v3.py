"""Plain reference of the DeepSeek-V3 decoder (Moonlight-16B-A3B's
``model_type``): the published forward pass of one sequence in float32
``jax.numpy`` at the highest matmul precision, with no cache, batching or
capacity.  It reads the weights of ``Model.init_params``'s tree by name and
nothing else of the program.

Followed (HF ``modeling_deepseek.py``, DeepSeek-V3 report arXiv:2412.19437):

* RMSNorm, eps ``rms_norm_eps``, before attention and FFN; ``kv_a_layernorm``
  on c_kv with its module default eps 1e-6;
* MLA without query compression: q = x W_q split into (q_nope, q_pe);
  x W_kv_a split into (c_kv, k_pe); k_nope and v from norm(c_kv) W_kv_b;
  rotary embedding on q_pe and the shared k_pe, in the published form that
  first permutes each vector from adjacent pairs to halves, then rotates
  halves; softmax scale (qk_nope + qk_rope) ** -0.5 (no YaRN: the config
  has no ``rope_scaling``);
* the first ``first_k_dense_replace`` layers with a dense SwiGLU FFN, the
  rest MoE: sigmoid scores of float32 router logits, the top-k of score +
  ``e_score_correction_bias`` chosen (``noaux_tc`` with one group), weights
  the chosen unbiased scores, renormalised (``norm_topk_prob``) and times
  ``routed_scaling_factor``; plus the shared experts as one SwiGLU;
* a final RMSNorm and an untied LM head.

Departures: routed experts are computed densely over every position and
weighted by the gate (zero where unchosen), which equals the sparse sum;
the router bias is a parameter here (a buffer in the published code).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["forward", "rms", "rope_permuted"]


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope_permuted(x, pos, theta):
    """x (T, [H,] D): the published form.  Adjacent pairs are permuted to
    halves, then the halves rotate (``rotate_half``)."""
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


def _swiglu(p, h):
    return (jax.nn.silu(h @ p["w1"]["w"].astype(jnp.float32))
            * (h @ p["w3"]["w"].astype(jnp.float32))) \
        @ p["w2"]["w"].astype(jnp.float32)


def _mla(p, h, cfg):
    t = h.shape[0]
    nh, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    f32 = jnp.float32
    pos = jnp.arange(t)
    q = (h @ p["wq"]["w"].astype(f32)).reshape(t, nh, dn + dr)
    q_nope, q_pe = q[..., :dn], rope_permuted(q[..., dn:], pos,
                                              cfg.rope_theta)
    kva = h @ p["wkv_a"]["w"].astype(f32)
    ckv = rms(kva[:, :r], p["kv_norm"]["scale"], 1e-6)
    k_pe = rope_permuted(kva[:, r:], pos, cfg.rope_theta)
    kv = (ckv @ p["wkv_b"]["w"].astype(f32)).reshape(t, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    s = (jnp.einsum("thn,shn->hts", q_nope, k_nope)
         + jnp.einsum("thr,sr->hts", q_pe, k_pe)) * (dn + dr) ** -0.5
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = jnp.einsum("hts,shv->thv", jax.nn.softmax(s, -1), v)
    return o.reshape(t, nh * dv) @ p["wo"]["w"].astype(f32)


def _moe(p, h, cfg):
    """(output, chosen experts (T, k)) of one MoE layer."""
    moe = p["moe"]
    scores = jax.nn.sigmoid(h @ moe["router"]["w"].astype(jnp.float32))
    _, top_e = jax.lax.top_k(scores + moe["router"]["bias"], cfg
                             .experts_per_token)
    top_w = jnp.take_along_axis(scores, top_e, -1)
    top_w = top_w / top_w.sum(-1, keepdims=True) * cfg.router_scale
    t = h.shape[0]
    gate = jnp.zeros_like(scores).at[jnp.arange(t)[:, None], top_e].set(
        top_w)

    def expert(acc, e):
        w1, w2, w3 = (moe[n][e].astype(jnp.float32)
                      for n in ("w1", "w2", "w3"))
        y = (jax.nn.silu(h @ w1) * (h @ w3)) @ w2
        return acc + gate[:, e][:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          jnp.arange(cfg.num_experts))
    return out + _swiglu(p["res_mlp"], h), top_e


def forward(cfg, params, tokens):
    """(logits (T, V), routing (MoE layers, T, k)) of one sequence
    ``tokens`` (T,)."""
    with jax.default_matmul_precision("highest"):
        eps = 1e-5
        x = params["embed"]["w"][tokens].astype(jnp.float32)
        lead = cfg.first_dense_layers
        routing = []
        for i in range(cfg.num_layers):
            stack, j = (("lead_layers", i) if i < lead
                        else ("layers", i - lead))
            p = jax.tree.map(lambda a: a[j], params[stack])
            x = x + _mla(p["attn"], rms(x, p["ln1"]["scale"], eps), cfg)
            h = rms(x, p["ln2"]["scale"], eps)
            if i < lead:
                x = x + _swiglu(p["mlp"], h)
            else:
                y, top_e = _moe(p, h, cfg)
                x = x + y
                routing.append(top_e)
        x = rms(x, params["final_norm"]["scale"], eps)
        return x @ params["lm_head"]["w"].astype(jnp.float32), \
            jnp.stack(routing)
