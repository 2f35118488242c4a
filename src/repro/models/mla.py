"""Multi-head latent attention (DeepSeek-V3, arXiv:2412.19437 §2.1), without
query compression (``q_lora_rank`` null, as Moonlight-16B-A3B).

Per token, ``wkv_a`` projects the hidden state to a latent ``c_kv``
(``kv_lora_rank`` wide, RMS-normed by ``kv_norm``) and one rotary key part
``k_pe`` (``qk_rope_head_dim``) that every head shares.  ``wkv_b`` lifts
``c_kv`` to each head's key part ``k_nope`` and value; the query is
``[q_nope, rope(q_pe)]``, and the softmax scale is
``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5``.

``mla_fwd`` is that computation over a whole sequence (training and the
forward pass).  ``mla_cached`` is the serving form: the **latent cache**
holds only ``c_kv`` and the rotated ``k_pe`` of each position (576 values a
token at Moonlight's widths, in place of 16 heads of k and v), and the
attention reads it in the absorbed form: the query is moved into the latent
space through ``wkv_b``'s key half, the scores are taken against ``c_kv``
and ``k_pe`` directly, and the latent output is lifted by the value half.
The same function serves a prefill chunk (S tokens) and a decode step
(S = 1).  Rotary embeddings turn adjacent pairs (x[2i], x[2i+1]), which
gives the dot products of DeepSeek-V3's permuted half-split form.

A prefill chunk takes the scores and the weighted sum as two einsums over
every slot of the layer, the invalid ones masked (``slot_pos`` outside
``[0, qpos]``).  A decode step takes one Pallas kernel
(``kernels.mla_decode``), the shape alone deciding: each lane walks only
the 512-row blocks that can hold its attended rows,
``ceil(min(pos + 1, T) / 512)`` of them, under an online softmax, reading
each row once and writing no logits; inside a visited block it keeps the
einsums' mask, so a wrapped ring sees every slot and a reused lane's stale
rows stay out.  It reads the stacked cache where it lies (layer index as
a scalar operand), after the step's row is written in place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.mla_decode import mla_decode_attention
from repro.models import layers as L

__all__ = ["init_mla", "mla_fwd", "mla_cached", "init_mla_cache",
           "rope_pairs", "KV_NORM_EPS"]

KV_NORM_EPS = 1e-6     # DeepseekV3RMSNorm's default, used by kv_a_layernorm


def init_mla(key, cfg, *, dtype=jnp.float32):
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": L.init_linear(ks[0], d, h * (dn + dr), dtype=dtype),
        "wkv_a": L.init_linear(ks[1], d, r + dr, dtype=dtype),
        "kv_norm": L.init_norm(None, r),
        "wkv_b": L.init_linear(ks[2], r, h * (dn + dv), dtype=dtype),
        "wo": L.init_linear(ks[3], h * dv, d, dtype=dtype),
    }


def init_mla_cache(cfg, batch, cache_len, dtype):
    """One layer's latent cache; ``slot_pos`` is per lane."""
    return {
        "ckv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dtype),
        "kpe": jnp.zeros((batch, cache_len, cfg.qk_rope_head_dim), dtype),
        "slot_pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


def rope_pairs(x, positions, *, theta):
    """x (..., S, [H,] D) with positions (..., S): rotate (x[2i], x[2i+1])
    by positions * theta ** (-2i / D)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (..., S, D/2)
    if x.ndim == positions.ndim + 2:                          # a heads axis
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _project(p, x, cfg, positions):
    """q_nope (B,S,H,dn), rotated q_pe (B,S,H,dr), normed c_kv (B,S,r) and
    rotated k_pe (B,S,dr) of the tokens ``x`` at ``positions`` (B|1, S)."""
    b, s, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = L.linear(p["wq"], x).reshape(b, s, h, dn + dr)
    q_nope = q[..., :dn]
    q_pe = rope_pairs(q[..., dn:], positions, theta=cfg.rope_theta)
    kva = L.linear(p["wkv_a"], x)
    ckv = L.norm_apply(p["kv_norm"], kva[..., :r], eps=KV_NORM_EPS)
    k_pe = rope_pairs(kva[..., r:], positions, theta=cfg.rope_theta)
    return q_nope, q_pe, ckv, k_pe


def _scale(cfg):
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def mla_fwd(p, x, cfg):
    """Causal MLA over whole sequences x (B, S, D), the naive form: keys and
    values lifted from c_kv for every position."""
    b, s, _ = x.shape
    h, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    pos = jnp.arange(s)[None]
    q_nope, q_pe, ckv, k_pe = _project(p, x, cfg, pos)
    kv = L.linear(p["wkv_b"], ckv).reshape(b, s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    f32 = jnp.float32
    logits = (jnp.einsum("bshn,bthn->bhst", q_nope.astype(f32),
                         k_nope.astype(f32))
              + jnp.einsum("bshr,btr->bhst", q_pe.astype(f32),
                           k_pe.astype(f32))) * _scale(cfg)
    causal = pos[0][:, None] >= pos[0][None, :]
    logits = jnp.where(causal[None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthv->bshv", w, v.astype(f32))
    return L.linear(p["wo"], out.reshape(b, s, h * dv).astype(x.dtype))


def mla_cached(p, x, cfg, cache, layer, pos):
    """S tokens x (B, S, D) per lane at positions pos + [0, S) (``pos``
    scalar or (B,)): write their latent rows into layer ``layer`` of the
    stacked cache ``cache`` ({ckv, kpe, slot_pos}, each (L, B, T, ...)) and
    attend, in the absorbed form, over every valid row of that layer.
    Returns (y (B, S, D), the updated stacked cache)."""
    b, s, _ = x.shape
    h, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    cache_len = cache["slot_pos"].shape[-1]
    qpos = jnp.broadcast_to(pos, (b,))[:, None] + jnp.arange(s)[None]
    q_nope, q_pe, ckv, k_pe = _project(p, x, cfg, qpos)

    lane = jnp.arange(b)[:, None]
    slots = qpos % cache_len
    dt = cache["ckv"].dtype
    cache = {
        "ckv": cache["ckv"].at[layer, lane, slots].set(ckv.astype(dt)),
        "kpe": cache["kpe"].at[layer, lane, slots].set(k_pe.astype(dt)),
        "slot_pos": cache["slot_pos"].at[layer, lane, slots].set(
            qpos.astype(jnp.int32)),
    }

    wkv_b = p["wkv_b"]["w"].reshape(r, h, dn + dv)
    f32 = jnp.float32
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(f32),
                       wkv_b[..., :dn].astype(f32))
    if s == 1:
        # kpe with the slot axis last: the layout the TPU keeps it in, so
        # the swap is free and the kernel reads the cache where it lies
        o_lat = mla_decode_attention(
            q_lat[:, 0], q_pe[:, 0], cache["ckv"],
            jnp.swapaxes(cache["kpe"], 2, 3), cache["slot_pos"], layer,
            qpos[:, 0], scale=_scale(cfg))[:, None]
    else:
        o_lat = _attend(q_lat, q_pe, cache, layer, qpos, _scale(cfg))
    out = jnp.einsum("bshr,rhv->bshv", o_lat, wkv_b[..., dn:].astype(f32))
    y = L.linear(p["wo"], out.reshape(b, s, h * dv).astype(x.dtype))
    return y, cache


def _attend(q_lat, q_pe, cache, layer, qpos, scale):
    """The latent output (B, S, H, R) of S queries a lane: scores and the
    weighted sum in float32 over every slot of layer ``layer``, the
    invalid ones masked."""
    f32 = jnp.float32
    c_all, pe_all, sp = (cache["ckv"][layer], cache["kpe"][layer],
                         cache["slot_pos"][layer])
    logits = (jnp.einsum("bshr,btr->bhst", q_lat, c_all.astype(f32))
              + jnp.einsum("bshp,btp->bhst", q_pe.astype(f32),
                           pe_all.astype(f32))) * scale
    valid = (sp[:, None, :] >= 0) & (sp[:, None, :] <= qpos[..., None])
    logits = jnp.where(valid[:, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,btr->bshr", w, c_all.astype(f32))
