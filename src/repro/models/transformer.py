"""The model zoo: one scan-over-layers transformer covering the assigned
architectures (dense / MoE / SSM / hybrid / enc-dec / VLM), with GQA or
multi-head latent attention (``models.mla``) and optional leading dense
layers ahead of the scanned stack (DeepSeek-V3).

Everything is pure-functional: ``Model.init_params`` builds a nested-dict
pytree (safe under ``jax.eval_shape`` for the dry-run), ``Model.forward``
is the training forward, ``Model.init_cache``/``prefill``/``decode_step``
serve inference.  Sharding is injected from outside via the ``RunCtx``
constraint callbacks (runtime/sharding.py), keeping model code
mesh-agnostic.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import mla as A
from repro.models import moe as M
from repro.models import ssm as S

__all__ = ["RunCtx", "Model", "lm_loss"]


@dataclasses.dataclass(frozen=True)
class RunCtx:
    """Runtime context: grouping for MoE dispatch, remat policy, and
    sharding-constraint hooks (None = single-device smoke)."""

    moe_groups: int = 1
    remat: str = "full"          # none | full | dots
    constrain: Callable[[jax.Array, str], jax.Array] | None = None
    act_dtype: Any = jnp.bfloat16
    vocab_shards: int = 1        # model-axis size (embed strategy divisibility)
    scan_barrier: bool = True    # optimization_barrier on the layer-scan
    # carry: stops XLA hoisting the residual-stack bf16->f32 convert out of
    # the backward loop (a whole-stack f32 copy; see EXPERIMENTS.md §Perf)
    remat_groups: int = 1        # >1: nested (sqrt) remat — outer scan over
    # groups of layers is checkpointed, so only G boundary residuals are
    # saved instead of L (peak activations / L*(1/G + G/L); one extra fwd)
    cast_params_once: bool = False  # cast layer stack f32->act_dtype before
    # the scan: FSDP all-gathers then move bf16 instead of f32 master params
    # (2x weight-collective cut; see EXPERIMENTS.md §Perf)
    ssm_scan_dtype: Any = jnp.float32  # bf16 halves SSM recurrence traffic
    # Serving hook: when set, the MoE FFN of a SINGLE-TOKEN decode step is
    # routed through fn(moe_params, h) -> (out (B, 1, D), experts (B, k),
    # dropped) instead of the in-jit moe_fwd dispatch — repro.serve wires
    # the per-batch-routed DynamicMoELayer comm schedule in here
    # (docs/serving.md).  Prefill and training (s_len > 1) keep moe_fwd.
    moe_step: Callable[[Any, jax.Array], tuple] | None = None

    def c(self, x, tag):
        return self.constrain(x, tag) if self.constrain is not None else x


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _stack_init(key, n, init_one):
    # one layer at a time: a jitted init holds one layer's random bits as
    # scratch, not the whole stack's (same values as a vmap over the keys)
    return jax.lax.map(init_one, jax.random.split(key, n))


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _init_block(key, cfg, dtype, *, kind: str):
    """kind: dense | lead | moe | ssm | hybrid | encdec_dec | enc | cross
    ("lead": a leading dense layer of width ``cfg.dense_d_ff``)"""
    ks = jax.random.split(key, 8)
    p: dict[str, Any] = {"ln1": L.init_norm(ks[0], cfg.d_model, kind=cfg.norm)}
    if kind == "ssm":
        p["ssm"] = S.init_ssm(ks[1], cfg, dtype=dtype)
        return p
    if kind == "cross":
        p["attn"] = L.init_attention(ks[1], cfg, dtype=dtype)
        p["ln2"] = L.init_norm(ks[2], cfg.d_model, kind=cfg.norm)
        p["mlp"] = L.init_mlp(ks[3], cfg.d_model, cfg.d_ff, act=cfg.act,
                              dtype=dtype)
        return p
    if kind in ("dense", "lead", "enc", "encdec_dec", "hybrid", "moe"):
        p["attn"] = (A.init_mla(ks[1], cfg, dtype=dtype) if cfg.is_mla
                     else L.init_attention(ks[1], cfg, dtype=dtype))
        p["ln2"] = L.init_norm(ks[2], cfg.d_model, kind=cfg.norm)
        if kind == "hybrid":
            p["ssm"] = S.init_ssm(ks[4], cfg, dtype=dtype)
            p["mlp"] = L.init_mlp(ks[3], cfg.d_model, cfg.d_ff, act=cfg.act,
                                  dtype=dtype)
        elif kind == "moe":
            p["moe"] = M.init_moe(ks[3], cfg, dtype=dtype)
            if cfg.dense_residual:
                p["res_mlp"] = L.init_mlp(
                    ks[5], cfg.d_model, cfg.residual_d_ff, act=cfg.act,
                    dtype=dtype)
        else:
            f = cfg.dense_d_ff if kind == "lead" else cfg.d_ff
            p["mlp"] = L.init_mlp(ks[3], cfg.d_model, f, act=cfg.act,
                                  dtype=dtype)
        if kind == "encdec_dec":
            p["ln_cross"] = L.init_norm(ks[6], cfg.d_model, kind=cfg.norm)
            p["cross"] = L.init_attention(ks[7], cfg, dtype=dtype)
        return p
    raise ValueError(kind)


def _mixer_fwd(p, h, cfg, ctx, *, kind, kv_ctx=None):
    """The token-mixing half of a block (h already normed)."""
    if kind == "ssm":
        return S.ssm_fwd(p["ssm"], h, cfg, scan_dtype=ctx.ssm_scan_dtype)
    if kind == "hybrid":
        a = L.attention_fwd(p["attn"], h, cfg, causal=True,
                            window=cfg.swa_window)
        s = S.ssm_fwd(p["ssm"], h, cfg, scan_dtype=ctx.ssm_scan_dtype)
        return 0.5 * (a + s)
    if kind == "cross":
        return L.attention_fwd(p["attn"], h, cfg, kv_x=kv_ctx, causal=False,
                               use_rope=False)
    if cfg.is_mla:
        with jax.named_scope("mla"):
            return A.mla_fwd(p["attn"], h, cfg)
    causal = kind != "enc"
    return L.attention_fwd(p["attn"], h, cfg, causal=causal,
                           window=cfg.swa_window,
                           use_rope=kind != "enc")


def _ffn_fwd(p, x, cfg, ctx, *, kind, stats=None):
    """The FFN half of a block; a MoE block puts its routing (``experts``)
    and the assignments it dropped (``dropped``) into ``stats``."""
    h = L.norm_apply(p["ln2"], x, kind=cfg.norm)
    if kind != "moe":
        return L.mlp_fwd(p["mlp"], h, act=cfg.act)
    b, s_len, d = h.shape
    aux: dict = {}
    if ctx.moe_step is not None and s_len == 1:
        # serving decode: the comm-scheduled per-step MoE exchange
        out, aux["experts"], aux["dropped"] = ctx.moe_step(p["moe"], h)
    else:
        g = min(ctx.moe_groups, b)
        hg = h.reshape(g, (b // g) * s_len, d)
        out = M.moe_fwd(p["moe"], hg, cfg, constrain=ctx.constrain,
                        aux=aux)
        out = out.reshape(b, s_len, d)
    if stats is not None:
        stats["experts"] = aux["experts"].reshape(
            b * s_len, cfg.experts_per_token)
        stats["dropped"] = aux["dropped"].astype(jnp.int32)
    if cfg.dense_residual:
        with jax.named_scope("moe.shared"):
            out = out + L.mlp_fwd(p["res_mlp"], h, act=cfg.act)
    return out


def _block_fwd(p, x, cfg, ctx, *, kind, kv_ctx=None):
    h = L.norm_apply(p["ln1"], x, kind=cfg.norm)
    x = x + ctx.c(_mixer_fwd(p, h, cfg, ctx, kind=kind, kv_ctx=kv_ctx), "act")
    if kind == "encdec_dec":
        hc = L.norm_apply(p["ln_cross"], x, kind=cfg.norm)
        x = x + L.attention_fwd(p["cross"], hc, cfg, kv_x=kv_ctx,
                                causal=False, use_rope=False)
    if kind != "ssm":
        x = x + ctx.c(_ffn_fwd(p, x, cfg, ctx, kind=kind), "act")
    return x


# ---------------------------------------------------------------------------
# decode-path blocks (single token, cache)
# ---------------------------------------------------------------------------

def _init_layer_cache(cfg, batch, cache_len, dtype, *, kind, cross_len=0,
                      per_slot=False):
    c: dict[str, Any] = {}
    if kind in ("dense", "moe", "hybrid", "encdec_dec", "cross"):
        hkv, hd = cfg.num_kv_heads, cfg.head_dim
        if kind != "cross":
            c["k"] = jnp.zeros((batch, cache_len, hkv, hd), dtype)
            c["v"] = jnp.zeros((batch, cache_len, hkv, hd), dtype)
            # per_slot: each batch lane advances independently (continuous
            # batching), so positions are tracked per lane too
            spos_shape = (batch, cache_len) if per_slot else (cache_len,)
            c["slot_pos"] = jnp.full(spos_shape, -1, jnp.int32)
        if kind in ("encdec_dec", "cross"):
            c["cross_k"] = jnp.zeros((batch, cross_len, hkv, hd), dtype)
            c["cross_v"] = jnp.zeros((batch, cross_len, hkv, hd), dtype)
    if kind in ("ssm", "hybrid"):
        c["ssm"] = S.init_ssm_cache(batch, cfg, dtype=dtype)
    return c


def _attn_decode(p, x, cfg, cache, pos, *, window=0):
    """x: (B, 1, D); ring-buffer KV cache with per-slot positions.

    ``pos`` scalar: every batch lane sits at the same position (the batch
    demo / the oracle scan) and ``slot_pos`` is shared ``(cache_len,)``.
    ``pos`` (B,): continuous-batching lanes at independent positions with
    per-lane ``slot_pos`` ``(B, cache_len)`` (``init_cache(per_slot=True)``).
    """
    b = x.shape[0]
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cache_len = cache["k"].shape[1]
    q = L.linear(p["wq"], x).reshape(b, 1, h, hd)
    k = L.linear(p["wk"], x).reshape(b, 1, hkv, hd)
    v = L.linear(p["wv"], x).reshape(b, 1, hkv, hd)
    positions = pos[None, None] if jnp.ndim(pos) == 0 else pos[:, None]
    q = L.rope(q, positions, theta=cfg.rope_theta)
    k = L.rope(k, positions, theta=cfg.rope_theta)

    slot = pos % cache_len  # ring slot (== pos when cache_len >= seq)
    if jnp.ndim(pos) == 0:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        spos = jax.lax.dynamic_update_slice(
            cache["slot_pos"], pos[None].astype(jnp.int32), (slot,))
        valid = (spos >= 0) & (spos <= pos)
        if window:
            valid &= spos > pos - window
        valid = valid[None, None, None, :]
    else:
        lane = jnp.arange(b)
        ck = cache["k"].at[lane, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[lane, slot].set(v[:, 0].astype(cache["v"].dtype))
        spos = cache["slot_pos"].at[lane, slot].set(pos.astype(jnp.int32))
        valid = (spos >= 0) & (spos <= pos[:, None])       # (B, cache_len)
        if window:
            valid &= spos > (pos - window)[:, None]
        valid = valid[:, None, None, :]
    d = hd
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    logits = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) * (d ** -0.5)
    logits = jnp.where(valid, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", w, cv.astype(jnp.float32))
    out = out.reshape(b, 1, h * hd).astype(x.dtype)
    y = L.linear(p["wo"], out)
    return y, {"k": ck, "v": cv, "slot_pos": spos}


def _attn_prefill(p, x, cfg, cache, pos, *, window=0):
    """x: (B, S, D) prompt chunk; writes positions [pos, pos+S) into the
    ring cache and attends causally over everything valid — the fused
    counterpart of S successive ``_attn_decode`` calls (same f32 einsum,
    same -1e30 masking, same softmax length over the full cache), so the
    two paths agree bit-for-bit as long as the chunk fits the ring
    (S <= cache_len: no slot is written twice within one call).

    ``pos`` scalar for a shared-position cache, (B,) for a per-slot cache
    (each lane prefills from its own start — the continuous-batching
    insert path).
    """
    b, s_len = x.shape[:2]
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cache_len = cache["k"].shape[1]
    q = L.linear(p["wq"], x).reshape(b, s_len, h, hd)
    k = L.linear(p["wk"], x).reshape(b, s_len, hkv, hd)
    v = L.linear(p["wv"], x).reshape(b, s_len, hkv, hd)
    offs = jnp.arange(s_len)
    per_slot = jnp.ndim(pos) == 1
    qpos = pos[:, None] + offs[None] if per_slot else pos + offs
    positions = qpos if per_slot else qpos[None]       # (B, S) | (1, S)
    q = L.rope(q, positions, theta=cfg.rope_theta)
    k = L.rope(k, positions, theta=cfg.rope_theta)

    slots = qpos % cache_len
    if per_slot:
        lane = jnp.arange(b)[:, None]
        ck = cache["k"].at[lane, slots].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[lane, slots].set(v.astype(cache["v"].dtype))
        spos = cache["slot_pos"].at[lane, slots].set(qpos.astype(jnp.int32))
        sp = spos                                      # (B, cache_len)
    else:
        ck = cache["k"].at[:, slots].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[:, slots].set(v.astype(cache["v"].dtype))
        spos = cache["slot_pos"].at[slots].set(qpos.astype(jnp.int32))
        sp = spos[None]                                # (1, cache_len)
    qp = qpos if per_slot else qpos[None]              # (B, S) | (1, S)
    valid = (sp[:, None, :] >= 0) & (sp[:, None, :] <= qp[..., None])
    if window:
        valid &= sp[:, None, :] > qp[..., None] - window
    g = h // hkv
    qg = q.reshape(b, s_len, hkv, g, hd)
    logits = jnp.einsum("bshgd,blhd->bhgsl", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) * (hd ** -0.5)
    logits = jnp.where(valid[:, None, None, :, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgsl,blhd->bshgd", w, cv.astype(jnp.float32))
    out = out.reshape(b, s_len, h * hd).astype(x.dtype)
    y = L.linear(p["wo"], out)
    return y, {"k": ck, "v": cv, "slot_pos": spos}


def _block_prefill(p, x, cfg, ctx, cache, pos, *, kind):
    """Prefill twin of ``_block_decode`` for attention stacks: identical
    residual/norm/FFN math (no training-path sharding constraints), S
    positions at once."""
    h = L.norm_apply(p["ln1"], x, kind=cfg.norm)
    a, kvc = _attn_prefill(p["attn"], h, cfg, cache, pos,
                           window=cfg.swa_window)
    new_cache = dict(cache)
    new_cache.update(kvc)
    x = x + a
    stats: dict = {}
    x = x + _ffn_fwd(p, x, cfg, ctx, kind=kind, stats=stats)
    return x, new_cache, stats


def _mla_block(p, x, cfg, ctx, cache, layer, pos, *, kind):
    """A block over S new tokens with MLA and the stacked latent cache
    (prefill chunk or decode step): attention writes layer ``layer`` of
    ``cache`` in place.  Returns (x, cache, stats)."""
    h = L.norm_apply(p["ln1"], x, kind=cfg.norm)
    with jax.named_scope("mla"):
        a, cache = A.mla_cached(p["attn"], h, cfg, cache, layer, pos)
    x = x + a
    stats: dict = {}
    x = x + _ffn_fwd(p, x, cfg, ctx, kind=kind, stats=stats)
    return x, cache, stats


def _cross_decode(p, x, cfg, cache):
    """Cross-attention against precomputed (cached) encoder/image KV."""
    b = x.shape[0]
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = L.linear(p["wq"], x).reshape(b, 1, h, hd)
    out = L.attention(q, cache["cross_k"], cache["cross_v"], causal=False)
    return L.linear(p["wo"], out.reshape(b, 1, h * hd))


def _block_decode(p, x, cfg, ctx, cache, pos, *, kind):
    """Returns (x, new_cache, stats): ``stats`` as ``_ffn_fwd`` fills it."""
    h = L.norm_apply(p["ln1"], x, kind=cfg.norm)
    new_cache = dict(cache)
    stats: dict = {}
    if kind == "ssm":
        y, new_cache["ssm"] = S.ssm_decode_step(p["ssm"], h, cache["ssm"], cfg)
        return x + y, new_cache, stats
    if kind == "hybrid":
        a, kvc = _attn_decode(p["attn"], h, cfg, cache, pos,
                              window=cfg.swa_window)
        s_out, new_cache["ssm"] = S.ssm_decode_step(
            p["ssm"], h, cache["ssm"], cfg)
        new_cache.update(kvc)
        x = x + 0.5 * (a + s_out)
    elif kind == "cross":
        x = x + _cross_decode(p["attn"], h, cfg, cache)
    else:
        a, kvc = _attn_decode(p["attn"], h, cfg, cache, pos,
                              window=cfg.swa_window)
        new_cache.update(kvc)
        x = x + a
        if kind == "encdec_dec":
            hc = L.norm_apply(p["ln_cross"], x, kind=cfg.norm)
            x = x + _cross_decode(p["cross"], hc, cfg, cache)
    x = x + _ffn_fwd(p, x, cfg, ctx, kind=kind, stats=stats)
    return x, new_cache, stats


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def _embed_fwd(p, tokens, cfg, ctx):
    w = p["w"]
    sharded = cfg.vocab_size % ctx.vocab_shards == 0 and ctx.vocab_shards > 1
    if cfg.embed_gather == "replicate" or not sharded:
        # naive: gather from a (conceptually) replicated table — also the
        # fallback when the vocab does not divide the model axis
        x = w.astype(ctx.act_dtype)[tokens]
        return x
    # onehot_psum: vocab-sharded table; the contraction over V turns the
    # irregular gather into a planned reduction (the condensed analogue).
    # Chunked over S under remat so the one-hot never materializes whole.
    b, s = tokens.shape
    chunk = min(512, s)
    if s % chunk:
        oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=ctx.act_dtype)
        return oh @ w.astype(ctx.act_dtype)
    nc = s // chunk
    ts = tokens.reshape(b, nc, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(_, tc):
        oh = jax.nn.one_hot(tc, cfg.vocab_size, dtype=ctx.act_dtype)
        return None, oh @ w.astype(ctx.act_dtype)

    _, xs = jax.lax.scan(body, None, ts)                 # (nc, B, C, D)
    return xs.swapaxes(0, 1).reshape(b, s, -1)


def lm_loss(logits, labels, mask=None):
    """Cross-entropy with vocab-sharded logits (one-hot contraction keeps
    the sharded dim out of gather ops)."""
    v = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    oh = jax.nn.one_hot(labels, v, dtype=jnp.float32)
    ll = (oh * lf).sum(-1)
    nll = lse - ll
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def fused_ce_loss(x, head, labels, *, chunk=512, constrain=None):
    """Memory-fused cross-entropy: the (B, S, V) logits tensor is never
    materialized — the head matmul + log-softmax run per sequence chunk
    under remat (the same "plan bulk movement, keep irregularity local"
    principle applied to the loss).  x: (B, S, D) post-norm hidden."""
    b, s, d = x.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    xs = x.reshape(b, nc, chunk, d).swapaxes(0, 1)       # (nc, B, C, D)
    ls = labels.reshape(b, nc, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(acc, args):
        xc, lc = args
        logits = xc @ head.astype(xc.dtype)              # (B, C, V)
        if constrain is not None:
            logits = constrain(logits, "logits")
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        oh = jax.nn.one_hot(lc, lf.shape[-1], dtype=jnp.float32)
        ll = (oh * lf).sum(-1)
        return acc + (lse - ll).sum(), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    return total / (b * s)


def _dropped(cache):
    """The cache's running count of dropped MoE assignments (a cache made
    without one counts from 0)."""
    return cache["moe"]["dropped"] if "moe" in cache else jnp.zeros(
        (), jnp.int32)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    """Family-dispatching model wrapper around the pure functions above."""

    def __init__(self, cfg, ctx: RunCtx | None = None):
        self.cfg = cfg
        self.ctx = ctx or RunCtx()
        self.kind = {
            "dense": "dense", "moe": "moe", "ssm": "ssm", "hybrid": "hybrid",
            "encdec": "encdec_dec", "vlm": "dense",
        }[cfg.family]

    # ---- init ----
    def init_params(self, key, dtype=jnp.float32):
        """f32 masters for training (cast to act_dtype in forward); a
        server passes its act_dtype and holds the weights once, in it."""
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        p: dict[str, Any] = {
            "embed": {"w": jax.random.normal(
                ks[0], (cfg.vocab_size, cfg.d_model), dtype) * 0.02},
            "final_norm": L.init_norm(ks[1], cfg.d_model, kind=cfg.norm),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = {"w": jax.random.normal(
                ks[2], (cfg.d_model, cfg.vocab_size), dtype)
                * cfg.d_model ** -0.5}

        if cfg.is_vlm and cfg.cross_attn_period:
            per = cfg.cross_attn_period
            groups = cfg.num_layers // per
            p["groups"] = {
                "self": _stack_init(
                    ks[3], groups,
                    lambda k: _stack_init(
                        k, per - 1,
                        lambda k2: _init_block(k2, cfg, dtype, kind="dense"))),
                "cross": _stack_init(
                    ks[4], groups,
                    lambda k: _init_block(k, cfg, dtype, kind="cross")),
            }
        else:
            lead = cfg.first_dense_layers
            if lead:
                p["lead_layers"] = _stack_init(
                    ks[7], lead,
                    lambda k: _init_block(k, cfg, dtype, kind="lead"))
            p["layers"] = _stack_init(
                ks[3], cfg.num_layers - lead,
                lambda k: _init_block(k, cfg, dtype, kind=self.kind))
        if cfg.is_encdec:
            p["encoder"] = {
                "layers": _stack_init(
                    ks[5], cfg.encoder_layers,
                    lambda k: _init_block(k, cfg, dtype, kind="enc")),
                "norm": L.init_norm(ks[6], cfg.d_model, kind=cfg.norm),
            }
        return p

    # ---- training forward ----
    def hidden(self, params, tokens, *, extra=None):
        """Post-final-norm hidden states (B, S, D)."""
        cfg, ctx = self.cfg, self.ctx
        x = ctx.c(_embed_fwd(params["embed"], tokens, cfg, ctx), "act")

        kv_ctx = None
        if cfg.is_encdec:
            kv_ctx = self._encode(params["encoder"], extra["frames"])
        if cfg.is_vlm:
            kv_ctx = extra["image_embeds"].astype(ctx.act_dtype)

        if ctx.cast_params_once and "layers" in params:
            params = dict(params)
            params["layers"] = jax.tree.map(
                lambda a: a.astype(ctx.act_dtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a,
                params["layers"])

        if "lead_layers" in params:
            body = _remat(functools.partial(
                self._scan_body, kind="lead", kv_ctx=kv_ctx), ctx.remat)
            x, _ = jax.lax.scan(body, x, params["lead_layers"])
        n_main = cfg.num_layers - cfg.first_dense_layers
        if cfg.is_vlm and cfg.cross_attn_period:
            x = self._vlm_stack(params["groups"], x, kv_ctx)
        elif ctx.remat_groups > 1 and n_main % ctx.remat_groups == 0:
            g = ctx.remat_groups
            per = n_main // g
            grouped = jax.tree.map(
                lambda a: a.reshape(g, per, *a.shape[1:]), params["layers"])

            def group_body(x, gp):
                def inner(x2, lp):
                    return self._scan_body(x2, lp, kv_ctx=kv_ctx)
                x, _ = jax.lax.scan(inner, x, gp)
                return x, None

            x, _ = jax.lax.scan(_remat(group_body, ctx.remat), x, grouped)
        else:
            body = _remat(
                functools.partial(self._scan_body, kv_ctx=kv_ctx), ctx.remat)
            x, _ = jax.lax.scan(body, x, params["layers"])

        return L.norm_apply(params["final_norm"], x, kind=cfg.norm)

    def head_weight(self, params):
        return (params["embed"]["w"].T if self.cfg.tie_embeddings
                else params["lm_head"]["w"])

    def forward(self, params, tokens, *, extra=None, last_only=False):
        """tokens: (B, S) int32.  extra: {"frames"|"image_embeds": (B,T,D)}.
        Returns logits (B, S, V) — or (B, 1, V) when ``last_only`` (prefill:
        the head matmul runs on the final position only)."""
        ctx = self.ctx
        x = self.hidden(params, tokens, extra=extra)
        if last_only:
            x = x[:, -1:, :]
        logits = x @ self.head_weight(params).astype(x.dtype)
        return ctx.c(logits, "logits")

    def loss(self, params, tokens, labels, *, extra=None, chunk=512):
        """Fused chunked cross-entropy (never materializes full logits)."""
        x = self.hidden(params, tokens, extra=extra)
        return fused_ce_loss(x, self.head_weight(params), labels,
                             chunk=chunk, constrain=self.ctx.constrain)

    def _scan_body(self, x, layer_p, *, kind=None, kv_ctx=None):
        if self.ctx.scan_barrier:
            x = jax.lax.optimization_barrier(x)
        return _block_fwd(layer_p, x, self.cfg, self.ctx,
                          kind=kind or self.kind, kv_ctx=kv_ctx), None

    def _vlm_stack(self, groups_p, x, kv_ctx):
        cfg, ctx = self.cfg, self.ctx

        def group_body(x, gp):
            def self_body(x2, lp):
                return _block_fwd(lp, x2, cfg, ctx, kind="dense"), None
            x, _ = jax.lax.scan(_remat(self_body, ctx.remat), x, gp["self"])
            x = _remat(
                lambda x3: _block_fwd(gp["cross"], x3, cfg, ctx,
                                      kind="cross", kv_ctx=kv_ctx),
                ctx.remat)(x)
            return x, None

        x, _ = jax.lax.scan(group_body, x, groups_p)
        return x

    def _encode(self, enc_p, frames):
        cfg, ctx = self.cfg, self.ctx
        x = frames.astype(ctx.act_dtype)

        def body(x, lp):
            return _block_fwd(lp, x, cfg, ctx, kind="enc"), None

        x, _ = jax.lax.scan(_remat(body, ctx.remat), x, enc_p["layers"])
        return L.norm_apply(enc_p["norm"], x, kind=cfg.norm)

    # ---- serving ----
    def init_cache(self, batch, cache_len, *, cross_len=0, dtype=jnp.bfloat16,
                   per_slot=False):
        """``per_slot=True`` builds a continuous-batching cache: ``pos``
        becomes (B,) and ``slot_pos`` (B, cache_len), so every batch lane
        (a serving *slot*) tracks its own sequence independently —
        ``decode_step`` / ``prefill`` dispatch on the pos rank.  Needs an
        attention-only stack (SSM recurrences carry no per-lane position)."""
        cfg = self.cfg
        if cfg.swa_window:
            cache_len = min(cache_len, cfg.swa_window)
        stats = ({"moe": {"experts": jnp.zeros(
            (cfg.num_layers - cfg.first_dense_layers, batch,
             cfg.experts_per_token), jnp.int32),
            "dropped": jnp.zeros((), jnp.int32)}} if cfg.is_moe else {})
        pos0 = (jnp.zeros((batch,), jnp.int32) if per_slot
                else jnp.zeros((), jnp.int32))
        if cfg.is_mla:
            # the latent cache of every layer, leading dense ones included,
            # stacked (L, B, T, ...); positions are always per lane
            one = A.init_mla_cache(cfg, batch, cache_len, dtype)
            layers = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.num_layers, *a.shape)),
                one)
            return {"pos": pos0, "layers": layers, **stats}
        if per_slot and self.kind not in ("dense", "moe"):
            raise NotImplementedError(
                "per-slot caches (continuous batching) need an "
                f"attention-only stack, got family {cfg.family!r}")

        def one(_):
            return _init_layer_cache(cfg, batch, cache_len, dtype,
                                     kind=self.kind, cross_len=cross_len,
                                     per_slot=per_slot)

        if cfg.is_vlm and cfg.cross_attn_period:
            per = cfg.cross_attn_period
            groups = cfg.num_layers // per
            layers = {
                "self": jax.vmap(lambda i: jax.vmap(one)(
                    jnp.arange(per - 1)))(jnp.arange(groups)),
                "cross": jax.vmap(
                    lambda i: _init_layer_cache(
                        cfg, batch, cache_len, dtype, kind="cross",
                        cross_len=cross_len))(jnp.arange(groups)),
            }
        else:
            layers = jax.vmap(one)(jnp.arange(cfg.num_layers))
        return {"pos": pos0, "layers": layers, **stats}

    def decode_step(self, params, cache, tokens):
        """tokens: (B, 1). Returns (logits (B, 1, V), new_cache)."""
        cfg, ctx = self.cfg, self.ctx
        x = ctx.c(_embed_fwd(params["embed"], tokens, cfg, ctx), "act")
        pos = cache["pos"]

        if cfg.is_mla:
            x, new_layers, stats = self._mla_stack(params, cache, x, pos)
        elif cfg.is_vlm and cfg.cross_attn_period:
            def group_body(x, args):
                gp, gc = args

                def self_body(x2, a2):
                    lp, lc = a2
                    y, nc, _ = _block_decode(lp, x2, cfg, ctx, lc, pos,
                                             kind="dense")
                    return y, nc
                x, nself = jax.lax.scan(
                    self_body, x, (gp["self"], gc["self"]))
                x, ncross, _ = _block_decode(gp["cross"], x, cfg, ctx,
                                             gc["cross"], pos, kind="cross")
                return x, {"self": nself, "cross": ncross}

            x, new_layers = jax.lax.scan(
                group_body, x, (params["groups"], cache["layers"]))
            stats = {}
        else:
            def body(x, args):
                lp, lc = args
                y, nc, st = _block_decode(lp, x, cfg, ctx, lc, pos,
                                          kind=self.kind)
                return y, (nc, st)

            x, (new_layers, stats) = jax.lax.scan(
                body, x, (params["layers"], cache["layers"]))

        x = L.norm_apply(params["final_norm"], x, kind=cfg.norm)
        head = (params["embed"]["w"].T if cfg.tie_embeddings
                else params["lm_head"]["w"])
        logits = ctx.c(x @ head.astype(x.dtype), "logits")
        new = {**cache, "pos": pos + 1, "layers": new_layers}
        if cfg.is_moe:
            new["moe"] = {"experts": stats["experts"],
                          "dropped": _dropped(cache) + stats["dropped"].sum()}
        return logits, new

    def _mla_stack(self, params, cache, x, pos):
        """The leading dense layers, then the scanned main stack, over S new
        tokens with the stacked latent cache carried (updated in place).
        Returns (x, cache layers, stats of the main stack)."""
        cfg, ctx = self.cfg, self.ctx
        lead = cfg.first_dense_layers

        def stack(kind):
            def body(carry, args):
                x, lc = carry
                lp, i = args
                x, lc, st = _mla_block(lp, x, cfg, ctx, lc, i, pos,
                                       kind=kind)
                return (x, lc), st
            return body

        layers = cache["layers"]
        if lead:
            (x, layers), _ = jax.lax.scan(
                stack("lead"), (x, layers),
                (params["lead_layers"], jnp.arange(lead)))
        (x, layers), stats = jax.lax.scan(
            stack(self.kind), (x, layers),
            (params["layers"], jnp.arange(lead, cfg.num_layers)))
        return x, layers, stats

    def prefill(self, params, cache, tokens):
        """Fused prompt prefill: one forward over ``tokens`` (B, S) that
        ALSO writes the prompt's K/V into the decode cache at positions
        [pos, pos+S) — the production path, replacing the sequential
        decode_step scan (kept as the oracle in ``launch.serve.prefill_into_cache``).

        Returns ``(last_logits (B, 1, V), new_cache)``; chunked prefill is
        consecutive calls, each advancing ``cache["pos"]`` by its chunk
        length.  Works on shared-position and per-slot caches; needs an
        attention-only stack (dense/moe) — other families prefill through
        the decode_step scan.  ``S <= cache_len`` (one ring lap per call).
        """
        cfg, ctx = self.cfg, self.ctx
        if self.kind not in ("dense", "moe"):
            raise NotImplementedError(
                "fused prefill supports attention-only stacks (dense/moe); "
                f"family {cfg.family!r} prefills via the decode_step scan")
        cache_len = cache["layers"]["slot_pos"].shape[-1]
        if tokens.shape[1] > cache_len:
            raise ValueError(
                f"prefill chunk ({tokens.shape[1]} tokens) exceeds the ring "
                f"cache ({cache_len} slots); chunk the prompt")
        x = ctx.c(_embed_fwd(params["embed"], tokens, cfg, ctx), "act")
        pos = cache["pos"]

        if cfg.is_mla:
            x, new_layers, stats = self._mla_stack(params, cache, x, pos)
        else:
            def body(x, args):
                lp, lc = args
                y, nc, st = _block_prefill(lp, x, cfg, ctx, lc, pos,
                                           kind=self.kind)
                return y, (nc, st)

            x, (new_layers, stats) = jax.lax.scan(
                body, x, (params["layers"], cache["layers"]))
        x = L.norm_apply(params["final_norm"], x, kind=cfg.norm)
        x = x[:, -1:, :]
        logits = ctx.c(x @ self.head_weight(params).astype(x.dtype), "logits")
        new = {**cache, "pos": pos + tokens.shape[1], "layers": new_layers}
        if cfg.is_moe:
            # the routing of each lane's last prompt token
            b, s_len = tokens.shape
            experts = stats["experts"].reshape(-1, b, s_len,
                                               cfg.experts_per_token)
            new["moe"] = {"experts": experts[:, :, -1],
                          "dropped": _dropped(cache) + stats["dropped"].sum()}
        return logits, new

    def prefill_cross(self, params, cache, context):
        """Fill cross-attention KV from encoder output / image embeds."""
        cfg = self.cfg
        if cfg.is_encdec:
            enc = self._encode(params["encoder"], context)

            def fill(lp, lc):
                b = enc.shape[0]
                hkv, hd = cfg.num_kv_heads, cfg.head_dim
                k = L.linear(lp["cross"]["wk"], enc).reshape(b, -1, hkv, hd)
                v = L.linear(lp["cross"]["wv"], enc).reshape(b, -1, hkv, hd)
                lc = dict(lc)
                lc["cross_k"] = k.astype(lc["cross_k"].dtype)
                lc["cross_v"] = v.astype(lc["cross_v"].dtype)
                return lc

            new_layers = jax.vmap(fill)(params["layers"], cache["layers"])
            return {**cache, "layers": new_layers}
        if cfg.is_vlm:
            ctx_e = context.astype(self.ctx.act_dtype)

            def fill(gp, gc):
                b = ctx_e.shape[0]
                hkv, hd = cfg.num_kv_heads, cfg.head_dim
                k = L.linear(gp["cross"]["attn"]["wk"], ctx_e).reshape(
                    b, -1, hkv, hd)
                v = L.linear(gp["cross"]["attn"]["wv"], ctx_e).reshape(
                    b, -1, hkv, hd)
                gc = dict(gc)
                cc = dict(gc["cross"])
                cc["cross_k"] = k.astype(cc["cross_k"].dtype)
                cc["cross_v"] = v.astype(cc["cross_v"].dtype)
                gc["cross"] = cc
                return gc

            new_layers = jax.vmap(fill)(params["groups"], cache["layers"])
            return {**cache, "layers": new_layers}
        return cache
