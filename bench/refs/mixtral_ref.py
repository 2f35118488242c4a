"""The Mixtral yardstick: seeded weights, the plain float32 reference forward
pass, and the float8 control.

The weights are made here, on the device, in one jitted call from the seed,
in bfloat16 (the type they are served in), with the shapes of the program's
parameter tree.  The reference reads them by name and follows the published
Mixtral decoder (arXiv:2401.04088, HF ``MixtralForCausalLM``): RMSNorm
(eps 1e-5), rotary embeddings over half-split pairs (theta from the
config), grouped-query causal attention, a softmax router whose top-2
weights are renormalized, SwiGLU experts, a final RMSNorm and an untied LM
head.  It runs in float32 at the highest matmul precision, over one whole
sequence at a time, with no cache, batching or capacity.
"""
from __future__ import annotations

import functools

import numpy as np

from bench.common import key32

FP8_MAX = 448.0            # largest finite float8_e4m3fn


def make_params(abstract, seed: int):
    """Weights with the structure and shapes of ``abstract`` (a pytree of
    ``ShapeDtypeStruct``), in bfloat16: norm scales are ones, the embedding
    is standard normal, every other matrix normal / sqrt(fan-in) with the
    fan-in its second-to-last axis.  Stacked leaves are drawn one slab at a
    time (``lax.map``), so the random bits of one slab are the scratch."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def draw(key, path, leaf):
        names = [getattr(k, "key", str(k)) for k in path]
        shape, dtype = leaf.shape, jnp.bfloat16
        if names[-1] == "scale":
            return jnp.ones(shape, dtype)
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


def fp8_weight(w):
    """float8 e4m3 with one scale per output channel (the last axis):
    the weight-only quantization a later change would be tempted by."""
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True),
                    1e-30) / FP8_MAX
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps=1e-5):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (T, H, D); rotary embedding over (first half, second half)."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(cfg: dict, params, tokens, *, quantize=False):
    """Logits (T, V) in float32 of one sequence ``tokens`` (T,).  With
    ``quantize`` every weight matrix goes through ``fp8_weight`` first
    (the control)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
    wf = fp8_weight if quantize else (lambda w: w.astype(jnp.float32))

    def mm(a, w):
        return jnp.matmul(a, wf(w), precision=hi)

    d = cfg["hidden_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // nh
    g = nh // nkv
    k_top = cfg["num_experts_per_tok"]
    theta = float(cfg["rope_theta"])
    eps = float(cfg["rms_norm_eps"])
    t = tokens.shape[0]
    pos = jnp.arange(t)
    causal = pos[:, None] >= pos[None, :]

    x = params["embed"]["w"][tokens].astype(jnp.float32)
    lay = params["layers"]
    for i in range(cfg["num_hidden_layers"]):
        # the small leaves of layer i; the expert stacks are read one
        # expert's slab at a time below, never copied whole
        p = jax.tree.map(lambda a: a[i], {k: v for k, v in lay.items()
                                          if k != "moe"})
        moe = lay["moe"]
        h = _rms(x, p["ln1"]["scale"], eps)
        q = _rope(mm(h, p["attn"]["wq"]["w"]).reshape(t, nh, hd), pos, theta)
        k = _rope(mm(h, p["attn"]["wk"]["w"]).reshape(t, nkv, hd), pos,
                  theta)
        v = mm(h, p["attn"]["wv"]["w"]).reshape(t, nkv, hd)

        def head_group(j):
            qj = lax.dynamic_slice_in_dim(q, j * g, g, axis=1)  # (T, g, D)
            kj, vj = k[:, j], v[:, j]                          # (T, D)
            s = jnp.einsum("tgd,sd->gts", qj, kj, precision=hi) * hd ** -0.5
            s = jnp.where(causal[None], s, -jnp.inf)
            w = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("gts,sd->tgd", w, vj, precision=hi)

        att = lax.map(head_group, jnp.arange(nkv))             # (KV,T,g,D)
        att = att.transpose(1, 0, 2, 3).reshape(t, nh * hd)
        x = x + mm(att, p["attn"]["wo"]["w"])

        h = _rms(x, p["ln2"]["scale"], eps)
        probs = jax.nn.softmax(mm(h, moe["router"]["w"][i]), axis=-1)
        top_p, top_e = lax.top_k(probs, k_top)
        top_p = top_p / top_p.sum(-1, keepdims=True)
        n_exp = probs.shape[-1]
        gate = jnp.zeros_like(probs).at[
            jnp.arange(t)[:, None], top_e].set(top_p)          # (T, E)

        def expert(acc, e):
            w1, w2, w3 = (moe[n][i, e] for n in ("w1", "w2", "w3"))
            y = mm(jax.nn.silu(mm(h, w1)) * mm(h, w3), w2)
            return acc + gate[:, e][:, None] * y, None

        out, _ = lax.scan(expert, jnp.zeros_like(x), jnp.arange(n_exp))
        x = x + out
    x = _rms(x, params["final_norm"]["scale"], eps)
    return mm(x, params["lm_head"]["w"])


@functools.lru_cache(maxsize=4)
def compiled_forward(cfg_items: tuple, quantize: bool):
    import jax
    cfg = dict(cfg_items)
    return jax.jit(functools.partial(forward, cfg, quantize=quantize))


def served_gaps(logits, prompt_len: int, served) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at that position.  Token i is predicted at position
    prompt_len - 1 + i."""
    logits = np.asarray(logits, np.float64)
    rows = logits[prompt_len - 1:prompt_len - 1 + len(served)]
    best = rows.max(axis=1)
    return best - rows[np.arange(len(served)), np.asarray(served)]
