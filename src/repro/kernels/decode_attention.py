"""Pallas TPU kernel for single-token (decode) GQA attention.

Flash-decoding schedule: the sequential TPU grid walks KV-cache chunks for
one query token, carrying running (max, sum, accumulator) in VMEM scratch —
the KV cache streams HBM→VMEM exactly once, and the softmax never
materializes (the decode-step hot-spot: decode_32k cells are KV-read-bound,
see EXPERIMENTS.md §Roofline).

Grid: (B, n_kv_chunks); the chunk axis is innermost (sequential on TPU), so
scratch persists across chunks of the same batch element.  Validity of cache
slots is passed as a per-batch length (scalar prefetch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import interpret_mode

__all__ = ["decode_attention"]


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, kv_chunk: int, nchunks: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # (Hkv, G, D)
    k = k_ref[0].astype(jnp.float32)          # (C, Hkv, D)
    v = v_ref[0].astype(jnp.float32)          # (C, Hkv, D)

    logits = jnp.einsum("hgd,chd->hgc", q, k) * scale    # (Hkv, G, C)
    pos = j * kv_chunk + jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 2)
    valid = pos < len_ref[b]
    logits = jnp.where(valid, logits, -1e30)

    m_prev = m_scr[...]                        # (Hkv, G)
    m_new = jnp.maximum(m_prev, logits.max(axis=-1))
    p = jnp.exp(logits - m_new[..., None])     # (Hkv, G, C)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
    acc_scr[...] = acc_scr[...] * corr[..., None] + jnp.einsum(
        "hgc,chd->hgd", p, v)
    m_scr[...] = m_new

    @pl.when(j == nchunks - 1)
    def _finish():
        norm = jnp.maximum(l_scr[...], 1e-30)[..., None]
        o_ref[0] = (acc_scr[...] / norm).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,        # (B, H, D) single query token
    k: jax.Array,        # (B, S, Hkv, D) KV cache
    v: jax.Array,        # (B, S, Hkv, D)
    lengths: jax.Array,  # (B,) int32 valid cache length per batch elem
    *,
    kv_chunk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    b, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    kv_chunk = min(kv_chunk, s)
    assert s % kv_chunk == 0
    nchunks = s // kv_chunk
    qg = q.reshape(b, hkv, g, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nchunks),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), lambda i, j, L: (i, 0, 0, 0)),
            pl.BlockSpec((1, kv_chunk, hkv, d), lambda i, j, L: (i, j, 0, 0)),
            pl.BlockSpec((1, kv_chunk, hkv, d), lambda i, j, L: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d), lambda i, j, L: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
    )
    kern = functools.partial(_kernel, kv_chunk=kv_chunk, nchunks=nchunks,
                             scale=d ** -0.5)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret_mode() if interpret is None else interpret,
    )(lengths.astype(jnp.int32), qg, k, v)
    return out.reshape(b, h, d)
