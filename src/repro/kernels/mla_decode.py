"""Pallas TPU kernel for one decode step of absorbed multi-head latent
attention over the stacked latent cache (flash-decoding form).

Each lane attends, with all heads at once, over the latent rows of one
layer of the cache: scores ``q_lat . c_kv + q_pe . k_pe`` and the weighted
sum of ``c_kv`` under an online softmax (running max, sum and an
``(H, R)`` accumulator), so no logits reach HBM and every row is read
once.

*The block walk.*  Lane ``b`` at query position ``qpos[b]`` visits the
first ``ceil(min(qpos[b] + 1, T) / blk)`` blocks of its ring of ``T``
slots: before the ring wraps its positions ``0 .. qpos[b]`` sit in slots
``0 .. qpos[b]``, and once it has wrapped every slot is valid.  Inside a
visited block the mask is the einsum form's (``0 <= slot_pos <= qpos``),
so stale rows of a reused lane stay out.  Slots past the visited blocks
hold positions above ``qpos[b]`` or none, which that mask would drop too.
A cache length that ``blk`` does not divide ends in a block clamped to
``[T - blk, T)``, whose rows already seen are masked.

*The stacked cache in place.*  ``ckv`` (L, B, T, R) and ``kpe``
(L, B, P, T) stay in HBM (``pl.ANY``): the layer index comes in as a
scalar-prefetch operand and the kernel copies each visited block to VMEM
itself, two blocks in flight, across lane boundaries too.  Handing the
kernel ``cache[...][layer]`` would make XLA copy that slice (1.07 GB of
c_kv a layer at Moonlight's sizes).  The layer's ``slot_pos`` (B, T) comes
whole into VMEM through a block index map on the same scalar: a lane's
row of it cannot be copied alone, being narrower than a tile.

The kernel runs once per call (one grid step): the lanes and their blocks
are a loop inside it.  Its matmul operands take the cache's dtype and
accumulate in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import (check_resident, compiler_params,
                                  interpret_mode)

__all__ = ["BLOCK", "block_rows", "rows_read", "resident_arrays",
           "mla_decode_attention"]

BLOCK = 512      # latent rows a visited block holds (fewer if T is shorter)


def block_rows(cache_len: int) -> int:
    """Rows per block for a ring of ``cache_len`` slots."""
    return min(BLOCK, cache_len)


def rows_read(qpos, cache_len: int) -> int:
    """Latent rows one layer of the kernel reads for lanes at query
    positions ``qpos`` (host numpy): whole visited blocks."""
    blk = block_rows(cache_len)
    n = np.clip(np.asarray(qpos, np.int64) + 1, 1, cache_len)
    return int((-(-n // blk)).sum() * blk)


def resident_arrays(lanes, heads, rank, rope, cache_len, blk, itemsize):
    """What the kernel keeps in VMEM, as ``layout.check_resident``'s
    ``(items, feat, itemsize)`` triples: the queries, the layer's slot
    positions (two buffers), the output, the two block buffers of c_kv
    and k_pe, and the softmax state."""
    return [(lanes * heads, (rank,), itemsize),
            (lanes * heads, (rope,), itemsize),
            (2 * lanes, (cache_len,), 4),
            (lanes * heads, (rank,), 4),
            (2 * blk, (rank,), itemsize),
            (2 * rope, (blk,), itemsize),
            (heads, (rank,), 4),
            (2 * heads, (), 4)]


def _kernel(layer_ref, qpos_ref, q_ref, qpe_ref, sp_ref, ckv_hbm, kpe_hbm,
            o_ref, ckv_buf, kpe_buf, sem, m_scr, l_scr, acc_scr, *,
            blk: int, cache_len: int, scale: float):
    lanes = q_ref.shape[0]
    layer = layer_ref[0]
    aligned = blk % 128 == 0 and cache_len % 128 == 0

    def nblocks(b):
        n = jnp.clip(qpos_ref[b] + 1, 1, cache_len)
        return (n + blk - 1) // blk

    def start_of(j):
        start = jnp.minimum(j * blk, cache_len - blk)
        return pl.multiple_of(start, 128) if aligned else start

    def copies(b, j, slot):
        start = start_of(j)
        return (
            pltpu.make_async_copy(ckv_hbm.at[layer, b, pl.ds(start, blk)],
                                  ckv_buf.at[slot], sem.at[0, slot]),
            pltpu.make_async_copy(kpe_hbm.at[layer, b, :, pl.ds(start, blk)],
                                  kpe_buf.at[slot], sem.at[1, slot]))

    def lane(b, slot):
        n = nblocks(b)
        q, qpe, qpos = q_ref[b], qpe_ref[b], qpos_ref[b]
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        def block(j, slot):
            more = j + 1 < n
            nb = jnp.where(more, b, b + 1)

            @pl.when(nb < lanes)
            def _():
                for c in copies(nb, jnp.where(more, j + 1, 0), 1 - slot):
                    c.start()

            for c in copies(b, j, slot):
                c.wait()
            c_kv = ckv_buf[slot]                                # (blk, R)
            s = jax.lax.dot_general(
                q, c_kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = s + jnp.dot(qpe, kpe_buf[slot],
                            preferred_element_type=jnp.float32)
            s = s * scale                                       # (H, blk)
            start = start_of(j)
            slot_idx = start + jax.lax.broadcasted_iota(jnp.int32, (1, blk),
                                                        1)
            sp = sp_ref[pl.ds(b, 1), pl.ds(start, blk)]         # (1, blk)
            valid = (slot_idx >= j * blk) & (sp >= 0) & (sp <= qpos)
            s = jnp.where(valid, s, -1e30)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[...] = corr * l_scr[...] + p.sum(axis=-1, keepdims=True)
            acc_scr[...] = corr * acc_scr[...] + jnp.dot(
                p.astype(c_kv.dtype), c_kv,
                preferred_element_type=jnp.float32)
            m_scr[...] = m_new
            return 1 - slot

        slot = jax.lax.fori_loop(0, n, block, slot)
        o_ref[b] = acc_scr[...] / l_scr[...]
        return slot

    for c in copies(0, 0, 0):
        c.start()
    jax.lax.fori_loop(0, lanes, lane, 0)


def mla_decode_attention(q_lat, q_pe, ckv, kpe, slot_pos, layer, qpos, *,
                         scale: float, interpret: bool | None = None):
    """Latent output (B, H, R) float32 of one query a lane.

    ``q_lat`` (B, H, R) and ``q_pe`` (B, H, P) are the absorbed and rotary
    query parts; ``ckv`` (L, B, T, R), ``kpe`` (L, B, P, T) and
    ``slot_pos`` (L, B, T) the stacked cache, read at layer ``layer`` (a
    traced scalar) for lanes at query positions ``qpos`` (B,)."""
    b, h, r = q_lat.shape
    p = q_pe.shape[-1]
    t = ckv.shape[2]
    blk = block_rows(t)
    dt = ckv.dtype
    check_resident("mla_decode_attention", *resident_arrays(
        b, h, r, p, t, blk, jnp.dtype(dt).itemsize))
    kern = functools.partial(_kernel, blk=blk, cache_len=t, scale=scale)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # the layer's slot positions, (B, T) int32, whole in VMEM
    spos = pl.BlockSpec((None, b, t), lambda i, layer, qpos: (layer[0], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(1,),
        in_specs=[vmem, vmem, spos, hbm, hbm],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((2, blk, r), dt),
            pltpu.VMEM((2, p, blk), dt),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, r), jnp.float32),
        ])
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret_mode() if interpret is None else interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), qpos.astype(jnp.int32),
      q_lat.astype(dt), q_pe.astype(dt), slot_pos, ckv, kpe)
