"""Pallas kernels for the exchange fast path (paper Listing 5, both loops,
both directions).

Once messages are condensed, what remains of the communication cost is the
local pack/unpack around one exchange.  These kernels keep the irregular
side of it in VMEM:

* ``pack_gather``        — ``out[k] = x[idx[k]]``: extract the condensed
  message values from the owned shard into a contiguous send buffer.
* ``unpack_dest``        — the Destination-targeted unpack: deliver the
  landed recv buffer straight into the consumer's named slots, fusing the
  foreign gather, the owned gather and the mask combine of
  ``strategies.dest_gather_local`` into one pass over the L slots.
* ``unpack_scatter_set`` — the full-materialization unpack: scatter the
  landed messages into a fresh x_copy and (optionally) copy the owned
  shard in — the gather direction's eq.-14/15 fused.
* ``accumulate_segments`` / ``accumulate_into`` — the put direction's
  segment-combine under ``reduce="add"|"set"|"max"``.
  ``accumulate_segments`` starts from the reduce identity (the pack-side
  message combine and the own-target accumulate); ``accumulate_into``
  continues from a prior accumulator (the landed-foreign combine of the
  push-side split).

Shape of every kernel: the array that is addressed irregularly (the shard,
the recv buffer, the accumulator) stays whole in VMEM; the index table is
streamed through SMEM in blocks of ``block`` entries, with the values or
outputs that run alongside it; one ``fori_loop`` per block moves one item
(``kernels.layout``) per index.  An array that cannot stay resident raises
``VmemBudgetError``.

Results equal ``kernels.ref``: gathers and sets move bits, and the
accumulate kernels combine in index order on one sequential grid — the
order XLA's own scatter uses off-TPU, so kernel and jnp rungs agree bit
for bit there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import (
    LANES, check_resident, combine_item, compiler_params, from_items,
    interpret_mode, load_item, store_item, to_items,
)

__all__ = [
    "pack_gather", "unpack_dest", "unpack_scatter_set",
    "accumulate_segments", "accumulate_into", "reduce_identity",
]

# index entries per grid step: a few SMEM buffers of this size stay far
# inside v5e's 1 MiB of SMEM; wide feature rows take fewer per step, so a
# double-buffered value block stays near BLOCK_BYTES
BLOCK = 4096
BLOCK_BYTES = 1 << 20


def reduce_identity(dtype, reduce: str):
    """The reduce identity padded lanes carry, as a host scalar a kernel
    can bake in (mirrors ``strategies._reduce_identity`` — duplicated so
    the kernel layer never imports comm machinery)."""
    if reduce != "max":
        return 0
    if jnp.issubdtype(dtype, jnp.floating):
        return -np.inf
    return int(np.iinfo(dtype).min)


def _widen(a):
    """Kernels compute on 32-bit items; widening is exact."""
    a = jnp.asarray(a)
    if a.dtype.itemsize >= 4:
        return a
    if jnp.issubdtype(a.dtype, jnp.floating):
        return a.astype(jnp.float32)
    return a.astype(jnp.int32)


def _is_scalar(feat) -> bool:
    return int(np.prod(feat, dtype=np.int64)) == 1


def _grid(m: int, block: int | None, feat):
    f = int(np.prod(feat, dtype=np.int64))
    row_bytes = 4 if f == 1 else -(-f // LANES) * LANES * 4
    cap = max(8, BLOCK_BYTES // row_bytes // 8 * 8)
    block = max(1, min(block or BLOCK, cap, m))
    return block, max(1, -(-m // block))


def _index_blocks(a, block: int, nb: int, dtype=jnp.int32):
    """(m,) -> (nb, 1, block) SMEM blocks, zero-padded."""
    a = jnp.asarray(a).astype(dtype).reshape(-1)
    a = jnp.pad(a, (0, nb * block - a.shape[0]))
    return a.reshape(nb, 1, block)


def _block_shape(block: int, nb: int, feat):
    """(nb, block/L, L) lane-dense for scalar items, (nb, block, F)."""
    if _is_scalar(feat):
        lanes = LANES if block % LANES == 0 else block
        return (nb, block // lanes, lanes)
    return (nb, block, int(np.prod(feat, dtype=np.int64)))


def _value_blocks(v, block: int, nb: int):
    """(m, *feat) -> its ``_block_shape`` view, zero-padded."""
    m = v.shape[0]
    flat = jnp.pad(v.reshape(m, -1), ((0, nb * block - m), (0, 0)))
    return flat.reshape(_block_shape(block, nb, v.shape[1:]))


def _idx_spec(block):
    return pl.BlockSpec((None, 1, block), lambda i: (i, 0, 0),
                        memory_space=pltpu.SMEM)


def _blocked_spec(shape):
    return pl.BlockSpec((None,) + tuple(shape[1:]), lambda i: (i, 0, 0))


_RESIDENT = pl.BlockSpec(memory_space=pltpu.VMEM)


def _resident(name, *arrays):
    check_resident(name, *((a.shape[0], a.shape[1:], a.dtype.itemsize)
                           for a in arrays))


# --------------------------------------------------------------------------
# Pack (paper Listing 5 pack loop)
# --------------------------------------------------------------------------

def _pack_kernel(idx_ref, x_ref, out_ref, *, scalar):
    def body(k, carry):
        store_item(out_ref, k, load_item(x_ref, idx_ref[0, k], scalar),
                   scalar)
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[1], body, 0)


def pack_gather(x, idx, *, block: int | None = None,
                interpret: bool | None = None) -> jax.Array:
    """out[k] = x[idx[k]] for x ``(shard, *feat)`` resident in VMEM.

    Any message count works: the index table is padded to a block multiple
    (padding gathers item 0, sliced off)."""
    x, idx = jnp.asarray(x), jnp.asarray(idx)
    m, feat, dtype = idx.shape[0], x.shape[1:], x.dtype
    if m == 0:
        return jnp.zeros((0,) + feat, dtype)
    _resident("pack_gather", x)
    scalar = _is_scalar(feat)
    block, nb = _grid(m, block, feat)
    out_shape = _block_shape(block, nb, feat)
    out = pl.pallas_call(
        functools.partial(_pack_kernel, scalar=scalar),
        grid=(nb,),
        in_specs=[_idx_spec(block), _RESIDENT],
        out_specs=_blocked_spec(out_shape),
        out_shape=jax.ShapeDtypeStruct(out_shape, _widen(x).dtype),
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret_mode() if interpret is None else interpret,
    )(_index_blocks(idx, block, nb), to_items(_widen(x)))
    return from_items(out, m, feat).astype(dtype)


# --------------------------------------------------------------------------
# Destination-targeted unpack (fused strategies.dest_gather_local)
# --------------------------------------------------------------------------

def _dest_kernel(src_ref, own_ref, own_m_ref, rem_m_ref, recv_ref, x_ref,
                 out_ref, *, scalar):
    def body(k, carry):
        rem = load_item(recv_ref, src_ref[0, k], scalar)
        own = load_item(x_ref, own_ref[0, k], scalar)
        store_item(out_ref, k, rem * rem_m_ref[0, k] + own * own_m_ref[0, k],
                   scalar)
        return carry

    jax.lax.fori_loop(0, src_ref.shape[1], body, 0)


def unpack_dest(recv_flat, x_local, src_idx, own_idx, own_mask, rem_mask,
                *, block: int | None = None,
                interpret: bool | None = None) -> jax.Array:
    """Deliver landed values straight into the L named consumer slots.

    Each slot reads the recv buffer (foreign), the owned shard, or exactly
    0.0 (both masks 0) — ``recv * rem_mask + own * own_mask``, as the jnp
    path computes it; the full-length x_copy is never built.  Recv buffer
    and shard are resident; the slot axis streams in blocks.
    """
    recv_flat, x_local = jnp.asarray(recv_flat), jnp.asarray(x_local)
    L, feat, dtype = src_idx.shape[0], x_local.shape[1:], x_local.dtype
    if L == 0:
        return jnp.zeros((0,) + feat, dtype)
    if recv_flat.shape[0] == 0:
        recv_flat = jnp.zeros((1,) + feat, dtype)
    _resident("unpack_dest", recv_flat, x_local)
    scalar = _is_scalar(feat)
    block, nb = _grid(L, block, feat)
    x32 = _widen(x_local)
    out_shape = _block_shape(block, nb, feat)
    out = pl.pallas_call(
        functools.partial(_dest_kernel, scalar=scalar),
        grid=(nb,),
        in_specs=[_idx_spec(block)] * 4 + [_RESIDENT, _RESIDENT],
        out_specs=_blocked_spec(out_shape),
        out_shape=jax.ShapeDtypeStruct(out_shape, x32.dtype),
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret_mode() if interpret is None else interpret,
    )(_index_blocks(src_idx, block, nb), _index_blocks(own_idx, block, nb),
      _index_blocks(own_mask, block, nb, x32.dtype),
      _index_blocks(rem_mask, block, nb, x32.dtype),
      to_items(_widen(recv_flat).astype(x32.dtype)), to_items(x32))
    return from_items(out, L, feat).astype(dtype)


# --------------------------------------------------------------------------
# Full-materialization unpack (fused eq. 14 own-copy + eq. 15 scatter)
# --------------------------------------------------------------------------

def _unpack_set_kernel(idx_ref, recv_ref, off_ref, x_ref, out_ref, *,
                       scalar, total, block, rows_own, copy_own):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def land(k, carry):
        store_item(out_ref, idx_ref[0, k], load_item(recv_ref, k, scalar),
                   scalar)
        return carry

    jax.lax.fori_loop(0, jnp.minimum(block, total - i * block), land, 0)

    if copy_own:
        @pl.when(i == pl.num_programs(0) - 1)
        def _():
            off = off_ref[0]

            def own(r, carry):
                store_item(out_ref, off + r, load_item(x_ref, r, scalar),
                           scalar)
                return carry

            jax.lax.fori_loop(0, rows_own, own, 0)


def unpack_scatter_set(recv, idx, x_own, offset, *, out_len: int,
                       copy_own: bool = True,
                       interpret: bool | None = None) -> jax.Array:
    """x_copy = zeros((out_len,) + rest); x_copy[idx] = recv; then the
    eq.-14 own-shard copy at ``offset`` — the condensed/blockwise full
    unpack as ONE kernel (rows are whole virtual blocks for blockwise).

    Landing runs in index order and the own copy runs last, as in the jnp
    path, so duplicate dump-row writes and the own/recv overlap resolve
    identically.  The assembled copy and the owned rows are resident.
    """
    recv, x_own = jnp.asarray(recv), jnp.asarray(x_own)
    rest, dtype = x_own.shape[1:], x_own.dtype
    total = recv.shape[0]
    if total == 0:
        recv = jnp.zeros((1,) + rest, dtype)
    _resident("unpack_scatter_set", x_own,
              jax.ShapeDtypeStruct((out_len,) + rest, dtype))
    scalar = _is_scalar(rest)
    block, nb = _grid(max(total, 1), None, rest)
    x32 = _widen(x_own)
    out_items = to_items(jnp.zeros((out_len,) + rest, x32.dtype))
    off = jnp.asarray(offset, jnp.int32).reshape((1,))
    out = pl.pallas_call(
        functools.partial(_unpack_set_kernel, scalar=scalar, total=total,
                          block=block, rows_own=x_own.shape[0],
                          copy_own=copy_own),
        grid=(nb,),
        in_specs=[_idx_spec(block),
                  _blocked_spec(_block_shape(block, nb, rest)),
                  pl.BlockSpec(memory_space=pltpu.SMEM), _RESIDENT],
        out_specs=_RESIDENT,
        out_shape=jax.ShapeDtypeStruct(out_items.shape, x32.dtype),
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret_mode() if interpret is None else interpret,
    )(_index_blocks(idx, block, nb),
      _value_blocks(_widen(recv).astype(x32.dtype), block, nb), off,
      to_items(x32))
    return from_items(out, out_len, rest).astype(dtype)


# --------------------------------------------------------------------------
# Segment accumulate (put direction: pack-combine and accumulate-unpack)
# --------------------------------------------------------------------------

def _accumulate_kernel(idx_ref, vals_ref, *refs, scalar, total, block,
                       reduce, identity, round_to, from_init):
    if from_init:
        init_ref, out_ref, sem = refs
    else:
        (out_ref,) = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        if from_init:
            cp = pltpu.make_async_copy(init_ref, out_ref, sem)
            cp.start()
            cp.wait()
        else:
            out_ref[...] = jnp.full(out_ref.shape, identity, out_ref.dtype)

    def body(k, carry):
        combine_item(out_ref, idx_ref[0, k], load_item(vals_ref, k, scalar),
                     scalar, reduce, round_to)
        return carry

    jax.lax.fori_loop(0, jnp.minimum(block, total - i * block), body, 0)


def _accumulate(vals, idx, init, out_len, reduce, interpret):
    vals = jnp.asarray(vals)
    rest, dtype = vals.shape[1:], vals.dtype
    total = vals.shape[0]
    if total == 0:
        vals = jnp.zeros((1,) + rest, dtype)
    _resident("accumulate_segments" if init is None else "accumulate_into",
              jax.ShapeDtypeStruct((out_len,) + rest, dtype))
    scalar = _is_scalar(rest)
    block, nb = _grid(max(total, 1), None, rest)
    v32 = _widen(vals)
    identity = reduce_identity(v32.dtype, reduce)
    out_items = to_items(jnp.zeros((out_len,) + rest, v32.dtype))
    args = [_index_blocks(idx, block, nb), _value_blocks(v32, block, nb)]
    in_specs = [_idx_spec(block), _blocked_spec(args[1].shape)]
    scratch = []
    if init is not None:
        args.append(to_items(_widen(init).astype(v32.dtype)))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch = [pltpu.SemaphoreType.DMA(())]
    out = pl.pallas_call(
        functools.partial(
            _accumulate_kernel, scalar=scalar, total=total, block=block,
            reduce=reduce, identity=identity,
            round_to=None if dtype == v32.dtype else dtype,
            from_init=init is not None),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=_RESIDENT,
        out_shape=jax.ShapeDtypeStruct(out_items.shape, v32.dtype),
        scratch_shapes=scratch,
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret_mode() if interpret is None else interpret,
    )(*args)
    return from_items(out, out_len, rest).astype(dtype)


def accumulate_segments(vals, idx, *, out_len: int, reduce: str = "add",
                        interpret: bool | None = None) -> jax.Array:
    """acc = full((out_len,) + rest, identity); combine vals at idx.

    The put direction's segment-combine: the sender-side message pack
    (12ᵀ), the own-target accumulate (the half of 15ᵀ that needs no landed
    data — issue it while the all_to_all flies), and the blockwise block
    combine are all this kernel at different ``out_len``.  ``reduce`` set
    semantics are realized by the caller pre-masking (the plan's winner
    mask), exactly like the jnp path.
    """
    return _accumulate(vals, idx, None, out_len, reduce, interpret)


def accumulate_into(init, vals, idx, *, reduce: str = "add",
                    interpret: bool | None = None) -> jax.Array:
    """Combine ``vals`` into an existing accumulator (the landed-foreign
    half of the push-side split: takes the own-accumulate kernel's output,
    which the scheduler computed while the collective was in flight)."""
    init = jnp.asarray(init)
    return _accumulate(vals, idx, init, init.shape[0], reduce, interpret)
