"""The paper's communication-strategy ladder, as shard_map-local gathers.

Each strategy turns a sharded vector ``x`` (one contiguous shard per device on
the communication mesh axis) into a device-private copy ``x_copy`` — the
paper's ``mythread_x_copy`` — that the local computation then indexes with
*global* indices (the paper stresses that retaining global indices is what
keeps UPCv3 easier than MPI; we retain them too).

All functions here are *local* functions: they must be called inside a
``shard_map`` over ``axis_name`` (a mesh axis name, or a tuple of axis names
to gather over their product — e.g. Heat2D's 2D process grid).  They return
an array whose leading dimension is >= n with the first n entries valid;
entries at index >= n are a padding dump.  ``x`` may carry trailing feature
dimensions (e.g. token embeddings of width d): every strategy moves whole
feature rows.

Strategies (paper §4):
  * ``replicate`` — naive: all-gather the whole vector (volume n per device).
  * ``blockwise`` — UPCv2: move whole virtual blocks that contain >=1 needed
    element, via a padded block all_to_all (volume = needed blocks × BS).
  * ``condensed`` — UPCv3: pack exactly the unique needed values, one padded
    message per pair, single all_to_all, scatter-unpack (volume = Σ unique).
  * ``overlap``   — beyond paper: same condensed exchange, but the consumer
    splits its compute so the own-shard partial runs while the all_to_all is
    in flight (see ``comm.gather.OverlapHandle``); as a pure gather it is
    identical to ``condensed``.

The ``*_start_local`` / ``*_finish_local`` pairs split each strategy at its
collective so ``OverlapHandle`` can expose an own-compute window between the
two (XLA's latency-hiding scheduler overlaps anything scheduled in between
that has no data dependency on the collective's result).

When the plan carries a ``Destination`` descriptor (``plan.dest_len > 0``),
each strategy additionally exposes a *targeted* finish: the landed recv
buffer is gathered straight into the consumer's flat slot buffer (length
``dest_len``) — O(slots + recv) work instead of the O(n) zeros+scatter that
assembling ``x_copy`` costs.  The assembled full copy remains available via
``finish(..., materialize="full")``.

Every function here puts its ops under one of three ``jax.named_scope``
names, so a profiler trace (and the ``op_name`` of each compiled
instruction) says which side of the wire an op belongs to: ``comm.pack``
(the send side: gather into messages, sender-side combine),
``comm.exchange`` (the collective and the reshapes around it) and
``comm.unpack`` (the receive side: scatter or gather into the consumer's
layout, accumulate).  The scopes change op metadata only.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.plan import CommPlan, ScatterPlan

__all__ = [
    "STRATEGIES",
    "SCATTER_REDUCES",
    "replicate_gather_local",
    "blockwise_gather_local",
    "condensed_gather_local",
    "dest_gather_local",
    "dest_slot_kinds",
    "plan_device_args",
    "gather_in_specs",
    "make_gather_local",
    "make_start_local",
    "replicate_scatter_local",
    "blockwise_scatter_local",
    "condensed_scatter_local",
    "scatter_plan_device_args",
    "scatter_in_specs",
    "make_scatter_start_local",
]


PACK, EXCHANGE, UNPACK = "comm.pack", "comm.exchange", "comm.unpack"


def _my_shard(axis_name) -> jax.Array:
    """Linear shard index on the comm axis (handles tuple axis names)."""
    return jax.lax.axis_index(axis_name)


def replicate_gather_local(x_local: jax.Array, *, axis_name: str) -> jax.Array:
    """Naive strategy: materialize the entire shared vector on every device."""
    with jax.named_scope(EXCHANGE):
        return jax.lax.all_gather(x_local, axis_name, tiled=True)


def condensed_start_local(
    x_local: jax.Array,
    send_local_idx: jax.Array,   # (1, P, s_max) local slice of plan array
    *,
    axis_name: str,
) -> jax.Array:
    """UPCv3 pack + consolidated exchange (paper Listing 5 pack loop +
    ``upc_memput``/``upc_barrier``).  Returns the landed (P, s_max, ...) recv
    buffer, not yet unpacked."""
    with jax.named_scope(PACK):
        buf = x_local[send_local_idx[0]]                  # (P, s_max, ...)
    with jax.named_scope(EXCHANGE):
        return jax.lax.all_to_all(                        # memput + barrier
            buf, axis_name, split_axis=0, concat_axis=0, tiled=True
        )


def condensed_finish_local(
    recv: jax.Array,
    x_local: jax.Array,
    recv_global_idx: jax.Array,  # (1, P, s_max)
    *,
    axis_name: str,
    n: int,
    shard_size: int,
    extra_slots: int = 0,
    copy_own: bool = True,
) -> jax.Array:
    """UPCv3 unpack: scatter the landed messages into x_copy.

    Slot ``n`` is the recv padding dump (holds garbage); slots
    ``n+1 .. n+extra_slots`` are guaranteed zero (consumers use them as the
    padding target of their own index tables)."""
    feat = x_local.shape[1:]
    with jax.named_scope(UNPACK):
        x_copy = jnp.zeros((n + 1 + extra_slots,) + feat, x_local.dtype)
        x_copy = x_copy.at[recv_global_idx[0].ravel()].set(
            recv.reshape((-1,) + feat))
        if copy_own:
            me = _my_shard(axis_name)
            # copy own shard (paper: memcpy of own blocks into
            # mythread_x_copy)
            x_copy = jax.lax.dynamic_update_slice(
                x_copy, x_local, (me * shard_size,) + (0,) * len(feat))
        return x_copy


def condensed_gather_local(
    x_local: jax.Array,
    send_local_idx: jax.Array,   # (1, P, s_max) local slice of plan array
    recv_global_idx: jax.Array,  # (1, P, s_max)
    *,
    axis_name: str,
    n: int,
    shard_size: int,
) -> jax.Array:
    """UPCv3: pack -> one consolidated message per pair -> unpack.

    The pack loop (paper Listing 5) is the gather ``x_local[send_idx]``; the
    ``upc_memput`` + ``upc_barrier`` pair is the bulk-synchronous
    ``all_to_all``; the unpack loop is the scatter into ``x_copy``.  Padding
    lands in the dump slot at index n.
    """
    recv = condensed_start_local(x_local, send_local_idx, axis_name=axis_name)
    return condensed_finish_local(
        recv, x_local, recv_global_idx,
        axis_name=axis_name, n=n, shard_size=shard_size,
    )


def blockwise_start_local(
    x_local: jax.Array,
    send_local_blk: jax.Array,   # (1, P, b_max)
    *,
    axis_name: str,
    shard_size: int,
    blocksize: int,
) -> jax.Array:
    """UPCv2 block exchange.  Returns the landed (P, b_max, BS, ...) blocks."""
    feat = x_local.shape[1:]
    blocks_per_shard = shard_size // blocksize
    with jax.named_scope(PACK):
        xb = x_local.reshape((blocks_per_shard, blocksize) + feat)
        buf = xb[send_local_blk[0]]                        # (P, b_max, BS, ..)
    with jax.named_scope(EXCHANGE):
        return jax.lax.all_to_all(
            buf, axis_name, split_axis=0, concat_axis=0, tiled=True
        )


def blockwise_finish_local(
    recv: jax.Array,
    x_local: jax.Array,
    recv_global_blk: jax.Array,  # (1, P, b_max)
    *,
    axis_name: str,
    n: int,
    shard_size: int,
    blocksize: int,
    extra_slots: int = 0,
    copy_own: bool = True,
) -> jax.Array:
    """UPCv2 unpack: scatter whole landed blocks into x_copy.

    With ``extra_slots`` the dump block is remapped past the zero-guaranteed
    region so slots ``n+1 .. n+extra_slots`` stay zero (requires
    ``extra_slots < blocksize``)."""
    feat = x_local.shape[1:]
    nblks = n // blocksize
    if extra_slots:
        assert extra_slots < blocksize, (
            "zero-slot region must fit inside one virtual block")
    with jax.named_scope(UNPACK):
        blk_idx = recv_global_blk[0].ravel()
        if extra_slots:
            # dump block nblks would cover slots [n, n+BS); remap it one
            # block further so [n, n+BS) — including the zero slots — is
            # never written
            blk_idx = jnp.where(blk_idx == nblks, nblks + 1, blk_idx)
            x_blocks = jnp.zeros((nblks + 2, blocksize) + feat,
                                 x_local.dtype)
        else:
            x_blocks = jnp.zeros((nblks + 1, blocksize) + feat,
                                 x_local.dtype)
        x_blocks = x_blocks.at[blk_idx].set(
            recv.reshape((-1, blocksize) + feat))
        x_copy = x_blocks.reshape((-1,) + feat)
        if copy_own:
            me = _my_shard(axis_name)
            x_copy = jax.lax.dynamic_update_slice(
                x_copy, x_local, (me * shard_size,) + (0,) * len(feat))
        return x_copy


def dest_gather_local(
    recv_flat: jax.Array,   # (R, ...) flattened landed recv buffer
    x_local: jax.Array,     # (shard, ...)
    src_idx: jax.Array,     # (L,) position in recv_flat of each foreign slot
    own_idx: jax.Array,     # (L,) position in x_local of each owned slot
    own_mask: jax.Array,    # (L,) int8: 1 where the slot is owned
    rem_mask: jax.Array,    # (L,) int8: 1 where the slot is foreign
    *,
    has_own: bool = True,
    has_foreign: bool = True,
    has_zero: bool = True,
) -> jax.Array:
    """Consumer-targeted unpack: deliver values straight into the L named
    slots.  Each slot is exactly one of {owned, foreign, zero}: owned slots
    gather from ``x_local``, foreign slots from the landed recv buffer, and
    zero slots (both masks 0) read exactly 0.0.  All operands are O(L) or
    O(recv) — the full-length x_copy is never built.

    ``has_own`` / ``has_foreign`` / ``has_zero`` are static facts of the
    plan (``dest_slot_kinds``): whether any device has a slot of that kind.
    Only the gathers and selects those slots need are compiled — a
    destination without owned slots reads no ``x_local``, one of foreign
    slots only is a single gather.  With owned and foreign slots both
    present the program is the same whatever ``has_zero`` says."""
    feat = x_local.shape[1:]

    def bmask(mask):
        return mask.reshape(mask.shape + (1,) * len(feat)) != 0

    # every index is in bounds by construction (masked slots read 0);
    # promising it spares XLA an O(L) normalize-and-clamp pass per gather
    def take(a, idx):
        return a.at[idx].get(mode="promise_in_bounds")

    with jax.named_scope(UNPACK):
        zero = jnp.zeros((), x_local.dtype)
        if has_own and has_foreign:
            return jnp.where(bmask(rem_mask), take(recv_flat, src_idx),
                             jnp.where(bmask(own_mask),
                                       take(x_local, own_idx), zero))
        if has_foreign:
            mask, out = rem_mask, take(recv_flat, src_idx)
        elif has_own:
            mask, out = own_mask, take(x_local, own_idx)
        else:
            return jnp.zeros(src_idx.shape + feat, x_local.dtype)
        return jnp.where(bmask(mask), out, zero) if has_zero else out


def dest_slot_kinds(plan: CommPlan) -> dict[str, bool]:
    """Whether any device's ``Destination`` slot is owned, foreign or zero
    (the static flags of ``dest_gather_local``)."""
    own = plan.dest_own_mask != 0
    rem = plan.dest_rem_mask != 0
    return {"has_own": bool(own.any()), "has_foreign": bool(rem.any()),
            "has_zero": bool((~(own | rem)).any())}


def blockwise_gather_local(
    x_local: jax.Array,
    send_local_blk: jax.Array,   # (1, P, b_max)
    recv_global_blk: jax.Array,  # (1, P, b_max)
    *,
    axis_name: str,
    n: int,
    shard_size: int,
    blocksize: int,
) -> jax.Array:
    """UPCv2: move whole needed virtual blocks (upc_memget analogue).

    Every needed block travels in its entirety regardless of how many of its
    elements are actually used — exactly the paper's trade-off: fewer, larger,
    latency-amortizing transfers at the price of extra volume.
    """
    recv = blockwise_start_local(
        x_local, send_local_blk,
        axis_name=axis_name, shard_size=shard_size, blocksize=blocksize)
    return blockwise_finish_local(
        recv, x_local, recv_global_blk,
        axis_name=axis_name, n=n, shard_size=shard_size, blocksize=blocksize,
    )


def plan_device_args(plan: CommPlan, strategy: str,
                     with_dest: bool = False) -> tuple[Any, ...]:
    """Host (numpy) plan arrays each strategy needs, to be passed through
    shard_map with ``gather_in_specs`` so every device holds only its slice.

    ``with_dest=True`` (requires a plan built with a ``Destination``)
    appends the four targeted-unpack arrays: the strategy's recv-buffer
    source index, the own-shard index, and the owned/foreign masks.
    """
    if strategy == "replicate":
        base = ()
    elif strategy in ("condensed", "overlap"):
        base = (plan.send_local_idx, plan.recv_global_idx)
    elif strategy == "blockwise":
        base = (plan.send_local_blk, plan.recv_global_blk)
    else:
        raise ValueError(f"unknown strategy {strategy!r}")
    if not with_dest:
        return base
    assert plan.dest_own_idx is not None, (
        "plan has no Destination; build it with destination=")
    src = {"replicate": plan.dest_global_idx,
           "blockwise": plan.dest_blk_src}.get(strategy, plan.dest_cond_src)
    return base + (src, plan.dest_own_idx, plan.dest_own_mask,
                   plan.dest_rem_mask)


def gather_in_specs(strategy: str, axis_name, with_dest: bool = False):
    """PartitionSpecs matching ``plan_device_args`` (sharded on dim 0)."""
    p = jax.sharding.PartitionSpec
    base = () if strategy == "replicate" else (p(axis_name), p(axis_name))
    if with_dest:
        base = base + (p(axis_name),) * 4
    return base


def make_gather_local(plan: CommPlan, strategy: str, axis_name):
    """Returns local_fn(x_local, *plan_args) -> x_copy (len >= n)."""
    if strategy == "replicate":
        return functools.partial(replicate_gather_local, axis_name=axis_name)
    if strategy in ("condensed", "overlap"):
        return functools.partial(
            condensed_gather_local,
            axis_name=axis_name,
            n=plan.n,
            shard_size=plan.shard_size,
        )
    if strategy == "blockwise":
        return functools.partial(
            blockwise_gather_local,
            axis_name=axis_name,
            n=plan.n,
            shard_size=plan.shard_size,
            blocksize=plan.blocksize,
        )
    raise ValueError(f"unknown strategy {strategy!r}")


def make_start_local(plan: CommPlan, strategy: str, axis_name, *,
                     use_kernel: bool = False):
    """Returns (start_fn, finish_fn) splitting the strategy at its collective.

    ``start_fn(x_local, *plan_args) -> in_flight``; ``finish_fn(in_flight,
    x_local, *plan_args, extra_slots=..., copy_own=..., materialize=...)``.
    Between the two calls the consumer runs compute that depends only on
    ``x_local`` — the generalized own/foreign window of the ``overlap`` rung.

    When the plan args carry the four targeted-unpack arrays (built via
    ``plan_device_args(plan, strategy, with_dest=True)``), ``finish``
    honors ``materialize``: ``"full"`` assembles the classic x_copy (len >=
    n); ``"dest"`` returns the flat ``(dest_len, ...)`` consumer-slot buffer
    with no full-length intermediate.  Without a destination only
    ``"full"`` is available.

    ``use_kernel=True`` swaps the jnp pack/unpack around the (unchanged)
    collective for the fused Pallas kernels in ``repro.kernels`` — one HBM
    pass per element on each side of the wire, bit-identical to the jnp
    path (the kernels execute the same op sequence; see
    kernels/pack_gather.py).  Replicate has no pack side, so only its
    targeted unpack kernelizes.
    """
    if use_kernel:
        return _make_kernel_start_local(plan, strategy, axis_name)
    kinds = dest_slot_kinds(plan) if plan.dest_len else {}

    def unpack_dest(recv_flat, x_local, dest):
        src, own_idx, own_mask, rem_mask = dest
        return dest_gather_local(recv_flat, x_local, src[0], own_idx[0],
                                 own_mask[0], rem_mask[0], **kinds)

    if strategy == "replicate":
        def start(x_local, *args):
            return replicate_gather_local(x_local, axis_name=axis_name)

        def finish(recv, x_local, *args, extra_slots=0, copy_own=True,
                   materialize="full"):
            if materialize == "dest":
                return unpack_dest(recv, x_local, args)
            if extra_slots:
                feat = x_local.shape[1:]
                with jax.named_scope(UNPACK):
                    pad = jnp.zeros((1 + extra_slots,) + feat, x_local.dtype)
                    return jnp.concatenate([recv, pad], axis=0)
            return recv

        return start, finish
    if strategy in ("condensed", "overlap"):
        def start(x_local, send_idx, recv_idx, *dest):
            return condensed_start_local(
                x_local, send_idx, axis_name=axis_name)

        def finish(recv, x_local, send_idx, recv_idx, *dest, extra_slots=0,
                   copy_own=True, materialize="full"):
            if materialize == "dest":
                feat = x_local.shape[1:]
                return unpack_dest(recv.reshape((-1,) + feat), x_local, dest)
            return condensed_finish_local(
                recv, x_local, recv_idx, axis_name=axis_name, n=plan.n,
                shard_size=plan.shard_size, extra_slots=extra_slots,
                copy_own=copy_own)

        return start, finish
    if strategy == "blockwise":
        def start(x_local, send_blk, recv_blk, *dest):
            return blockwise_start_local(
                x_local, send_blk, axis_name=axis_name,
                shard_size=plan.shard_size, blocksize=plan.blocksize)

        def finish(recv, x_local, send_blk, recv_blk, *dest, extra_slots=0,
                   copy_own=True, materialize="full"):
            if materialize == "dest":
                feat = x_local.shape[1:]
                return unpack_dest(recv.reshape((-1,) + feat), x_local, dest)
            return blockwise_finish_local(
                recv, x_local, recv_blk, axis_name=axis_name, n=plan.n,
                shard_size=plan.shard_size, blocksize=plan.blocksize,
                extra_slots=extra_slots, copy_own=copy_own)

        return start, finish
    raise ValueError(f"unknown strategy {strategy!r}")


def _make_kernel_start_local(plan: CommPlan, strategy: str, axis_name):
    """Kernelized (start, finish) pair: fused Pallas pack / unpack around
    the same collective (the ``use_kernel=True`` arm of
    ``make_start_local``).

    Pack = ``kernels.pack_gather`` (Listing 5's pack loop, shard
    VMEM-resident); full finish = ``kernels.unpack_scatter_set`` (eq.-15
    scatter + eq.-14 own memcpy in one pass); dest finish =
    ``kernels.unpack_dest`` (the fused ``dest_gather_local``).  Blockwise
    rides the same kernels with whole virtual blocks as the unit rows.
    """
    from repro.kernels import ops as kops  # deferred: kernels never import comm

    def unpack_dest(recv_flat, x_local, dest):
        src, own_idx, own_mask, rem_mask = dest
        with jax.named_scope(UNPACK):
            return kops.unpack_dest(recv_flat, x_local, src[0], own_idx[0],
                                    own_mask[0], rem_mask[0])

    if strategy == "replicate":
        def start(x_local, *args):
            return replicate_gather_local(x_local, axis_name=axis_name)

        def finish(recv, x_local, *args, extra_slots=0, copy_own=True,
                   materialize="full"):
            if materialize == "dest":
                return unpack_dest(recv, x_local, args)
            if extra_slots:
                feat = x_local.shape[1:]
                with jax.named_scope(UNPACK):
                    pad = jnp.zeros((1 + extra_slots,) + feat, x_local.dtype)
                    return jnp.concatenate([recv, pad], axis=0)
            return recv

        return start, finish
    if strategy in ("condensed", "overlap"):
        def start(x_local, send_idx, recv_idx, *dest):
            feat = x_local.shape[1:]
            p, s_max = send_idx.shape[1], send_idx.shape[2]
            with jax.named_scope(PACK):
                buf = kops.pack_gather(x_local, send_idx[0].reshape(-1))
            with jax.named_scope(EXCHANGE):
                return jax.lax.all_to_all(
                    buf.reshape((p, s_max) + feat), axis_name,
                    split_axis=0, concat_axis=0, tiled=True)

        def finish(recv, x_local, send_idx, recv_idx, *dest, extra_slots=0,
                   copy_own=True, materialize="full"):
            feat = x_local.shape[1:]
            if materialize == "dest":
                return unpack_dest(recv.reshape((-1,) + feat), x_local, dest)
            with jax.named_scope(UNPACK):
                me = _my_shard(axis_name)
                return kops.unpack_scatter_set(
                    recv.reshape((-1,) + feat), recv_idx[0].ravel(), x_local,
                    me * plan.shard_size, out_len=plan.n + 1 + extra_slots,
                    copy_own=copy_own)

        return start, finish
    if strategy == "blockwise":
        blocksize = plan.blocksize
        blocks_per_shard = plan.shard_size // blocksize
        nblks = plan.n // blocksize

        def start(x_local, send_blk, recv_blk, *dest):
            feat = x_local.shape[1:]
            p, b_max = send_blk.shape[1], send_blk.shape[2]
            with jax.named_scope(PACK):
                xb = x_local.reshape((blocks_per_shard, blocksize) + feat)
                buf = kops.pack_gather(xb, send_blk[0].reshape(-1))
            with jax.named_scope(EXCHANGE):
                return jax.lax.all_to_all(
                    buf.reshape((p, b_max, blocksize) + feat), axis_name,
                    split_axis=0, concat_axis=0, tiled=True)

        def finish(recv, x_local, send_blk, recv_blk, *dest, extra_slots=0,
                   copy_own=True, materialize="full"):
            feat = x_local.shape[1:]
            if materialize == "dest":
                return unpack_dest(recv.reshape((-1,) + feat), x_local, dest)
            if extra_slots:
                assert extra_slots < blocksize, (
                    "zero-slot region must fit inside one virtual block")
            with jax.named_scope(UNPACK):
                blk_idx = recv_blk[0].ravel()
                if extra_slots:
                    blk_idx = jnp.where(blk_idx == nblks, nblks + 1, blk_idx)
                    out_blocks = nblks + 2
                else:
                    out_blocks = nblks + 1
                me = _my_shard(axis_name)
                # own copy lands at flat offset me*shard_size == block row
                # me*blocks_per_shard — block-aligned, so the block-unit
                # kernel writes the exact same elements as the flat jnp
                # update
                x_blocks = kops.unpack_scatter_set(
                    recv.reshape((-1, blocksize) + feat), blk_idx,
                    x_local.reshape((blocks_per_shard, blocksize) + feat),
                    me * blocks_per_shard, out_len=out_blocks,
                    copy_own=copy_own)
                return x_blocks.reshape((-1,) + feat)

        return start, finish
    raise ValueError(f"unknown strategy {strategy!r}")


STRATEGIES = ("replicate", "blockwise", "condensed", "overlap")

# --------------------------------------------------------------------------
# Push direction (put / scatter): the same rung ladder, roles swapped.
#
# Each scatter strategy turns a sharded table of *contributions* ``vals``
# ((rows_per_shard, r) per device, optional trailing feature dims; slot
# (i, j) contributes to global element ``tgt_global[i, j]``) into each
# device's combined owned slice ``y_local`` (shard_size, ...).  Duplicate
# targets combine under ``reduce``:
#
#   * "add" — y[t] = sum of contributions (0 where none);
#   * "max" — y[t] = max of contributions (0 where none; the -inf identity
#     is masked out by the plan's static ``touched`` table);
#   * "set" — y[t] = the last contribution in row-major accessor order
#     (0 where none).  Implemented as "add" with the plan's precomputed
#     winner mask zeroing every non-winning slot, so it is deterministic
#     and rides the identical collective on every rung.
#
# The pack side combines duplicates *before* the wire (sender-side
# condensing); padded message lanes carry the reduce identity, so the
# receiver's accumulate treats them as no-ops without any masking.
# --------------------------------------------------------------------------

SCATTER_REDUCES = ("add", "set", "max")


def _reduce_identity(dtype, reduce: str):
    if reduce == "max":
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.array(-jnp.inf, dtype)
        return jnp.array(jnp.iinfo(dtype).min, dtype)
    return jnp.array(0, dtype)


def _accumulate(acc: jax.Array, idx: jax.Array, vals: jax.Array,
                reduce: str) -> jax.Array:
    """Combine ``vals`` into ``acc`` at ``idx`` under the reduce semantic."""
    if reduce == "max":
        return acc.at[idx].max(vals)
    return acc.at[idx].add(vals)


def _apply_set_mask(vals: jax.Array, win_mask: jax.Array,
                    reduce: str) -> jax.Array:
    if reduce != "set":
        return vals
    feat = vals.shape[2:]
    return vals * win_mask.reshape(win_mask.shape + (1,) * len(feat)).astype(
        vals.dtype)


def _mask_untouched(y: jax.Array, touched: jax.Array,
                    reduce: str) -> jax.Array:
    """reduce="max" leaves the -inf identity on never-written elements;
    the static touched table replaces it with the documented 0."""
    if reduce != "max":
        return y
    feat = y.shape[1:]
    return jnp.where(
        touched.reshape(touched.shape + (1,) * len(feat)) > 0, y,
        jnp.zeros((), y.dtype))


def replicate_scatter_local(
    vals: jax.Array,       # (rows, r, ...) contributions
    tgt: jax.Array,        # (rows, r) global targets
    win_mask: jax.Array,   # (rows, r) int8
    touched: jax.Array,    # (1, shard_size) int8
    *,
    axis_name,
    n: int,
    shard_size: int,
    reduce: str,
) -> jax.Array:
    """Naive put: every device combines ALL its contributions into a private
    full-length accumulator, then a whole-vector cross-device reduction
    (psum / pmax) delivers each owner its slice — the push dual of the
    replicate all-gather, O(n) volume per device."""
    feat = vals.shape[2:]
    with jax.named_scope(PACK):
        vals = _apply_set_mask(vals, win_mask, reduce)
        acc = jnp.full((n,) + feat, _reduce_identity(vals.dtype, reduce),
                       vals.dtype)
        acc = _accumulate(acc, tgt.ravel(), vals.reshape((-1,) + feat),
                          reduce)
    return _owned_slice(_all_reduce(acc, axis_name, reduce), touched,
                        axis_name=axis_name, shard_size=shard_size,
                        reduce=reduce)


def _all_reduce(acc: jax.Array, axis_name, reduce: str) -> jax.Array:
    """The replicate put's whole-vector cross-device combine."""
    with jax.named_scope(EXCHANGE):
        if reduce == "max":
            return jax.lax.pmax(acc, axis_name)
        return jax.lax.psum(acc, axis_name)


def _owned_slice(y_full: jax.Array, touched: jax.Array, *, axis_name,
                 shard_size: int, reduce: str) -> jax.Array:
    """Each owner's slice of the combined vector."""
    with jax.named_scope(UNPACK):
        me = _my_shard(axis_name)
        y = jax.lax.dynamic_slice_in_dim(y_full, me * shard_size,
                                         shard_size, 0)
        return _mask_untouched(y, touched[0], reduce)


def condensed_scatter_start_local(
    vals: jax.Array,
    cond_msg_idx: jax.Array,   # (rows, r) flat pos in (P*s_max); own -> dump
    win_mask: jax.Array,
    *,
    axis_name,
    p: int,
    s_max: int,
    reduce: str,
) -> jax.Array:
    """UPCv3 put: sender-side segment-combine into one padded message per
    (sender, receiver) pair, then the consolidated exchange (the transpose
    of the gather's pack + ``upc_memput``).  Returns the landed (P, s_max,
    ...) contribution buffer, not yet accumulated."""
    feat = vals.shape[2:]
    with jax.named_scope(PACK):
        vals = _apply_set_mask(vals, win_mask, reduce)
        buf = jnp.full((p * s_max + 1,) + feat,
                       _reduce_identity(vals.dtype, reduce), vals.dtype)
        buf = _accumulate(buf, cond_msg_idx.ravel(),
                          vals.reshape((-1,) + feat), reduce)
    with jax.named_scope(EXCHANGE):
        return jax.lax.all_to_all(
            buf[:p * s_max].reshape((p, s_max) + feat), axis_name,
            split_axis=0, concat_axis=0, tiled=True)


def condensed_scatter_finish_local(
    recv: jax.Array,
    vals: jax.Array,
    unpack_idx: jax.Array,   # (1, P, s_max) = base send_local_idx, swapped
    own_idx: jax.Array,      # (rows, r) local target; foreign -> shard_size
    win_mask: jax.Array,
    touched: jax.Array,
    *,
    shard_size: int,
    reduce: str,
) -> jax.Array:
    """Accumulate-unpack: landed foreign contributions combine into the
    owned slice at the gather's pack positions (send/recv tables swap
    roles); own contributions combine directly, never touching the wire.
    Padded lanes carry the reduce identity, so no masking is needed."""
    feat = vals.shape[2:]
    with jax.named_scope(UNPACK):
        vals = _apply_set_mask(vals, win_mask, reduce)
        acc = jnp.full((shard_size + 1,) + feat,
                       _reduce_identity(vals.dtype, reduce), vals.dtype)
        acc = _accumulate(acc, own_idx.ravel(), vals.reshape((-1,) + feat),
                          reduce)
        acc = _accumulate(acc, unpack_idx[0].ravel(),
                          recv.reshape((-1,) + feat), reduce)
        return _mask_untouched(acc[:shard_size], touched[0], reduce)


def condensed_scatter_local(vals, cond_msg_idx, unpack_idx, own_idx,
                            win_mask, touched, *, axis_name, p, s_max,
                            shard_size, reduce):
    recv = condensed_scatter_start_local(
        vals, cond_msg_idx, win_mask, axis_name=axis_name, p=p, s_max=s_max,
        reduce=reduce)
    return condensed_scatter_finish_local(
        recv, vals, unpack_idx, own_idx, win_mask, touched,
        shard_size=shard_size, reduce=reduce)


def blockwise_scatter_start_local(
    vals: jax.Array,
    blk_msg_idx: jax.Array,   # (rows, r) flat pos in (P*b_max*BS)
    win_mask: jax.Array,
    *,
    axis_name,
    p: int,
    b_max: int,
    blocksize: int,
    reduce: str,
) -> jax.Array:
    """UPCv2 put: contributions combine into whole virtual blocks (only
    blocks containing >= 1 target travel); one padded block all_to_all.
    Returns the landed (P, b_max, BS, ...) blocks."""
    feat = vals.shape[2:]
    with jax.named_scope(PACK):
        vals = _apply_set_mask(vals, win_mask, reduce)
        buf = jnp.full((p * b_max * blocksize + 1,) + feat,
                       _reduce_identity(vals.dtype, reduce), vals.dtype)
        buf = _accumulate(buf, blk_msg_idx.ravel(),
                          vals.reshape((-1,) + feat), reduce)
    with jax.named_scope(EXCHANGE):
        return jax.lax.all_to_all(
            buf[:p * b_max * blocksize].reshape(
                (p, b_max * blocksize) + feat),
            axis_name, split_axis=0, concat_axis=0, tiled=True)


def blockwise_scatter_finish_local(
    recv: jax.Array,
    vals: jax.Array,
    unpack_blk: jax.Array,   # (1, P, b_max) = base send_local_blk, swapped
    own_idx: jax.Array,
    win_mask: jax.Array,
    touched: jax.Array,
    *,
    shard_size: int,
    blocksize: int,
    reduce: str,
) -> jax.Array:
    feat = vals.shape[2:]
    blocks_per_shard = shard_size // blocksize
    with jax.named_scope(UNPACK):
        vals = _apply_set_mask(vals, win_mask, reduce)
        ident = _reduce_identity(vals.dtype, reduce)
        accb = jnp.full((blocks_per_shard + 1, blocksize) + feat, ident,
                        vals.dtype)
        accb = _accumulate(accb, unpack_blk[0].ravel(),
                           recv.reshape((-1, blocksize) + feat), reduce)
        y_blocks = accb[:blocks_per_shard].reshape((shard_size,) + feat)
        acc = jnp.full((shard_size + 1,) + feat, ident, vals.dtype)
        acc = _accumulate(acc, own_idx.ravel(), vals.reshape((-1,) + feat),
                          reduce)
        y_own = acc[:shard_size]
        y = (jnp.maximum(y_blocks, y_own) if reduce == "max"
             else y_blocks + y_own)
        return _mask_untouched(y, touched[0], reduce)


def blockwise_scatter_local(vals, blk_msg_idx, unpack_blk, own_idx,
                            win_mask, touched, *, axis_name, p, b_max,
                            shard_size, blocksize, reduce):
    recv = blockwise_scatter_start_local(
        vals, blk_msg_idx, win_mask, axis_name=axis_name, p=p, b_max=b_max,
        blocksize=blocksize, reduce=reduce)
    return blockwise_scatter_finish_local(
        recv, vals, unpack_blk, own_idx, win_mask, touched,
        shard_size=shard_size, blocksize=blocksize, reduce=reduce)


def scatter_plan_device_args(splan: ScatterPlan, strategy: str):
    """Host plan arrays each scatter strategy needs, passed through
    shard_map with ``scatter_in_specs`` (all sharded on dim 0).

    The condensed/overlap and blockwise rungs reuse the *base gather
    plan's* pack tables (``send_local_idx`` / ``send_local_blk``) as their
    accumulate-unpack tables — the send/recv role swap made concrete.
    """
    if strategy == "replicate":
        return (splan.tgt_global, splan.win_mask, splan.touched)
    if strategy in ("condensed", "overlap"):
        return (splan.cond_msg_idx, splan.base.send_local_idx,
                splan.own_tgt_idx, splan.win_mask, splan.touched)
    if strategy == "blockwise":
        return (splan.blk_msg_idx, splan.base.send_local_blk,
                splan.own_tgt_idx, splan.win_mask, splan.touched)
    raise ValueError(f"unknown strategy {strategy!r}")


# positions of the (m, r) tables in ``scatter_plan_device_args``: each
# entry pairs with one contribution, all combined through ``ravel``
SCATTER_SLOT_TABLES = {"replicate": (0, 1), "condensed": (0, 2, 3),
                       "overlap": (0, 2, 3), "blockwise": (0, 2, 3)}


def shard_slot_major(table: np.ndarray, p: int) -> np.ndarray:
    """(p*rows, r, ...) -> (p*r, rows, ...): each shard's (rows, r) block
    transposed, so a device holds its contributions' tables as (r, rows)."""
    rows = table.shape[0] // p
    t = table.reshape((p, rows) + table.shape[1:])
    return np.ascontiguousarray(np.swapaxes(t, 1, 2)).reshape(
        (-1, rows) + table.shape[2:])


def scatter_in_specs(strategy: str, axis_name):
    """PartitionSpecs matching ``scatter_plan_device_args``."""
    p = jax.sharding.PartitionSpec
    nargs = 3 if strategy == "replicate" else 5
    return (p(axis_name),) * nargs


def make_scatter_start_local(splan: ScatterPlan, strategy: str, axis_name,
                             reduce: str, *, use_kernel: bool = False):
    """Returns (start_fn, finish_fn) splitting the scatter at its collective.

    ``start_fn(vals_local, *plan_args) -> in_flight`` packs (sender-side
    combine) and issues the exchange; ``finish_fn(in_flight, vals_local,
    *plan_args) -> y_local`` runs the own-accumulate — which depends only on
    local contributions, so XLA's latency-hiding scheduler overlaps it (and
    anything else scheduled in between) with the in-flight collective — and
    then combines the landed foreign contributions.  The ``overlap`` rung is
    the ``condensed`` exchange consumed through this split.

    ``use_kernel=True`` swaps the jnp segment-combines for the push-side
    split kernels: ``kernels.accumulate_segments`` for the sender-side pack
    (12ᵀ) and the own-target accumulate (the half of 15ᵀ with no data
    dependency on the collective — it runs while the all_to_all is in
    flight, mirroring ``ops.make_spmv_overlap_sharded``'s own/foreign
    split), then ``kernels.accumulate_into`` folds the landed foreign
    contributions into that result.  Bit-identical to the jnp path on every
    rung × reduce (same op sequence, single-program combine order).
    """
    if use_kernel:
        return _make_kernel_scatter_start_local(splan, strategy, axis_name,
                                                reduce)
    if reduce not in SCATTER_REDUCES:
        raise ValueError(f"reduce must be one of {SCATTER_REDUCES}")
    shard_size = splan.shard_size
    if strategy == "replicate":
        def start(vals, tgt, win, touched):
            feat = vals.shape[2:]
            with jax.named_scope(PACK):
                v = _apply_set_mask(vals, win, reduce)
                acc = jnp.full((splan.n,) + feat,
                               _reduce_identity(v.dtype, reduce), v.dtype)
                acc = _accumulate(acc, tgt.ravel(), v.reshape((-1,) + feat),
                                  reduce)
            return _all_reduce(acc, axis_name, reduce)

        def finish(y_full, vals, tgt, win, touched):
            return _owned_slice(y_full, touched, axis_name=axis_name,
                                shard_size=shard_size, reduce=reduce)

        return start, finish
    if strategy in ("condensed", "overlap"):
        def start(vals, msg_idx, unpack_idx, own_idx, win, touched):
            return condensed_scatter_start_local(
                vals, msg_idx, win, axis_name=axis_name, p=splan.p,
                s_max=splan.s_max, reduce=reduce)

        def finish(recv, vals, msg_idx, unpack_idx, own_idx, win, touched):
            return condensed_scatter_finish_local(
                recv, vals, unpack_idx, own_idx, win, touched,
                shard_size=shard_size, reduce=reduce)

        return start, finish
    if strategy == "blockwise":
        def start(vals, msg_idx, unpack_blk, own_idx, win, touched):
            return blockwise_scatter_start_local(
                vals, msg_idx, win, axis_name=axis_name, p=splan.p,
                b_max=splan.b_max, blocksize=splan.blocksize, reduce=reduce)

        def finish(recv, vals, msg_idx, unpack_blk, own_idx, win, touched):
            return blockwise_scatter_finish_local(
                recv, vals, unpack_blk, own_idx, win, touched,
                shard_size=shard_size, blocksize=splan.blocksize,
                reduce=reduce)

        return start, finish
    raise ValueError(f"unknown strategy {strategy!r}")


def _make_kernel_scatter_start_local(splan: ScatterPlan, strategy: str,
                                     axis_name, reduce: str):
    """Kernelized (start, finish) pair for the put direction (the
    ``use_kernel=True`` arm of ``make_scatter_start_local``).

    The winner mask for ``reduce="set"`` stays a jnp elementwise multiply
    outside the kernels (deterministic either way; keeps the kernels
    reduce-generic), exactly mirroring where the jnp path applies it.
    """
    from repro.kernels import ops as kops  # deferred: kernels never import comm

    if reduce not in SCATTER_REDUCES:
        raise ValueError(f"reduce must be one of {SCATTER_REDUCES}")
    shard_size = splan.shard_size
    if strategy == "replicate":
        def start(vals, tgt, win, touched):
            feat = vals.shape[2:]
            with jax.named_scope(PACK):
                v = _apply_set_mask(vals, win, reduce)
                acc = kops.accumulate_segments(
                    v.reshape((-1,) + feat), tgt.ravel(), out_len=splan.n,
                    reduce=reduce)
            return _all_reduce(acc, axis_name, reduce)

        def finish(y_full, vals, tgt, win, touched):
            return _owned_slice(y_full, touched, axis_name=axis_name,
                                shard_size=shard_size, reduce=reduce)

        return start, finish
    if strategy in ("condensed", "overlap"):
        p, s_max = splan.p, splan.s_max

        def start(vals, msg_idx, unpack_idx, own_idx, win, touched):
            feat = vals.shape[2:]
            with jax.named_scope(PACK):
                v = _apply_set_mask(vals, win, reduce)
                buf = kops.accumulate_segments(
                    v.reshape((-1,) + feat), msg_idx.ravel(),
                    out_len=p * s_max + 1, reduce=reduce)
            with jax.named_scope(EXCHANGE):
                return jax.lax.all_to_all(
                    buf[:p * s_max].reshape((p, s_max) + feat), axis_name,
                    split_axis=0, concat_axis=0, tiled=True)

        def finish(recv, vals, msg_idx, unpack_idx, own_idx, win, touched):
            feat = vals.shape[2:]
            with jax.named_scope(UNPACK):
                v = _apply_set_mask(vals, win, reduce)
                # push-side split: the own-accumulate reads only local
                # contributions, so it runs while the all_to_all is in
                # flight; the landed-foreign kernel then folds recv into
                # its result
                own = kops.accumulate_segments(
                    v.reshape((-1,) + feat), own_idx.ravel(),
                    out_len=shard_size + 1, reduce=reduce)
                acc = kops.accumulate_into(
                    own, recv.reshape((-1,) + feat), unpack_idx[0].ravel(),
                    reduce=reduce)
                return _mask_untouched(acc[:shard_size], touched[0], reduce)

        return start, finish
    if strategy == "blockwise":
        p, b_max, blocksize = splan.p, splan.b_max, splan.blocksize
        blocks_per_shard = shard_size // blocksize

        def start(vals, msg_idx, unpack_blk, own_idx, win, touched):
            feat = vals.shape[2:]
            with jax.named_scope(PACK):
                v = _apply_set_mask(vals, win, reduce)
                buf = kops.accumulate_segments(
                    v.reshape((-1,) + feat), msg_idx.ravel(),
                    out_len=p * b_max * blocksize + 1, reduce=reduce)
            with jax.named_scope(EXCHANGE):
                return jax.lax.all_to_all(
                    buf[:p * b_max * blocksize].reshape(
                        (p, b_max * blocksize) + feat),
                    axis_name, split_axis=0, concat_axis=0, tiled=True)

        def finish(recv, vals, msg_idx, unpack_blk, own_idx, win, touched):
            feat = vals.shape[2:]
            with jax.named_scope(UNPACK):
                v = _apply_set_mask(vals, win, reduce)
                own = kops.accumulate_segments(
                    v.reshape((-1,) + feat), own_idx.ravel(),
                    out_len=shard_size + 1, reduce=reduce)
                y_own = own[:shard_size]
                accb = kops.accumulate_segments(
                    recv.reshape((-1, blocksize) + feat),
                    unpack_blk[0].ravel(),
                    out_len=blocks_per_shard + 1, reduce=reduce)
                y_blocks = accb[:blocks_per_shard].reshape(
                    (shard_size,) + feat)
                y = (jnp.maximum(y_blocks, y_own) if reduce == "max"
                     else y_blocks + y_own)
                return _mask_untouched(y, touched[0], reduce)

        return start, finish
    raise ValueError(f"unknown strategy {strategy!r}")
