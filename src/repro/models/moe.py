"""Mixture-of-Experts block with the paper's communication-strategy ladder.

Token->expert routing is the LM-scale instance of the paper's fine-grained
irregular communication: each token (array element) must reach the shard
owning its expert (owner thread).  Following DESIGN.md §4:

* ``tp_local``  — experts are *weight-sharded* over the model axis (tensor
  parallel); tokens never move.  The analogue of the paper's single-node
  case where no remote transfers exist (natural for few-expert models:
  mixtral's 8 experts < 16-way model axis).
* ``ep_a2a``    — experts are sharded over the model axis (expert parallel);
  tokens are *sort-packed* into per-expert capacity-bounded buffers —
  message condensing (only selected tokens move) and consolidation (one
  buffer per expert) with a static capacity bound standing in for the
  paper's one-time plan, as XLA's static shapes require.  The resharding of
  the packed buffer is where GSPMD materializes the all-to-all.

Dispatch is computed per data-parallel group (the ``G`` leading dim) so no
collective sort is ever needed — the paper's per-thread preparation step.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import init_linear

__all__ = ["init_moe", "route", "dropped_assignments", "moe_fwd",
           "moe_capacity", "random_router",
           "moe_dispatch_pattern", "moe_dispatch_ref", "MoEDispatchGather",
           "moe_combine_weights", "moe_combine_ref", "MoECombineScatter",
           "moe_expert_local", "MoELayer", "DynamicMoELayer"]


def random_router(key, num_tokens: int, num_experts: int, top_e: int = 2):
    """Seeded zipf-skewed routing, the shared stand-in for a trained router.

    Expert popularity follows the paper-style skew real routers exhibit
    (weights ∝ 1/rank): every benchmark and test that needs a routing draws
    it here so the load imbalance — the thing the ladder optimizes — is the
    same everywhere.  Per token the ``top_e`` experts are drawn *without
    replacement* (Gumbel top-k over the skewed logits) and the routing
    weights are normalized to sum to 1.

    Returns ``(top_e_idx (T, k) int32, top_w (T, k) float32)``.
    """
    rng = np.random.default_rng(key)
    weights = 1.0 / np.arange(1, num_experts + 1)
    weights /= weights.sum()
    # Gumbel top-k: k distinct experts per token with P(expert) ∝ weights
    g = rng.gumbel(size=(num_tokens, num_experts)) + np.log(weights)
    idx = np.argsort(-g, axis=1)[:, :top_e].astype(np.int32)
    raw = rng.random((num_tokens, top_e)).astype(np.float32) + 0.1
    top_w = raw / raw.sum(axis=1, keepdims=True)
    return idx, top_w.astype(np.float32)


def init_moe(key, cfg, dtype=jnp.float32):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    scale = d ** -0.5
    p = {
        "router": init_linear(ks[0], d, e, dtype=dtype),
        "w1": jax.random.normal(ks[1], (e, d, f), dtype) * scale,
        "w2": jax.random.normal(ks[2], (e, f, d), dtype) * (f ** -0.5),
    }
    if cfg.act == "swiglu":
        p["w3"] = jax.random.normal(ks[3], (e, d, f), dtype) * scale
    if cfg.router_score == "sigmoid":
        # selection-only bias (deepseek-v3 e_score_correction_bias)
        p["router"]["bias"] = jnp.zeros((e,), jnp.float32)
    return p


def _router_scores(p_router, x, cfg):
    """(scores, choice) over the experts, float32: the weighing scores and
    the values whose top-k chooses."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        p_router["w"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        return scores, scores + p_router["bias"].astype(jnp.float32)
    if cfg.router_score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        return scores, scores
    raise ValueError(f"unknown router_score {cfg.router_score!r}")


def route(p_router, x, cfg):
    """The one routing rule of every MoE path (training, prefill, decode).

    x (..., D) -> (top_w (..., k) float32, top_e (..., k) int32).  Logits
    are computed in float32 at the highest matmul precision, as DeepSeek-V3
    publishes (a bfloat16 product flips near-tied choices).  ``softmax``:
    the top-k of the softmax.  ``sigmoid``: the top-k of sigmoid score +
    ``bias`` chooses, the unbiased scores weigh.  The chosen weights are
    renormalised over the k (Mixtral; DeepSeek-V3's ``norm_topk_prob``),
    then scaled by ``cfg.router_scale``.
    """
    scores, choice = _router_scores(p_router, x, cfg)
    _, top_e = jax.lax.top_k(choice, cfg.experts_per_token)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    return top_w * cfg.router_scale, top_e.astype(jnp.int32)


def dropped_assignments(top_e, num_experts: int, capacity: int):
    """Assignments past ``capacity`` in one routed batch ``top_e`` (T, k):
    the ones a capacity-bounded dispatch drops (int32 scalar)."""
    counts = jax.nn.one_hot(top_e.reshape(-1), num_experts,
                            dtype=jnp.int32).sum(0)
    return jnp.maximum(counts - capacity, 0).sum()


def moe_capacity(tokens_per_group: int, cfg) -> int:
    c = math.ceil(
        tokens_per_group * cfg.experts_per_token / cfg.num_experts
        * cfg.capacity_factor
    )
    return max(8, -(-c // 8) * 8)  # round up to 8


def _expert_mlp(p, buf, act):
    """buf: (G, E, C, D) -> (G, E, C, D)."""
    w1 = p["w1"].astype(buf.dtype)
    w2 = p["w2"].astype(buf.dtype)
    h = jnp.einsum("gecd,edf->gecf", buf, w1)
    if act == "swiglu":
        h = jax.nn.silu(h) * jnp.einsum(
            "gecd,edf->gecf", buf, p["w3"].astype(buf.dtype))
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("gecf,efd->gecd", h, w2)


def moe_fwd(p, x, cfg, *, constrain=None, aux=None):
    """x: (G, T, D) tokens grouped by data-parallel rank.

    ``constrain``: optional fn(array, stage) -> array applying sharding
    constraints; stage in {"dispatch", "expert"} (runtime/sharding.py).
    ``aux``: optional dict populated with the load-balancing loss, the
    routing (``experts``, (G, T, k)) and the assignments past capacity
    (``dropped``).
    """
    g, t, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    c = moe_capacity(t, cfg)

    with jax.named_scope("moe.route"):
        top_p, top_e = route(p["router"], x, cfg)         # (G, T, k)

    if aux is not None:
        # Switch-style load-balance loss: E * mean(frac_tokens * frac_prob)
        scores, _ = _router_scores(p["router"], x, cfg)
        me = (scores / scores.sum(-1, keepdims=True)).mean(axis=1)  # (G, E)
        ce = jax.nn.one_hot(top_e[..., 0], e).mean(axis=1)
        aux["moe_loss"] = (e * (me * ce).sum(-1)).mean()
        aux["experts"] = top_e

    with jax.named_scope("moe.experts"):
        # ---- condensed dispatch: sort tokens by expert, pack to capacity
        flat_e = top_e.reshape(g, t * k)
        flat_w = top_p.reshape(g, t * k)
        sort_idx = jnp.argsort(flat_e, axis=-1)           # (G, T*k) stable
        se = jnp.take_along_axis(flat_e, sort_idx, axis=-1)
        counts = jax.nn.one_hot(flat_e, e, dtype=jnp.int32).sum(axis=1)
        seg_start = jnp.cumsum(counts, axis=-1) - counts  # exclusive
        pos = jnp.arange(t * k)[None] - jnp.take_along_axis(seg_start, se,
                                                            axis=-1)
        keep = pos < c
        if aux is not None:
            aux["dropped"] = (~keep).sum()
        dest = jnp.where(keep, se * c + pos, e * c)       # dump slot
        tok = sort_idx // k

        gather_tok = jnp.take_along_axis(x, tok[..., None], axis=1)

        def scatter_one(vals, dst):
            buf = jnp.zeros((e * c + 1, d), vals.dtype)
            return buf.at[dst].set(vals)[: e * c]

        buf = jax.vmap(scatter_one)(gather_tok, dest).reshape(g, e, c, d)
        if constrain is not None:
            buf = constrain(buf, "expert")                # -> a2a under EP

        out_buf = _expert_mlp(p, buf, cfg.act)            # (G, E, C, D)
        if constrain is not None:
            out_buf = constrain(out_buf, "dispatch")      # -> back to dp

        flat_out = jnp.concatenate(
            [out_buf.reshape(g, e * c, d),
             jnp.zeros((g, 1, d), out_buf.dtype)], axis=1)
        y_sorted = jnp.take_along_axis(flat_out, dest[..., None], axis=1)
        w_sorted = jnp.take_along_axis(flat_w, sort_idx, axis=-1)
        y_sorted = y_sorted * (w_sorted * keep)[..., None].astype(
            y_sorted.dtype)

        def combine_one(ys, tk):
            return jnp.zeros((t, d), ys.dtype).at[tk].add(ys)

        out = jax.vmap(combine_one)(y_sorted, tok)        # (G, T, D)
    return out


# ---------------------------------------------------------------------------
# MoE dispatch as the paper's irregular gather (repro.comm consumer)
# ---------------------------------------------------------------------------
#
# The dispatch above rides inside one jitted forward where XLA/GSPMD places
# the all-to-all.  At *serving* scale the routing of a decoded batch is a
# static fact between steps: tokens live sharded over devices, experts live
# sharded over (possibly other) devices, and each expert shard must gather
# exactly the token vectors routed to it — a fine-grained irregular gather
# with expert-capacity slots as accessor rows and tokens as the shared
# vector.  ``MoEDispatchGather`` runs that gather through the same
# ``CommPlan`` / strategy ladder / §5 models as SpMV and Heat2D.


def _pack_slots(top_e, num_tokens: int, num_experts: int, capacity: int):
    """Shared slot packing: sort (token, choice) pairs by expert, truncate
    at capacity.  Returns (slot_expert, slot_pos, src_flat, keep) over the
    flattened (num_tokens * k) routing choices, token-major within each
    expert — the same tokens ``moe_fwd`` keeps."""
    top_e = np.asarray(top_e)
    k = top_e.shape[1]
    e_flat = top_e.ravel()
    order = np.argsort(e_flat, kind="stable")     # (e, then token-major)
    se = e_flat[order]
    counts = np.bincount(e_flat, minlength=num_experts)
    seg_start = np.cumsum(counts) - counts
    pos = np.arange(num_tokens * k) - seg_start[se]
    keep = pos < capacity
    return se, pos, order, keep


def moe_dispatch_pattern(top_e, num_tokens: int, num_experts: int,
                         capacity: int, p: int, *, packed=None):
    """Token→expert assignment as an access-pattern index table.

    ``top_e``: (num_tokens, k) expert choices per token.  Accessor row
    ``e*capacity + c`` reads the c-th token routed to expert e (token-major
    order, truncated at capacity — the same tokens ``moe_fwd`` keeps).
    Returns ``(idx (E*C,) int32, valid (E*C,) bool)``; empty slots pad with
    a token *owned by the expert's shard* so padding costs no communication.
    ``packed`` accepts a precomputed ``_pack_slots`` result so a caller
    that also builds the combine weights runs the sort pipeline once.
    """
    top_e = np.asarray(top_e)
    assert num_tokens % p == 0 and num_experts % p == 0
    t_loc, e_loc = num_tokens // p, num_experts // p
    k = top_e.shape[1]
    se, pos, order, keep = packed if packed is not None else _pack_slots(
        top_e, num_tokens, num_experts, capacity)
    st = np.repeat(np.arange(num_tokens, dtype=np.int64), k)[order]

    idx = np.zeros((num_experts, capacity), np.int64)
    valid = np.zeros((num_experts, capacity), bool)
    idx[se[keep], pos[keep]] = st[keep]
    valid[se[keep], pos[keep]] = True
    # pad empty slots with an owned token id (zero-cost access)
    own_token = np.repeat(np.arange(p) * t_loc, e_loc * capacity).reshape(
        num_experts, capacity)
    idx = np.where(valid, idx, own_token)
    return idx.reshape(-1).astype(np.int32), valid.reshape(-1)


def moe_combine_weights(top_e, top_w, num_tokens: int, num_experts: int,
                        capacity: int, *, packed=None):
    """Per-slot combine weight for the expert→token return path.

    ``top_w``: (num_tokens, k) routing weights aligned with ``top_e``.
    Slot ``e*capacity + c`` gets the weight of the token occupying it under
    ``moe_dispatch_pattern``'s packing; empty (over-capacity) slots get 0,
    so their contribution vanishes exactly.  Returns (E*C,) float32.
    ``packed`` accepts a precomputed ``_pack_slots`` result, as in
    ``moe_dispatch_pattern``.
    """
    top_w = np.asarray(top_w)
    se, pos, order, keep = packed if packed is not None else _pack_slots(
        top_e, num_tokens, num_experts, capacity)
    sw = top_w.ravel()[order]
    w = np.zeros((num_experts, capacity), np.float32)
    w[se[keep], pos[keep]] = sw[keep]
    return w.reshape(-1)


def moe_dispatch_ref(x, idx, valid, num_experts: int, capacity: int):
    """NumPy ground truth: buf[e, c] = x[idx[e*C+c]] (0 where invalid)."""
    x = np.asarray(x)
    out = x[idx] * valid.reshape(-1, *([1] * (x.ndim - 1)))
    return out.reshape((num_experts, capacity) + x.shape[1:])


class MoEDispatchGather:
    """Expert-capacity-slot gather over sharded tokens via ``repro.comm``.

    Tokens (the shared vector, length ``num_tokens``, optional feature dims)
    and experts (``num_experts``, ``capacity`` slots each) are both sharded
    contiguously over ``axis_name``.  Any ladder rung or ``"auto"`` applies.

    ``materialize="dest"`` (default) registers the expert-capacity slots as
    a ``Destination``: each exchange lands token vectors directly in
    ``(expert, capacity-slot)`` order — O(slots + recv) work per dispatch,
    empty slots read exactly 0.0, and no length-``num_tokens`` private copy
    is ever assembled.  ``materialize="full"`` keeps the classic
    assemble-then-index path (bit-identical output); there the ``overlap``
    rung fills owned-token slots from ``x_local`` while the condensed
    exchange is in flight (the plan's own/foreign split with r = 1).
    """

    def __init__(self, top_e, num_tokens: int, num_experts: int,
                 capacity: int, mesh, *, axis_name: str = "data",
                 strategy: str = "auto", blocksize=None,
                 shards_per_node=None, materialize: str = "dest",
                 hw=None, use_plan_cache: bool = True):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.comm.gather import IrregularGather
        from repro.comm.pattern import AccessPattern, Destination
        from repro.comm.plan import Topology

        p = int(mesh.shape[axis_name])
        self.p = p
        self.num_tokens = num_tokens
        self.num_experts = num_experts
        self.capacity = capacity
        assert materialize in ("dest", "full"), materialize
        self.materialize = materialize
        idx, valid = moe_dispatch_pattern(
            top_e, num_tokens, num_experts, capacity, p)
        self.idx, self.valid = idx, valid
        pattern = AccessPattern.from_indices(idx, n=num_tokens)
        destination = None
        if materialize == "dest":
            # capacity slots ARE the consumer buffer: empty slots (whose
            # pattern entry is an owned zero-cost pad token) deliver 0.0
            slot_idx = np.where(valid, idx.astype(np.int64),
                                Destination.ZERO)
            destination = Destination.from_slots(
                slots=slot_idx.reshape(p, -1))
        self.gather = IrregularGather(
            pattern, mesh, axis_name=axis_name, strategy=strategy,
            blocksize=blocksize, destination=destination,
            topology=Topology(p, shards_per_node or p), hw=hw,
            use_plan_cache=use_plan_cache,
        )
        self.strategy = self.gather.strategy
        self.requested_strategy = strategy
        self.predicted_times = self.gather.predicted_times
        self.plan = self.gather.plan
        gather = self.gather

        shard = NamedSharding(mesh, P(axis_name))
        n = num_tokens
        if materialize == "dest":
            extra = ()
        elif self.strategy == "overlap":
            plan = self.plan
            extra = (plan.loc_cols[:, 0], plan.rem_cols[:, 0],
                     valid.astype(np.float32))
        else:
            extra = (idx, valid.astype(np.float32))
        self._extra_args = tuple(jax.device_put(a, shard) for a in extra)

        def step_local(x_local, *args):
            gargs = args[:len(gather.plan_args)]
            rest = args[len(gather.plan_args):]
            feat = x_local.shape[1:]
            e_loc = num_experts // p
            if materialize == "dest":
                # one targeted delivery: owned tokens from x_local, foreign
                # tokens from the landed recv buffer, empty slots exactly 0
                vals = gather.local(x_local, *gargs)["slots"]
                return vals.reshape((e_loc, capacity) + feat)
            if self.strategy == "overlap":
                loc_l, rem_l, valid_l = rest
                handle = gather.start_local(x_local, *gargs)
                # own-token slots resolve from x_local while the exchange
                # flies; padding points at the zero slot appended here
                x_ext = jnp.concatenate(
                    [x_local, jnp.zeros((1,) + feat, x_local.dtype)])
                own = x_ext[loc_l]
                x_copy = handle.finish(extra_slots=1, copy_own=False)
                vals = own + x_copy[rem_l]   # each slot is own xor foreign
            else:
                idx_l, valid_l = rest
                x_copy = gather.local(x_local, *gargs)
                vals = x_copy[idx_l]
            mask = valid_l.reshape(valid_l.shape + (1,) * len(feat))
            buf = vals * mask.astype(vals.dtype)
            return buf.reshape((e_loc, capacity) + feat)

        in_specs = ((P(axis_name),) + gather.in_specs
                    + (P(axis_name),) * len(extra))
        mapped = jax.shard_map(
            step_local, mesh=mesh, in_specs=in_specs,
            out_specs=P(axis_name), check_vma=False)

        self._dispatch_args = tuple(gather.plan_args) + tuple(
            self._extra_args)
        self._dispatch = jax.jit(mapped)

    @property
    def counts(self):
        return self.plan.counts

    def shard_tokens(self, x) -> jax.Array:
        return self.gather.shard_vector(x)

    def __call__(self, x: jax.Array) -> jax.Array:
        """x: (num_tokens, ...) sharded -> (num_experts, capacity, ...)
        expert input buffers, sharded over the expert dim."""
        return self._dispatch(x, *self._dispatch_args)


def moe_combine_ref(buf, idx, valid, w_slot, num_tokens: int):
    """NumPy ground truth for the combine: y[t] = Σ_slots→t w_slot * buf.

    ``buf``: (num_experts, capacity, ...) expert outputs; ``idx``/``valid``
    from ``moe_dispatch_pattern``; ``w_slot`` from ``moe_combine_weights``.
    """
    buf = np.asarray(buf)
    feat = buf.shape[2:]
    flat = buf.reshape((-1,) + feat)
    wshape = (-1,) + (1,) * len(feat)
    contrib = flat * (np.asarray(w_slot) * valid).reshape(wshape)
    y = np.zeros((num_tokens,) + feat, buf.dtype)
    np.add.at(y, np.asarray(idx), contrib.astype(buf.dtype))
    return y


class MoECombineScatter:
    """Weighted expert→token combine via ``repro.comm`` — the true inverse
    of ``MoEDispatchGather``.

    After the experts run, each (expert, capacity-slot) row holds the
    processed vector of the token that occupied it; the combine pushes
    ``w_slot * buf[e, c]`` back to that token and sums across a token's
    experts (``reduce="add"``) — what ``moe_fwd``'s ``combine_one`` vmap
    does *locally* inside one jitted forward.  On the cross-device serving
    path (experts sharded over ``axis_name``, tokens sharded over the same
    axis) this class replaces that local-only combine: the same
    ``AccessPattern`` that planned the dispatch gather plans the combine
    scatter — ``CommPlan.transpose()`` reuses the cached base plan, so the
    pair costs one O(nnz) preparation step total — and any ladder rung (or
    ``"auto"`` via the §5 put models) moves exactly the selected tokens'
    vectors back.

    Over-capacity (invalid) slots carry weight 0, so they contribute
    exactly nothing, matching ``moe_fwd``'s capacity-drop semantics.
    """

    def __init__(self, top_e, top_w, num_tokens: int, num_experts: int,
                 capacity: int, mesh, *, axis_name: str = "data",
                 strategy: str = "auto", blocksize=None,
                 shards_per_node=None, hw=None, use_plan_cache: bool = True):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.comm.pattern import AccessPattern
        from repro.comm.plan import Topology
        from repro.comm.scatter import IrregularScatter

        p = int(mesh.shape[axis_name])
        self.p = p
        self.num_tokens = num_tokens
        self.num_experts = num_experts
        self.capacity = capacity
        packed = _pack_slots(top_e, num_tokens, num_experts, capacity)
        idx, valid = moe_dispatch_pattern(
            top_e, num_tokens, num_experts, capacity, p, packed=packed)
        w_slot = moe_combine_weights(
            top_e, top_w, num_tokens, num_experts, capacity, packed=packed)
        self.idx, self.valid, self.w_slot = idx, valid, w_slot
        # same pattern as the dispatch gather: slot (e, c) touches its
        # token — pulled on dispatch, pushed on combine
        pattern = AccessPattern.from_indices(idx, n=num_tokens)
        self.scatter = IrregularScatter(
            pattern, mesh, axis_name=axis_name, strategy=strategy,
            blocksize=blocksize, reduce="add",
            topology=Topology(p, shards_per_node or p), hw=hw,
            use_plan_cache=use_plan_cache,
        )
        self.strategy = self.scatter.strategy
        self.requested_strategy = strategy
        self.predicted_times = self.scatter.predicted_times
        self.plan = self.scatter.plan
        self.splan = self.scatter.splan
        scatter = self.scatter

        shard = NamedSharding(mesh, P(axis_name))
        # invalid slots: weight 0 -> contribution exactly 0
        w_masked = (w_slot * valid).astype(np.float32)[:, None]
        self._w = jax.device_put(w_masked, shard)

        @jax.jit
        def combine(buf, w):
            # merging the sharded expert dim into the slot dim keeps the
            # expert sharding; explicit-axis meshes need it spelled out
            s = jax.typeof(buf).sharding
            flat = jax.lax.reshape(
                buf, (num_experts * capacity, 1) + buf.shape[2:],
                out_sharding=s.update(spec=P(s.spec[0])))
            w = w.reshape((num_experts * capacity, 1) + (1,) * (buf.ndim - 2))
            return scatter(flat * w.astype(buf.dtype))

        self._combine = combine

    @property
    def counts(self):
        """Put-direction §5 volume counts of the combine exchange."""
        return self.splan.counts

    def shard_expert_buf(self, buf) -> jax.Array:
        """Place a host (num_experts, capacity, ...) buffer on the mesh,
        sharded over the expert dim."""
        return self.scatter.shard_vector(buf)

    def __call__(self, buf: jax.Array) -> jax.Array:
        """buf: (num_experts, capacity, ...) expert outputs sharded over
        the expert dim -> (num_tokens, ...) combined tokens, sharded."""
        return self._combine(buf, self._w)


# ---------------------------------------------------------------------------
# The fused serving-path layer: dispatch → expert → combine through ONE
# ExchangeSchedule (repro.comm.schedule) — one shard_map, one planned window
# ---------------------------------------------------------------------------


def moe_expert_local(buf, w1, w2, w3=None, act="gelu"):
    """Per-shard expert MLP: ``buf`` (E_loc, C, D) with this shard's expert
    weights ``w1`` (E_loc, D, F) / ``w2`` (E_loc, F, D) (and ``w3`` under
    swiglu).  Shared by ``MoELayer``'s compute stage and any composed
    baseline so the two paths run the identical local math."""
    w1 = w1.astype(buf.dtype)
    w2 = w2.astype(buf.dtype)
    h = jnp.einsum("ecd,edf->ecf", buf, w1)
    if act == "swiglu":
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf,
                                        w3.astype(buf.dtype))
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, w2)


class MoELayer:
    """Fused dispatch → expert MLP → combine via one ``ExchangeSchedule``.

    The composed serving path pays three windows: the
    ``MoEDispatchGather`` jit, the expert-MLP jit, the
    ``MoECombineScatter`` jit — each with its own dispatch overhead, and
    the middle one re-reading the landed expert buffers from HBM.
    ``MoELayer`` declares the whole chain as one ``Schedule``:

    * one gather stage (the token→expert ``Destination`` delivery of
      ``MoEDispatchGather``), one compute stage (``moe_expert_local`` +
      the combine-weight multiply), one scatter stage (the
      ``reduce="add"`` push of ``MoECombineScatter``);
    * both exchange stages share one base ``CommPlan`` (the combine's
      executor tables are the transpose-derived delta) and one
      hw-calibration memo hit;
    * ``compile`` emits a **single** ``shard_map``: the expert compute and
      the combine's own-shard accumulate run inside the scatter's
      collective window, and the fused window is priced by
      ``perfmodel.predict_schedule`` (``.predicted_window``).

    Bit-identical to the composed
    ``MoEDispatchGather → moe_expert_local → MoECombineScatter`` path on
    every ladder rung (tested in ``tests/test_schedule.py``).

    ``params``: ``{"w1": (E, D, F), "w2": (E, F, D)[, "w3": (E, D, F)]}``
    (the ``init_moe`` layout), sharded over the expert dim at compile.
    """

    def __init__(self, params, top_e, top_w, num_tokens: int,
                 num_experts: int, capacity: int, mesh, *,
                 axis_name: str = "data", act: str = "gelu",
                 strategy: str = "auto", blocksize=None,
                 shards_per_node=None, hw=None, use_plan_cache: bool = True):
        from repro.comm import AccessPattern, Destination, Schedule
        from repro.comm.plan import Topology

        p = int(mesh.shape[axis_name])
        assert num_experts % p == 0 and num_tokens % p == 0
        self.p = p
        self.num_tokens = num_tokens
        self.num_experts = num_experts
        self.capacity = capacity
        e_loc = num_experts // p
        d = params["w1"].shape[1]

        # one sort pipeline builds the dispatch pattern AND the combine
        # weights (the pair shares the packing, like the two front doors)
        packed = _pack_slots(top_e, num_tokens, num_experts, capacity)
        idx, valid = moe_dispatch_pattern(
            top_e, num_tokens, num_experts, capacity, p, packed=packed)
        w_slot = moe_combine_weights(
            top_e, top_w, num_tokens, num_experts, capacity, packed=packed)
        self.idx, self.valid, self.w_slot = idx, valid, w_slot
        pattern = AccessPattern.from_indices(idx, n=num_tokens)
        slot_idx = np.where(valid, idx.astype(np.int64), Destination.ZERO)
        destination = Destination.from_slots(slots=slot_idx.reshape(p, -1))
        # invalid (over-capacity) slots: weight 0 -> contribution exactly 0
        w_masked = (w_slot * valid).astype(np.float32)[:, None]

        sched = Schedule()
        x_ref = sched.input("tokens")
        w1 = sched.constant(np.asarray(params["w1"]), "w1")
        w2 = sched.constant(np.asarray(params["w2"]), "w2")
        wexperts = (w1, w2)
        if act == "swiglu":
            wexperts += (sched.constant(np.asarray(params["w3"]), "w3"),)
        wc = sched.constant(w_masked, "combine_w")
        g = sched.gather(pattern, src=x_ref, destination=destination,
                         name="dispatch")

        def expert_fn(delivered, *weights):
            *wx, wc_l = weights
            w3_l = wx[2] if len(wx) == 3 else None
            # tokens land in (expert, capacity) order; empty slots are
            # exactly 0 and carry combine weight 0
            buf = delivered["slots"].reshape(e_loc, capacity, d)
            out = moe_expert_local(buf, wx[0], wx[1], w3_l, act)
            flat = out.reshape(e_loc * capacity, 1, d)
            return flat * wc_l.reshape(
                e_loc * capacity, 1, 1).astype(flat.dtype)

        y = sched.compute(expert_fn, g, *wexperts, wc, name="expert")
        out = sched.scatter(pattern, y, reduce="add", name="combine")
        self.schedule = sched.compile(
            mesh, axis_name=axis_name, strategy=strategy,
            blocksize=blocksize, topology=Topology(p, shards_per_node or p),
            hw=hw, use_plan_cache=use_plan_cache, output=out)
        self.gather = sched.exchange_of(g)
        self.scatter = sched.exchange_of(out)
        self.requested_strategy = strategy
        self.strategies = self.schedule.strategies
        self.predicted_times = self.schedule.predicted_times
        self.predicted_window = self.schedule.predicted_window

    def shard_tokens(self, x) -> jax.Array:
        return self.schedule.shard_input(x)

    def __call__(self, x: jax.Array) -> jax.Array:
        """x: (num_tokens, d) sharded -> (num_tokens, d) combined expert
        outputs, sharded — the full dispatch→expert→combine step in one
        fused window."""
        return self.schedule(x)


# ---------------------------------------------------------------------------
# Per-batch routing: the DynamicPattern consumer (repro.comm.dynamic)
# ---------------------------------------------------------------------------


class DynamicMoELayer:
    """Per-batch routed dispatch → expert MLP → combine with ZERO host plan
    builds after warmup.

    ``MoELayer`` bakes one routing into its compiled window: a new routing
    means a new host ``CommPlan`` build, a new trace, a new compile — the
    §5 ``T_plan`` tax every batch.  ``DynamicMoELayer`` instead wraps one
    representative routing in a ``DynamicPattern``: the plan cache serves a
    capacity-bounded *envelope* plan (bucket-reused across compatible
    routings, ``plan_cache.get_envelope_plan``), and each batch's executor
    tables are re-derived **in-jit** from that batch's ``(top_e, top_w)``
    (``repro.comm.dynamic``) — one derivation pass feeds BOTH directions,
    the ``CommPlan.transpose()`` economy on device.  One jit serves every
    routing of the same shape; after the first call the only per-batch plan
    work is the traced derivation (telemetry source ``"device-derive"``).

    The per-call cost the auto ranking pays for this is
    ``perfmodel.plan_build_time(..., source="device-derive")``, threaded
    through ``select.rank_strategies(plan_cost=...)`` — exposed as
    ``.plan_time`` so consumers can ask ``replan_break_even_steps`` whether
    rebuilding a static ``MoELayer`` would ever pay off.

    Bit-identical to a freshly host-planned
    ``MoEDispatchGather(materialize="full") → moe_expert_local →
    MoECombineScatter`` per routing (tests/test_dynamic_pattern.py).

    ``params``: the ``init_moe`` layout (``w1``/``w2``[/``w3``]), sharded
    over the expert dim at construction — or ``jax.ShapeDtypeStruct``s of
    one layer's weights, for a layer that only ever runs ``apply``.  ``top_e`` is a *template*
    routing (T, k) — only its shape and load envelope matter.
    """

    def __init__(self, params, top_e, num_tokens: int, num_experts: int,
                 capacity: int, mesh, *, axis_name: str = "data",
                 act: str = "gelu", strategy: str = "auto", blocksize=None,
                 shards_per_node=None, hw=None, use_plan_cache: bool = True,
                 s_max: int | None = None, decode: bool = False):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.comm import dynamic as dyn
        from repro.comm.exchange import measure_hw
        from repro.comm.gather import IrregularGather
        from repro.comm.pattern import AccessPattern
        from repro.comm.plan import Topology
        from repro.comm.scatter import IrregularScatter
        from repro.comm import perfmodel

        p = int(mesh.shape[axis_name])
        assert num_experts % p == 0 and num_tokens % p == 0
        self.p = p
        self.num_tokens = num_tokens
        self.num_experts = num_experts
        self.capacity = capacity
        t_loc, e_loc = num_tokens // p, num_experts // p
        d = params["w1"].shape[1]
        k = np.asarray(top_e).shape[1]
        self.k = k
        m = num_experts * capacity

        # the template routing founds the envelope plan; every later batch
        # reuses it (memory/bucket tier) and re-derives tables on device
        idx, _ = moe_dispatch_pattern(
            top_e, num_tokens, num_experts, capacity, p)
        template = AccessPattern.from_indices(idx, n=num_tokens)
        self.pattern = dyn.DynamicPattern.from_template(
            template, p, s_max=s_max)

        if hw is None:
            hw = measure_hw(mesh, axis_name)
        # the per-batch T_plan this layer actually pays: the traced
        # derivation sort, not a host build
        self.plan_time = perfmodel.plan_build_time(
            m, 1, hw, source="device-derive")
        topo = Topology(p, shards_per_node or p)
        gather = IrregularGather(
            self.pattern, mesh, axis_name=axis_name, strategy=strategy,
            blocksize=blocksize, topology=topo, hw=hw,
            use_plan_cache=use_plan_cache, plan_cost=self.plan_time,
            decode=decode)
        scatter = IrregularScatter(
            self.pattern, mesh, axis_name=axis_name, strategy=strategy,
            reduce="add", blocksize=blocksize, topology=topo, hw=hw,
            use_plan_cache=use_plan_cache, plan_cost=self.plan_time,
            decode=decode)
        self.gather, self.scatter = gather, scatter
        self.decode = decode
        self.strategies = {"dispatch": gather.strategy,
                           "combine": scatter.strategy}
        self.predicted_times = {"dispatch": gather.predicted_times,
                                "combine": scatter.predicted_times}
        self.requested_strategy = strategy

        shard = NamedSharding(mesh, P(axis_name))
        wlist = [params["w1"], params["w2"]]
        if act == "swiglu":
            wlist.append(params["w3"])
        self._n_weights = len(wlist)
        # abstract weights (ShapeDtypeStruct) build an apply()-only layer:
        # the caller passes each layer's weights per call, so no copy of
        # them is placed here
        self._weights = (
            None if isinstance(wlist[0], jax.ShapeDtypeStruct)
            else tuple(jax.device_put(w, shard) for w in wlist))
        # empty-slot pad: an owned token id per expert shard (zero-cost)
        own_token = jnp.asarray(np.repeat(
            np.arange(p, dtype=np.int32) * t_loc, e_loc * capacity))

        n, e, c, t = num_tokens, num_experts, capacity, num_tokens
        s_max_r = self.pattern.s_max

        def pack(top_e_d, top_w_d):
            # the in-jit twin of _pack_slots + moe_dispatch_pattern +
            # moe_combine_weights: same stable sort, same capacity drop,
            # same owned-token padding — bit-identical slot tables
            flat_e = top_e_d.reshape(t * k).astype(jnp.int32)
            flat_w = top_w_d.reshape(t * k)
            sort_idx = jnp.argsort(flat_e)                    # stable
            se = flat_e[sort_idx]
            counts = jax.nn.one_hot(flat_e, e, dtype=jnp.int32).sum(axis=0)
            seg_start = jnp.cumsum(counts) - counts
            pos = jnp.arange(t * k) - seg_start[se]
            keep = pos < c
            dest = jnp.where(keep, se * c + pos, e * c)       # dump slot
            tok = (sort_idx // k).astype(jnp.int32)
            sw = flat_w[sort_idx].astype(jnp.float32)
            valid = jnp.zeros((e * c + 1,), bool).at[dest].set(True)[:e * c]
            slot_tok = jnp.zeros((e * c + 1,),
                                 jnp.int32).at[dest].set(tok)[:e * c]
            w_slot = jnp.zeros((e * c + 1,),
                               jnp.float32).at[dest].set(sw)[:e * c]
            cols = jnp.where(valid, slot_tok, own_token)
            return cols, w_slot           # w_slot is 0 at invalid slots

        ng, ns = len(gather.in_specs), len(scatter.in_specs)

        def step_local(x_local, *args):
            gargs = args[:ng]
            sargs = args[ng:ng + ns]
            cols_l, w_l = args[ng + ns], args[ng + ns + 1]
            wx = args[ng + ns + 2:]
            x_copy = gather.local(x_local, *gargs)
            buf = x_copy[cols_l].reshape(e_loc, capacity, d)
            w3_l = wx[2] if len(wx) == 3 else None
            out = moe_expert_local(buf, wx[0], wx[1], w3_l, act)
            flat = out.reshape(e_loc * capacity, 1, d)
            contrib = flat * w_l.reshape(
                e_loc * capacity, 1, 1).astype(flat.dtype)
            return scatter.local(contrib, *sargs)

        in_specs = ((P(axis_name),) + gather.in_specs + scatter.in_specs
                    + (P(axis_name), P(axis_name))
                    + (P(axis_name),) * self._n_weights)
        mapped = jax.shard_map(
            step_local, mesh=mesh, in_specs=in_specs,
            out_specs=P(axis_name), check_vma=False)

        def routed_step(x, top_e_d, top_w_d, wx):
            cols, w_slot = pack(top_e_d, top_w_d)
            cols2 = cols.reshape(-1, 1)
            # ONE derivation pass serves both directions (the transpose
            # economy, in-jit): the gather tables seed the scatter derive
            g = dyn.derive_gather_tables(cols2, n, p, s_max_r)
            gargs = (g.send_local_idx, g.recv_global_idx)
            sargs = scatter.derive_plan_args(cols2, gather_tables=g)
            return mapped(x, *gargs, *sargs, cols, w_slot, *wx)

        self._routed_step = routed_step
        # weights are an argument, never closure constants
        self._fwd = jax.jit(routed_step)

    def shard_tokens(self, x) -> jax.Array:
        return self.gather.shard_vector(x)

    def apply(self, x: jax.Array, top_e, top_w, *weights) -> jax.Array:
        """One routed step with the expert weights passed PER CALL (traced)
        instead of baked at construction — the embeddable twin of
        ``__call__`` for consumers that already sit inside a jit, e.g. the
        transformer decode step scanning over its layer stack: one layer
        instance (template shapes) serves every scanned layer, each
        supplying its own traced ``w1, w2[, w3]`` slices.

        Same shard_map window, same in-jit derivation, same math as
        ``__call__``.  No telemetry is recorded here (this runs under the
        caller's trace); the caller records one ``"device-derive"`` per
        *executed* step host-side — ``repro.serve.engine`` does this per
        decode tick."""
        if len(weights) != self._n_weights:
            raise ValueError(
                f"expected {self._n_weights} expert weight arrays "
                f"(w1, w2{', w3' if self._n_weights == 3 else ''}), "
                f"got {len(weights)}")
        return self._routed_step(x, jnp.asarray(top_e), jnp.asarray(top_w),
                                 tuple(weights))

    def lower(self, x: jax.Array, top_e, top_w):
        """``jax.stages.Lowered`` of ``self(x, top_e, top_w)``."""
        return self._fwd.lower(x, jnp.asarray(top_e), jnp.asarray(top_w),
                               self._weights)

    def __call__(self, x: jax.Array, top_e, top_w) -> jax.Array:
        """One routed step: x (num_tokens, d) sharded + THIS batch's
        routing (T, k) -> (num_tokens, d) combined expert outputs.

        No host plan work happens here — the tables come from the traced
        derivation (recorded per call as ``"device-derive"``; the trace
        itself compiles once for all routings of this shape)."""
        from repro.comm import telemetry
        if self._weights is None:
            raise ValueError("built from abstract weights: use apply()")
        telemetry.record("device-derive")
        return self._fwd(x, jnp.asarray(top_e), jnp.asarray(top_w),
                         self._weights)
