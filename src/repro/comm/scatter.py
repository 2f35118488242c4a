"""IrregularScatter — the push-direction front door to the strategy ladder.

The paper's condensing/consolidation strategies and §5 cost models apply
symmetrically to puts and gets: the performance formulas hinge only on
message volumes, not direction.  ``IrregularScatter`` is the put-side dual
of ``IrregularGather``: accessor row i's slot j *contributes* a value to
global element ``pattern.indices[i, j]`` of a sharded vector, duplicate
targets combine under a ``reduce`` semantic, and every ladder rung (or
``"auto"`` via the put-direction §5 models) moves exactly the same per-pair
message sets as the gather of the same pattern — the plan is literally the
gather plan with send/recv tables swapped (``CommPlan.transpose()``,
persisted as a format-v4 plan-cache delta).

Reduce semantics (all deterministic, see ``strategies.SCATTER_REDUCES``):

* ``"add"`` — y[t] = sum of contributions (0 where none); the MoE
  expert→token combine and the SpMV-transpose accumulate.
* ``"max"`` — y[t] = max of contributions (0 where none).
* ``"set"`` — y[t] = the last contribution in row-major accessor order
  (0 where none), via the plan's precomputed winner mask.

Composition mirrors the gather exactly:

* standalone: ``y = scatter(vals)`` with ``vals`` the (m, r, feat...)
  contribution table sharded over accessor rows; returns the combined
  length-n vector sharded over owners.
* fused: thread ``scatter.plan_args`` through your own ``shard_map`` and
  call ``scatter.local(vals_local, *plan_args_l)`` inside — or use the
  handle protocol to hide the exchange behind local compute::

      def step_local(vals_local, *plan_args_l):
          handle = scatter.start_local(vals_local, *plan_args_l)  # issued
          extra = ...            # anything that doesn't need the landed msgs
          y_local = handle.finish()   # own-accumulate + landed foreign
          return y_local + extra

  ``finish`` runs the own-shard accumulate first — it has no data
  dependency on the collective, so XLA's latency-hiding scheduler overlaps
  it (that is the ``overlap`` rung's whole trick; as a pure scatter it is
  identical to ``condensed``).

See docs/comm_api.md for a runnable walkthrough and docs/perf_model.md for
the put-direction pricing.  In a ``repro.comm.schedule`` chain a scatter
is one *stage*: it reuses a sibling gather stage's base plan (its executor
tables are the transpose-derived delta) and its own-shard accumulate runs
inside the fused window.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import dynamic as dyn
from repro.comm import plan_cache
from repro.comm import strategies as strat
from repro.comm.exchange import IrregularExchange
from repro.comm.plan import CommPlan, ScatterPlan, transpose_counts

__all__ = ["IrregularScatter", "ScatterHandle"]


@dataclasses.dataclass
class ScatterHandle:
    """An in-flight scatter: the packed contributions are on the wire, the
    owned slice is not yet combined.  ``finish()`` returns the device's
    combined ``y_local`` (shard_size, feat...)."""

    vals_local: jax.Array
    _finish: Callable[[], jax.Array]

    def finish(self) -> jax.Array:
        return self._finish()


class IrregularScatter(IrregularExchange):
    """Plan + strategy + device state for scattering contributions to one
    ``AccessPattern``'s targets over one mesh axis (or tuple of axes).

    The pattern plays the transposed role: its (m, r) indices are *write*
    targets.  Accessor rows and vector elements are partitioned contiguously
    over the same shards, exactly as for the gather — so a gather and a
    scatter of the same pattern share one cached base plan.
    """

    direction = "put"

    def __init__(self, pattern, where, *, reduce: str = "add",
                 slot_major: bool = False, **kwargs):
        """``reduce`` picks the duplicate-combining semantic (``"add"`` /
        ``"set"`` / ``"max"``).  ``slot_major=True`` takes each device's
        contributions as one flat vector in slot-major order (slot j of
        row i at ``j * rows + i``) instead of a (rows, r) table — a layout
        that pads nothing on a TPU when r is narrow; the executor tables
        are laid out to match, so the result is the same set of
        contributions combined (``"add"`` in another order).  Remaining
        keyword arguments (``axis_name``, ``strategy``, ``blocksize``,
        ``shards_per_node``, ``topology``, ``hw``, ``candidates``,
        ``use_plan_cache``, ``use_kernel``) are the shared
        ``IrregularExchange`` surface."""
        if reduce not in strat.SCATTER_REDUCES:
            raise ValueError(
                f"reduce must be one of {strat.SCATTER_REDUCES}")
        self.reduce = reduce
        self.slot_major = slot_major
        super().__init__(pattern, where, **kwargs)

    def _prepare(self, base_plan: CommPlan) -> None:
        # the transpose-derived executor tables are strategy-independent,
        # so they are resolved (and cached as a v4 delta) before the §5
        # ranking, whose put-direction counts they carry
        if self.dynamic_pattern is not None:
            # the envelope base plan's tables may belong to a different
            # founding routing (bucket reuse), so the host transpose-derive
            # cannot probe them — derive the template's put tables on
            # device instead (bit-identical to the host derivation at the
            # envelope s_max); blockwise is outside the dynamic ladder, its
            # table stays all-dump
            cols = np.asarray(self.pattern.indices)
            n, p, s_max = base_plan.n, base_plan.p, base_plan.s_max
            g = dyn.derive_gather_tables(cols, n, p, s_max)
            s = dyn.derive_scatter_tables(cols, n, p, s_max, gather=g)
            m, r = cols.shape
            dump_blk = base_plan.p * base_plan.b_max * base_plan.blocksize
            self._dyn_send_local_idx = np.asarray(g.send_local_idx)
            self.splan = ScatterPlan(
                base=base_plan,
                tgt_global=cols.astype(np.int32),
                cond_msg_idx=np.asarray(s.cond_msg_idx),
                blk_msg_idx=np.full((m, r), dump_blk, np.int32),
                own_tgt_idx=np.asarray(s.own_tgt_idx),
                win_mask=np.asarray(s.win_mask),
                touched=np.asarray(s.touched),
                counts=transpose_counts(base_plan),
            )
            return
        self.splan = plan_cache.get_scatter_plan(
            self.pattern.indices, base_plan.n, base_plan.p,
            blocksize=base_plan.blocksize, topology=base_plan.topology,
            base=base_plan, cache=self._use_plan_cache,
        )

    def _ranking_plan(self, base_plan: CommPlan):
        return self.splan

    def _bind(self, base_plan: CommPlan, strategy: str) -> None:
        mesh, axis_name = self.mesh, self.axis_name
        self.plan = base_plan  # the shared (direction-agnostic) base plan
        splan = self.splan

        shard = NamedSharding(mesh, P(axis_name))
        self.in_specs = strat.scatter_in_specs(strategy, axis_name)
        if self.dynamic_pattern is not None:
            if self.slot_major:
                raise ValueError("slot_major=True needs a static pattern")
            # same substitution as the gather: the envelope base plan's
            # accumulate-unpack table may belong to a different founding
            # routing, so the static surface carries the template's own
            # device-derived table (the other four came from _prepare)
            device_args = (splan.cond_msg_idx, self._dyn_send_local_idx,
                           splan.own_tgt_idx, splan.win_mask, splan.touched)
        else:
            device_args = strat.scatter_plan_device_args(splan, strategy)
            if self.slot_major:
                # every (m, r) table pairs with one contribution: lay each
                # shard's block out like the contributions themselves
                device_args = tuple(
                    strat.shard_slot_major(a, self.p).reshape(-1)
                    if i in strat.SCATTER_SLOT_TABLES[strategy] else a
                    for i, a in enumerate(device_args))
        self.plan_args = tuple(
            jax.device_put(a, shard) for a in device_args
        )
        self._start, self._finish = strat.make_scatter_start_local(
            splan, strategy, axis_name, self.reduce,
            use_kernel=self.use_kernel)

        self._scatter_all = jax.jit(jax.shard_map(
            self.local,
            mesh=mesh,
            in_specs=(P(axis_name),) + self.in_specs,
            out_specs=P(axis_name),
            check_vma=False,
        ))

    @property
    def counts(self):
        """Put-direction per-shard volume counts (§5 put-model inputs)."""
        return self.splan.counts

    # ---- shard_map-local surface (compose inside a consumer's step) ----
    def local(self, vals_local: jax.Array, *plan_args) -> jax.Array:
        """One-shot local scatter: contributions (rows, r, feat...) ->
        combined owned slice (shard_size, feat...)."""
        in_flight = self._start(vals_local, *plan_args)
        return self._finish(in_flight, vals_local, *plan_args)

    def start_local(self, vals_local: jax.Array,
                    *plan_args) -> ScatterHandle:
        """Pack + issue the exchange; compute while it flies.  The
        own-shard accumulate runs inside ``finish`` and has no dependency
        on the collective, so the scheduler hides the exchange behind it
        (plus anything the consumer schedules in between)."""
        in_flight = self._start(vals_local, *plan_args)

        def finish():
            return self._finish(in_flight, vals_local, *plan_args)

        return ScatterHandle(vals_local=vals_local, _finish=finish)

    # ---- dynamic surface (per-batch patterns, see repro.comm.dynamic) ----
    def derive_plan_args(self, cols, gather_tables=None) -> tuple:
        """Traced per-batch replacement for ``plan_args``.

        ``cols`` is this batch's (m, r) int32 target table — traced inside
        the consumer's jit.  Pass ``gather_tables`` (the
        ``DynamicGatherTables`` a sibling gather of the same pattern
        already derived) to share the one sort between both directions —
        the ``CommPlan.transpose()`` economy, in-jit.  Returns the five
        condensed/overlap executor tables in ``in_specs`` order.  The
        caller records ``telemetry.record("device-derive")`` once per call
        (not here: this body runs once per trace).
        """
        if self.strategy not in dyn.DYNAMIC_STRATEGIES:
            raise ValueError(
                f"derive_plan_args serves {dyn.DYNAMIC_STRATEGIES} "
                f"executor tables, not {self.strategy!r}")
        n, p, s_max = self.plan.n, self.p, self.plan.s_max
        g = gather_tables
        if g is None:
            g = dyn.derive_gather_tables(cols, n, p, s_max)
        s = dyn.derive_scatter_tables(cols, n, p, s_max, gather=g)
        return (s.cond_msg_idx, g.send_local_idx, s.own_tgt_idx,
                s.win_mask, s.touched)

    # ---- standalone surface ----
    def shard_values(self, vals) -> jax.Array:
        """Place a host (m, r, feat...) contribution table on the mesh,
        sharded over accessor rows like the plan expects (the scatter-
        flavored name for the inherited contiguous placement)."""
        return self.shard_vector(vals)

    def __call__(self, vals: jax.Array) -> jax.Array:
        """Combined length-n vector (plus feature dims), sharded over the
        owning devices: y[t] = reduce of all contributions targeting t."""
        return self._scatter_all(vals, *self.plan_args)
