"""IrregularGather — the pull-direction front door to the strategy ladder.

One object owns everything the paper's §4 machinery needs for one access
pattern on one mesh: the one-time ``CommPlan`` (persistently cached), the
resolved strategy (any ladder rung or ``"auto"`` via the §5 models), the
device-resident plan arrays, and the ``shard_map``-local gather functions.
The direction-agnostic machinery (plan resolution, rung dispatch, hardware
calibration memo, the ``OverlapHandle`` protocol) lives in
``repro.comm.exchange`` and is shared with the push-direction
``IrregularScatter``.

Consumers compose it two ways:

* standalone: ``x_copy_all = gather(x)`` returns every device's private copy
  stacked (row q = device q's ``mythread_x_copy``) — convenient for tests
  and simple pipelines;
* fused: the consumer threads ``gather.plan_args`` through its own
  ``shard_map`` (as operands, with ``gather.in_specs`` — each device must
  see only its slice) and calls ``gather.local(x_local, *plan_args_l)``
  inside — or, to hide the exchange behind own-shard compute (the
  generalized own/foreign split of the ``overlap`` rung), the
  ``OverlapHandle`` protocol::

      def step_local(x_local, *plan_args_l):
          handle = gather.start_local(x_local, *plan_args_l)  # issued
          y_own = ...                           # depends on x_local only
          x_copy = handle.finish()              # unpack landed messages
          return y_own + foreign_part(x_copy)

      mapped = shard_map(step_local, mesh=mesh,
                         in_specs=(P(axis),) + gather.in_specs, ...)
      y = jax.jit(lambda x: mapped(x, *gather.plan_args))(x)

  XLA's latency-hiding scheduler overlaps the collective with everything
  scheduled between ``start_local`` and ``finish`` that does not consume the
  collective's result.

With a ``Destination`` descriptor (named consumer slots — halo strips,
EllPack rows, expert-capacity slots), ``finish()`` / ``local()`` default to
``materialize="dest"``: the landed recv buffer is scattered straight into
the named slots and returned as ``{name: slot_array}`` — O(slots + recv)
work, no full-length ``x_copy`` ever assembled.  ``materialize="full"``
keeps the classic assembled copy on the same gather, bit-identically, and
``strategy="auto"`` prices whichever unpack the consumer will actually run
(the §5 extension in docs/perf_model.md).

The shared vector may carry trailing feature dimensions (token embeddings,
stacked right-hand sides): strategies move whole feature rows and all §5
volumes scale by the feature width.  A chain of exchanges fuses through
the third front door, ``repro.comm.schedule`` — there a gather is one
*stage*, constructed against the schedule's shared plan/calibration
context (a single-stage schedule is bit-identical to this class).  See
docs/comm_api.md for runnable walkthroughs of every surface.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import dynamic as dyn
from repro.comm import plan_cache
from repro.comm import strategies as strat
from repro.comm import telemetry
from repro.comm.exchange import IrregularExchange, OverlapHandle
from repro.comm.pattern import AccessPattern, Destination
from repro.comm.plan import CommPlan
from repro.comm.shared import SharedVector

__all__ = ["IrregularGather", "OverlapHandle"]


class IrregularGather(IrregularExchange):
    """Plan + strategy + device state for gathering one ``AccessPattern``
    over one mesh axis (or tuple of axes)."""

    direction = "get"

    def __init__(
        self,
        pattern: AccessPattern,
        where: jax.sharding.Mesh | SharedVector,
        *,
        destination: Destination | None = None,
        dest_slots: int | None = None,
        **kwargs,
    ):
        """``destination`` may be a ``Destination`` or a callable
        ``(resolved_strategy, base_plan) -> Destination`` for consumers
        whose slot layout depends on the resolved rung (e.g. SpMV targets
        foreign slots only under ``overlap``); it is materialized and
        attached once, after strategy resolution, so no throwaway plan
        entry is ever cached.  ``dest_slots`` is the flattened slot count
        the auto ranking prices when ``destination`` is a callable (a
        plain ``Destination`` knows its own).  Remaining keyword arguments
        (``axis_name``, ``strategy``, ``blocksize``, ``shards_per_node``,
        ``topology``, ``hw``, ``candidates``, ``use_plan_cache``,
        ``use_kernel``) are the shared ``IrregularExchange`` surface."""
        self._destination_arg = destination
        self._dest_slots = dest_slots
        super().__init__(pattern, where, **kwargs)

    def _price_kwargs(self) -> dict:
        kw = super()._price_kwargs()
        destination = self._destination_arg
        if destination is None:
            return kw
        # with a destination, price the targeted O(slots + recv) unpack
        # instead of the O(n) full-copy assembly (§5 + the new term)
        if callable(destination):
            if self._dest_slots is None:
                raise ValueError(
                    'strategy="auto" with a callable destination '
                    "requires dest_slots= — the flattened slot "
                    "count the ranking prices (otherwise the "
                    "targeted unpack would be priced at 0 slots "
                    "and skew the rung selection)")
            slots = self._dest_slots
        else:
            slots = destination.num_slots
        kw.update(materialize="dest", dest_slots=slots)
        return kw

    def _bind(self, base_plan: CommPlan, strategy: str) -> None:
        mesh, axis_name, p, n = self.mesh, self.axis_name, self.p, self.pattern.n
        destination = self._destination_arg
        if self.dynamic_pattern is not None and destination is not None:
            raise ValueError(
                "Destination descriptors are host-precomputed per pattern "
                "and cannot serve a DynamicPattern (whose tables change "
                "every batch) — land with materialize='full' instead")
        with telemetry.span("plan.destination"):
            if callable(destination):
                destination = destination(strategy, base_plan)
            if destination is not None:
                assert destination.p == p, (
                    f"destination has {destination.p} per-device slot "
                    f"tables for a {p}-shard mesh axis")
                assert destination.indices.max() < n, (
                    "destination indices must lie in [-1, n)")
        if destination is not None:
            self.plan: CommPlan = plan_cache.get_comm_plan(
                self.pattern.indices, n, p, blocksize=base_plan.blocksize,
                topology=base_plan.topology, destination=destination,
                base=base_plan, cache=self._use_plan_cache,
            )
        else:
            self.plan = base_plan
        self.destination = destination

        with_dest = destination is not None
        shard = NamedSharding(mesh, P(axis_name))
        self.in_specs = strat.gather_in_specs(strategy, axis_name,
                                              with_dest=with_dest)
        if self.dynamic_pattern is not None:
            # on a bucket-reuse hit the envelope plan's index tables belong
            # to the entry's founding routing, not this template — derive
            # the template's own tables on device (bit-identical to a host
            # build at the envelope s_max) so the static surface stays
            # honest; per-batch consumers swap in derive_plan_args(cols)
            g = dyn.derive_gather_tables(
                self.pattern.indices, n, p, self.plan.s_max)
            device_args = (g.send_local_idx, g.recv_global_idx)
        else:
            device_args = strat.plan_device_args(self.plan, strategy,
                                                 with_dest=with_dest)
        self.plan_args = tuple(
            jax.device_put(a, shard) for a in device_args
        )
        self._start, self._finish = strat.make_start_local(
            self.plan, strategy, axis_name, use_kernel=self.use_kernel)

        def gather_only_local(x_local, *plan_args):
            recv = self._start(x_local, *plan_args)
            return self._finish(recv, x_local, *plan_args,
                                materialize="full")[None]

        self._gather_all = jax.jit(jax.shard_map(
            gather_only_local,
            mesh=mesh,
            in_specs=(P(axis_name),) + self.in_specs,
            out_specs=P(axis_name),
            check_vma=False,
        ))

    def _resolve_materialize(self, materialize: str | None) -> str:
        if materialize is None:
            return "dest" if self.destination is not None else "full"
        if materialize == "dest" and self.destination is None:
            raise ValueError(
                'materialize="dest" requires constructing the gather with '
                "a Destination descriptor")
        if materialize not in ("dest", "full"):
            raise ValueError(f"unknown materialize mode {materialize!r}")
        return materialize

    # ---- shard_map-local surface (compose inside a consumer's step) ----
    def local(self, x_local: jax.Array, *plan_args,
              materialize: str | None = None):
        """One-shot local gather.

        ``materialize="full"`` (default without a destination): x_local
        (shard, ...) -> x_copy (>= n, ...).  ``materialize="dest"`` (default
        with one): -> ``{name: slots}`` named consumer buffers, no
        full-length intermediate.
        """
        mode = self._resolve_materialize(materialize)
        recv = self._start(x_local, *plan_args)
        out = self._finish(recv, x_local, *plan_args, materialize=mode)
        if mode == "dest":
            return self.destination.split_local(out)
        return out

    def start_local(self, x_local: jax.Array, *plan_args) -> OverlapHandle:
        """Issue the exchange; compute on ``x_local`` while it flies."""
        in_flight = self._start(x_local, *plan_args)

        def finish(*, extra_slots=0, copy_own=True, materialize=None):
            mode = self._resolve_materialize(materialize)
            out = self._finish(in_flight, x_local, *plan_args,
                               extra_slots=extra_slots, copy_own=copy_own,
                               materialize=mode)
            if mode == "dest":
                return self.destination.split_local(out)
            return out

        return OverlapHandle(x_local=x_local, _finish=finish)

    # ---- dynamic surface (per-batch patterns, see repro.comm.dynamic) ----
    def derive_plan_args(self, cols) -> tuple:
        """Traced per-batch replacement for ``plan_args``.

        ``cols`` is this batch's (m, r) int32 global index table — a traced
        array inside the consumer's jit (replicated; derivation runs
        *outside* the ``shard_map``).  Returns the condensed/overlap
        executor tables ``(send_local_idx, recv_global_idx)`` computed on
        device, bit-identical to a host plan build at the envelope
        ``s_max``; feed them through the unchanged ``in_specs`` in place of
        the static ``plan_args``.  No host round-trip, no plan-cache probe
        — the caller records ``telemetry.record("device-derive")`` once per
        *call* (not here: this body runs once per trace).
        """
        if self.strategy not in dyn.DYNAMIC_STRATEGIES:
            raise ValueError(
                f"derive_plan_args serves {dyn.DYNAMIC_STRATEGIES} "
                f"executor tables, not {self.strategy!r}")
        if self.destination is not None:
            raise ValueError(
                "derive_plan_args cannot rebuild host-precomputed "
                "Destination arrays")
        g = dyn.derive_gather_tables(cols, self.plan.n, self.p,
                                     self.plan.s_max)
        return (g.send_local_idx, g.recv_global_idx)

    # ---- standalone surface ----
    def __call__(self, x: jax.Array) -> jax.Array:
        """(P, >=n, ...) array: row q is device q's private x_copy.

        Always the full materialization (tests and simple pipelines want
        the global-indexable copy), regardless of any ``Destination``.
        """
        return self._gather_all(x, *self.plan_args)
