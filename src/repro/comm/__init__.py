"""repro.comm — the paper's irregular-communication runtime, workload-agnostic.

The optimization unit is an ``AccessPattern`` (which global elements of a
``SharedVector`` does each accessor touch), not any one workload — and not
any one *direction*: ``IrregularGather`` (pull — accessors read their
elements) and ``IrregularScatter`` (push — accessors contribute to their
elements, duplicates combining under ``reduce="add"|"set"|"max"``) are the
two front doors over one shared exchange core.  Each plans once (§4.3.1,
persistently cached; the scatter plan is the gather plan with send/recv
tables swapped, ``CommPlan.transpose()``), picks a ladder rung (§4) by hand
or by the §5 models (``perfmodel``; ``strategy="auto"``,
``blocksize="auto"`` — put-model pricing for scatters — fed by the one
hardware calibration, ``exchange.measure_hw``), and exposes both a
standalone call and ``shard_map``-local functions — including the handle-based
start/compute/finish protocol that generalizes the own/foreign split.

A ``Destination`` descriptor names *where* gathered values land (halo
strips, EllPack slots, expert-capacity rows): with one attached, every
strategy's ``finish`` scatters the landed recv buffer straight into the
consumer's named slots — O(slots + recv) work — instead of assembling the
O(n) full-length private copy (still available via
``finish(materialize="full")``).

The third, higher-level front door is the ``Schedule`` builder /
``ExchangeSchedule``: a declared *chain* of exchanges
(gather → compute → scatter, any length) compiled into one ``shard_map``
whose stages share a single exchange-core context (one hw-calibration
memo hit, one base-plan probe per pattern, transpose-derived scatter
plans reused from sibling gathers) and pipeline through the handle
protocol — priced as one consolidated window by
``perfmodel.predict_schedule``.

Consumers: ``repro.core.spmv`` (the paper's workload, plus its transposed
product ``transpose=True`` via scatter-accumulate), ``repro.core.heat2d``
(§8 stencil halos), ``repro.models.moe`` (token→expert dispatch gather and
its inverse, the weighted expert→token combine scatter).  See
``docs/comm_api.md`` for the API walkthrough and ``docs/perf_model.md`` for
the paper-formula-to-code map.
"""
from repro.comm.pattern import AccessPattern, Destination
from repro.comm.shared import SharedVector
from repro.comm.plan import (CommPlan, GatherCounts, ScatterPlan, Topology,
                             attach_destination, build_comm_plan,
                             blockwise_block_counts, derive_scatter_plan)
from repro.comm.plan_cache import (get_comm_plan, get_envelope_plan,
                                   get_scatter_plan)
from repro.comm.dynamic import (DynamicPattern, derive_gather_tables,
                                derive_scatter_tables, envelope_s_max)
from repro.comm.strategies import SCATTER_REDUCES, STRATEGIES
from repro.comm.exchange import IrregularExchange
from repro.comm.gather import IrregularGather, OverlapHandle
from repro.comm.scatter import IrregularScatter, ScatterHandle
from repro.comm.schedule import ExchangeSchedule, Schedule, StageRef
from repro.comm import (plan, plan_cache, pattern, perfmodel, shared,
                        strategies, select)
from repro.comm import dynamic, exchange, gather, scatter, schedule
from repro.comm import telemetry

__all__ = [
    "AccessPattern", "Destination", "SharedVector", "IrregularExchange",
    "IrregularGather", "IrregularScatter", "OverlapHandle", "ScatterHandle",
    "ExchangeSchedule", "Schedule", "StageRef",
    "CommPlan", "GatherCounts", "ScatterPlan", "Topology", "DynamicPattern",
    "attach_destination", "build_comm_plan", "blockwise_block_counts",
    "derive_scatter_plan", "get_comm_plan", "get_scatter_plan",
    "get_envelope_plan", "derive_gather_tables", "derive_scatter_tables",
    "envelope_s_max", "STRATEGIES", "SCATTER_REDUCES",
    "plan", "plan_cache", "pattern", "perfmodel", "shared", "strategies",
    "select",
    "dynamic", "exchange", "gather", "scatter", "schedule", "telemetry",
]
