"""ExchangeSchedule — chains of exchanges fused into one planned window.

The paper optimizes one exchange at a time; real consumers issue *chains*
of them: MoE dispatch-gather → expert MLP → combine-scatter, SpMV
``y = A x`` followed by ``z = Aᵀ y``, a halo exchange before every stencil
step.  Run through the one-shot front doors, each link pays its own plan
resolution, hardware calibration, ``shard_map`` window and unpack.  A
``Schedule`` declares the whole chain up front so ``compile`` can resolve
every stage against **one shared exchange-core context**:

* one hardware-calibration memo hit (``exchange.measure_hw``) prices every
  ``strategy="auto"`` stage;
* one plan-cache probe batch — each unique pattern's destination-independent
  base ``CommPlan`` is resolved once and shared by every stage that uses it;
* a scatter stage whose pattern matches a sibling gather stage reuses that
  gather's base plan, so its executor tables are a cheap transpose-derived
  delta (``CommPlan.transpose()``), never a second O(nnz) build;
* the §5 composition model (``perfmodel.predict_schedule``) prices the
  *fused* window — per-stage eq. 12–15 / 12ᵀ–15ᵀ terms with the
  window-setup latency paid once per consolidated window — so ``"auto"``
  may pick a different rung per stage while sharing one consolidation
  point.

``compile`` emits a **single** ``shard_map``.  Inside it the stages
pipeline through the handle protocol: an exchange stage *issues* its
collective (``start_local``) when reached, and its landed messages are
delivered (``finish``) only when a later stage actually consumes them —
every stage scheduled in between runs inside the collective's window, and
a scatter's own-shard accumulate overlaps its own exchange by
construction.  Stage order in the builder is therefore the schedule: put
the compute that should hide an exchange *after* that exchange stage and
*before* the stage that reads its result.

``IrregularGather`` / ``IrregularScatter`` stay exactly what they were —
a schedule stage IS one of them, constructed against the shared context —
so a single-stage schedule is bit-identical to the one-shot front door
(shim-tested in ``tests/test_schedule.py``).

Time loops go one level further: ``Schedule.scan`` compiles the same stage
pipeline through ``lax.scan`` *inside* the single ``shard_map``, so the
exchange window is persistent across iterations — one plan-cache probe and
one hardware-calibration memo hit for the entire loop, and zero per-step
host dispatch (the whole n-step loop is one XLA program).  See
``ScanSchedule`` and docs/schedules.md for the carry and double-buffer
contracts.

>>> import jax, numpy as np
>>> from repro.comm import AccessPattern, Schedule
>>> p = len(jax.devices())
>>> mesh = jax.make_mesh((p,), ("data",))
>>> n = 16 * p
>>> rng = np.random.default_rng(0)
>>> idx = rng.integers(0, n, size=(n, 3)).astype(np.int32)
>>> pattern = AccessPattern.from_indices(idx, n=n)
>>> sched = Schedule()
>>> x = sched.input("x")
>>> rows = sched.constant(idx)      # (n, 3) index table, row-sharded
>>> g = sched.gather(pattern, src=x)
>>> y = sched.compute(lambda xc, r: xc[r].sum(-1), g, rows)
>>> step = sched.compile(mesh, strategy="condensed", blocksize=8)
>>> xv = rng.standard_normal(n).astype(np.float32)
>>> out = np.asarray(step(step.shard_input(xv)))
>>> bool(np.allclose(out, xv[idx].sum(-1), rtol=1e-5))
True
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import perfmodel as pm
from repro.comm import plan_cache
from repro.comm import select
from repro.comm import strategies as strat
from repro.comm.exchange import measure_hw
from repro.comm.gather import IrregularGather
from repro.comm.pattern import AccessPattern
from repro.comm.plan import CommPlan, Topology
from repro.comm.scatter import IrregularScatter
from repro.comm.shared import axis_size

__all__ = ["Schedule", "ExchangeSchedule", "ScanSchedule", "StageRef"]


def _unwrap_dynamic(pattern) -> AccessPattern:
    """Schedules resolve stages against host plans, so a ``DynamicPattern``
    degrades to its template here (a documented limitation: per-batch
    device-derived tables inside a compiled schedule need the consumer to
    thread ``derive_plan_args`` output through its own shard_map — see
    ``models.moe.DynamicMoELayer`` for the fused pattern done by hand)."""
    from repro.comm.dynamic import DynamicPattern
    if isinstance(pattern, DynamicPattern):
        return pattern.template
    return pattern


@dataclasses.dataclass(frozen=True)
class StageRef:
    """Symbolic handle to one stage's output inside a ``Schedule``."""

    sid: int
    kind: str
    name: str
    owner: int = 0      # id() of the owning Schedule — refs don't cross


class _Stage:
    """Builder-side record of one stage (mutable until compile)."""

    def __init__(self, sid: int, kind: str, name: str, owner: int, **kw):
        self.sid = sid
        self.kind = kind
        self.name = name
        self.owner = owner
        self.__dict__.update(kw)

    @property
    def ref(self) -> StageRef:
        return StageRef(self.sid, self.kind, self.name, self.owner)


class Schedule:
    """Declarative builder for an ``ExchangeSchedule``.

    Build stages in execution order (the order IS the pipeline schedule),
    then ``compile(mesh, strategy="auto")``::

        sched = Schedule()
        h = sched.gather(pattern, destination=dest)
        y = sched.compute(expert_fn, h, weights)
        sched.scatter(pattern, y, reduce="add")
        step = sched.compile(mesh, strategy="auto")

    ``resolve`` may be called explicitly before the compute stages are
    added when a later stage's shape depends on the resolved rung (e.g.
    Heat2D only adds its interior stage when ``"auto"`` picks ``overlap``).
    """

    def __init__(self):
        self._stages: list[_Stage] = []
        self._ctx: dict | None = None       # set by resolve()
        self._exchanges: dict[int, Any] = {}
        self._compiled = False

    # ---- builder surface ----
    def _add(self, kind: str, name: str | None, **kw) -> StageRef:
        assert not self._compiled, "schedule already compiled"
        sid = len(self._stages)
        name = name or f"{kind}{sid}"
        if any(s.name == name for s in self._stages):
            raise ValueError(
                f"duplicate stage name {name!r} — names key the "
                ".strategies/.predicted_times reporting, so each stage "
                "needs its own")
        st = _Stage(sid, kind, name, id(self), **kw)
        self._stages.append(st)
        return st.ref

    def _check_ref(self, ref, *, array_valued: bool = False) -> StageRef:
        assert isinstance(ref, StageRef), (
            f"stage arguments must be StageRefs, got {type(ref).__name__}")
        if ref.owner != id(self):
            raise ValueError(
                f"stage ref {ref.name!r} belongs to a different Schedule "
                "— refs cannot cross builders")
        assert 0 <= ref.sid < len(self._stages), ref
        st = self._stages[ref.sid]
        if array_valued and st.kind == "gather" and st.destination is not None:
            raise ValueError(
                f"stage {st.name!r} delivers named Destination slots (a "
                "dict); wrap it in a compute stage that selects/combines "
                "the slots before feeding an exchange")
        return ref

    def input(self, name: str | None = None, *, spec=None) -> StageRef:
        """Declare an external operand of the compiled step (call-time
        positional argument, in declaration order).  ``spec`` is its
        ``PartitionSpec`` (default: sharded over the comm axis)."""
        return self._add("input", name, spec=spec)

    def constant(self, value, name: str | None = None, *, spec=None,
                 replicated: bool = False) -> StageRef:
        """Bind a fixed array operand (matrix values, expert weights,
        combine weights).  It is ``device_put`` once at compile time and
        rides the single ``shard_map`` with ``spec`` (default: dim 0
        sharded over the comm axis; ``replicated=True`` for ``P()``)."""
        if replicated:
            assert spec is None, "pass spec OR replicated, not both"
            spec = P()
        return self._add("constant", name, value=value, spec=spec)

    def gather(self, pattern: AccessPattern, *, src: StageRef | None = None,
               destination=None, dest_slots: int | None = None,
               strategy: str | None = None, blocksize=None,
               use_kernel: bool | None = None,
               finish_kwargs: dict | None = None,
               double_buffer: bool = False, prime: StageRef | None = None,
               name: str | None = None) -> StageRef:
        """Pull stage: deliver ``pattern``'s elements of the ``src`` value
        (default: the first declared input, auto-declared if absent).

        The stage value is the strategy's default materialization: the
        ``{name: slots}`` dict with a ``destination``, else the full
        ``x_copy``.  ``strategy`` / ``blocksize`` / ``use_kernel`` override
        the schedule defaults per stage; ``finish_kwargs`` are forwarded to
        ``OverlapHandle.finish`` (``extra_slots=`` / ``copy_own=``).

        ``double_buffer=True`` (only under ``Schedule.scan``): the stage's
        value is the delivery of the exchange issued by this schedule's
        matching ``feed()`` stage one iteration EARLIER, carried across the
        scan boundary — so the compute of iteration k+1 hides inside the
        window opened during iteration k.  Such a stage has no in-body
        ``src``; ``prime=`` names the exchange-free stage whose value seeds
        iteration 0's exchange before the loop starts."""
        if double_buffer:
            if src is not None:
                raise ValueError(
                    "a double_buffer gather has no in-body src: its value "
                    "is the delivery of the exchange issued by feed() one "
                    "iteration earlier — pass prime= (the stage seeding "
                    "iteration 0) and add a feed() stage instead")
            if prime is None:
                raise ValueError(
                    "double_buffer=True needs prime= — the stage whose "
                    "value seeds iteration 0's exchange in the scan "
                    "prologue (it must not depend on any exchange stage)")
            src = prime
        elif prime is not None:
            raise ValueError("prime= only applies to double_buffer=True")
        else:
            if src is None:
                src = next((s.ref for s in self._stages
                            if s.kind == "input"), None)
                if src is None:
                    src = self.input()
        self._check_ref(src, array_valued=True)
        pattern = _unwrap_dynamic(pattern)
        return self._add("gather", name, pattern=pattern, src=src,
                         destination=destination, dest_slots=dest_slots,
                         strategy=strategy, blocksize=blocksize,
                         use_kernel=use_kernel,
                         double_buffer=double_buffer,
                         finish_kwargs=dict(finish_kwargs or {}))

    def compute(self, fn: Callable, *args: StageRef,
                name: str | None = None) -> StageRef:
        """Local compute stage: ``fn(*values)`` runs per device inside the
        fused ``shard_map``, where each value is the referenced stage's
        device-local output.  A compute stage placed after an exchange
        stage but before anything consumes that exchange runs inside its
        collective window."""
        for a in args:
            self._check_ref(a)
        return self._add("compute", name, fn=fn, args=tuple(args))

    def feed(self, gather: StageRef, src: StageRef, *,
             name: str | None = None) -> StageRef:
        """Issue the NEXT iteration's exchange of a ``double_buffer``
        gather stage (only meaningful under ``Schedule.scan``).

        ``src``'s value — typically this iteration's refreshed operand —
        is packed and sent where the feed stage sits in the pipeline; the
        delivery is finished at the end of the body and carried across the
        scan boundary, becoming the gather stage's value next iteration.
        Every stage between the feed and the end of the body (and the next
        iteration's stages up to the gather's first consumer) runs inside
        the collective's window.  The final iteration's feed issues one
        exchange whose delivery is never consumed — the price of the
        branch-free scan body."""
        self._check_ref(gather)
        g = self._stages[gather.sid]
        if g.kind != "gather" or not g.double_buffer:
            raise ValueError(
                "feed() targets a gather(double_buffer=True, ...) stage; "
                f"{g.name!r} is not one")
        self._check_ref(src, array_valued=True)
        if any(s.kind == "feed" and s.gather.sid == gather.sid
               for s in self._stages):
            raise ValueError(
                f"stage {g.name!r} already has a feed() stage — a "
                "double-buffer depth of one carries exactly one in-flight "
                "exchange")
        return self._add("feed", name, gather=gather, src=src)

    def scatter(self, pattern: AccessPattern, src: StageRef, *,
                reduce: str = "add", strategy: str | None = None,
                blocksize=None, use_kernel: bool | None = None,
                name: str | None = None) -> StageRef:
        """Push stage: ``src``'s value is the (rows_local, r, feat...)
        contribution table; the stage value is the combined owned slice.
        A pattern already gathered by a sibling stage reuses its base plan
        (the scatter tables are a transpose-derived delta).  ``strategy`` /
        ``blocksize`` / ``use_kernel`` override the schedule defaults per
        stage."""
        self._check_ref(src, array_valued=True)
        if reduce not in strat.SCATTER_REDUCES:
            raise ValueError(f"reduce must be one of {strat.SCATTER_REDUCES}")
        pattern = _unwrap_dynamic(pattern)
        return self._add("scatter", name, pattern=pattern, src=src,
                         reduce=reduce, strategy=strategy,
                         blocksize=blocksize, use_kernel=use_kernel)

    # ---- resolution (shared exchange-core context) ----
    def _exchange_stages(self) -> list[_Stage]:
        return [s for s in self._stages if s.kind in ("gather", "scatter")]

    def resolve(self, mesh, *, axis_name="data", strategy: str = "auto",
                blocksize=None, use_kernel: bool = False,
                topology: Topology | None = None,
                shards_per_node: int | None = None, hw=None,
                use_plan_cache: bool = True,
                scan_steps: int | None = None) -> "Schedule":
        """Resolve every exchange stage against one shared context: one
        ``measure_hw`` memo hit, one base-plan probe per unique pattern,
        transpose-derived scatter plans reused from sibling gathers.

        ``use_kernel`` is the schedule-wide default for the fused Pallas
        pack/unpack path (each stage's own ``use_kernel=`` wins when set);
        ``"auto"`` stages are priced with the kernelized compute terms so
        the ranking matches what the window will actually run.

        ``scan_steps`` (set by ``Schedule.scan(n_steps_hint=...)``) makes
        every ``"auto"`` stage rank rungs on the n-step steady-state LOOP
        cost (``perfmodel.scan_loop_cost`` — window setup paid once)
        instead of the single-call cost.

        Idempotent prerequisite of ``compile``; call it explicitly when a
        later stage's shape depends on a resolved rung
        (``strategy_of(ref)``)."""
        assert self._ctx is None, "schedule already resolved"
        exchanges = self._exchange_stages()
        assert exchanges, "a schedule needs at least one exchange stage"
        p = axis_size(mesh, axis_name)
        if topology is None:
            topology = Topology(p, shards_per_node or p)

        needs_hw = any((s.strategy or strategy) == "auto"
                       or (s.blocksize if s.blocksize is not None
                           else blocksize) == "auto"
                       for s in exchanges)
        if needs_hw and hw is None:
            hw = measure_hw(mesh, axis_name)   # ONE memo hit for all stages

        # one plan-cache probe per unique (pattern, blocksize): every stage
        # over the same index set shares one base CommPlan object, so a
        # scatter stage derives its executor tables from the sibling
        # gather's plan instead of rebuilding
        base_plans: dict[str, CommPlan] = {}
        for st in exchanges:
            bs = st.blocksize if st.blocksize is not None else blocksize
            if bs == "auto":
                bs = select.choose_blocksize(
                    st.pattern.indices, st.pattern.n, p, topology=topology,
                    hw=hw)
            shard_size = st.pattern.n // p
            bs_key = shard_size if bs is None else bs
            key = plan_cache.plan_key(st.pattern.indices, st.pattern.n, p,
                                      bs_key, topology)
            if key not in base_plans:
                base_plans[key] = plan_cache.get_comm_plan(
                    st.pattern.indices, st.pattern.n, p, blocksize=bs,
                    topology=topology, cache=use_plan_cache)
            st_strategy = st.strategy if st.strategy is not None else strategy
            st_use_kernel = (st.use_kernel if st.use_kernel is not None
                             else use_kernel)
            kwargs = dict(axis_name=axis_name, strategy=st_strategy,
                          topology=topology, hw=hw,
                          use_plan_cache=use_plan_cache,
                          base_plan=base_plans[key],
                          scan_steps=scan_steps,
                          use_kernel=st_use_kernel)
            if st.kind == "gather":
                ex = IrregularGather(
                    st.pattern, mesh, destination=st.destination,
                    dest_slots=st.dest_slots, **kwargs)
            else:
                ex = IrregularScatter(st.pattern, mesh, reduce=st.reduce,
                                      **kwargs)
            self._exchanges[st.sid] = ex

        self._ctx = dict(mesh=mesh, axis_name=axis_name, topology=topology,
                         hw=hw, default_strategy=strategy)
        return self

    def exchange_of(self, ref: StageRef):
        """The resolved ``IrregularGather``/``IrregularScatter`` behind one
        exchange stage (available after ``resolve``)."""
        assert self._ctx is not None, "call resolve()/compile() first"
        return self._exchanges[ref.sid]

    def strategy_of(self, ref: StageRef) -> str:
        """The resolved rung of one exchange stage."""
        return self.exchange_of(ref).strategy

    def _stage_specs(self):
        """Per-exchange-stage §5 pricing specs: the ``(name, direction,
        workload, strategy)`` rows ``perfmodel.predict_schedule`` /
        ``predict_scan_schedule`` consume (available after ``resolve``)."""
        specs = []
        for st in self._exchange_stages():
            ex = self._exchanges[st.sid]
            if st.kind == "gather":
                materialize = "dest" if ex.destination is not None else None
                dest_slots = (ex.destination.num_slots
                              if ex.destination is not None else None)
                w = select.workload_from_plan(
                    ex.plan, st.pattern.r, materialize=materialize,
                    dest_slots=dest_slots, use_kernel=ex.use_kernel)
                specs.append((st.name, "get", w, ex.strategy))
            else:
                w = select.workload_from_plan(ex.splan, st.pattern.r,
                                              use_kernel=ex.use_kernel)
                specs.append((st.name, "put", w, ex.strategy))
        return specs

    def _predict_window(self):
        """§5 fused-window composition for the resolved rungs (None when
        no hardware parameters are in scope)."""
        hw = self._ctx["hw"]
        if hw is None:
            return None
        return pm.predict_schedule(self._stage_specs(), hw)

    def _finish_build(self, mesh, resolve_kw):
        assert not self._compiled, "schedule already compiled"
        if self._ctx is None:
            assert mesh is not None, "compile() needs a mesh (or resolve())"
            self.resolve(mesh, **resolve_kw)
        else:
            assert mesh is None or mesh is self._ctx["mesh"], (
                "schedule was resolved on a different mesh")
            if resolve_kw:
                raise ValueError(
                    "schedule already resolved — these compile() keywords "
                    f"would be silently ignored: {sorted(resolve_kw)}; "
                    "pass them to resolve() instead")

    # ---- compilation (the single shard_map) ----
    def compile(self, mesh=None, *, output=None,
                out_spec=None, **resolve_kw) -> "ExchangeSchedule":
        """Finalize into an ``ExchangeSchedule``: one ``shard_map`` whose
        stages pipeline through the handle protocol.

        ``output`` picks the stage whose value the step returns (default:
        the last stage; must be array-valued) — a tuple of refs makes the
        step return the matching tuple; ``out_spec`` its ``PartitionSpec``
        (or tuple thereof; default: sharded over the comm axis).  ``mesh``
        and the remaining keywords are forwarded to ``resolve`` unless it
        already ran."""
        bad = [s.name for s in self._stages
               if (s.kind == "feed"
                   or (s.kind == "gather" and s.double_buffer))]
        if bad:
            raise ValueError(
                f"stages {bad} double-buffer across iterations; a one-shot "
                "compile() has no previous iteration to carry the delivery "
                "from — build them through Schedule.scan() instead")
        self._finish_build(mesh, resolve_kw)
        if output is None:
            output = self._stages[-1].ref
        single = not isinstance(output, (tuple, list))
        outputs = (output,) if single else tuple(output)
        for o in outputs:
            self._check_ref(o, array_valued=True)
        self._compiled = True
        return ExchangeSchedule(self, outputs, out_spec, single=single)

    def scan(self, mesh=None, *, carry, output,
             n_steps_hint: int | None = None,
             **resolve_kw) -> "ScanSchedule":
        """Finalize into a ``ScanSchedule``: the stage pipeline becomes the
        body of a ``lax.scan`` running INSIDE one persistent ``shard_map``
        window — plans, calibration and dispatch are paid once for the
        whole loop, not per step.

        ``carry`` — every declared input stage, as a tuple of refs in call
        order (a bare ref for a single carry); ``output`` — a matching
        tuple: the stage whose value becomes the corresponding carry next
        iteration (and the loop's final result).  ``n_steps_hint`` prices
        ``strategy="auto"`` stages on the hinted steady-state loop cost
        (setup amortized) instead of the single-call cost.  The compiled
        object is called as ``scan(*carries, n_steps=k)`` with ``n_steps``
        static per compilation."""
        single = not isinstance(carry, (tuple, list))
        carry = (carry,) if single else tuple(carry)
        output = (output,) if not isinstance(output, (tuple, list)) \
            else tuple(output)
        if self._ctx is None:
            resolve_kw.setdefault("scan_steps", n_steps_hint)
        self._finish_build(mesh, resolve_kw)
        self._compiled = True
        return ScanSchedule(self, carry, output, single=single,
                            n_steps_hint=n_steps_hint)


def _bind_operands(stages, exchanges, mesh, axis_name):
    """Operand layout shared by ``ExchangeSchedule`` and ``ScanSchedule``:
    all inputs first (call order), then per-stage bound operands
    (constants + plan arrays) in stage order.  Returns ``(input_sids,
    input_specs, step_args, bound_specs, slots)`` with ``slots[sid]`` the
    slice of the bound-args tuple belonging to stage ``sid``."""
    input_sids = [st.sid for st in stages if st.kind == "input"]
    input_specs = tuple(
        st.spec if st.spec is not None else P(axis_name)
        for st in stages if st.kind == "input")
    step_args: list = []
    bound_specs: list = []
    slots: dict[int, slice] = {}
    for st in stages:
        lo = len(step_args)
        if st.kind == "constant":
            spec = st.spec if st.spec is not None else P(axis_name)
            step_args.append(jax.device_put(
                np.asarray(st.value), NamedSharding(mesh, spec)))
            bound_specs.append(spec)
            st.value = None   # free the host copy; only the device
            # array (in step_args) is ever read again
        elif st.kind in ("gather", "scatter"):
            ex = exchanges[st.sid]
            step_args.extend(ex.plan_args)
            bound_specs.extend(ex.in_specs)
        slots[st.sid] = slice(lo, len(step_args))
    return (input_sids, input_specs, tuple(step_args), tuple(bound_specs),
            slots)


def _run_stages(stages, exchanges, slots, input_pos, inputs, bound, *,
                db_vals=None, prologue=False):
    """Trace the stage pipeline once (one ``shard_map`` body, one scan
    body, or — with ``prologue=True`` — the exchange-free prefix that
    seeds a scan's double-buffer carries).

    Returns ``(force, finish_feeds)``: ``force(sid)`` delivers a stage's
    value, finishing any exchange it consumes lazily so everything
    scheduled between issue and first consumption runs inside the
    collective's window; ``finish_feeds()`` delivers the ``feed()``
    exchanges issued this body — the next iteration's double-buffer
    carries."""
    env: dict[int, Any] = {}
    pending: dict[int, Callable[[], Any]] = {}
    feeds: dict[int, Callable[[], Any]] = {}

    def force(sid):
        if sid in pending:
            env[sid] = pending.pop(sid)()
        return env[sid]

    def finish_of(handle, finish_kwargs):
        if finish_kwargs:
            return lambda h=handle, kw=finish_kwargs: h.finish(**kw)
        return handle.finish

    for st in stages:
        if st.kind == "input":
            env[st.sid] = inputs[input_pos[st.sid]]
        elif st.kind == "constant":
            (env[st.sid],) = bound[slots[st.sid]]
        elif st.kind == "compute":
            if prologue:
                continue   # forced on demand below only via ancestors
            vals = [force(a.sid) for a in st.args]
            env[st.sid] = st.fn(*vals)
        elif prologue:
            continue       # no exchange ever runs in the prologue
        elif st.kind == "feed":
            # issue the NEXT iteration's exchange of a double-buffer
            # gather; its delivery is collected by finish_feeds() at the
            # end of the body and carried across the scan boundary
            g = stages[st.gather.sid]
            ex = exchanges[g.sid]
            handle = ex.start_local(force(st.src.sid), *bound[slots[g.sid]])
            feeds[g.sid] = finish_of(handle, g.finish_kwargs)
            env[st.sid] = ()
        elif st.kind == "gather" and st.double_buffer:
            # value delivered by the previous iteration's feed()
            env[st.sid] = db_vals[st.sid]
        else:
            # exchange stage: ISSUE the collective now; deliver (finish)
            # lazily when a later stage consumes it — everything in
            # between runs inside its window
            ex = exchanges[st.sid]
            handle = ex.start_local(force(st.src.sid), *bound[slots[st.sid]])
            pending[st.sid] = finish_of(
                handle, st.finish_kwargs if st.kind == "gather" else None)

    if prologue:
        # compute stages were skipped above; force() must still be able to
        # evaluate the exchange-free ancestry of a prime ref on demand
        def force_prologue(sid):
            if sid not in env:
                st = stages[sid]
                assert st.kind == "compute", (
                    f"prologue reached a {st.kind!r} stage — prime refs "
                    "must have exchange-free ancestry")
                env[sid] = st.fn(*[force_prologue(a.sid) for a in st.args])
            return env[sid]
        return force_prologue, None

    def finish_feeds():
        return {sid: fn() for sid, fn in feeds.items()}

    return force, finish_feeds


class ExchangeSchedule:
    """A compiled multi-exchange step: one ``shard_map``, one fused window.

    * ``step(*inputs)`` — jitted end-to-end call (inputs in declaration
      order, placed like ``shard_input`` expects);
    * ``.mapped`` / ``.step_args`` / ``.in_specs`` — the raw
      ``shard_map``-ed local function and its bound operands, for
      consumers that embed the step in their own ``jit``/``scan``;
    * ``.strategies`` — resolved rung per exchange stage;
    * ``.predicted_times`` — per-stage §5 rung rankings (auto stages);
    * ``.predicted_window`` — the fused-window composition prediction
      (``perfmodel.predict_schedule``), with per-stage terms and the
      consolidation saving; ``None`` when no hardware parameters were in
      scope (every stage on a fixed rung and no ``hw=`` passed).
    """

    def __init__(self, sched: Schedule, outputs: tuple, out_spec,
                 single: bool = True):
        ctx = sched._ctx
        mesh, axis_name = ctx["mesh"], ctx["axis_name"]
        self.mesh = mesh
        self.axis_name = axis_name
        self.topology = ctx["topology"]
        self.hw = ctx["hw"]
        self._stages = sched._stages
        self._exchanges = sched._exchanges
        self._outputs = outputs
        self._single = single
        stages = self._stages

        self.strategies = {st.name: self._exchanges[st.sid].strategy
                           for st in stages
                           if st.kind in ("gather", "scatter")}
        self.predicted_times = {
            st.name: self._exchanges[st.sid].predicted_times
            for st in stages if st.kind in ("gather", "scatter")}
        self.predicted_window = sched._predict_window()

        (self._input_sids, self._input_specs, self.step_args, bound_specs,
         slots) = _bind_operands(stages, self._exchanges, mesh, axis_name)
        self.in_specs = self._input_specs + bound_specs
        n_inputs = len(self._input_sids)
        input_pos = {sid: i for i, sid in enumerate(self._input_sids)}
        exchanges = self._exchanges

        def step_local(*args):
            inputs, bound = args[:n_inputs], args[n_inputs:]
            force, _ = _run_stages(stages, exchanges, slots, input_pos,
                                   inputs, bound)
            vals = tuple(force(o.sid) for o in outputs)
            return vals[0] if single else vals

        if out_spec is None:
            out_specs = P(axis_name) if single \
                else tuple(P(axis_name) for _ in outputs)
        else:
            out_specs = out_spec if single else tuple(out_spec)
        self.mapped = jax.shard_map(
            step_local, mesh=mesh, in_specs=self.in_specs,
            out_specs=out_specs, check_vma=False,
        )
        # bound arrays are arguments, never closure constants (a
        # closed-over array is embedded in the compiled program)
        self._step = jax.jit(self.mapped)

    def shard_input(self, value, which: int = 0) -> jax.Array:
        """Place a host value on the mesh with input ``which``'s spec."""
        spec = self._input_specs[which]
        return jax.device_put(value, NamedSharding(self.mesh, spec))

    # kept as the SpMV-flavored alias every front door exposes
    def shard_vector(self, value) -> jax.Array:
        return self.shard_input(value, 0)

    def __call__(self, *inputs) -> jax.Array:
        return self._step(*inputs, *self.step_args)


def _exchange_free(stages, sid) -> bool:
    """True when stage ``sid``'s ancestry contains no exchange/feed stage
    (so the scan prologue can evaluate it from the initial carries)."""
    st = stages[sid]
    if st.kind in ("gather", "scatter", "feed"):
        return False
    if st.kind == "compute":
        return all(_exchange_free(stages, a.sid) for a in st.args)
    return True


class ScanSchedule:
    """A compiled scan-level schedule: ``lax.scan`` INSIDE one persistent
    ``shard_map`` window.

    Where ``ExchangeSchedule`` fuses a chain of exchanges into one window
    per call, ``ScanSchedule`` keeps that window open across a whole time
    loop: the scan body is the stage pipeline, so the entire n-step loop is
    ONE jitted XLA program entered once — one plan-cache probe and one
    ``measure_hw`` memo hit at build time, zero per-step host dispatch at
    run time.  ``n_steps`` is a static argument of the call: each distinct
    step count compiles once and is cached by jit.

    Carry contract: every ``input`` stage is a loop carry; calling
    ``scan(*carries, n_steps=k)`` runs ``k`` iterations where iteration
    outputs (the ``output=`` refs passed to ``Schedule.scan``) become the
    next iteration's inputs, and returns the final carries (a bare array
    when a single carry was declared).

    Double-buffer contract: a ``gather(double_buffer=True, prime=...)``
    stage reads the delivery of the exchange issued by its ``feed()`` stage
    one iteration earlier — the delivered value (not the in-flight handle)
    is an implicit extra carry, so step k+1's compute between the feed and
    the gather's consumer hides inside step k's collective window.  The
    prologue seeds iteration 0 from ``prime`` (evaluated on the initial
    carries); the final iteration's feed issues one exchange that is never
    consumed.

    * ``.strategies`` / ``.predicted_times`` / ``.predicted_window`` — as
      on ``ExchangeSchedule`` (the window entries price ONE iteration);
    * ``.predicted_loop(n_steps)`` — the eq.-23 steady-state extension
      (``perfmodel.predict_scan_schedule``): setup paid once, per-iteration
      window term, optional overlap credit.
    """

    def __init__(self, sched: Schedule, carry: tuple, outputs: tuple, *,
                 single: bool, n_steps_hint: int | None):
        ctx = sched._ctx
        mesh, axis_name = ctx["mesh"], ctx["axis_name"]
        self.mesh = mesh
        self.axis_name = axis_name
        self.topology = ctx["topology"]
        self.hw = ctx["hw"]
        self.n_steps_hint = n_steps_hint
        stages = sched._stages
        exchanges = sched._exchanges
        self._stages = stages
        self._single = single

        self.strategies = {st.name: exchanges[st.sid].strategy
                           for st in stages
                           if st.kind in ("gather", "scatter")}
        self.predicted_times = {
            st.name: exchanges[st.sid].predicted_times
            for st in stages if st.kind in ("gather", "scatter")}
        self.predicted_window = sched._predict_window()
        self._pricing_specs = sched._stage_specs()

        # ---- carry/output validation ----
        for c in carry:
            sched._check_ref(c)
            if c.kind != "input":
                raise ValueError(
                    f"carry refs must be input stages; {c.name!r} is a "
                    f"{c.kind} stage")
        input_sids = [st.sid for st in stages if st.kind == "input"]
        if sorted(c.sid for c in carry) != sorted(input_sids):
            raise ValueError(
                "carry= must name every input stage exactly once (each "
                "input is re-fed from its paired output every iteration)")
        if len(outputs) != len(carry):
            raise ValueError(
                f"output= must pair one stage per carry ({len(carry)} "
                f"carries, {len(outputs)} outputs)")
        for o in outputs:
            sched._check_ref(o, array_valued=True)

        db_stages = [st for st in stages
                     if st.kind == "gather" and st.double_buffer]
        fed = {st.gather.sid for st in stages if st.kind == "feed"}
        for st in db_stages:
            if st.sid not in fed:
                raise ValueError(
                    f"double_buffer stage {st.name!r} has no feed() stage "
                    "— nothing would issue its next-iteration exchange")
            if not _exchange_free(stages, st.src.sid):
                raise ValueError(
                    f"prime stage of {st.name!r} depends on an exchange "
                    "stage; the scan prologue runs before any exchange, "
                    "so prime ancestry must be input/constant/compute only")

        (all_input_sids, input_specs, self.step_args, bound_specs,
         slots) = _bind_operands(stages, exchanges, mesh, axis_name)
        spec_of = dict(zip(all_input_sids, input_specs))
        self._carry_specs = tuple(spec_of[c.sid] for c in carry)
        self.in_specs = self._carry_specs + bound_specs
        # inputs arrive in CARRY order (the call order), not declaration
        # order
        input_pos = {c.sid: i for i, c in enumerate(carry)}
        n_carry = len(carry)

        def loop_local(n_steps, *args):
            carries, bound = args[:n_carry], args[n_carry:]
            db0 = {}
            if db_stages:
                # prologue: seed each double-buffer carry by running its
                # prime exchange on the initial carries
                force0, _ = _run_stages(stages, exchanges, slots, input_pos,
                                        carries, bound, prologue=True)
                for st in db_stages:
                    ex = exchanges[st.sid]
                    handle = ex.start_local(force0(st.src.sid),
                                            *bound[slots[st.sid]])
                    kw = st.finish_kwargs
                    db0[st.sid] = handle.finish(**kw) if kw \
                        else handle.finish()

            def body(c, _):
                user, db_vals = c
                force, finish_feeds = _run_stages(
                    stages, exchanges, slots, input_pos, user, bound,
                    db_vals=db_vals)
                new_user = tuple(force(o.sid) for o in outputs)
                return (new_user, finish_feeds()), None

            (final, _), _ = jax.lax.scan(body, (tuple(carries), db0), None,
                                         length=n_steps)
            return final

        in_specs_t = self.in_specs
        out_specs_t = self._carry_specs

        # n_steps must reach the scan as a static length, so the shard_map
        # is constructed inside the jit: one persistent window per distinct
        # step count, cached by jit like any static argument; bound arrays
        # are arguments, never closure constants
        @functools.partial(jax.jit, static_argnames=("n_steps",))
        def run(n_steps, carries, bound):
            mapped = jax.shard_map(
                functools.partial(loop_local, n_steps), mesh=mesh,
                in_specs=in_specs_t, out_specs=out_specs_t, check_vma=False)
            return mapped(*carries, *bound)

        self._run = run

    def shard_input(self, value, which: int = 0) -> jax.Array:
        """Place a host value on the mesh with carry ``which``'s spec."""
        spec = self._carry_specs[which]
        return jax.device_put(value, NamedSharding(self.mesh, spec))

    # the SpMV-flavored alias every front door exposes
    def shard_vector(self, value) -> jax.Array:
        return self.shard_input(value, 0)

    def predicted_loop(self, n_steps: int, *,
                       overlap_credit: float = 0.0) -> dict | None:
        """§5 steady-state loop pricing (``perfmodel.
        predict_scan_schedule``): setup paid once, ``n_steps`` per-iteration
        window terms, ``overlap_credit`` seconds of cross-step compute
        hidden per iteration by double-buffered stages.  ``None`` when no
        hardware parameters were in scope at resolve time."""
        if self.hw is None:
            return None
        return pm.predict_scan_schedule(self._pricing_specs, self.hw,
                                        n_steps,
                                        overlap_credit=overlap_credit)

    def lower(self, *carries, n_steps: int):
        """``jax.stages.Lowered`` of ``self(*carries, n_steps=n_steps)``."""
        return self._run.lower(n_steps, carries, self.step_args)

    def __call__(self, *carries, n_steps: int):
        out = self._run(n_steps, carries, self.step_args)
        return out[0] if self._single else out
