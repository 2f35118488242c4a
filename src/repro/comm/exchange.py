"""Direction-agnostic exchange core — shared by gather (pull) and scatter
(push).

The paper's machinery is symmetric in direction: the one-time plan, the
strategy rung ladder, the §5 pricing, and the start/compute/finish overlap
protocol all depend only on *which elements cross which (sender, receiver)
boundary*, never on which side initiates the transfer.  ``IrregularExchange``
owns everything that is common to both directions for one
``AccessPattern`` on one mesh:

* mesh / ``SharedVector`` resolution and partitioning checks,
* BLOCKSIZE resolution (fixed or eq.-11 ``"auto"``),
* the cached destination-independent base ``CommPlan``,
* strategy resolution (any rung or ``"auto"`` via ``select.rank_strategies``
  with the subclass's direction — get-models for ``IrregularGather``,
  put-models for ``IrregularScatter``),
* the one hardware calibration (the §5.4 probes, memoized module-wide
  in ``measure_hw``; every other caller reaches it through the memo),
* the ``OverlapHandle`` protocol type.

Subclasses implement ``_bind`` to wire the resolved strategy to their
direction's ``shard_map``-local functions (``repro.comm.strategies``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.comm import plan_cache
from repro.comm import select
from repro.comm import strategies as strat
from repro.comm import telemetry
from repro.comm.dynamic import DYNAMIC_STRATEGIES, DynamicPattern
from repro.comm.pattern import AccessPattern
from repro.comm.perfmodel import HardwareParams
from repro.comm.plan import CommPlan, Topology
from repro.comm.shared import SharedVector, axis_size

__all__ = ["IrregularExchange", "OverlapHandle", "measure_hw",
           "clear_hw_memo"]


# One calibration per (devices, axis names, axis, axis size, element size)
# for the life of the process: constructing several gathers/scatters on the
# same mesh must not re-run the §5.4 latency/bandwidth probes each time.
_HW_MEMO: dict[tuple, HardwareParams] = {}


def _hw_key(mesh, axis_name, elem_bytes: int) -> tuple:
    axis = tuple(axis_name) if isinstance(axis_name, (tuple, list)) \
        else axis_name
    # the axis *size* must participate: the same devices factorized
    # (2, 4) vs (4, 2) calibrate different ring lengths on the same name
    return (tuple(d.id for d in mesh.devices.flat), mesh.axis_names, axis,
            axis_size(mesh, axis_name), elem_bytes)


def _ring_mesh(axis: str) -> jax.sharding.Mesh:
    """Every visible device on one axis (``launch.mesh.make_local_mesh``'s
    call, made here so the library does not import the launcher)."""
    return jax.make_mesh((len(jax.devices()),), (axis,),
                         axis_types=(AxisType.Auto,))


def clear_hw_memo() -> None:
    _HW_MEMO.clear()


def measure_hw(mesh=None, axis_name=None, *, elem_bytes: int = 4,
               force: bool = False) -> HardwareParams:
    """The §5.4 hardware parameters for one mesh axis, memoized.

    With no ``mesh`` every visible device joins one ring on ``axis_name``
    (default ``"data"``); with a mesh and no axis, its first axis is probed.
    A tuple of axes calibrates over the whole visible device set: the
    parameters describe the machine, not the mesh factorization.  Pass
    ``force=True`` to re-measure.
    """
    if mesh is None:
        mesh = _ring_mesh(axis_name or "data")
    axis = axis_name or mesh.axis_names[0]
    key = _hw_key(mesh, axis, elem_bytes)
    if force or key not in _HW_MEMO:
        with telemetry.span("comm.measure_hw"):
            if isinstance(axis, (tuple, list)):
                mesh, axis = _ring_mesh("data"), "data"
            _HW_MEMO[key] = _probe(mesh, axis, elem_bytes)
    return _HW_MEMO[key]


def _timeit(fn, *args, iters: int = 10, warmup: int = 3) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _probe(mesh, axis: str, elem_bytes: int) -> HardwareParams:
    """Micro-benchmark the four §5.4 parameters over ``mesh``'s ``axis``:
    a STREAM-like copy for ``w_private``, a random-gather probe for the
    effective non-contiguous access granularity ``cacheline``, a large
    ring ``ppermute`` for ``w_remote`` and a tiny one for ``tau``."""
    # -- w_private: STREAM-like copy (read + write) --
    n = 1 << 22
    x = jnp.arange(n, dtype=jnp.float32)
    copy = jax.jit(lambda a: a * 1.0000001)
    t_copy = _timeit(copy, x, iters=10)
    w_private = 2.0 * n * 4 / t_copy

    # -- cacheline: random-gather probe; the model charges every
    # non-contiguous local access one ``cacheline`` of traffic, so the
    # effective value is gather-time * w_private / accesses --
    g = 1 << 20
    idx = jnp.asarray(
        np.random.default_rng(0).integers(0, n, size=g, dtype=np.int32))
    gather = jax.jit(lambda a, i: a[i])
    t_gather = _timeit(gather, x, idx, iters=10)
    cacheline = int(np.clip(t_gather * w_private / g, 16, 4096))

    # -- w_remote and tau: ring ppermute, big minus tiny --
    ndev = mesh.shape[axis]
    if ndev > 1:
        perm = [(i, (i + 1) % ndev) for i in range(ndev)]

        def ring(a):
            return jax.shard_map(
                lambda v: jax.lax.ppermute(v, axis, perm), mesh=mesh,
                in_specs=P(axis), out_specs=P(axis))(a)

        sh = NamedSharding(mesh, P(axis))
        big = jax.device_put(jnp.zeros((ndev * (1 << 20),), jnp.float32), sh)
        t_big = _timeit(jax.jit(ring), big, iters=5)
        tiny = jax.device_put(jnp.zeros((ndev * 8,), jnp.float32), sh)
        tau = _timeit(jax.jit(ring), tiny, iters=20)
        w_remote = (1 << 20) * 4 / max(t_big - tau, 1e-9)
    else:
        w_remote = w_private
        tau = _timeit(copy, jnp.zeros((8,), jnp.float32), iters=30)

    return HardwareParams(
        w_private=w_private, w_remote=w_remote, tau=tau,
        cacheline=cacheline, elem=elem_bytes, idx=4)


@dataclasses.dataclass
class OverlapHandle:
    """An in-flight exchange: the collective has been issued, the landed
    messages are not yet delivered.  Everything computed before ``finish``
    that only reads the local operand runs inside the communication window.

    For a gather, ``finish`` has two materializations:

    * ``materialize="full"`` — assemble the classic device-private
      ``x_copy`` (length >= n, indexable with global indices);
    * ``materialize="dest"`` — requires the gather to own a ``Destination``:
      scatter the landed recv buffer straight into the consumer's named
      slots and return ``{name: (slot_shape..., feat...) array}``.  No
      full-length intermediate is built — O(slots + recv) work.

    The default is ``"dest"`` when the gather was constructed with a
    ``Destination``, else ``"full"``.

    For a scatter (push), ``finish`` takes no options: it runs the
    own-accumulate (no dependency on the collective, so it overlaps) and
    combines the landed foreign contributions into the owned slice.
    """

    x_local: jax.Array
    _finish: Callable[..., jax.Array]

    def finish(self, *, extra_slots: int = 0, copy_own: bool = True,
               materialize: str | None = None):
        """Deliver the landed messages (see class docstring for modes).

        ``extra_slots`` (gather, full mode): number of guaranteed-zero
        slots appended after the recv dump — x_copy[n+1 .. n+extra_slots]
        read as 0 for any strategy, so consumers can point padding indices
        there.  ``copy_own=False`` (gather, full mode) skips the eq.-14
        own-shard memcpy for consumers that read their own shard from
        ``x_local`` directly.
        """
        return self._finish(extra_slots=extra_slots, copy_own=copy_own,
                            materialize=materialize)


class IrregularExchange:
    """Plan + strategy + device state for one ``AccessPattern`` over one
    mesh axis (or tuple of axes), in one direction.

    ``direction`` is a class attribute: ``"get"`` (gather — accessors pull
    the elements they read) or ``"put"`` (scatter — accessors push
    contributions to the elements they write); it selects which §5 model
    family prices ``strategy="auto"``.
    """

    direction = "get"

    def __init__(
        self,
        pattern: AccessPattern,
        where: jax.sharding.Mesh | SharedVector,
        *,
        axis_name: str | tuple = "data",
        strategy: str = "auto",
        blocksize: int | str | None = None,
        shards_per_node: int | None = None,
        topology: Topology | None = None,
        hw=None,
        candidates=None,
        use_plan_cache: bool = True,
        base_plan: CommPlan | None = None,
        scan_steps: int | None = None,
        plan_cost: float = 0.0,
        use_kernel: bool = False,
        decode: bool = False,
    ):
        # ``use_kernel`` swaps the jnp pack/unpack around the collective for
        # the fused Pallas kernels (repro.kernels), bit-identical on every
        # rung; the §5 ranking prices the kernelized compute terms so
        # strategy="auto" stays honest either way.  ``decode`` prices the
        # rungs for a token-by-token serving step instead (the eqs. 12δ–15δ
        # α/latency floors via predict_decode_exchange) — at decode batch
        # sizes the per-message τ terms decide the ladder, not the volumes
        self.use_kernel = use_kernel
        self.decode = decode
        if isinstance(where, SharedVector):
            assert where.n == pattern.n, (where.n, pattern.n)
            mesh = where.mesh
            axis_name = where.axis_name
            topology = topology or where.topology
        else:
            mesh = where
        valid = strat.STRATEGIES + ("auto",)
        if strategy not in valid:
            raise ValueError(f"strategy must be one of {valid}")
        # a DynamicPattern duck-types the AccessPattern surface (indices /
        # n / m / r come from its template) but switches plan resolution to
        # the bucketed envelope tier and restricts the rung ladder to the
        # strategies whose executor tables comm.dynamic can re-derive
        # per batch in-jit
        self.dynamic_pattern = (pattern if isinstance(pattern, DynamicPattern)
                                else None)
        if self.dynamic_pattern is not None:
            if strategy == "auto":
                if candidates is None:
                    candidates = DYNAMIC_STRATEGIES
                else:
                    bad = tuple(c for c in candidates
                                if c not in DYNAMIC_STRATEGIES)
                    if bad:
                        raise ValueError(
                            f"candidates {bad} cannot serve a "
                            f"DynamicPattern — device-side table "
                            f"derivation covers {DYNAMIC_STRATEGIES}")
            elif strategy not in DYNAMIC_STRATEGIES:
                raise ValueError(
                    f"strategy {strategy!r} cannot serve a DynamicPattern "
                    f"— device-side table derivation covers "
                    f"{DYNAMIC_STRATEGIES}")
        self.pattern = pattern
        self.mesh = mesh
        self.axis_name = axis_name
        p = axis_size(mesh, axis_name)
        self.p = p
        n = pattern.n
        assert n % p == 0, "pad the vector so n divides the mesh axis"
        assert pattern.m % p == 0, "pad the pattern so m divides the mesh axis"
        if topology is None:
            topology = Topology(p, shards_per_node or p)

        if base_plan is not None:
            # an already-resolved destination-independent base plan (e.g.
            # one ExchangeSchedule stage sharing it with a sibling stage of
            # the same pattern): skip the probe and any blocksize sweep
            assert (base_plan.n == n and base_plan.p == p
                    and base_plan.m == pattern.m), (
                "base_plan was built for a different pattern/partitioning: "
                f"{(base_plan.n, base_plan.p, base_plan.m)} != "
                f"{(n, p, pattern.m)}")
            blocksize = base_plan.blocksize
        else:
            if blocksize == "auto":
                if hw is None:
                    hw = measure_hw(mesh, axis_name)
                blocksize = select.choose_blocksize(
                    pattern.indices, n, p, topology=topology, hw=hw)
            # destination-independent base plan first: the strategy resolves
            # against it, and any direction- or consumer-specific delta (the
            # scatter executor tables, a Destination descriptor) is attached
            # only afterwards
            if self.dynamic_pattern is not None:
                # the bucketed-reuse tier: an envelope plan keyed on
                # quantized pattern stats, shared across routings — its
                # static geometry and pricing serve this exchange while the
                # exact tables are (re-)derived from the template / each
                # batch on device
                base_plan = plan_cache.get_envelope_plan(
                    pattern.indices, n, p, blocksize=blocksize,
                    topology=topology, s_max=self.dynamic_pattern.s_max,
                    cache=use_plan_cache,
                )
            else:
                base_plan = plan_cache.get_comm_plan(
                    pattern.indices, n, p, blocksize=blocksize,
                    topology=topology, cache=use_plan_cache,
                )
        self._use_plan_cache = use_plan_cache
        self._prepare(base_plan)

        self.requested_strategy = strategy
        self.scan_steps = scan_steps
        self.predicted_times: dict[str, float] | None = None
        if strategy == "auto":
            if hw is None:
                hw = measure_hw(mesh, axis_name)
            # scan_steps (a ScanSchedule resolving this stage) prices the
            # rungs on the n-step steady-state loop cost — setup amortized
            # over the persistent window — instead of the single-call cost
            # plan_cost (the §5 T_plan term for however this exchange
            # obtains its tables) is a flat per-use addend — it never
            # reorders the rungs but makes predicted_times comparable
            # against wall clocks that include the plan acquisition
            with telemetry.span("comm.rank"):
                ranked = select.rank_strategies(
                    self._ranking_plan(base_plan), pattern.r, hw,
                    candidates=candidates, direction=self.direction,
                    scan_steps=scan_steps, plan_cost=plan_cost,
                    decode=decode, **self._price_kwargs())
            self.predicted_times = dict(ranked)
            strategy = ranked[0][0]
        self.strategy = strategy
        self.hw = hw

        self._bind(base_plan, strategy)

    # ---- subclass hooks ----
    def _prepare(self, base_plan: CommPlan) -> None:
        """Derive direction-specific plan state before strategy resolution."""

    def _ranking_plan(self, base_plan: CommPlan):
        """The plan whose counts feed the §5 ranking (base by default)."""
        return base_plan

    def _price_kwargs(self) -> dict:
        """Extra ``rank_strategies`` kwargs (e.g. gather unpack pricing)."""
        return {"use_kernel": self.use_kernel}

    def _bind(self, base_plan: CommPlan, strategy: str) -> None:
        """Wire the resolved strategy: set ``self.plan`` / ``plan_args`` /
        ``in_specs`` / local start+finish and the standalone jit."""
        raise NotImplementedError

    # ---- shared surface ----
    def shard_vector(self, x) -> jax.Array:
        """Place host values on the mesh in the plan's contiguous layout."""
        return jax.device_put(
            x, NamedSharding(self.mesh, P(self.axis_name)))

    @property
    def counts(self):
        """The plan's exact per-shard volume counts (§5.2 model inputs)."""
        return self.plan.counts
