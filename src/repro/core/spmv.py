"""Distributed SpMV engine — the paper's workload on the repro.comm runtime.

``DistributedSpMV`` is now a *consumer* of ``repro.comm``: it derives an
``AccessPattern`` from the EllPack column table, hands it to
``IrregularGather`` (which owns the cached ``CommPlan``, the strategy
resolution, and the device-resident plan arrays), and fuses the gather with
the local EllPack compute inside one jitted ``shard_map``.  The local
compute can run through the Pallas kernels (``use_kernel=True``) or the
pure-jnp reference.

``strategy`` may be any rung of the ladder (``replicate`` / ``blockwise`` /
``condensed`` / ``overlap``) or ``"auto"``, which micro-benchmarks the
hardware parameters once per mesh and lets the §5 performance models pick.
``blocksize`` may likewise be ``"auto"`` (eq.-11-minimizing BLOCKSIZE).  The
resolved choices are available as ``engine.strategy`` / ``engine.blocksize``;
the request is kept in ``engine.requested_strategy``.

``materialize`` picks the unpack: ``"dest"`` (default on the jnp paths)
registers the EllPack slot table as a ``Destination`` so each exchange
lands directly in gather-slot order — O(slots + recv) per step, no
full-length ``x_copy`` ever assembled; ``"full"`` keeps the paper's UPCv3
layout (assemble ``mythread_x_copy``, then index it), bit-identical
results.  Under ``overlap`` the own partial reads ``x_local``, so the
destination is the compact list of the real foreign slots alone, summed
into rows (``engine.dest_slots``); results then differ from ``"full"``
only in the order each row's foreign products are added.  With
``use_kernel=True`` the default is ``"full"`` (the split SpMV compute
kernels consume the assembled copy, itself built by the fused unpack
kernel); an explicit ``materialize="dest"`` instead routes
the exchange through the kernelized dest-unpack (``kernels.unpack_dest``
delivers the recv buffer straight into the EllPack slots) with the slot
compute in jnp.  ``transpose=True`` with ``use_kernel=True`` runs the
push-side split kernels: the own-target accumulate overlaps the in-flight
collective, then the landed contributions fold in
(``kernels.accumulate_segments`` / ``accumulate_into``).

The ``overlap`` strategy uses the ``OverlapHandle`` protocol: issue the
condensed ``all_to_all``, run the own-shard partial SpMV (which depends only
on ``x_local``) while the exchange is in flight, then finish with the
foreign partial on the unpacked remote values — XLA's latency-hiding
scheduler can hide the collective behind the first partial.  With
``use_kernel=True`` both partials run through the windowed Pallas kernel
(the split-kernel on-copy variant).

Usage:
    mesh = jax.make_mesh((8,), ("data",))
    m = make_mesh_like_matrix(1 << 16, 16)
    engine = DistributedSpMV(m, mesh, strategy="auto")
    x = engine.shard_vector(x_host)
    y = engine(x)              # y = (D + A) x, sharded like x
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm import telemetry
from repro.comm.gather import IrregularGather
from repro.comm.pattern import AccessPattern, Destination
from repro.comm.plan import CommPlan, Topology
from repro.comm.scatter import IrregularScatter
from repro.comm.strategies import shard_slot_major
from repro.core.matrix import EllpackMatrix

__all__ = ["DistributedSpMV", "normal_equations_step",
           "normal_equations_stages"]


def _spmv_local(x_copy, diag_l, vals_l, cols_l, *, shard_size, axis_name):
    """Local EllPack compute on the device-private x_copy (global indices);
    ``vals_l`` / ``cols_l`` are slot-major (r_nz, shard)."""
    with jax.named_scope("spmv.local"):
        me = jax.lax.axis_index(axis_name)
        offset = me * shard_size
        own = jax.lax.dynamic_slice(x_copy, (offset,), (shard_size,))
        gathered = x_copy[cols_l]                       # (r_nz, shard)
        return diag_l * own + (vals_l * gathered).sum(axis=0)


def _compact_foreign(rem_cols, n: int, p: int):
    """The overlap rung's foreign ``Destination`` as a compact slot list.

    ``rem_cols`` is the plan's ``(m, r_rem_max)`` foreign column table
    (padding >= n).  Per device, its real entries in row-then-slot order,
    padded to the longest device's count (at least 1) with
    ``Destination.ZERO``.  Returns ``(ids, rows, slots)``, each ``(p, L)``:
    the global column each slot reads, its local row (padding: the last
    row, so rows stay sorted) and its column in ``rem_cols`` (padding: 0).
    """
    rows_per_shard, r = rem_cols.shape[0] // p, rem_cols.shape[1]
    blocks = rem_cols.reshape(p, -1)        # device q's (rows, r) block
    pos = [np.flatnonzero(b < n) for b in blocks]
    L = max(1, *map(len, pos))
    ids = np.full((p, L), Destination.ZERO, np.int32)
    rows = np.full((p, L), rows_per_shard - 1, np.int32)
    slots = np.zeros((p, L), np.int32)
    for q, f in enumerate(pos):
        ids[q, :len(f)] = blocks[q, f]
        rows[q, :len(f)], slots[q, :len(f)] = np.divmod(f, r)
    return ids, rows, slots


def _compact_foreign_vals(vals, rem_src, ids, rows, slots):
    """``(p, L)`` matrix values of the compact foreign slots of
    ``_compact_foreign``, 0 in padding."""
    p, rows_per_shard = ids.shape[0], vals.shape[0] // ids.shape[0]
    g = rows + (np.arange(p, dtype=np.int32) * rows_per_shard)[:, None]
    return np.where(ids != Destination.ZERO, vals[g, rem_src[g, slots]],
                    np.zeros((), vals.dtype))


class DistributedSpMV:
    """y = (D + A) x with x, y, D, A, J sharded over ``axis_name``.

    ``transpose=True`` computes y = (D + A)ᵀ x instead — the push-direction
    workload: row i's off-diagonal entries become *contributions*
    ``vals[i, j] * x[i]`` to ``y[cols[i, j]]``, scatter-accumulated through
    ``IrregularScatter`` (``reduce="add"``) over the transpose-derived plan,
    so forward and transposed products share one cached base ``CommPlan``.
    """

    def __init__(
        self,
        matrix: EllpackMatrix,
        mesh: jax.sharding.Mesh,
        *,
        axis_name: str = "data",
        strategy: str = "condensed",
        blocksize: int | str | None = None,
        shards_per_node: int | None = None,
        use_kernel: bool = False,
        materialize: str | None = None,
        transpose: bool = False,
        hw=None,
        use_plan_cache: bool = True,
    ):
        self.matrix = matrix
        self.mesh = mesh
        self.axis_name = axis_name
        p = int(np.prod([mesh.shape[axis_name]]))
        self.p = p
        n = matrix.n
        assert n % p == 0, "pad the matrix so n divides the mesh axis"
        topology = Topology(p, shards_per_node or p)
        self.transpose = transpose
        # {"delivered", "dense"} slots per device of the overlap rung's
        # compact foreign destination; None on every other path
        self.dest_slots = None
        if transpose:
            assert materialize is None, (
                "materialize= is a gather-unpack knob; the transposed "
                "product always accumulates straight into the owned slice")
            self._init_transpose(matrix, mesh, axis_name=axis_name,
                                 strategy=strategy, blocksize=blocksize,
                                 topology=topology, hw=hw,
                                 use_kernel=use_kernel,
                                 use_plan_cache=use_plan_cache)
            return

        if materialize is None:
            # the split SpMV compute kernels consume the assembled copy, so
            # the kernel default is "full"; an explicit materialize="dest"
            # with use_kernel=True routes the exchange through the fused
            # dest-unpack kernel instead (slot compute stays jnp)
            materialize = "full" if use_kernel else "dest"
        assert materialize in ("dest", "full"), materialize
        self.materialize = materialize
        rows_per_shard = matrix.cols.shape[0] // p

        destination = None
        if materialize == "dest":
            # land every gathered value in slot-major EllPack order: slot
            # (j, i) of a device reads x[J[i, j]] for its row i — delivered
            # without ever building the length-n private copy.  The
            # overlap rung resolves owned slots from x_local inside the own
            # partial, so there the destination is the compact list of the
            # plan's real foreign (rem) slots; resolved per strategy, after
            # "auto" picks (no throwaway plan entry gets cached).
            def slots(table):
                return table.reshape(p, rows_per_shard, -1).transpose(
                    0, 2, 1)

            def destination(resolved, base_plan):
                if resolved == "overlap":
                    self._foreign = _compact_foreign(
                        base_plan.rem_cols, n, p)
                    ids = self._foreign[0]
                    self.dest_slots = {
                        "delivered": ids.shape[1],
                        "dense": rows_per_shard * base_plan.r_rem_max}
                    telemetry.record_dest_slots(**self.dest_slots)
                    return Destination.from_slots(foreign=ids)
                return Destination.from_slots(ellpack=slots(matrix.cols))
        self.gather = IrregularGather(
            AccessPattern.from_ellpack(matrix), mesh,
            axis_name=axis_name, strategy=strategy, blocksize=blocksize,
            topology=topology, destination=destination,
            dest_slots=rows_per_shard * matrix.cols.shape[1],
            hw=hw, use_kernel=use_kernel, use_plan_cache=use_plan_cache,
        )
        self.plan: CommPlan = self.gather.plan
        self.requested_strategy = strategy
        self.predicted_times = self.gather.predicted_times
        strategy = self.gather.strategy
        self.strategy = strategy
        self.blocksize = self.plan.blocksize

        shard = NamedSharding(mesh, P(axis_name))
        shard2 = NamedSharding(mesh, P(axis_name, None))

        def put_slots(table):
            # the jnp paths keep every EllPack-shaped table slot-major:
            # row-major, the r_nz-wide minor dimension pads to 128 lanes in
            # a TPU's HBM (8x at r_nz = 16); (r_nz, rows) tiles unpadded
            return jax.device_put(shard_slot_major(table, p), shard2)

        with telemetry.span("spmv.place"):
            self._diag = jax.device_put(matrix.diag, shard)
            if strategy == "overlap":
                # the overlap step never reads the unsplit matrix; keeping
                # vals/cols resident would double the device footprint
                self._vals = self._cols = None
            elif use_kernel and materialize == "full":
                # the SpMV compute kernels read row-major (rows, r_nz)
                # tables
                self._vals = jax.device_put(matrix.vals, shard2)
                self._cols = jax.device_put(matrix.cols, shard2)
            elif materialize == "dest":
                # targeted delivery arrives already in EllPack slot order —
                # the runtime column table is baked into the plan, not an
                # operand
                self._vals = put_slots(matrix.vals)
                self._cols = None
            else:
                self._vals = put_slots(matrix.vals)
                self._cols = put_slots(matrix.cols)
        self._gather_args = self.gather.plan_args
        self._plan_args = self._gather_args

        gather = self.gather
        shard_size = self.plan.shard_size

        if strategy == "overlap" and use_kernel and materialize == "full":
            from repro.kernels import ops as kops
            plan = self.plan
            own_fn, rem_fn, kargs = kops.make_spmv_overlap_sharded(
                plan, matrix.vals)
            with telemetry.span("spmv.place"):
                self._plan_args = self._gather_args + tuple(
                    jax.device_put(a, shard) for a in kargs)
            n_kargs = len(kargs)

            def step_local(x_local, diag_l, send_idx, recv_idx, *args):
                assert len(args) == n_kargs
                handle = gather.start_local(x_local, send_idx, recv_idx)
                # own-shard partial through the kernel on x_local (+ its
                # one zero pad slot), overlapping the in-flight exchange
                with jax.named_scope("spmv.local/own"):
                    x_ext = jnp.concatenate(
                        [x_local, jnp.zeros((1,), x_local.dtype)])
                    y_own = own_fn(diag_l, x_ext, *args[:4])
                x_copy = handle.finish(extra_slots=1, copy_own=False)
                with jax.named_scope("spmv.local/foreign"):
                    y_rem = rem_fn(x_copy, *args[4:])
                with jax.named_scope("spmv.local"):
                    return y_own + y_rem

            kernel_specs = (P(axis_name),) * n_kargs
        elif strategy == "overlap" and materialize == "dest":
            plan = self.plan
            # split vals the same way the plan split cols; the foreign vals
            # follow the compact destination's slots, 0 in its padding
            with telemetry.span("spmv.split"):
                loc_vals = np.take_along_axis(matrix.vals, plan.loc_src,
                                              axis=1)
                ids, rem_rows, slots = self._foreign
                del self._foreign
                rem_vals = _compact_foreign_vals(matrix.vals, plan.rem_src,
                                                 ids, rem_rows, slots)
            with telemetry.span("spmv.place"):
                # the compact tables are flat per device: 1-D, unpadded
                self._plan_args = self._gather_args + tuple(
                    put_slots(a) for a in (plan.loc_cols, loc_vals)) + tuple(
                    jax.device_put(a.ravel(), shard)
                    for a in (rem_rows, rem_vals))
            n_gargs = len(self._gather_args)

            def step_local(x_local, diag_l, *args):
                loc_cols_l, loc_vals_l, rem_rows_l, rem_vals_l = \
                    args[n_gargs:]
                # 1. issue the condensed exchange (paper Listing 5 pack)
                handle = gather.start_local(x_local, *args[:n_gargs])
                # 2. own-shard partial: no dependency on the landed messages,
                # so the scheduler can run it while the collective is in
                # flight
                with jax.named_scope("spmv.local/own"):
                    x_ext = jnp.concatenate(
                        [x_local, jnp.zeros((1,), x_local.dtype)])
                    y_own = diag_l * x_local + (
                        loc_vals_l * x_ext[loc_cols_l]).sum(axis=0)
                # 3. foreign partial straight off the targeted delivery:
                # the landed messages arrive as the compact slot list,
                # sorted by row
                foreign = handle.finish()["foreign"]
                with jax.named_scope("spmv.local/foreign"):
                    y_rem = jax.ops.segment_sum(
                        rem_vals_l * foreign, rem_rows_l,
                        num_segments=rows_per_shard, indices_are_sorted=True)
                with jax.named_scope("spmv.local"):
                    return y_own + y_rem

            kernel_specs = (P(axis_name, None),) * 2 + (P(axis_name),) * 2
        elif strategy == "overlap":
            plan = self.plan
            # split vals the same way the plan split cols; padded slots point
            # at a guaranteed-zero x slot, so their vals are never observed
            with telemetry.span("spmv.split"):
                loc_vals = np.take_along_axis(matrix.vals, plan.loc_src,
                                              axis=1)
                rem_vals = np.take_along_axis(matrix.vals, plan.rem_src,
                                              axis=1)
            with telemetry.span("spmv.place"):
                self._plan_args = self._gather_args + tuple(
                    put_slots(a) for a in
                    (plan.loc_cols, loc_vals, plan.rem_cols, rem_vals))

            def step_local(x_local, diag_l, send_idx,
                           recv_idx, loc_cols_l, loc_vals_l, rem_cols_l,
                           rem_vals_l):
                # 1. issue the condensed exchange (paper Listing 5 pack)
                handle = gather.start_local(x_local, send_idx, recv_idx)
                # 2. own-shard partial: no dependency on the landed messages,
                # so the scheduler can run it while the collective is in
                # flight
                with jax.named_scope("spmv.local/own"):
                    x_ext = jnp.concatenate(
                        [x_local, jnp.zeros((1,), x_local.dtype)])
                    y_own = diag_l * x_local + (
                        loc_vals_l * x_ext[loc_cols_l]).sum(axis=0)
                # 3. foreign partial on the landed remote values; slot n is
                # the recv padding dump, slot n+1 the compute padding (zero)
                x_copy = handle.finish(extra_slots=1, copy_own=False)
                with jax.named_scope("spmv.local/foreign"):
                    y_rem = (rem_vals_l * x_copy[rem_cols_l]).sum(axis=0)
                with jax.named_scope("spmv.local"):
                    return y_own + y_rem

            kernel_specs = (P(axis_name, None),) * 4
        elif use_kernel and materialize == "full":
            from repro.kernels import ops as kops
            kernel_local, kplan = kops.make_spmv_on_copy_sharded(
                matrix.cols, p
            )
            with telemetry.span("spmv.place"):
                kplan_args = tuple(
                    jax.device_put(a, NamedSharding(mesh, P(axis_name)))
                    for a in kplan
                )
            self._plan_args = self._plan_args + kplan_args
            n_gather_args = len(self._gather_args)

            def step_local(x_local, diag_l, vals_l, cols_l, *args):
                x_copy = gather.local(x_local, *args[:n_gather_args])
                with jax.named_scope("spmv.local"):
                    return kernel_local(diag_l, vals_l, x_copy,
                                        *args[n_gather_args:])

            kernel_specs = (P(axis_name, None), P(axis_name, None, None),
                            P(axis_name, None))
        elif materialize == "dest":
            def step_local(x_local, diag_l, vals_l, *plan_args):
                # landed values arrive already in EllPack slot order; owned
                # slots were gathered from x_local by the same delivery
                gathered = gather.local(x_local, *plan_args)["ellpack"]
                with jax.named_scope("spmv.local"):
                    return diag_l * x_local + (vals_l * gathered).sum(axis=0)

            kernel_specs = ()
        else:
            def step_local(x_local, diag_l, vals_l, cols_l, *plan_args):
                x_copy = gather.local(x_local, *plan_args)
                return _spmv_local(
                    x_copy, diag_l, vals_l, cols_l,
                    shard_size=shard_size, axis_name=axis_name,
                )

            kernel_specs = ()

        if strategy == "overlap":
            base_args = (self._diag,)
            base_specs = (P(axis_name), P(axis_name))
        elif materialize == "dest":
            base_args = (self._diag, self._vals)
            base_specs = (P(axis_name), P(axis_name), P(axis_name, None))
        else:
            base_args = (self._diag, self._vals, self._cols)
            base_specs = (P(axis_name), P(axis_name), P(axis_name, None),
                          P(axis_name, None))
        in_specs = (base_specs
                    + self.gather.in_specs
                    + kernel_specs)
        mapped = jax.shard_map(
            step_local, mesh=mesh, in_specs=in_specs, out_specs=P(axis_name),
            check_vma=False,  # pallas_call inside shard_map needs this
        )

        # the matrix and plan tables are arguments, never closure constants
        # (a closed-over array is embedded in the compiled program)
        self._args = tuple(base_args) + tuple(self._plan_args)
        self._step = jax.jit(mapped)

    def _init_transpose(self, matrix, mesh, *, axis_name, strategy,
                        blocksize, topology, hw, use_kernel,
                        use_plan_cache):
        """y = (D + A)ᵀ x via scatter-accumulate of partial products.

        Each shard forms its contributions ``vals * x_local[:, None]`` (its
        rows' partial products) and pushes them to the column owners; the
        diagonal term is purely local (Dᵀ = D).  The ``ScatterHandle``
        protocol issues the exchange first, so the own-column accumulate
        runs while the collective is in flight — the ``overlap`` rung's
        window, available on every rung.  With
        ``use_kernel=True`` the pack-accumulate, the own-target accumulate
        and the landed-contribution fold each run as one fused Pallas pass
        (push-side split kernels), bit-identical to the jnp path.
        """
        scatter = IrregularScatter(
            AccessPattern.from_ellpack(matrix), mesh,
            axis_name=axis_name, strategy=strategy, blocksize=blocksize,
            topology=topology, reduce="add", hw=hw,
            use_kernel=use_kernel, use_plan_cache=use_plan_cache,
            slot_major=True,
        )
        self.scatter = scatter
        self.gather = None
        self.plan: CommPlan = scatter.plan
        self.splan = scatter.splan
        self.requested_strategy = strategy
        self.predicted_times = scatter.predicted_times
        self.strategy = scatter.strategy
        self.blocksize = self.plan.blocksize
        self.materialize = None

        shard = NamedSharding(mesh, P(axis_name))
        with telemetry.span("spmv.place"):
            self._diag = jax.device_put(matrix.diag, shard)
            # flat slot-major, like the scatter's tables: a (r_nz, rows)
            # table would need a relayout into the kernels' item blocks,
            # which the TPU compiler takes minutes to build at 2^23 rows
            self._vals = jax.device_put(
                shard_slot_major(matrix.vals, self.p).reshape(-1), shard)
        self._cols = None
        self._plan_args = scatter.plan_args
        r_nz = matrix.vals.shape[1]

        def step_local(x_local, diag_l, vals_l, *plan_args):
            with jax.named_scope("spmv.local"):
                contrib = vals_l * jnp.tile(x_local, r_nz)  # (r_nz * shard,)
            handle = scatter.start_local(contrib, *plan_args)
            # the diagonal term is local (Dᵀ = D); written after finish()
            # its product fuses into the final add the same way on every
            # rung and path, so kernel and jnp steps round alike
            y = handle.finish()
            with jax.named_scope("spmv.local"):
                return y + diag_l * x_local

        mapped = jax.shard_map(
            step_local, mesh=mesh,
            in_specs=(P(axis_name),) * 3 + scatter.in_specs,
            out_specs=P(axis_name), check_vma=False,
        )

        self._args = (self._diag, self._vals) + tuple(self._plan_args)
        self._step = jax.jit(mapped)

    # ---- public API ----
    def shard_vector(self, x: np.ndarray) -> jax.Array:
        if self.transpose:
            return self.scatter.shard_vector(x)
        return self.gather.shard_vector(x)

    def __call__(self, x: jax.Array) -> jax.Array:
        with telemetry.span("spmv.call"):
            return self._step(x, *self._args)

    def lower(self, x: jax.Array):
        """``jax.stages.Lowered`` of one step (``.compile().as_text()`` is
        the program a call runs)."""
        return self._step.lower(x, *self._args)

    def gather_x_copy(self, x: jax.Array) -> jax.Array:
        """(P, >=n) array: row q is device q's private x_copy (testing)."""
        assert not self.transpose, "the transposed product never gathers"
        return self.gather(x)

    @property
    def counts(self):
        """Exact per-shard §5 volume counts — put-direction counts when
        ``transpose=True`` (the direction the step actually runs)."""
        if self.transpose:
            return self.splan.counts
        return self.plan.counts

    def iterate(self, x: jax.Array, steps: int) -> jax.Array:
        """Paper §6.1 time loop: x <- M x, ``steps`` times (power iteration).

        Normalizes each step to keep values finite over 1000 iterations.
        """
        @jax.jit
        def run(x, args):
            def body(x, _):
                y = self._step(x, *args)
                return y / jnp.max(jnp.abs(y)), None

            return jax.lax.scan(body, x, None, length=steps)[0]

        return run(x, self._args)


def normal_equations_stages(sched, matrix: EllpackMatrix, p: int, x_ref):
    """Declare the z = MᵀM x stage graph on an existing ``Schedule``.

    ``x_ref`` is the (already declared) input/stage whose value is the
    length-n operand; the return value is the ``z`` stage ref.  Shared by
    ``normal_equations_step`` (one-shot window) and the iterative solvers
    (``repro.core.solvers``), which embed the same graph inside a
    ``ScanSchedule`` body next to their own recurrence stages.

    The graph chains the two SpMV directions in one window: gather-product
    ``y = M x`` (EllPack-slot ``Destination``), push-product ``z = Mᵀ y``
    whose scatter stage derives its executor tables from the gather stage's
    base plan, and the diagonal product ``D·y`` scheduled after the scatter
    so it runs inside the push collective's window.
    """
    n = matrix.n
    assert n % p == 0, "pad the matrix so n divides the mesh axis"
    rows_per_shard = matrix.cols.shape[0] // p
    pattern = AccessPattern.from_ellpack(matrix)
    # forward product lands gathered x in EllPack slot order (the same
    # Destination the forward engine registers on the jnp path)
    destination = Destination.from_slots(
        ellpack=matrix.cols.reshape(p, rows_per_shard, -1))

    diag = sched.constant(matrix.diag, "diag")
    vals = sched.constant(matrix.vals, "vals")
    g = sched.gather(pattern, src=x_ref, destination=destination,
                     name="gather_x")

    def forward(x_l, d_l, v_l, delivered):
        return d_l * x_l + (v_l * delivered["ellpack"]).sum(axis=-1)

    y = sched.compute(forward, x_ref, diag, vals, g, name="y=Mx")
    contrib = sched.compute(lambda y_l, v_l: v_l * y_l[:, None], y, vals,
                            name="partials")
    s = sched.scatter(pattern, contrib, reduce="add", name="scatter_t")
    # scheduled after the scatter stage: D·y runs inside the push window
    y_diag = sched.compute(lambda y_l, d_l: d_l * y_l, y, diag,
                           name="diag_t")
    return sched.compute(lambda a, b: a + b, s, y_diag, name="z=Mty")


def normal_equations_step(
    matrix: EllpackMatrix,
    mesh: jax.sharding.Mesh,
    *,
    axis_name: str = "data",
    strategy: str = "auto",
    blocksize: int | str | None = None,
    shards_per_node: int | None = None,
    hw=None,
    use_plan_cache: bool = True,
):
    """z = MᵀM x with M = (D + A), as ONE fused ``ExchangeSchedule``.

    The normal-equations step (the CGNR/least-squares inner product) chains
    the two SpMV directions: the forward gather-product ``y = M x`` and the
    transposed scatter-product ``z = Mᵀ y``.  Run through two
    ``DistributedSpMV`` engines it pays two plan resolutions, two windows
    and an intermediate round trip; declared as one ``Schedule`` it shares
    everything — the scatter stage derives its executor tables from the
    gather stage's base plan (one O(nnz) preparation step total, exactly
    like the forward/transpose engine pair), one hw-calibration memo hit
    prices both stages, and the diagonal product ``D·y`` is scheduled
    *after* the scatter stage so it runs inside the push collective's
    window.

    Returns the compiled ``ExchangeSchedule``: ``step(x_sharded) -> z``
    (use ``step.shard_vector`` for placement; ``step.predicted_window``
    holds the §5 fused-window pricing).
    """
    from repro.comm.schedule import Schedule

    p = int(mesh.shape[axis_name]) if not isinstance(axis_name, tuple) \
        else int(np.prod([mesh.shape[a] for a in axis_name]))
    sched = Schedule()
    x_ref = sched.input("x")
    z = normal_equations_stages(sched, matrix, p, x_ref)
    return sched.compile(
        mesh, axis_name=axis_name, strategy=strategy, blocksize=blocksize,
        topology=Topology(p, shards_per_node or p), hw=hw,
        use_plan_cache=use_plan_cache, output=z)
