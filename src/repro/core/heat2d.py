"""2D heat equation on a uniform mesh — the paper's §8 validation workload.

The global M×N field is partitioned over a 2D process grid (mprocs × nprocs =
two mesh axes), exactly like the paper's UPC code: each device owns an
(m_loc × n_loc) interior tile; every step exchanges four halo sides and then
applies the 5-point Jacobi update.

The halo exchange is now a consumer of ``repro.comm``: the stencil
neighborhood is an ``AccessPattern`` (``AccessPattern.from_stencil5``) over
the tile-major flattening of the field, and the per-step exchange+stencil
is compiled through a ``repro.comm.schedule.Schedule`` — a gather stage
planned over the *product* of the two mesh axes, an interior compute stage
scheduled inside its collective window (when the split runs), and the
halo-consuming update stage, all in one ``shard_map``.  The condensed plan
works out to exactly the four halo strips (the paper's
``halo_exchange_intrinsic``), but the full ladder applies: ``strategy=``
accepts any rung or ``"auto"`` — ranked on the FULL per-step window cost
(``perfmodel.predict_heat2d_window``: eqs. 19–22 plus the edge-ring
recompute term of the overlap split) for the overlap/condensed pair, by
the generic §5 exchange models for the rest.

Devices at the grid boundary read guaranteed-zero slots, which is harmless:
the update is masked to the global interior, reproducing the paper's
"boundary rows/cols are copied" semantics.

The halo strips are a ``Destination`` descriptor (four named slot tables:
``up`` / ``down`` / ``left`` / ``right``), so by default each step's
``finish`` scatters the landed recv buffer *straight into the strips* —
O(perimeter) unpack work for the O(perimeter) exchange.  Pass
``materialize="full"`` to fall back to assembling the full-length
``mythread_x_copy`` (big_m*big_n elements, the paper's UPCv3 layout) and
indexing the strips out of it — bit-identical results, O(area) buffer
traffic per step.

``overlap=True`` (or ``strategy="overlap"``) splits each step via the
``OverlapHandle`` protocol: the tile-interior update (no halo dependency)
runs while the exchange is in flight; only the one-cell edge ring consumes
the landed halos.  Composes with ``use_kernel=True`` (interior and edge
strips through the Pallas stencil kernel) and with either materialization.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.comm.pattern import AccessPattern, Destination
from repro.comm.plan import Topology

__all__ = ["Heat2D"]


def _halo_indices(big_m, big_n, mprocs, nprocs, zero_slot):
    """Per-rank global ids of the four incoming halo strips (tile-major
    layout, see AccessPattern.from_stencil5); out-of-domain -> zero_slot."""
    m_loc, n_loc = big_m // mprocs, big_n // nprocs
    tile = m_loc * n_loc
    p = mprocs * nprocs
    up = np.full((p, n_loc), zero_slot, np.int32)
    down = np.full((p, n_loc), zero_slot, np.int32)
    left = np.full((p, m_loc), zero_slot, np.int32)
    right = np.full((p, m_loc), zero_slot, np.int32)
    cols = np.arange(n_loc)
    rows = np.arange(m_loc)
    for ip in range(mprocs):
        for kp in range(nprocs):
            r = ip * nprocs + kp
            if ip > 0:      # neighbor above sends its last row
                up[r] = (r - nprocs) * tile + (m_loc - 1) * n_loc + cols
            if ip < mprocs - 1:  # neighbor below sends its first row
                down[r] = (r + nprocs) * tile + cols
            if kp > 0:      # left neighbor sends its last column
                left[r] = (r - 1) * tile + rows * n_loc + (n_loc - 1)
            if kp < nprocs - 1:  # right neighbor sends its first column
                right[r] = (r + 1) * tile + rows * n_loc
    return up, down, left, right


class Heat2D:
    """Distributed 2D heat solver on a (row_axis × col_axis) device grid.

    ``strategy`` picks the gather rung for the halo exchange (default
    ``condensed``; ``"auto"`` lets the §5 models choose); ``overlap=True``
    additionally splits each step into the tile-interior update (which
    needs no halo and can hide the exchange) plus a thin edge-ring update
    that consumes the landed halos — the heat-equation analogue of the SpMV
    ``overlap`` strategy.  ``materialize`` picks the unpack: ``"dest"``
    (default) lands the exchange straight into the four named halo strips
    (O(halo) per step); ``"full"`` assembles the paper's full-length
    ``mythread_x_copy`` first (bit-identical result).
    """

    def __init__(self, mesh, big_m: int, big_n: int, *,
                 row_axis: str = "data", col_axis: str = "model",
                 coef: float = 0.1, use_kernel: bool = False,
                 overlap: bool = False, strategy: str | None = None,
                 blocksize: int | str | None = None,
                 shards_per_node: int | None = None,
                 materialize: str = "dest", hw=None,
                 n_steps_hint: int | None = None):
        if strategy is None:
            strategy = "overlap" if overlap else "condensed"
        assert materialize in ("dest", "full"), materialize
        self.mesh = mesh
        mprocs = mesh.shape[row_axis]
        nprocs = mesh.shape[col_axis]
        assert big_m % mprocs == 0 and big_n % nprocs == 0
        self.mprocs, self.nprocs = mprocs, nprocs
        self.big_m, self.big_n = big_m, big_n
        m_loc, n_loc = big_m // mprocs, big_n // nprocs
        self.spec = P(row_axis, col_axis)
        self.sharding = NamedSharding(mesh, self.spec)
        self.materialize = materialize

        comm_axes = (row_axis, col_axis)
        p = mprocs * nprocs
        n = big_m * big_n
        topo = Topology(p, shards_per_node or p)
        # the tile edge rings hold every foreign access: planning on them
        # alone gives the same exchange at O(perimeter) host cost
        pattern = AccessPattern.from_stencil5(big_m, big_n, mprocs, nprocs,
                                              edge_only=True)
        destination = None
        if materialize == "dest":
            # the four halo strips ARE the consumer slots: finish() lands
            # the exchange straight into them, no length-n x_copy ever built
            up, down, left, right = _halo_indices(
                big_m, big_n, mprocs, nprocs, zero_slot=Destination.ZERO)
            destination = Destination.from_slots(
                up=up, down=down, left=left, right=right)

        self.predicted_times = None
        if strategy == "auto":
            # ROADMAP refinement: rank overlap vs condensed on the FULL
            # per-step window — eqs. 19–22 plus the edge-ring recompute
            # term of the interior/edge split (the generic §5 exchange
            # models keep pricing the replicate/blockwise rungs; without
            # the ring term the model mispicks overlap on tiles so small
            # the four strip stencils recompute more than the whole tile)
            from repro.comm import perfmodel as pm, plan_cache, select
            from repro.comm.exchange import measure_hw

            if hw is None:
                hw = measure_hw(mesh, comm_axes)
            bs = blocksize
            if bs == "auto":
                bs = select.choose_blocksize(pattern.indices, n, p,
                                             topology=topo, hw=hw)
            base_plan = plan_cache.get_comm_plan(
                pattern.indices, n, p, blocksize=bs, topology=topo)
            pred = dict(select.rank_strategies(
                base_plan, pattern.r, hw,
                materialize="dest" if destination is not None else None,
                dest_slots=(destination.num_slots
                            if destination is not None else None)))
            w2d = pm.Heat2DWorkload(big_m=big_m, big_n=big_n,
                                    mprocs=mprocs, nprocs=nprocs,
                                    topology=topo)
            win = pm.predict_heat2d_window(
                w2d, hw,
                materialize="full" if materialize == "full" else None)
            # bridge the generic exchange-scale entries onto the window
            # scale before the argmin compares them: shift replicate/
            # blockwise by the delta that maps the generic condensed price
            # to its full-window price, so all four entries carry the same
            # (exchange + whole-tile compute) units
            offset = win["condensed"] - pred["condensed"]
            for rung in ("replicate", "blockwise"):
                pred[rung] = max(pred[rung] + offset, 0.0)
            pred["condensed"] = win["condensed"]
            pred["overlap"] = win["overlap"]
            if n_steps_hint is not None:
                # rank on the n-step steady-state LOOP instead of one call:
                # window setup amortizes away (eq.-23 extension) and the
                # overlap rung earns its double-buffer credit — a rung that
                # wins one dispatch can lose the loop and vice versa
                setup = pm.window_setup_time(topo, hw)
                for rung in ("replicate", "blockwise"):
                    pred[rung] = pm.scan_loop_cost(pred[rung], setup,
                                                   n_steps_hint)
                scn = pm.predict_heat2d_scan(
                    w2d, hw, n_steps_hint,
                    materialize="full" if materialize == "full" else None)
                pred["condensed"] = scn["condensed"]
                pred["overlap"] = scn["overlap"]
            strategy = min(pred, key=pred.get)
            blocksize = bs
            self.predicted_times = pred
        self.strategy = strategy
        # split on the RESOLVED strategy: "auto" may pick overlap, whose
        # predicted win exists only if the interior/edge split actually runs
        self.overlap = overlap or strategy == "overlap"
        split = self.overlap

        # --- the per-step halo exchange + stencil as ONE ExchangeSchedule:
        # the gather stage issues the exchange, the interior stage (when
        # split) runs inside its collective window, the final stage unpacks
        # the landed halos and applies the paper's Listing-8 update
        from repro.comm.schedule import Schedule

        halo_idx = None
        if materialize != "dest":
            # runtime halo index tables into the assembled x_copy; padding
            # reads the guaranteed-zero slot
            halo_idx = _halo_indices(big_m, big_n, mprocs, nprocs,
                                     zero_slot=n + 1)

        def stencil(x):
            if use_kernel:
                from repro.kernels import ops as kops
                return kops.stencil2d(x, coef=coef)
            from repro.kernels import ref as kref
            return kref.stencil2d_ref(x, coef)

        def add_common_stages(sched, *, double_buffer):
            phi_ref = sched.input("phi", spec=self.spec)
            flat = sched.compute(lambda phi: phi.reshape(-1), phi_ref,
                                 name="flatten")
            halo_refs = ()
            if materialize != "dest":
                halo_refs = tuple(
                    sched.constant(a, nm, spec=P(comm_axes))
                    for nm, a in zip(("up_i", "down_i", "left_i", "right_i"),
                                     halo_idx))
            fk = (None if materialize == "dest"
                  else dict(extra_slots=1, copy_own=False))
            if double_buffer:
                g = sched.gather(pattern, double_buffer=True, prime=flat,
                                 destination=destination, name="halo",
                                 finish_kwargs=fk)
            else:
                g = sched.gather(pattern, src=flat, destination=destination,
                                 name="halo", finish_kwargs=fk)
            return phi_ref, g, halo_refs

        def unpack_halos(landed, rest):
            if materialize == "dest":
                return (landed["up"], landed["down"],
                        landed["left"], landed["right"]), rest
            up_i, dn_i, lf_i, rt_i = rest[:4]
            return (landed[up_i[0]], landed[dn_i[0]],
                    landed[lf_i[0]], landed[rt_i[0]]), rest[4:]

        def pad_with_halos(phi, halos):
            up_v, dn_v, lf_v, rt_v = halos
            padded = jnp.zeros((m_loc + 2, n_loc + 2), phi.dtype)
            padded = padded.at[1:-1, 1:-1].set(phi)
            padded = padded.at[0, 1:-1].set(up_v)
            padded = padded.at[-1, 1:-1].set(dn_v)
            padded = padded.at[1:-1, 0].set(lf_v)
            padded = padded.at[1:-1, -1].set(rt_v)
            return padded

        def ring_strips(padded):
            # only the one-cell edge ring consumes the landed halos, via
            # four thin strips of the padded assembly
            top = stencil(padded[0:3, :])[1, 1:-1]
            bottom = stencil(padded[-3:, :])[1, 1:-1]
            left = stencil(padded[:, 0:3])[1:-1, 1]
            right = stencil(padded[:, -3:])[1:-1, 1]
            return top, bottom, left, right

        def interior_mask(phi):
            # global boundary cells keep their value (paper copies the
            # boundary)
            ip = jax.lax.axis_index(row_axis)
            kp = jax.lax.axis_index(col_axis)
            grow = ip * m_loc + jax.lax.broadcasted_iota(jnp.int32,
                                                         phi.shape, 0)
            gcol = kp * n_loc + jax.lax.broadcasted_iota(jnp.int32,
                                                         phi.shape, 1)
            return ((grow > 0) & (grow < big_m - 1)
                    & (gcol > 0) & (gcol < big_n - 1))

        def build_step():
            sched = Schedule()
            phi_ref, g, halo_refs = add_common_stages(sched,
                                                      double_buffer=False)
            inner_refs = ()
            if split:
                # interior update (cells 1..m-2 × 1..n-2) has no halo
                # dependency — it runs inside the exchange window
                inner_refs = (sched.compute(stencil, phi_ref,
                                            name="interior"),)

            def finalize(phi, landed, *rest):
                halos, rest = unpack_halos(landed, rest)
                padded = pad_with_halos(phi, halos)
                # --- compute (paper Listing 8) ---
                if split:
                    (inner,) = rest
                    top, bottom, left, right = ring_strips(padded)
                    upd = inner.at[0, :].set(top).at[-1, :].set(bottom)
                    upd = upd.at[:, 0].set(left).at[:, -1].set(right)
                else:
                    upd = stencil(padded)[1:-1, 1:-1]
                return jnp.where(interior_mask(phi), upd, phi)

            out = sched.compute(finalize, phi_ref, g, *halo_refs,
                                *inner_refs, name="update")
            return sched, phi_ref, out

        def build_scan_overlap():
            # double-buffered body: the delivered halos were issued by the
            # PREVIOUS iteration's feed, so this iteration pays no exchange
            # launch before the ring.  The edge ring is refreshed first,
            # its flattened field feeds the NEXT exchange, and the
            # tile-interior stencil runs inside that freshly opened window
            # (step k+1's gather in flight while step k's interior
            # computes).
            sched = Schedule()
            phi_ref, g, halo_refs = add_common_stages(sched,
                                                      double_buffer=True)

            def ring_half(phi, landed, *rest):
                halos, _ = unpack_halos(landed, rest)
                padded = pad_with_halos(phi, halos)
                top, bottom, left, right = ring_strips(padded)
                half = phi.at[0, :].set(top).at[-1, :].set(bottom)
                half = half.at[:, 0].set(left).at[:, -1].set(right)
                # half's boundary ring now holds step-(k+1) values (masked
                # to the paper's copied global boundary); its interior
                # still holds step k.  The exchange only ever delivers
                # tile-perimeter cells, so feeding half is bit-identical
                # to feeding the finished step-(k+1) field.
                return jnp.where(interior_mask(phi), half, phi)

            half = sched.compute(ring_half, phi_ref, g, *halo_refs,
                                 name="ring_half")
            flat_half = sched.compute(lambda h: h.reshape(-1), half,
                                      name="flatten_half")
            sched.feed(g, flat_half)
            inner = sched.compute(stencil, phi_ref, name="interior")

            def combine(half, inner):
                # local interior cells are never on the global boundary,
                # so only the ring (already masked in half) needs care
                upd = inner.at[0, :].set(half[0, :])
                upd = upd.at[-1, :].set(half[-1, :])
                return upd.at[:, 0].set(half[:, 0]).at[:, -1].set(half[:, -1])

            out = sched.compute(combine, half, inner, name="update")
            return sched, phi_ref, out

        sched, _, out = build_step()
        self.schedule = sched.compile(
            mesh, axis_name=comm_axes, strategy=strategy,
            blocksize=blocksize, topology=topo, hw=hw,
            output=out, out_spec=self.spec)
        self.gather = sched.exchange_of(
            next(s.ref for s in sched._stages if s.kind == "gather"))
        if self.predicted_times is None:
            self.predicted_times = self.gather.predicted_times

        # --- the n-step loop as ONE ScanSchedule: the shard_map window
        # persists across iterations (one plan probe, one hw memo hit,
        # zero per-step host dispatch).  The overlap rung scans the
        # double-buffered body; the other rungs scan the per-step body
        # unchanged.  Sharing the step schedule's resolved plan makes the
        # second resolve a plan-cache memory hit, not a re-probe.
        builder = build_scan_overlap if split else build_step
        sscan, phi_in, sout = builder()
        self.scan_schedule = sscan.scan(
            mesh, carry=phi_in, output=sout, axis_name=comm_axes,
            strategy=strategy, blocksize=self.gather.plan.blocksize,
            topology=topo, hw=hw, n_steps_hint=n_steps_hint)

    @property
    def counts(self):
        return self.gather.counts

    def init_field(self, seed: int = 0) -> jax.Array:
        rng = np.random.default_rng(seed)
        phi = rng.standard_normal((self.big_m, self.big_n)).astype(np.float32)
        return jax.device_put(phi, self.sharding)

    def run(self, phi: jax.Array, steps: int) -> jax.Array:
        """Advance ``steps`` iterations in ONE persistent exchange window
        (``ScanSchedule``): plans resolve once, the hardware memo is probed
        once, and no per-step host dispatch happens inside the loop."""
        return self.scan_schedule(phi, n_steps=steps)

    def reference(self, phi: np.ndarray, steps: int, coef: float = 0.1):
        from repro.kernels import ref as kref
        x = jnp.asarray(phi)
        for _ in range(steps):
            x = kref.stencil2d_ref(x, coef)
        return np.asarray(x)
