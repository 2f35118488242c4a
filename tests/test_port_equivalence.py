"""Port equivalence: the IrregularGather-based consumers must produce
BIT-IDENTICAL outputs to the pre-refactor implementations.

The pre-refactor paths are reconstructed here verbatim: SpMV as the direct
composition of the strategy-local gather with the local EllPack compute
(what ``DistributedSpMV.step_local`` used to inline), Heat2D as the
ppermute-based halo exchange (``_shift`` + padded-tile update).  Both moved
pure float values with no arithmetic on the wire, so the ported versions
must agree to the last bit — any nonzero difference means the refactor
changed semantics, not just structure.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import strategies as strat
from repro.core.heat2d import Heat2D
from repro.core.matrix import make_mesh_like_matrix
from repro.core.spmv import DistributedSpMV
from jax.sharding import NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# pre-refactor SpMV step (direct strategy-local composition)
# ---------------------------------------------------------------------------

def _legacy_spmv(matrix, mesh, strategy, plan, axis_name="data"):
    p = mesh.shape[axis_name]
    shard_size = plan.shard_size
    n = plan.n
    gather_local = strat.make_gather_local(plan, strategy, axis_name)
    shard = NamedSharding(mesh, P(axis_name))
    shard2 = NamedSharding(mesh, P(axis_name, None))
    diag = jax.device_put(matrix.diag, shard)

    if strategy == "overlap":
        loc_vals = np.take_along_axis(matrix.vals, plan.loc_src, axis=1)
        rem_vals = np.take_along_axis(matrix.vals, plan.rem_src, axis=1)
        args = tuple(
            jax.device_put(a, shard)
            for a in strat.plan_device_args(plan, strategy)
        ) + tuple(
            jax.device_put(a, shard2)
            for a in (plan.loc_cols, loc_vals, plan.rem_cols, rem_vals))

        def step_local(x_local, diag_l, send_idx, recv_idx, loc_cols_l,
                       loc_vals_l, rem_cols_l, rem_vals_l):
            buf = x_local[send_idx[0]]
            recv = jax.lax.all_to_all(
                buf, axis_name, split_axis=0, concat_axis=0, tiled=True)
            x_ext = jnp.concatenate([x_local, jnp.zeros((1,), x_local.dtype)])
            y_own = diag_l * x_local + (
                loc_vals_l * x_ext[loc_cols_l]).sum(axis=-1)
            x_copy = jnp.zeros((n + 2,), x_local.dtype)
            x_copy = x_copy.at[recv_idx[0].ravel()].set(recv.ravel())
            y_rem = (rem_vals_l * x_copy[rem_cols_l]).sum(axis=-1)
            return y_own + y_rem

        in_specs = (P(axis_name), P(axis_name),
                    P(axis_name), P(axis_name)) + (P(axis_name, None),) * 4
        base = (diag,)
    else:
        vals = jax.device_put(matrix.vals, shard2)
        cols = jax.device_put(matrix.cols, shard2)
        args = tuple(jax.device_put(a, shard)
                     for a in strat.plan_device_args(plan, strategy))

        def step_local(x_local, diag_l, vals_l, cols_l, *plan_args):
            x_copy = gather_local(x_local, *plan_args)
            me = jax.lax.axis_index(axis_name)
            own = jax.lax.dynamic_slice(
                x_copy, (me * shard_size,), (shard_size,))
            return diag_l * own + (vals_l * x_copy[cols_l]).sum(axis=-1)

        in_specs = ((P(axis_name), P(axis_name), P(axis_name, None),
                     P(axis_name, None))
                    + strat.gather_in_specs(strategy, axis_name))
        base = (diag, vals, cols)

    mapped = jax.shard_map(step_local, mesh=mesh, in_specs=in_specs,
                              out_specs=P(axis_name), check_vma=False)
    return jax.jit(lambda x: mapped(x, *base, *args))


def test_spmv_port_is_bit_identical():
    ndev = len(jax.devices())
    mesh = jax.make_mesh((ndev,), ("data",))
    n = 128 * ndev
    m = make_mesh_like_matrix(n, 8, locality_window=n // 8,
                              long_range_frac=0.1, seed=11)
    x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
    for strategy in strat.STRATEGIES:
        eng = DistributedSpMV(m, mesh, strategy=strategy, blocksize=32)
        legacy = _legacy_spmv(m, mesh, strategy, eng.plan)
        xs = eng.shard_vector(x)
        np.testing.assert_array_equal(
            np.asarray(eng(xs)), np.asarray(legacy(xs)),
            err_msg=f"strategy={strategy} diverged from pre-refactor step")


# ---------------------------------------------------------------------------
# pre-refactor Heat2D step (ppermute halo exchange)
# ---------------------------------------------------------------------------

def _shift(x, axis_name, direction, size):
    perm = [(i, i + direction) for i in range(size)
            if 0 <= i + direction < size]
    return jax.lax.ppermute(x, axis_name, perm)


def _legacy_heat2d_step(phi, *, row_axis, col_axis, mprocs, nprocs, coef,
                        overlap):
    m_loc, n_loc = phi.shape
    ip = jax.lax.axis_index(row_axis)
    kp = jax.lax.axis_index(col_axis)

    up_halo = _shift(phi[-1:, :], row_axis, +1, mprocs)
    down_halo = _shift(phi[:1, :], row_axis, -1, mprocs)
    left_halo = _shift(phi[:, -1:], col_axis, +1, nprocs)
    right_halo = _shift(phi[:, :1], col_axis, -1, nprocs)

    padded = jnp.zeros((m_loc + 2, n_loc + 2), phi.dtype)
    padded = padded.at[1:-1, 1:-1].set(phi)
    padded = padded.at[0, 1:-1].set(up_halo[0])
    padded = padded.at[-1, 1:-1].set(down_halo[0])
    padded = padded.at[1:-1, 0].set(left_halo[:, 0])
    padded = padded.at[1:-1, -1].set(right_halo[:, 0])

    from repro.kernels import ref as kref
    if overlap:
        inner = kref.stencil2d_ref(phi, coef)
        top = kref.stencil2d_ref(padded[0:3, :], coef)[1, 1:-1]
        bottom = kref.stencil2d_ref(padded[-3:, :], coef)[1, 1:-1]
        left = kref.stencil2d_ref(padded[:, 0:3], coef)[1:-1, 1]
        right = kref.stencil2d_ref(padded[:, -3:], coef)[1:-1, 1]
        upd = inner.at[0, :].set(top).at[-1, :].set(bottom)
        upd = upd.at[:, 0].set(left).at[:, -1].set(right)
    else:
        upd = kref.stencil2d_ref(padded, coef)[1:-1, 1:-1]

    grow = ip * m_loc + jax.lax.broadcasted_iota(jnp.int32, phi.shape, 0)
    gcol = kp * n_loc + jax.lax.broadcasted_iota(jnp.int32, phi.shape, 1)
    big_m, big_n = mprocs * m_loc, nprocs * n_loc
    interior = ((grow > 0) & (grow < big_m - 1)
                & (gcol > 0) & (gcol < big_n - 1))
    return jnp.where(interior, upd, phi)


def _legacy_heat2d(mesh, big_m, big_n, coef, overlap,
                   row_axis="data", col_axis="model"):
    mprocs, nprocs = mesh.shape[row_axis], mesh.shape[col_axis]
    spec = P(row_axis, col_axis)
    local = functools.partial(
        _legacy_heat2d_step, row_axis=row_axis, col_axis=col_axis,
        mprocs=mprocs, nprocs=nprocs, coef=coef, overlap=overlap)
    mapped = jax.shard_map(local, mesh=mesh, in_specs=spec,
                              out_specs=spec, check_vma=False)

    @functools.partial(jax.jit, static_argnames=("steps",))
    def run(phi, steps):
        def body(x, _):
            return mapped(x), None
        out, _ = jax.lax.scan(body, phi, None, length=steps)
        return out

    return run


def test_heat2d_port_is_bit_identical():
    ndev = len(jax.devices())
    shape = (2, ndev // 2) if ndev % 2 == 0 and ndev > 1 else (1, ndev)
    mesh = jax.make_mesh(shape, ("data", "model"))
    big_m, big_n = shape[0] * 12, shape[1] * 20
    for overlap in (False, True):
        h = Heat2D(mesh, big_m, big_n, coef=0.13, overlap=overlap)
        legacy = _legacy_heat2d(mesh, big_m, big_n, 0.13, overlap)
        phi = h.init_field(9)
        np.testing.assert_array_equal(
            np.asarray(h.run(phi, 6)), np.asarray(legacy(phi, 6)),
            err_msg=f"overlap={overlap} diverged from ppermute halo path")
