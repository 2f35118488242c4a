"""Blocking-tier coverage for the ``overlap`` rung.

The end-to-end multi-device checks live in the slow subprocess tests; these
run in-process on whatever devices the pytest process has (1 locally, 8
under the CI gate's XLA_FLAGS) so a numerics regression in the own/foreign
split or the interior/edge split cannot pass the blocking job.
"""
import jax
import numpy as np
import pytest

from helpers.check_compact_overlap import CASES, run
from repro.core.heat2d import Heat2D
from repro.core.matrix import make_mesh_like_matrix, spmv_ref_np
from repro.core.spmv import DistributedSpMV


def test_overlap_spmv_matches_reference():
    ndev = len(jax.devices())
    mesh = jax.make_mesh((ndev,), ("data",))
    n = 128 * ndev
    m = make_mesh_like_matrix(n, 8, locality_window=n // 8,
                              long_range_frac=0.1, seed=5)
    eng = DistributedSpMV(m, mesh, strategy="overlap", blocksize=32)
    x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
    np.testing.assert_allclose(np.asarray(eng(eng.shard_vector(x))),
                               spmv_ref_np(m, x), rtol=2e-4, atol=2e-4)
    # the gather-only view (condensed exchange) still delivers every index
    xc = np.asarray(eng.gather_x_copy(eng.shard_vector(x)))
    ss = eng.plan.shard_size
    for q in range(ndev):
        needed = np.unique(m.cols[q * ss:(q + 1) * ss])
        np.testing.assert_array_equal(xc[q, needed], x[needed])


def test_overlap_heat2d_matches_reference():
    ndev = len(jax.devices())
    shape = (2, ndev // 2) if ndev % 2 == 0 and ndev > 1 else (1, ndev)
    mesh = jax.make_mesh(shape, ("data", "model"))
    h = Heat2D(mesh, shape[0] * 16, shape[1] * 16, coef=0.1, overlap=True)
    phi = h.init_field(1)
    got = np.asarray(h.run(phi, 5))
    want = h.reference(np.asarray(phi), 5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_heat2d_auto_enables_split_when_overlap_wins():
    """strategy="auto" resolving to overlap must actually run the
    interior/edge split — the §5 model's predicted win exists only if
    compute is scheduled inside the exchange window."""
    from repro.comm import perfmodel as pm

    ndev = len(jax.devices())
    shape = (2, ndev // 2) if ndev % 2 == 0 and ndev > 1 else (1, ndev)
    mesh = jax.make_mesh(shape, ("data", "model"))
    h = Heat2D(mesh, shape[0] * 16, shape[1] * 16, strategy="auto",
               hw=pm.ABEL)
    assert h.overlap == (h.strategy == "overlap")
    phi = h.init_field(4)
    got = np.asarray(h.run(phi, 5))
    want = h.reference(np.asarray(phi), 5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_overlap_composes_with_kernel():
    """The ladder's fourth rung through the Pallas path: the split-kernel
    on-copy variant runs the own partial on x_local and the foreign partial
    on the condensed x_copy, both through the windowed kernel."""
    ndev = len(jax.devices())
    mesh = jax.make_mesh((ndev,), ("data",))
    n = 128 * ndev
    m = make_mesh_like_matrix(n, 4, locality_window=n // 8,
                              long_range_frac=0.1, seed=0)
    eng = DistributedSpMV(m, mesh, strategy="overlap", blocksize=32,
                          use_kernel=True)
    x = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    np.testing.assert_allclose(np.asarray(eng(eng.shard_vector(x))),
                               spmv_ref_np(m, x), rtol=2e-4, atol=2e-4)

    mesh2 = jax.make_mesh((1, ndev), ("data", "model"))
    h = Heat2D(mesh2, 16, 16 * ndev, coef=0.1, overlap=True, use_kernel=True)
    phi = h.init_field(2)
    got = np.asarray(h.run(phi, 4))
    want = h.reference(np.asarray(phi), 4)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the compact foreign delivery on 4 host devices (one subprocess)
# ---------------------------------------------------------------------------

# f32 products of r_nz 8 over 3 normalised steps: a few ulps of max |y|
F32_TOL = 8 * np.finfo(np.float32).eps


@pytest.fixture(scope="module")
def compact():
    return run("overlap")


@pytest.mark.parametrize("case", list(CASES))
def test_compact_overlap_matches_reference(compact, case):
    """Forward ``iterate`` through the overlap rung's compact foreign
    delivery (jnp and kernel unpack) against a float64 power iteration and
    the ``condensed`` rung: mesh-like, edge-heavy (many foreign columns at
    the shard edges, none in the middle), block-diagonal (no device has a
    foreign slot) and a one-device mesh."""
    got = compact[case]
    assert got["err_ref"] < F32_TOL, got
    assert got["err_condensed"] < F32_TOL, got
    assert got["err_kernel"] < F32_TOL, got


@pytest.mark.parametrize("case", list(CASES))
def test_compact_overlap_counts_slots(compact, case):
    """The engine and the telemetry counter give the compact length (the
    most off-shard reads of any device, at least 1) beside the dense
    rows x r_rem_max table it replaced."""
    got = compact[case]
    assert got["delivered"] == got["dest_len"] == max(1, got["foreign_max"])
    assert got["dense"] == got["rows_x_r_rem_max"]
    assert got["counter"] == [1, got["delivered"], got["dense"]]
    assert got["delivered"] < got["dense"]
