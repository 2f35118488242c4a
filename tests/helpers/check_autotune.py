"""Subprocess helper: autotuner predictions vs measurements at P=8.

Validates the ISSUE-1 closing-the-loop claim on the paper's mesh-like matrix:
the §5 models, fed with the measured hardware parameters of THIS host, must
rank the strategies well enough that either (a) the predicted winner measures
within 2x of the measured winner, or (b) the model itself calls the two a
near-tie (predicted times within 25%) — on CPU host devices tau dominates
every strategy's prediction, so the model legitimately reports "these rungs
are equivalent here" and measurement noise picks the winner.  A strict
total-order comparison is not meaningful in that regime.  The structurally
robust part of the ranking — blockwise pays the whole-block volume tax and
comes last — is asserted unconditionally.

Also asserts ``strategy="auto"`` resolves to a concrete rung and matches the
reference SpMV bit-for-tolerance.
"""
import os
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import numpy as np

from repro.comm import select
from repro.comm.exchange import measure_hw
from repro.core.matrix import make_mesh_like_matrix, spmv_ref_np
from repro.core.spmv import DistributedSpMV
from repro.comm.strategies import STRATEGIES


def _measure(eng, x, iters=20):
    jax.block_until_ready(eng(x))
    jax.block_until_ready(eng(x))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(eng(x))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = jax.make_mesh((8,), ("data",))
    n, r_nz = 1 << 15, 16
    m = make_mesh_like_matrix(n, r_nz, locality_window=n // 64,
                              long_range_frac=0.02, seed=1)
    x_host = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    y_ref = spmv_ref_np(m, x_host)
    bs = n // 8 // 16

    # measured hardware parameters for THIS mesh (host devices = own nodes)
    hw = measure_hw(mesh, "data")
    print(f"calibrated w_private={hw.w_private/1e9:.2f}GB/s "
          f"w_remote={hw.w_remote/1e9:.2f}GB/s tau={hw.tau*1e6:.1f}us "
          f"cacheline={hw.cacheline}B")

    engines, measured = {}, {}
    for strategy in STRATEGIES:
        eng = DistributedSpMV(m, mesh, strategy=strategy, blocksize=bs,
                              shards_per_node=1)
        x = eng.shard_vector(x_host)
        np.testing.assert_allclose(np.asarray(eng(x)), y_ref, rtol=2e-4,
                                   atol=2e-4)
        engines[strategy] = eng
        measured[strategy] = _measure(eng, x)

    ranked = select.rank_strategies(engines["condensed"].plan, r_nz, hw)
    predicted = dict(ranked)
    predicted_best = ranked[0][0]
    measured_best = min(measured, key=measured.get)
    print("predicted:", [(s, f"{t*1e6:.0f}us") for s, t in ranked])
    print("measured: ", sorted(((s, f"{t*1e6:.0f}us")
                                for s, t in measured.items()),
                               key=lambda kv: float(kv[1][:-2])))

    # structural claim: whole-block volume tax puts blockwise last
    assert ranked[-1][0] == "blockwise", ranked

    # prediction quality gate: the model's pick must be competitive, unless
    # the model itself declares a near-tie with the measured winner (same
    # symmetric-drift metric as the benchmark matrix gate; predicted_best/
    # measured_best each minimize their dict, so the ratios are >= 1)
    from model_error import model_error
    competitive = model_error(measured[predicted_best],
                              measured[measured_best]) <= 1.0
    near_tie = model_error(predicted[measured_best],
                           predicted[predicted_best]) <= 0.25
    assert competitive or near_tie, (
        f"model picked {predicted_best} "
        f"({measured[predicted_best]*1e6:.0f}us measured, "
        f"{predicted[predicted_best]*1e6:.0f}us predicted) but "
        f"{measured_best} measured {measured[measured_best]*1e6:.0f}us "
        f"({predicted[measured_best]*1e6:.0f}us predicted)")

    # auto resolves to a concrete rung and matches the reference
    eng = DistributedSpMV(m, mesh, strategy="auto", blocksize=bs,
                          shards_per_node=1, hw=hw)
    assert eng.strategy == predicted_best, (eng.strategy, predicted_best)
    x = eng.shard_vector(x_host)
    np.testing.assert_allclose(np.asarray(eng(x)), y_ref, rtol=2e-4,
                               atol=2e-4)
    print(f"AUTOTUNE_OK auto={eng.strategy} measured_best={measured_best}")


if __name__ == "__main__":
    main()
