"""Quickstart: distributed SpMV with the paper's condensed communication.

Runs on however many devices exist (1 CPU device works; for a multi-device
demo: XLA_FLAGS=--xla_force_host_platform_device_count=8 python
examples/quickstart.py).
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.core.matrix import make_mesh_like_matrix, spmv_ref_np
from repro.comm.perfmodel import ABEL, TPU_V5E, SpmvWorkload, predict_all
from repro.core.spmv import DistributedSpMV

from repro.launch.mesh import make_local_mesh


def main():
    n_dev = len(jax.devices())
    mesh = make_local_mesh((n_dev,), ("data",))
    print(f"devices: {n_dev}")

    # a synthetic unstructured-mesh matrix (paper §6.1 structure)
    n, r_nz = n_dev * 8192, 16
    matrix = make_mesh_like_matrix(n, r_nz, long_range_frac=0.02, seed=0)

    # the paper's UPCv3: one-time plan -> condensed, consolidated messages
    engine = DistributedSpMV(matrix, mesh, strategy="condensed",
                             blocksize=512)
    x = engine.shard_vector(
        np.random.default_rng(0).standard_normal(n).astype(np.float32))
    y = engine(x)
    np.testing.assert_allclose(
        np.asarray(y), spmv_ref_np(matrix, np.asarray(x)),
        rtol=2e-4, atol=2e-4)
    print("condensed SpMV matches the dense reference ✓")

    c = engine.counts
    print(f"comm volume (elements): condensed={c.total_condensed_volume()} "
          f"blockwise={c.total_blockwise_volume()} replicate={n_dev * n}")

    # the paper's performance models predict this workload on Abel and on
    # a TPU v5e pod with the same four hardware parameters
    w = SpmvWorkload(n=n, r_nz=r_nz, p=n_dev, blocksize=512,
                     topology=engine.plan.topology, counts=c)
    for name, hw in (("Abel(paper)", ABEL), ("TPUv5e", TPU_V5E)):
        t = predict_all(w, hw)
        print(f"predicted seconds/iter on {name}: " +
              " ".join(f"{k}={v:.2e}" for k, v in t.items()))


if __name__ == "__main__":
    main()
