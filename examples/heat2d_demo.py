"""Paper §8: 2D heat equation with halo exchange on a 2D device grid,
verified against the sequential stencil and timed vs the eq.(19)-(22) model.

Run: python examples/heat2d_demo.py   (re-execs itself with 8 devices)
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

from repro.core.heat2d import Heat2D
from repro.comm.perfmodel import Heat2DWorkload, predict_heat2d
from repro.comm.plan import Topology

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.common import calibrate_host  # noqa: E402

from repro.launch.mesh import make_local_mesh


def main():
    mesh = make_local_mesh((2, 4), ("data", "model"))
    big_m, big_n, steps = 1024, 2048, 200
    # default materialize="dest": the halo exchange lands straight in the
    # four named strips (up/down/left/right Destination slots) — O(halo)
    # unpack per step, no big_m*big_n x_copy ever assembled
    h = Heat2D(mesh, big_m, big_n, coef=0.1)
    phi = h.init_field(0)

    # correctness vs the sequential reference (few steps)
    got = np.asarray(h.run(phi, 5))
    want = h.reference(np.asarray(phi), 5)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    print("distributed heat2d matches sequential stencil ✓")

    def timed(solver):
        jax.block_until_ready(solver.run(phi, steps))
        t0 = time.perf_counter()
        jax.block_until_ready(solver.run(phi, steps))
        return time.perf_counter() - t0

    dt = timed(h)
    # the paper's layout for comparison: assemble the full-length copy,
    # then index the strips out of it (bit-identical results)
    dt_full = timed(Heat2D(mesh, big_m, big_n, coef=0.1,
                           materialize="full"))

    hw = calibrate_host()
    w = Heat2DWorkload(big_m=big_m, big_n=big_n, mprocs=2, nprocs=4,
                       topology=Topology(8, 8))
    pred = predict_heat2d(w, hw, steps=steps)
    print(f"{steps} steps on 2x4 grid: measured {dt:.3f}s targeted-unpack "
          f"({dt_full:.3f}s with full x_copy assembly), "
          f"predicted {pred['halo'] + pred['comp']:.3f}s "
          f"(halo {pred['halo']:.3f} + comp {pred['comp']:.3f})")


if __name__ == "__main__":
    main()
