"""Subprocess helper: distributed heat2d vs sequential reference (8 dev)."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import numpy as np

from repro.core.heat2d import Heat2D

from repro.launch.mesh import make_local_mesh


def main():
    mesh = make_local_mesh((2, 4), ("data", "model"))
    for use_kernel, overlap in ((False, False), (True, False), (False, True)):
        h = Heat2D(mesh, 32, 64, coef=0.07, use_kernel=use_kernel,
                   overlap=overlap)
        phi0 = h.init_field(3)
        got = np.asarray(h.run(phi0, 7))
        want = h.reference(np.asarray(phi0), 7, coef=0.07)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    print("HEAT2D_OK")


if __name__ == "__main__":
    main()
