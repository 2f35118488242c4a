"""Subprocess helper: elastic checkpoint restore across mesh shapes (8 dev).

Saves a sharded tree from an (8,)-data mesh, restores onto a (2,4) mesh with
different shardings — the elastic-restart path.
"""
import os
import sys
import tempfile

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import manager as ckpt

from repro.launch.mesh import make_local_mesh


def main():
    tmp = tempfile.mkdtemp()
    mesh_a = make_local_mesh((8,), ("data",))
    tree = {
        "w": jax.device_put(np.arange(64.0).reshape(8, 8),
                            NamedSharding(mesh_a, P("data", None))),
        "b": jax.device_put(np.arange(16.0),
                            NamedSharding(mesh_a, P("data"))),
    }
    ckpt.save(tmp, 3, tree)

    mesh_b = make_local_mesh((2, 4), ("data", "model"))
    shardings = {
        "w": NamedSharding(mesh_b, P("model", "data")),
        "b": NamedSharding(mesh_b, P(("data", "model"))),
    }
    target = {"w": jnp.zeros((8, 8)), "b": jnp.zeros((16,))}
    restored, _ = ckpt.restore(tmp, 3, target, shardings=shardings)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(64.0).reshape(8, 8))
    np.testing.assert_array_equal(np.asarray(restored["b"]),
                                  np.arange(16.0))
    assert restored["w"].sharding.spec == P("model", "data")
    print("ELASTIC_CKPT_OK")


if __name__ == "__main__":
    main()
