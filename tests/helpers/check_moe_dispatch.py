"""Subprocess helper: the MoE-dispatch gather on 8 host devices.

Every ladder rung plus ``auto`` must reproduce the NumPy reference dispatch
bit-exactly (the gather moves values, it never computes on them), and the
§5 predictions — priced with this host's measured hardware parameters and
the token embedding width folded into ``elem`` — must be finite and cover
all rungs.  Run as:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 python check_moe_dispatch.py
Exits nonzero on failure.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import numpy as np

from repro.comm import STRATEGIES
from repro.comm.exchange import measure_hw
from repro.models.moe import (MoEDispatchGather, moe_dispatch_pattern,
                              moe_dispatch_ref, random_router)


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = jax.make_mesh((8,), ("data",))
    p = 8
    n_tok, k, d = 8 * 512, 2, 16
    e_total, cap = 32, 80
    rng = np.random.default_rng(0)
    # skewed routing (zipf-ish) so experts differ in load, like real routers
    top_e, _ = random_router(0, n_tok, e_total, k)
    x = rng.standard_normal((n_tok, d)).astype(np.float32)

    idx, valid = moe_dispatch_pattern(top_e, n_tok, e_total, cap, p)
    ref = moe_dispatch_ref(x, idx, valid, e_total, cap)

    hw = measure_hw(mesh, "data").replace(elem=4 * d)
    for strategy in STRATEGIES + ("auto",):
        g = MoEDispatchGather(top_e, n_tok, e_total, cap, mesh,
                              strategy=strategy, blocksize=64,
                              shards_per_node=4, hw=hw)
        buf = np.asarray(g(g.shard_tokens(x)))
        np.testing.assert_array_equal(buf, ref)
        c = g.counts
        print(f"OK {strategy}->{g.strategy} "
              f"condensed_vol={c.total_condensed_volume()} "
              f"blockwise_vol={c.total_blockwise_volume()}")

    # auto must carry the full §5 ranking
    g = MoEDispatchGather(top_e, n_tok, e_total, cap, mesh, strategy="auto",
                          blocksize=64, shards_per_node=4, hw=hw)
    assert set(g.predicted_times) == set(STRATEGIES)
    assert all(np.isfinite(t) and t > 0 for t in g.predicted_times.values())
    order = sorted(g.predicted_times, key=g.predicted_times.get)
    print(f"AUTO_OK resolved={g.strategy} predicted_order={'>'.join(order)}")
    print("MOE_DISPATCH_OK")


if __name__ == "__main__":
    main()
