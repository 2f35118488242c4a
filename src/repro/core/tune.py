"""Hardware calibration for the model-driven autotuner (§5.4 / §6.2).

``measure_hardware`` micro-benchmarks the paper's hardware characteristic
parameters ONCE PER MESH — a STREAM-like copy for ``w_private``, a large
ring ``ppermute`` for ``w_remote``, a tiny one for ``tau``, and a
random-gather probe for the effective non-contiguous access granularity
``cacheline`` (the per-element pack/unpack cost).  Results are memoized per
(devices, axis) for the life of the process.

The *selection* half of the autotuner (ranking strategies and sweeping
BLOCKSIZE through the §5 formulas) moved to ``repro.comm.select`` with the
rest of the communication machinery; ``rank_strategies`` /
``choose_strategy`` / ``choose_blocksize`` / ``workload_from_plan`` are
re-exported here for compatibility (``rank_strategies`` /
``choose_strategy`` now take ``direction="get"|"put"`` to price the push
rungs of ``IrregularScatter``).  The per-(mesh, axis) calibration memo used
by the exchange front doors — ``measure_hw`` / ``clear_hw_memo`` — lives in
``repro.comm.exchange`` and is re-exported here too.
"""
from __future__ import annotations

import time

import numpy as np

from repro.comm.exchange import (  # noqa: F401  (compat re-exports)
    clear_hw_memo, measure_hw,
)
from repro.comm.select import (  # noqa: F401  (compat re-exports)
    choose_blocksize, choose_strategy, rank_strategies, workload_from_plan,
)
from repro.core.perfmodel import HardwareParams

__all__ = [
    "measure_hardware", "rank_strategies", "choose_strategy",
    "choose_blocksize", "clear_hardware_cache", "workload_from_plan",
    "measure_hw", "clear_hw_memo",
]

_hw_cache: dict[tuple, HardwareParams] = {}


def clear_hardware_cache() -> None:
    _hw_cache.clear()


def _timeit(fn, *args, iters: int = 10, warmup: int = 3) -> float:
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def measure_hardware(
    mesh=None,
    axis_name: str | None = None,
    *,
    elem_bytes: int = 4,
    force: bool = False,
) -> HardwareParams:
    """Micro-benchmark the four §5.4 parameters on this process's devices.

    ``mesh``/``axis_name`` select the communication axis to probe; with no
    mesh every visible device joins a ring.  Memoized per (device set, axis,
    elem size) — pass ``force=True`` to re-measure.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_local_mesh

    if mesh is not None:
        axis = axis_name or mesh.axis_names[0]
        devices = tuple(d.id for d in mesh.devices.flat)
        ndev = mesh.shape[axis]
    else:
        axis = axis_name or "data"
        devices = tuple(d.id for d in jax.devices())
        ndev = len(devices)
    key = (devices, axis, ndev, elem_bytes)
    if not force and key in _hw_cache:
        return _hw_cache[key]

    # -- w_private: STREAM-like copy (read + write) --
    n = 1 << 22
    x = jnp.arange(n, dtype=jnp.float32)
    copy = jax.jit(lambda a: a * 1.0000001)
    t_copy = _timeit(copy, x, iters=10)
    w_private = 2.0 * n * 4 / t_copy

    # -- cacheline: random-gather probe; the model charges every
    # non-contiguous local access one ``cacheline`` of traffic, so the
    # effective value is gather-time * w_private / accesses --
    g = 1 << 20
    idx = jnp.asarray(
        np.random.default_rng(0).integers(0, n, size=g, dtype=np.int32))
    gather = jax.jit(lambda a, i: a[i])
    t_gather = _timeit(gather, x, idx, iters=10)
    cacheline = int(np.clip(t_gather * w_private / g, 16, 4096))

    # -- w_remote and tau: ring ppermute, big minus tiny --
    if ndev > 1:
        ring_mesh = mesh
        if ring_mesh is None:
            ring_mesh = make_local_mesh((ndev,), (axis,))
        perm = [(i, (i + 1) % ndev) for i in range(ndev)]

        def ring(a):
            return jax.shard_map(
                lambda v: jax.lax.ppermute(v, axis, perm), mesh=ring_mesh,
                in_specs=P(axis), out_specs=P(axis))(a)

        sh = NamedSharding(ring_mesh, P(axis))
        big = jax.device_put(jnp.zeros((ndev * (1 << 20),), jnp.float32), sh)
        t_big = _timeit(jax.jit(ring), big, iters=5)
        tiny = jax.device_put(jnp.zeros((ndev * 8,), jnp.float32), sh)
        tau = _timeit(jax.jit(ring), tiny, iters=20)
        w_remote = (1 << 20) * 4 / max(t_big - tau, 1e-9)
    else:
        w_remote = w_private
        tau = _timeit(copy, jnp.zeros((8,), jnp.float32), iters=30)

    hw = HardwareParams(
        w_private=w_private, w_remote=w_remote, tau=tau,
        cacheline=cacheline, elem=elem_bytes, idx=4)
    _hw_cache[key] = hw
    return hw
