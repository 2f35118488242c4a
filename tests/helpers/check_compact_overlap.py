"""Subprocess helper: the overlap rung's compact foreign delivery on 4 host
devices.  Run as:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python check_compact_overlap.py {overlap|destination}
Prints one JSON object, {case: {reading: value}}.  ``overlap``: for each
matrix, the forward ``iterate`` of the compact overlap engine against a
float64 power iteration and against the ``condensed`` rung, and the
engine's slot counter beside an independent count.  ``destination``: the
compact delivery itself (padding slots read 0, real slots read x) and a
plan-cache round trip.
"""
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from repro.comm import Destination, plan_cache, telemetry  # noqa: E402
from repro.core.matrix import EllpackMatrix, make_mesh_like_matrix  # noqa: E402
from repro.core.spmv import DistributedSpMV  # noqa: E402

N, STEPS = 1024, 3


def edge_heavy(n: int, p: int, r: int = 8, band: int = 6) -> EllpackMatrix:
    """The first and last ``band`` rows of each shard read all ``r``
    columns from the neighbouring shards; every other row reads its own."""
    rng = np.random.default_rng(3)
    ss = n // p
    i = np.arange(n)
    local = i % ss
    cols = (i[:, None] - ss // 2 + rng.integers(0, ss, (n, r))) % n
    own_lo = (i // ss * ss)[:, None]
    cols = np.where((cols >= own_lo) & (cols < own_lo + ss), cols,
                    own_lo + (cols % ss))          # middle rows: own only
    head, tail = local < band, local >= ss - band
    cols[head] = (own_lo[head] - 1 - np.arange(r)) % n     # previous shard
    cols[tail] = (own_lo[tail] + ss + np.arange(r)) % n    # next shard
    return EllpackMatrix(n=n, r_nz=r,
                         diag=rng.standard_normal(n).astype(np.float32),
                         vals=rng.standard_normal((n, r)).astype(np.float32),
                         cols=cols.astype(np.int32))


def block_diagonal(n: int, p: int, r: int = 8) -> EllpackMatrix:
    rng = np.random.default_rng(4)
    ss = n // p
    i = np.arange(n)
    cols = (i // ss * ss)[:, None] + rng.integers(0, ss, (n, r))
    return EllpackMatrix(n=n, r_nz=r,
                         diag=rng.standard_normal(n).astype(np.float32),
                         vals=rng.standard_normal((n, r)).astype(np.float32),
                         cols=cols.astype(np.int32))


def mesh_of(p: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:p]), ("data",))


CASES = {
    "mesh_like-4": (lambda: make_mesh_like_matrix(
        N, 8, locality_window=N // 8, long_range_frac=0.1, seed=5), 4),
    "edge_heavy-4": (lambda: edge_heavy(N, 4), 4),
    "block_diagonal-4": (lambda: block_diagonal(N, 4), 4),
    "mesh_like-1": (lambda: make_mesh_like_matrix(
        N, 8, locality_window=N // 8, long_range_frac=0.1, seed=5), 1),
}


def power_iteration_f64(m: EllpackMatrix, x: np.ndarray) -> np.ndarray:
    x = x.astype(np.float64)
    for _ in range(STEPS):
        y = m.diag * x + np.einsum("ij,ij->i", m.vals.astype(np.float64),
                                   x[m.cols])
        x = y / np.abs(y).max()
    return x


def foreign_per_device(m: EllpackMatrix, p: int) -> np.ndarray:
    """Off-shard column reads of each device, counted from the matrix."""
    ss = m.n // p
    owner = m.cols // ss
    return np.array([(owner[q * ss:(q + 1) * ss] != q).sum()
                     for q in range(p)])


def rel(a, b) -> float:
    return float(np.abs(np.asarray(a, np.float64) - b).max()
                 / np.abs(b).max())


def overlap_case(m: EllpackMatrix, p: int) -> dict:
    mesh = mesh_of(p)
    x = np.random.default_rng(0).standard_normal(m.n).astype(np.float32)
    with telemetry.isolated() as tel:
        eng = DistributedSpMV(m, mesh, strategy="overlap", blocksize=32,
                              use_plan_cache=False)
        counted = tel.since({})
    cond = DistributedSpMV(m, mesh, strategy="condensed", blocksize=32,
                           use_plan_cache=False)
    kern = DistributedSpMV(m, mesh, strategy="overlap", blocksize=32,
                           use_plan_cache=False, use_kernel=True,
                           materialize="dest")
    want = power_iteration_f64(m, x)
    y = np.asarray(eng.iterate(eng.shard_vector(x), STEPS))
    return {
        "err_ref": rel(y, want),
        "err_condensed": rel(y, np.asarray(
            cond.iterate(cond.shard_vector(x), STEPS))),
        "err_kernel": rel(np.asarray(
            kern.iterate(kern.shard_vector(x), STEPS)), want),
        "delivered": eng.dest_slots["delivered"],
        "dense": eng.dest_slots["dense"],
        "dest_len": eng.plan.dest_len,
        "rows_x_r_rem_max": m.n // p * eng.plan.r_rem_max,
        "foreign_max": int(foreign_per_device(m, p).max()),
        "counter": [counted["dest_compact"], counted["dest_slots"],
                    counted["dest_slots_dense"]],
    }


def delivered(eng, x: np.ndarray) -> np.ndarray:
    """(p, L): each device's compact foreign delivery of ``x``."""
    g = eng.gather

    def local(x_local, *args):
        return g.local(x_local, *args)["foreign"][None]

    f = jax.jit(jax.shard_map(local, mesh=eng.mesh,
                              in_specs=(P("data"),) + g.in_specs,
                              out_specs=P("data"), check_vma=False))
    return np.asarray(f(g.shard_vector(x), *g.plan_args))


def destination_case(m: EllpackMatrix, p: int) -> dict:
    mesh = mesh_of(p)
    x = np.random.default_rng(1).standard_normal(m.n).astype(np.float32)
    eng = DistributedSpMV(m, mesh, strategy="overlap", blocksize=32,
                          use_plan_cache=False)
    ids = eng.gather.destination.indices
    out = delivered(eng, x)
    pad = ids == Destination.ZERO
    ss = m.n // p
    owner = np.where(pad, -1, ids // ss)
    return {
        "slots": int(ids.shape[1]),
        "padding": int(pad.sum()),
        "padding_zero": bool((out[pad] == 0).all()),
        "real_read_x": bool((out[~pad] == x[ids[~pad]]).all()),
        "none_owned": bool(all((owner[q] != q).all() for q in range(p))),
        "rows_sorted": bool(all(
            (np.diff(eng._args[-2].reshape(p, -1)[q]) >= 0).all()
            for q in range(p))),
    }


def cache_round_trip() -> dict:
    m, p = CASES["mesh_like-4"][0](), 4
    mesh = mesh_of(p)
    x = np.random.default_rng(2).standard_normal(m.n).astype(np.float32)
    with tempfile.TemporaryDirectory() as d:
        os.environ["REPRO_PLAN_CACHE_DIR"] = d
        plan_cache.clear_memory_cache()
        built = DistributedSpMV(m, mesh, strategy="overlap", blocksize=32)
        y_built = np.asarray(built(built.shard_vector(x)))
        plan_cache.clear_memory_cache()
        with telemetry.isolated() as tel:
            loaded = DistributedSpMV(m, mesh, strategy="overlap",
                                     blocksize=32)
            sources = tel.since({})
        y_loaded = np.asarray(loaded(loaded.shard_vector(x)))
        files = len(os.listdir(d))
    return {
        "disk_hits": sources["disk-hit"],
        "host_builds": sources["host-build"],
        "entries": files,
        "same_destination": bool(np.array_equal(
            built.gather.destination.indices,
            loaded.gather.destination.indices)),
        "same_product": bool(np.array_equal(y_built, y_loaded)),
    }


def run(group: str) -> dict:
    """This script's JSON for ``group``, from a 4-device subprocess (the
    test process keeps its own devices)."""
    repo = Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(repo), str(repo / "src")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               REPRO_PLAN_CACHE="1")
    proc = subprocess.run([sys.executable, __file__, group], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main(group: str) -> None:
    assert len(jax.devices()) == 4, jax.devices()
    if group == "overlap":
        out = {name: overlap_case(make(), p)
               for name, (make, p) in CASES.items()}
    else:
        out = {name: destination_case(make(), p)
               for name, (make, p) in CASES.items()}
        out["cache_round_trip"] = cache_round_trip()
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1])
