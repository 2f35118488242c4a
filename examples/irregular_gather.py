"""The workload-agnostic irregular-communication API, five consumers deep.

The paper's machinery — plan once (§4.3.1), pick a ladder rung (§4), price
it with the §5 models — is exposed behind ``repro.comm``, in both
directions:

  * ``SharedVector``    — a sharded vector with contiguous ownership,
  * ``AccessPattern``   — the global index set each accessor touches,
  * ``IrregularGather`` — pull: plans, autotunes, and gathers,
  * ``IrregularScatter``— push: the same plan transposed, duplicate
    targets combining under ``reduce="add"|"set"|"max"``.

This example drives the raw API, then the consumers built on it:
``DistributedSpMV`` (the paper's workload, plus ``transpose=True`` for
y = (D+A)ᵀx), ``Heat2D`` (§8 stencil halos), and the MoE pair
(``MoEDispatchGather`` token→expert, ``MoECombineScatter`` expert→token).

Run: python examples/irregular_gather.py   (re-execs itself with 8 devices)
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

import jax
import numpy as np

from repro.launch.mesh import make_local_mesh
from repro.comm import AccessPattern, IrregularGather, SharedVector
from repro.comm import perfmodel as pm


def raw_api(mesh):
    print("== raw API: any index set over any sharded vector ==")
    n = 1 << 14
    sv = SharedVector(mesh, n=n, axis_name="data")
    rng = np.random.default_rng(0)
    # every accessor reads 8 mostly-local indices + the occasional far one
    idx = (np.arange(n)[:, None]
           + rng.integers(-64, 65, size=(n, 8))).clip(0, n - 1)
    far = rng.random((n, 8)) < 0.01
    idx[far] = rng.integers(0, n, size=int(far.sum()))
    pattern = AccessPattern.from_indices(idx.astype(np.int32), n=n)

    g = IrregularGather(pattern, sv, strategy="auto", blocksize="auto")
    print(f"  resolved strategy={g.strategy} blocksize={g.plan.blocksize}")
    print("  predicted:", {s: f"{t*1e6:.0f}us"
                           for s, t in sorted(g.predicted_times.items(),
                                              key=lambda kv: kv[1])})
    c = g.counts
    print(f"  condensed volume={c.total_condensed_volume()} elems, "
          f"blockwise volume={c.total_blockwise_volume()} elems, "
          f"replicate volume={8 * n} elems")

    x = rng.standard_normal(n).astype(np.float32)
    x_copies = np.asarray(g(sv.put(x)))          # (P, >=n): private copies
    q = 3
    rows = pattern.m // g.p
    needed = np.unique(pattern.indices[q * rows:(q + 1) * rows])
    assert (x_copies[q][needed] == x[needed]).all()
    print(f"  device {q}: x_copy delivers all {len(needed)} needed indices\n")


def destination_api(mesh):
    print("== Destination: land values straight in named consumer slots ==")
    from jax.sharding import PartitionSpec as P
    from repro.comm import Destination

    n, p = 1 << 14, 8
    sv = SharedVector(mesh, n=n, axis_name="data")
    rng = np.random.default_rng(7)
    idx = rng.integers(0, n, size=(n, 4)).astype(np.int32)
    pattern = AccessPattern.from_indices(idx, n=n)
    # each device wants a sparse, named slice of its reads delivered; -1
    # slots are guaranteed to read exactly 0.0
    slots = idx[::32, :2].reshape(p, -1).astype(np.int64).copy()
    slots[:, -1] = Destination.ZERO
    dest = Destination.from_slots(window=slots)
    g = IrregularGather(pattern, sv, strategy="condensed", blocksize="auto",
                        destination=dest)

    def step_local(x_local, *plan_args):
        # O(slots + recv) delivery: no length-n x_copy is ever assembled
        return g.local(x_local, *plan_args)["window"][None]

    mapped = jax.shard_map(
        step_local, mesh=mesh, in_specs=(P("data"),) + g.in_specs,
        out_specs=P("data"), check_vma=False)
    x = rng.standard_normal(n).astype(np.float32)
    out = np.asarray(jax.jit(lambda v: mapped(v, *g.plan_args))(sv.put(x)))
    want = np.where(slots >= 0, x[np.clip(slots, 0, None)], 0.0)
    assert (out == want).all()
    print(f"  {dest.num_slots} slots/device delivered targeted "
          f"(vs assembling {n}-long x_copy); full mode still available "
          "via materialize=\"full\"\n")


def scatter_api(mesh):
    print("== push direction: IrregularScatter over the transposed plan ==")
    from repro.comm import IrregularScatter

    n = 1 << 14
    sv = SharedVector(mesh, n=n, axis_name="data")
    rng = np.random.default_rng(4)
    idx = (np.arange(n)[:, None]
           + rng.integers(-64, 65, size=(n, 8))).clip(0, n - 1)
    pattern = AccessPattern.from_indices(idx.astype(np.int32), n=n)
    s = IrregularScatter(pattern, sv, strategy="auto", reduce="add")
    print(f"  resolved strategy={s.strategy} (put-model ranking); "
          "scatter plan = gather plan transposed "
          f"(round-trips: {s.splan.transpose() is s.plan})")
    vals = rng.integers(-4, 5, size=idx.shape).astype(np.float32)
    y = np.asarray(s(s.shard_values(vals)))
    ref = np.zeros(n, np.float32)
    np.add.at(ref, idx.ravel(), vals.ravel())
    print(f"  scatter-add over {idx.size} contributions bit-exact: "
          f"{np.array_equal(y, ref)}\n")


def spmv_consumer(mesh):
    print("== consumer 1: DistributedSpMV (the paper's workload) ==")
    from repro.core.matrix import (make_mesh_like_matrix, spmv_ref_np,
                                   spmv_t_ref_np)
    from repro.core.spmv import DistributedSpMV

    n = 1 << 14
    m = make_mesh_like_matrix(n, 16, locality_window=n // 64,
                              long_range_frac=0.02, seed=1)
    eng = DistributedSpMV(m, mesh, strategy="auto", blocksize="auto",
                          shards_per_node=4)
    x = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    y = np.asarray(eng(eng.shard_vector(x)))
    err = np.abs(y - spmv_ref_np(m, x)).max()
    print(f"  auto -> {eng.strategy}, blocksize={eng.blocksize}, "
          f"max_err={err:.2e}")
    # the transposed product pushes partial products to the column owners
    engt = DistributedSpMV(m, mesh, strategy="auto", shards_per_node=4,
                           transpose=True)
    yt = np.asarray(engt(engt.shard_vector(x)))
    errt = np.abs(yt - spmv_t_ref_np(m, x)).max()
    print(f"  transpose=True (y = Mᵀx) auto -> {engt.strategy}, "
          f"max_err={errt:.2e}\n")


def heat2d_consumer():
    print("== consumer 2: Heat2D (§8 halo exchange as an AccessPattern) ==")
    from repro.core.heat2d import Heat2D

    mesh = make_local_mesh((2, 4), ("data", "model"))
    for kw in (dict(strategy="condensed"), dict(strategy="auto"),
               dict(overlap=True)):
        h = Heat2D(mesh, 64, 128, coef=0.1, **kw)
        phi = h.init_field(0)
        got = np.asarray(h.run(phi, 10))
        want = h.reference(np.asarray(phi), 10)
        c = h.counts
        print(f"  {kw} -> strategy={h.strategy} "
              f"halo_volume={c.total_condensed_volume()} elems "
              f"max_err={np.abs(got - want).max():.2e}")
    print()


def moe_consumer(mesh):
    print("== consumer 3: MoE dispatch + combine (one plan, two directions) "
          "==")
    from repro.models.moe import (MoECombineScatter, MoEDispatchGather,
                                  moe_combine_ref, moe_combine_weights,
                                  moe_dispatch_pattern, moe_dispatch_ref)

    n_tok, k, d, e_total = 1 << 13, 2, 16, 32
    cap = int(1.25 * n_tok * k / e_total)
    rng = np.random.default_rng(2)
    top_e = rng.integers(0, e_total, size=(n_tok, k))
    x = rng.standard_normal((n_tok, d)).astype(np.float32)
    g = MoEDispatchGather(top_e, n_tok, e_total, cap, mesh, strategy="auto",
                          hw=pm.ABEL.replace(elem=4 * d))
    buf = np.asarray(g(g.shard_tokens(x)))
    idx, valid = moe_dispatch_pattern(top_e, n_tok, e_total, cap, 8)
    ref = moe_dispatch_ref(x, idx, valid, e_total, cap)
    print(f"  dispatch auto -> {g.strategy}; expert buffers {buf.shape}; "
          f"bit-exact={np.array_equal(buf, ref)}")
    c = g.counts
    print(f"  condensed moves {c.total_condensed_volume()} of "
          f"{n_tok} token vectors; replicate would move {8 * n_tok}")

    # the return path: weighted expert->token combine over the SAME plan
    top_w = rng.random((n_tok, k)).astype(np.float32)
    comb = MoECombineScatter(top_e, top_w, n_tok, e_total, cap, mesh,
                             strategy="auto",
                             hw=pm.ABEL.replace(elem=4 * d))
    y = np.asarray(comb(comb.shard_expert_buf(buf)))
    w_slot = moe_combine_weights(top_e, top_w, n_tok, e_total, cap)
    want = moe_combine_ref(buf, idx, valid, w_slot, n_tok)
    print(f"  combine auto -> {comb.strategy}; tokens back {y.shape}; "
          f"max_err={np.abs(y - want).max():.2e}")


def main():
    mesh = make_local_mesh((8,), ("data",))
    raw_api(mesh)
    destination_api(mesh)
    scatter_api(mesh)
    spmv_consumer(mesh)
    heat2d_consumer()
    moe_consumer(mesh)


if __name__ == "__main__":
    main()
