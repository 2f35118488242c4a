"""Persistent CommPlan cache: content-addressing, hit/miss accounting, and
engine-level reuse (second construction performs no O(nnz) rebuild)."""
import dataclasses

import numpy as np
import pytest

from repro.core.matrix import make_mesh_like_matrix
from repro.comm.plan import Topology, build_comm_plan
from repro.comm import plan_cache


@pytest.fixture(autouse=True)
def isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path / "plans"))
    monkeypatch.delenv("REPRO_PLAN_CACHE", raising=False)
    plan_cache.clear_memory_cache()
    plan_cache.stats.reset()
    yield
    plan_cache.clear_memory_cache()


def _case(seed=0, n=256, p=4, bs=16):
    m = make_mesh_like_matrix(n, 4, locality_window=n // 4,
                              long_range_frac=0.1, seed=seed)
    return m, n, p, bs, Topology(p, 2)


def _assert_plans_equal(a, b):
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if f.name == "counts":
            for cf in dataclasses.fields(va):
                np.testing.assert_array_equal(getattr(va, cf.name),
                                              getattr(vb, cf.name))
        elif isinstance(va, np.ndarray):
            np.testing.assert_array_equal(va, vb)
        else:
            assert va == vb, f.name


def test_memory_and_disk_hits():
    m, n, p, bs, topo = _case()
    p1 = plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    assert plan_cache.stats.misses == 1
    p2 = plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    assert plan_cache.stats.memory_hits == 1 and plan_cache.stats.misses == 1
    _assert_plans_equal(p1, p2)

    plan_cache.clear_memory_cache()
    p3 = plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    assert plan_cache.stats.disk_hits == 1 and plan_cache.stats.misses == 1
    _assert_plans_equal(p1, p3)
    # round-tripped plan is bit-identical to a fresh host-side build
    fresh = build_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    _assert_plans_equal(p3, fresh)


def test_key_sensitivity():
    m, n, p, bs, topo = _case()
    base = plan_cache.plan_key(m.cols, n, p, bs, topo)
    assert base == plan_cache.plan_key(m.cols.copy(), n, p, bs, topo)
    assert base != plan_cache.plan_key(m.cols, n, p, bs * 2, topo)
    assert base != plan_cache.plan_key(m.cols, n, p, bs, Topology(p, p))
    cols2 = m.cols.copy()
    cols2[0, 0] = (cols2[0, 0] + 1) % n
    assert base != plan_cache.plan_key(cols2, n, p, bs, topo)


def test_different_matrices_do_not_collide():
    m1, n, p, bs, topo = _case(seed=1)
    m2 = make_mesh_like_matrix(n, 4, locality_window=n // 4,
                               long_range_frac=0.1, seed=2)
    p1 = plan_cache.get_comm_plan(m1.cols, n, p, blocksize=bs, topology=topo)
    p2 = plan_cache.get_comm_plan(m2.cols, n, p, blocksize=bs, topology=topo)
    assert plan_cache.stats.misses == 2
    assert not np.array_equal(p1.send_counts, p2.send_counts) or \
        not np.array_equal(p1.recv_global_idx, p2.recv_global_idx)


def test_memory_lru_eviction(monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE_MEM_ENTRIES", "2")
    n, p, bs = 256, 4, 16
    topo = Topology(p, 2)
    mats = [make_mesh_like_matrix(n, 4, locality_window=n // 4,
                                  long_range_frac=0.1, seed=s)
            for s in range(3)]
    for m in mats:
        plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    assert len(plan_cache._memory) == 2  # oldest evicted
    # evicted entry falls back to the disk tier, not a rebuild
    plan_cache.get_comm_plan(mats[0].cols, n, p, blocksize=bs, topology=topo)
    assert plan_cache.stats.misses == 3 and plan_cache.stats.disk_hits == 1


def test_disable_via_env(monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE", "0")
    m, n, p, bs, topo = _case()
    plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    assert plan_cache.stats.misses == 2 and plan_cache.stats.hits == 0


def test_corrupt_disk_entry_degrades_to_rebuild(tmp_path):
    m, n, p, bs, topo = _case()
    plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    key = plan_cache.plan_key(m.cols, n, p, bs, topo)
    path = plan_cache._disk_path(key)
    with open(path, "wb") as f:
        f.write(b"not an npz")
    plan_cache.clear_memory_cache()
    plan = plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs,
                                    topology=topo)
    assert plan_cache.stats.misses == 2  # corrupt entry -> rebuild
    fresh = build_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    _assert_plans_equal(plan, fresh)


def test_destination_plans_round_trip_and_reuse_base():
    """v3 entries carry the targeted-unpack arrays; attaching a Destination
    to an already-planned pattern reuses the cached base plan (no second
    O(nnz) build)."""
    from repro.comm.pattern import Destination

    m, n, p, bs, topo = _case()
    slots = m.cols[::4, :2].reshape(p, -1).astype(np.int64).copy()
    slots[:, -1] = Destination.ZERO
    dest = Destination.from_slots(s=slots)

    base = plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    assert plan_cache.stats.misses == 1
    p1 = plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo,
                                  destination=dest)
    # the destination entry was derived from the cached base, not rebuilt
    assert plan_cache.stats.misses == 1
    assert p1.dest_len == slots.shape[1] and base.dest_len == 0
    assert p1.dest_own_idx is not None

    # disk round trip is bit-identical, including the dest arrays
    plan_cache.clear_memory_cache()
    p2 = plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo,
                                  destination=dest)
    assert plan_cache.stats.disk_hits >= 1
    _assert_plans_equal(p1, p2)
    # distinct destinations get distinct keys
    k0 = plan_cache.plan_key(m.cols, n, p, bs, topo)
    k1 = plan_cache.plan_key(m.cols, n, p, bs, topo, dest)
    slots2 = slots.copy()
    slots2[0, 0] = (slots2[0, 0] + 1) % n
    k2 = plan_cache.plan_key(m.cols, n, p, bs, topo,
                             Destination.from_slots(s=slots2))
    assert len({k0, k1, k2}) == 3


@pytest.mark.parametrize("legacy", [2, 3, 4])
def test_legacy_cache_entry_rejected_with_clear_message(legacy):
    """A genuine pre-v5 → v5 upgrade: the old build keyed its entries with
    its own version prefix, so a v5 lookup must probe those filenames too,
    surface the explicit migration warning, delete the stale-format orphan
    (it would otherwise count against the disk cap forever), count the
    eviction, and rebuild."""
    import os

    m, n, p, bs, topo = _case()
    plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    cur_path = plan_cache._disk_path(plan_cache.plan_key(m.cols, n, p, bs,
                                                         topo))
    # simulate the pre-upgrade cache: the entry lives under the legacy key
    old_path = plan_cache._disk_path(
        plan_cache._key_for_version(legacy, m.cols, n, p, bs, topo))
    os.rename(cur_path, old_path)

    plan_cache.clear_memory_cache()
    assert plan_cache.stats.evictions == 0
    with pytest.warns(UserWarning, match=f"v{legacy}.*v5"):
        plan = plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs,
                                        topology=topo)
    assert not os.path.exists(old_path)  # orphan evicted, not left behind
    assert plan_cache.stats.misses == 2  # stale entry -> rebuild
    assert plan_cache.stats.evictions == 1  # ...and the unlink was counted
    fresh = build_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    _assert_plans_equal(plan, fresh)


def test_stale_format_meta_rejected_by_deserialize():
    """Belt and braces: an entry whose meta says pre-v5 (however it got
    under the current key) is refused with the migration message and
    rebuilt — never reinterpreted as a current-format plan."""
    m, n, p, bs, topo = _case()
    plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    path = plan_cache._disk_path(plan_cache.plan_key(m.cols, n, p, bs, topo))
    with np.load(path) as data:
        entries = {k: data[k] for k in data.files}
    meta = entries["meta"].copy()
    meta[0] = 4  # a v4-era entry: same field set, older format stamp
    entries["meta"] = meta
    np.savez_compressed(path, **entries)

    plan_cache.clear_memory_cache()
    with pytest.warns(UserWarning, match="format v4.*v5"):
        plan = plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs,
                                        topology=topo)
    assert plan_cache.stats.misses == 2  # stale entry -> rebuild
    fresh = build_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    _assert_plans_equal(plan, fresh)


def test_cache_stats_snapshot_and_isolated():
    """CacheStats is capture-safe: snapshot() detaches, isolated() swaps a
    fresh module-global in and restores the old one (counts untouched)."""
    m, n, p, bs, topo = _case()
    plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    before = plan_cache.stats.snapshot()
    assert before["misses"] == 1 and before["evictions"] == 0
    with plan_cache.isolated() as inner:
        assert plan_cache.stats is inner
        assert inner.misses == 0  # fresh counters inside the context
        plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
        assert inner.memory_hits == 1 and inner.misses == 0
    assert plan_cache.stats.snapshot() == before  # outer stats untouched
    # snapshot is a detached copy, not a live view
    plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    assert before["memory_hits"] == 0


def _envelope_case(seed=0, n=256, p=4, m_rows=128, r=2):
    rng = np.random.default_rng(seed)
    cols = rng.integers(0, n, size=(m_rows, r)).astype(np.int32)
    return cols, n, p


def test_envelope_plan_bucket_reuse_hits_and_misses():
    """Two routings whose quantized per-(reader, owner) stats round up to
    the same bucket boundaries share ONE envelope entry; a routing whose
    load crosses a bucket boundary founds a new one."""
    from repro.comm import telemetry

    cols, n, p = _envelope_case(seed=0)
    with telemetry.isolated() as tel:
        p1 = plan_cache.get_envelope_plan(cols, n, p, blocksize=16,
                                          s_max=n // p, bucket=n)
        assert plan_cache.stats.misses == 1
        assert tel.sources["host-build"] == 1
        # a different routing, same envelope: bucket=n quantizes every
        # per-pair count to the same ceiling -> reuse, no rebuild
        cols2, _, _ = _envelope_case(seed=1)
        p2 = plan_cache.get_envelope_plan(cols2, n, p, blocksize=16,
                                          s_max=n // p, bucket=n)
        assert plan_cache.stats.misses == 1
        assert plan_cache.stats.memory_hits == 1
        assert tel.sources["bucket-reuse"] == 1
        assert p2 is p1  # the founding entry, verbatim
        # the envelope geometry serves any routing it covers
        assert p2.s_max == n // p

        # fine buckets separate routings with different load envelopes
        plan_cache.get_envelope_plan(cols, n, p, blocksize=16,
                                     s_max=n // p, bucket=1)
        assert plan_cache.stats.misses == 2
        assert tel.sources["host-build"] == 2

        # disk tier: evicting memory still avoids the host rebuild
        plan_cache.clear_memory_cache()
        p3 = plan_cache.get_envelope_plan(cols2, n, p, blocksize=16,
                                          s_max=n // p, bucket=n)
        assert plan_cache.stats.misses == 2
        assert plan_cache.stats.disk_hits == 1
        _assert_plans_equal(p3, p1)


def test_envelope_plan_key_sensitivity():
    """The envelope key quantizes the routing stats — identical routings
    and bucket-equivalent routings collide (that is the point); different
    geometry, s_max, or bucket granularity never do."""
    cols, n, p = _envelope_case(seed=0)
    topo = Topology(p, 2)
    k0 = plan_cache.envelope_plan_key(cols, n, p, 16, topo, n // p, bucket=8)
    assert k0 == plan_cache.envelope_plan_key(cols.copy(), n, p, 16, topo,
                                              n // p, bucket=8)
    assert k0 != plan_cache.envelope_plan_key(cols, n, p, 32, topo, n // p,
                                              bucket=8)
    assert k0 != plan_cache.envelope_plan_key(cols, n, p, 16, topo,
                                              n // p // 2, bucket=8)
    assert k0 != plan_cache.envelope_plan_key(cols, n, p, 16, topo, n // p,
                                              bucket=4)
    assert k0 != plan_cache.envelope_plan_key(cols, n, p, 16,
                                              Topology(p, p), n // p,
                                              bucket=8)
    # a routing with a genuinely heavier per-pair load breaks the bucket
    heavy = cols.copy()
    heavy[: len(heavy) // 2] = 0  # pile half the reads onto owner 0
    assert k0 != plan_cache.envelope_plan_key(heavy, n, p, 16, topo, n // p,
                                              bucket=8)


def _assert_scatter_plans_equal(a, b):
    _assert_plans_equal(a.base, b.base)
    for name in ("tgt_global", "cond_msg_idx", "blk_msg_idx", "own_tgt_idx",
                 "win_mask", "touched"):
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name))
    for cf in dataclasses.fields(a.counts):
        np.testing.assert_array_equal(getattr(a.counts, cf.name),
                                      getattr(b.counts, cf.name))


def test_scatter_plan_round_trip_and_reuse_base():
    """v4 scatter entries are O(m*r) deltas referencing the base plan: the
    gather and the scatter of one pattern share a single O(nnz) build, and
    the disk round trip reconstructs the transpose bit-identically."""
    from repro.comm.plan import derive_scatter_plan

    m, n, p, bs, topo = _case()
    base = plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    s1 = plan_cache.get_scatter_plan(m.cols, n, p, blocksize=bs,
                                     topology=topo)
    assert plan_cache.stats.misses == 1 and plan_cache.stats.derives == 1
    _assert_scatter_plans_equal(s1, derive_scatter_plan(base))
    # transpose round-trips onto the cached base
    assert s1.transpose() is s1.base
    _assert_plans_equal(s1.transpose(), base)

    plan_cache.clear_memory_cache()
    s2 = plan_cache.get_scatter_plan(m.cols, n, p, blocksize=bs,
                                     topology=topo)
    assert plan_cache.stats.disk_hits >= 1
    assert plan_cache.stats.derives == 1  # no re-derivation
    _assert_scatter_plans_equal(s1, s2)
    # scatter and gather keys never collide
    assert plan_cache.plan_key(m.cols, n, p, bs, topo) != \
        plan_cache.plan_key(m.cols, n, p, bs, topo, scatter=True)


def test_concurrent_writers_no_torn_reads():
    """The write-to-temp + atomic-rename protocol must keep every reader
    seeing either a complete entry or a miss — never torn bytes — while
    several threads build/load the same plans concurrently."""
    import threading

    m, n, p, bs, topo = _case()
    fresh = build_comm_plan(m.cols, n, p, blocksize=bs, topology=topo)
    fresh_s = fresh.transpose()
    errors = []

    def worker():
        try:
            for _ in range(6):
                plan = plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs,
                                                topology=topo)
                _assert_plans_equal(plan, fresh)
                splan = plan_cache.get_scatter_plan(m.cols, n, p,
                                                    blocksize=bs,
                                                    topology=topo)
                _assert_scatter_plans_equal(splan, fresh_s)
                plan_cache.clear_memory_cache()  # force the disk tier
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    # the cache directory holds only complete entries (no leftover temps
    # visible under the entry names) and both load cleanly
    plan_cache.clear_memory_cache()
    _assert_plans_equal(
        plan_cache.get_comm_plan(m.cols, n, p, blocksize=bs, topology=topo),
        fresh)
    _assert_scatter_plans_equal(
        plan_cache.get_scatter_plan(m.cols, n, p, blocksize=bs,
                                    topology=topo),
        fresh_s)


def test_spmv_auto_dest_attaches_exactly_one_destination():
    """strategy="auto" with targeted unpack must not persist a throwaway
    destination entry: the strategy resolves against the base plan first,
    then exactly one Destination (the one the step actually runs) is
    attached and cached — one base entry + one dest entry on disk."""
    import glob
    import os

    import jax
    from repro.comm import perfmodel as pm
    from repro.core.spmv import DistributedSpMV

    ndev = len(jax.devices())
    mesh = jax.make_mesh((ndev,), ("data",))
    n = 128 * ndev
    m = make_mesh_like_matrix(n, 4, locality_window=n // 4,
                              long_range_frac=0.1, seed=9)
    eng = DistributedSpMV(m, mesh, strategy="auto", blocksize=32, hw=pm.ABEL)
    assert eng.materialize == "dest" and eng.requested_strategy == "auto"
    files = glob.glob(os.path.join(plan_cache.cache_dir(), "*.npz"))
    assert len(files) == 2, files      # base plan + the one used Destination
    assert plan_cache.stats.misses == 1  # one O(nnz) build total


def test_engine_second_construction_hits_cache():
    import jax
    from repro.core.spmv import DistributedSpMV

    ndev = len(jax.devices())
    mesh = jax.make_mesh((ndev,), ("data",))
    n = 128 * ndev
    m = make_mesh_like_matrix(n, 4, locality_window=n // 4,
                              long_range_frac=0.1, seed=3)
    e1 = DistributedSpMV(m, mesh, strategy="condensed", blocksize=32)
    assert plan_cache.stats.misses == 1
    e2 = DistributedSpMV(m, mesh, strategy="condensed", blocksize=32)
    assert plan_cache.stats.misses == 1 and plan_cache.stats.hits >= 1
    _assert_plans_equal(e1.plan, e2.plan)
    # cached-plan engine still computes the right answer
    from repro.core.matrix import spmv_ref_np
    x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
    np.testing.assert_allclose(np.asarray(e2(e2.shard_vector(x))),
                               spmv_ref_np(m, x), rtol=2e-4, atol=2e-4)
