"""Plan-source telemetry, and the dynamic-MoE acceptance criterion:
N distinct routings through one ``DynamicMoELayer``, zero host plan
builds after warmup — every hot-path acquisition is a device derivation.
"""
import numpy as np
import pytest

from repro.comm import plan_cache, telemetry


@pytest.fixture(autouse=True)
def isolated_everything(tmp_path, monkeypatch):
    """Fresh telemetry AND a private plan cache per test — module-global
    counters never leak across tests (or from other test files)."""
    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path / "plans"))
    monkeypatch.delenv("REPRO_PLAN_CACHE", raising=False)
    plan_cache.clear_memory_cache()
    plan_cache.stats.reset()
    with telemetry.isolated() as tel:
        yield tel
    plan_cache.clear_memory_cache()


def test_record_counts_and_latency(isolated_everything):
    tel = isolated_everything
    telemetry.record("host-build", seconds=0.5)
    telemetry.record("host-build", seconds=0.25)
    telemetry.record("memory-hit")
    snap = tel.snapshot()
    assert snap["sources"]["host-build"] == 2
    assert snap["sources"]["memory-hit"] == 1
    assert snap["build_seconds"]["host-build"] == pytest.approx(0.75)
    assert snap["total"] == 3
    assert tel.total == 3


def test_unknown_source_rejected(isolated_everything):
    with pytest.raises(ValueError, match="unknown plan source"):
        telemetry.record("clairvoyance")
    assert isolated_everything.total == 0


def test_snapshot_is_detached_and_since_is_flat(isolated_everything):
    tel = isolated_everything
    telemetry.record("disk-hit")
    snap = tel.snapshot()
    telemetry.record("device-derive")
    telemetry.record("device-derive")
    telemetry.record("bucket-reuse")
    telemetry.record_tick("decode_steps", 3)
    assert snap["sources"]["device-derive"] == 0      # detached
    delta = tel.since(snap)
    assert delta == {"memory-hit": 0, "disk-hit": 0, "bucket-reuse": 1,
                     "device-derive": 2, "host-build": 0,
                     "decode_steps": 3, "prefill_chunks": 0,
                     "compiles": 0, "cache_loads": 0, "compile_s": 0.0,
                     "dest_compact": 0, "dest_slots": 0,
                     "dest_slots_dense": 0, "moe_dropped": 0,
                     "mla_kernel_steps": 0, "mla_rows_read": 0,
                     "spans": {}}


def test_decode_host_free_interval(isolated_everything):
    """The serving steady-state predicate: decode ticks happened and no
    host build landed inside the interval."""
    tel = isolated_everything
    telemetry.record("host-build", seconds=0.1)       # warmup build
    snap = tel.snapshot()
    assert not tel.decode_host_free(snap)             # no ticks yet
    telemetry.record_tick("decode_steps")
    telemetry.record("device-derive")
    assert tel.decode_host_free(snap)                 # warm + host-free
    telemetry.record("host-build")                    # steady-state bug
    assert not tel.decode_host_free(snap)


def test_host_free_warmup_boundary(isolated_everything):
    tel = isolated_everything
    telemetry.record("host-build", seconds=0.1)
    telemetry.record("device-derive")
    telemetry.record("device-derive")
    assert not tel.host_free()            # the warmup build counts
    assert tel.host_free(warmup=1)        # ... until it is excused
    telemetry.record("host-build")        # a post-warmup build is a bug
    assert not tel.host_free(warmup=1)


def test_isolated_restores_previous_stats():
    outer = telemetry.stats
    with telemetry.isolated() as inner:
        assert telemetry.stats is inner and inner is not outer
        telemetry.record("memory-hit")
        assert inner.total == 1
    assert telemetry.stats is outer


def test_plan_cache_feeds_telemetry(isolated_everything, tmp_path):
    """The three static-cache tiers each land in the right counter, with
    host builds carrying a positive measured latency."""
    tel = isolated_everything
    rng = np.random.default_rng(0)
    n, p = 256, 4
    cols = rng.integers(0, n, size=(64, 2)).astype(np.int32)

    plan_cache.get_comm_plan(cols, n, p)                 # cold: host build
    snap = tel.snapshot()
    assert snap["sources"]["host-build"] == 1
    assert snap["build_seconds"]["host-build"] > 0.0

    plan_cache.get_comm_plan(cols, n, p)                 # warm: memory LRU
    assert tel.since(snap)["memory-hit"] == 1

    plan_cache.clear_memory_cache()
    snap = tel.snapshot()
    plan_cache.get_comm_plan(cols, n, p)                 # persistent tier
    assert tel.since(snap)["disk-hit"] == 1

    snap = tel.snapshot()
    plan_cache.get_envelope_plan(cols, n, p, bucket=n)   # new envelope tier
    d = tel.since(snap)
    assert d["host-build"] == 1                          # founding build
    snap = tel.snapshot()
    other = rng.integers(0, n, size=(64, 2)).astype(np.int32)
    plan_cache.get_envelope_plan(other, n, p, bucket=n)  # coarse bucket
    assert tel.since(snap)["bucket-reuse"] == 1


def test_dynamic_moe_layer_runs_host_free(isolated_everything):
    """The tentpole acceptance test: one DynamicMoELayer, N distinct
    routings — after the construction/compile warmup, every routing is a
    single device-derive and host-build stays exactly zero."""
    import jax

    from repro.comm import perfmodel as pm
    from repro.models.moe import DynamicMoELayer, random_router

    tel = isolated_everything
    ndev = len(jax.devices())
    mesh = jax.make_mesh((ndev,), ("data",))
    n_tok, d, f, k, e_total, cap = 128, 4, 8, 2, 8, 16
    rng = np.random.default_rng(0)
    params = {
        "w1": (rng.standard_normal((e_total, d, f)) * 0.1).astype(np.float32),
        "w2": (rng.standard_normal((e_total, f, d)) * 0.1).astype(np.float32),
    }
    te0, tw0 = random_router(0, n_tok, e_total, k)
    layer = DynamicMoELayer(params, te0, n_tok, e_total, cap, mesh,
                            strategy="auto", hw=pm.ABEL)
    assert layer.plan_time > 0.0          # T_plan priced into the ranking
    x = layer.shard_tokens(rng.standard_normal((n_tok, d)).astype(np.float32))
    jax.block_until_ready(layer(x, te0, tw0))            # warmup: traces
    warmup = tel.snapshot()["total"]

    n_routings = 4
    snap = tel.snapshot()
    for s in range(1, 1 + n_routings):
        te, tw = random_router(s, n_tok, e_total, k)
        jax.block_until_ready(layer(x, te, tw))
    delta = tel.since(snap)
    assert delta["device-derive"] == n_routings
    assert delta["host-build"] == 0
    assert sum(delta[k] for k in telemetry.PLAN_SOURCES
               + telemetry.TICK_KINDS) == n_routings     # nothing else fired
    assert tel.host_free(warmup=warmup)


def test_span_accumulates_nests_and_times(isolated_everything):
    tel = isolated_everything
    with telemetry.span("plan.load") as outer:
        with telemetry.span("plan.key"):
            pass
        with telemetry.span("plan.key") as inner:
            pass
    spans = tel.snapshot()["spans"]
    assert spans["plan.key"]["count"] == 2
    assert spans["plan.load"] == {"seconds": outer.seconds, "count": 1}
    assert outer.seconds >= spans["plan.key"]["seconds"] >= inner.seconds
    snap = tel.snapshot()
    with telemetry.span("plan.key"):
        pass
    delta = tel.since(snap)["spans"]
    assert set(delta) == {"plan.key"} and delta["plan.key"]["count"] == 1


def test_span_lands_on_the_object_active_at_entry():
    with telemetry.isolated() as outer:
        with telemetry.span("comm.rank"):
            with telemetry.isolated() as inner:
                with telemetry.span("plan.key"):
                    pass
        assert set(inner.snapshot()["spans"]) == {"plan.key"}
        assert set(outer.snapshot()["spans"]) == {"comm.rank"}
    assert telemetry.stats is not outer


def test_span_closes_on_error(isolated_everything):
    with pytest.raises(RuntimeError):
        with telemetry.span("plan.build"):
            raise RuntimeError("boom")
    assert isolated_everything.snapshot()["spans"]["plan.build"]["count"] == 1


def test_host_build_record_is_timed_by_its_span(isolated_everything):
    tel = isolated_everything
    rng = np.random.default_rng(1)
    cols = rng.integers(0, 256, size=(64, 2)).astype(np.int32)
    plan_cache.get_comm_plan(cols, 256, 4)
    snap = tel.snapshot()
    assert snap["spans"]["plan.build"]["seconds"] == \
        snap["build_seconds"]["host-build"] > 0
    assert {"plan.key", "plan.store"} <= set(snap["spans"])
    plan_cache.clear_memory_cache()
    plan_cache.get_comm_plan(cols, 256, 4)
    assert tel.snapshot()["spans"]["plan.load"]["count"] == 1


def test_records_keep_no_per_record_history(isolated_everything):
    """A serving process records a device-derive every tick: nothing may
    grow with them, and host_free still answers from the host builds."""
    tel = isolated_everything
    telemetry.record("host-build")
    for _ in range(1000):
        telemetry.record("device-derive")
    assert not hasattr(tel, "events")
    assert tel._host_builds == [0]
    assert tel.host_free(warmup=1) and not tel.host_free()
    telemetry.record("host-build")
    assert tel._host_builds == [0, 1001]
    assert tel.host_free(warmup=1002) and not tel.host_free(warmup=1001)


def test_compile_listener_counts_compiles(isolated_everything):
    import jax
    import jax.numpy as jnp

    tel = isolated_everything
    telemetry.watch_compiles()           # already registered: adds none

    @jax.jit
    def fresh(x):
        return jnp.sin(x) * 3.0 + 0.125

    x = jnp.arange(7.0)
    snap = tel.snapshot()
    fresh(x).block_until_ready()
    first = tel.since(snap)
    assert first["compiles"] == 1 and first["compile_s"] > 0
    snap = tel.snapshot()
    fresh(x).block_until_ready()
    assert tel.since(snap)["compiles"] == 0


def test_spans_lie_in_a_profiler_trace(isolated_everything, tmp_path):
    import jax

    from bench import scopes, trace

    jax.profiler.start_trace(str(tmp_path))
    with telemetry.span("spmv.call"):
        with telemetry.span("plan.load"):
            pass
    jax.profiler.stop_trace()
    data = scopes.load_xplane(trace.find_xplane(str(tmp_path)))
    names = [name for name, _, _ in data["program_spans"]]
    assert "spmv.call" in names and "plan.load" in names
