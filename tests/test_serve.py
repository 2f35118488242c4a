"""repro.serve: queue admission, slot lifecycle, the fused-prefill oracle,
and the engine's token-for-token identity with the naive batch-loop.

The greedy-decode comparisons are EXACT (assert_array_equal / ``==`` on
token lists): the engine and the baseline run the same jitted prefill /
insert / decode functions, so any drift is a real scheduling bug, not
float noise.  MoE configs use no-drop capacity (``capacity_factor =
E / k`` ⇒ capacity == tokens) — with drops enabled, fused prefill routes
B·S tokens per call while the sequential oracle routes B per step, and
different tokens lose the capacity race.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.models.transformer import Model, RunCtx
from repro.serve import (Request, RequestQueue, ServeEngine, SlotManager,
                         generate_batch_loop)

KEY = jax.random.PRNGKey(0)


def _cfg(family="moe", e=4, k=2):
    kw = dict(name="t", family=family, num_layers=2, d_model=16,
              num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=64)
    if family == "moe":
        kw.update(num_experts=e, experts_per_token=k,
                  capacity_factor=float(e) / k, act="swiglu")
    return ArchConfig(**kw)


def _model(cfg):
    model = Model(cfg, RunCtx(remat="none", act_dtype=jnp.float32))
    return model, model.init_params(KEY)


# -- queue: FIFO within arrival, arrival-time gating --

def test_queue_fifo_and_arrival_gating():
    q = RequestQueue()
    q.submit(Request(id="late", prompt=[1], max_new_tokens=1,
                     arrival_time=5.0))
    q.submit(Request(id="a", prompt=[1], max_new_tokens=1, arrival_time=0.0))
    q.submit(Request(id="b", prompt=[1], max_new_tokens=1, arrival_time=0.0))
    # nothing has arrived before t=0 ... and same-arrival pops are FIFO
    assert q.pop_ready(-1.0) is None
    assert q.pop_ready(0.0).id == "a"
    assert q.pop_ready(0.0).id == "b"
    # "late" is submitted but not yet arrived
    assert len(q) == 1 and q.pop_ready(4.9) is None
    assert q.next_arrival() == 5.0
    assert q.pop_ready(5.0).id == "late"
    assert not q


# -- slots: exhaustion, release, lowest-free reuse --

def test_slot_manager_lifecycle():
    sm = SlotManager(2)
    s0 = sm.allocate("r0", max_new_tokens=4)
    s1 = sm.allocate("r1", max_new_tokens=4)
    assert (s0, s1) == (0, 1)
    assert sm.allocate("r2") is None          # exhausted
    assert [s.index for s in sm.active()] == [0, 1]
    sm.release(0)
    assert sm.num_free == 1 and sm[0].free
    # reuse hands out the lowest free lane
    assert sm.allocate("r2", max_new_tokens=1) == 0
    assert sm[0].request_id == "r2" and sm[0].generated == 0


# -- fused prefill == sequential decode oracle, within f32 rounding --

# fused prefill and the sequential oracle sum attention in different
# orders, so they agree to f32 rounding, not bit for bit
PREFILL_RTOL = PREFILL_ATOL = 1e-5


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_fused_prefill_matches_sequential_oracle(family):
    from repro.launch.serve import prefill_into_cache

    cfg = _cfg(family)
    model, params = _model(cfg)
    B, S, L = 2, 6, 12
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, S)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        c_seq, logits_seq = prefill_into_cache(
            model, params, model.init_cache(B, L, dtype=jnp.float32), toks)
        logits_fused, c_fused = model.prefill(
            params, model.init_cache(B, L, dtype=jnp.float32), toks)
    np.testing.assert_allclose(np.asarray(logits_fused),
                               np.asarray(logits_seq),
                               rtol=PREFILL_RTOL, atol=PREFILL_ATOL)
    for leaf in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(c_fused["layers"][leaf]),
            np.asarray(c_seq["layers"][leaf]),
            rtol=PREFILL_RTOL, atol=PREFILL_ATOL)


# -- the engine vs the naive batch-loop: token-for-token --

def test_engine_matches_batch_loop_with_slot_reuse():
    cfg = _cfg("moe")
    model, params = _model(cfg)
    rng = np.random.default_rng(1)
    reqs = [Request(id=f"r{i}",
                    prompt=rng.integers(0, cfg.vocab_size,
                                        (int(rng.integers(3, 7)),)).tolist(),
                    max_new_tokens=3,
                    arrival_time=float(i // 2))
            for i in range(5)]

    engine = ServeEngine(model, params, num_slots=2, cache_len=12,
                         prefill_chunk=3, cache_dtype=jnp.float32)
    for r in reqs:
        engine.submit(r)
    rep = engine.run()
    base = generate_batch_loop(model, params, reqs, cache_len=12,
                               prefill_chunk=3, cache_dtype=jnp.float32)
    assert rep.outputs == base                # greedy tokens, bit-identical
    # 5 requests over 2 lanes: admission must have reused released slots
    assert set(rep.slot_of.values()) == {0, 1}
    assert len(rep.slot_of) == 5
    # equal budgets + staggered arrivals => completions in admission order
    assert rep.completed == [r.id for r in reqs]
    # every decode tick and prefill chunk was counted
    assert rep.telemetry["decode_steps"] == len(rep.tick_seconds) > 0
    assert rep.telemetry["prefill_chunks"] >= len(reqs)
    assert rep.total_tokens == sum(r.max_new_tokens for r in reqs)
    assert set(rep.ttft_seconds) == {r.id for r in reqs}


def test_engine_submit_validation():
    model, params = _model(_cfg("dense"))
    engine = ServeEngine(model, params, num_slots=1, cache_len=4,
                         cache_dtype=jnp.float32)
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit(Request(id="x", prompt=[1], max_new_tokens=0))
    with pytest.raises(ValueError, match="prompt length"):
        engine.submit(Request(id="x", prompt=[1] * 5, max_new_tokens=1))
    with pytest.raises(ValueError, match="prompt length"):
        engine.submit(Request(id="x", prompt=[], max_new_tokens=1))


def test_engine_rejects_mismatched_moe_layer():
    class FakeLayer:
        num_tokens = 4

    model, params = _model(_cfg("moe"))
    with pytest.raises(ValueError, match="num_tokens"):
        ServeEngine(model, params, num_slots=2, cache_len=8,
                    moe_layer=FakeLayer())


# -- 8-device sharded MoE decode path (CI: non-blocking slow job) --

@pytest.mark.slow
def test_engine_moe_comm_bit_identity_and_host_free():
    """The ISSUE's acceptance smoke: on 8 devices, the engine with the
    §5-priced DynamicMoELayer decode hook emits bit-identical greedy
    tokens to the naive batch-loop running the SAME hook, and the
    steady-state interval performs zero host plan builds."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (XLA_FLAGS host device count)")
    from repro.configs.registry import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import build_moe_layer

    cfg = get_config("mixtral-8x22b", reduced=True)
    # experts divide the mesh, full attention, no-drop capacity
    cfg = dataclasses.replace(cfg, num_experts=8, swa_window=0,
                              capacity_factor=8.0 / cfg.experts_per_token)
    model, params = _model(cfg)
    mesh = make_local_mesh((8,), ("data",))
    layer = build_moe_layer(model, params, 8, mesh)
    assert layer.decode and layer.gather.decode and layer.scatter.decode

    engine = ServeEngine(model, params, num_slots=8, cache_len=16,
                         prefill_chunk=4, moe_layer=layer,
                         cache_dtype=jnp.float32)
    rng = np.random.default_rng(2)

    def batch(tag, gen):
        return [Request(id=f"{tag}{i}",
                        prompt=rng.integers(0, cfg.vocab_size,
                                            (8,)).tolist(),
                        max_new_tokens=gen, arrival_time=float(i // 4))
                for i in range(8)]

    for r in batch("warm", 2):                # warmup: traces + compiles
        engine.submit(r)
    engine.run()
    snap = engine.snapshot()

    reqs = batch("req", 4)
    for r in reqs:
        engine.submit(r)
    rep = engine.run()
    delta = engine.assert_steady_state(snap)  # raises on any host-build
    assert delta["host-build"] == 0 and delta["decode_steps"] > 0
    # one in-jit derivation per MoE layer per executed decode tick
    assert delta["device-derive"] == cfg.num_layers * delta["decode_steps"]

    base = generate_batch_loop(model, params, reqs, cache_len=16,
                               prefill_chunk=4, moe_layer=layer,
                               cache_dtype=jnp.float32)
    assert {r.id: rep.outputs[r.id] for r in reqs} == base
