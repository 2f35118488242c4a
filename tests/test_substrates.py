"""Optimizer, data pipeline, checkpointing, fused loss, fault utilities."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import manager as ckpt
from repro.data.pipeline import DataState, SyntheticLM
from repro.models.transformer import fused_ce_loss, lm_loss
from repro.optim.adamw import (AdamW, clip_by_global_norm, cosine_schedule,
                               global_norm)
from repro.runtime.fault import StragglerWatch, retrying

from repro.launch.mesh import make_local_mesh


# ---------------------------------------------------------------- optimizer
def test_adamw_minimizes_quadratic():
    opt = AdamW(lr=0.1, weight_decay=0.0, clip_norm=0.0)
    params = {"x": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"x": 2 * params["x"]}
        params, state, _ = opt.apply(params, grads, state)
    assert float(jnp.abs(params["x"]).max()) < 1e-2
    assert int(state["step"]) == 200


def test_clip_by_global_norm():
    tree = {"a": jnp.ones((4,)) * 3.0, "b": jnp.ones((2, 2)) * 4.0}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    np.testing.assert_allclose(float(global_norm(clipped)), 1.0, rtol=1e-5)
    assert float(norm) > 1.0


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, warmup_steps=10, total_steps=100)
    assert float(lr(0)) < 0.2
    np.testing.assert_allclose(float(lr(10)), 1.0, rtol=0.1)
    assert float(lr(99)) < 0.2


def test_weight_decay_pulls_to_zero():
    opt = AdamW(lr=0.05, weight_decay=0.5, clip_norm=0.0)
    params = {"x": jnp.array([5.0])}
    state = opt.init(params)
    for _ in range(100):
        params, state, _ = opt.apply(params, {"x": jnp.zeros(1)}, state)
    assert float(jnp.abs(params["x"])[0]) < 1.0


# ------------------------------------------------------------------- data
def test_data_deterministic_and_resumable():
    d = SyntheticLM(1000, 64, 4, seed=7)
    t1, l1 = d.batch_at(5)
    t2, l2 = d.batch_at(5)
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(t1[:, 1:], l1[:, :-1])
    assert t1.shape == (4, 64) and t1.dtype == np.int32
    assert t1.min() >= 0 and t1.max() < 1000
    # iterating from a restored state replays the exact stream
    it = d.iterate(DataState(step=5))
    t3, _ = next(it)
    np.testing.assert_array_equal(t1, t3)


def test_data_batches_differ_across_steps():
    d = SyntheticLM(1000, 64, 4, seed=7)
    a, _ = d.batch_at(0)
    b, _ = d.batch_at(1)
    assert (a != b).any()


# --------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    tree = {"w": jnp.arange(6.0).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.int32)}}
    ckpt.save(str(tmp_path), 7, tree, extra={"data": {"step": 7}})
    assert ckpt.latest_step(str(tmp_path)) == 7
    target = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    restored, extra = ckpt.restore(str(tmp_path), 7, target)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), restored, tree)
    assert extra == {"data": {"step": 7}}


def test_checkpoint_manager_gc_and_async(tmp_path):
    mgr = ckpt.CheckpointManager(str(tmp_path), keep_last=2, save_every=1)
    tree = {"w": jnp.ones((8,))}
    for step in (1, 2, 3, 4):
        assert mgr.maybe_save(step, tree)
    mgr.wait()
    steps = sorted(int(n.split("_")[1]) for n in os.listdir(tmp_path)
                   if n.startswith("step_"))
    assert steps == [3, 4]
    got, _, _ = mgr.restore_latest(tree)
    assert got == 4


def test_checkpoint_shape_mismatch_raises(tmp_path):
    ckpt.save(str(tmp_path), 1, {"w": jnp.ones((4,))})
    with pytest.raises(ValueError, match="shape mismatch"):
        ckpt.restore(str(tmp_path), 1, {"w": jnp.ones((5,))})


# --------------------------------------------------------------- fused loss
def test_fused_ce_matches_full_logits_loss():
    key = jax.random.PRNGKey(0)
    b, s, d, v = 2, 32, 16, 50
    x = jax.random.normal(key, (b, s, d))
    head = jax.random.normal(jax.random.PRNGKey(1), (d, v))
    labels = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0, v)
    full = lm_loss(x @ head, labels)
    fused = fused_ce_loss(x, head, labels, chunk=8)
    np.testing.assert_allclose(float(fused), float(full), rtol=1e-5)
    # gradients agree too
    g1 = jax.grad(lambda xx: lm_loss(xx @ head, labels))(x)
    g2 = jax.grad(lambda xx: fused_ce_loss(xx, head, labels, chunk=8))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-6)


# -------------------------------------------------------------------- fault
def test_straggler_watch_flags_outlier():
    w = StragglerWatch(window=50, z_thresh=4.0, patience=2)
    for _ in range(30):
        assert not w.observe(0.1 + np.random.default_rng(0).normal() * 1e-4)
    assert w.observe(10.0)
    assert not w.persistent
    assert w.observe(10.0)
    assert w.persistent


def test_retrying_recovers_then_raises():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 2:
            raise RuntimeError("transient")
        return "ok"

    assert retrying(flaky, retries=2)() == "ok"

    def always_fails():
        raise RuntimeError("hard")

    with pytest.raises(RuntimeError):
        retrying(always_fails, retries=1)()


# -------------------------------------------------------- int8 compression
def test_compressed_psum_error_feedback_single_device():
    """Error feedback: quantization residual is re-injected, so the running
    sum of dequantized values tracks the true sum (unbiased over steps)."""
    from repro.optim.compress import compressed_psum
    mesh = make_local_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P

    g = jnp.asarray(np.random.default_rng(0).standard_normal(128) * 1e-3,
                    jnp.float32)
    r = jnp.zeros_like(g)
    total_true, total_deq = jnp.zeros_like(g), jnp.zeros_like(g)
    f = jax.jit(jax.shard_map(
        lambda gg, rr: compressed_psum(gg, rr, "data"), mesh=mesh,
        in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False))
    for _ in range(50):
        out, r = f(g, r)
        total_deq = total_deq + out
        total_true = total_true + g
    # cumulative relative error shrinks thanks to error feedback
    rel = float(jnp.abs(total_deq - total_true).max()
                / jnp.abs(total_true).max())
    assert rel < 0.02, rel
