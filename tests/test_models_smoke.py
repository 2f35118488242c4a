"""Per-architecture smoke tests (assignment deliverable f): every assigned
arch instantiates a REDUCED same-family config and runs one forward/train
step and one decode step on CPU, asserting output shapes and finiteness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_NAMES, get_config
from repro.models.transformer import Model, RunCtx, lm_loss

KEY = jax.random.PRNGKey(0)


def _extra_for(cfg, b, key):
    if cfg.is_encdec:
        return {"frames": jax.random.normal(
            key, (b, cfg.encoder_seq, cfg.d_model))}
    if cfg.is_vlm:
        return {"image_embeds": jax.random.normal(
            key, (b, cfg.num_image_tokens, cfg.d_model))}
    return None


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_full_config_matches_assignment(name):
    cfg = get_config(name)
    spec = {
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "granite-20b": (52, 6144, 48, 1, 24576, 49152),
        "minitron-4b": (32, 3072, 24, 8, 9216, 256000),
        "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
        "llama3-8b": (32, 4096, 32, 8, 14336, 128256),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "falcon-mamba-7b": (64, 4096, 0, 0, 0, 65024),
        "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
        "llama-3.2-vision-90b": (100, 8192, 64, 8, 28672, 128256),
        # d_ff: the routed experts' width (moe_intermediate_size)
        "moonlight-16b-a3b": (27, 2048, 16, 16, 1408, 163840),
    }[name]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == spec


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_train_step(name):
    cfg = get_config(name, reduced=True)
    model = Model(cfg, RunCtx(remat="none", act_dtype=jnp.float32))
    params = model.init_params(KEY)
    b, s = 2, 32
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    extra = _extra_for(cfg, b, KEY)

    logits = model.forward(params, tokens, extra=extra)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())

    loss, grads = jax.value_and_grad(
        lambda p: model.loss(p, tokens, tokens, extra=extra, chunk=16))(params)
    assert bool(jnp.isfinite(loss))
    gnorm = sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_decode_step(name):
    cfg = get_config(name, reduced=True)
    model = Model(cfg, RunCtx(remat="none", act_dtype=jnp.float32))
    params = model.init_params(KEY)
    b = 2
    cross_len = cfg.encoder_seq or cfg.num_image_tokens or 0
    cache = model.init_cache(b, 16, cross_len=cross_len, dtype=jnp.float32)
    extra = _extra_for(cfg, b, KEY)
    if extra is not None:
        context = next(iter(extra.values()))
        cache = model.prefill_cross(params, cache, context)
    tok = jax.random.randint(KEY, (b, 1), 0, cfg.vocab_size)
    logits, cache2 = model.decode_step(params, cache, tok)
    assert logits.shape == (b, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert int(cache2["pos"]) == 1
    # second step advances
    logits2, cache3 = model.decode_step(params, cache2, tok)
    assert int(cache3["pos"]) == 2
    assert bool(jnp.isfinite(logits2).all())


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_param_count_matches_eval_shape(name):
    cfg = get_config(name, reduced=True)
    model = Model(cfg, RunCtx())
    shapes = jax.eval_shape(model.init_params, KEY)
    actual = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    analytic, active = cfg.param_count()
    assert active <= analytic
    # analytic count tracks the real tree within 2% (rope/minor buffers)
    assert abs(actual - analytic) / actual < 0.02, (actual, analytic)


def test_decode_matches_forward_dense():
    """Teacher-forced decode reproduces the training forward logits."""
    cfg = get_config("llama3-8b", reduced=True)
    model = Model(cfg, RunCtx(remat="none", act_dtype=jnp.float32))
    params = model.init_params(KEY)
    b, s = 2, 12
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    full = model.forward(params, tokens)
    cache = model.init_cache(b, s, dtype=jnp.float32)
    outs = []
    for i in range(s):
        logits, cache = model.decode_step(params, cache, tokens[:, i:i + 1])
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


def test_decode_matches_forward_ssm():
    cfg = get_config("falcon-mamba-7b", reduced=True)
    model = Model(cfg, RunCtx(remat="none", act_dtype=jnp.float32))
    params = model.init_params(KEY)
    b, s = 2, 8
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    full = model.forward(params, tokens)
    cache = model.init_cache(b, s, dtype=jnp.float32)
    outs = []
    for i in range(s):
        logits, cache = model.decode_step(params, cache, tokens[:, i:i + 1])
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full),
                               rtol=2e-3, atol=2e-3)


def test_swa_ring_cache_equals_full_window():
    """Ring-buffer SWA cache must agree with full attention as long as the
    context fits the window (mixtral long_500k mechanism)."""
    import dataclasses
    cfg = get_config("mixtral-8x22b", reduced=True)  # swa_window=16
    # huge capacity so train-path MoE drops cannot diverge from decode
    cfg = dataclasses.replace(cfg, capacity_factor=16.0)
    model = Model(cfg, RunCtx(remat="none", act_dtype=jnp.float32))
    params = model.init_params(KEY)
    b, s = 1, 12  # < window
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    full = model.forward(params, tokens)
    cache = model.init_cache(b, 64, dtype=jnp.float32)  # clamps to window 16
    assert cache["layers"]["k"].shape[2] == cfg.swa_window
    outs = []
    for i in range(s):
        logits, cache = model.decode_step(params, cache, tokens[:, i:i + 1])
        outs.append(logits[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full), rtol=2e-3, atol=2e-3)
