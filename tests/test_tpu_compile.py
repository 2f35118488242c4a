"""Compile every main-path Pallas kernel for a described TPU v5e.

Nothing runs: each test lowers a kernel at the sizes ``chip_smoke.py``
drives and compiles it with the TPU compiler for a chip that is described,
not attached.  Mosaic's refusals (unaligned dynamic slices, unsupported
primitives, VMEM or SMEM overflow) surface here at no chip time; each
compiled program must hold the Mosaic kernel (``tpu_custom_call``).
"""
import functools
import importlib
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.layout import VMEM_BUDGET_BYTES, item_bytes

pg = importlib.import_module("repro.kernels.pack_gather")
md = importlib.import_module("repro.kernels.mla_decode")
es = importlib.import_module("repro.kernels.ellpack_spmv")
st = importlib.import_module("repro.kernels.stencil2d")

# chip_smoke.py's kernel phase: the largest power-of-two matrix whose
# x_copy and SpMV window fit the VMEM budget together
N = 2**23
MSGS = 2**16            # a condensed message table
R_NZ = 16
ROWS_PER_BLOCK = 256
HEAT = 16384            # chip_smoke.py's Heat2D grid
D_MODEL = 6144          # mixtral-8x22b token rows (MoE exchange kernels)
# moonlight_decode.1chip: 5 stacked layers, 128 lanes, cache 8192; MLA at
# Moonlight-16B-A3B's widths (16 heads, rank 512, rope 64)
MLA_LAYERS, LANES, CACHE, HEADS, RANK, ROPE = 5, 128, 8192, 16, 512, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


I32, F32, BF16 = jnp.int32, jnp.float32, jnp.bfloat16

# (items, feature dims, dtype, messages): the SpMV shard of the kernel
# phase, and a block of mixtral token rows
CASES = [(N, (), F32, MSGS), (2048, (D_MODEL,), BF16, 512)]


@pytest.mark.parametrize("rows,feat,dtype,msgs", CASES)
def test_pack_gather_compiles(one_chip, rows, feat, dtype, msgs):
    _compile(functools.partial(pg.pack_gather, interpret=False), one_chip,
             ((rows,) + feat, dtype), ((msgs,), I32))


@pytest.mark.parametrize("rows,feat,dtype,msgs", CASES)
def test_unpack_dest_compiles(one_chip, rows, feat, dtype, msgs):
    _compile(functools.partial(pg.unpack_dest, interpret=False), one_chip,
             ((msgs,) + feat, dtype), ((rows,) + feat, dtype),
             ((msgs,), I32), ((msgs,), I32), ((msgs,), jnp.int8),
             ((msgs,), jnp.int8))


def test_unpack_scatter_set_compiles(one_chip):
    _compile(lambda r, i, x, o: pg.unpack_scatter_set(
        r, i, x, o, out_len=N + 2, interpret=False), one_chip,
        ((MSGS,), F32), ((MSGS,), I32), ((N,), F32), ((), I32))


@pytest.mark.parametrize("reduce", ["add", "max"])
@pytest.mark.parametrize("rows,feat,dtype,msgs", CASES)
def test_accumulate_segments_compiles(one_chip, reduce, rows, feat, dtype,
                                      msgs):
    _compile(lambda v, i: pg.accumulate_segments(
        v, i, out_len=rows + 1, reduce=reduce, interpret=False), one_chip,
        ((msgs,) + feat, dtype), ((msgs,), I32))


def test_accumulate_into_compiles(one_chip):
    _compile(functools.partial(pg.accumulate_into, interpret=False),
             one_chip, ((N + 1,), F32), ((MSGS,), F32), ((MSGS,), I32))


def test_ellpack_spmv_windowed_compiles(one_chip):
    # a 5%-long-range matrix spans the whole vector: window == N
    window = N
    _compile(lambda d, v, c, o, w, x: es.ellpack_spmv_windowed(
        d, v, c, o, w, x, window=window, rows_per_block=ROWS_PER_BLOCK,
        interpret=False), one_chip,
        ((N,), F32), ((N, R_NZ), F32), ((N, R_NZ), I32), ((N,), I32),
        ((N // ROWS_PER_BLOCK,), I32), ((2 * window,), F32))


def test_stencil2d_compiles(one_chip):
    _compile(lambda x: st.stencil2d(x, coef=0.1, interpret=False), one_chip,
             ((HEAT, HEAT), F32))


def test_resident_budget_compiles_at_its_limit(one_chip):
    # the largest scalar accumulator the budget admits still compiles
    n = VMEM_BUDGET_BYTES // 4 - 8 * 128
    assert item_bytes(n) <= VMEM_BUDGET_BYTES
    _compile(lambda v, i: pg.accumulate_segments(
        v, i, out_len=n, interpret=False), one_chip,
        ((MSGS,), F32), ((MSGS,), I32))


def test_mla_decode_compiles(one_chip):
    arrays = md.resident_arrays(LANES, HEADS, RANK, ROPE, CACHE,
                                md.block_rows(CACHE), 2)
    assert sum(item_bytes(*a) for a in arrays) <= VMEM_BUDGET_BYTES
    _compile(lambda q, qpe, ckv, kpe, sp, layer, pos: md.mla_decode_attention(
        q, qpe, ckv, kpe, sp, layer, pos, scale=0.07, interpret=False),
        one_chip, ((LANES, HEADS, RANK), F32), ((LANES, HEADS, ROPE), BF16),
        ((MLA_LAYERS, LANES, CACHE, RANK), BF16),
        ((MLA_LAYERS, LANES, ROPE, CACHE), BF16),
        ((MLA_LAYERS, LANES, CACHE), I32), ((), I32), ((LANES,), I32))


def test_decode_step_attends_through_the_kernel_in_scope(one_chip,
                                                         monkeypatch):
    """The decode module of the cell's model runs latent attention as the
    Mosaic kernel, under the ``mla`` scope the benchmark attributes by,
    and holds no copy of a cache layer."""
    import dataclasses

    from bench import scopes, serve_scopes
    from repro.configs.registry import get_config
    from repro.models.transformer import Model, RunCtx

    # the described chip is not the default backend: steer the kernel off
    # the interpreter
    monkeypatch.setattr(md, "interpret_mode", lambda: False)
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"),
                              num_layers=MLA_LAYERS)
    model = Model(cfg, RunCtx(remat="none", act_dtype=BF16))

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(
        model.init_params, dtype=BF16), jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(functools.partial(
        model.init_cache, LANES, CACHE, per_slot=True, dtype=BF16)))
    tokens = jax.ShapeDtypeStruct((LANES, 1), I32, sharding=one_chip)
    compiled = jax.jit(model.decode_step, donate_argnums=1).lower(
        params, cache, tokens).compile()
    text = compiled.as_text()
    names = scopes.hlo_op_names(text)
    kernels = [scopes.instruction(line.strip()) for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert kernels
    assert {serve_scopes.group_of(names[k]) for k in kernels} == {"mla"}
    # one layer's c_kv is 1.07 GB; the step's temporaries stay far below
    layer_bytes = LANES * CACHE * RANK * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes / 10
