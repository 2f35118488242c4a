"""Subprocess helper (8 dev): sharded train step == single-device train step,
and MoE ep_a2a sharding preserves outputs.  This is the distributed-equals-
local contract for the whole model stack."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.models.transformer import Model, RunCtx
from repro.optim.adamw import AdamW
from repro.runtime import sharding as sh
from repro.runtime.steps import build_train_step

from repro.launch.mesh import make_local_mesh


def run(name, ep_expected):
    cfg = get_config(name, reduced=True)
    mesh = make_local_mesh((4, 2), ("data", "model"))
    rules = sh.ShardingRules(
        mesh=mesh, fsdp_axes="data",
        ep_mode=cfg.is_moe and cfg.num_experts >= 2)
    assert rules.ep_mode == ep_expected

    b, s = 8, 32
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)

    # single device reference
    model0 = Model(cfg, RunCtx(remat="none", act_dtype=jnp.float32))
    params0 = model0.init_params(key)
    opt = AdamW(lr=1e-2)
    step0 = jax.jit(build_train_step(model0, opt))
    p0, _, m0 = step0(params0, opt.init(params0), (tokens, tokens), None)

    # sharded (moe_groups=1 so capacity semantics match the reference run;
    # grouped dispatch is exercised in test_moe_ssm + the dry-run)
    ctx = RunCtx(remat="none", act_dtype=jnp.float32, moe_groups=1,
                 constrain=sh.make_constrain(rules),
                 vocab_shards=2)
    model1 = Model(cfg, ctx)
    params1 = model1.init_params(key)
    pshard = sh.param_shardings(rules, jax.eval_shape(lambda: params1))
    params1 = jax.tree.map(jax.device_put, params1, pshard)
    ostate = opt.init(params1)
    step1 = jax.jit(build_train_step(model1, opt, grad_shardings=pshard))
    bshard = sh.batch_sharding(rules, (b, s))
    tok_s = jax.device_put(tokens, bshard)
    p1, _, m1 = step1(params1, ostate, (tok_s, tok_s), None)

    np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                               rtol=2e-3, atol=2e-3)
    # parameters after one step agree (spot-check a couple of leaves)
    l0 = jax.tree.leaves(p0)
    l1 = jax.tree.leaves(p1)
    for a, b_ in list(zip(l0, l1))[:6]:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-3, atol=5e-3)
    print(f"OK {name} loss={float(m1['loss']):.4f}")


def main():
    run("llama3-8b", False)       # dense GQA
    run("arctic-480b", True)      # MoE expert-parallel (condensed a2a)
    run("falcon-mamba-7b", False)  # SSM
    print("SHARDED_MODEL_OK")


if __name__ == "__main__":
    main()
