"""The comparison that decides ``correct`` fails what it must: each fault a
cell can have, planted under the timed path, turns ``correct`` false; the
control (the reference one precision below the configuration's) fails the
limit; the unbroken program passes it."""
from __future__ import annotations

import json

import numpy as np
import pytest

from perfbench_roots import DATA, make_root, run

SPMV_FAULTS = ("unchanged", "half_rows", "no_exchange", "altered")
SERVE_FAULTS = ("token_altered", "cache_unchanged", "moe_left_out")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("fault_root"))


@pytest.fixture(scope="module")
def spmv_runs(root):
    return run(root, "spmv_tiny.4chip", 0.5, ("none",) + SPMV_FAULTS,
               devices=4)


@pytest.fixture(scope="module")
def serve_runs(root):
    return run(root, "serve_tiny.steady", 2.0, ("none",) + SERVE_FAULTS)


def test_spmv_unbroken_passes(spmv_runs):
    rc, line = spmv_runs["none"]
    assert rc == 0 and line["correct"] is True


@pytest.mark.parametrize("fault", SPMV_FAULTS)
def test_spmv_fault_fails(spmv_runs, fault):
    rc, line = spmv_runs[fault]
    assert rc == 0 and line["correct"] is False, line


def test_serve_unbroken_passes(serve_runs):
    rc, line = serve_runs["none"]
    assert rc == 0 and line["correct"] is True


@pytest.mark.parametrize("fault", SERVE_FAULTS)
def test_serve_fault_fails(serve_runs, fault):
    rc, line = serve_runs[fault]
    assert rc == 0 and line["correct"] is False, line


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_spmv_control_fails_the_limit(seed):
    from bench.common import rng
    from bench.refs import spmv_ref
    from perfbench_roots import DATA

    cfg = json.loads((DATA / "configs/spmv_tiny.json").read_text())
    diag, vals, cols = spmv_ref.make_matrix(cfg)
    x = rng(seed, "x").standard_normal(cfg["n"], dtype=np.float32)
    want = spmv_ref.reference(diag, vals, cols, x)
    ctl = spmv_ref.rel_err(spmv_ref.control_bf16(diag, vals, cols, x), want)
    f32 = ((vals * x[cols]).sum(axis=1, dtype=np.float32) + diag * x)
    assert spmv_ref.rel_err(f32, want) <= cfg["check"]["max_rel_err"]
    assert ctl > 3 * cfg["check"]["max_rel_err"]


def test_serve_control_fails_the_limit():
    """The float8-weight control's widest gap over three seeds' prompts
    exceeds the tiny configuration's limit; the float32 reference against
    itself reads 0."""
    import jax
    import jax.numpy as jnp
    from bench.refs import mixtral_ref
    from bench.systems import serve as S
    from perfbench_roots import DATA

    cfg = json.loads((DATA / "configs/mixtral_tiny.json").read_text())
    arch = S.program_config(cfg)
    from repro.models.transformer import Model, RunCtx
    import functools
    model = Model(arch, RunCtx(remat="none", act_dtype=jnp.bfloat16))
    abstract = jax.eval_shape(functools.partial(model.init_params,
                                                dtype=jnp.bfloat16),
                              jax.random.PRNGKey(0))
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, str))))
    ref = mixtral_ref.compiled_forward(key, False)
    ctl = mixtral_ref.compiled_forward(key, True)
    worst = []
    for seed in (1, 2, 3):
        params = mixtral_ref.make_params(abstract, seed)
        toks = np.random.default_rng(seed).integers(
            0, cfg["vocab_size"], cfg["program"]["cache_len"]).astype(np.int32)
        want = np.asarray(ref(params, jnp.asarray(toks)))
        first = np.asarray(ctl(params, jnp.asarray(toks))).argmax(1)
        gaps = mixtral_ref.served_gaps(want, 1, first[:-1])
        assert mixtral_ref.served_gaps(want, 1, want.argmax(1)[:-1]).max() \
            == 0.0
        worst.append(gaps.max())
    assert min(worst) > cfg["check"]["max_logit_gap"], worst


def test_spmv_calibration_reuses_the_check(tmp_path):
    """The calibration finds the system's driver by name and reads program
    and control through the run's own check: on four CPU devices the
    program reads under the tiny cell's limit and the control over it."""
    import os
    import subprocess
    import sys

    from perfbench_roots import REPO

    root = make_root(tmp_path / "cal")
    code = f"""
import json, sys
from pathlib import Path
from types import SimpleNamespace
from bench import harness
root = Path({str(root)!r})
found = harness.resolve(root, "spmv_tiny.4chip")
system = harness.load_module(harness.find_file(
    root, "systems", found["config"]["system"], ".py"), "bench_system")
args = SimpleNamespace(seeds=[11, 12], control_seeds=[13], seconds=0.3,
                       trace_out="", probe=False)
out = system.calibrate(found, args, 4, None)
print(json.dumps(out))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
                         "--xla_cpu_multi_thread_eigen=false")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    limit = json.loads((DATA / "configs/spmv_tiny.json").read_text())[
        "check"]["max_rel_err"]
    assert set(out["program"]) == {"11", "12"} and set(out["control"]) == {
        "13"}
    assert max(out["program"].values()) < limit < out["control"]["13"]
