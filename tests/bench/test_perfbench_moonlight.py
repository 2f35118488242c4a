"""The closed-batch decode cell (``moonlight_decode.1chip``'s system,
``bench/systems/serve_decode.py``) at test size on the CPU: the harness
path end to end, its check against planted faults and the float8 control,
the byte counts of its rooflines against hand counts, and the scope split
its readers share."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import serve_scopes, serve_work

DATA = Path(__file__).resolve().parent / "data_moonlight"
REPO = Path(__file__).resolve().parents[2]
RUNNER = Path(__file__).resolve().parent / "moonlight_runner.py"
CELL = "moonlight_tiny.decode"
SEED = 3000000017
FAULTS = ("latent_zeroed", "routing_forced", "lane_altered")


def _root(tmp: Path) -> Path:
    for kind in ("configs", "traffic"):
        shutil.copytree(DATA / kind, tmp / "bench" / kind)
    shutil.copy(DATA / "BENCHMARK.json", tmp / "BENCHMARK.json")
    return tmp


def _run(root: Path, names, seconds: float = 1.0) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=1 "
                         "--xla_cpu_multi_thread_eigen=false")
    proc = subprocess.run(
        [sys.executable, str(RUNNER), str(root), CELL, str(seconds),
         str(SEED), ",".join(names)], env=env, capture_output=True,
        text=True, timeout=900)
    out = {}
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            _, fault, payload = line.split(" ", 2)
            d = json.loads(payload)
            out[fault] = (d["rc"], json.loads(d["line"]))
    assert set(out) == set(names), proc.stderr[-4000:]
    return out


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = _root(tmp_path_factory.mktemp("moonlight"))
    return _run(root, ("none",) + FAULTS + ("control",))


def test_cell_runs_and_passes(runs):
    rc, line = runs["none"]
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {"step_ms", "setup_s"}
    assert line["metrics"]["step_ms"]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    checks = line["checks"]
    assert set(checks) == {"max_logit_gap", "rms_logit_gap",
                           "outside_margin_share",
                           "moe_dropped"}
    assert checks["moe_dropped"]["value"] == 0
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_fails(runs, fault):
    rc, line = runs[fault]
    assert rc == 0 and line["correct"] is False, line.get("checks")


def test_routing_fault_reads_outside_the_margin(runs):
    checks = runs["routing_forced"][1]["checks"]
    assert checks["outside_margin_share"]["value"] > 0.5


def test_float8_control_fails_the_limits(runs):
    readings = runs["control"][1]
    limits = json.loads((DATA / "configs" / "moonlight_tiny.json")
                        .read_text())["check"]
    assert readings["program"]["max_logit_gap"] <= limits["max_logit_gap"]
    assert readings["control"]["max_logit_gap"] > limits["max_logit_gap"]
    assert readings["program"]["rms_logit_gap"] <= limits["rms_logit_gap"]
    assert readings["control"]["rms_logit_gap"] > limits["rms_logit_gap"]


MOONLIGHT = json.loads((REPO / "bench" / "configs" /
                        "moonlight_16b_a3b.json").read_text())


def test_byte_counts_by_hand():
    cfg = MOONLIGHT
    # W_q 2048 x 16*(128+64), W_kv_a 2048 x (512+64), the c_kv norm 512,
    # W_kv_b 512 x 16*(128+128), W_o 16*128 x 2048: 13,763,072 values
    assert serve_work.mla_weight_bytes(cfg) == 2 * (
        6_291_456 + 1_179_648 + 512 + 2_097_152 + 4_194_304)
    assert serve_work.latent_row_bytes(cfg) == 1152
    assert serve_work.mla_least_bytes(cfg, 1000) == 5 * (
        1000 * 1152 + 27_526_144)
    assert serve_work.expert_bytes(cfg) == 2 * 3 * 2048 * 1408
    # two shared experts of 1408 = one SwiGLU of 2816; router 2048 x 64
    # and its 64 biases
    assert serve_work.moe_fixed_bytes(cfg) == 2 * (
        3 * 2048 * 2816 + 2048 * 64 + 64)
    assert serve_work.moe_least_bytes(cfg, 256) == \
        256 * 17_301_504 + 4 * 34_865_280
    # the whole latent cache of the cell: 128 lanes x 8192 x 5 layers
    assert 128 * 8192 * 5 * serve_work.latent_row_bytes(cfg) == \
        6_039_797_760


def test_decode_split_attributes_by_scope():
    """Two executions of the decode module; an op under ``mla``, one under
    ``moe.experts/comm.pack`` nested in a ``moe.route`` one, one unscoped,
    and an op of another module that the split leaves out."""
    names = {"fusion.1": "jit(decode_step)/while/body/mla/dot_general",
             "fusion.2": "jit(decode_step)/while/body/moe.route/top_k",
             "fusion.3": "jit(decode_step)/moe.experts/comm.pack/gather",
             "fusion.4": "jit(decode_step)/dot_general"}
    ops, modules = [], []
    for base in (0, 1000):
        modules.append(("jit_decode_step(1)", base, 100))
        ops += [("%fusion.1 = f32[8] fusion(a)", base, 30),
                ("%fusion.2 = f32[8] fusion(b)", base + 30, 40),
                ("%fusion.3 = f32[8] fusion(c)", base + 40, 10),
                ("%fusion.4 = f32[8] fusion(d)", base + 80, 20)]
    ops.append(("%fusion.9 = f32[8] fusion(e)", 500, 50))
    data = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "spans": [("window", 0, 2000)]}
    from bench.trace import TraceSummary
    ctx = SimpleNamespace(trace=TraceSummary(data), measured=SimpleNamespace(
        counters={"decode_op_names": names}))
    split = serve_scopes.decode_split(ctx)
    assert split == pytest.approx({"mla": 30e-9, "moe": 40e-9,
                                   "unscoped": 20e-9})
    assert serve_scopes.group_of(names["fusion.3"]) == "moe"
    # no op names (an untraced run, or the parent's program): nothing read
    ctx.measured.counters = {}
    assert serve_scopes.decode_split(ctx) is None
