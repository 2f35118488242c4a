"""Each cell's harness path at a test size on the CPU: the SpMV cell on four
virtual devices, the serving cell on one; a cell added as files only is
found by name and runs; a measuring run without a TPU is refused."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from perfbench_roots import REPO, make_root, run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench_root"))


def test_spmv_cell_runs_on_four_devices(root):
    rc, line = run(root, "spmv_tiny.4chip", 1.0, devices=4)["none"]
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {"step_ms", "setup_s"}
    assert line["device"]["count"] == 4
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["max_rel_err"]["value"] < 1e-6
    assert list(line)[-1] == "checks"


def test_serve_cell_runs_on_one_device(root):
    rc, line = run(root, "serve_tiny.steady", 2.0)["none"]
    assert rc == 0 and line["correct"] is True, line
    assert set(line["metrics"]) == {"tokens_per_s", "ttft_p95_ms",
                                    "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] == 12 and line["failed"] == 0


def test_cell_added_as_files_only(root, tmp_path):
    """A new configuration, traffic mix and cell: files and entries only."""
    new = make_root(tmp_path / "added")
    spec = json.loads((new / "BENCHMARK.json").read_text())
    cfg = json.loads((new / "bench/configs/spmv_tiny.json").read_text())
    cfg.update(n=4096, mesh=[1], long_range_frac=0.2, matrix_seed=5)
    (new / "bench/configs/spmv_wide.json").write_text(json.dumps(cfg))
    (new / "bench/traffic/power_two.json").write_text(json.dumps(
        {"kind": "power_iteration", "check_samples": 2}))
    spec["configs"].append({"name": "spmv_wide", "source": "test",
                            "file": "bench/configs/spmv_wide.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "spmv_wide.1chip",
                              "config": "spmv_wide", "traffic": "power_two",
                              "chips": 1, "why": "added by files"})
    spec["end_to_end"][0]["workloads"].append("spmv_wide.1chip")
    (new / "BENCHMARK.json").write_text(json.dumps(spec))

    from bench.harness import resolve
    found = resolve(new, "spmv_wide.1chip")
    assert found["config"]["n"] == 4096
    assert found["traffic"]["check_samples"] == 2
    assert [m["name"] for m in found["e2e"]] == ["step_ms", "setup_s"]
    rc, line = run(new, "spmv_wide.1chip", 0.5)["none"]
    assert rc == 0 and line["correct"] is True


def test_measuring_run_without_tpu_is_refused(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    code = ("import sys; from bench.harness import main; "
            f"sys.exit(main(['--workload', 'serve_tiny.steady', '--seed', "
            f"'1', '--seconds', '1', '--trace', '0'], root={str(root)!r}))")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_unknown_workload_is_an_error(root):
    from bench.harness import resolve
    with pytest.raises(KeyError):
        resolve(root, "no_such.cell")
