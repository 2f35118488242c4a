"""A checkout-shaped directory for the benchmark tests: ``BENCHMARK.json``
with test-size cells, their configuration and traffic files, and no
``src`` (the program comes from the repository)."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"
REPO = Path(__file__).resolve().parents[2]
RUNNER = Path(__file__).resolve().parent / "perfbench_runner.py"


def make_root(tmp: Path) -> Path:
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    shutil.copy(DATA / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for f in (DATA / "configs").iterdir():
        shutil.copy(f, tmp / "bench" / "configs" / f.name)
    for f in (DATA / "traffic").iterdir():
        shutil.copy(f, tmp / "bench" / "traffic" / f.name)
    return tmp


def run(root: Path, workload: str, seconds: float, faults=("none",),
        devices: int = 1, timeout: int = 240) -> dict:
    """Run the harness in a child process on ``devices`` CPU devices; maps
    each fault to (rc, last line as a dict).  The child's XLA runs on one
    thread, so it leaves the cores to tests that time themselves."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices} "
                         "--xla_cpu_multi_thread_eigen=false")
    proc = subprocess.run(
        [sys.executable, str(RUNNER), str(root), workload, str(seconds),
         ",".join(faults)], env=env, capture_output=True, text=True,
        timeout=timeout)
    out = {}
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            _, fault, payload = line.split(" ", 2)
            d = json.loads(payload)
            out[fault] = (d["rc"], json.loads(d["line"]))
    assert set(out) == set(faults), proc.stderr[-4000:]
    return out
