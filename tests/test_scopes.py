"""Named scopes on the exchange and the SpMV product: every gather, scatter,
dynamic-update-slice, reduce and collective instruction of the compiled step
carries ``comm.pack``, ``comm.exchange``, ``comm.unpack`` or ``spmv.local``
in its ``op_name``, on every rung, unpack and direction, jnp and kernel
paths (4 host devices, one subprocess compiles them all)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HELPER = Path(__file__).parent / "helpers" / "scope_coverage.py"
REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HELPER.parent))

from scope_coverage import cases  # noqa: E402

CASES = [name for name, _ in cases()]


@pytest.fixture(scope="module")
def compiled():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
                         "--xla_cpu_multi_thread_eigen=false")
    proc = subprocess.run([sys.executable, str(HELPER)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", CASES)
def test_step_ops_carry_a_scope(compiled, case):
    got = compiled[case]
    assert got["unscoped"] == [], got["unscoped"]
    scopes = {s.split("/")[0] for s in got["scopes"]}
    # every step holds its collective; a forward product gathers and sums
    # under spmv.local (the transposed one only multiplies and adds there);
    # replicate's forward gather packs nothing; its full copy is the
    # all-gather's output, and its transposed unpack only slices
    rung, forward = case.split("-")[0], "forward" in case
    assert "comm.exchange" in scopes, scopes
    assert ("spmv.local" in scopes) is forward, scopes
    assert ("comm.pack" in scopes) is (rung != "replicate" or not forward)
    assert ("comm.unpack" in scopes) is (rung != "replicate" or (
        forward and "-dest-" in case)), scopes


def test_overlap_splits_own_and_foreign(compiled):
    scopes = compiled["overlap-dest-forward-jnp"]["scopes"]
    assert "spmv.local/own" in scopes and "spmv.local/foreign" in scopes
    assert "comm.unpack" in compiled["overlap-full-forward-jnp"]["scopes"]
