"""The entry points' persistent compile cache: JAX's own variable when it
is set, else a fixed ``.jax_cache/`` at the checkout root."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(tmp_path, env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(ROOT / "src")
    want = ROOT / ".jax_cache"
    if env_dir:
        want = tmp_path / env_dir
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; from repro.launch.cache import enable_compile_cache;"
         "print(enable_compile_cache()); "
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, check=True).stdout.split()
    assert out == [str(want), str(want)]
