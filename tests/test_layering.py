"""The package graph points one way: kernels <- comm <- {core, models} <-
serve <- launch.

Two checks hold it.  An AST check over every module of ``repro.comm`` and
``repro.kernels``, function-level imports included: a comm module may import
only ``repro.comm`` and ``repro.kernels``, a kernels module only
``repro.kernels``.  And, per front door of the comm library that reaches the
§5 models or the hardware calibration, a fresh CPU process runs it and then
finds no module of a consumer package loaded.
"""
import ast
import os
import pathlib
import subprocess
import sys

import pytest

SRC = pathlib.Path(__file__).resolve().parent.parent / "src"
ALLOWED = {"comm": ("repro.comm", "repro.kernels"),
           "kernels": ("repro.kernels",)}
CONSUMERS = ("repro.core", "repro.models", "repro.serve", "repro.launch")


def _imported(path: pathlib.Path, package: str):
    """Every ``repro`` module named by an import statement in ``path``."""
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            if node.level:  # relative: resolve against the package
                base = ".".join(
                    ["repro", package][:max(0, 3 - node.level)])
                mod = f"{base}.{node.module}" if node.module else base
            else:
                mod = node.module
            if mod == "repro":
                yield from (f"repro.{a.name}" for a in node.names)
            else:
                yield mod


def _within(name: str, prefixes) -> bool:
    return any(name == p or name.startswith(p + ".") for p in prefixes)


@pytest.mark.parametrize("package,module", [
    (pkg, path.name) for pkg in ALLOWED
    for path in sorted((SRC / "repro" / pkg).glob("*.py"))])
def test_imports_point_down(package, module):
    path = SRC / "repro" / package / module
    bad = sorted({name for name in _imported(path, package)
                  if _within(name, ("repro",))
                  and not _within(name, ALLOWED[package])})
    assert not bad, f"repro/{package}/{module} imports {bad}"


_PRELUDE = """
import sys
import jax
import numpy as np
from repro.comm import AccessPattern, STRATEGIES
p = len(jax.devices())
mesh = jax.make_mesh((p,), ("data",))
n = 64 * p
idx = np.random.default_rng(0).integers(0, n, size=(n, 3)).astype(np.int32)
pattern = AccessPattern.from_indices(idx, n=n)
"""

_ENTRY_POINTS = {
    "gather_auto": """
from repro.comm import IrregularGather
g = IrregularGather(pattern, mesh, strategy="auto", blocksize="auto")
assert g.strategy in STRATEGIES and g.hw is not None
""",
    "scatter_auto": """
from repro.comm import IrregularScatter
s = IrregularScatter(pattern, mesh, strategy="auto", blocksize=8)
assert s.strategy in STRATEGIES and s.hw is not None
""",
    "choose_blocksize": """
from repro.comm.select import choose_blocksize
bs = choose_blocksize(idx, n, p, hw=None)
assert (n // p) % bs == 0
""",
    "schedule_pricing": """
from repro.comm import Schedule
sched = Schedule()
x = sched.input("x")
g = sched.gather(pattern, src=x)
y = sched.compute(lambda xc, xl: xc[:xl.shape[0]] + xl, g, x)
step = sched.compile(mesh, output=y, strategy="auto", blocksize=8)
assert step.predicted_window["total"] > 0
""",
}

_POSTLUDE = """
loaded = sorted(m for m in sys.modules
                if any(m == c or m.startswith(c + ".") for c in %r))
assert not loaded, loaded
print("LAYERING_OK")
""" % (CONSUMERS,)


@pytest.mark.parametrize("entry", sorted(_ENTRY_POINTS))
def test_comm_entry_point_loads_no_consumer(entry, tmp_path):
    env = dict(os.environ)
    env.update(
        PYTHONPATH=str(SRC) + os.pathsep + env.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu", REPRO_PLAN_CACHE_DIR=str(tmp_path / "plans"),
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _PRELUDE + _ENTRY_POINTS[entry] + _POSTLUDE
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode == 0 and "LAYERING_OK" in proc.stdout, (
        proc.stdout[-2000:] + proc.stderr[-3000:])
