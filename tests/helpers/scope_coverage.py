"""Subprocess helper: compile ``DistributedSpMV``'s step on 4 host devices
for every rung x materialize x direction (jnp and kernel paths) and report,
per case, the instructions of the compiled program by the named scope of
their ``op_name``.  Run as:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 python scope_coverage.py
Prints one JSON object: {case: {"scopes": {scope: count}, "unscoped":
[[opcode, op_name], ...]}} over the gather, scatter, dynamic-update-slice,
reduce and collective instructions.
"""
import json
import os
import re
import sys
from pathlib import Path

if __name__ == "__main__":      # the test imports ``cases`` from here too
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")
    os.environ.setdefault("REPRO_PLAN_CACHE", "0")
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench.scopes import UNSCOPED, scope_of  # noqa: E402
from repro.core.matrix import make_mesh_like_matrix  # noqa: E402
from repro.core.spmv import DistributedSpMV  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402

OPCODES = ("gather", "scatter", "dynamic-update-slice", "reduce",
           "all-to-all", "all-gather", "all-reduce", "reduce-scatter",
           "collective-permute")
_INSTR = re.compile(r"%\S+ = .*? (" + "|".join(map(re.escape, OPCODES))
                    + r")\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
RUNGS = ("replicate", "blockwise", "condensed", "overlap")


def cases():
    for kernel in (False, True):
        path = "kernel" if kernel else "jnp"
        for rung in RUNGS:
            for mat in ("dest", "full"):
                yield f"{rung}-{mat}-forward-{path}", dict(
                    strategy=rung, materialize=mat, use_kernel=kernel)
            yield f"{rung}-transpose-{path}", dict(
                strategy=rung, transpose=True, use_kernel=kernel)


def coverage(text: str) -> dict:
    scopes, unscoped = {}, []
    for line in text.splitlines():
        m = _INSTR.search(line)
        if not m:
            continue
        on = _OP_NAME.search(line)
        scope = scope_of(on.group(1) if on else "")
        if scope == UNSCOPED:
            unscoped.append([m.group(1), on.group(1) if on else None])
        else:
            scopes[scope] = scopes.get(scope, 0) + 1
    return {"scopes": scopes, "unscoped": unscoped}


def main():
    assert len(jax.devices()) == 4, jax.devices()
    mesh = make_local_mesh((4,), ("data",))
    n = 1024
    m = make_mesh_like_matrix(n, 4, seed=1)
    out = {}
    for name, kw in cases():
        eng = DistributedSpMV(m, mesh, blocksize=64, **kw)
        x = eng.shard_vector(np.ones(n, np.float32))
        out[name] = coverage(eng.lower(x).compile().as_text())
    print(json.dumps(out))


if __name__ == "__main__":
    main()
