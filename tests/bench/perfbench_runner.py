"""Drive the benchmark harness on the CPU for the tests: skip its look for a
chip, optionally break the timed path underneath, and print what each run
reported.

    python tests/bench/perfbench_runner.py ROOT WORKLOAD SECONDS FAULT[,FAULT...]

For each fault (``none`` for the unbroken program) the harness runs the
cell once with that fault planted, and one line ``RESULT <fault> <json>``
is printed with the run's last line.
"""
from __future__ import annotations

import contextlib
import io
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def _spmv_faults():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.spmv import DistributedSpMV

    orig = DistributedSpMV.__call__

    def unchanged(self, x):
        return x                                    # state returned as is

    def half_rows(self, x):
        y = orig(self, x)
        n = y.shape[0]
        # half of the rows left out, the rest scaled to keep the total
        return jnp.where(jnp.arange(n) < n // 2, 2.0 * y, 0.0).astype(y.dtype)

    def no_exchange(self, x):
        y = orig(self, x)
        m = self.matrix
        own = np.arange(m.n) // (m.n // self.p)
        foreign = own[m.cols] != own[:, None]
        xh = np.asarray(x)
        lost = (np.where(foreign, m.vals * xh[m.cols], 0.0)).sum(axis=1)
        return jax.device_put(np.asarray(y) - lost.astype(np.float32),
                              y.sharding)

    def altered(self, x):
        y = orig(self, x)
        return y.at[3].add(1e-3 * jnp.max(jnp.abs(y)))

    return DistributedSpMV, "__call__", {
        "unchanged": unchanged, "half_rows": half_rows,
        "no_exchange": no_exchange, "altered": altered}


def _serve_faults():
    import jax.numpy as jnp
    from repro.models.moe import DynamicMoELayer
    from repro.models.transformer import Model
    from repro.serve.engine import ServeEngine

    emit = ServeEngine._emit
    decode = Model.decode_step
    apply = DynamicMoELayer.apply

    def token_altered(self, s, tok):
        if s.generated == 3:                        # the 4th token of each
            tok = (tok + 1) % self.model.cfg.vocab_size
        return emit(self, s, tok)

    def cache_unchanged(self, params, cache, tokens):
        logits, new = decode(self, params, cache, tokens)
        return logits, {"pos": new["pos"], "layers": cache["layers"]}

    def moe_left_out(self, x, top_e, top_w, *weights):
        return jnp.zeros_like(apply(self, x, top_e, top_w, *weights))

    return {"token_altered": (ServeEngine, "_emit", token_altered),
            "cache_unchanged": (Model, "decode_step", cache_unchanged),
            "moe_left_out": (DynamicMoELayer, "apply", moe_left_out)}


def plant(fault: str):
    """(class, attribute, replacement) of ``fault``."""
    if fault in ("unchanged", "half_rows", "no_exchange", "altered"):
        cls, attr, table = _spmv_faults()
        return cls, attr, table[fault]
    return _serve_faults()[fault]


def main() -> int:
    root, workload, seconds, faults = sys.argv[1:5]
    from bench.harness import main as bench_main

    for fault in faults.split(","):
        patch = None if fault == "none" else plant(fault)
        if patch:
            cls, attr, new = patch
            old = getattr(cls, attr)
            setattr(cls, attr, new)
        out = io.StringIO()
        try:
            with contextlib.redirect_stdout(out):
                rc = bench_main(["--workload", workload, "--seed",
                                 "3000000017", "--seconds", seconds,
                                 "--trace", "0"], root=Path(root),
                                allow_cpu=True)
        finally:
            if patch:
                setattr(cls, attr, old)
        last = out.getvalue().strip().splitlines()[-1] if rc == 0 else "{}"
        print(f"RESULT {fault} {json.dumps({'rc': rc, 'line': last})}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
