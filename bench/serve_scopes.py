"""The decode step's device time by the serving path's own scopes.

The program names its decode-step ops (``repro.models``): ``mla`` around
latent attention, ``moe.route``, ``moe.experts`` (with the exchange's
``comm.pack``, ``comm.exchange`` and ``comm.unpack`` inside it) and
``moe.shared`` around the MoE FFN.  A v5e trace's op events carry no
op_name, so the cell's system (``bench/systems/serve_decode.py``) puts the
compiled decode module's {instruction: op_name}
(``bench.scopes.hlo_op_names``) into ``Measured.counters
["decode_op_names"]``; ``decode_split`` joins them to the trace's op events
by instruction name.

Each instant of an execution of the decode module goes to the op that
started last (``bench.scopes._attribute``), collectives included, so the
groups partition the module's busy time: ``mla``, ``moe`` (every op under a
``moe.*`` scope) and ``unscoped`` (embedding, the leading dense layer's
FFN, norms, the LM head and the greedy argmax).
"""
from __future__ import annotations

from bench import scopes, trace

MODULE = "decode_step"
GROUPS = ("mla", "moe", "unscoped")


def group_of(op_name: str) -> str:
    parts = op_name.split("/") if op_name else []
    if any(p.startswith("moe.") for p in parts):
        return "moe"
    return "mla" if "mla" in parts else "unscoped"


def decode_split(ctx) -> dict[str, float] | None:
    """{group: device seconds per execution of the decode module}, averaged
    over the chips that ran it; None without a trace or op names."""
    counters = ctx.measured.counters
    names = counters.get("decode_op_names")
    if ctx.trace is None or not names:
        return None
    if "decode_split" not in counters:       # four readers share one split
        counters["decode_split"] = _split(ctx.trace, names)
    return counters["decode_split"]


def _split(summary_in, names: dict) -> dict[str, float] | None:
    summary = scopes.ScopedSummary(summary_in.data)
    per_dev = []
    for dev in summary.devices:
        runs = summary._executions(dev, MODULE)
        if not runs:
            continue
        events = [(float(s), float(s) + float(d),
                   group_of(names.get(scopes.instruction(n), "")))
                  for n, s, d in summary.data["devices"][dev]["ops"]]
        split = scopes._attribute(events, trace._merge(runs))
        per_dev.append({g: split.get(g, 0.0) / len(runs) for g in GROUPS})
    if not per_dev:
        return None
    return {g: sum(d[g] for d in per_dev) / len(per_dev) for g in GROUPS}
