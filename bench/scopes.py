"""Trace reduction by the program's own names: the device time of a traced
window split by the ``jax.named_scope`` each op carries, and the idle gaps
labelled by the program's host spans.

The program puts its exchange ops under ``comm.pack``, ``comm.exchange`` and
``comm.unpack`` (``repro.comm.strategies``) and the SpMV product under
``spmv.local`` (``repro.core.spmv``, with ``own`` and ``foreign`` inside it
where the overlap rung splits the product); the compiled program keeps the
path as each instruction's ``op_name``.  Its host spans
(``repro.comm.telemetry.span``) lie in the trace as ``repro.<name>``.

``load_xplane`` extends ``bench.trace.load_xplane`` by the ``repro.*`` host
events, kept under ``program_spans``.  A TPU trace's op events name their
HLO instruction but carry no op_name (a v5e's carry only their offset and
duration), so ``join_hlo`` gives each device the ``op_names`` of its ops
(aligned with ``ops``; "" for an op of another module) from the compiled
module's HLO text.  ``ScopedSummary`` adds to ``bench.trace.TraceSummary``
(whose numbers it leaves as they are):

* ``scope_split(module)``: device seconds per execution of a module's
  non-collective ops by scope.  Where ops nest or overlap, each instant goes
  to the op that started last (the innermost), so the scopes partition the
  time ``TraceSummary.module_compute_s`` reads;
* ``scope_s(scope, module)``: one scope of that split ("unscoped": ops under
  none), as (seconds, executions) averaged over chips;
* ``idle_gaps_program``: the idle gaps labelled by the innermost program
  span the host was in;
* ``breakdown``: the parent's, plus ``device_scopes`` (time in the window by
  scope) and ``idle_gaps_program``.
"""
from __future__ import annotations

import collections
import re

from bench import trace

SCOPES = ("comm.pack", "comm.exchange", "comm.unpack", "spmv.local")
SUBSCOPES = ("own", "foreign")
UNSCOPED = "unscoped"
PROGRAM_PREFIX = "repro."
_HLO_LINE = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` on an op_name path ("unscoped" if none),
    with the overlap rung's ``own`` / ``foreign`` part of ``spmv.local``."""
    parts = op_name.split("/") if op_name else []
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in SCOPES:
            if (parts[i] == "spmv.local" and i + 1 < len(parts)
                    and parts[i + 1] in SUBSCOPES):
                return f"spmv.local/{parts[i + 1]}"
            return parts[i]
    return UNSCOPED


def instruction(event_name: str) -> str:
    """The HLO instruction name of a device op event (``%fusion.2 = ...``
    gives ``fusion.2``)."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def load_xplane(path: str) -> dict:
    """``bench.trace.load_xplane``'s structure, plus the program's host
    spans under ``program_spans``."""
    from jax.profiler import ProfileData

    data = trace.load_xplane(path)
    data["program_spans"] = [
        (e.name[len(PROGRAM_PREFIX):], e.start_ns, e.duration_ns)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith(PROGRAM_PREFIX)]
    return data


def hlo_op_names(hlo_text: str) -> dict[str, str]:
    """{instruction name: op_name} of a compiled module's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def join_hlo(data: dict, hlo_text: str, module: str) -> int:
    """Name the ops that ran inside executions of the modules whose name
    holds ``module`` from that module's HLO text (by instruction name), as
    each device's ``op_names``; returns how many were named."""
    names = hlo_op_names(hlo_text)
    named = 0
    for dev in data["devices"].values():
        runs = [(float(s), float(s) + float(d))
                for n, s, d in dev["modules"] if module in n]
        op_names = dev.setdefault("op_names", [""] * len(dev["ops"]))
        for i, (name, s, d) in enumerate(dev["ops"]):
            mid = float(s) + 0.5 * float(d)
            if any(a <= mid < b for a, b in runs):
                op_names[i] = names.get(instruction(name), "")
                named += bool(op_names[i])
    return named


def _attribute(events, clip) -> dict[str, float]:
    """Seconds by scope of the (start, end, scope) ``events`` inside the
    sorted disjoint ``clip`` intervals: each instant goes to the covering
    event that started last."""
    bounds = sorted({t for a, b, _ in events for t in (a, b)}
                    | {t for a, b in clip for t in (a, b)})
    starts = sorted(events, key=lambda e: e[0])
    out: dict[str, float] = collections.defaultdict(float)
    active: list[tuple[float, float, str]] = []
    k = j = 0
    for lo, hi in zip(bounds, bounds[1:]):
        while k < len(starts) and starts[k][0] <= lo:
            active.append(starts[k])
            k += 1
        active = [e for e in active if e[1] > lo]
        while j < len(clip) and clip[j][1] <= lo:
            j += 1
        if not active or j == len(clip) or clip[j][0] > lo:
            continue
        out[max(active, key=lambda e: e[0])[2]] += (hi - lo) * 1e-9
    return out


class ScopedSummary(trace.TraceSummary):
    """``TraceSummary`` with the program's scopes and spans."""

    def __init__(self, data: dict, window_span: str = "window"):
        super().__init__(data, window_span)
        self.program_spans = [tuple(s) for s in data.get("program_spans", ())]

    def _scoped_ops(self, dev, window: bool = True):
        d = self.data["devices"][dev]
        names = d.get("op_names") or [""] * len(d["ops"])
        for (name, s, dur), op_name in zip(d["ops"], names):
            if trace.is_collective(name):
                continue
            a, b = float(s), float(s) + float(dur)
            if window:
                a, b = max(a, self.w0), min(b, self.w1)
            if b > a:
                yield a, b, scope_of(op_name)

    def has_scopes(self) -> bool:
        """Whether any device op carries an op_name."""
        return any(any(d.get("op_names") or ())
                   for d in self.data["devices"].values())

    def scope_split(self, module: str) -> dict[str, float]:
        """{scope: device seconds per execution} of the non-collective ops
        inside the executions of the modules whose name holds ``module``,
        averaged over the chips that ran it."""
        per_dev = []
        for dev in self.devices:
            runs = self._executions(dev, module)
            if runs:
                split = _attribute(list(self._scoped_ops(dev, False)),
                                   trace._merge(runs))
                per_dev.append({k: v / len(runs) for k, v in split.items()})
        if not per_dev:
            return {}
        keys = sorted({k for d in per_dev for k in d})
        return {k: sum(d.get(k, 0.0) for d in per_dev) / len(per_dev)
                for k in keys}

    def scope_s(self, scope: str, module: str) -> tuple[float, int]:
        """(device seconds, executions) of the non-collective ops under
        ``scope`` (its ``own`` / ``foreign`` parts included; "unscoped":
        under none) inside the module's executions, averaged over chips."""
        def seconds(dev, runs):
            split = _attribute(list(self._scoped_ops(dev, False)),
                               trace._merge(runs))
            return sum(v for k, v in split.items()
                       if k == scope or k.startswith(scope + "/"))

        return self._per_device(module, seconds)

    def program_label(self, t: float) -> str:
        """The innermost program span (latest start) covering ``t``."""
        best, best_start = "none", None
        for name, s, d in self.program_spans:
            s, d = float(s), float(d)
            if s <= t < s + d and (best_start is None or s > best_start):
                best, best_start = name, s
        return best

    def idle_gaps_program(self, dev=None):
        """(label, seconds, start) of each idle gap, labelled by the
        program span the host was in when it began."""
        return [(self.program_label(a), s, a)
                for _, s, a in self.idle_gaps(dev)]

    def device_scopes(self) -> dict[str, float]:
        """Device seconds in the window by scope of the non-collective ops,
        averaged over devices."""
        acc: dict[str, float] = collections.defaultdict(float)
        for dev in self.devices:
            for k, v in _attribute(list(self._scoped_ops(dev)),
                                   [(self.w0, self.w1)]).items():
                acc[k] += v
        return {k: v / len(self.devices) for k, v in acc.items()}

    def breakdown(self, top: int = 10) -> dict:
        out = super().breakdown(top)
        out["device_scopes"] = [[k, v] for k, v in sorted(
            self.device_scopes().items(), key=lambda kv: -kv[1])]
        gaps = sorted(self.idle_gaps_program(), key=lambda g: -g[1])[:top]
        out["idle_gaps_program"] = [[label, s] for label, s, _ in gaps]
        return out
