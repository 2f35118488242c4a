"""Reduction from a profiler trace to the numbers the per-layer readers use.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain structure: per device, the events of its "XLA Ops" and "XLA Modules"
lines, and the host spans the harness wrote (``bench:<name>``), all on the
trace's one clock in nanoseconds.  ``TraceSummary`` computes from that
structure:

* busy time: the union of the intervals in which an operation ran on a
  device, clipped to the traced window, and the idle share 1 - busy/window;
* device time and count of executions per XLA module (found by a substring
  of its name, such as the jitted function's), and the part of that time in
  which an operation other than a collective ran;
* collective time, and its exposed part: the part of the collectives'
  intervals in which no other operation ran on that device (an op is a
  collective by its HLO opcode);
* the ``breakdown`` of the result line: the device operations that took most
  time, and the longest idle gaps labelled by the harness span the host was
  in when each began.

The same structure is written as JSON by ``dump`` so that a small trace
recorded on the chip can be kept with the tests.
"""
from __future__ import annotations

import collections
import functools
import glob
import gzip
import json
import os
import re

COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "send", "recv")
SPAN_PREFIX = "bench:"
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9_\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


@functools.lru_cache(maxsize=None)
def op_info(name: str) -> tuple[str, str]:
    """(short name, opcode) of a TPU "XLA Ops" event, whose name is the HLO
    instruction's text ``%name = shape opcode(operands), ...``.  The opcode
    decides what an op is: XLA also names reshapes after the collective
    they came from, and operand lists name other ops.  Layouts are dropped
    from the short name."""
    if " = " not in name:
        return name, name.split(".")[0]
    head, rest = name.split(" = ", 1)
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    return (head + " = " + _LAYOUT.sub("", rest))[:160], opcode


def is_collective(name: str) -> bool:
    opcode = op_info(name)[1]
    return any(opcode == c or opcode.startswith(c + "-")
               for c in COLLECTIVES)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """Device op/module events and harness spans of one xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, dict] = {}
    spans = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:") and "CPU" not in name:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.name, e.start_ns, e.duration_ns)
                               for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend((e.name, e.start_ns, e.duration_ns)
                                   for e in line.events)
            if ops or modules:
                devices[name] = {"ops": ops, "modules": modules}
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):], e.start_ns,
                                      e.duration_ns))
    return {"devices": devices, "spans": spans}


def dump(data: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(data, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _merge(intervals):
    """Union of (start, end) intervals as a sorted disjoint list."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _length(merged) -> float:
    return float(sum(b - a for a, b in merged))


def _subtract(intervals, merged_cover) -> float:
    """Total length of ``intervals`` (merged first) not covered by
    ``merged_cover`` (sorted, disjoint)."""
    total = 0.0
    j = 0
    for a, b in _merge(intervals):
        while j < len(merged_cover) and merged_cover[j][1] <= a:
            j += 1
        cur = a
        k = j
        while k < len(merged_cover) and merged_cover[k][0] < b:
            ca, cb = merged_cover[k]
            if ca > cur:
                total += ca - cur
            cur = max(cur, cb)
            if cur >= b:
                break
            k += 1
        if cur < b:
            total += b - cur
    return total


class TraceSummary:
    """The numbers a traced window gives, per device and averaged."""

    def __init__(self, data: dict, window_span: str = "window"):
        self.data = data
        self.spans = [tuple(s) for s in data["spans"]]
        win = [s for s in self.spans if s[0] == window_span]
        if not win:
            raise ValueError(f"the trace holds no {SPAN_PREFIX}{window_span} "
                             "span")
        _, w0, dur = win[0]
        self.w0, self.w1 = float(w0), float(w0) + float(dur)
        self.devices = sorted(data["devices"])
        if not self.devices:
            raise ValueError("the trace holds no device events")

    # ---- helpers ----
    def _ops(self, dev):
        for name, s, d in self.data["devices"][dev]["ops"]:
            a, b = max(float(s), self.w0), min(float(s) + float(d), self.w1)
            if b > a:
                yield name, a, b

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    def busy_s(self, dev) -> float:
        return _length(_merge((a, b) for _, a, b in self._ops(dev))) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s() / self.window_s

    def op_seconds(self) -> dict[str, float]:
        """Device seconds per op name in the window, averaged over
        devices."""
        acc: dict[str, float] = collections.defaultdict(float)
        for dev in self.devices:
            for name, a, b in self._ops(dev):
                acc[op_info(name)[0]] += (b - a) * 1e-9
        return {k: v / len(self.devices) for k, v in acc.items()}

    def _executions(self, dev, substring: str) -> list[tuple[float, float]]:
        """(start, end) of each execution of the XLA modules whose name
        holds ``substring`` and whose midpoint lies in the window.  (The
        device's clock in a trace may lead the host's by a millisecond or
        so, so an execution the window started can appear to begin just
        before it.)"""
        out = []
        for name, s, d in self.data["devices"][dev]["modules"]:
            a, b = float(s), float(s) + float(d)
            if substring in name and self.w0 <= 0.5 * (a + b) < self.w1:
                out.append((a, b))
        return out

    def _per_device(self, substring: str, seconds) -> tuple[float, int]:
        """(``seconds(dev, executions)``, executions) averaged over the
        devices that ran any execution of the modules."""
        per_dev = []
        for dev in self.devices:
            runs = self._executions(dev, substring)
            if runs:
                per_dev.append((seconds(dev, runs), len(runs)))
        if not per_dev:
            return 0.0, 0
        return (sum(s for s, _ in per_dev) / len(per_dev),
                round(sum(c for _, c in per_dev) / len(per_dev)))

    def module_time(self, substring: str) -> tuple[float, int]:
        """(device seconds, executions) of the XLA modules whose name holds
        ``substring`` (a substring such as the jitted function's name)."""
        return self._per_device(substring, lambda dev, runs: sum(
            b - a for a, b in runs) * 1e-9)

    def module_compute_s(self, substring: str) -> tuple[float, int]:
        """(device seconds, executions) in which an operation other than a
        collective ran inside the executions of those modules: their time
        less the collectives and the gaps within them."""
        def seconds(dev, runs):
            ops = _merge((float(s), float(s) + float(d)) for name, s, d in
                         self.data["devices"][dev]["ops"]
                         if not is_collective(name))
            return (_length(ops) - _subtract(ops, _merge(runs))) * 1e-9

        return self._per_device(substring, seconds)

    def collective_s(self) -> tuple[float, float]:
        """(collective seconds, exposed collective seconds) in the window,
        averaged over devices.  Exposed: no non-collective op ran then."""
        tot = exp = 0.0
        for dev in self.devices:
            coll, other = [], []
            for name, a, b in self._ops(dev):
                (coll if is_collective(name) else other).append((a, b))
            tot += _length(_merge(coll))
            exp += _subtract(coll, _merge(other))
        n = len(self.devices)
        return tot * 1e-9 / n, exp * 1e-9 / n

    def host_label(self, t: float) -> str:
        """The innermost harness span (latest start) covering ``t``."""
        best, best_start = "none", None
        for name, s, d in self.spans:
            s, d = float(s), float(d)
            if s <= t < s + d and name != "window" and (
                    best_start is None or s > best_start):
                best, best_start = name, s
        return best

    def idle_gaps(self, dev=None):
        """(label, seconds, start) of every idle gap of ``dev`` in the
        window, labelled by the host span at the gap's start."""
        dev = dev or self.devices[0]
        busy = _merge((a, b) for _, a, b in self._ops(dev))
        gaps, cur = [], self.w0
        for a, b in busy:
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if cur < self.w1:
            gaps.append((cur, self.w1))
        return [(self.host_label(a), (b - a) * 1e-9, a) for a, b in gaps]

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[label, s] for label, s, _ in gaps]}
