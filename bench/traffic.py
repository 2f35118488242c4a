"""The one generator of serving traffic: an open loop read from a traffic
file's parameters.

Every seed gets the same multiset of sizes and arrival gaps, in another
order: prompt and output lengths are the log-normal's quantiles at
(i + 1/2) / N, gaps the exponential's, scaled so that exactly N requests
fall due in the window.  The seed permutes each list and draws the token
ids, so seeds change which request comes when, not how much work a window
holds.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

from bench.common import rng


@dataclasses.dataclass(frozen=True)
class Arrival:
    id: int
    due: float                # seconds after the window opens
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int


def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])
    v = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    mult = int(spec.get("multiple", 1))
    v = np.ceil(v / mult) * mult
    return np.clip(v, spec["min"], spec["max"]).astype(np.int64)


def open_loop(traffic: dict, seed: int, seconds: float, vocab: int,
              rate: float | None = None) -> list[Arrival]:
    """Arrivals of one window.  ``rate`` (requests/s) defaults to the
    traffic file's; the knee sweep passes others."""
    rate = float(traffic["rate_per_s"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / gaps.sum()
    prompts = _lognormal_quantiles(traffic["prompt_tokens"], n)
    outputs = _lognormal_quantiles(traffic["output_tokens"], n)
    r = rng(seed, "traffic")
    gaps, prompts, outputs = (r.permutation(a) for a in
                              (gaps, prompts, outputs))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    tok = rng(seed, "tokens")
    return [Arrival(id=i, due=float(due[i]),
                    prompt=tok.integers(0, vocab, int(prompts[i]),
                                        dtype=np.int32),
                    max_new_tokens=int(outputs[i]))
            for i in range(n)]


def check_traffic(traffic: dict, cache_len: int, chunk: int) -> None:
    """Refuse a mix the engine cannot serve as configured: every prompt a
    whole number of prefill chunks (so the window compiles nothing new) and
    prompt plus output within the cache."""
    p, o = traffic["prompt_tokens"], traffic["output_tokens"]
    if int(p.get("multiple", 1)) % chunk or p["min"] % chunk:
        raise ValueError(f"prompt lengths must be multiples of the "
                         f"{chunk}-token prefill chunk")
    if p["max"] + o["max"] > cache_len:
        raise ValueError(f"prompt {p['max']} + output {o['max']} exceeds "
                         f"the cache of {cache_len}")
    if not math.isfinite(float(traffic["rate_per_s"])):
        raise ValueError("rate_per_s must be finite")
