"""Sharding rules: param/activation/cache PartitionSpecs for every family.

Principles (DESIGN.md §6):
  * train: FSDP over the data axes (("pod","data") when multi-pod) + tensor
    parallel over "model"; every 2D weight is sharded on both of its dims.
  * serve: params sharded over "model"; additionally over the data axes
    (ZeRO-inference) when the per-device residency would not fit otherwise.
  * decode KV caches: batch over data axes, sequence over "model"
    (flash-decoding layout — the only layout divisible for GQA kv_heads <
    model-axis size).
  * every spec passes through ``fit_spec`` which *drops* axes that do not
    divide the dimension — replication instead of a compile error, and the
    drop is logged so the roofline table can attribute the cost.

The rules are name-pattern based on the param-tree path, with any number of
stacked leading scan dims (layers / vlm groups) automatically skipped.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingRules", "fit_spec", "param_shardings", "batch_sharding",
           "make_constrain"]


def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    return int(np.prod([mesh.shape[a] for a in axes]))


def fit_spec(mesh: Mesh, shape, spec: P) -> P:
    """Drop spec axes that don't divide their dim (replicate instead)."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, axes in zip(shape, parts):
        if axes is not None and dim % _axes_size(mesh, axes) != 0:
            axes = None
        out.append(axes)
    return P(*out)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    mesh: Mesh
    fsdp_axes: Any            # e.g. ("pod","data") or "data" or None
    tp_axis: str = "model"
    ep_mode: bool = False     # expert-parallel MoE (experts on tp axis)
    seq_parallel: bool = False  # shard residual-stream seq dim over tp_axis
    # (Megatron-SP: activations at rest are 1/tp the size; XLA swaps the
    # block all-reduce for all-gather + reduce-scatter at equal bytes)
    opt_fsdp_axes: Any = None   # optional distinct FSDP axes for optimizer
    # state (master/m/v): e.g. params gather pod-locally over "data" while
    # the 4x-larger optimizer state spreads over ("pod","data") — per-layer
    # weight gathers then never cross the DCI (hierarchical ZeRO)
    ep_axes: Any = None         # expert-parallel axes (default: tp_axis);
    # e.g. ("pod","model") spreads 128 experts over 32 shards, halving the
    # per-device expert-weight gather volume

    @property
    def dp_axes(self):
        """axes that shard the batch — always the full data parallelism
        (pod+data when multi-pod), independent of how far the *weights*
        spread (fsdp_axes)."""
        return ("pod", "data") if "pod" in self.mesh.shape else "data"

    # ---- rule table: pattern over the LAST dims of the param ----
    def _rules(self):
        fs, tp = self.fsdp_axes, self.tp_axis
        ep = self.ep_axes if self.ep_axes is not None else tp
        # fsdp axes used on expert weights must not collide with ep axes
        ep_set = {ep} if isinstance(ep, str) else set(ep)
        fs_moe = fs
        if fs is not None and not isinstance(fs, str):
            fs_moe = tuple(a for a in fs if a not in ep_set) or None
        elif isinstance(fs, str) and fs in ep_set:
            fs_moe = None
        moe_w1 = (P(ep, fs_moe, None) if self.ep_mode else P(None, fs, tp))
        moe_w2 = (P(ep, None, fs_moe) if self.ep_mode else P(None, tp, fs))
        return [
            (r"embed.*\['w'\]", P(tp, fs)),           # (V, D) vocab-sharded
            (r"lm_head.*\['w'\]", P(fs, tp)),         # (D, V)
            (r"\['moe'\].*\['w1'\]", moe_w1),         # (E, D, F)
            (r"\['moe'\].*\['w3'\]", moe_w1),
            (r"\['moe'\].*\['w2'\]", moe_w2),         # (E, F, D)
            (r"\['router'\].*\['w'\]", P(fs, None)),  # (D, E)
            (r"\['(wq|wk|wv)'\].*\['w'\]", P(fs, tp)),
            (r"\['(wq|wk|wv)'\].*\['b'\]", P(tp)),
            (r"\['wo'\]\['w'\]", P(tp, fs)),
            (r"\['w1'\]\['w'\]", P(fs, tp)),          # mlp (D, F)
            (r"\['w3'\]\['w'\]", P(fs, tp)),
            (r"\['w2'\]\['w'\]", P(tp, fs)),          # mlp (F, D)
            (r"\['in_proj'\]\['w'\]", P(fs, tp)),     # ssm (D, 2di)
            (r"\['conv_w'\]", P(None, tp)),           # (K, di)
            (r"\['conv_b'\]", P(tp)),
            (r"\['x_proj'\]\['w'\]", P(tp, None)),    # (di, dr+2st)
            (r"\['dt_proj'\]\['w'\]", P(None, tp)),   # (dr, di)
            (r"\['dt_proj'\]\['b'\]", P(tp)),
            (r"\['a_log'\]", P(tp, None)),            # (di, st)
            (r"\['d_skip'\]", P(tp)),
            (r"\['out_proj'\]\['w'\]", P(tp, fs)),    # (di, D)
        ]

    def spec_for(self, path_str: str, shape) -> P:
        for pat, rule in self._rules():
            if re.search(pat, path_str):
                lead = len(shape) - len(rule)
                spec = P(*([None] * lead), *rule)
                return fit_spec(self.mesh, shape, spec)
        return P()  # norms, biases, scalars: replicate


def param_shardings(rules: ShardingRules, params_shapes):
    """Pytree of NamedSharding matching an eval_shape'd param tree.

    When ``opt_fsdp_axes`` is set, leaves under an optimizer-state subtree
    (path contains 'master'/'m'/'v') use those axes instead (hierarchical
    ZeRO: optimizer spreads wider than the compute copy)."""
    opt_rules = (dataclasses.replace(rules, fsdp_axes=rules.opt_fsdp_axes)
                 if rules.opt_fsdp_axes is not None else None)

    def one(path, leaf):
        pstr = "".join(str(jax.tree_util.keystr((k,))) for k in path)
        r = rules
        if opt_rules is not None and re.match(
                r"^\['(master|m|v)'\]", pstr):
            r = opt_rules
        spec = r.spec_for(pstr, leaf.shape)
        return NamedSharding(rules.mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_shapes)


def batch_sharding(rules: ShardingRules, shape):
    spec = fit_spec(rules.mesh, shape, P(rules.dp_axes))
    return NamedSharding(rules.mesh, spec)


def make_constrain(rules: ShardingRules):
    """RunCtx constraint callback: tag -> with_sharding_constraint."""
    mesh, dp, tp = rules.mesh, rules.dp_axes, rules.tp_axis

    def constrain(x, tag):
        nd = x.ndim
        if tag == "act":          # (B, S, D)
            if rules.seq_parallel and nd >= 3:
                spec = P(dp, tp, *([None] * (nd - 2)))
            else:
                spec = P(dp, *([None] * (nd - 1)))
        elif tag == "logits":     # (B, S, V)
            spec = P(dp, *([None] * (nd - 2)), tp)
        elif tag == "expert":     # moe buffer (G, E, C, D)
            if rules.ep_mode:
                ep = rules.ep_axes if rules.ep_axes is not None else tp
                ep_set = {ep} if isinstance(ep, str) else set(ep)
                g_axes = tuple(a for a in
                               ((dp,) if isinstance(dp, str) else dp)
                               if a not in ep_set) or None
                spec = P(g_axes, ep, *([None] * (nd - 2)))
            else:
                spec = P(dp, *([None] * (nd - 1)))
        elif tag == "dispatch":   # moe buffer back on dp
            spec = P(dp, *([None] * (nd - 1)))
        else:
            return x
        spec = fit_spec(mesh, x.shape, spec)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return constrain
