"""Work the algorithms need, counted from shapes: the yardstick for every
roofline and utilization share.  It counts the same work whatever rung,
kernel or layout the program uses.
"""
from __future__ import annotations


def spmv_least_bytes(rows: int, r_nz: int, *, val_bytes: int = 4,
                     idx_bytes: int = 4) -> int:
    """Least HBM bytes of y = D x + A x over ``rows`` rows in modified
    EllPack: every value and column index of A read once, D, x and y once
    each (x read once per owned row: the remote values a chip receives are
    counted in the exchange, not here)."""
    return rows * (r_nz * (val_bytes + idx_bytes) + 3 * val_bytes)


def mixtral_layer_params(cfg: dict) -> int:
    """Parameters one token touches in one Mixtral decoder layer: the four
    attention projections, the router, and ``num_experts_per_tok`` SwiGLU
    experts (three matrices each).  ``cfg`` holds the HF config keys."""
    d = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // heads
    f = cfg["intermediate_size"]
    attn = d * heads * hd + 2 * d * kv * hd + heads * hd * d
    router = d * cfg["num_local_experts"]
    experts = cfg["num_experts_per_tok"] * 3 * d * f
    return attn + router + experts


def mixtral_flops(cfg: dict, *, tokens: int, attended: int,
                  logit_rows: int) -> float:
    """Model FLOPs of a Mixtral forward over ``tokens`` positions.

    2 x active parameters per position in the decoder layers, 2 x d x vocab
    per row of logits the program computes (decode positions and the last
    position of each prefill chunk), and causal attention over the context
    each position actually attends: 4 x heads x head_dim per attended key
    per layer (scores and the weighted sum).  ``attended`` is the sum over
    positions of keys attended (position + 1)."""
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // heads
    layers = cfg["num_hidden_layers"]
    return (2.0 * tokens * layers * mixtral_layer_params(cfg)
            + 2.0 * logit_rows * d * cfg["vocab_size"]
            + 4.0 * layers * heads * hd * attended)
