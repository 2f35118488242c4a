"""Least HBM bytes of one decode tick's latent attention and MoE FFN,
counted from the configuration's published sizes (HF keys), in bfloat16:
the yardstick of ``serve.mla_roofline`` and ``serve.moe_roofline``.  It
counts the same bytes whatever kernel or layout the program uses.
"""
from __future__ import annotations

BF16 = 2


def mla_weight_bytes(cfg: dict) -> int:
    """One layer's MLA weights: W_q, W_kv_a, the c_kv norm, W_kv_b, W_o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r = cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    return BF16 * (d * h * (dn + dr) + d * (r + dr) + r
                   + r * h * (dn + dv) + h * dv * d)


def latent_row_bytes(cfg: dict) -> int:
    """One cached position of one layer: c_kv and the rotated k_pe."""
    return BF16 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def mla_least_bytes(cfg: dict, attended_rows: float) -> float:
    """Every layer reads its weights once and the latent rows the lanes
    actually attend (``attended_rows``: positions summed over lanes)."""
    return cfg["num_hidden_layers"] * (attended_rows * latent_row_bytes(cfg)
                                       + mla_weight_bytes(cfg))


def expert_bytes(cfg: dict) -> int:
    """One routed expert's SwiGLU (three matrices)."""
    return BF16 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_fixed_bytes(cfg: dict) -> int:
    """One MoE layer's shared experts (one SwiGLU of n_shared x the expert
    width), router and selection bias."""
    d, e = cfg["hidden_size"], cfg["n_routed_experts"]
    shared = 3 * d * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return BF16 * (shared + d * e + e)


def moe_least_bytes(cfg: dict, experts_hit: float) -> float:
    """The experts that received a token (``experts_hit``, summed over the
    MoE layers) and every MoE layer's fixed weights."""
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return experts_hit * expert_bytes(cfg) + moe_layers * moe_fixed_bytes(cfg)
