"""Architecture configuration schema for the model zoo.

One frozen dataclass describes every assigned architecture; family-specific
fields are zero/None when unused.  ``reduced()`` produces the small smoke-test
variant of the same family (assignment: smoke tests instantiate a reduced
config; full configs are exercised only via the dry-run).
"""
from __future__ import annotations

import dataclasses

__all__ = ["ArchConfig"]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str            # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int         # query heads; 0 for attention-free (ssm)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0      # 0 -> d_model // num_heads

    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    moe_dispatch: str = "auto"   # auto | tp_local | ep_a2a (condensed)
    capacity_factor: float = 1.25
    dense_residual: bool = False  # arctic: dense FFN in parallel with MoE;
    residual_d_ff: int = 0        # deepseek-v3: its shared experts as one
    # router: "softmax" top-k, or "sigmoid" scores whose top-k is chosen on
    # score + a learned bias (deepseek-v3 noaux_tc); the chosen scores are
    # renormalised over the k, then times ``router_scale``
    router_score: str = "softmax"
    router_scale: float = 1.0
    first_dense_layers: int = 0   # deepseek-v3: leading dense layers ...
    dense_d_ff: int = 0           # ... of this FFN width

    # --- multi-head latent attention (deepseek-v3); kv_lora_rank 0 = off ---
    kv_lora_rank: int = 0         # the cached latent c_kv
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0     # the cached, rotated key part shared by heads
    v_head_dim: int = 0

    # --- attention flavor ---
    qkv_bias: bool = False        # qwen2.5
    swa_window: int = 0           # 0 = full attention; mixtral/hymba use SWA
    rope_theta: float = 1e4

    # --- SSM (mamba-1) ---
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0          # 0 -> d_model // 16

    # --- encoder-decoder (whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 0          # precomputed frame count (frontend stub)

    # --- VLM ---
    cross_attn_period: int = 0    # every k-th layer cross-attends to images
    num_image_tokens: int = 0     # precomputed patch embeds (frontend stub)

    act: str = "swiglu"           # swiglu | gelu
    norm: str = "rmsnorm"         # rmsnorm | layernorm
    tie_embeddings: bool = False
    dtype: str = "bfloat16"

    # --- embedding gather strategy (the paper's ladder; DESIGN.md §4) ---
    embed_gather: str = "onehot_psum"   # replicate | onehot_psum

    def __post_init__(self):
        if self.num_heads and not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.ssm_state and not self.ssm_dt_rank:
            object.__setattr__(self, "ssm_dt_rank", max(1, self.d_model // 16))

    # ---- derived ----
    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_ssm_only(self) -> bool:
        return self.family == "ssm"

    @property
    def is_hybrid(self) -> bool:
        return self.family == "hybrid"

    @property
    def is_encdec(self) -> bool:
        return self.family == "encdec"

    @property
    def is_vlm(self) -> bool:
        return self.family == "vlm"

    def param_count(self) -> tuple[int, int]:
        """(total_params, active_params). Analytic; cross-checked against
        eval_shape in tests."""
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.head_dim
        n_attn = 0
        if self.is_mla:
            h, r, dr = self.num_heads, self.kv_lora_rank, self.qk_rope_head_dim
            n_attn = (d * h * (self.qk_nope_head_dim + dr) + d * (r + dr) + r
                      + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                      + h * self.v_head_dim * d)
        elif self.num_heads:
            n_attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd \
                + self.num_heads * hd * d
            if self.qkv_bias:
                n_attn += (self.num_heads + 2 * self.num_kv_heads) * hd
        n_mlp_dense = (3 if self.act == "swiglu" else 2) * d * f
        n_ssm = 0
        if self.ssm_state:
            di, st, dr = self.d_inner, self.ssm_state, self.ssm_dt_rank
            n_ssm = (d * 2 * di + di * self.ssm_conv + di
                     + di * (dr + 2 * st) + dr * di + di
                     + di * st + di + di * d)
        n_norms = 2 * d

        per_layer_total = n_norms
        per_layer_active = n_norms
        if self.is_moe:
            n_expert = (3 if self.act == "swiglu" else 2) * d * f
            n_router = d * self.num_experts
            if self.router_score == "sigmoid":
                n_router += self.num_experts      # the selection bias
            per_layer_total += n_attn + n_router + self.num_experts * n_expert
            per_layer_active += n_attn + n_router \
                + self.experts_per_token * n_expert
            if self.dense_residual:
                rff = (3 if self.act == "swiglu" else 2) * d * self.residual_d_ff
                per_layer_total += rff
                per_layer_active += rff
        elif self.is_ssm_only:
            per_layer_total += n_ssm
            per_layer_active += n_ssm
        elif self.is_hybrid:
            per_layer_total += n_attn + n_ssm + n_mlp_dense
            per_layer_active += n_attn + n_ssm + n_mlp_dense
        else:
            per_layer_total += n_attn + n_mlp_dense
            per_layer_active += n_attn + n_mlp_dense

        lead = self.first_dense_layers
        n_lead = n_norms + n_attn + (3 if self.act == "swiglu" else 2) \
            * d * self.dense_d_ff
        total = (self.num_layers - lead) * per_layer_total + lead * n_lead
        active = (self.num_layers - lead) * per_layer_active + lead * n_lead

        # VLM: every period-th layer is a cross-attn block with the same
        # parameter volume as a dense block (attn shapes match) — no extra.

        if self.is_encdec:
            enc_layer = n_attn + n_mlp_dense + n_norms
            total += self.encoder_layers * enc_layer
            active += self.encoder_layers * enc_layer
            # decoder cross-attn per layer
            total += self.num_layers * (n_attn + d)
            active += self.num_layers * (n_attn + d)

        emb = v * d * (1 if self.tie_embeddings else 2)
        total += emb + d  # final norm
        active += emb + d
        return int(total), int(active)
