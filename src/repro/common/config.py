"""Configuration system.

One flat :class:`ModelConfig` covers every architecture family in the zoo
(dense / moe / ssm / hybrid / vlm / audio / dit-moe).  Arch config files in
``repro.configs`` instantiate it with the exact published hyper-parameters
(citations in each file) and also expose a reduced ``smoke()`` variant used
by the CPU tests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Hardware constants (TPU v5e target — used by the roofline, not the runtime)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class _HW:
    peak_flops_bf16: float = 197e12     # per chip
    hbm_bw: float = 819e9               # bytes/s per chip
    ici_bw: float = 50e9                # bytes/s per link
    hbm_bytes: float = 16e9             # per chip
    vmem_bytes: float = 128 * 1024 * 1024


HW = _HW()


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio | dit_moe
    num_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    num_heads: int = 0               # 0 for attention-free families
    num_kv_heads: int = 0
    head_dim: int = 0                # derived if 0: d_model // num_heads

    # --- attention variants -------------------------------------------------
    qk_norm: bool = False                    # qwen3
    attn_logit_softcap: Optional[float] = None   # gemma2: 50.0
    final_logit_softcap: Optional[float] = None  # gemma2: 30.0
    sliding_window: Optional[int] = None         # window size for local layers
    local_global_pattern: bool = False           # gemma2: alternate local/global
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    post_norm: bool = False          # gemma2 sandwich norms
    embed_scale: bool = False        # gemma2: x *= sqrt(d_model)
    act: str = "silu"                # silu | gelu

    # --- MoE ----------------------------------------------------------------
    num_experts: int = 0
    experts_per_token: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0                # expert hidden size (qwen3-moe: 768)
    shared_d_ff: Optional[int] = None  # the shared experts' fused FFN width
    #                                  (DiT-MoE: num_shared_experts x d_model);
    #                                  None: num_shared_experts x expert_d_ff
    capacity_factor: float = 1.25
    router_jitter: float = 0.0

    # --- SSM / hybrid (rwkv6 "Finch", mamba2 in zamba2) ----------------------
    ssm_state: int = 0               # mamba2 state size
    ssm_heads: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    hybrid_attn_every: int = 0       # zamba2: shared attn block every k mamba blocks

    # --- VLM / audio frontends (stubbed: precomputed embeddings) -------------
    num_image_tokens: int = 0        # llama-3.2-vision: patch embeddings per image
    cross_attn_every: int = 0        # cross-attn layer every k layers
    num_audio_frames: int = 0        # seamless: encoder frames
    encoder_layers: int = 0          # enc-dec: encoder depth (decoder = num_layers)

    # --- DiT-MoE (the paper's model) -----------------------------------------
    patch_tokens: int = 0            # sequence length of latent patches
    num_classes: int = 0             # class-conditional ImageNet
    in_channels: int = 0             # latent channels per patch

    # --- serving variants -----------------------------------------------------
    long_context_window: int = 0     # >0: sliding-window decode variant for long_500k

    # --- misc ------------------------------------------------------------------
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    source: str = ""                 # citation

    def __post_init__(self):
        if self.num_heads and not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    # ---- derived -------------------------------------------------------------
    @property
    def attention_free(self) -> bool:
        return self.family == "ssm" and self.num_heads == 0

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def shared_width(self) -> int:
        """Hidden width of the shared experts, fused into one gated FFN."""
        if self.shared_d_ff is not None:
            return self.shared_d_ff
        return self.expert_d_ff * self.num_shared_experts

    def param_count(self) -> int:
        """Analytic parameter count (used for 6ND model-FLOPs)."""
        d = self.d_model
        n_q = self.num_heads * self.head_dim
        n_kv = self.num_kv_heads * self.head_dim
        attn = d * n_q + 2 * d * n_kv + n_q * d if self.num_heads else 0
        if self.is_moe:
            ffn = 3 * d * self.expert_d_ff * self.num_experts
            if self.num_shared_experts:
                ffn += 3 * d * self.shared_width
            ffn += d * self.num_experts            # router
        else:
            ffn = 3 * d * self.d_ff
        if self.family == "ssm":                    # rwkv6-style blocks
            attn = 6 * d * d                        # r,k,v,g,o + decay projections
            ffn = 2 * d * self.d_ff + d * d
        if self.family == "hybrid":
            # mamba blocks have no MLP; the attention block (attn + MLP) is
            # weight-SHARED across all its insertion points (zamba2)
            inner = self.ssm_expand * d
            mamba = d * (2 * inner) + inner * d + inner * (2 * self.ssm_state)
            k = max(self.hybrid_attn_every, 1)
            n_mamba = self.num_layers - (self.num_layers // k
                                         if self.hybrid_attn_every else 0)
            shared_attn = 4 * d * d + 3 * d * self.d_ff
            return int(n_mamba * mamba + shared_attn + 2 * self.vocab_size * d)
        per_layer = attn + ffn
        total = self.num_layers * per_layer + 2 * self.vocab_size * d
        if self.encoder_layers:
            total += self.encoder_layers * per_layer
        return int(total)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed-active experts)."""
        if not self.is_moe:
            return self.param_count()
        d = self.d_model
        n_q = self.num_heads * self.head_dim
        n_kv = self.num_kv_heads * self.head_dim
        attn = d * n_q + 2 * d * n_kv + n_q * d if self.num_heads else 0
        ffn = 3 * d * self.expert_d_ff * self.experts_per_token
        if self.num_shared_experts:
            ffn += 3 * d * self.shared_width
        per_layer = attn + ffn + d * self.num_experts
        return int(self.num_layers * per_layer + 2 * self.vocab_size * d)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k":    ShapeConfig("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768,   32, "prefill"),
    "decode_32k":  ShapeConfig("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapeConfig("long_500k",  524_288,    1, "decode"),
}
