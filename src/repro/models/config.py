"""Unified model configuration covering all assigned architecture families."""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN ``rope_scaling`` (DeepSeek-V2's keys): RoPE frequencies
    interpolated by ``factor`` beyond a ramp between the dimensions that
    turn ``beta_fast`` and ``beta_slow`` times over the original context,
    and attention logits scaled by ``softmax_scale``."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def get_mscale(scale: float, m: float) -> float:
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    @property
    def cos_sin_scale(self) -> float:
        """Factor on the cos/sin tables."""
        return self.get_mscale(self.factor, self.mscale) / \
            self.get_mscale(self.factor, self.mscale_all_dim)

    @property
    def softmax_scale(self) -> float:
        """Factor on ``1/sqrt(head dim)``: ``mscale(mscale_all_dim)^2``."""
        if not self.mscale_all_dim:
            return 1.0
        return self.get_mscale(self.factor, self.mscale_all_dim) ** 2


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | hybrid | xlstm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // n_heads

    # attention
    qkv_bias: bool = False
    rope_theta: float = 1e4
    rope_scaling: Optional[YaRN] = None
    window: int | None = None      # sliding-window attention
    learned_pos: bool = False      # learned absolute positions (whisper)

    # MLA (DeepSeek)
    mla: bool = False
    kv_lora_rank: int = 0
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128

    # MoE
    moe: bool = False
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    first_dense_layers: int = 0
    dense_d_ff: int = 0            # d_ff of the first dense layers
    norm_topk_prob: bool = True    # renormalize the top-k router weights
    # the routed experts this chip holds: ``experts_held`` of them from
    # ``expert_first`` (0 -> all ``n_experts``); the router spans all
    expert_first: int = 0
    experts_held: int = 0

    # SSM (Mamba2 / hybrid)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 256
    attn_every: int = 0            # hybrid: shared attention block period

    # xLSTM
    slstm_layers: tuple = ()       # layer indices running sLSTM (others mLSTM)
    proj_factor_mlstm: float = 2.0
    proj_factor_slstm: float = 4.0 / 3.0

    # encoder-decoder (whisper)
    encdec: bool = False
    n_enc_layers: int = 0
    enc_seq: int = 1500

    # VLM (pixtral)
    vlm: bool = False
    n_img_tokens: int = 1024

    act: str = "silu"
    norm_eps: float = 1e-5

    # the paper's technique as a framework feature: quantized near-memory
    # execution of projections (none | w8 | w8a8)
    nmc_mode: str = "none"
    # beyond-paper extension of the same idea to decode state: int8 KV cache
    # with per-token-per-head scales (bf16 | int8)
    kv_cache_dtype: str = "bf16"

    dtype: Any = jnp.bfloat16

    # distribution / training knobs
    remat: bool = True
    remat_policy: str = "full"   # full | dots (save matmul outputs — trades
                                 # recompute flops/traffic for residency)
    scan_layers: bool = True
    seq_parallel: bool = False   # Megatron-SP residual stream: x sharded on
                                 # sequence over `model` between blocks
                                 # (§Perf hillclimb; shards norm/elementwise
                                 # traffic 1/TP at equal collective bytes)

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def held_experts(self) -> tuple[int, int]:
        """(first, count) of the routed experts held here."""
        return self.expert_first, self.experts_held or self.n_experts

    @property
    def d_inner(self) -> int:      # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for the long_500k shape (DESIGN.md §4)."""
        return (self.family in ("hybrid", "xlstm")
                or self.window is not None)

    def scaled(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def param_count(self) -> int:
        """Approximate parameter count (used for MODEL_FLOPS in §Roofline)."""
        d, v = self.d_model, self.vocab_size
        n = v * d * 2                               # embed + head
        hd = self.head_dim
        if self.family == "xlstm":
            for i in range(self.n_layers):
                if i in self.slstm_layers:
                    di = int(self.d_model * self.proj_factor_slstm)
                    n += 4 * d * d + 2 * d * di     # r/z/i/f gates + up/down
                else:
                    di = int(self.d_model * self.proj_factor_mlstm)
                    n += 2 * d * di + 3 * di * di + di * d  # up/gate + qkv + down
            return n
        if self.family == "hybrid":
            di = self.d_inner
            per_mamba = d * (2 * di) + di * d + di * (2 * self.ssm_state) \
                + di  # in/out proj + BC proj + dt
            n += self.n_layers * per_mamba
            n_attn_blocks = 1  # shared weights
            n += n_attn_blocks * (d * (self.n_heads + 2 * self.n_kv_heads) * hd
                                  + self.n_heads * hd * d + 3 * d * self.d_ff)
            return n
        # attention
        if self.mla:
            per_attn = (d * self.kv_lora_rank + d * self.qk_rope_dim
                        + self.kv_lora_rank * self.n_heads
                        * (self.qk_nope_dim + self.v_head_dim)
                        + d * self.n_heads * (self.qk_nope_dim + self.qk_rope_dim)
                        + self.n_heads * self.v_head_dim * d)
        else:
            per_attn = (d * (self.n_heads + 2 * self.n_kv_heads) * hd
                        + self.n_heads * hd * d)
        n_dec = self.n_layers
        if self.moe:
            per_ffn_moe = 3 * d * self.moe_d_ff * (self.n_experts
                                                   + self.n_shared_experts) \
                + d * self.n_experts
            n_moe = self.n_layers - self.first_dense_layers
            n += n_moe * (per_attn + per_ffn_moe)
            n += self.first_dense_layers * (per_attn + 3 * d *
                                            (self.dense_d_ff or self.d_ff))
            return n
        per_ffn = 3 * d * self.d_ff if self.act == "silu" else 2 * d * self.d_ff
        n += n_dec * (per_attn + per_ffn)
        if self.encdec:
            n += self.n_enc_layers * (per_attn + per_ffn)
            n += self.n_layers * per_attn          # cross-attention
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed top-k + shared)."""
        if not self.moe:
            return self.param_count()
        d = self.d_model
        full = self.param_count()
        all_experts = 3 * d * self.moe_d_ff * self.n_experts \
            * (self.n_layers - self.first_dense_layers)
        active = 3 * d * self.moe_d_ff * self.top_k \
            * (self.n_layers - self.first_dense_layers)
        return full - all_experts + active
