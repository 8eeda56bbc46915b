"""Transformer configuration (decoder-only LM family)."""
from __future__ import annotations

import dataclasses
import math
from typing import Optional


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int  # routed experts the router scores (its output width)
    top_k: int
    d_ff: int  # per-expert hidden width
    # None = dropless routing over a held share (moe.moe_held): every token
    # reaches each of its top-k experts that this chip holds
    capacity_factor: Optional[float] = 1.25
    shard_mode: str = "expert"  # "expert" (EP) or "tp" (TP within expert)
    # the held share of an expert-parallel deployment (dropless only):
    # experts first_held .. first_held + n_held - 1 live on this chip, and
    # the layer computes their part of the result alone
    n_held: Optional[int] = None  # None = all n_experts
    first_held: int = 0
    d_shared: int = 0  # shared experts, as one SwiGLU every token runs
    norm_topk: bool = True  # renormalise the top-k gates to sum to one
    dense_layers: int = 0  # leading layers with the dense FFN (d_ff wide)

    @property
    def held(self) -> int:
        return self.n_experts if self.n_held is None else self.n_held


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2) without a query low-rank
    projection, with YaRN-scaled rotary embeddings on the rope dims."""

    kv_rank: int  # width of the cached latent (kv_lora_rank)
    rope_dim: int  # rotated query/key dims per head, one key shared by heads
    nope_dim: int  # unrotated query/key dims per head
    v_dim: int  # value dims per head
    # YaRN (factor 1.0 = plain rotary embeddings)
    yarn_factor: float = 1.0
    yarn_original_max: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def softmax_scale(self) -> float:
        m = yarn_get_mscale(self.yarn_factor, self.yarn_mscale_all_dim) \
            if self.yarn_mscale_all_dim else 1.0
        return self.qk_dim ** -0.5 * m * m

    @property
    def rope_mscale(self) -> float:
        """Factor on the rotary cos/sin tables."""
        return (yarn_get_mscale(self.yarn_factor, self.yarn_mscale)
                / yarn_get_mscale(self.yarn_factor, self.yarn_mscale_all_dim))


def yarn_get_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature factor (1 where nothing is scaled)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int  # dense FFN width (ignored when moe is set)
    vocab: int
    rope_theta: float = 10_000.0
    sliding_window: Optional[int] = None  # None = full causal attention
    moe: Optional[MoEConfig] = None
    dtype: str = "bfloat16"  # parameter / activation dtype
    remat: bool = True
    scan_layers: bool = True
    q_chunk: int = 512  # chunked-attention block sizes (flash-style)
    kv_chunk: int = 512
    loss_chunk: int = 512  # seq chunk for streamed cross-entropy
    norm_eps: float = 1e-5
    kv_quant: bool = False  # int8 KV cache (per-row absmax scales)
    mla: Optional[MLAConfig] = None  # latent attention in place of GQA

    def __post_init__(self):
        if self.moe is not None and self.moe.dense_layers and self.mla is None:
            raise ValueError("leading dense layers are served by the "
                             "latent-attention (mla) path only")

    @property
    def n_rep(self) -> int:
        assert self.n_heads % self.n_kv_heads == 0
        return self.n_heads // self.n_kv_heads

    def param_count(self) -> tuple[int, int]:
        """(total params N, active params N_active) excluding embeddings'
        contribution is included — standard 6ND accounting uses non-embedding
        + embedding; we report both terms folded in."""
        d, h, kv, dh = self.d_model, self.n_heads, self.n_kv_heads, self.d_head
        attn = d * h * dh + 2 * d * kv * dh + h * dh * d
        if self.moe is not None:
            ff_tot = 3 * d * self.moe.d_ff * self.moe.n_experts + d * self.moe.n_experts
            ff_act = 3 * d * self.moe.d_ff * self.moe.top_k + d * self.moe.n_experts
        else:
            ff_tot = ff_act = 3 * d * self.d_ff
        per_layer_t = attn + ff_tot + 2 * d
        per_layer_a = attn + ff_act + 2 * d
        emb = self.vocab * d * 2  # embed + head
        return (
            self.n_layers * per_layer_t + emb + d,
            self.n_layers * per_layer_a + emb + d,
        )
