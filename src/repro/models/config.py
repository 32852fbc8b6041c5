"""Model configuration for the architecture zoo.

One ``ModelConfig`` covers every assigned family: dense GQA transformers,
MoE, Mamba2/SSD, hybrid (SSM + shared attention), encoder-decoder (whisper)
and VLM backbones (frontends are stubs per the assignment: ``input_specs``
feeds precomputed frame/patch embeddings).
"""

from __future__ import annotations

import dataclasses
from typing import Literal, Optional

Act = Literal["swiglu", "geglu", "gelu"]
BlockKind = Literal["attn", "ssm"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared_experts: int = 0
    capacity_factor: float = 1.5
    group_size: int = 512  # tokens per dispatch group (bounds dispatch memory)
    router_aux_weight: float = 0.01
    # experts are sharded over the "model" axis; pad to a multiple of it
    pad_experts_to: int | None = None
    # renormalize the top-k router probabilities to sum to 1 (False keeps
    # the raw softmax probabilities, DeepSeek-V2's ``norm_topk_prob``)
    norm_topk_prob: bool = True
    # dropless dispatch in every forward (the decode step is always
    # dropless): every (token, choice) pair reaches its expert as one
    # grouped product over the pairs sorted by expert (``lax.ragged_dot``),
    # with no (G, E, C, D) capacity buffer
    dropless: bool = False

    @property
    def padded_experts(self) -> int:
        return self.pad_experts_to or self.num_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1)
    without a query down-projection, with yarn RoPE on the decoupled
    ``qk_rope_head_dim`` part."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    # yarn context extension of the rope part (factor 1: plain RoPE)
    rope_factor: float = 1.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 256  # SSD chunk length (matmul-friendly scan)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None  # default d_model // n_heads (gemma: 256)
    act: Act = "swiglu"
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    tie_embeddings: bool = False
    # block pattern: None = all-attention; "ssm" = all-SSM (mamba2);
    # "hybrid" = SSM stack with a SHARED attention block every
    # ``shared_attn_every`` layers (zamba2)
    family: Literal["dense", "ssm", "hybrid", "encdec", "moe", "vlm", "audio"] = (
        "dense"
    )
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    mla: Optional[MLAConfig] = None  # latent attention in place of GQA
    # moe: the leading layers that keep a dense MLP of width d_ff
    first_k_dense: int = 0
    # multiply token embeddings by sqrt(d_model) (Gemma-style)
    scale_embeddings: bool = True
    shared_attn_every: int = 6  # hybrid only
    # encoder-decoder (whisper): encoder layer count; frontend supplies
    # precomputed frame embeddings (conv stem is a stub per the assignment)
    n_enc_layers: int = 0
    # vlm: leading positions of the sequence are precomputed patch embeddings
    n_frontend_tokens: int = 0
    # numerics / performance knobs
    dtype: str = "bfloat16"  # activation/compute dtype
    param_dtype: str = "float32"
    attn_impl: Literal["dense", "chunked", "chunked_skip"] = "chunked_skip"
    attn_chunk: int = 1024
    remat: bool = True  # recompute each layer's activations in backward
    logits_chunk: int = 0  # 0 = unchunked; >0 = sequence-chunked loss
    scan_layers: bool = True
    # FSDP (ZeRO-3-style): additionally shard params/optimizer over the
    # "data" axis for training — required for archs whose fp32 params +
    # Adam state exceed HBM under TP-only sharding (qwen3-moe, internvl2)
    fsdp: bool = False
    # ZeRO-1: shard Adam m/v over the "data" axis (params keep their TP
    # sharding; GSPMD inserts the post-update weight all-gather).  Frees
    # 8 bytes/param of replicated state at low-TP mesh ratios
    zero1: bool = False

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def q_rep(self) -> int:
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"{self.name}: n_heads {self.n_heads} not divisible by "
                f"n_kv_heads {self.n_kv_heads}"
            )
        return self.n_heads // self.n_kv_heads

    def validate(self) -> "ModelConfig":
        if self.family in ("moe",) and self.moe is None:
            raise ValueError(f"{self.name}: family=moe requires moe config")
        if self.family in ("ssm", "hybrid") and self.ssm is None:
            raise ValueError(f"{self.name}: family={self.family} requires ssm config")
        if self.family == "encdec" and self.n_enc_layers <= 0:
            raise ValueError(f"{self.name}: encdec requires n_enc_layers")
        if self.first_k_dense and (
            self.family != "moe" or self.first_k_dense >= self.n_layers
        ):
            raise ValueError(
                f"{self.name}: first_k_dense={self.first_k_dense} needs a "
                f"moe family with more than that many layers"
            )
        _ = self.q_rep
        return self

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks), for 6·N·D."""
        d, v = self.d_model, self.vocab
        hd = self.resolved_head_dim
        total = v * d * (1 if self.tie_embeddings else 2)
        attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
        if self.mla is not None:
            a, h = self.mla, self.n_heads
            attn = (
                d * h * a.qk_head_dim
                + d * (a.kv_lora_rank + a.qk_rope_head_dim)
                + a.kv_lora_rank * h * (a.qk_nope_head_dim + a.v_head_dim)
                + h * a.v_head_dim * d
            )

        def mlp(ff: int) -> int:
            gates = 2 if self.act in ("swiglu", "geglu") else 1
            return d * ff * gates + ff * d

        if self.family in ("dense", "vlm"):
            total += self.n_layers * (attn + mlp(self.d_ff) + 2 * d)
        elif self.family == "moe":
            m = self.moe
            expert = d * m.d_ff_expert * 3  # gate/up/down
            k = self.first_k_dense
            total += k * (attn + mlp(self.d_ff) + 2 * d)
            total += (self.n_layers - k) * (
                attn + (m.num_experts + m.num_shared_experts) * expert
                + d * m.num_experts + 2 * d
            )
        elif self.family == "ssm":
            total += self.n_layers * self._ssm_block_params()
        elif self.family == "hybrid":
            n_shared = 1
            total += self.n_layers * self._ssm_block_params()
            total += n_shared * (attn + mlp(self.d_ff) + 2 * d)
        elif self.family in ("encdec", "audio"):
            total += (self.n_layers + self.n_enc_layers) * (
                attn + mlp(self.d_ff) + 2 * d
            )
            total += self.n_layers * attn  # decoder cross-attention
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k of num_experts)."""
        if self.family != "moe":
            return self.param_count()
        m = self.moe
        d = self.d_model
        expert = d * m.d_ff_expert * 3
        total = self.param_count()
        total -= (self.n_layers - self.first_k_dense) * (
            m.num_experts - m.top_k
        ) * expert
        return total

    def _ssm_block_params(self) -> int:
        s = self.ssm
        d = self.d_model
        d_inner = s.expand * d
        n_heads = d_inner // s.head_dim
        in_proj = d * (2 * d_inner + 2 * s.n_groups * s.d_state + n_heads)
        conv = s.d_conv * (d_inner + 2 * s.n_groups * s.d_state)
        out = d_inner * d
        return in_proj + conv + out + 2 * d_inner + 2 * n_heads + d
