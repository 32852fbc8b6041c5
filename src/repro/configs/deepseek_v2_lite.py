"""deepseek-v2-lite [moe]: 27L d_model=2048, MLA 16 heads (kv_lora_rank 512,
qk_nope 128 + qk_rope 64, v_head_dim 128, no q LoRA, yarn RoPE factor 40),
layer 0 a dense SwiGLU of 10944, layers 1-26 MoE with 64 routed experts of
1408 (softmax, greedy top-6, not renormalized) and 2 shared experts,
vocab 102400 [hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434].

The forward follows the published modelling code, each layer
``h += MLA(RMSNorm(h))`` then ``h += FFN(RMSNorm(h))``, with these
departures:

  * dispatch is dropless and grouped (``MoEConfig.dropless``): every token
    reaches its 6 experts; the published inference loop computes the same
    sum with a Python loop over experts;
  * the 2 shared experts are one SwiGLU of their summed width 2816, which
    the published ``DeepseekV2MLP(intermediate_size=1408 * 2)`` also is;
  * the load-balance loss is the repository's Switch-style one (the
    published sequence-level auxiliary loss is a training term only);
  * compute is ``dtype`` (bfloat16) with the router in float32, as the
    published gate casts to float32; ``routed_scaling_factor`` is 1 and
    is not applied.

There is no KV cache for the latent attention yet: the model runs the
full-sequence forward (training, prefill capture), not ``decode_step``.
"""

from repro.models.config import MLAConfig, ModelConfig, MoEConfig

# the published config.json's numbers, under its own keys
PUBLISHED = {
    "first_k_dense_replace": 1,
    "hidden_size": 2048,
    "intermediate_size": 10944,
    "kv_lora_rank": 512,
    "moe_intermediate_size": 1408,
    "n_routed_experts": 64,
    "n_shared_experts": 2,
    "norm_topk_prob": False,
    "num_attention_heads": 16,
    "num_experts_per_tok": 6,
    "num_hidden_layers": 27,
    "num_key_value_heads": 16,
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000,
    "routed_scaling_factor": 1,
    "scoring_func": "softmax",
    "tie_word_embeddings": False,
    "topk_method": "greedy",
    "v_head_dim": 128,
    "vocab_size": 102400,
}


def from_hf(c: dict, **overrides) -> ModelConfig:
    """A ``ModelConfig`` from a DeepSeek-V2 ``config.json`` dict."""
    if c.get("q_lora_rank") is not None:
        raise ValueError("a query down-projection (q_lora_rank) is not "
                         "implemented")
    if (c["scoring_func"], c["topk_method"]) != ("softmax", "greedy"):
        raise ValueError("only softmax scoring with greedy top-k routing")
    if c["routed_scaling_factor"] != 1:
        raise ValueError("routed_scaling_factor must be 1")
    rs = c.get("rope_scaling") or {}
    if rs and rs.get("type") != "yarn":
        raise ValueError(f"rope scaling {rs.get('type')!r} is not yarn")
    mla = MLAConfig(
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"],
        rope_factor=float(rs.get("factor", 1.0)),
        original_max_position=int(
            rs.get("original_max_position_embeddings", 4096)),
        beta_fast=float(rs.get("beta_fast", 32)),
        beta_slow=float(rs.get("beta_slow", 1)),
        mscale=float(rs.get("mscale", 1.0)),
        mscale_all_dim=float(rs.get("mscale_all_dim", 1.0)),
    )
    fields = dict(
        name="deepseek-v2-lite",
        family="moe",
        n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=mla.qk_head_dim,
        d_ff=c["intermediate_size"],
        vocab=c["vocab_size"],
        act="swiglu",
        rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"],
        scale_embeddings=False,
        mla=mla,
        first_k_dense=c["first_k_dense_replace"],
        moe=MoEConfig(
            num_experts=c["n_routed_experts"],
            top_k=c["num_experts_per_tok"],
            d_ff_expert=c["moe_intermediate_size"],
            num_shared_experts=c["n_shared_experts"],
            norm_topk_prob=c["norm_topk_prob"],
            dropless=True,
        ),
    )
    fields.update(overrides)
    return ModelConfig(**fields).validate()


CONFIG = from_hf(PUBLISHED)

SMOKE = dict(
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=24, d_ff=96,
    vocab=256,
    mla=MLAConfig(kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16, rope_factor=40.0, original_max_position=64,
                  beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                  mscale_all_dim=0.707),
    moe=MoEConfig(num_experts=8, top_k=3, d_ff_expert=32,
                  num_shared_experts=2, norm_topk_prob=False, dropless=True),
)
