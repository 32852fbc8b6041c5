"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1).

Keys and values of every head are expanded from one ``kv_lora_rank``-wide
latent per token; the rotary part of the key (``qk_rope_head_dim``) is one
vector shared by all heads.  Without a query down-projection:

    q            = x W_q                      (H x (nope + rope))
    [c, k_pe]    = x W_kva                    (kv_lora_rank + rope)
    [k_nope, v]  = RMSNorm(c) W_kvb           (H x (nope + v_head_dim))
    q_pe, k_pe   = yarn RoPE on interleaved pairs
    out          = softmax(q k^T * scale) v,  scale = (nope + rope)^-0.5 m^2
    y            = out W_o                    ((H x v_head_dim) -> d)

with ``m = 0.1 * mscale_all_dim * ln(factor) + 1`` (yarn's attention
temperature).  The rope pairs are the interleaved (2i, 2i+1) elements, as
the published modelling code permutes them before its rotate-half rope.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .config import MLAConfig, ModelConfig
from .layers import _sdpa_chunked, _sdpa_dense, apply_rope, dense_init, rms_norm

Params = Dict[str, Any]


def init_mla(key: jax.Array, cfg: ModelConfig) -> Params:
    a, pdt = cfg.mla, jnp.dtype(cfg.param_dtype)
    d, h, r = cfg.d_model, cfg.n_heads, cfg.mla.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, h, a.qk_head_dim), d, pdt),
        "wkv_a": dense_init(ks[1], (d, r + a.qk_rope_head_dim), d, pdt),
        "kv_norm": jnp.ones((r,), pdt),
        "wkv_b": dense_init(
            ks[2], (r, h, a.qk_nope_head_dim + a.v_head_dim), r, pdt
        ),
        "wo": dense_init(ks[3], (h, a.v_head_dim, d), h * a.v_head_dim, pdt),
    }


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(a: MLAConfig, theta: float) -> np.ndarray:
    """Inverse frequencies of the rope part: interpolated (divided by the
    factor) below the correction range of ``beta_slow``, extrapolated
    (kept) above that of ``beta_fast``, a linear ramp between."""
    dim = a.qk_rope_head_dim
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if a.rope_factor <= 1:
        return extra.astype(np.float32)
    inter = extra / a.rope_factor

    def corr_dim(rotations: float) -> float:
        return (dim * math.log(a.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(a.beta_fast)), 0)
    high = min(math.ceil(corr_dim(a.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp  # 1: extrapolate, 0: interpolate
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def yarn_tables(positions: jax.Array, a: MLAConfig, theta: float):
    """cos/sin of the rope part, (..., qk_rope_head_dim // 2), scaled by
    yarn's mscale ratio (1 when ``mscale == mscale_all_dim``)."""
    inv = jnp.asarray(yarn_inv_freq(a, theta))
    ang = positions.astype(jnp.float32)[..., None] * inv
    s = _yarn_mscale(a.rope_factor, a.mscale) / _yarn_mscale(
        a.rope_factor, a.mscale_all_dim)
    return jnp.cos(ang) * s, jnp.sin(ang) * s


def softmax_scale(a: MLAConfig) -> float:
    m = _yarn_mscale(a.rope_factor, a.mscale_all_dim)
    return a.qk_head_dim ** -0.5 * m * m


def _deinterleave(x: jax.Array) -> jax.Array:
    """Pairs (2i, 2i+1) -> halves (i, i + D/2), for the rotate-half rope."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def mla_attention(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    causal: bool = True,
) -> jax.Array:
    """Full-sequence latent attention.  x: (B, S, d) -> (B, S, d)."""
    a, dt = cfg.mla, x.dtype
    b, s, _ = x.shape
    nope, r = a.qk_nope_head_dim, a.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    ckv = x @ params["wkv_a"].astype(dt)
    c = rms_norm(ckv[..., :r], params["kv_norm"], cfg.rms_eps)
    kv = jnp.einsum("bsr,rhk->bshk", c, params["wkv_b"].astype(dt))
    cos, sin = yarn_tables(positions, a, cfg.rope_theta)
    q_pe = apply_rope(_deinterleave(q[..., nope:]), cos, sin)
    k_pe = apply_rope(_deinterleave(ckv[..., None, r:]), cos, sin)
    h = cfg.n_heads
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, h, a.qk_rope_head_dim))],
        axis=-1,
    )
    v = kv[..., nope:]
    scale = softmax_scale(a)
    if not causal or cfg.attn_impl == "dense" or s <= cfg.attn_chunk:
        out = _sdpa_dense(q, k, v, scale, causal)
    else:
        skip = cfg.attn_impl == "chunked_skip"
        chunk = max(cfg.attn_chunk, s // 8) if skip else cfg.attn_chunk
        out = _sdpa_chunked(q, k, v, scale, chunk, skip=skip,
                            unroll=not cfg.scan_layers)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
