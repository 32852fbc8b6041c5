"""Shared neural-net layers: norms, RoPE, attention (train + decode), MLPs.

Pure-JAX (no flax): parameters are plain pytrees of arrays, initialisers are
explicit, and every layer is a function ``(params, inputs, ...) -> outputs``.
Attention supports three implementations (config.attn_impl):

  dense        -- full (S, S) score matrix; smoke tests and short sequences.
  chunked      -- lax.scan over query chunks, online softmax over all KV
                  chunks with causal masking (memory-bound, 2x causal FLOPs).
  chunked_skip -- statically unrolled query-chunk loop that *skips* KV chunks
                  above the causal diagonal (FLOP-optimal; the default).
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# initialisers / norms / rope
# --------------------------------------------------------------------------


def dense_init(key: jax.Array, shape: tuple[int, ...], in_axis_size: int, dtype):
    scale = 1.0 / math.sqrt(max(1, in_axis_size))
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return ((xf * lax.rsqrt(var + eps)) * (w.astype(jnp.float32))).astype(dt)


def rope_tables(positions: jax.Array, head_dim: int, theta: float) -> tuple:
    """cos/sin tables for given positions: (..., head_dim // 2)."""
    half = head_dim // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (B, S, D/2) or (S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------


def init_mlp(key: jax.Array, cfg: ModelConfig, d_ff: int) -> Params:
    pdt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    gated = cfg.act in ("swiglu", "geglu")
    p: Params = {"down": dense_init(ks[2], (d_ff, d), d_ff, pdt)}
    if gated:
        p["gate"] = dense_init(ks[0], (d, d_ff), d, pdt)
        p["up"] = dense_init(ks[1], (d, d_ff), d, pdt)
    else:
        p["up"] = dense_init(ks[1], (d, d_ff), d, pdt)
    return p


def mlp(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    dt = x.dtype
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ params["gate"].astype(dt)) * (x @ params["up"].astype(dt))
    elif cfg.act == "geglu":
        h = jax.nn.gelu(x @ params["gate"].astype(dt), approximate=True) * (
            x @ params["up"].astype(dt)
        )
    else:
        h = jax.nn.gelu(x @ params["up"].astype(dt), approximate=True)
    return h @ params["down"].astype(dt)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------


def init_attention(key: jax.Array, cfg: ModelConfig) -> Params:
    if cfg.mla is not None:
        from .mla import init_mla

        return init_mla(key, cfg)
    pdt = jnp.dtype(cfg.param_dtype)
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], (d, cfg.n_heads, hd), d, pdt),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads, hd), d, pdt),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads, hd), d, pdt),
        "wo": dense_init(ks[3], (cfg.n_heads, hd, d), cfg.n_heads * hd, pdt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), pdt)
        p["k_norm"] = jnp.ones((hd,), pdt)
    return p


def _qkv(params: Params, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """Project + (optional) qk-norm + rope.  x: (B, S, d)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.rms_eps)
        k = rms_norm(k, params["k_norm"], cfg.rms_eps)
    cos, sin = rope_tables(positions, cfg.resolved_head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def _sdpa_dense(q, k, v, scale: float, causal: bool) -> jax.Array:
    """q: (B, Sq, H, D), k/v: (B, Sk, Hkv, D) with H = Hkv * rep."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    rep = h // hkv
    qg = q.reshape(b, sq, hkv, rep, d)
    scores = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v)
    return out.reshape(b, sq, h, v.shape[-1])


def _attn_block(q, k, v, scale, mask_bias):
    """One (q-chunk, kv-chunk) online-softmax block.

    q: (B, Cq, Hkv, rep, D); k/v: (B, Ck, Hkv, D).
    Returns (m, l, acc) partials with m/l: (B, Hkv, rep, Cq), acc like q.
    """
    s = jnp.einsum("bqhrd,bkhd->bhrqk", q, k).astype(jnp.float32) * scale
    if mask_bias is not None:
        s = s + mask_bias
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhrqk,bkhd->bqhrd", p.astype(q.dtype), v)
    return m, l, acc


def _merge_blocks(m1, l1, a1, m2, l2, a2):
    m = jnp.maximum(m1, m2)
    e1 = jnp.exp(m1 - m)
    e2 = jnp.exp(m2 - m)
    l = l1 * e1 + l2 * e2
    # scale accumulators: acc axes (B, Cq, Hkv, rep, D) vs stats (B,Hkv,rep,Cq)
    s1 = jnp.transpose(e1, (0, 3, 1, 2))[..., None].astype(a1.dtype)
    s2 = jnp.transpose(e2, (0, 3, 1, 2))[..., None].astype(a2.dtype)
    return m, l, a1 * s1 + a2 * s2


def _finalize(m, l, acc):
    denom = jnp.transpose(l, (0, 3, 1, 2))[..., None]
    return (acc.astype(jnp.float32) / jnp.maximum(denom, 1e-30)).astype(acc.dtype)


def _sdpa_chunked(
    q, k, v, scale: float, chunk: int, skip: bool, unroll: bool = False
) -> jax.Array:
    """Causal online-softmax attention over chunks.

    skip=True statically unrolls the query loop and skips KV chunks above the
    causal diagonal (FLOP-optimal); skip=False lax.scans over query chunks
    with full masked KV (compact HLO, 2x causal FLOPs).  ``unroll`` unrolls
    the scans so XLA's cost analysis, which visits loop bodies once,
    counts every iteration.
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    rep = h // hkv
    cq = min(chunk, sq)
    ck = min(chunk, sk)
    if sq % cq or sk % ck:
        return _sdpa_dense(q, k, v, scale, causal=True)
    nq, nk = sq // cq, sk // ck
    qg = q.reshape(b, nq, cq, hkv, rep, d)
    kg = k.reshape(b, nk, ck, hkv, d)
    vg = v.reshape(b, nk, ck, hkv, d)
    hist = sk - sq  # KV positions preceding the query window (decode prefill)

    def block_bias(qi: jax.Array, kj: jax.Array):
        qpos = qi * cq + hist + jnp.arange(cq)
        kpos = kj * ck + jnp.arange(ck)
        keep = qpos[:, None] >= kpos[None, :]
        return jnp.where(keep, 0.0, -1e30)[None, None, None]

    if skip:
        outs = []
        for i in range(nq):
            m = jnp.full((b, hkv, rep, cq), -1e30, jnp.float32)
            l = jnp.zeros((b, hkv, rep, cq), jnp.float32)
            acc = jnp.zeros((b, cq, hkv, rep, dv), q.dtype)
            hi = min(nk, ((i + 1) * cq + hist + ck - 1) // ck)
            for j in range(hi):
                diag = (j + 1) * ck > i * cq + hist  # block touches the mask
                bias = block_bias(i, j) if diag else None
                mb, lb, ab = _attn_block(qg[:, i], kg[:, j], vg[:, j], scale, bias)
                m, l, acc = _merge_blocks(m, l, acc, mb, lb, ab)
            outs.append(_finalize(m, l, acc))
        out = jnp.stack(outs, axis=1)
    else:

        def q_step(_, i):
            m = jnp.full((b, hkv, rep, cq), -1e30, jnp.float32)
            l = jnp.zeros((b, hkv, rep, cq), jnp.float32)
            acc = jnp.zeros((b, cq, hkv, rep, dv), q.dtype)
            qi = qg[:, i]

            def kv_step(carry, j):
                m, l, acc = carry
                mb, lb, ab = _attn_block(qi, kg[:, j], vg[:, j], scale, block_bias(i, j))
                return _merge_blocks(m, l, acc, mb, lb, ab), None

            (m, l, acc), _ = lax.scan(
                kv_step, (m, l, acc), jnp.arange(nk), unroll=unroll
            )
            return None, _finalize(m, l, acc)

        _, out = lax.scan(q_step, None, jnp.arange(nq), unroll=unroll)
        out = jnp.moveaxis(out, 0, 1)  # (B, nq, cq, hkv, rep, dv)
    return out.reshape(b, sq, h, dv)


def attention(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    causal: bool = True,
) -> jax.Array:
    """Full-sequence attention (training / prefill).  x: (B, S, d)."""
    if cfg.mla is not None:
        from .mla import mla_attention

        return mla_attention(params, x, cfg, positions, causal)
    q, k, v = _qkv(params, x, cfg, positions)
    scale = 1.0 / math.sqrt(cfg.resolved_head_dim)
    if not causal or cfg.attn_impl == "dense" or x.shape[1] <= cfg.attn_chunk:
        out = _sdpa_dense(q, k, v, scale, causal)
    else:
        skip = cfg.attn_impl == "chunked_skip"
        # skip statically unrolls (q,kv) blocks: floor the chunk at S/8 to
        # bound HLO size; the scan impl has no such constraint
        chunk = max(cfg.attn_chunk, x.shape[1] // 8) if skip else cfg.attn_chunk
        out = _sdpa_chunked(
            q, k, v, scale, chunk, skip=skip, unroll=not cfg.scan_layers
        )
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))


def cross_attention(
    params: Params,
    x: jax.Array,
    kv_k: jax.Array,
    kv_v: jax.Array,
    cfg: ModelConfig,
) -> jax.Array:
    """Decoder cross-attention against precomputed encoder K/V (no rope)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.rms_eps)
    scale = 1.0 / math.sqrt(cfg.resolved_head_dim)
    out = _sdpa_dense(q, kv_k, kv_v, scale, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))


def encode_kv(params: Params, enc_out: jax.Array, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder output."""
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"].astype(dt))
    if cfg.qk_norm:
        k = rms_norm(k, params["k_norm"], cfg.rms_eps)
    return k, v


def attention_decode(
    params: Params,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    pos: jax.Array,
    cfg: ModelConfig,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Single-token decode with KV cache.

    x: (B, 1, d); cache_k/v: (B, S_max, Hkv, D); pos: scalar int32 (tokens
    already in cache).  Returns (y, new_k, new_v).
    """
    dt = x.dtype
    b, _, _ = x.shape
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = _qkv(params, x, cfg, positions)
    # one-hot masked write instead of dynamic_update_slice: a dynamic-index
    # write on a sequence-SHARDED cache axis otherwise degrades to a full
    # all-gather of the cache (measured +8 GB/device on qwen3-moe decode)
    slot = (jnp.arange(cache_k.shape[1]) == pos)[None, :, None, None]
    cache_k = jnp.where(slot, k.astype(cache_k.dtype), cache_k)
    cache_v = jnp.where(slot, v.astype(cache_v.dtype), cache_v)
    smax = cache_k.shape[1]
    hkv = cfg.n_kv_heads
    rep = cfg.q_rep
    scale = 1.0 / math.sqrt(cfg.resolved_head_dim)
    qg = q.reshape(b, 1, hkv, rep, q.shape[-1])
    scores = (
        jnp.einsum("bqhrd,bkhd->bhrqk", qg, cache_k.astype(dt)).astype(jnp.float32)
        * scale
    )
    valid = (jnp.arange(smax) <= pos)[None, None, None, None, :]
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, cache_v.astype(dt))
    out = out.reshape(b, 1, cfg.n_heads, cfg.resolved_head_dim)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return y, cache_k, cache_v
