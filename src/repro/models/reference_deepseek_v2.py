"""Plain float32 reference of the DeepSeek-V2 forward (arXiv:2405.04434).

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``: no kernels, no cache, no grouped product.  Each layer is
``h += MLA(RMSNorm(h))`` then ``h += FFN(RMSNorm(h))``; the first
``first_k_dense_replace`` FFNs are dense SwiGLUs, the rest a softmax router
with greedy top-k (renormalized only under ``norm_topk_prob``), routed
experts computed one expert at a time over the tokens routed to it, and
the shared experts as one SwiGLU.  Attention runs one sequence at a time
and the weights are widened to float32 one layer (one expert) at a time,
so the published widths fit beside the program's state.

It reads the published ``config.json`` keys and a parameter tree laid out
as ``repro.models.init_params`` lays it out (``embed``, ``dense_layers``,
``layers``: leaves stacked over layers), but imports nothing of ``repro``:
the same file is ``perfbench/reference_deepseek_v2.py``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(F32)


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(c):
    """The rope part's inverse frequencies, yarn-interpolated."""
    dim, theta = c["qk_rope_head_dim"], float(c["rope_theta"])
    rs = c.get("rope_scaling") or {}
    factor = float(rs.get("factor", 1.0))
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return extra.astype(np.float32)
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def _scales(c):
    """(cos/sin factor, softmax scale) of the attention."""
    rs = c.get("rope_scaling") or {}
    f = float(rs.get("factor", 1.0))
    m_all = _yarn_mscale(f, rs.get("mscale_all_dim", 1.0))
    rope = _yarn_mscale(f, rs.get("mscale", 1.0)) / m_all
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return rope, qk ** -0.5 * m_all * m_all


def rope(x, positions, c):
    """Rotate the interleaved pairs (2i, 2i+1) of x (S, ..., D) by
    position * inv_freq[i]; the result holds the rotated pairs as halves
    (first elements, then second elements)."""
    ang = positions.astype(F32)[:, None] * jnp.asarray(rope_inv_freq(c))
    s = _scales(c)[0]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos, sin = (jnp.cos(ang) * s).reshape(shape), (jnp.sin(ang) * s).reshape(shape)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)


@jax.jit
def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def attention(p, x, c):
    """Causal latent attention of one sequence.  x: (S, D) float32."""
    nope, rd, r = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["kv_lora_rank"]
    s = x.shape[0]
    pos = jnp.arange(s)
    q = jnp.einsum("sd,dhk->shk", x, p["wq"])
    ckv = x @ p["wkv_a"]
    kv = jnp.einsum("sr,rhk->shk", rms_norm(ckv[:, :r], p["kv_norm"], 1e-6), p["wkv_b"])
    k_pe = rope(ckv[:, r:], pos, c)
    h = q.shape[1]
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, c)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (s, h, rd))], -1)
    v = kv[..., nope:]
    scores = jnp.einsum("qhd,khd->hqk", q, k) * _scales(c)[1]
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return jnp.einsum("qhk,hkd->qd", out, p["wo"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@jax.jit
def _expert_add(y, x, idx, w, gate, up, down):
    """y plus one expert's weighted output on the tokens ``idx``."""
    out = swiglu(x[idx], gate.astype(F32), up.astype(F32), down.astype(F32))
    return y.at[idx].add(out * w[:, None])


@jax.jit
def _shared(x, gate, up, down):
    return swiglu(x, gate.astype(F32), up.astype(F32), down.astype(F32))


@partial(jax.jit, static_argnames=("k",))
def _route(x, router, k):
    probs = jax.nn.softmax(x @ router.astype(F32), -1)
    top_p, top_e = jax.lax.top_k(probs, k + 1)  # greedy, and the (k+1)-th
    return probs, top_p, top_e


def moe(p, x, c):
    """The routed and shared experts on x (T, D): (output, probabilities,
    top-k ids, top-k probabilities).  Expert e runs on its own tokens,
    padded with weight 0 to one size per layer (the busiest expert's count
    rounded up to a power of two) so that one program serves them all."""
    k = c["num_experts_per_tok"]
    probs, top_p, top_e = _route(x, p["router"], k)
    w = top_p[:, :k]
    if c["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    y = _shared(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    ids, wts = np.asarray(top_e[:, :k]), np.asarray(w)
    size = 1 << (int(np.bincount(ids.reshape(-1)).max()) - 1).bit_length()
    for e in range(c["n_routed_experts"]):
        tok, slot = np.nonzero(ids == e)
        if not tok.size:
            continue
        pad = size - tok.size
        idx = np.concatenate([tok, np.zeros(pad, tok.dtype)])
        we = np.concatenate([wts[tok, slot], np.zeros(pad, np.float32)])
        y = _expert_add(y, x, idx, we, p["gate"][e], p["up"][e], p["down"][e])
    return y, probs, top_e, top_p


@jax.jit
def _dense(x, mlp):
    return swiglu(x, *(mlp[n].astype(F32) for n in ("gate", "up", "down")))


def forward(params, c, tokens):
    """tokens (B, S) -> {"hidden": (B, S, D) before the final norm,
    "moe": one dict per expert layer with the layer's input rows ``x``
    (T, D), router ``probs`` (T, E), ``expert_ids`` (T, k) and ``margin``
    (T,), the k-th probability less the (k+1)-th}, all float32."""
    eps = c["rms_norm_eps"]
    k_dense = c["first_k_dense_replace"]
    b, s = tokens.shape
    attend = jax.jit(lambda p, x: attention(p, x, c))  # one sequence
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens.reshape(-1)].astype(F32)
        out = []
        for i in range(c["num_hidden_layers"]):
            stack, j = ("dense_layers", i) if i < k_dense else ("layers", i - k_dense)
            lp = jax.tree.map(lambda a: a[j], params[stack])
            x = rms_norm(h, lp["attn_norm"], eps)
            att = _f32(lp["attn"])
            h = h + jnp.concatenate(
                [attend(att, x[q * s:(q + 1) * s]) for q in range(b)])
            x = rms_norm(h, lp["mlp_norm"], eps)
            if i < k_dense:
                h = h + _dense(x, lp["mlp"])
                continue
            y, probs, top_e, top_p = moe(lp["moe"], x, c)
            kk = c["num_experts_per_tok"]
            out.append({"x": x, "probs": probs, "expert_ids": top_e[:, :kk],
                        "margin": top_p[:, kk - 1] - top_p[:, kk]})
            h = h + y
    return {"hidden": h.reshape(b, s, -1), "moe": out}
