"""Model assembly: init / forward / prefill / decode for every family.

Parameters are plain pytrees with layer-stacked leaves (leading axis L) so
layer application is a ``lax.scan`` — compile time and HLO size stay flat in
depth.  ``jax.checkpoint`` wraps
the scan body when ``cfg.remat`` (activation recomputation).

Families:
  dense / vlm      -- GQA attention + (Ge/Swi)GLU MLP stack
  moe              -- attention (GQA or latent) + top-k MoE MLP, after
                      ``first_k_dense`` leading dense layers
  ssm              -- Mamba-2 / SSD stack (attention-free)
  hybrid           -- SSD stack with one SHARED attention+MLP block applied
                      after every ``shared_attn_every`` SSM layers (zamba2)
  encdec / audio   -- encoder (bidirectional) + causal decoder with
                      cross-attention (whisper); frame frontend is a stub
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro import _obs_hooks

from .config import ModelConfig
from .layers import (
    attention,
    attention_decode,
    cross_attention,
    dense_init,
    encode_kv,
    init_attention,
    init_mlp,
    mlp,
    rms_norm,
    _qkv,
)
from .moe import init_moe, moe_block
from .ssd import init_ssd, init_ssd_cache, ssd_block, ssd_decode, ssm_dims

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------


def _init_layer(key: jax.Array, cfg: ModelConfig, kind: str) -> Params:
    pdt = jnp.dtype(cfg.param_dtype)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    if kind == "ssm":
        return {"norm": jnp.ones((d,), pdt), "ssd": init_ssd(ks[0], cfg)}
    p: Params = {
        "attn_norm": jnp.ones((d,), pdt),
        "attn": init_attention(ks[0], cfg),
        "mlp_norm": jnp.ones((d,), pdt),
    }
    if kind == "moe":
        p["moe"] = init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg, cfg.d_ff)
    if kind == "dec":
        p["cross_norm"] = jnp.ones((d,), pdt)
        p["cross_attn"] = init_attention(ks[2], cfg)
    return p


def _init_stack(key: jax.Array, cfg: ModelConfig, kind: str, n: int) -> Params:
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _init_layer(k, cfg, kind))(keys)


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    p: Params = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model)) * 0.02).astype(pdt),
        "final_norm": jnp.ones((cfg.d_model,), pdt),
    }
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab), cfg.d_model, pdt)
    fam = cfg.family
    if fam in ("dense", "vlm"):
        p["layers"] = _init_stack(ks[2], cfg, "attn", cfg.n_layers)
    elif fam == "moe":
        k = cfg.first_k_dense
        if k:
            p["dense_layers"] = _init_stack(ks[3], cfg, "attn", k)
        p["layers"] = _init_stack(ks[2], cfg, "moe", cfg.n_layers - k)
    elif fam == "ssm":
        p["layers"] = _init_stack(ks[2], cfg, "ssm", cfg.n_layers)
    elif fam == "hybrid":
        groups = cfg.n_layers // cfg.shared_attn_every
        trailing = cfg.n_layers % cfg.shared_attn_every
        p["layers"] = _init_stack(ks[2], cfg, "ssm", groups * cfg.shared_attn_every)
        if trailing:
            p["trailing"] = _init_stack(ks[3], cfg, "ssm", trailing)
        p["shared"] = _init_layer(ks[4], cfg, "attn")
    elif fam in ("encdec", "audio"):
        p["enc_layers"] = _init_stack(ks[2], cfg, "attn", cfg.n_enc_layers)
        p["enc_norm"] = jnp.ones((cfg.d_model,), pdt)
        p["layers"] = _init_stack(ks[3], cfg, "dec", cfg.n_layers)
    else:
        raise ValueError(f"unknown family {fam}")
    return p


def param_shapes(cfg: ModelConfig) -> Params:
    """Abstract init (no allocation): the parameter tree's shapes."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))


# --------------------------------------------------------------------------
# layer bodies
# --------------------------------------------------------------------------


def _attn_layer(lp: Params, h: jax.Array, cfg: ModelConfig, positions, causal=True):
    a = attention(lp["attn"], rms_norm(h, lp["attn_norm"], cfg.rms_eps), cfg, positions, causal)
    h = h + a
    m = mlp(lp["mlp"], rms_norm(h, lp["mlp_norm"], cfg.rms_eps), cfg)
    return h + m


def _moe_layer(lp: Params, h: jax.Array, cfg: ModelConfig, positions,
               return_route: bool = False):
    a = attention(lp["attn"], rms_norm(h, lp["attn_norm"], cfg.rms_eps), cfg, positions, True)
    h = h + a
    out = moe_block(lp["moe"], rms_norm(h, lp["mlp_norm"], cfg.rms_eps), cfg,
                    return_route=return_route)
    return (h + out[0], *out[1:])


def _ssm_layer(lp: Params, h: jax.Array, cfg: ModelConfig):
    y = ssd_block(lp["ssd"], rms_norm(h, lp["norm"], cfg.rms_eps), cfg)
    return h + y


def _dec_layer(lp: Params, h: jax.Array, ek: jax.Array, ev: jax.Array, cfg, positions):
    h = h + attention(lp["attn"], rms_norm(h, lp["attn_norm"], cfg.rms_eps), cfg, positions, True)
    h = h + cross_attention(lp["cross_attn"], rms_norm(h, lp["cross_norm"], cfg.rms_eps), ek, ev, cfg)
    h = h + mlp(lp["mlp"], rms_norm(h, lp["mlp_norm"], cfg.rms_eps), cfg)
    return h



def _scan(cfg: ModelConfig, body, carry, xs):
    """lax.scan honoring cfg.scan_layers: ``scan_layers=False`` unrolls so
    XLA's cost_analysis (which visits while bodies ONCE) reports true
    totals."""
    return lax.scan(body, carry, xs, unroll=not cfg.scan_layers)


def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


# --------------------------------------------------------------------------
# forward (training / full-sequence)
# --------------------------------------------------------------------------


def embed_tokens(params: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    h = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    return h * math.sqrt(cfg.d_model) if cfg.scale_embeddings else h


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,
    inputs_embeds: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    causal: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Decoder-only forward.  Returns (hidden (B,S,d), aux_loss)."""
    if inputs_embeds is not None and tokens is not None:
        text = embed_tokens(params, cfg, tokens)
        h = jnp.concatenate([inputs_embeds.astype(text.dtype), text], axis=1)
    elif tokens is not None:
        h = embed_tokens(params, cfg, tokens)
    else:
        h = inputs_embeds.astype(jnp.dtype(cfg.dtype))
    s = h.shape[1]
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    aux = jnp.float32(0.0)
    fam = cfg.family

    if fam in ("dense", "vlm"):
        def body(carry, lp):
            return _attn_layer(lp, carry, cfg, positions, causal), None
        h, _ = _scan(cfg, _maybe_remat(body, cfg), h, params["layers"])
    elif fam == "moe":
        if _obs_hooks.capturing() and not isinstance(h, jax.core.Tracer):
            h, aux = _moe_forward_captured(params, cfg, h, positions)
        else:
            h, aux = _moe_forward(params, cfg, h, positions)
    elif fam == "ssm":
        def body(carry, lp):
            return _ssm_layer(lp, carry, cfg), None
        h, _ = _scan(cfg, _maybe_remat(body, cfg), h, params["layers"])
    elif fam == "hybrid":
        h = _hybrid_forward(params, cfg, h, positions)
    else:
        raise ValueError(f"forward() does not handle family {fam}; use encdec_forward")
    return rms_norm(h, params["final_norm"], cfg.rms_eps), aux


def _moe_forward(params: Params, cfg: ModelConfig, h, positions):
    """The MoE stack: the leading dense layers, then the expert layers."""
    if "dense_layers" in params:
        def dense_body(carry, lp):
            return _attn_layer(lp, carry, cfg, positions), None
        h, _ = _scan(cfg, _maybe_remat(dense_body, cfg), h,
                     params["dense_layers"])

    def body(carry, lp):
        return _moe_layer(lp, carry, cfg, positions)
    h, auxs = _scan(cfg, _maybe_remat(body, cfg), h, params["layers"])
    return h, auxs.sum()


@partial(jax.jit, static_argnames=("cfg",))
def _dense_layer_at(stack: Params, i, h, positions, *, cfg: ModelConfig):
    lp = jax.tree.map(lambda x: x[i], stack)
    return _attn_layer(lp, h, cfg, positions)


@partial(jax.jit, static_argnames=("cfg",))
def _moe_layer_at(stack: Params, i, h, positions, *, cfg: ModelConfig):
    lp = jax.tree.map(lambda x: x[i], stack)
    return _moe_layer(lp, h, cfg, positions, return_route=True)


def _moe_forward_captured(params: Params, cfg: ModelConfig, h, positions):
    """:func:`_moe_forward` for a traffic capture: the same layer bodies,
    one jitted call per layer, so that each expert layer hands back its
    routing and the ``moe.dispatch`` tap fires with real arrays (inside
    the scan it would see tracers).  Taken only while a capture is active
    and ``forward`` runs outside ``jit``; every traced jaxpr is that of
    :func:`_moe_forward`."""
    k = cfg.first_k_dense
    for i in range(k):
        h = _dense_layer_at(params["dense_layers"], i, h, positions, cfg=cfg)
    aux = jnp.float32(0.0)
    for i in range(cfg.n_layers - k):
        h, a, route = _moe_layer_at(params["layers"], i, h, positions,
                                    cfg=cfg)
        aux = aux + a
        _obs_hooks.tap("moe.dispatch", layer=k + i, **route)
    return h, aux


def _hybrid_forward(params: Params, cfg: ModelConfig, h, positions):
    per = cfg.shared_attn_every
    groups = cfg.n_layers // per
    stacked = jax.tree.map(
        lambda x: x.reshape(groups, per, *x.shape[1:]), params["layers"]
    )
    shared = params["shared"]

    def group_body(carry, gp):
        def inner(c, lp):
            return _ssm_layer(lp, c, cfg), None
        c, _ = _scan(cfg, inner, carry, gp)
        c = _attn_layer(shared, c, cfg, positions)  # shared weights
        return c, None

    h, _ = _scan(cfg, _maybe_remat(group_body, cfg), h, stacked)
    if "trailing" in params:
        def body(c, lp):
            return _ssm_layer(lp, c, cfg), None
        h, _ = _scan(cfg, _maybe_remat(body, cfg), h, params["trailing"])
    return h


def encdec_forward(
    params: Params,
    cfg: ModelConfig,
    frames: jax.Array,  # (B, S_enc, d) precomputed frontend embeddings (stub)
    dec_tokens: jax.Array,  # (B, S_dec)
) -> tuple[jax.Array, jax.Array]:
    """Encoder-decoder forward (whisper).  Returns (dec hidden, aux)."""
    dt = jnp.dtype(cfg.dtype)
    enc = frames.astype(dt)
    s_enc = enc.shape[1]
    enc_pos = jnp.arange(s_enc, dtype=jnp.int32)[None, :]

    def enc_body(carry, lp):
        return _attn_layer(lp, carry, cfg, enc_pos, causal=False), None

    enc, _ = _scan(cfg, _maybe_remat(enc_body, cfg), enc, params["enc_layers"])
    enc = rms_norm(enc, params["enc_norm"], cfg.rms_eps)

    h = embed_tokens(params, cfg, dec_tokens)
    dec_pos = jnp.arange(h.shape[1], dtype=jnp.int32)[None, :]

    def dec_body(carry, lp):
        ek, ev = encode_kv(lp["cross_attn"], enc, cfg)
        return _dec_layer(lp, carry, ek, ev, cfg, dec_pos), None

    h, _ = _scan(cfg, _maybe_remat(dec_body, cfg), h, params["layers"])
    return rms_norm(h, params["final_norm"], cfg.rms_eps), jnp.float32(0.0)


# --------------------------------------------------------------------------
# logits / loss
# --------------------------------------------------------------------------


def unembed(params: Params, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return h @ w.astype(h.dtype)


def _ce(logits: jax.Array, labels: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Sum of CE over valid (label >= 0) positions; returns (sum, count)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    valid = labels >= 0
    ce = jnp.where(valid, lse - gold, 0.0)
    return ce.sum(), valid.sum()


def lm_loss(params: Params, cfg: ModelConfig, h: jax.Array, labels: jax.Array) -> jax.Array:
    """Cross-entropy; optionally sequence-chunked to bound logits memory."""
    chunk = cfg.logits_chunk
    s = h.shape[1]
    if chunk and s % chunk == 0 and s > chunk:
        nc = s // chunk
        hc = h.reshape(h.shape[0], nc, chunk, h.shape[-1])
        lc = labels.reshape(labels.shape[0], nc, chunk)

        def body(carry, xs):
            hh, ll = xs
            cs, cn = _ce(unembed(params, cfg, hh), ll)
            tot, cnt = carry
            return (tot + cs, cnt + cn), None

        (tot, cnt), _ = _scan(cfg, 
            jax.checkpoint(body),
            (jnp.float32(0.0), jnp.int32(0)),
            (jnp.moveaxis(hc, 1, 0), jnp.moveaxis(lc, 1, 0)),
        )
        return tot / jnp.maximum(cnt, 1)
    tot, cnt = _ce(unembed(params, cfg, h), labels)
    return tot / jnp.maximum(cnt, 1)


# --------------------------------------------------------------------------
# prefill / decode (serving)
# --------------------------------------------------------------------------


def _attn_with_kv(lp, h, cfg, positions):
    """Attention layer that also returns (k, v) for cache population."""
    x = rms_norm(h, lp["attn_norm"], cfg.rms_eps)
    q, k, v = _qkv(lp["attn"], x, cfg, positions)
    return k, v


def init_cache(cfg: ModelConfig, batch: int, max_len: int, enc_len: int = 0) -> Params:
    """Abstract-safe cache allocation for every family."""
    if cfg.mla is not None or cfg.first_k_dense:
        raise NotImplementedError(
            f"{cfg.name}: no KV cache for latent attention or leading dense "
            "layers yet; run the full-sequence forward"
        )
    dt = jnp.dtype(cfg.dtype)
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    fam = cfg.family
    cache: Params = {"pos": jnp.zeros((), jnp.int32)}
    if fam in ("dense", "vlm", "moe"):
        cache["k"] = jnp.zeros((cfg.n_layers, batch, max_len, hkv, hd), dt)
        cache["v"] = jnp.zeros((cfg.n_layers, batch, max_len, hkv, hd), dt)
    elif fam == "ssm":
        stack = init_ssd_cache(cfg, batch, dt)
        cache["ssm"] = jax.tree.map(
            lambda x: jnp.zeros((cfg.n_layers, *x.shape), x.dtype), stack
        )
    elif fam == "hybrid":
        per = cfg.shared_attn_every
        groups = cfg.n_layers // per
        trailing = cfg.n_layers % per
        stack = init_ssd_cache(cfg, batch, dt)
        cache["ssm"] = jax.tree.map(
            lambda x: jnp.zeros((groups * per, *x.shape), x.dtype), stack
        )
        if trailing:
            cache["ssm_trailing"] = jax.tree.map(
                lambda x: jnp.zeros((trailing, *x.shape), x.dtype), stack
            )
        cache["k"] = jnp.zeros((groups, batch, max_len, hkv, hd), dt)
        cache["v"] = jnp.zeros((groups, batch, max_len, hkv, hd), dt)
    elif fam in ("encdec", "audio"):
        cache["k"] = jnp.zeros((cfg.n_layers, batch, max_len, hkv, hd), dt)
        cache["v"] = jnp.zeros((cfg.n_layers, batch, max_len, hkv, hd), dt)
        cache["cross_k"] = jnp.zeros((cfg.n_layers, batch, enc_len, hkv, hd), dt)
        cache["cross_v"] = jnp.zeros((cfg.n_layers, batch, enc_len, hkv, hd), dt)
    return cache


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    max_len: int,
    frames: Optional[jax.Array] = None,
    inputs_embeds: Optional[jax.Array] = None,
) -> tuple[jax.Array, Params]:
    """Process a prompt, returning (last-position logits, populated cache).

    ``max_len`` is the cache capacity (>= prompt length).  For encdec,
    ``frames`` is the encoder input (stub frontend embeddings) and ``tokens``
    the decoder prompt.
    """
    fam = cfg.family
    eps = cfg.rms_eps
    dt = jnp.dtype(cfg.dtype)
    if inputs_embeds is not None:
        text = embed_tokens(params, cfg, tokens)
        h = jnp.concatenate([inputs_embeds.astype(text.dtype), text], axis=1)
    else:
        h = embed_tokens(params, cfg, tokens)
    b, s = h.shape[0], h.shape[1]
    if s > max_len:
        raise ValueError(f"prompt length {s} exceeds cache capacity {max_len}")
    positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    cache = init_cache(
        cfg, b, max_len, enc_len=frames.shape[1] if frames is not None else 0
    )

    def pad_kv(k):  # (B, S, Hkv, D) -> (B, max_len, Hkv, D)
        return jnp.pad(k, ((0, 0), (0, max_len - s), (0, 0), (0, 0))).astype(dt)

    if fam in ("dense", "vlm", "moe"):
        # run the layer normally; re-project k/v from the normed input for the
        # cache (cheap relative to attention itself, keeps one code path)
        if fam == "moe":
            def body(carry, lp):
                x = rms_norm(carry, lp["attn_norm"], eps)
                _, k, v = _qkv(lp["attn"], x, cfg, positions)
                carry, _aux = _moe_layer(lp, carry, cfg, positions)
                return carry, (pad_kv(k), pad_kv(v))
        else:
            def body(carry, lp):
                x = rms_norm(carry, lp["attn_norm"], eps)
                _, k, v = _qkv(lp["attn"], x, cfg, positions)
                carry = _attn_layer(lp, carry, cfg, positions)
                return carry, (pad_kv(k), pad_kv(v))

        h, (ks, vs) = _scan(cfg, body, h, params["layers"])
        cache.update(k=ks, v=vs, pos=jnp.int32(s))

    elif fam == "ssm":
        def body(carry, lp):
            x = rms_norm(carry, lp["norm"], eps)
            y, c = ssd_block(lp["ssd"], x, cfg, return_cache=True)
            return carry + y, c

        h, cs = _scan(cfg, body, h, params["layers"])
        cache.update(ssm=cs, pos=jnp.int32(s))

    elif fam == "hybrid":
        per = cfg.shared_attn_every
        groups = cfg.n_layers // per
        stacked = jax.tree.map(
            lambda x: x.reshape(groups, per, *x.shape[1:]), params["layers"]
        )
        shared = params["shared"]

        def group_body(carry, gp):
            def inner(c, lp):
                x = rms_norm(c, lp["norm"], eps)
                y, sc = ssd_block(lp["ssd"], x, cfg, return_cache=True)
                return c + y, sc

            c, scs = _scan(cfg, inner, carry, gp)
            x = rms_norm(c, shared["attn_norm"], eps)
            _, k, v = _qkv(shared["attn"], x, cfg, positions)
            c = _attn_layer(shared, c, cfg, positions)
            return c, (scs, pad_kv(k), pad_kv(v))

        h, (scs, ks, vs) = _scan(cfg, group_body, h, stacked)
        cache.update(
            ssm=jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), scs),
            k=ks,
            v=vs,
            pos=jnp.int32(s),
        )
        if "trailing" in params:
            def body(c, lp):
                x = rms_norm(c, lp["norm"], eps)
                y, sc = ssd_block(lp["ssd"], x, cfg, return_cache=True)
                return c + y, sc

            h, trail = _scan(cfg, body, h, params["trailing"])
            cache["ssm_trailing"] = trail

    elif fam in ("encdec", "audio"):
        enc = frames.astype(dt)
        enc_pos = jnp.arange(enc.shape[1], dtype=jnp.int32)[None, :]

        def enc_body(carry, lp):
            return _attn_layer(lp, carry, cfg, enc_pos, causal=False), None

        enc, _ = _scan(cfg, enc_body, enc, params["enc_layers"])
        enc = rms_norm(enc, params["enc_norm"], cfg.rms_eps)

        def dec_body(carry, lp):
            ek, ev = encode_kv(lp["cross_attn"], enc, cfg)
            x = rms_norm(carry, lp["attn_norm"], eps)
            _, k, v = _qkv(lp["attn"], x, cfg, positions)
            carry = _dec_layer(lp, carry, ek, ev, cfg, positions)
            return carry, (pad_kv(k), pad_kv(v), ek.astype(dt), ev.astype(dt))

        h, (ks, vs, eks, evs) = _scan(cfg, dec_body, h, params["layers"])
        cache.update(k=ks, v=vs, cross_k=eks, cross_v=evs, pos=jnp.int32(s))
    else:
        raise ValueError(fam)

    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    return unembed(params, cfg, h[:, -1:, :]), cache


def decode_step(
    params: Params, cfg: ModelConfig, cache: Params, tokens: jax.Array
) -> tuple[jax.Array, Params]:
    """One decode step.  tokens: (B, 1).  Returns (logits (B,1,V), cache)."""
    h = embed_tokens(params, cfg, tokens)
    pos = cache["pos"]
    fam = cfg.family
    eps = cfg.rms_eps

    if fam in ("dense", "vlm", "moe"):
        def body(carry, xs):
            lp, ck, cv = xs
            x = rms_norm(carry, lp["attn_norm"], eps)
            y, nk, nv = attention_decode(lp["attn"], x, ck, cv, pos, cfg)
            carry = carry + y
            x = rms_norm(carry, lp["mlp_norm"], eps)
            if fam == "moe":
                m, _ = moe_block(lp["moe"], x, cfg, dropless=True)
            else:
                m = mlp(lp["mlp"], x, cfg)
            return carry + m, (nk, nv)

        h, (nk, nv) = _scan(cfg, body, h, (params["layers"], cache["k"], cache["v"]))
        new_cache = {**cache, "k": nk, "v": nv, "pos": pos + 1}

    elif fam == "ssm":
        def body(carry, xs):
            lp, c = xs
            x = rms_norm(carry, lp["norm"], eps)
            y, nc = ssd_decode(lp["ssd"], x, c, cfg)
            return carry + y, nc

        h, ncache = _scan(cfg, body, h, (params["layers"], cache["ssm"]))
        new_cache = {**cache, "ssm": ncache, "pos": pos + 1}

    elif fam == "hybrid":
        per = cfg.shared_attn_every
        groups = cfg.n_layers // per
        stacked = jax.tree.map(
            lambda x: x.reshape(groups, per, *x.shape[1:]), params["layers"]
        )
        sstack = jax.tree.map(
            lambda x: x.reshape(groups, per, *x.shape[1:]), cache["ssm"]
        )
        shared = params["shared"]

        def group_body(carry, xs):
            gp, gc, ck, cv = xs

            def inner(c, ys):
                lp, sc = ys
                x = rms_norm(c, lp["norm"], eps)
                y, nsc = ssd_decode(lp["ssd"], x, sc, cfg)
                return c + y, nsc

            c, nsc = _scan(cfg, inner, carry, (gp, gc))
            x = rms_norm(c, shared["attn_norm"], eps)
            y, nk, nv = attention_decode(shared["attn"], x, ck, cv, pos, cfg)
            c = c + y
            c = c + mlp(shared["mlp"], rms_norm(c, shared["mlp_norm"], eps), cfg)
            return c, (nsc, nk, nv)

        h, (nsc, nk, nv) = _scan(cfg, 
            group_body, h, (stacked, sstack, cache["k"], cache["v"])
        )
        new_cache = {
            **cache,
            "ssm": jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), nsc),
            "k": nk,
            "v": nv,
            "pos": pos + 1,
        }
        if "ssm_trailing" in cache:
            def body(c, ys):
                lp, sc = ys
                x = rms_norm(c, lp["norm"], eps)
                y, nsc2 = ssd_decode(lp["ssd"], x, sc, cfg)
                return c + y, nsc2

            h, ntrail = _scan(cfg, body, h, (params["trailing"], cache["ssm_trailing"]))
            new_cache["ssm_trailing"] = ntrail

    elif fam in ("encdec", "audio"):
        def body(carry, xs):
            lp, ck, cv, xk, xv = xs
            x = rms_norm(carry, lp["attn_norm"], eps)
            y, nk, nv = attention_decode(lp["attn"], x, ck, cv, pos, cfg)
            carry = carry + y
            x = rms_norm(carry, lp["cross_norm"], eps)
            carry = carry + cross_attention(lp["cross_attn"], x, xk, xv, cfg)
            x = rms_norm(carry, lp["mlp_norm"], eps)
            return carry + mlp(lp["mlp"], x, cfg), (nk, nv)

        h, (nk, nv) = _scan(cfg, 
            body,
            h,
            (params["layers"], cache["k"], cache["v"], cache["cross_k"], cache["cross_v"]),
        )
        new_cache = {**cache, "k": nk, "v": nv, "pos": pos + 1}
    else:
        raise ValueError(fam)

    h = rms_norm(h, params["final_norm"], cfg.rms_eps)
    return unembed(params, cfg, h), new_cache
