"""Mixture-of-Experts layer: top-k routing with grouped, capacity-bounded
dispatch (GShard-style) sized so the dispatch tensors stay small, or
dropless dispatch as one grouped product per projection
(``MoEConfig.dropless`` and the decode step, :func:`grouped_moe_block`).

Memory shape analysis (DESIGN.md §5): with group size ``gs`` the dispatch
one-hot is (G, gs, E, C) with C = gs*k*cf/E, i.e. total = T * gs * k * cf
elements *independent of E* — small groups bound dispatch memory.  The
choice-level one-hot (G, gs*k, E, C) is never materialised: dispatch/combine
are accumulated over the k choices in a short unrolled loop.

Expert sharding: experts live on the "model" mesh axis.  Counts that don't
divide the axis (granite's 40) are padded (``pad_experts_to``) and the router
masks padded experts to -inf, so they receive no tokens and contribute no
FLOPs worth of useful work but keep GSPMD shardings legal.

Token permutation hook (the paper's technique): tokens inside an expert's
capacity buffer are an *unordered set* — ``repro.traffic`` exploits this by
popcount-bucket-ordering dispatch buffers before they cross ICI.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import dense_init

Params = Dict[str, Any]


def init_moe(key: jax.Array, cfg: ModelConfig) -> Params:
    m = cfg.moe
    pdt = jnp.dtype(cfg.param_dtype)
    d, ff = cfg.d_model, m.d_ff_expert
    e = m.padded_experts
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": dense_init(ks[0], (d, e), d, pdt),
        "gate": dense_init(ks[1], (e, d, ff), d, pdt),
        "up": dense_init(ks[2], (e, d, ff), d, pdt),
        "down": dense_init(ks[3], (e, ff, d), ff, pdt),
    }
    if m.num_shared_experts:
        p["shared_gate"] = dense_init(ks[4], (d, ff * m.num_shared_experts), d, pdt)
        p["shared_up"] = dense_init(ks[4], (d, ff * m.num_shared_experts), d, pdt)
        p["shared_down"] = dense_init(ks[4], (ff * m.num_shared_experts, d), ff, pdt)
    return p


def capacity(cfg: ModelConfig, group_size: int) -> int:
    m = cfg.moe
    return max(1, math.ceil(group_size * m.top_k * m.capacity_factor / m.num_experts))


def moe_block(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    dropless: bool = False,
    return_route: bool = False,
):
    """Top-k MoE.  x: (B, S, d) -> (y, aux_loss).

    ``dropless=True`` (or a config with ``moe.dropless``) takes the
    dropless grouped path (:func:`grouped_moe_block`), where no token can
    ever be dropped; the decode path uses it, where capacity drops would
    make serving non-deterministic w.r.t. batch composition.

    ``return_route=True`` appends the dispatch's routing to the result,
    ``{"x": (T, d) rows in the compute dtype, "expert_ids": (T, k),
    "probs": (T, E) float32}``: what the ``moe.dispatch`` tap records.
    """
    if dropless or cfg.moe.dropless:
        return grouped_moe_block(params, x, cfg, return_route)
    m = cfg.moe
    dt = x.dtype
    bsz, s, d = x.shape
    t = bsz * s
    gs = min(m.group_size, t)
    if t % gs:
        gs = t  # smoke-test fallback: one group
    g = t // gs
    c = min(capacity(cfg, gs), gs)
    e = m.padded_experts
    xg = x.reshape(g, gs, d)

    logits = (xg @ params["router"].astype(dt)).astype(jnp.float32)  # (G,gs,E)
    if e > m.num_experts:  # mask padded experts
        pad_mask = jnp.arange(e) >= m.num_experts
        logits = jnp.where(pad_mask[None, None, :], -1e30, logits)
    probs_all = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs_all, m.top_k)  # (G,gs,k)
    if m.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert's capacity buffer
    flat_e = top_e.reshape(g, gs * m.top_k)
    oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (G, gs*k, E)
    pos_all = jnp.cumsum(oh, axis=1) - 1  # (G, gs*k, E)
    pos = jnp.take_along_axis(pos_all, flat_e[..., None], axis=-1)[..., 0]
    pos = pos.reshape(g, gs, m.top_k)
    keep = pos < c

    dispatch = jnp.zeros((g, gs, e, c), dt)
    combine = jnp.zeros((g, gs, e, c), dt)
    for j in range(m.top_k):  # accumulate per choice; never materialise k*E*C
        ohe = jax.nn.one_hot(top_e[:, :, j], e, dtype=dt)
        ohc = jax.nn.one_hot(pos[:, :, j], c, dtype=dt)
        sel = (ohe[..., :, None] * ohc[..., None, :]) * keep[:, :, j, None, None].astype(dt)
        dispatch = dispatch + sel
        combine = combine + sel * top_p[:, :, j, None, None].astype(dt)

    expert_in = jnp.einsum("gsec,gsd->gecd", dispatch, xg)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, params["gate"].astype(dt)))
    h = h * jnp.einsum("gecd,edf->gecf", expert_in, params["up"].astype(dt))
    expert_out = jnp.einsum("gecf,efd->gecd", h, params["down"].astype(dt))
    y = jnp.einsum("gsec,gecd->gsd", combine, expert_out)

    if m.num_shared_experts:
        y = y + _shared_experts(params, xg)
    aux = _balance_loss(probs_all, top_e, cfg)
    if return_route:
        route = {"x": xg.reshape(t, d), "expert_ids": top_e.reshape(t, -1),
                 "probs": probs_all.reshape(t, e)}
        return y.reshape(bsz, s, d), aux, route
    return y.reshape(bsz, s, d), aux


def _shared_experts(params: Params, x: jax.Array) -> jax.Array:
    """The always-on shared experts, one SwiGLU of their summed width."""
    dt = x.dtype
    sh = jax.nn.silu(x @ params["shared_gate"].astype(dt)) * (
        x @ params["shared_up"].astype(dt)
    )
    return sh @ params["shared_down"].astype(dt)


def _balance_loss(probs_all: jax.Array, top_e: jax.Array,
                  cfg: ModelConfig) -> jax.Array:
    """Switch-style load-balance loss over the real experts."""
    m = cfg.moe
    lead = tuple(range(probs_all.ndim - 1))
    me = probs_all[..., : m.num_experts].mean(axis=lead)  # mean router prob
    ce = (
        jax.nn.one_hot(top_e[..., 0], m.padded_experts,
                       dtype=jnp.float32)[..., : m.num_experts]
        .mean(axis=lead)
    )  # fraction of tokens whose top-1 is e
    aux = jnp.sum(me * ce) * (m.num_experts**1) * m.router_aux_weight
    return aux.astype(jnp.float32)


def grouped_moe_block(
    params: Params, x: jax.Array, cfg: ModelConfig, return_route: bool = False
):
    """Dropless top-k MoE as a grouped product.  x: (B, S, d) -> (y, aux).

    The router runs in float32 (softmax over all experts, greedy top-k,
    renormalized only under ``norm_topk_prob``).  Every (token, choice)
    pair is kept: the pairs are sorted by expert, each expert's SwiGLU
    runs over its contiguous rows as one ``lax.ragged_dot`` per
    projection, and the outputs return to token order, weighted by their
    probabilities and summed in float32.  No (G, E, C, D) buffer exists.
    """
    m = cfg.moe
    dt = x.dtype
    bsz, s, d = x.shape
    t, k, e = bsz * s, m.top_k, m.padded_experts
    xf = x.reshape(t, d)
    logits = xf.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    if e > m.num_experts:  # mask padded experts
        logits = jnp.where(jnp.arange(e) >= m.num_experts, -1e30, logits)
    probs_all = jax.nn.softmax(logits, axis=-1)  # (T, E)
    top_p, top_e = jax.lax.top_k(probs_all, k)  # (T, k)
    if m.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(t * k)
    order = jnp.argsort(flat_e, stable=True)  # pairs grouped by expert
    sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)
    xs = jnp.take(xf, order // k, axis=0)
    h = jax.nn.silu(jax.lax.ragged_dot(xs, params["gate"].astype(dt), sizes))
    h = h * jax.lax.ragged_dot(xs, params["up"].astype(dt), sizes)
    ys = jax.lax.ragged_dot(h, params["down"].astype(dt), sizes)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=order.dtype))
    yk = jnp.take(ys, back, axis=0).reshape(t, k, d)
    y = (yk.astype(jnp.float32) * top_p[..., None]).sum(axis=1).astype(dt)
    if m.num_shared_experts:
        y = y + _shared_experts(params, xf)
    aux = _balance_loss(probs_all, top_e, cfg)
    if return_route:
        route = {"x": xf, "expert_ids": top_e, "probs": probs_all}
        return y.reshape(bsz, s, d), aux, route
    return y.reshape(bsz, s, d), aux
