"""Sharding rules: DP / TP / EP / SP assignment per parameter and input.

Strategy (DESIGN.md §5):
  * batch           -> ("pod","data")  [DP; falls back to sequence (SP) when
                       the batch doesn't divide, e.g. long_500k's batch=1]
  * attention heads -> "model" (TP); GQA archs whose kv-head count doesn't
                       divide the axis shard the contraction (d_model) side
  * d_ff            -> "model" (Megatron column->row pair: one all-reduce)
  * experts         -> "model" (EP; granite pads 40 -> 48 experts)
  * vocab           -> "model" when divisible, else embedding d-axis
  * SSD blocks      -> contraction sharding on in/out projections; SSM head
                       axis of activations/caches on "model"

Every rule guards divisibility and falls back to replication, so every
(arch x shape x mesh) cell is *legal by construction*.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

from .mesh import dp_axes


def _div(n: int, m: int) -> bool:
    return m > 0 and n % m == 0


def _pad_rank(spec: tuple, rank: int) -> P:
    """Left-pad a trailing-dims spec with None up to the leaf rank (covers
    the layer-stack leading axis)."""
    pad = rank - len(spec)
    return P(*((None,) * pad + spec))


def _norm_path(path: str) -> str:
    """Normalize keystr paths: "['layers']['attn']['wq']" -> ".layers.attn.wq"."""
    return (
        path.replace("['", ".").replace("']", "").replace("[", ".").replace("]", "")
    )


def param_spec(
    path: str, shape: tuple[int, ...], cfg: ModelConfig, mesh, mode: str = "train"
) -> P:
    m = mesh.shape["model"]
    path = _norm_path(path)
    name = path.rsplit(".", 1)[-1]
    rank = len(shape)
    if cfg.fsdp and mode == "train":
        return _fsdp_spec(path, name, shape, cfg, mesh)

    if name == "embed":
        v, d = shape
        if _div(v, m):
            return P("model", None)
        if _div(d, m):
            return P(None, "model")
        return P()
    if name == "head":
        d, v = shape
        if _div(v, m):
            return P(None, "model")
        if _div(d, m):
            return P("model", None)
        return P()

    if ".attn" in path or ".cross_attn" in path:
        if name == "wq":
            d, h, hd = shape[-3:]
            if _div(h, m):
                return _pad_rank((None, "model", None), rank)
            if _div(d, m):
                return _pad_rank(("model", None, None), rank)
            return P()
        if name in ("wk", "wv"):
            d, hkv, hd = shape[-3:]
            if _div(hkv, m):
                return _pad_rank((None, "model", None), rank)
            # GQA with kv-heads < TP degree: REPLICATE the (small) kv
            # projections — d-contraction sharding costs an all-gather-heavy
            # backward
            return P()
        if name == "wo":
            h, hd, d = shape[-3:]
            if _div(h, m):
                return _pad_rank(("model", None, None), rank)
            if _div(d, m):
                return _pad_rank((None, None, "model"), rank)
            return P()
        return P()  # q_norm / k_norm / biases

    if ".moe" in path:
        if name == "router":
            d, e = shape[-2:]
            return _pad_rank((None, "model"), rank) if _div(e, m) else P()
        if name in ("gate", "up", "down"):
            e = shape[-3]
            if _div(e, m):
                return _pad_rank(("model", None, None), rank)
            ff_axis = -1 if name in ("gate", "up") else -2
            if _div(shape[ff_axis], m):
                spec = [None, None, None]
                spec[ff_axis] = "model"
                return _pad_rank(tuple(spec), rank)
            return P()
        if name.startswith("shared_"):
            ff_axis = -1 if name in ("shared_gate", "shared_up") else -2
            spec = [None, None]
            if _div(shape[ff_axis], m):
                spec[ff_axis] = "model"
            return _pad_rank(tuple(spec), rank)
        return P()

    if ".mlp" in path:
        if name in ("gate", "up"):
            d, ff = shape[-2:]
            return _pad_rank((None, "model"), rank) if _div(ff, m) else P()
        if name == "down":
            ff, d = shape[-2:]
            return _pad_rank(("model", None), rank) if _div(ff, m) else P()
        return P()

    if ".ssd" in path:
        if name == "in_proj":  # contraction (d_model) sharding
            d = shape[-2]
            return _pad_rank(("model", None), rank) if _div(d, m) else P()
        if name == "out_proj":  # contraction (d_inner) sharding
            di = shape[-2]
            return _pad_rank(("model", None), rank) if _div(di, m) else P()
        return P()  # conv / dt / a_log / norms: small, replicated

    return P()  # norms and anything unmatched: replicated


def _fsdp_spec(path: str, name: str, shape: tuple[int, ...], cfg, mesh) -> P:
    """ZeRO-3-style 2D sharding: "model" on the TP axis as usual, plus the
    largest remaining axis sharded over "data".  GSPMD all-gathers weights
    at use (per layer inside the scan) and reduce-scatters gradients — the
    standard FSDP dataflow, required where fp32 params + Adam exceed HBM."""
    m = mesh.shape["model"]
    d = mesh.shape["data"]
    rank = len(shape)

    def pick(tp_axis: int | None) -> P:
        spec: list = [None] * rank
        if tp_axis is not None:
            spec[tp_axis] = "model"
        # largest un-taken axis divisible by the data-axis size
        best, best_size = None, 0
        for i, s in enumerate(shape):
            if i == tp_axis:
                continue
            if _div(s, d) and s > best_size:
                best, best_size = i, s
        if best is not None:
            spec[best] = "data"
        return P(*spec)

    if name == "embed":
        return pick(0 if _div(shape[0], m) else (1 if _div(shape[1], m) else None))
    if name == "head":
        return pick(1 if _div(shape[1], m) else None)
    if name in ("wq",):
        h = shape[-2]
        return pick(rank - 2 if _div(h, m) else None)
    if name in ("wk", "wv"):
        hkv = shape[-2]
        return pick(rank - 2 if _div(hkv, m) else None)
    if name == "wo":
        h = shape[-3]
        return pick(rank - 3 if _div(h, m) else None)
    if name in ("gate", "up", "down") and ".moe" in path:
        e = shape[-3]
        return pick(rank - 3 if _div(e, m) else None)
    if name == "router":
        return pick(rank - 1 if _div(shape[-1], m) else None)
    if name in ("gate", "up") and ".mlp" in path:
        return pick(rank - 1 if _div(shape[-1], m) else None)
    if name == "down" and ".mlp" in path:
        return pick(rank - 2 if _div(shape[-2], m) else None)
    if name in ("in_proj", "out_proj"):
        return pick(rank - 2 if _div(shape[-2], m) else None)
    # small leaves (norms, biases): replicate
    return P()


def params_shardings(cfg: ModelConfig, mesh, params_shapes: Any, mode: str = "train") -> Any:
    def leaf(path, x):
        spec = param_spec(jax.tree_util.keystr(path), x.shape, cfg, mesh, mode)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf, params_shapes)


def opt_shardings(cfg: ModelConfig, mesh, opt_shapes: Any, params_shapes: Any) -> Any:
    """Optimizer m/v mirror the parameter shardings; step is replicated.

    With ``cfg.zero1`` the m/v leaves are additionally sharded over the
    "data" axis (largest free divisible dim) — ZeRO-1: the optimizer state
    is never replicated across data-parallel ranks; GSPMD reshards grads in
    and all-gathers updated params out.
    """
    p_sh = params_shardings(cfg, mesh, params_shapes)
    if not cfg.zero1:
        return type(opt_shapes)(
            step=NamedSharding(mesh, P()),
            m=p_sh,
            v=jax.tree.map(lambda s: s, p_sh),
        )
    d = mesh.shape["data"]

    def add_data_axis(sh: NamedSharding, shape_leaf) -> NamedSharding:
        spec = list(sh.spec) + [None] * (len(shape_leaf.shape) - len(sh.spec))
        for i, s in enumerate(shape_leaf.shape):
            if spec[i] is None and _div(s, d):
                spec[i] = "data"
                break
        return NamedSharding(mesh, P(*spec))

    mv_sh = jax.tree.map(add_data_axis, p_sh, params_shapes)
    return type(opt_shapes)(
        step=NamedSharding(mesh, P()),
        m=mv_sh,
        v=jax.tree.map(lambda s: s, mv_sh),
    )


# --------------------------------------------------------------------------
# input / cache shardings
# --------------------------------------------------------------------------


def batch_shardings(cfg: ModelConfig, mesh, batch_shapes: dict) -> dict:
    dp = dp_axes(mesh)
    dpn = int(np.prod([mesh.shape[a] for a in dp]))

    def leaf(x):
        b = x.shape[0]
        spec = [None] * len(x.shape)
        if _div(b, dpn):
            spec[0] = dp
        elif len(x.shape) > 1 and _div(x.shape[1], dpn):
            spec[1] = dp  # SP fallback: shard sequence
        return NamedSharding(mesh, P(*spec))

    return {k: leaf(v) for k, v in batch_shapes.items()}


def cache_shardings(cfg: ModelConfig, mesh, cache_shapes: dict) -> dict:
    """KV/SSM cache shardings for decode cells.

    k/v: (L, B, S, Hkv, hd)  -> B over DP (or S when B=1: SP), Hkv over model
    ssm state: (L, B, H, N, P) -> B over DP, H over model
    conv: (L, B, K, C) -> B over DP, C over model
    """
    dp = dp_axes(mesh)
    dpn = int(np.prod([mesh.shape[a] for a in dp]))
    m = mesh.shape["model"]

    def kv(x):
        l, b, s, hkv, hd = x.shape
        spec: list = [None] * 5
        if _div(b, dpn):
            spec[1] = dp
        elif _div(s, dpn):
            spec[2] = dp
        if _div(hkv, m):
            spec[3] = "model"
        elif spec[2] is None and _div(s, m):
            # GQA archs with kv-heads < model axis: shard the KV sequence
            # instead (flash-decoding-style split-K) — removes both the
            # replicated-cache memory and the redundant attention compute
            spec[2] = "model"
        return NamedSharding(mesh, P(*spec))

    def ssm_state(x):
        l, b, h, n, p = x.shape
        spec: list = [None] * 5
        if _div(b, dpn):
            spec[1] = dp
        if _div(h, m):
            spec[2] = "model"
        return NamedSharding(mesh, P(*spec))

    def conv(x):
        l, b, k, c = x.shape
        spec: list = [None] * 4
        if _div(b, dpn):
            spec[1] = dp
        if _div(c, m):
            spec[3] = "model"
        return NamedSharding(mesh, P(*spec))

    out: dict = {}
    for key, val in cache_shapes.items():
        if key == "pos":
            out[key] = NamedSharding(mesh, P())
        elif key in ("k", "v", "cross_k", "cross_v"):
            out[key] = kv(val)
        elif key in ("ssm", "ssm_trailing"):
            out[key] = {"state": ssm_state(val["state"]), "conv": conv(val["conv"])}
        else:
            out[key] = NamedSharding(mesh, P())
    return out


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
