"""Popcount-ordering applied to real model traffic — the paper's technique
as a first-class framework feature (DESIGN.md §3.3).

This module owns the *model-side* integration points (which tensors may be
permuted, and how, without changing results); the stream mechanics (encode /
row-bucket keys / flit layout / BT measurement) live in the unified TX
pipeline, :mod:`repro.link`, and are delegated to it.

Three integration points, all exploiting order-insensitive accumulation:

  1. **Contraction-axis weight permutation** (`apply_mlp_ordering`,
     `apply_head_ordering`): for ``y = act(x @ Wg, x @ Wu) @ Wd`` the d_ff
     axis order is free — permuting Wg/Wu columns together with Wd rows is a
     numeric no-op (up to fp addition order).  We order d_ff rows by the
     popcount bucket of their int8-quantized bytes so the *weight stream*
     (HBM -> VMEM during decode; the dominant decode traffic) has monotone
     Hamming weight — the TPU analogue of the paper's link ordering.
     Attention heads are permuted analogously (KV-head groups move with
     their q-head blocks and output rows).

  2. **Gradient egress permutation** (`egress_permutation`): a static
     permutation of the int8 gradient wire image, derived from the weight
     bytes so it is identical on every replica (value-dependent per-step
     sorting would desynchronise the reduction — recorded as an adaptation
     from the paper's per-packet sorting, DESIGN.md §8).

  3. **BT accounting** (`stream_bt_report`): models any tensor as a 128-bit
     flit stream and measures bit transitions before/after ordering via a
     ``repro.link.TxPipeline`` row-stream measurement — this is what
     ``benchmarks/arch_bt.py`` reports.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.link import LinkSpec, TxPipeline, row_bucket_keys as _link_row_bucket_keys
from repro.link import tensor_flit_stream, to_sign_magnitude  # noqa: F401  (re-export)
from repro.models.config import ModelConfig

Strategy = Literal["none", "acc", "app"]


def _row_levels(strategy: Strategy, k: int) -> int:
    """ACC keeps the element-granularity 9-level mapping; APP coarsens to k."""
    return 9 if strategy == "acc" else k


# --------------------------------------------------------------------------
# int8 views and popcount keys
# --------------------------------------------------------------------------


def int8_view(w: jax.Array) -> jax.Array:
    """Symmetric per-tensor int8 quantization of a weight tensor (the wire /
    HBM-stream image used for BT accounting and ordering keys)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)))
    scale = jnp.maximum(amax / 127.0, 1e-12)
    return jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)


def row_bucket_keys(
    rows_int8: jax.Array, strategy: Strategy, k: int = 4
) -> jax.Array:
    """Bucket key per row of an (R, B) int8 matrix (see
    :func:`repro.link.row_bucket_keys` for the mapping)."""
    return _link_row_bucket_keys(
        rows_int8.astype(jnp.uint8), _row_levels(strategy, k)
    )


def row_order(rows_int8: jax.Array, strategy: Strategy, k: int = 4) -> jax.Array:
    """Stable comparison-free sort order of rows by popcount bucket."""
    if strategy == "none":
        return jnp.arange(rows_int8.shape[0], dtype=jnp.int32)
    pipe = TxPipeline(_row_spec(strategy, k, sign_magnitude=False, layout="row"))
    return pipe.row_order(rows_int8.astype(jnp.uint8))


# --------------------------------------------------------------------------
# contraction-axis weight permutation (numeric no-op graph rewrites)
# --------------------------------------------------------------------------


def mlp_permutation(mlp_params: dict, strategy: Strategy, k: int = 4) -> jax.Array:
    """d_ff permutation keyed on the down-projection rows (streamed axis)."""
    down = mlp_params["down"]  # (ff, d)
    return row_order(int8_view(down), strategy, k)


def apply_mlp_ordering(
    mlp_params: dict, perm: jax.Array
) -> dict:
    """Permute the d_ff axis: gate/up columns and down rows move together."""
    out = dict(mlp_params)
    if "gate" in out:
        out["gate"] = out["gate"][..., perm]
    out["up"] = out["up"][..., perm]
    out["down"] = jnp.take(out["down"], perm, axis=-2)
    return out


def head_permutation(attn_params: dict, cfg: ModelConfig, strategy: Strategy, k: int = 4) -> jax.Array:
    """KV-head-group permutation keyed on wk bytes (groups move atomically
    so GQA head->group mapping is preserved)."""
    wk = attn_params["wk"]  # (d, Hkv, hd)
    hkv = wk.shape[-2]
    rows = int8_view(wk).transpose(1, 0, 2).reshape(hkv, -1)
    return row_order(rows, strategy, k)


def apply_head_ordering(attn_params: dict, cfg: ModelConfig, perm: jax.Array) -> dict:
    """Permute KV-head groups (wk/wv) and the matching q-head blocks (wq/wo)."""
    out = dict(attn_params)
    rep = cfg.q_rep
    hkv = out["wk"].shape[-2]
    out["wk"] = jnp.take(out["wk"], perm, axis=-2)
    out["wv"] = jnp.take(out["wv"], perm, axis=-2)
    d, h, hd = out["wq"].shape
    wq = out["wq"].reshape(d, hkv, rep, hd)
    out["wq"] = jnp.take(wq, perm, axis=1).reshape(d, h, hd)
    wo = out["wo"].reshape(hkv, rep, hd, -1)
    out["wo"] = jnp.take(wo, perm, axis=0).reshape(h, hd, -1)
    return out


def apply_weight_ordering(
    params: dict, cfg: ModelConfig, strategy: Strategy = "app", k: int = 4
) -> dict:
    """Order every layer's MLP d_ff axis and attention KV groups.

    Layer-stacked params get per-layer permutations via vmap.  Returns a new
    params pytree; model outputs are unchanged up to fp summation order
    (verified in tests/test_traffic.py).
    """
    if strategy == "none":
        return params
    out = dict(params)

    def order_layer(lp: dict) -> dict:
        lp = dict(lp)
        if "mlp" in lp:
            perm = mlp_permutation(lp["mlp"], strategy, k)
            lp["mlp"] = apply_mlp_ordering(lp["mlp"], perm)
        if "attn" in lp:
            perm = head_permutation(lp["attn"], cfg, strategy, k)
            lp["attn"] = apply_head_ordering(lp["attn"], cfg, perm)
        return lp

    for key in ("layers", "enc_layers", "trailing"):
        if key in out and isinstance(out[key], dict) and (
            "mlp" in out[key] or "attn" in out[key]
        ):
            out[key] = jax.vmap(order_layer)(out[key])
    if "shared" in out:
        out["shared"] = order_layer(out["shared"])
    return out


# --------------------------------------------------------------------------
# gradient egress permutation (static, replica-identical)
# --------------------------------------------------------------------------


def _host_bitwise_count(bytes_u8: np.ndarray) -> np.ndarray:
    """Host-side per-byte popcount with a NumPy<2 fallback.

    ``np.bitwise_count`` is NumPy 2.x only; older NumPy gets the
    ``unpackbits`` formulation (identical results for uint8 views).
    """
    if hasattr(np, "bitwise_count"):
        return np.bitwise_count(bytes_u8)
    return np.unpackbits(bytes_u8[..., None], axis=-1).sum(axis=-1)


def egress_permutation(
    weights_flat_int8: jax.Array, packet: int = 64, strategy: Strategy = "app", k: int = 4
) -> tuple[np.ndarray, np.ndarray]:
    """Static wire permutation: int8 positions grouped into ``packet``-byte
    packets, packets ordered within by the *weight* byte popcount bucket.

    Returns (perm, inv_perm) as numpy int32 (host-side, computed once).
    """
    m = weights_flat_int8.shape[0]
    usable = (m // packet) * packet
    w = np.asarray(weights_flat_int8[:usable]).reshape(-1, packet)
    bits = _host_bitwise_count(w.view(np.uint8)).astype(np.int32)
    levels = _row_levels(strategy, k)
    keys = (bits * levels) // 9
    order = np.argsort(keys, axis=1, kind="stable")
    base = np.arange(0, usable, packet, dtype=np.int64)[:, None]
    perm = (base + order).reshape(-1)
    perm = np.concatenate([perm, np.arange(usable, m, dtype=np.int64)])
    inv = np.empty_like(perm)
    inv[perm] = np.arange(m, dtype=np.int64)
    return perm.astype(np.int32), inv.astype(np.int32)


# --------------------------------------------------------------------------
# BT accounting over modeled flit streams (delegates to repro.link)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BTStreamReport:
    name: str
    num_flits: int
    bt_none: float
    bt_ordered: float

    @property
    def reduction(self) -> float:
        return 1.0 - self.bt_ordered / max(self.bt_none, 1e-9)


def _row_spec(
    strategy: Strategy, k: int, sign_magnitude: bool, layout: str
) -> LinkSpec:
    return LinkSpec(
        width_bits=128,
        flits_per_packet=1,
        input_lanes=16,
        weight_lanes=0,
        key="none" if strategy == "none" else "row_bucket",
        encode="sign_magnitude" if sign_magnitude else "identity",
        pack="col" if layout == "col" else "row",
        k=_row_levels(strategy, k),
    )


def stream_bt_report(
    name: str,
    tensor: jax.Array,
    strategy: Strategy = "app",
    k: int = 4,
    row_axis: int = -2,
    lanes: int = 16,
    sign_magnitude: bool = False,
    layout: Literal["row", "col"] = "row",
) -> BTStreamReport:
    """BT of streaming ``tensor`` before/after popcount row ordering.

    ``layout="row"`` streams whole rows (the HBM-natural order; row ordering
    only touches row-boundary flits).  ``layout="col"`` interleaves rows
    column-major so consecutive flits carry *adjacent rows in the sorted
    order* — the layout under which row ordering has leverage (see the
    measured trade-off in ``benchmarks/arch_bt.py``).

    Implemented as two ``repro.link.TxPipeline`` row-stream measurements
    (baseline spec with key='none', ordered spec as configured).
    """
    t8 = int8_view(tensor)
    mat = jnp.moveaxis(t8, row_axis, 0).reshape(t8.shape[row_axis], -1)
    # encode is part of BOTH specs: the baseline wire image is the encoded
    # one, so the report isolates the *ordering* gain (the encoding gain is
    # measured by comparing reports with sign_magnitude on/off)
    base_spec = dataclasses.replace(
        _row_spec("none", k, sign_magnitude, layout),
        width_bits=lanes * 8, input_lanes=lanes,
    )
    ord_spec = dataclasses.replace(
        _row_spec(strategy, k, sign_magnitude, layout),
        width_bits=lanes * 8, input_lanes=lanes,
    )
    base = TxPipeline(base_spec).measure_rows(mat, name=name)
    ordered = TxPipeline(ord_spec).measure_rows(mat, name=name)
    return BTStreamReport(name, base.num_flits, base.total_bt, ordered.total_bt)
