"""Compressed + popcount-ordered gradient all-reduce (explicit-DP path).

Distributed-optimization tricks for the ICI collective term (DESIGN.md §5):

  * **bf16 wire**: grads cross ICI as bfloat16 (2x fewer bytes than fp32).
  * **int8 + error feedback**: blockwise symmetric int8 with *shared* scales
    (one cheap fp32 max-reduce per block), int16 wire accumulation (exact for
    DP degree <= 258), and an error-feedback buffer carrying quantization
    residue to the next step (EF-SGD semantics).
  * **popcount-ordered egress** (the paper's technique on ICI): a *static*
    permutation — derived from the corresponding weight bytes via
    ``repro.traffic.egress_permutation``, identical on all replicas, so the
    reduction stays aligned — reorders the int8 wire image so flits with
    similar Hamming weight are adjacent.  BT reduction is measured by the
    ``repro.link`` TX pipeline (DESIGN.md §8).

These run inside ``shard_map`` over the data axes, where the wire format is
explicit; the GSPMD path keeps implicit fp32 all-reduce.
"""

from __future__ import annotations

import dataclasses
from typing import Literal, Optional

import jax
import jax.numpy as jnp
from jax import lax

Mode = Literal["none", "bf16", "int8_ef"]


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    mode: Mode = "none"
    block: int = 256
    # static egress permutation (see repro.traffic); applied to the int8
    # wire image before the collective and inverted after.
    use_egress_ordering: bool = False


def _blockify(x: jax.Array, block: int) -> tuple[jax.Array, int]:
    m = x.shape[0]
    pad = (-m) % block
    return jnp.pad(x, (0, pad)), m


def compressed_psum(
    g: jax.Array,
    error: jax.Array,
    cfg: CompressionConfig,
    axis_names: tuple[str, ...],
    perm: Optional[jax.Array] = None,
    inv_perm: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """All-reduce a flat fp32 gradient vector with compression + EF.

    Must be called inside ``shard_map`` with ``axis_names`` bound.  Returns
    (summed gradient fp32 (same shape as g), new error buffer).
    """
    if cfg.mode == "none":
        return lax.psum(g, axis_names), error

    if cfg.mode == "bf16":
        wire = g.astype(jnp.bfloat16)
        out = lax.psum(wire, axis_names).astype(jnp.float32)
        return out, error  # rounding error is not fed back in bf16 mode

    # --- int8_ef ---
    x = g + error
    xb, m = _blockify(x, cfg.block)
    rows = xb.shape[0] // cfg.block
    xr = xb.reshape(rows, cfg.block)
    local_amax = jnp.max(jnp.abs(xr), axis=1)
    # shared scales: one fp32 max-reduce per block keeps dequantization exact
    amax = lax.pmax(local_amax, axis_names)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(xr / scale[:, None]), -127, 127).astype(jnp.int8)
    dq_local = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)[:m]
    new_error = x - dq_local

    wire = q.reshape(-1)
    if cfg.use_egress_ordering and perm is not None:
        wire = wire[perm]  # static, replica-identical: reduction stays aligned
    acc = lax.psum(wire.astype(jnp.int16), axis_names)  # 2-byte wire accum
    if cfg.use_egress_ordering and inv_perm is not None:
        acc = acc[inv_perm]
    out = (acc.astype(jnp.float32).reshape(rows, cfg.block) * scale[:, None]).reshape(-1)
    return out[:m], new_error


def init_error_buffer(params_flat_size: int) -> jax.Array:
    return jnp.zeros((params_flat_size,), jnp.float32)
