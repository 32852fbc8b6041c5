"""Pallas TPU kernel: blockwise int8 egress quantizer.

Used by the compressed gradient all-reduce path (``repro.optim.compress``):
gradients are quantized to symmetric int8 per block before crossing ICI, and
the popcount-ordered egress permutation is applied to the int8 view.  The
kernel fuses abs-max reduction, scale computation and rounding in one VMEM
pass per block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["quantize_egress_pallas", "quantize_egress_compiled"]


# max|x| / 127 as a product with the rounded reciprocal: what XLA already
# computes for a division by this constant under jit, spelled out so the
# Mosaic kernel and the jnp backend round the scale identically
_INV_127 = np.float32(1.0 / 127.0)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]  # (R, block) float32
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)  # (R, 1)
    scale = amax * _INV_127
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def quantize_egress_pallas(
    x: jax.Array,
    *,
    block: int = 256,
    rows_per_step: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Quantize a flat float32 vector to blockwise-symmetric int8.

    Args:
      x: (M,) float32 with M divisible by ``block`` (wrapper pads).
      rows_per_step: quantization blocks per grid step, a multiple of 32
        (the int8 sublane tile); the block rows are zero-padded to a
        multiple of it (a zero block quantizes to zeros with scale 0) and
        trimmed again.

    Returns:
      (q, scales): int8 (M,), float32 (M / block,).
    """
    m = x.shape[0]
    if m % block != 0:
        raise ValueError(f"size {m} not divisible by block {block}")
    if rows_per_step % 32 != 0:
        raise ValueError(
            f"rows_per_step={rows_per_step} is not a multiple of 32"
        )
    rows = m // block
    rp = min(rows_per_step, rows)  # a short vector is one full block
    padded = -(-rows // rp) * rp
    xr = jnp.pad(
        x.reshape(rows, block).astype(jnp.float32),
        ((0, padded - rows), (0, 0)),
    )
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(padded // rp,),
        in_specs=[pl.BlockSpec((rp, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rp, block), lambda i: (i, 0)),
            pl.BlockSpec((rp, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded, block), jnp.int8),
            jax.ShapeDtypeStruct((padded, 1), jnp.float32),
        ],
        interpret=interpret,
        name="quantize_kernel",
    )(xr)
    return q[:rows].reshape(m), s[:rows, 0]


def quantize_egress_compiled(
    x: jax.Array, *, block: int = 256
) -> tuple[jax.Array, jax.Array]:
    """The compiled (pure-jnp) backend: the kernel's abs-max / scale /
    round math as one reshaped pass — same primitives and dtypes, so the
    int8 codes and float32 scales are bit-identical."""
    m = x.shape[0]
    if m % block != 0:
        raise ValueError(f"size {m} not divisible by block {block}")
    rows = m // block
    xr = x.reshape(rows, block).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xr), axis=1)
    scale = amax * _INV_127
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xr / safe[:, None]), -127, 127).astype(jnp.int8)
    return q.reshape(m), scale
