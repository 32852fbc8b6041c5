"""Pallas TPU kernel: bit-transition counting over a flit stream.

The BT metric (Hamming distance between consecutive flits, summed) is the
paper's evaluation workhorse; at framework scale we run it over multi-GB
modeled traffic (weights, activations, collective payloads), so it gets a
kernel.  The wrapper presents the stream twice (rows [0, T-1) and rows
[1, T)) so each grid step reduces one (R, L) block of XOR popcounts with no
cross-block carry; per-block partials land in a (G, 1, 1) output (a block
whose two minor dims equal the array's, as Mosaic requires) reduced by the
caller.  Memory-bound by design: one pass over the stream, 8 ops/byte.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .psu import _popcount_bits

__all__ = ["bt_count_pallas", "bt_count_compiled"]


def _bt_kernel(a_ref, b_ref, out_ref, *, width: int):
    a = a_ref[...].astype(jnp.int32)
    b = b_ref[...].astype(jnp.int32)
    flips = jnp.bitwise_xor(a, b)
    out_ref[0] = _popcount_bits(flips, width).sum(keepdims=True)


def bt_count_pallas(
    stream: jax.Array,
    *,
    width: int = 8,
    block_rows: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Total bit transitions of a (T, L) flit stream (int32 scalar).

    Rows are consecutive flits, columns are byte lanes.  ``T - 1`` boundary
    rows are padded (with zeros on *both* shifted views, so pads contribute
    zero) to a multiple of ``block_rows``.
    """
    t, lanes = stream.shape
    if t < 2:
        return jnp.int32(0)
    a = stream[:-1].astype(jnp.int32)
    b = stream[1:].astype(jnp.int32)
    rows = t - 1
    pad = (-rows) % block_rows
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)))
        b = jnp.pad(b, ((0, pad), (0, 0)))
    grid = ((rows + pad) // block_rows,)
    kern = functools.partial(_bt_kernel, width=width)
    spec = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    partials = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(grid + (1, 1), jnp.int32),
        interpret=interpret,
        name="bt_count_kernel",
    )(a, b)
    return partials.sum()


def bt_count_compiled(stream: jax.Array, *, width: int = 8) -> jax.Array:
    """The compiled (pure-jnp) backend: one XOR-popcount reduction.

    Same contract and result as :func:`bt_count_pallas` (exact — integer
    popcount sums have one value).
    """
    t = stream.shape[0]
    if t < 2:
        return jnp.int32(0)
    x = stream.astype(jnp.int32)
    return _popcount_bits(x[1:] ^ x[:-1], width).sum().astype(jnp.int32)
