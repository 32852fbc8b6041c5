"""Pallas TPU kernel: the popcount-sorting unit (ACC-PSU / APP-PSU).

One grid step sorts a *block of packets* resident in VMEM, reproducing the
hardware dataflow of Fig. 1 stage-for-stage on TPU vector units
(DESIGN.md §3):

  popcount stage   -> bit-twiddling on int32 lanes (VPU), replacing the
                      4-bit LUT + adder tree,
  bucket encoder   -> integer multiply/divide (APP only; compiled away for
                      ACC exactly as the paper's synthesis prunes the LUT),
  one-hot + histogram + prefix sum + index mapping
                   -> one (BP, N) 0/1 one-hot per bucket, the earlier-equal
                      counts of every bucket as ONE exact 0/1 product with
                      the strictly-triangular (N, N) matrix on the MXU,
                      histograms and start addresses unrolled over the nb
                      buckets, rank = start[key] + #earlier-equal — the
                      hardware's addresses in O(N * nb) vector work per
                      packet, with no comparison between elements and no
                      in-kernel scan,
  scatter SRAM write
                   -> a one-hot compare + weighted sum (no random-access
                      writes).

Block shapes: packets are (BP, N) int32 in VMEM; the scatter's (BP, N, N)
one-hot bounds VMEM use, so BP defaults to 64 packets (N=64: 1 MiB per
int32 temporary, well inside a v5e core's VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

__all__ = ["psu_sort_pallas", "psu_sort_compiled"]


def _popcount_bits(x: jax.Array, width: int) -> jax.Array:
    """Branch-free popcount of the low ``width`` bits of int32 lanes.

    SWAR bit-twiddling; valid for width <= 16 (paper uses W=8).  This is the
    VPU replacement for the hardware 4-bit-LUT + adder tree.
    """
    mask = jnp.int32((1 << width) - 1)
    v = x & mask
    v = v - ((v >> 1) & jnp.int32(0x55555555))
    v = (v & jnp.int32(0x33333333)) + ((v >> 2) & jnp.int32(0x33333333))
    v = (v + (v >> 4)) & jnp.int32(0x0F0F0F0F)
    if width > 8:
        v = v + (v >> 8)
    return v & jnp.int32(0x1F)


# bfloat16 holds every integer of magnitude <= 256 exactly (8-bit significand)
_ONE_PASS_BOUND = 256


def _mxu_operand(x: jax.Array, bound: int, one_pass: bool) -> jax.Array:
    """``x`` as an f32 product operand.  A 0/1 operand (``bound`` 1) is
    exact in any dtype and is only cast; on the one-pass path a larger
    one is rounded through bfloat16, as the chip's single pass rounds it,
    so a CPU run (whose f32 product is exact) sees any wrong bound too."""
    x = x.astype(jnp.float32)
    if one_pass and bound > 1:
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _onehot_dot(
    a: jax.Array, b: jax.Array, dims, *, bounds, batch=((), ())
) -> jax.Array:
    """Exact integer contraction of ``a`` and ``b`` on the MXU, as int32.

    ``bounds`` states the largest magnitude of ``a``'s and of ``b``'s values
    (static: bytes 255, 0/1 masks 1, popcounts 8, positions N - 1).  Where
    both are at most 256 every value is exact in bfloat16, so ONE bf16 MXU
    pass with f32 accumulation gives the exact integers (DEFAULT precision:
    one pass on the TPU, exact f32 on the CPU); a larger bound keeps the
    multi-pass f32 HIGHEST product.  Either way each output must sum to
    below 2^24, where f32 accumulation is exact; that is checked here from
    the bounds and the contracted size.
    """
    bound_a, bound_b = bounds
    terms = 1
    for d in dims[0]:
        terms *= a.shape[d]
    if bound_a * bound_b * terms >= 1 << 24:
        raise ValueError(
            f"inexact contraction: {terms} terms of |a| <= {bound_a}, "
            f"|b| <= {bound_b} can reach 2^24"
        )
    one_pass = max(bounds) <= _ONE_PASS_BOUND
    return lax.dot_general(
        _mxu_operand(a, bound_a, one_pass),
        _mxu_operand(b, bound_b, one_pass),
        dimension_numbers=(dims, batch),
        preferred_element_type=jnp.float32,
        precision=(
            lax.Precision.DEFAULT if one_pass else lax.Precision.HIGHEST
        ),
    ).astype(jnp.int32)


def _rank_from_keys(key: jax.Array, nb: int) -> jax.Array:
    """Stages 2-3 of the PSU on one (BP, N) int32 key block with values in
    ``[0, nb)``: the stable counting-sort output address of every element.

    The hardware's dataflow, element by element: one-hot the key into the
    ``nb`` buckets, count each bucket's earlier elements (one exact 0/1
    product of the stacked one-hots with the strictly-triangular
    ``[j < i]`` matrix — Mosaic has no ``cumsum``), take each bucket's
    histogram from its last column and the start addresses as their
    exclusive prefix sum (unrolled over the static ``nb``), and map
    element i to ``start[key_i] + #{j < i : key_j == key_i}``.  No two
    elements are compared.  Factored out of :func:`_rank_block` so the
    multi-axis BT kernel (``axes.py``) can derive several bucketings from
    ONE popcount pass.
    """
    bp, n = key.shape
    earlier = lax.broadcasted_iota(jnp.int32, (n, n), 0) < (
        lax.broadcasted_iota(jnp.int32, (n, n), 1)
    )
    hits = [key == b for b in range(nb)]
    # 0/1 bucket one-hots x the 0/1 [j < i] matrix; each count is < N
    within = _onehot_dot(
        jnp.concatenate([h.astype(jnp.float32) for h in hits], axis=0),
        earlier,
        ((1,), (0,)),
        bounds=(1, 1),
    )  # (nb*BP, N): earlier elements in the same bucket
    last = key[:, n - 1:]
    rank = jnp.zeros_like(key)
    start = jnp.zeros((bp, 1), jnp.int32)
    for b, hit in enumerate(hits):
        within_b = within[b * bp:(b + 1) * bp]
        rank = jnp.where(hit, start + within_b, rank)
        # bucket b's histogram: its count before the last slot, plus that
        start = start + within_b[:, n - 1:] + (last == b).astype(jnp.int32)
    return rank


def _rank_block(
    x: jax.Array, *, width: int, k: int | None, descending: bool
) -> jax.Array:
    """Stages 1-3 of the PSU on one (BP, N) int32 block: popcount (+ APP
    bucket encoder), then the counting-sort address
    (:func:`_rank_from_keys`).

    Shared between the standalone sort kernel below and the multi-axis BT
    core (``axes.py``), so the key derivation cannot drift between them.
    Returns the (BP, N) int32 ``rank`` (stable counting-sort output
    addresses).
    """
    # --- popcount stage (+ APP bucket encoder) ---
    p = _popcount_bits(x, width)
    if k is None:
        key, nb = p, width + 1
    else:
        key, nb = (p * k) // (width + 1), k
    if descending:
        key = (nb - 1) - key
    return _rank_from_keys(key, nb)


def _psu_kernel(
    x_ref, order_ref, rank_ref, *, width: int, k: int | None, descending: bool
):
    """Sort one (BP, N) block of packets by (approximate) popcount."""
    x = x_ref[...].astype(jnp.int32)
    bp, n = x.shape
    rank = _rank_block(x, width=width, k=k, descending=descending)

    # scatter as one-hot compare + weighted sum: order[j] = i s.t. rank_i = j
    iota_j = lax.broadcasted_iota(jnp.int32, (bp, n, n), 2)
    iota_i = lax.broadcasted_iota(jnp.int32, (bp, n, n), 1)
    sel = (rank[:, :, None] == iota_j).astype(jnp.int32)
    order = (sel * iota_i).sum(axis=1)  # (BP, N)

    order_ref[...] = order
    rank_ref[...] = rank


def psu_sort_pallas(
    packets: jax.Array,
    *,
    width: int = 8,
    k: int | None = None,
    descending: bool = False,
    block_packets: int = 64,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Sort indices for a batch of packets with the PSU kernel.

    Args:
      packets: (P, N) integer array; P must be a multiple of
        ``block_packets`` (use the ``ops.py`` wrapper for padding).
      width: element bit width W.
      k: APP bucket count, or ``None`` for the exact ACC unit.
      descending: sort high-popcount-first (paper Fig. 2 streams a
        decreasing trend).
      block_packets: packets per grid step (VMEM block height).
      interpret: run the kernel body in Python (CPU validation mode).

    Returns:
      (order, rank) int32 arrays of shape (P, N).
    """
    p, n = packets.shape
    if p % block_packets != 0:
        raise ValueError(f"P={p} not a multiple of block_packets={block_packets}")
    grid = (p // block_packets,)
    kern = functools.partial(_psu_kernel, width=width, k=k, descending=descending)
    out_shape = [
        jax.ShapeDtypeStruct((p, n), jnp.int32),
        jax.ShapeDtypeStruct((p, n), jnp.int32),
    ]
    spec = pl.BlockSpec((block_packets, n), lambda i: (i, 0))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[spec],
        out_specs=[spec, spec],
        out_shape=out_shape,
        interpret=interpret,
        name="psu_sort_kernel",
    )(packets.astype(jnp.int32))


def psu_sort_compiled(
    packets: jax.Array,
    *,
    width: int = 8,
    k: int | None = None,
    descending: bool = False,
    block_packets: int = 64,
) -> tuple[jax.Array, jax.Array]:
    """The compiled (pure-jnp) backend of the PSU sort.

    Runs the SAME rank derivation as the kernel (:func:`_rank_block`) one
    (``block_packets``, N) block at a time (``lax.map``, so the stacked
    one-hots never materialize for more than one block) and inverts the
    rank permutation with an argsort instead of the kernel's one-hot
    scatter (identical output on a permutation).  Bit-exact with the
    kernel.  P must be a multiple of ``block_packets``.
    """
    p, n = packets.shape
    blocks = packets.astype(jnp.int32).reshape(p // block_packets, block_packets, n)
    rank = lax.map(
        lambda b: _rank_block(b, width=width, k=k, descending=descending),
        blocks,
    ).reshape(p, n)
    order = jnp.argsort(rank, axis=-1).astype(jnp.int32)
    return order, rank
