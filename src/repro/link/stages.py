"""Registered TX-pipeline stages (paper §III/§IV; DESIGN.md §3.2).

Every transmit path in the framework is the same five-stage pipeline —

    KEY -> ENCODE -> ORDER (counting sort) -> PACK -> MEASURE

— and this module holds the pluggable stages of it:

  * ``KEY_STAGES``    — sort-key derivation.  Everything is expressed as
    "keys + bucket count" so the ORDER stage is always the paper's stable
    counting sort: 'acc' keys on exact '1'-bit counts, 'app' on k coarse
    buckets, 'row_bucket' on whole-row popcount buckets (the TPU row-stream
    adaptation, DESIGN.md §3.3), and the data-independent 'none' /
    'column_major' degenerate to fixed permutations (keys = transmit rank).
  * ``ENCODE_STAGES`` — wire byte recoding ('identity', 'sign_magnitude').
  * ``PACK_STAGES``   — flit layout: 'row' (row-major), 'lane' (the PSU's
    lane-major packing, paper Fig. 2), 'col' (whole-stream column-major —
    the layout under which row ordering has leverage, measured by
    ``benchmarks/arch_bt.py``).  'col' is a stream layout only; the paired
    per-packet framing uses 'row'/'lane'.

The legacy strategy API (``make_order`` / ``order_packets`` /
``ORDER_STRATEGIES``) is preserved on top of the registries; the old import
path ``repro.core.ordering`` re-exports it.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Mapping, Optional

import jax
import jax.numpy as jnp

from repro.core.coding import gray_encode_bytes
from repro.core.popcount import bucket_map, popcount
from repro.core.sorting import counting_sort_indices

__all__ = [
    "KeyStage",
    "PackStage",
    "KEY_STAGES",
    "ENCODE_STAGES",
    "PACK_STAGES",
    "lookup_stage",
    "make_order",
    "order_packets",
    "ORDER_STRATEGIES",
    "to_sign_magnitude",
    "to_gray",
    "tensor_flit_stream",
    "row_bucket_keys",
    "row_bucket_order",
]


def lookup_stage(kind: str, name: str, registry: Mapping[str, object]):
    """Registry lookup with the harness-wide unknown-name UX: errors list
    every registered stage name (mirrors ``benchmarks/run.py``)."""
    stage = registry.get(name)
    if stage is None:
        raise ValueError(
            f"unknown {kind} stage {name!r}; registered {kind} stages: "
            f"{', '.join(sorted(registry))}"
        )
    return stage


# --------------------------------------------------------------------------
# encode stages
# --------------------------------------------------------------------------


def to_sign_magnitude(q_int8: jax.Array) -> jax.Array:
    """Recode two's-complement int8 as sign-magnitude bytes.

    Beyond-paper optimization (``benchmarks/arch_bt.py``): two's complement
    decorrelates popcount from magnitude (-1 = 0xFF has popcount 8), which
    both halves the ordering signal and inflates baseline BT.  Sign-magnitude
    makes popcount monotone in |value| — near-zero weights become near-zero
    bytes — cutting weight-stream BT by ~50 % *before* any ordering.  In
    hardware this is one XOR per bit at the link interface.
    """
    q = q_int8.astype(jnp.int16)
    sign = (q < 0).astype(jnp.uint8) << 7
    return (sign | jnp.abs(q).astype(jnp.uint8)).astype(jnp.uint8)


def to_gray(values: jax.Array) -> jax.Array:
    """Recode bytes as reflected-binary Gray code (repro.core.coding).

    The stateless half of the ``repro.codec`` family surfaced as an encode
    stage: applied before the KEY stage, so popcount keys are derived from
    the gray image — the element-level composition (DESIGN.md §11; the
    wire-level composition, keys from raw bytes, is the ``LinkSpec.codec``
    stage instead).
    """
    return gray_encode_bytes(values.astype(jnp.uint8))


ENCODE_STAGES: Dict[str, Callable[[jax.Array], jax.Array]] = {
    "identity": lambda v: v,
    "sign_magnitude": to_sign_magnitude,
    "gray": to_gray,
}


# --------------------------------------------------------------------------
# key stages
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KeyStage:
    """Sort-key derivation: fn(values, *, lanes, width, k) -> (keys, buckets).

    ``data_independent`` marks stages whose permutation is fixed by the
    framing alone (no data inspection): the pipeline broadcasts one
    precomputed row instead of counting-sorting every packet.
    """

    name: str
    fn: Callable[..., tuple[jax.Array, int]]
    data_independent: bool = False


def _key_none(values: jax.Array, **_: object) -> tuple[jax.Array, int]:
    n = values.shape[-1]
    keys = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), values.shape)
    return keys, n


def _key_column_major(
    values: jax.Array, *, lanes: int = 8, **_: object
) -> tuple[jax.Array, int]:
    """Keys = transmit rank of the column-major re-traversal of the packet's
    (flits, lanes) matrix: element at (f, l) is visited in order l*F + f."""
    n = values.shape[-1]
    if n % lanes != 0:
        raise ValueError(f"packet size {n} not divisible by lanes {lanes}")
    flits = n // lanes
    i = jnp.arange(n, dtype=jnp.int32)
    keys = jnp.broadcast_to((i % lanes) * flits + i // lanes, values.shape)
    return keys, n


def _key_acc(
    values: jax.Array, *, width: int = 8, **_: object
) -> tuple[jax.Array, int]:
    return popcount(values, width), width + 1


def _key_app(
    values: jax.Array, *, width: int = 8, k: int = 4, **_: object
) -> tuple[jax.Array, int]:
    return bucket_map(popcount(values, width), width, k), k


def row_bucket_keys(
    rows: jax.Array, levels: int, *, width: int = 8
) -> jax.Array:
    """Bucket key per row of an (R, B) byte matrix.

    Row key = total '1'-bit count of the row's bytes, mapped to ``levels``
    buckets the same way the paper maps element popcounts (uniform partition
    of the [0, 8*B] count range).  ACC element granularity corresponds to
    levels = W+1 = 9, APP to levels = k.
    """
    bits = popcount(rows.astype(jnp.uint8), width).sum(axis=-1)  # (R,)
    max_bits = width * rows.shape[-1]
    return (bits * levels) // (max_bits + 1)


def _key_row_bucket(
    values: jax.Array, *, width: int = 8, k: int = 4, **_: object
) -> tuple[jax.Array, int]:
    return row_bucket_keys(values, k, width=width), k


KEY_STAGES: Dict[str, KeyStage] = {
    "none": KeyStage("none", _key_none, data_independent=True),
    "column_major": KeyStage("column_major", _key_column_major, data_independent=True),
    "acc": KeyStage("acc", _key_acc),
    "app": KeyStage("app", _key_app),
    "row_bucket": KeyStage("row_bucket", _key_row_bucket),
}


def row_bucket_order(
    rows: jax.Array, levels: int, *, width: int = 8, descending: bool = False
) -> jax.Array:
    """Stable comparison-free sort order of rows by popcount bucket."""
    keys = row_bucket_keys(rows, levels, width=width)
    if descending:
        keys = (levels - 1) - keys
    return counting_sort_indices(keys, levels)


# --------------------------------------------------------------------------
# pack stages
# --------------------------------------------------------------------------


def tensor_flit_stream(mat: jax.Array, lanes: int = 16) -> jax.Array:
    """View a byte matrix as a (T, lanes) flit stream (row-major flatten,
    trimmed to whole flits) — for a weight matrix this is exactly the HBM
    row stream the decode path reads."""
    flat = mat.reshape(-1)
    usable = (flat.shape[0] // lanes) * lanes
    return flat[:usable].reshape(-1, lanes)


def _per_packet_row(values: jax.Array, lanes: int) -> jax.Array:
    p, n = values.shape
    if n % lanes != 0:
        raise ValueError(f"payload size {n} not divisible by lanes {lanes}")
    return values.reshape(p, n // lanes, lanes)


def _per_packet_lane(values: jax.Array, lanes: int) -> jax.Array:
    p, n = values.shape
    if n % lanes != 0:
        raise ValueError(f"payload size {n} not divisible by lanes {lanes}")
    return values.reshape(p, lanes, n // lanes).transpose(0, 2, 1)


@dataclasses.dataclass(frozen=True)
class PackStage:
    """Flit layout: ``per_packet`` shapes (P, N) payloads into (P, F, lanes)
    flit halves (None for stream-only layouts); ``stream`` lays a whole byte
    matrix out as (T, lanes) flit rows."""

    name: str
    per_packet: Optional[Callable[[jax.Array, int], jax.Array]]
    stream: Callable[[jax.Array, int], jax.Array]


PACK_STAGES: Dict[str, PackStage] = {
    "row": PackStage("row", _per_packet_row, tensor_flit_stream),
    "lane": PackStage(
        "lane",
        _per_packet_lane,
        lambda m, lanes: _per_packet_lane(m, lanes).reshape(-1, lanes),
    ),
    "col": PackStage("col", None, lambda m, lanes: tensor_flit_stream(m.T, lanes)),
}


# --------------------------------------------------------------------------
# legacy strategy API (paper §IV, Table I) — kept verbatim on the registries
# --------------------------------------------------------------------------


def make_order(
    strategy: str,
    values: jax.Array,
    *,
    lanes: int = 8,
    width: int = 8,
    k: int = 4,
    descending: bool = False,
    **_: object,
) -> jax.Array:
    """Per-packet element order for ``strategy``.

    Args:
      strategy: a packet-granularity ``KEY_STAGES`` name ('none',
        'column_major', 'acc', 'app').
      values: (..., N) uint8 input-side packet values the order is derived
        from (ACC/APP sort keys come from these).
      lanes / width / k / descending: stage parameters.

    Returns:
      int32 (..., N) permutation per packet; gather with it to reorder.
    """
    stage = KEY_STAGES.get(strategy)
    if stage is None or strategy == "row_bucket":
        choices = sorted(set(KEY_STAGES) - {"row_bucket"})
        raise ValueError(
            f"unknown ordering strategy {strategy!r}; choose from {choices}"
        )
    n = values.shape[-1]
    if stage.data_independent:
        # fixed permutation (descending is a sort-stage knob; layout stages
        # ignore it, matching the legacy strategy semantics): derive the
        # order from one key row and broadcast it over the batch
        if strategy == "none":
            order = jnp.arange(n, dtype=jnp.int32)
        else:
            keys, nb = stage.fn(
                jnp.zeros((n,), jnp.int32), lanes=lanes, width=width, k=k
            )
            order = counting_sort_indices(keys, nb)
        return jnp.broadcast_to(order, values.shape).astype(jnp.int32)
    keys, nb = stage.fn(values, lanes=lanes, width=width, k=k)
    if descending:
        keys = (nb - 1) - keys
    return counting_sort_indices(keys, nb).astype(jnp.int32)


def order_packets(
    strategy: str,
    inputs: jax.Array,
    weights: jax.Array | None = None,
    **kwargs: object,
) -> tuple[jax.Array, jax.Array | None]:
    """Reorder packets of (input, weight) pairs with one strategy.

    Args:
      inputs: (P, N) uint8 — P packets of N input bytes.
      weights: optional (P, N) uint8 paired weights (move with the inputs).

    Returns:
      (ordered_inputs, ordered_weights_or_None): each packet's elements in
      the order :func:`make_order` gives.  A data-dependent order is one
      stable sort of (key, position) carrying the bytes along, with no
      (..., N, N) permutation matrix and no per-element gather, so whole
      fabrics of packets reorder in one pass.
    """
    stage = KEY_STAGES.get(strategy)
    if strategy == "none":
        return inputs, weights
    if stage is None or stage.data_independent:
        order = make_order(strategy, inputs, **kwargs)
        out_i = jnp.take_along_axis(inputs, order, axis=-1)
        out_w = (
            jnp.take_along_axis(weights, order, axis=-1)
            if weights is not None else None
        )
        return out_i, out_w
    keys, nb = stage.fn(inputs, lanes=kwargs.get("lanes", 8),
                        width=kwargs.get("width", 8), k=kwargs.get("k", 4))
    if kwargs.get("descending", False):
        keys = (nb - 1) - keys
    # one unique key per element, key-major then position: the stable order
    n = inputs.shape[-1]
    kt = jnp.uint16 if nb * n <= 1 << 16 else jnp.int32
    pos = jax.lax.broadcasted_iota(kt, inputs.shape, inputs.ndim - 1)
    riders = (inputs,) if weights is None else (inputs, weights)
    out = jax.lax.sort((keys.astype(kt) * n + pos, *riders),
                       dimension=inputs.ndim - 1, num_keys=1)
    return out[1], (out[2] if weights is not None else None)


def _legacy_strategy(name: str) -> Callable[..., jax.Array]:
    def fn(values: jax.Array, **kwargs: object) -> jax.Array:
        return make_order(name, values, **kwargs)

    fn.__name__ = f"order_{name}"
    return fn


ORDER_STRATEGIES: Dict[str, Callable[..., jax.Array]] = {
    name: _legacy_strategy(name) for name in ("none", "column_major", "acc", "app")
}
