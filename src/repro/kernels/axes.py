"""Pallas TPU kernel: the ONE multi-axis BT measurement core.

Four near-duplicate kernels used to live in this package — ``psu_stream``
(fused TX pipeline), ``bt_links`` (per-link NoC batch), ``bt_variants``
(design-grid variant batch) and ``bt_codecs`` (codec x ordering batch) —
each reimplementing popcount -> bucket -> rank -> permutation-reorder ->
flit-pack -> BT with its own padding convention.  This module replaces all
four with one kernel whose launch carries three orthogonal axes:

  * **link** — grid dimension 0: each grid row measures one independent
    stream (a NoC link, a workload stream, a point-to-point wire).  Links
    may be jagged: a ``valid`` vector carries each link's real packet
    count and everything past it is masked *inside* the kernel.
  * **variant** (ordering) — static, unrolled at trace time: 'none' /
    'column_major' / 'acc' / 'app'(k) x direction.  One popcount pass per
    block is shared by every bucketing; each sorted ordering takes its rank
    from the PSU's comparison-free counting sort (``psu._rank_from_keys``:
    bucket one-hots, one triangular prefix product, histogram starts), and
    one permutation-matrix reorder is shared by every config naming the
    same ordering.
  * **codec** — static, unrolled at trace time: 'none' / 'gray' /
    'sign_magnitude' / 'transition' / 'bus_invert'(partition), applied to
    the assembled wire per config (DESIGN.md §11/§12).

One unified padding/masking convention (DESIGN.md §12): the wrapper pads
the packet axis to a block multiple with zero packets and the link axis
with zero links; the kernel masks every flit boundary at or past each
link's ``valid`` row count, so padded flits contribute ZERO data-side BT
**and zero aux (invert-line) BT** — in particular a bus-invert decision is
never evaluated on a padded flit (the old repeated-flit convention was
BT-neutral for data wires but could flip a coded link's invert line).

Cross-block state is the same partial/edge split as before, now per link:
each (link, block) emits per-config BT partials over its block-internal
valid boundaries plus first/last-valid edge flits (and bus-invert branch
states), from which the ``ops.py`` wrapper folds the O(G) inter-block
carry per link in plain jnp — no extra kernel launch.

The fused TX pipeline is this same kernel with ``emit_stream=True`` (one
link, one config): the permutation-matrix contraction then also yields
``order`` (permuted iota), ``rank`` and the packed wire stream, exactly as
the old ``psu_stream`` kernel did (DESIGN.md §3.2).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.coding import (
    bus_invert_partitions as _partitions,
    gray_encode_bytes,
    sign_magnitude_encode_bytes,
)

from .psu import _onehot_dot, _popcount_bits, _rank_from_keys

__all__ = [
    "Variant",
    "VARIANT_KEYS",
    "validate_variants",
    "CodecVariant",
    "CODEC_SCHEMES",
    "validate_codec_variants",
    "max_partitions",
    "bt_axes_pallas",
    "bt_axes_compiled",
]

VARIANT_KEYS = ("none", "column_major", "acc", "app")

CODEC_SCHEMES = ("none", "gray", "sign_magnitude", "transition", "bus_invert")


class Variant(NamedTuple):
    """One measured ordering configuration of the multi-axis kernel.

    ``key`` is a packet-granularity ordering ('none' | 'column_major' |
    'acc' | 'app'); ``k`` is the APP bucket count (None for every other
    key); ``descending`` flips the sort direction (ACC/APP only).
    """

    key: str = "acc"
    k: int | None = None
    descending: bool = False


class CodecVariant(NamedTuple):
    """One measured (ordering, codec) configuration of the multi-axis
    kernel.

    ``key`` / ``k`` / ``descending`` are the ordering axes of
    :class:`Variant`; ``codec`` is a static scheme id from
    ``CODEC_SCHEMES``; ``partition`` is the bus-invert group width in lanes
    (None = one invert line over the whole flit; meaningless otherwise).
    """

    key: str = "acc"
    k: int | None = None
    descending: bool = False
    codec: str = "none"
    partition: int | None = None

    @property
    def ordering(self) -> Variant:
        return Variant(self.key, self.k, self.descending)


def validate_variants(
    variants: tuple[Variant, ...], width: int
) -> tuple[Variant, ...]:
    """Check a static variant tuple against the kernel's contract."""
    if not variants:
        raise ValueError("need at least one variant")
    out = []
    for v in variants:
        v = Variant(*v)
        if v.key not in VARIANT_KEYS:
            raise ValueError(
                f"unknown variant key {v.key!r}; choose from {VARIANT_KEYS}"
            )
        if v.key == "app":
            if v.k is None or not 1 <= v.k <= width + 1:
                raise ValueError(
                    f"variant {v}: 'app' needs k in [1, {width + 1}]"
                )
        elif v.k is not None:
            raise ValueError(f"variant {v}: k is only meaningful for 'app'")
        if v.descending and v.key not in ("acc", "app"):
            raise ValueError(
                f"variant {v}: descending applies to sorted keys only"
            )
        out.append(v)
    return tuple(out)


def validate_codec_variants(
    configs: tuple[CodecVariant, ...], width: int, lanes: int
) -> tuple[CodecVariant, ...]:
    """Check a static config tuple against the kernel's contract."""
    if not configs:
        raise ValueError("need at least one codec config")
    out = []
    for cfg in configs:
        cfg = CodecVariant(*cfg)
        validate_variants((cfg.ordering,), width)
        if cfg.codec not in CODEC_SCHEMES:
            raise ValueError(
                f"config {cfg}: unknown codec scheme {cfg.codec!r}; "
                f"choose from {CODEC_SCHEMES}"
            )
        if cfg.codec == "bus_invert":
            _partitions(lanes, cfg.partition)
        elif cfg.partition is not None:
            raise ValueError(
                f"config {cfg}: partition is only meaningful for 'bus_invert'"
            )
        out.append(cfg)
    return tuple(out)


def max_partitions(
    configs: tuple[CodecVariant, ...], lanes: int
) -> int:
    """Invert-line slots the kernel's outputs must provide (>= 1)."""
    return max(
        [1]
        + [
            _partitions(lanes, c.partition)[0]
            for c in configs
            if c.codec == "bus_invert"
        ]
    )


def _shift_rows(a: jax.Array, s: int) -> jax.Array:
    """``a`` moved ``s`` rows down along axis 0, zero-filled at the top."""
    return jnp.concatenate([jnp.zeros((s,) + a.shape[1:], a.dtype), a[:-s]])


def _prefix_xor(a: jax.Array) -> jax.Array:
    """Inclusive prefix XOR along axis 0 in log2(T) shift-and-combine
    steps (Mosaic has no scan primitive; exact for any integer values)."""
    s = 1
    while s < a.shape[0]:
        a = a ^ _shift_rows(a, s)
        s *= 2
    return a


def _bus_invert_bits(hd: jax.Array, lbits: int) -> tuple[jax.Array, jax.Array]:
    """Invert-line states for both entry branches from pairwise data HDs.

    ``hd`` is (T-1, P) Hamming distances between consecutive data flit
    groups.  The sequential decision v_t = [2*HD(d_t, w_{t-1}) > L] obeys
    v_t = tie_t ? 0 : h_t ^ v_{t-1} (h_t = [2*HD_t > L], tie_t =
    [2*HD_t == L]), which is a prefix-XOR with resets at ties — evaluated
    here as a log-step segmented scan over (reset, xor) pairs instead of a
    sequential one.  Returns (v0, v1), both (T, P), for entry states
    v_0 = 0 and v_0 = 1.
    """
    npart = hd.shape[1]
    x = (2 * hd > lbits).astype(jnp.int32)  # h_t (never set at a tie)
    r = (2 * hd == lbits).astype(jnp.int32)  # a reset has happened
    s = 1
    while s < hd.shape[0]:
        x = jnp.where(r == 1, x, x ^ _shift_rows(x, s))
        r = r | _shift_rows(r, s)
        s *= 2
    zeros = jnp.zeros((1, npart), jnp.int32)
    v0 = jnp.concatenate([zeros, x], axis=0)
    # no tie yet -> the entry bit still propagates: v1 = v0 ^ [no tie <= t]
    return v0, v0 ^ jnp.concatenate([zeros + 1, 1 - r], axis=0)


def _group_matrix(lanes: int, pw: int, per_lane: int = 1) -> jax.Array:
    """(lanes*per_lane, lanes//pw) 0/1 membership of lane-groups of ``pw``
    lanes (``per_lane`` columns per lane, e.g. 8 wires), built from iota so
    a Pallas kernel body can use it."""
    shape = (lanes * per_lane, lanes // pw)
    col = lax.broadcasted_iota(jnp.int32, shape, 0) // (pw * per_lane)
    return (col == lax.broadcasted_iota(jnp.int32, shape, 1)).astype(
        jnp.int32
    )


def _slot_of_position(
    q: jax.Array, ln: int, flits: int, pack: str, column_major: bool
) -> jax.Array:
    """Packet slot feeding flit position ``q = f*ln + l`` of one side.

    'row' packing fills flit f from slots f*ln..f*ln+ln-1; 'lane' packing
    puts slot l*F + f on lane l of flit f.  The 'column_major' ordering is
    the fixed transpose of the (F, L) packet view composed in front: slot
    (l*F + f) carries element (f*L + l).
    """
    slot = q if pack == "row" else (q % ln) * flits + q // ln
    if column_major:
        slot = (slot % flits) * ln + slot // flits
    return slot


def _pack_side(values, ln, flits, pack, column_major):
    """(BP, F*ln) ordered packet payloads -> (BP*F, ln) flit rows.

    The packing permutation is one 0/1 selector product (skipped when it
    is the identity), then static lane slices stacked flit-major — the
    only relayouts Mosaic needs (no minor-dim split or 3-D transpose).
    """
    bp, n = values.shape
    q = np.arange(n)
    if not np.array_equal(
        _slot_of_position(q, ln, flits, pack, column_major), q
    ):
        shape = (n, n)
        src = _slot_of_position(
            lax.broadcasted_iota(jnp.int32, shape, 1), ln, flits, pack,
            column_major,
        )
        sel = lax.broadcasted_iota(jnp.int32, shape, 0) == src
        # byte payloads x a 0/1 permutation selector
        values = _onehot_dot(values, sel, ((1,), (0,)), bounds=(255, 1))
    if flits == 1:
        return values
    return jnp.stack(
        [values[:, f * ln:(f + 1) * ln] for f in range(flits)], axis=1
    ).reshape(bp * flits, ln)


def _axes_block(
    x,
    w,
    remaining_rows,
    start_row=None,
    *,
    configs: tuple[CodecVariant, ...],
    width: int,
    input_lanes: int,
    weight_lanes: int,
    split_lanes: int,
    pack: str,
    pmax: int,
    emit_stream: bool,
    window_rows: int = 0,
    num_windows: int = 0,
):
    """Measure one (link, packet-block) cell under every static config.

    The backend-shared block math (DESIGN.md §13): the Pallas kernel calls
    this from its grid body, the compiled jnp backend ``vmap``s it over the
    link axis and ``lax.map``s it over packet blocks — the two paths run
    the SAME traced operations, so they are bit-exact by construction.

    Args:
      x / w: (BP, N) int32 packet payloads of this block: bytes (the
        selector products' bounds rest on it, ``psu._onehot_dot``).
      remaining_rows: int32 scalar — this link's valid flit rows minus the
        rows consumed by earlier blocks (may be <= 0: fully-padded block).
      start_row: int32 scalar — global flit-row index of this block's first
        row (activity mode only; windows are indexed globally so chunked
        and unchunked runs land toggles in the same window).
      window_rows / num_windows: static activity-window length (flit rows)
        and total window count; ``num_windows > 0`` enables the per-wire
        activity outputs (DESIGN.md §15).

    Returns:
      (bt (C, 2, PMAX, 3), edge (C, 2, 2, lanes), inv (C, 2, 2, PMAX))
      int32 partials; with activity also (act (C, 2, NW, WIRES),
      ones (C, 2, WIRES)) where WIRES = lanes*8 data wires (wire = lane*8
      + bit, LSB first) followed by PMAX invert-line wires; plus
      (order, rank, stream) with ``emit_stream``.
    """
    x = x.astype(jnp.int32)  # (BP, N)
    w = w.astype(jnp.int32)
    bp, n = x.shape
    flits = n // input_lanes
    lanes = input_lanes + weight_lanes
    rows = bp * flits
    act_on = num_windows > 0

    # --- the ONE masking convention: rows at or past this link's valid
    # count contribute nothing (data BT, aux BT, edge flits alike) ---
    valid = jnp.minimum(jnp.int32(rows), remaining_rows)
    row_idx = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    bmask = (row_idx[1:] < valid).astype(jnp.int32)  # (rows-1, 1) boundaries
    last_onehot = (row_idx == valid - 1).astype(jnp.int32)  # last valid row

    def _edges(arr):  # (rows, L) -> (2, L): first row, last valid row
        last = (arr * last_onehot).sum(axis=0, keepdims=True)
        return jnp.concatenate([arr[:1], last], axis=0)

    def _pad_lanes(arr, width):  # zero-pad the minor dim up to ``width``
        if arr.shape[-1] == width:
            return arr
        fill = jnp.zeros(arr.shape[:-1] + (width - arr.shape[-1],), arr.dtype)
        return jnp.concatenate([arr, fill], axis=-1)

    def _sides(arr):  # (T, lanes) -> [input-side sum, weight-side sum, 0]
        zero = jnp.int32(0)
        return jnp.stack([
            arr[:, :split_lanes].sum() if split_lanes else zero,
            arr[:, split_lanes:].sum() if split_lanes < lanes else zero,
            zero,
        ])

    if act_on:
        nwires = lanes * 8 + pmax
        lane_to_wire = _group_matrix(lanes, 1, 8)  # (lanes*8, lanes)
        bit_of_wire = lax.broadcasted_iota(jnp.int32, (1, lanes * 8), 1) % 8

        def _wire_bits(arr):  # (T, lanes) bytes -> (T, lanes*8) bits
            # wire = lane*8 + bit, LSB first: copy each byte onto its 8
            # wires with one selector product (bytes x the 0/1 lane-to-wire
            # map), then pick each wire's bit
            per_wire = _onehot_dot(
                arr, lane_to_wire, ((1,), (1,)), bounds=(255, 1)
            )
            return (per_wire >> bit_of_wire) & 1

        rmask = (row_idx < valid).astype(jnp.int32)  # (rows, 1) levels
        # the boundary INTO local row i toggles inside row i's window
        bwin = (
            start_row + lax.broadcasted_iota(jnp.int32, (rows - 1, 1), 0) + 1
        ) // window_rows
        win_iota = lax.broadcasted_iota(
            jnp.int32, (rows - 1, num_windows), 1
        )
        win_onehot = (bwin == win_iota).astype(jnp.int32)

        def _scatter(toggles):  # (rows-1, W) 0/1 -> (NW, W) window counts
            # 0/1 window one-hot x 0/1 toggles, summed over the rows - 1
            # boundaries (far below 2^24)
            return _onehot_dot(
                win_onehot, toggles, ((0,), (0,)), bounds=(1, 1)
            )

        acts, ones_rows = [], []

    # --- popcount stage: ONCE per block, shared by every bucketing
    # (computed lazily — identity-ordering launches skip it entirely) ---
    pc = None

    # --- one reordered + packed stream per unique ordering ---
    streams: dict[Variant, jax.Array] = {}
    emitted = None  # (order, rank, stream) of configs[0] in emit_stream mode
    for cfg in configs:
        if cfg.ordering in streams:
            continue
        key_name, k, descending = cfg.ordering
        order = rank = None
        xs, ws = x, w
        if key_name in ("acc", "app"):
            # --- bucket encoder + shared rank machinery (psu.py) ---
            if pc is None:
                pc = _popcount_bits(x, width)
            if key_name == "acc":
                key, nb = pc, width + 1
            else:
                key, nb = (pc * k) // (width + 1), k
            if descending:
                key = (nb - 1) - key
            rank = _rank_from_keys(key, nb)
            # --- reorder: one permutation-matrix MXU product yields the
            # ordered payloads (and, in emit_stream mode, `order` = the
            # permuted iota) in a single contraction (DESIGN.md §3.2) ---
            iota_j = lax.broadcasted_iota(jnp.int32, (bp, n, n), 2)
            perm = rank[:, :, None] == iota_j
            rows_payload = [x, w] if weight_lanes else [x]
            if emit_stream:
                iota_i = lax.broadcasted_iota(jnp.int32, (bp, n), 1)
                rows_payload = [iota_i] + rows_payload
            # bytes (and positions below N) x the 0/1 permutation matrix
            moved = _onehot_dot(
                jnp.stack(rows_payload, axis=1),
                perm,
                ((2,), (1,)),
                batch=((0,), (0,)),
                bounds=(max(255, n - 1) if emit_stream else 255, 1),
            )  # (BP, 1|2|3, N)
            # static row picks (a negative index would lower as a
            # dynamic_slice, which Mosaic has no rule for)
            picked = [
                lax.index_in_dim(moved, i, axis=1, keepdims=False)
                for i in range(len(rows_payload))
            ]
            if weight_lanes:
                xs, ws = picked[-2:]
            else:
                xs = picked[-1]
            if emit_stream:
                order = picked[0]
        cm = key_name == "column_major"
        stream = _pack_side(xs, input_lanes, flits, pack, cm)
        if weight_lanes:
            stream = jnp.concatenate(
                [stream, _pack_side(ws, weight_lanes, flits, pack, cm)],
                axis=-1,
            )
        streams[cfg.ordering] = stream  # (rows, lanes)
        if emit_stream and cfg.ordering == configs[0].ordering:
            emitted = (order, rank, stream)

    # --- codec + BT-accumulate per config on the shared streams ---
    bts, edge_rows, inv_rows = [], [], []
    for cfg in configs:
        stream = streams[cfg.ordering]
        zero_inv = jnp.zeros((2, 2, pmax), jnp.int32)

        if cfg.codec in ("none", "gray", "sign_magnitude"):
            if cfg.codec == "gray":
                wire = gray_encode_bytes(stream)
            elif cfg.codec == "sign_magnitude":
                wire = sign_magnitude_encode_bytes(stream)
            else:
                wire = stream
            flips = _popcount_bits(wire[1:] ^ wire[:-1], 8) * bmask
            part = jnp.broadcast_to(_sides(flips), (2, 1, 3))
            bts.append(jnp.pad(part, ((0, 0), (0, pmax - 1), (0, 0))))
            edge_rows.append(jnp.broadcast_to(_edges(wire), (2, 2, lanes)))
            inv_rows.append(zero_inv)
            if act_on:
                tb = _wire_bits(wire[1:] ^ wire[:-1]) * bmask
                act = _pad_lanes(_scatter(tb), nwires)
                acts.append(jnp.broadcast_to(act, (2, num_windows, nwires)))
                ones_w = (_wire_bits(wire) * rmask).sum(axis=0, keepdims=True)
                ones_rows.append(
                    jnp.broadcast_to(_pad_lanes(ones_w, nwires), (2, nwires))
                )

        elif cfg.codec == "transition":
            # wire_t ^ wire_{t-1} == data_t: boundary flips = data popcount
            contrib = _popcount_bits(stream, 8)[1:] * bmask
            part = jnp.broadcast_to(_sides(contrib), (2, 1, 3))
            bts.append(jnp.pad(part, ((0, 0), (0, pmax - 1), (0, 0))))
            # edges carry DATA flits (the wrapper adds first-flit popcounts)
            edge_rows.append(
                jnp.broadcast_to(_edges(stream), (2, 2, lanes))
            )
            inv_rows.append(zero_inv)
            if act_on:
                # wire-bit toggle at boundary t == data bit of row t
                tb = _wire_bits(stream[1:]) * bmask
                act = _pad_lanes(_scatter(tb), nwires)
                acts.append(jnp.broadcast_to(act, (2, num_windows, nwires)))
                # the wire LEVEL is the running data parity; slot 0 = time
                # at 1 for a parity-0 entry, slot 1 = this block's parity
                # (the wrapper flips slot 0 per the carried entry parity)
                db = _wire_bits(stream) * rmask
                par = _prefix_xor(db)
                ones_rows.append(jnp.concatenate([
                    _pad_lanes((par * rmask).sum(axis=0, keepdims=True), nwires),
                    _pad_lanes(db.sum(axis=0, keepdims=True) & 1, nwires),
                ]))

        else:  # bus_invert
            npart, pw = _partitions(lanes, cfg.partition)
            lbits = 8 * pw
            grp = _group_matrix(lanes, pw)  # (lanes, npart) lane -> group
            dx = stream[1:] ^ stream[:-1]  # (rows-1, lanes)
            dpc = _popcount_bits(dx, 8)
            # per-lane popcounts (<= 8) x the 0/1 lane-to-group map
            v0, v1 = _bus_invert_bits(
                _onehot_dot(dpc, grp, ((1,), (0,)), bounds=(8, 1)), lbits
            )
            # input/weight lane split: global lane id < split_lanes
            in_mask = (
                lax.broadcasted_iota(jnp.int32, (1, lanes), 1) < split_lanes
            ).astype(jnp.int32)
            ones_col = jnp.ones((rows - 1, 1), jnp.int32)

            def _per_part(arr):  # (rows-1, lanes) -> (npart, 1) group sums
                # 0/1 groups x per-lane flips (<= 8 a row) summed over the
                # rows - 1 boundaries: past 33 rows the bound exceeds 256
                # and the product keeps f32 HIGHEST
                return _onehot_dot(
                    grp, arr.sum(axis=0, keepdims=True), ((0,), (1,)),
                    bounds=(1, 8 * (rows - 1)),
                )

            parts, edges, inv_edges = [], [], []
            acts_b, ones_b = [], []
            if act_on:
                grp8 = _group_matrix(lanes, pw, 8)  # (lanes*8, npart)
            for v in (v0, v1):
                e = v[1:] ^ v[:-1]  # (rows-1, npart) invert-line flips
                # 0/1 invert-line flips x the 0/1 group map
                e_lane = _onehot_dot(
                    e, grp, ((1,), (1,)), bounds=(1, 1)
                )  # (rows-1, lanes)
                lane_flips = jnp.where(e_lane == 1, 8 - dpc, dpc) * bmask
                parts.append(jnp.concatenate([
                    _per_part(lane_flips * in_mask),
                    _per_part(lane_flips * (1 - in_mask)),
                    # 0/1 flips summed over rows - 1 (far below 2^24)
                    _onehot_dot(
                        e * bmask, ones_col, ((0,), (0,)), bounds=(1, 1)
                    ),
                ], axis=1))  # (npart, 3)
                # 0/1 invert-line states x the 0/1 group map
                inv = _onehot_dot(v, grp, ((1,), (1,)), bounds=(1, 1))
                wire = stream ^ (inv * 0xFF)
                edges.append(_edges(wire))
                inv_edges.append(_pad_lanes(_edges(v), pmax))
                if act_on:
                    # wire-bit toggle = data-bit toggle XOR its partition's
                    # invert-line flip; the invert line itself is a wire
                    # (0/1 flips x the 0/1 wire-to-group map)
                    erep = _onehot_dot(e, grp8, ((1,), (1,)), bounds=(1, 1))
                    tb = (_wire_bits(dx) ^ erep) * bmask
                    aux_t = _pad_lanes(e * bmask, pmax)
                    acts_b.append(
                        _scatter(jnp.concatenate([tb, aux_t], axis=1))
                    )
                    ones_b.append(jnp.concatenate([
                        (_wire_bits(wire) * rmask).sum(axis=0, keepdims=True),
                        _pad_lanes(
                            (v * rmask).sum(axis=0, keepdims=True), pmax
                        ),
                    ], axis=1))
            bts.append(jnp.pad(
                jnp.stack(parts), ((0, 0), (0, pmax - npart), (0, 0))
            ))
            edge_rows.append(jnp.stack(edges))
            inv_rows.append(jnp.stack(inv_edges))
            if act_on:
                acts.append(jnp.stack(acts_b))
                ones_rows.append(jnp.concatenate(ones_b))

    out = (jnp.stack(bts), jnp.stack(edge_rows), jnp.stack(inv_rows))
    if act_on:
        out = out + (jnp.stack(acts), jnp.stack(ones_rows))
    return out + emitted if emit_stream else out


def _bt_axes_kernel(*refs, **static):
    """Pallas grid body: one (link, packet-block) cell via ``_axes_block``."""
    activity = static.get("num_windows", 0) > 0
    base_ref = order_ref = rank_ref = stream_ref = act_ref = ones_ref = None
    if activity:
        (x_ref, w_ref, valid_ref, base_ref,
         bt_ref, edge_ref, inv_edge_ref, act_ref, ones_ref) = refs
    elif static["emit_stream"]:
        (x_ref, w_ref, valid_ref, bt_ref, edge_ref, inv_edge_ref,
         order_ref, rank_ref, stream_ref) = refs
    else:
        x_ref, w_ref, valid_ref, bt_ref, edge_ref, inv_edge_ref = refs
    bp, n = x_ref.shape[1:]
    flits = n // static["input_lanes"]
    rows = jnp.int32(bp * flits)
    # per-link valid counts and the launch's base row live in SMEM
    remaining = valid_ref[pl.program_id(0)] * flits - pl.program_id(1) * rows
    start = base_ref[0] + pl.program_id(1) * rows if activity else None
    out = _axes_block(x_ref[0], w_ref[0], remaining, start, **static)
    bt_ref[0, 0] = out[0]
    edge_ref[0, 0] = out[1]
    inv_edge_ref[0, 0] = out[2]
    if activity:
        act_ref[0, 0] = out[3]
        ones_ref[0, 0] = out[4]
    if static["emit_stream"]:
        order_ref[0], rank_ref[0], stream_ref[0] = out[3:]


def bt_axes_pallas(
    inputs: jax.Array,
    weights: jax.Array,
    valid: jax.Array,
    *,
    configs: tuple[CodecVariant, ...],
    width: int = 8,
    input_lanes: int = 8,
    weight_lanes: int = 0,
    split_lanes: int | None = None,
    pack: str = "lane",
    block_packets: int = 64,
    emit_stream: bool = False,
    interpret: bool = False,
    window_rows: int = 0,
    num_windows: int = 0,
    base_row: jax.Array | None = None,
):
    """Per-(link, config) coded BT partials of a (L, P, N) batch, ONE launch.

    Args:
      inputs / weights: (L, P, N) int packets; P a multiple of
        ``block_packets`` (the ``ops.py`` wrappers zero-pad; padded rows
        are masked in-kernel via ``valid``).
      valid: (L,) int32 real packet count per link (rows past it are
        masked: zero data BT, zero aux BT).
      configs: static tuple of :class:`CodecVariant` configurations — the
        variant x codec axes of the launch.
      split_lanes: byte lane where the input side ends for the per-side BT
        accounting (default ``input_lanes``; the per-link NoC path packs
        pre-assembled flit rows as N = lanes packets and splits here).
      emit_stream: also emit (order, rank, stream) for ``configs[0]``'s
        ordering — the fused-TX-pipeline mode (requires exactly one config
        with an 'acc'/'app' ordering).
      window_rows / num_windows: static activity-window length in flit
        rows and total (global) window count; ``num_windows > 0`` enables
        the per-wire activity outputs (DESIGN.md §15; incompatible with
        ``emit_stream``).
      base_row: int32 scalar — global flit-row index of this launch's
        first row (chunked streaming offsets it per chunk; default 0).

    Returns:
      (partials, edges, inv_edges[, order, rank, stream]):
        * int32 (L, G, C, 2, PMAX, 3) per-block, per-entry-branch,
          per-partition (input, weight, invert-line) BT partials over
          block-internal valid boundaries (branches are identical for
          every codec except bus-invert; non-partitioned codecs use
          slot 0);
        * int32 (L, G, C, 2, 2, lanes) per-branch first/last-valid wire
          rows (DATA rows for 'transition');
        * int32 (L, G, C, 2, 2, PMAX) per-branch first/last-valid
          invert-line states (bus-invert only, zeros otherwise);
        * with activity: int32 (L, G, C, 2, NW, WIRES) per-branch window
          toggles and (L, G, C, 2, WIRES) per-branch wire-level 1-counts
          (DESIGN.md §15);
        * with ``emit_stream``: int32 (L, P, N) order, (L, P, N) rank and
          (L, P*F, lanes) packed stream.
    """
    configs, split_lanes = _validate_axes_call(
        inputs, valid, configs=configs, width=width, input_lanes=input_lanes,
        weight_lanes=weight_lanes, split_lanes=split_lanes, pack=pack,
        block_packets=block_packets, emit_stream=emit_stream,
        num_windows=num_windows, window_rows=window_rows,
    )
    links, p, n = inputs.shape
    lanes = input_lanes + weight_lanes
    nc = len(configs)
    flits = n // input_lanes
    pmax = max_partitions(configs, lanes)
    gblocks = p // block_packets
    activity = num_windows > 0
    grid = (links, gblocks)
    kern = functools.partial(
        _bt_axes_kernel,
        configs=configs,
        width=width,
        input_lanes=input_lanes,
        weight_lanes=weight_lanes,
        split_lanes=split_lanes,
        pack=pack,
        pmax=pmax,
        emit_stream=emit_stream,
        window_rows=window_rows,
        num_windows=num_windows,
    )
    pk_spec = pl.BlockSpec((1, block_packets, n), lambda l, g: (l, g, 0))
    # inside shard_map the outputs vary over the same mesh axes as the links
    vma = jax.typeof(inputs).vma

    def _out(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [pk_spec, pk_spec, smem]
    out_shape = [
        _out((links, gblocks, nc, 2, pmax, 3), jnp.int32),
        _out((links, gblocks, nc, 2, 2, lanes), jnp.int32),
        _out((links, gblocks, nc, 2, 2, pmax), jnp.int32),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, nc, 2, pmax, 3), lambda l, g: (l, g, 0, 0, 0, 0)),
        pl.BlockSpec((1, 1, nc, 2, 2, lanes), lambda l, g: (l, g, 0, 0, 0, 0)),
        pl.BlockSpec((1, 1, nc, 2, 2, pmax), lambda l, g: (l, g, 0, 0, 0, 0)),
    ]
    if activity:
        nwires = lanes * 8 + pmax
        in_specs.append(smem)
        out_shape += [
            _out(
                (links, gblocks, nc, 2, num_windows, nwires), jnp.int32
            ),
            _out((links, gblocks, nc, 2, nwires), jnp.int32),
        ]
        out_specs += [
            pl.BlockSpec(
                (1, 1, nc, 2, num_windows, nwires),
                lambda l, g: (l, g, 0, 0, 0, 0),
            ),
            pl.BlockSpec((1, 1, nc, 2, nwires), lambda l, g: (l, g, 0, 0, 0)),
        ]
    if emit_stream:
        out_shape += [
            _out((links, p, n), jnp.int32),
            _out((links, p, n), jnp.int32),
            _out((links, p * flits, lanes), jnp.int32),
        ]
        out_specs += [
            pk_spec,
            pk_spec,
            pl.BlockSpec(
                (1, block_packets * flits, lanes), lambda l, g: (l, g, 0)
            ),
        ]
    args = [
        inputs.astype(jnp.int32),
        weights.astype(jnp.int32),
        valid.astype(jnp.int32),
    ]
    if activity:
        base = jnp.int32(0) if base_row is None else base_row
        args.append(jnp.asarray(base, jnp.int32).reshape(1))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="bt_axes_kernel",
    )(*args)


def _validate_axes_call(
    inputs,
    valid,
    *,
    configs,
    width,
    input_lanes,
    weight_lanes,
    split_lanes,
    pack,
    block_packets,
    emit_stream,
    num_windows=0,
    window_rows=0,
):
    """The multi-axis launch contract, shared by every backend."""
    links, p, n = inputs.shape
    lanes = input_lanes + weight_lanes
    configs = validate_codec_variants(configs, width, lanes)
    if p % block_packets != 0:
        raise ValueError(f"P={p} not a multiple of block_packets={block_packets}")
    if n % input_lanes != 0:
        raise ValueError(f"packet size {n} not divisible by input_lanes={input_lanes}")
    if weight_lanes not in (0, input_lanes):
        raise ValueError(
            "the multi-axis kernel needs a symmetric (or absent) weight "
            f"side: weight_lanes={weight_lanes} vs input_lanes={input_lanes}"
        )
    if pack not in ("lane", "row"):
        raise ValueError(f"multi-axis kernel supports pack 'lane'|'row', got {pack!r}")
    if split_lanes is None:
        split_lanes = input_lanes
    if not 0 <= split_lanes <= lanes:
        raise ValueError(f"split_lanes={split_lanes} outside the {lanes}-lane flit")
    if num_windows > 0:
        if window_rows < 1:
            raise ValueError(
                f"activity needs window_rows >= 1, got {window_rows}"
            )
        if emit_stream:
            raise ValueError("activity and emit_stream are exclusive modes")
    if emit_stream:
        if len(configs) != 1 or configs[0].codec != "none":
            raise ValueError(
                "emit_stream needs exactly one uncoded config, got "
                f"{configs}"
            )
        if configs[0].key not in ("acc", "app"):
            raise ValueError(
                "emit_stream needs an 'acc'/'app' ordering (the fused TX "
                f"pipeline), got {configs[0].key!r}"
            )
    if valid.shape != (links,):
        raise ValueError(f"valid must be ({links},), got {tuple(valid.shape)}")
    return configs, split_lanes


def bt_axes_compiled(
    inputs: jax.Array,
    weights: jax.Array,
    valid: jax.Array,
    *,
    configs: tuple[CodecVariant, ...],
    width: int = 8,
    input_lanes: int = 8,
    weight_lanes: int = 0,
    split_lanes: int | None = None,
    pack: str = "lane",
    block_packets: int = 64,
    emit_stream: bool = False,
    window_rows: int = 0,
    num_windows: int = 0,
    base_row: jax.Array | None = None,
):
    """The compiled (pure-jnp) backend of the multi-axis measurement.

    Same contract, arguments and outputs as :func:`bt_axes_pallas`, but the
    block math runs as ordinary XLA: ``vmap`` over the link axis,
    ``lax.map`` over packet blocks (sequential, so the per-block
    permutation/one-hot intermediates never materialize for more than one
    block — the same VMEM discipline the kernel's grid gives for free).
    Because both backends execute the SAME ``_axes_block`` trace, they are
    bit-exact; ``tests/test_backends.py`` pins it per entry point.
    """
    configs, split_lanes = _validate_axes_call(
        inputs, valid, configs=configs, width=width, input_lanes=input_lanes,
        weight_lanes=weight_lanes, split_lanes=split_lanes, pack=pack,
        block_packets=block_packets, emit_stream=emit_stream,
        num_windows=num_windows, window_rows=window_rows,
    )
    links, p, n = inputs.shape
    lanes = input_lanes + weight_lanes
    flits = n // input_lanes
    pmax = max_partitions(configs, lanes)
    gblocks = p // block_packets
    rows = block_packets * flits
    activity = num_windows > 0
    block = functools.partial(
        _axes_block,
        configs=configs,
        width=width,
        input_lanes=input_lanes,
        weight_lanes=weight_lanes,
        split_lanes=split_lanes,
        pack=pack,
        pmax=pmax,
        emit_stream=emit_stream,
        window_rows=window_rows,
        num_windows=num_windows,
    )
    xb = jnp.moveaxis(
        inputs.astype(jnp.int32).reshape(links, gblocks, block_packets, n), 1, 0
    )
    wb = jnp.moveaxis(
        weights.astype(jnp.int32).reshape(links, gblocks, block_packets, n), 1, 0
    )
    remaining = (
        valid.astype(jnp.int32)[None, :] * flits
        - jnp.arange(gblocks, dtype=jnp.int32)[:, None] * rows
    )  # (G, L)
    if activity:
        base = jnp.int32(0) if base_row is None else base_row
        starts = (
            jnp.asarray(base, jnp.int32)
            + jnp.arange(gblocks, dtype=jnp.int32) * rows
        )  # (G,)
        per_block = jax.vmap(block, in_axes=(0, 0, 0, None))
        outs = lax.map(
            lambda args: per_block(*args), (xb, wb, remaining, starts)
        )
    else:
        per_block = jax.vmap(block)  # over the link axis
        outs = lax.map(lambda args: per_block(*args), (xb, wb, remaining))
    bt, edge, inv = (jnp.moveaxis(o, 1, 0) for o in outs[:3])  # (L, G, ...)
    if activity:
        act, ones = (jnp.moveaxis(o, 1, 0) for o in outs[3:5])
        return bt, edge, inv, act, ones
    if not emit_stream:
        return bt, edge, inv
    order, rank, stream = (jnp.moveaxis(o, 1, 0) for o in outs[3:])
    return (
        bt,
        edge,
        inv,
        order.reshape(links, p, n),
        rank.reshape(links, p, n),
        stream.reshape(links, p * flits, lanes),
    )
