"""Public wrappers around the kernel entry points, with backend dispatch.

Every BT entry point — ``psu_stream`` (fused TX pipeline),
``bt_count_links`` (per-link NoC batch), ``bt_count_variants`` (design-grid
batch), ``bt_count_codecs`` (codec x ordering batch) and the underlying
``bt_count_axes`` — is a thin configuration of the ONE multi-axis
measurement (``axes.py``, DESIGN.md §12) and executes on one of three
backends (``backend.py``, DESIGN.md §13):

  * ``"pallas"``    — the compiled Pallas TPU kernel (platform default on
    TPU only);
  * ``"compiled"``  — a jit-compiled pure-jnp path running the SAME block
    math (``axes._axes_block``), bit-exact with the kernel and the
    production path on CPU/GPU;
  * ``"interpret"`` — the Pallas interpreter, kept only as an explicit
    validation switch.

Resolution: explicit ``backend=`` > legacy ``interpret=`` bool >
``force_default_backend`` context > ``$REPRO_KERNEL_BACKEND`` > platform.

The wrappers also handle padding/trimming, the shared inter-block fold
(:func:`_fold_axes`), chunked streaming (``chunk_packets=``: a ``lax.scan``
over fixed-size packet chunks threading the fold carry across chunk
boundaries — O(chunk) live memory, bit-exact with the one-shot path) and a
``shard_map``-sharded link axis (:func:`bt_count_axes_sharded`).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import _obs_hooks as _obs
from repro.core.coding import bus_invert_partitions as _partitions

from .axes import (
    CodecVariant,
    Variant,
    bt_axes_compiled,
    bt_axes_pallas,
    max_partitions,
    validate_variants,
)
from .backend import (
    BACKENDS,
    BACKEND_ENV_VAR,
    default_backend,
    force_default_backend,
    resolve_backend,
)
from .btcount import bt_count_compiled, bt_count_pallas
from .psu import _popcount_bits, psu_sort_compiled, psu_sort_pallas
from .quantize import quantize_egress_compiled, quantize_egress_pallas

__all__ = [
    "psu_sort",
    "psu_reorder",
    "psu_stream",
    "PsuStreamResult",
    "AxesActivity",
    "LinkActivity",
    "bt_count",
    "bt_count_axes",
    "bt_count_axes_sharded",
    "bt_count_links",
    "bt_count_variants",
    "bt_count_codecs",
    "Variant",
    "CodecVariant",
    "quantize_egress",
    "default_interpret",
    "default_backend",
    "resolve_backend",
    "force_default_backend",
    "BACKENDS",
    "BACKEND_ENV_VAR",
    "pallas_launch_count",
]


def default_interpret() -> bool:
    """Legacy switch: True when the default backend is not the real
    compiled Pallas kernel (i.e. anywhere off-TPU).  Kept for callers that
    predate the three-way backend dispatch."""
    return default_backend() != "pallas"


def _probe(entry: str, resolved: str, **data):
    """One ``kernel.dispatch`` probe span per public entry point call
    (DESIGN.md §14).  Fires in Python OUTSIDE the jitted computation, so
    the traced jaxpr is byte-identical with observability off, on, or
    absent; a no-op ``None`` test when nothing collects.
    ``pallas_launches`` records what this dispatch costs on the pallas
    path (the cross-backend invariant is 1 per entry; the compiled jnp
    backend launches no kernel)."""
    return _obs.span(
        "kernel.dispatch",
        entry=entry,
        backend=resolved,
        pallas_launches=0 if resolved == "compiled" else 1,
        **data,
    )


def _entry(jitted, backend: str):
    """The jit-compiled impl for the perf backends ("pallas"/"compiled");
    the UN-jitted original for "interpret".  The Pallas interpreter is the
    step-by-step validation path (per-op execution, debug prints); jitting
    it would fuse the emulation into one XLA program — fast enough to pass
    for a perf path, and hiding exactly the per-op execution it exists to
    expose.  Inside an outer ``jax.jit`` it is traced like any eager code.
    """
    return jitted.__wrapped__ if backend == "interpret" else jitted


def pallas_launch_count(fn, *args) -> int:
    """Number of ``pallas_call`` equations in the traced jaxpr of ``fn``
    (recursing through pjit/scan/etc. sub-jaxprs) — the measurement behind
    every 1-launch claim in this repo (benchmarks and tests alike).

    Tracing runs under ``force_default_backend("interpret")`` so the
    *pallas* path is what gets counted even where the session default is
    "compiled" (launch counts are the cross-backend grid invariant; the
    compiled backend would trivially trace to zero).  An explicit
    ``backend=``/``interpret=`` inside ``fn`` still wins.
    """
    from jax.extend import core as jcore

    def walk(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    n += walk(sub)
        return n

    def _subjaxprs(v):
        if isinstance(v, jcore.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jcore.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                yield from _subjaxprs(item)

    with force_default_backend("interpret"):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return walk(jaxpr)


@partial(
    jax.jit,
    static_argnames=("width", "k", "descending", "block_packets", "backend"),
)
def _psu_sort(
    packets: jax.Array,
    *,
    width: int,
    k: int | None,
    descending: bool,
    block_packets: int,
    backend: str,
) -> tuple[jax.Array, jax.Array]:
    p, n = packets.shape
    bp = min(block_packets, max(1, p))
    pad = (-p) % bp
    x = jnp.pad(packets.astype(jnp.int32), ((0, pad), (0, 0)))
    if backend == "compiled":
        order, rank = psu_sort_compiled(
            x, width=width, k=k, descending=descending, block_packets=bp
        )
    else:
        order, rank = psu_sort_pallas(
            x,
            width=width,
            k=k,
            descending=descending,
            block_packets=bp,
            interpret=backend == "interpret",
        )
    return order[:p], rank[:p]


def psu_sort(
    packets: jax.Array,
    width: int = 8,
    k: int | None = None,
    descending: bool = False,
    block_packets: int = 64,
    interpret: bool | None = None,
    backend: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(order, rank) of each packet by (approximate) popcount.

    Accepts any (P, N) integer array; P is padded to the kernel block size
    and trimmed on return.
    """
    resolved = resolve_backend(backend, interpret)
    with _probe("psu_sort", resolved, shape=tuple(map(int, packets.shape)),
                width=width, k=k):
        return _entry(_psu_sort, resolved)(
            packets,
            width=width,
            k=k,
            descending=descending,
            block_packets=block_packets,
            backend=resolved,
        )


def psu_reorder(
    packets: jax.Array,
    width: int = 8,
    k: int | None = None,
    descending: bool = False,
    interpret: bool | None = None,
    backend: str | None = None,
) -> jax.Array:
    """Packets with elements transmitted in PSU order (gather by ``order``)."""
    order, _ = psu_sort(
        packets,
        width=width,
        k=k,
        descending=descending,
        interpret=interpret,
        backend=backend,
    )
    return jnp.take_along_axis(packets, order, axis=-1)


# --------------------------------------------------------------------------
# the shared launch + inter-block fold of the multi-axis measurement
# (DESIGN.md §12/§13)


def _launch_axes(x, w, valid, *, backend, **kw):
    """One (L, P, N) multi-axis launch on the resolved backend."""
    if backend == "compiled":
        return bt_axes_compiled(x, w, valid, **kw)
    return bt_axes_pallas(x, w, valid, interpret=backend == "interpret", **kw)


class AxesActivity(NamedTuple):
    """:func:`bt_count_axes` result with per-wire switching activity.

    Wire indexing (DESIGN.md §15): ``lanes * 8`` data wires first (wire =
    lane * 8 + bit, LSB first), then ``PMAX`` invert-line aux wires (only
    the first ``partitions`` of a bus-invert config ever toggle).
    """

    bt: jax.Array  # (L, C, 3) per-link, per-config BT totals
    toggles: jax.Array  # (L, C, NW, WIRES) toggle counts per time window
    ones: jax.Array  # (L, C, WIRES) flit rows each wire spent at level 1


class LinkActivity(NamedTuple):
    """:func:`bt_count_links` result with per-wire switching activity."""

    bt: jax.Array  # (L, 2) per-link (input, weight) BT totals
    toggles: jax.Array  # (L, NW, WIRES)
    ones: jax.Array  # (L, WIRES)


def _vary_like(tree, like):
    """``tree`` made varying over the manual mesh axes ``like`` varies over.

    Inside ``shard_map`` the per-link data varies over the mesh axis, and a
    ``lax.scan`` carry must keep one type from step to step, so zero
    initial carries are cast to varying (a no-op outside ``shard_map``).
    """
    vma = tuple(sorted(jax.typeof(like).vma))
    if not vma:
        return tree
    return jax.tree.map(lambda c: lax.pcast(c, vma, to="varying"), tree)


def _axes_carry(nl: int, configs, lanes: int, activity: bool = False):
    """The zero inter-chunk fold carry: nothing transmitted yet."""
    pmax = max_partitions(configs, lanes)
    carry = {
        "started": jnp.zeros((nl,), jnp.int32),
        "wire": jnp.zeros((len(configs), nl, lanes), jnp.int32),
        "inv": jnp.zeros((len(configs), nl, pmax), jnp.int32),
    }
    if activity:
        # per-wire level parity entering the next chunk ('transition'
        # signaling: the wire level is the running data parity)
        carry["parity"] = jnp.zeros((len(configs), nl, lanes * 8), jnp.int32)
    return carry


def _fold_axes(
    partials: jax.Array,  # (L, G, C, 2, PMAX, 3)
    edges: jax.Array,  # (L, G, C, 2, 2, lanes)
    inv_edges: jax.Array,  # (L, G, C, 2, 2, PMAX)
    configs: tuple[CodecVariant, ...],
    valid_rows: jax.Array,  # (L,) real flit rows per link (this chunk)
    rows: int,  # flit rows per block
    split_lanes: int,
    carry=None,
    return_carry: bool = False,
    activity=None,
    window_rows: int = 0,
    base_row=None,
):
    """Fold per-(link, block) kernel partials into (L, C, 3) totals.

    Block-internal boundaries are already masked in-kernel; this patches
    the inter-block boundaries per link in O(G) jnp — stateless codecs XOR
    adjacent edge flits, transition signaling adds each block's first-flit
    popcount, and bus-invert carries each block's entry branch from the
    previous block's last wire flit (``lax.scan``).  Boundaries into
    fully-padded blocks are masked by each link's ``valid_rows``.

    ``carry`` / ``return_carry`` extend the same fold across *chunk*
    boundaries (the ``chunk_packets`` streaming mode): the carry pytree
    holds, per link, whether anything was transmitted yet ("started"), the
    last wire flit per config ("wire") and the last invert-line states
    ("inv").  With ``carry=None`` the stream starts cold — block 0 enters
    uninverted and its first flit pays no boundary — which reproduces the
    single-shot fold exactly.

    ``activity`` is the optional (act, ones) kernel output pair
    (DESIGN.md §15); the fold then also returns the per-wire window
    toggles (L, C, NW, WIRES) and wire-level 1-counts (L, C, WIRES): the
    inter-block boundary toggles are scattered into the window of each
    block's first row (``base_row`` offsets the chunk), bus-invert branch
    outputs are selected per PARTITION over the wire axis, and transition
    1-counts are resolved against the carried per-wire entry parity (the
    "parity" carry slot).
    """
    nl, gblocks = partials.shape[:2]
    lanes = edges.shape[-1]
    pmax = partials.shape[-2]
    if carry is None:
        carry = _vary_like(
            _axes_carry(nl, configs, lanes, activity=activity is not None),
            partials,
        )
    started0 = carry["started"]
    has = (valid_rows > 0).astype(jnp.int32)
    # block g holds >= 1 valid row of this link
    gmask = (
        jnp.arange(gblocks, dtype=jnp.int32)[None, :] * rows
        < valid_rows[:, None]
    ).astype(jnp.int32)  # (L, G)
    # the last block holding valid rows (0 when the chunk is empty)
    glast = jnp.clip((valid_rows + rows - 1) // rows - 1, 0, gblocks - 1)

    def _sides(flips):  # (..., lanes) -> (..., 2) per-side sums
        in_side = flips[..., :split_lanes].sum(-1)
        w_side = (
            flips[..., split_lanes:].sum(-1)
            if split_lanes < lanes
            else jnp.zeros_like(in_side)
        )
        return jnp.stack([in_side, w_side], axis=-1)

    if activity is not None:
        act_in, ones_in = activity  # (L,G,C,2,NW,WIRES), (L,G,C,2,WIRES)
        num_windows = act_in.shape[-2]
        dwires = lanes * 8
        base = (
            jnp.int32(0) if base_row is None
            else jnp.asarray(base_row, jnp.int32)
        )
        # global first row of block g -> the window its entry boundary hits
        g_first = base + jnp.arange(gblocks, dtype=jnp.int32) * rows
        win_onehot_g = (
            (g_first // window_rows)[:, None]
            == jnp.arange(num_windows, dtype=jnp.int32)[None, :]
        ).astype(jnp.int32)  # (G, NW)
        valid_blk = jnp.clip(
            valid_rows[:, None]
            - jnp.arange(gblocks, dtype=jnp.int32)[None, :] * rows,
            0,
            rows,
        )  # (L, G) valid rows inside block g
        bit8 = jnp.arange(8, dtype=jnp.int32)

        def _bits8(arr):  # (..., K) bytes -> (..., K*8) bits, LSB first
            bits = (arr[..., None] >> bit8) & 1
            return bits.reshape(*arr.shape[:-1], arr.shape[-1] * 8)

        def _scatter_g(bnd):  # (L, G, W) -> (L, NW, W) window scatter
            return jnp.einsum("lgw,gn->lnw", bnd, win_onehot_g)

    totals, wire_out, inv_out = [], [], []
    acts_out, ones_out, parity_out = [], [], []
    for ci, cfg in enumerate(configs):
        if cfg.codec == "bus_invert":
            npart, pw = _partitions(lanes, cfg.partition)
            lbits = 8 * pw
            in_mask = (
                jnp.arange(lanes, dtype=jnp.int32) < split_lanes
            ).astype(jnp.int32).reshape(npart, pw)

            def fold(state, blk):
                cw, civ, st = state  # (L,npart,pw), (L,npart), (L,)
                part_g, edge_g, inv_g, m = blk
                # branch-0 first wire IS the block's first data flit
                d_first = edge_g[:, 0, 0].reshape(nl, npart, pw)
                hd = _popcount_bits(d_first ^ cw, 8).sum(-1)
                # entry branch; forced 0 before anything was transmitted
                b = (2 * hd > lbits).astype(jnp.int32) * st[:, None]
                first_wire = d_first ^ (b[..., None] * 0xFF)
                flips = _popcount_bits(cw ^ first_wire, 8)
                bnd = jnp.stack(
                    [
                        (flips * in_mask).sum(-1),
                        (flips * (1 - in_mask)).sum(-1),
                        (civ != b).astype(jnp.int32),
                    ],
                    axis=-1,
                ) * st[:, None, None]  # no boundary into the first flit ever
                sel = jnp.where(b[..., None] == 1, part_g[:, 1], part_g[:, 0])
                ew = edge_g[:, :, 1].reshape(nl, 2, npart, pw)
                new_wire = jnp.where(b[..., None] == 1, ew[:, 1], ew[:, 0])
                iv = inv_g[:, :, 1]  # (L, 2, npart)
                new_inv = jnp.where(b == 1, iv[:, 1], iv[:, 0])
                # links whose valid rows end before this block keep their
                # carry and contribute nothing
                m3 = m[:, None, None]
                new_wire = jnp.where(m3 == 1, new_wire, cw)
                new_inv = jnp.where(m[:, None] == 1, new_inv, civ)
                ys = (bnd + sel) * m3
                if activity is not None:
                    # per-wire boundary toggles + the entry branch per
                    # partition (selects the kernel's per-branch activity)
                    stm = (st * m)[:, None]
                    ys = (
                        ys,
                        b,
                        _bits8((cw ^ first_wire).reshape(nl, lanes)) * stm,
                        (civ != b).astype(jnp.int32) * stm,
                    )
                return (new_wire, new_inv, jnp.maximum(st, m)), ys

            carry0 = (
                carry["wire"][ci].reshape(nl, npart, pw),
                carry["inv"][ci, :, :npart],
                started0,
            )
            (cw, civ, _), scan_ys = lax.scan(
                fold,
                carry0,
                (
                    jnp.moveaxis(partials[:, :, ci, :, :npart], 1, 0),
                    jnp.moveaxis(edges[:, :, ci], 1, 0),
                    jnp.moveaxis(inv_edges[:, :, ci, :, :, :npart], 1, 0),
                    jnp.moveaxis(gmask, 1, 0),
                ),
            )
            contribs = scan_ys[0] if activity is not None else scan_ys
            totals.append(contribs.sum(axis=0).sum(axis=1))  # (L, 3)
            wire_out.append(cw.reshape(nl, lanes))
            inv_out.append(jnp.pad(civ, ((0, 0), (0, pmax - npart))))
            if activity is not None:
                _, bs, bnd_bits, aux_bnd = scan_ys
                # map every wire to its partition's entry branch: data wire
                # lane*8+bit -> partition wire // (8*pw); aux wire i -> i
                part_of_wire = jnp.concatenate([
                    jnp.arange(dwires, dtype=jnp.int32) // (8 * pw),
                    jnp.minimum(
                        jnp.arange(pmax, dtype=jnp.int32), npart - 1
                    ),
                ])
                bsel = jnp.moveaxis(bs, 0, 1)[:, :, part_of_wire]
                acts_out.append(jnp.where(
                    bsel[:, :, None, :] == 1,
                    act_in[:, :, ci, 1],
                    act_in[:, :, ci, 0],
                ).sum(axis=1) + _scatter_g(jnp.concatenate([
                    jnp.moveaxis(bnd_bits, 0, 1),
                    jnp.pad(
                        jnp.moveaxis(aux_bnd, 0, 1),
                        ((0, 0), (0, 0), (0, pmax - npart)),
                    ),
                ], axis=-1)))
                ones_out.append(jnp.where(
                    bsel == 1, ones_in[:, :, ci, 1], ones_in[:, :, ci, 0]
                ).sum(axis=1))
                parity_out.append(carry["parity"][ci])
        else:
            # branch 0 carries every stateless codec; padded slots are zero
            total = partials[:, :, ci, 0].sum(axis=(1, 2))  # (L, 3)
            first = edges[:, :, ci, 0, 0, :]  # (L, G, lanes)
            last = edges[:, :, ci, 0, 1, :]
            if cfg.codec == "transition":
                # boundary flips = each block's first DATA flit bits
                bnd_bytes = first
            else:
                prev = jnp.concatenate(
                    [carry["wire"][ci][:, None], last[:, :-1]], axis=1
                )
                bnd_bytes = prev ^ first
            flips = _popcount_bits(bnd_bytes, 8)
            # boundary into block g counts iff block g is real AND there is
            # a previous flit (g > 0, or the stream already started)
            entry = jnp.concatenate(
                [started0[:, None], jnp.ones((nl, gblocks - 1), jnp.int32)],
                axis=1,
            )
            bnd = (_sides(flips) * (gmask * entry)[..., None]).sum(axis=1)
            totals.append(
                total
                + jnp.concatenate([bnd, jnp.zeros((nl, 1), jnp.int32)], axis=-1)
            )
            lastw = jnp.take_along_axis(last, glast[:, None, None], axis=1)[:, 0]
            wire_out.append(
                jnp.where(has[:, None] == 1, lastw, carry["wire"][ci])
            )
            inv_out.append(carry["inv"][ci])
            if activity is not None:
                bb = _bits8(bnd_bytes) * (gmask * entry)[..., None]
                acts_out.append(
                    act_in[:, :, ci, 0].sum(axis=1)
                    + _scatter_g(jnp.pad(bb, ((0, 0), (0, 0), (0, pmax))))
                )
                if cfg.codec == "transition":
                    # resolve slot-0 1-counts against the carried per-wire
                    # entry parity; slot 1 holds each block's data parity
                    ones_e0 = ones_in[:, :, ci, 0, :dwires]  # (L, G, D)
                    pblk = ones_in[:, :, ci, 1, :dwires]
                    pcarry = carry["parity"][ci]  # (L, D)
                    pent = (
                        pcarry[:, None, :]
                        + jnp.cumsum(pblk, axis=1) - pblk
                    ) & 1
                    ones_g = jnp.where(
                        pent == 1,
                        valid_blk[..., None] - ones_e0,
                        ones_e0,
                    )
                    ones_out.append(jnp.pad(
                        ones_g.sum(axis=1), ((0, 0), (0, pmax))
                    ))
                    parity_out.append((pcarry + pblk.sum(axis=1)) & 1)
                else:
                    ones_out.append(ones_in[:, :, ci, 0].sum(axis=1))
                    parity_out.append(carry["parity"][ci])
    out = jnp.stack(totals, axis=1).astype(jnp.int32)  # (L, C, 3)
    res = (out,)
    if activity is not None:
        res += (
            jnp.stack(acts_out, axis=1).astype(jnp.int32),  # (L,C,NW,WIRES)
            jnp.stack(ones_out, axis=1).astype(jnp.int32),  # (L,C,WIRES)
        )
    if not return_carry:
        return res[0] if activity is None else res
    new_carry = {
        "started": jnp.maximum(started0, has),
        "wire": jnp.stack(wire_out),
        "inv": jnp.stack(inv_out),
    }
    if activity is not None:
        new_carry["parity"] = jnp.stack(parity_out)
        return res + (new_carry,)
    return out, new_carry


def _dispatch_axes(
    inputs,
    weights,
    valid,
    *,
    configs,
    width,
    input_lanes,
    weight_lanes,
    split_lanes,
    pack,
    block_packets,
    backend,
    chunk_packets=None,
    activity_windows=None,
):
    """Pad, launch (on the resolved backend) and fold — optionally chunked.

    The one driver every BT entry point reduces to.  With ``chunk_packets``
    the packet axis becomes a ``lax.scan`` over fixed-size chunks threading
    the :func:`_fold_axes` carry (bus-invert wire/invert-line state,
    stateless-codec edge flits) across chunk boundaries — bit-exact with
    the single-launch path while bounding live intermediates to one chunk.

    With ``activity_windows`` every launch also accumulates the per-wire
    window-toggle tensor (DESIGN.md §15): windows are indexed by GLOBAL
    flit row (each chunk offsets its blocks by ``base_row``), so the
    chunked path lands every toggle in the same window as the one-shot
    path and the trimmed :class:`AxesActivity` result is bit-exact.
    """
    links, p, n = inputs.shape
    flits = n // input_lanes
    sl = input_lanes if split_lanes is None else split_lanes
    bp = min(block_packets, max(1, p))
    kw = dict(
        configs=configs,
        width=width,
        input_lanes=input_lanes,
        weight_lanes=weight_lanes,
        split_lanes=split_lanes,
        pack=pack,
        block_packets=bp,
    )
    wlen = activity_windows
    nw_real = 0 if wlen is None else -(-(p * flits) // wlen)
    x = inputs.astype(jnp.int32)
    w = weights.astype(jnp.int32)
    if chunk_packets is None:
        pad = (-p) % bp
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)))
        if wlen is None:
            partials, edges, inv_edges = _launch_axes(
                x, w, valid, backend=backend, **kw
            )
            return _fold_axes(
                partials, edges, inv_edges, configs, valid * flits,
                bp * flits, sl,
            )
        nw = -(-((p + pad) * flits) // wlen)
        partials, edges, inv_edges, act, ones = _launch_axes(
            x, w, valid, backend=backend, window_rows=wlen, num_windows=nw,
            **kw,
        )
        bt, act_t, ones_t = _fold_axes(
            partials, edges, inv_edges, configs, valid * flits, bp * flits,
            sl, activity=(act, ones), window_rows=wlen,
        )
        return AxesActivity(bt, act_t[:, :, :nw_real], ones_t)
    # chunked streaming: the chunk is rounded up to a whole block count
    cp = -(-chunk_packets // bp) * bp
    pad = (-p) % cp
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)))
    nchunks = (p + pad) // cp
    xb = jnp.moveaxis(x.reshape(links, nchunks, cp, n), 1, 0)
    wb = jnp.moveaxis(w.reshape(links, nchunks, cp, n), 1, 0)
    cvalid = jnp.clip(
        valid[None, :] - jnp.arange(nchunks, dtype=jnp.int32)[:, None] * cp,
        0,
        cp,
    )  # (nchunks, L) valid packets per chunk
    nw = 0 if wlen is None else -(-(nchunks * cp * flits) // wlen)
    bases = jnp.arange(nchunks, dtype=jnp.int32) * (cp * flits)

    def step(state, blk):
        if wlen is None:
            fold_carry, total = state
            xc, wc, vc, _ = blk
            partials, edges, inv_edges = _launch_axes(
                xc, wc, vc, backend=backend, **kw
            )
            bt, fold_carry = _fold_axes(
                partials, edges, inv_edges, configs, vc * flits, bp * flits,
                sl, carry=fold_carry, return_carry=True,
            )
            return (fold_carry, total + bt), None
        fold_carry, total, act_tot, ones_tot = state
        xc, wc, vc, basec = blk
        partials, edges, inv_edges, act, ones = _launch_axes(
            xc, wc, vc, backend=backend, window_rows=wlen, num_windows=nw,
            base_row=basec, **kw,
        )
        bt, act_t, ones_t, fold_carry = _fold_axes(
            partials, edges, inv_edges, configs, vc * flits, bp * flits, sl,
            carry=fold_carry, return_carry=True, activity=(act, ones),
            window_rows=wlen, base_row=basec,
        )
        return (
            fold_carry, total + bt, act_tot + act_t, ones_tot + ones_t
        ), None

    lanes = input_lanes + weight_lanes
    carry0 = _axes_carry(links, configs, lanes, activity=wlen is not None)
    total0 = jnp.zeros((links, len(configs), 3), jnp.int32)
    state0 = (carry0, total0)
    if wlen is not None:
        nwires = lanes * 8 + max_partitions(configs, lanes)
        state0 += (
            jnp.zeros((links, len(configs), nw, nwires), jnp.int32),
            jnp.zeros((links, len(configs), nwires), jnp.int32),
        )
    state0 = _vary_like(state0, x)
    state, _ = lax.scan(step, state0, (xb, wb, cvalid, bases))
    if wlen is None:
        return state[1]
    return AxesActivity(state[1], state[2][:, :, :nw_real], state[3])


def _paired(inputs, weights, weight_lanes, input_lanes):
    """Shared (weights, weight_lanes) defaulting of the packet wrappers."""
    if weights is None:
        weight_lanes = 0 if weight_lanes is None else weight_lanes
        weights = jnp.zeros_like(inputs)
    elif weight_lanes is None:
        weight_lanes = input_lanes
    if weights.shape != inputs.shape:
        raise ValueError(f"paired shapes differ: {inputs.shape} vs {weights.shape}")
    return weights, weight_lanes


class PsuStreamResult(NamedTuple):
    """Everything the fused TX pipeline produces in one kernel launch."""

    order: jax.Array  # (P, N) int32: input index transmitted j-th
    rank: jax.Array  # (P, N) int32: output slot of input element i
    stream: jax.Array  # (P*F, lanes) uint8 packed flit rows
    bt_input: jax.Array  # int32 scalar: input-side bit transitions
    bt_weight: jax.Array  # int32 scalar: weight-side bit transitions


@partial(
    jax.jit,
    static_argnames=(
        "width",
        "k",
        "descending",
        "input_lanes",
        "weight_lanes",
        "pack",
        "block_packets",
        "backend",
    ),
)
def _psu_stream(
    inputs: jax.Array,
    weights: jax.Array | None,
    *,
    width: int,
    k: int | None,
    descending: bool,
    input_lanes: int,
    weight_lanes: int | None,
    pack: str,
    block_packets: int,
    backend: str,
) -> PsuStreamResult:
    weights, weight_lanes = _paired(inputs, weights, weight_lanes, input_lanes)
    p, n = inputs.shape
    flits = n // input_lanes
    bp = min(block_packets, max(1, p))
    pad = (-p) % bp
    x = jnp.pad(inputs.astype(jnp.int32), ((0, pad), (0, 0)))
    w = jnp.pad(weights.astype(jnp.int32), ((0, pad), (0, 0)))
    cfg = CodecVariant("acc" if k is None else "app", k, descending)
    valid = jnp.full((1,), p, jnp.int32)
    partials, edges, inv_edges, order, rank, stream = _launch_axes(
        x[None],
        w[None],
        valid,
        backend=backend,
        configs=(cfg,),
        width=width,
        input_lanes=input_lanes,
        weight_lanes=weight_lanes,
        pack=pack,
        block_packets=bp,
        emit_stream=True,
    )
    bt = _fold_axes(
        partials, edges, inv_edges, (cfg,), valid * flits, bp * flits,
        input_lanes,
    )[0, 0]
    return PsuStreamResult(
        order[0, :p],
        rank[0, :p],
        stream[0, : p * flits].astype(jnp.uint8),
        bt[0],
        bt[1],
    )


def psu_stream(
    inputs: jax.Array,
    weights: jax.Array | None = None,
    width: int = 8,
    k: int | None = None,
    descending: bool = False,
    input_lanes: int = 8,
    weight_lanes: int | None = None,
    pack: str = "lane",
    block_packets: int = 64,
    interpret: bool | None = None,
    backend: str | None = None,
) -> PsuStreamResult:
    """Fused popcount-sort -> reorder -> flit-pack -> BT-count, one launch.

    The multi-axis measurement in ``emit_stream`` mode: one link, one
    uncoded 'acc'/'app' config, with the permutation-matrix contraction
    also yielding ``order``/``rank`` and the packed wire stream.  Accepts
    any (P, N) integer packets; P is padded to the kernel block size and
    the padded tail is masked inside the launch (the unified convention) —
    the wrapper only folds the G-1 inter-block flit boundaries.
    """
    resolved = resolve_backend(backend, interpret)
    with _probe("psu_stream", resolved, shape=tuple(map(int, inputs.shape)),
                width=width, k=k, pack=pack,
                blocks=-(-int(inputs.shape[0]) // max(1, block_packets))):
        return _entry(_psu_stream, resolved)(
            inputs,
            weights,
            width=width,
            k=k,
            descending=descending,
            input_lanes=input_lanes,
            weight_lanes=weight_lanes,
            pack=pack,
            block_packets=block_packets,
            backend=resolved,
        )


@partial(jax.jit, static_argnames=("width", "block_rows", "backend"))
def _bt_count(
    stream: jax.Array, *, width: int, block_rows: int, backend: str
) -> jax.Array:
    if backend == "compiled":
        return bt_count_compiled(stream, width=width)
    return bt_count_pallas(
        stream, width=width, block_rows=block_rows,
        interpret=backend == "interpret",
    )


def bt_count(
    stream: jax.Array,
    width: int = 8,
    block_rows: int = 512,
    interpret: bool | None = None,
    backend: str | None = None,
) -> jax.Array:
    """Total bit transitions of a (T, L) flit stream."""
    resolved = resolve_backend(backend, interpret)
    with _probe("bt_count", resolved, shape=tuple(map(int, stream.shape)),
                width=width):
        return _entry(_bt_count, resolved)(
            stream,
            width=width,
            block_rows=block_rows,
            backend=resolved,
        )


@partial(
    jax.jit,
    static_argnames=(
        "configs",
        "width",
        "input_lanes",
        "weight_lanes",
        "split_lanes",
        "pack",
        "block_packets",
        "backend",
        "chunk_packets",
        "activity_windows",
    ),
)
def _bt_count_axes(
    inputs: jax.Array,
    weights: jax.Array | None,
    valid,
    *,
    configs: tuple[CodecVariant, ...],
    width: int,
    input_lanes: int,
    weight_lanes: int | None,
    split_lanes: int | None,
    pack: str,
    block_packets: int,
    backend: str,
    chunk_packets: int | None,
    activity_windows: int | None = None,
) -> jax.Array:
    weights, weight_lanes = _paired(inputs, weights, weight_lanes, input_lanes)
    links, p, n = inputs.shape
    nc = len(configs)
    if links == 0 or p == 0:
        bt = jnp.zeros((links, nc, 3), jnp.int32)
        if activity_windows is None:
            return bt
        lanes = input_lanes + weight_lanes
        nwires = lanes * 8 + max_partitions(configs, lanes)
        nw = 0 if p == 0 else -(-(p * (n // input_lanes)) // activity_windows)
        return AxesActivity(
            bt,
            jnp.zeros((links, nc, nw, nwires), jnp.int32),
            jnp.zeros((links, nc, nwires), jnp.int32),
        )
    if valid is None:
        valid = jnp.full((links,), p, jnp.int32)
    else:
        # clamp to the packets actually present: a valid count past P would
        # silently count the last-real -> zero-pad boundary as real
        valid = jnp.minimum(jnp.asarray(valid, jnp.int32), p)
    return _dispatch_axes(
        inputs,
        weights,
        valid,
        configs=configs,
        width=width,
        input_lanes=input_lanes,
        weight_lanes=weight_lanes,
        split_lanes=split_lanes,
        pack=pack,
        block_packets=block_packets,
        backend=backend,
        chunk_packets=chunk_packets,
        activity_windows=activity_windows,
    )


def bt_count_axes(
    inputs: jax.Array,
    weights: jax.Array | None = None,
    valid: jax.Array | Sequence[int] | None = None,
    configs: tuple[CodecVariant, ...] = (CodecVariant(),),
    width: int = 8,
    input_lanes: int = 8,
    weight_lanes: int | None = None,
    split_lanes: int | None = None,
    pack: str = "lane",
    block_packets: int = 64,
    interpret: bool | None = None,
    backend: str | None = None,
    chunk_packets: int | None = None,
    activity_windows: int | None = None,
) -> jax.Array:
    """The full multi-axis measurement: per-LINK, per-(ordering, codec)
    config BT of a (L, P, N) packet batch in ONE kernel launch.

    This is the grid the whole stack reduces to (DESIGN.md §12): NoC links,
    DSE variants and wire codecs are orthogonal axes of one launch.  Links
    may be jagged — ``valid`` gives each link's real packet count and
    everything past it contributes zero data BT and zero aux BT (so a
    bus-invert decision is never evaluated on a padded flit).

    Args:
      inputs: (L, P, N) integer packets (P = the longest link, zero-padded).
      weights: optional (L, P, N) paired weight bytes.
      valid: (L,) real packet counts (default: all P real).
      configs: static tuple of :class:`CodecVariant` configurations.
      split_lanes: lane where the input side ends for per-side accounting
        (default ``input_lanes``; the NoC path feeds pre-assembled flit
        rows as N = lanes packets and splits at the spec's input_lanes).
      backend / interpret: backend selection (DESIGN.md §13); default
        resolves platform/env via :func:`repro.kernels.default_backend`.
      chunk_packets: process the packet axis as a scan over chunks of this
        many packets (rounded up to a block multiple), threading the
        inter-block fold carry across chunk edges — bit-exact, O(chunk)
        live memory.
      activity_windows: also accumulate the per-wire switching-activity
        tensor with this window length in FLIT ROWS (DESIGN.md §15); the
        return type becomes :class:`AxesActivity` with ``toggles`` of
        shape (L, C, ceil(P*F / activity_windows), lanes*8 + PMAX) and
        ``ones`` (time-at-1 per wire, in flit rows) of (L, C, wires).

    Returns:
      int32 (L, C, 3): per-link, per-config (input-side BT, weight-side
      BT, invert-line BT) totals — or :class:`AxesActivity` when
      ``activity_windows`` is set.
    """
    if inputs.ndim != 3:
        raise ValueError(f"expected (L, P, N) packets, got {inputs.shape}")
    if activity_windows is not None and activity_windows < 1:
        raise ValueError(f"activity_windows must be >= 1, got {activity_windows}")
    resolved = resolve_backend(backend, interpret)
    links, p, _ = (int(d) for d in inputs.shape)
    with _probe("bt_count_axes", resolved,
                shape=tuple(map(int, inputs.shape)),
                configs=len(tuple(configs)), width=width,
                blocks=links * -(-p // max(1, min(block_packets, max(1, p)))),
                chunked=chunk_packets is not None,
                activity=activity_windows is not None):
        return _entry(_bt_count_axes, resolved)(
            inputs,
            weights,
            valid,
            configs=tuple(configs),
            width=width,
            input_lanes=input_lanes,
            weight_lanes=weight_lanes,
            split_lanes=split_lanes,
            pack=pack,
            block_packets=block_packets,
            backend=resolved,
            chunk_packets=chunk_packets,
            activity_windows=activity_windows,
        )


@partial(
    jax.jit,
    static_argnames=(
        "mesh",
        "configs",
        "width",
        "input_lanes",
        "weight_lanes",
        "split_lanes",
        "pack",
        "block_packets",
        "backend",
        "chunk_packets",
        "activity_windows",
    ),
)
def _bt_count_axes_sharded(
    inputs: jax.Array,
    weights: jax.Array,
    valid: jax.Array,
    *,
    mesh,
    configs: tuple[CodecVariant, ...],
    width: int,
    input_lanes: int,
    weight_lanes: int,
    split_lanes: int | None,
    pack: str,
    block_packets: int,
    backend: str,
    chunk_packets: int | None,
    activity_windows: int | None,
):
    from jax.sharding import PartitionSpec

    links = inputs.shape[0]
    nd = mesh.devices.size
    lpad = (-links) % nd
    x = jnp.pad(inputs.astype(jnp.int32), ((0, lpad), (0, 0), (0, 0)))
    w = jnp.pad(weights.astype(jnp.int32), ((0, lpad), (0, 0), (0, 0)))
    v = jnp.pad(valid, (0, lpad))
    ltot = links + lpad
    shard = ltot // nd

    def _assemble(arr):
        # scatter this shard's rows into the full-link layout and psum
        full = jnp.zeros((ltot,) + arr.shape[1:], arr.dtype)
        idx = (lax.axis_index("links") * shard,) + (0,) * (arr.ndim - 1)
        return lax.psum(lax.dynamic_update_slice(full, arr, idx), "links")

    def local(xs, ws, vs):
        out = _dispatch_axes(
            xs,
            ws,
            vs,
            configs=configs,
            width=width,
            input_lanes=input_lanes,
            weight_lanes=weight_lanes,
            split_lanes=split_lanes,
            pack=pack,
            block_packets=block_packets,
            backend=backend,
            chunk_packets=chunk_packets,
            activity_windows=activity_windows,
        )
        if activity_windows is None:
            return _assemble(out)
        return AxesActivity(*(_assemble(o) for o in out))

    spec = PartitionSpec("links")
    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=PartitionSpec(),
    )(x, w, v)
    if activity_windows is None:
        return out[:links]
    return AxesActivity(*(o[:links] for o in out))


def bt_count_axes_sharded(
    inputs: jax.Array,
    weights: jax.Array | None = None,
    valid: jax.Array | Sequence[int] | None = None,
    configs: tuple[CodecVariant, ...] = (CodecVariant(),),
    width: int = 8,
    input_lanes: int = 8,
    weight_lanes: int | None = None,
    split_lanes: int | None = None,
    pack: str = "lane",
    block_packets: int = 64,
    interpret: bool | None = None,
    backend: str | None = None,
    chunk_packets: int | None = None,
    activity_windows: int | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> jax.Array:
    """:func:`bt_count_axes` with the LINK axis sharded across devices.

    ``jax.shard_map`` splits the links of a NoC grid
    over a 1-D device mesh; each device measures its shard with the same
    launch + fold as the unsharded path, scatters it into the full-table
    layout and a ``psum`` assembles the replicated (L, C, 3) BT table.
    Links are padded to a device multiple with ``valid = 0`` links, whose
    rows the unified masking convention zeroes — so the padding is exact,
    not approximate.  Per-link results are bit-identical to the unsharded
    entry point (each link's fold never crosses the shard boundary).  Like
    every entry point the sharded program is jit-compiled once per shape
    and configuration (the interpreter backend runs it eagerly).
    """
    if inputs.ndim != 3:
        raise ValueError(f"expected (L, P, N) packets, got {inputs.shape}")
    from jax.sharding import Mesh

    backend = resolve_backend(backend, interpret)
    devices = list(jax.devices() if devices is None else devices)
    weights, weight_lanes = _paired(inputs, weights, weight_lanes, input_lanes)
    links, p, n = inputs.shape
    nc = len(configs := tuple(configs))
    lanes = input_lanes + weight_lanes
    if links == 0 or p == 0:
        bt = jnp.zeros((links, nc, 3), jnp.int32)
        if activity_windows is None:
            return bt
        nwires = lanes * 8 + max_partitions(configs, lanes)
        nw = 0 if p == 0 else -(-(p * (n // input_lanes)) // activity_windows)
        return AxesActivity(
            bt,
            jnp.zeros((links, nc, nw, nwires), jnp.int32),
            jnp.zeros((links, nc, nwires), jnp.int32),
        )
    if valid is None:
        valid = jnp.full((links,), p, jnp.int32)
    else:
        valid = jnp.minimum(jnp.asarray(valid, jnp.int32), p)
    with _probe("bt_count_axes_sharded", backend,
                shape=(int(links), int(p), int(n)), configs=nc, width=width,
                devices=len(devices), activity=activity_windows is not None):
        return _entry(_bt_count_axes_sharded, backend)(
            inputs,
            weights,
            valid,
            mesh=Mesh(np.asarray(devices), ("links",)),
            configs=configs,
            width=width,
            input_lanes=input_lanes,
            weight_lanes=weight_lanes,
            split_lanes=split_lanes,
            pack=pack,
            block_packets=block_packets,
            backend=backend,
            chunk_packets=chunk_packets,
            activity_windows=activity_windows,
        )


@partial(
    jax.jit,
    static_argnames=(
        "input_lanes", "width", "block_rows", "backend", "chunk_rows",
        "activity_windows",
    ),
)
def _bt_count_links(
    streams: jax.Array,
    lengths,
    *,
    input_lanes: int,
    width: int,
    block_rows: int,
    backend: str,
    chunk_rows: int | None,
    activity_windows: int | None = None,
) -> jax.Array:
    links, t, lanes = streams.shape
    valid = (
        jnp.full((links,), t, jnp.int32)
        if lengths is None
        else jnp.minimum(jnp.asarray(lengths, jnp.int32), t)
    )
    out = _dispatch_axes(
        streams,
        jnp.zeros_like(streams),
        valid,
        configs=(CodecVariant("none"),),
        width=width,
        input_lanes=lanes,
        weight_lanes=0,
        split_lanes=input_lanes,
        pack="row",
        block_packets=block_rows,
        backend=backend,
        chunk_packets=chunk_rows,
        activity_windows=activity_windows,
    )
    if activity_windows is None:
        return out[:, 0, :2]
    # one uncoded config: drop the config axis and the (zero) aux wire
    return LinkActivity(
        out.bt[:, 0, :2], out.toggles[:, 0, :, : lanes * 8],
        out.ones[:, 0, : lanes * 8],
    )


def bt_count_links(
    streams: jax.Array,
    input_lanes: int | None = None,
    lengths: jax.Array | Sequence[int] | None = None,
    width: int = 8,
    block_links: int = 8,
    block_rows: int = 512,
    interpret: bool | None = None,
    backend: str | None = None,
    chunk_rows: int | None = None,
    activity_windows: int | None = None,
) -> jax.Array:
    """Per-link BT of a (L, T, lanes) stream batch in ONE kernel launch.

    The batched replacement for looping ``bt_count`` over the links of a
    NoC: each pre-assembled flit row is one N = lanes "packet" of the
    multi-axis measurement with the identity ordering, so the link axis
    rides the kernel grid.  Jagged links pass their real flit counts via
    ``lengths`` and everything past them is masked inside the launch (the
    unified convention) — any padding value is neutral, including the
    repeated-last-flit rows ``repro.noc.simulate.stack_link_streams``
    emits (which are also zero-BT on their own).

    Args:
      streams: (L, T, lanes) integer flit streams, one per link.
      input_lanes: lanes carrying input bytes (rest = weight side);
        default all lanes.
      lengths: (L,) real flit counts for jagged links (default: all T).
      width: element bit width of the lanes (byte lanes: 8).
      block_links: unused (one grid row per link); kept for call
        compatibility with the pre-unification kernel.
      block_rows: flit rows per grid step.
      backend / chunk_rows: backend selection and chunked streaming over
        the flit-row axis (see :func:`bt_count_axes`).
      activity_windows: also accumulate per-wire switching activity with
        this window length in flit rows; the return type becomes
        :class:`LinkActivity` with ``toggles`` (L, NW, lanes*8) and
        ``ones`` (L, lanes*8) over the data wires (wire = lane*8 + bit).

    Returns:
      int32 (L, 2): per-link (input-side, weight-side) bit transitions —
      or :class:`LinkActivity` when ``activity_windows`` is set.
    """
    del block_links  # the link axis is unblocked on the unified grid
    links, t, lanes = streams.shape
    if input_lanes is None:
        input_lanes = lanes
    if not 0 <= input_lanes <= lanes:
        raise ValueError(
            f"input_lanes={input_lanes} outside the {lanes}-lane flit"
        )
    if links == 0 or t == 0 or (t < 2 and activity_windows is None):
        bt = jnp.zeros((links, 2), jnp.int32)
        if activity_windows is None:
            return bt
        nw = -(-int(t) // activity_windows)
        return LinkActivity(
            bt,
            jnp.zeros((links, nw, lanes * 8), jnp.int32),
            jnp.zeros((links, lanes * 8), jnp.int32),
        )
    resolved = resolve_backend(backend, interpret)
    with _probe("bt_count_links", resolved,
                shape=(int(links), int(t), int(lanes)), width=width,
                chunked=chunk_rows is not None,
                activity=activity_windows is not None):
        return _entry(_bt_count_links, resolved)(
            streams,
            lengths,
            input_lanes=input_lanes,
            width=width,
            block_rows=block_rows,
            backend=resolved,
            chunk_rows=chunk_rows,
            activity_windows=activity_windows,
        )


def bt_count_variants(
    inputs: jax.Array,
    weights: jax.Array | None = None,
    variants: tuple[Variant, ...] = (Variant("acc"),),
    width: int = 8,
    input_lanes: int = 8,
    weight_lanes: int | None = None,
    pack: str = "lane",
    block_packets: int = 64,
    interpret: bool | None = None,
    backend: str | None = None,
    chunk_packets: int | None = None,
) -> jax.Array:
    """Ordered BT of (P, N) packets under MANY variants in ONE kernel launch.

    The multi-axis measurement restricted to one link and uncoded configs:
    the variant axis lives inside the single launch (one popcount pass per
    block shared by every bucketing), which is what makes a whole
    ``repro.dse`` grid one launch per measured stream.

    Returns:
      int32 (V, 2): per-variant (input-side, weight-side) bit transitions.
    """
    variants = validate_variants(tuple(variants), width)
    weights, weight_lanes = _paired(inputs, weights, weight_lanes, input_lanes)
    configs = tuple(CodecVariant(v.key, v.k, v.descending) for v in variants)
    out = bt_count_axes(
        inputs[None],
        weights[None],
        None,
        configs=configs,
        width=width,
        input_lanes=input_lanes,
        weight_lanes=weight_lanes,
        pack=pack,
        block_packets=block_packets,
        interpret=interpret,
        backend=backend,
        chunk_packets=chunk_packets,
    )
    return out[0, :, :2]


def bt_count_codecs(
    inputs: jax.Array,
    weights: jax.Array | None = None,
    configs: tuple[CodecVariant, ...] = (CodecVariant(),),
    width: int = 8,
    input_lanes: int = 8,
    weight_lanes: int | None = None,
    pack: str = "lane",
    block_packets: int = 64,
    interpret: bool | None = None,
    backend: str | None = None,
    chunk_packets: int | None = None,
    activity_windows: int | None = None,
) -> jax.Array:
    """Coded + ordered BT of (P, N) packets under MANY (ordering, codec)
    configurations in ONE kernel launch.

    The multi-axis measurement restricted to one link: the whole codec x
    ordering grid lives inside the launch (one popcount pass, one reorder
    per distinct ordering, stateful codecs as vectorized per-block prefix
    scans with the wrapper folding the O(G) inter-block carry).

    Returns:
      int32 (C, 3): per-config (input-side BT, weight-side BT, invert-line
      BT) totals.  The invert-line column is the coding overhead the wire
      still pays switching energy for (zero for codecs without extra
      lines).  With ``activity_windows`` the return type becomes
      :class:`AxesActivity` with the one-link axis dropped: bt (C, 3),
      toggles (C, NW, WIRES), ones (C, WIRES).
    """
    weights, weight_lanes = _paired(inputs, weights, weight_lanes, input_lanes)
    out = bt_count_axes(
        inputs[None],
        weights[None],
        None,
        configs=tuple(configs),
        width=width,
        input_lanes=input_lanes,
        weight_lanes=weight_lanes,
        pack=pack,
        block_packets=block_packets,
        interpret=interpret,
        backend=backend,
        chunk_packets=chunk_packets,
        activity_windows=activity_windows,
    )
    if activity_windows is None:
        return out[0]
    return AxesActivity(out.bt[0], out.toggles[0], out.ones[0])


@partial(jax.jit, static_argnames=("block", "backend"))
def _quantize_egress(
    x: jax.Array, *, block: int, backend: str
) -> tuple[jax.Array, jax.Array, jax.Array]:
    m = x.shape[0]
    pad = (-m) % block
    xp = jnp.pad(x.astype(jnp.float32), (0, pad))
    if backend == "compiled":
        q, s = quantize_egress_compiled(xp, block=block)
    else:
        q, s = quantize_egress_pallas(
            xp, block=block, interpret=backend == "interpret"
        )
    return q, s, jnp.int32(m + pad)


def quantize_egress(
    x: jax.Array,
    block: int = 256,
    interpret: bool | None = None,
    backend: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Blockwise int8 quantization of a flat vector (pads internally).

    Returns (q, scales, padded_size) where q/scales cover the padded vector;
    callers keep ``padded_size`` to dequantize and trim.
    """
    resolved = resolve_backend(backend, interpret)
    with _probe("quantize_egress", resolved, elems=int(x.shape[0]),
                block=block):
        return _entry(_quantize_egress, resolved)(
            x, block=block, backend=resolved
        )
