"""Plain references for the benchmark's comparisons.

Independent of the program under test: nothing here imports ``repro``.
The functions restate, in straightforward ``jax.numpy``, what the paper's
transmit path does to a byte stream:

  * popcount sort keys: the exact '1'-bit count (ACC) or its k-bucket map
    ``bucket = ones * k // (W + 1)`` (APP, paper §III-B.2);
  * a stable sort of each packet by its keys (the counting sort's order);
  * lane packing: element j of an N-byte packet on ``lanes`` lanes rides
    lane ``j // F`` in flit ``j % F``, F = N / lanes;
  * the wire codecs: gray (``b ^ (b >> 1)`` per byte), transition signalling
    (wire_t = wire_{t-1} ^ data_t) and bus-invert (each partition of the
    flit is sent complemented iff that strictly lowers its Hamming distance
    to the previous wire flit; the first flit is never inverted);
  * bit transitions: popcount of the XOR of consecutive wire flits.

``quantize_blocks`` is the blockwise symmetric int8 quantizer:
scale = max|x| / 127 per block of 256, q = clip(round(x / scale), -127, 127).

Everything is jitted: XLA evaluates a division by a constant as a
multiplication by the constant's rounded reciprocal, on the CPU and the TPU
alike, and the comparison runs under the same compiler as the program.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

WIDTH = 8  # bits per element


class Design(NamedTuple):
    """One (ordering, codec) design point of a measured stream."""

    ordering: str = "none"  # none | acc | app
    k: int | None = None  # APP bucket count
    codec: str = "none"  # none | gray | transition | bus_invert
    partition: int | None = None  # bus-invert lanes per invert line


def ones8(x: jax.Array) -> jax.Array:
    """'1'-bit count of the low byte of each element (int32)."""
    return lax.population_count(x.astype(jnp.uint32) & 0xFF).astype(jnp.int32)


def sort_keys(packets: jax.Array, ordering: str, k: int | None) -> jax.Array:
    """(P, N) sort key of every element: its '1'-bit count or bucket."""
    keys = ones8(packets)
    if ordering == "app":
        return keys * k // (WIDTH + 1)
    if ordering != "acc":
        raise ValueError(f"unknown ordering {ordering!r}")
    return keys


def transmit_order(packets: jax.Array, ordering: str, k: int | None) -> jax.Array:
    """(P, N) order: ``order[p, j]`` is the element of packet p sent j-th."""
    p, n = packets.shape
    if ordering == "none":
        return jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (p, n))
    keys = sort_keys(packets, ordering, k)
    return jnp.argsort(keys, axis=-1, stable=True).astype(jnp.int32)


def in_send_order(packets: jax.Array, ordering: str, k: int | None,
                  *riders: jax.Array) -> tuple[jax.Array, ...]:
    """The packets, and any arrays riding with them (the paired weights),
    with each packet's elements in the order they are sent: a sort by
    (key, position), which is :func:`transmit_order` without a gather."""
    if ordering == "none":
        return (packets, *riders)
    pos = lax.broadcasted_iota(jnp.int32, packets.shape, 1)
    out = lax.sort((sort_keys(packets, ordering, k), pos, packets, *riders),
                   dimension=1, num_keys=2)
    return tuple(out[2:])


def lane_flits(packets: jax.Array, lanes: int) -> jax.Array:
    """(P, N) packets to the (lanes, P * F) wire image, F = N / lanes.

    Row l is lane l over time: element j of packet p rides lane ``j // F``
    in flit ``p * F + j % F``.  Time is the minor axis, which keeps a
    TPU's 128-wide tiles full for streams of a few lanes."""
    f = packets.shape[1] // lanes
    return jnp.stack([packets[:, l * f:(l + 1) * f].reshape(-1)
                      for l in range(lanes)])


def bus_invert_scan(data: jax.Array, partition: int | None):
    """Bus-invert as the hardware recurrence, one flit per step.

    ``data`` is a (lanes, T) image; returns (wire, invert): (lanes, T) and
    (partitions, T) int32.  Slow on long streams; kept as the statement
    that :func:`bus_invert_parity` is checked against in the tests.
    """
    lanes, t = data.shape
    pw = lanes if partition is None else partition
    npart = lanes // pw
    d = data.astype(jnp.int32).T.reshape(t, npart, pw)

    def step(prev_wire, dt):
        hd = ones8(dt ^ prev_wire).sum(axis=-1)
        inv = (2 * hd > 8 * pw).astype(jnp.int32)
        wt = dt ^ (inv[:, None] * 0xFF)
        return wt, (wt, inv)

    _, (wires, invs) = lax.scan(step, d[0], d[1:])
    wire = jnp.concatenate([d[:1], wires]).reshape(t, lanes).T
    inv = jnp.concatenate([jnp.zeros((1, npart), jnp.int32), invs]).T
    return wire, inv


def bus_invert_parity(data: jax.Array, partition: int | None):
    """:func:`bus_invert_scan`, computed for a whole stream at once.

    With h the Hamming distance of a partition between data flits t-1 and
    t, and L its bits, the previous wire flit is data_{t-1} or its
    complement, so the distance the decision sees is h or L - h.  Hence
    inv_t = not inv_{t-1} when 2h > L, inv_t = inv_{t-1} when 2h < L, and
    inv_t = 0 on a tie.  So inv_t is the parity of the flips since the
    last tie (flit 0 counts as one).
    """
    lanes, t = data.shape
    pw = lanes if partition is None else partition
    npart = lanes // pw
    d = data.astype(jnp.int32).reshape(npart, pw, t)
    h = ones8(d[..., 1:] ^ d[..., :-1]).sum(axis=1)  # (npart, T-1)
    zero = jnp.zeros((npart, 1), jnp.int32)
    flip = jnp.concatenate([zero, (2 * h > 8 * pw).astype(jnp.int32)], axis=1)
    reset = jnp.concatenate([zero + 1, (2 * h == 8 * pw).astype(jnp.int32)],
                            axis=1)
    flips = jnp.cumsum(flip, axis=1)
    # flips never falls, so its value at the last reset is its running
    # maximum over the resets so far
    base = lax.cummax(jnp.where(reset == 1, flips, 0), axis=1)
    inv = (flips - base) & 1
    wire = (d ^ (inv[:, None, :] * 0xFF)).reshape(lanes, t)
    return wire, inv


def encode(data: jax.Array, codec: str, partition: int | None):
    """(wire, invert lines or None) of a (lanes, T) image."""
    data = data.astype(jnp.int32)
    if codec == "none":
        return data, None
    if codec == "gray":
        return data ^ (data >> 1), None
    if codec == "transition":
        # each wire bit is the parity of its data bit's running count (a
        # byte-wide count keeps its parity when it wraps)
        bits = [((data >> b) & 1).astype(jnp.uint8) for b in range(WIDTH)]
        return sum(
            (jnp.cumsum(x, axis=1, dtype=jnp.uint8) & 1).astype(jnp.int32) << b
            for b, x in enumerate(bits)
        ), None
    if codec == "bus_invert":
        return bus_invert_parity(data, partition)
    raise ValueError(f"unknown codec {codec!r}")


def toggles(wire: jax.Array) -> jax.Array:
    """Total bit transitions of a (lanes, T) image (int32)."""
    return ones8(wire[:, 1:] ^ wire[:, :-1]).sum()


def wire_toggles(wire: jax.Array, invert: jax.Array | None) -> jax.Array:
    """Transitions per wire: data wires lane-major, bit 0 first, then one
    wire per invert line."""
    flips = (wire[:, 1:] ^ wire[:, :-1]).astype(jnp.int32)
    per = jnp.stack([((flips >> b) & 1).sum(axis=1) for b in range(WIDTH)],
                    axis=1).reshape(-1)
    if invert is not None:
        per = jnp.concatenate(
            [per, (invert[:, 1:] != invert[:, :-1]).sum(axis=1)])
    return per.astype(jnp.int32)


@partial(jax.jit, static_argnames=("design", "lanes"))
def design_bt(packets: jax.Array, design: Design, lanes: int):
    """(data BT, invert-line BT) of one link that sends the (P, N) packets
    in order under one design, every lane carrying the packets' bytes."""
    wire, inv = encode(lane_flits(in_send_order(packets, design.ordering, design.k)[0], lanes),
                       design.codec, design.partition)
    aux = jnp.int32(0) if inv is None else (inv[:, 1:] != inv[:, :-1]).sum()
    return jnp.stack([toggles(wire), aux.astype(jnp.int32)])


def stream_bt(packets: jax.Array, designs: tuple[Design, ...], lanes: int):
    """(D, 2): :func:`design_bt` of every design, one design at a time so
    that a whole layer's stream fits beside its temporaries."""
    return jnp.stack([design_bt(packets, d, lanes) for d in designs])


@partial(jax.jit, static_argnames=("designs", "lanes"))
def stream_wire_bt(packets: jax.Array, designs: tuple[Design, ...], lanes: int):
    """Per design, the per-wire transitions of :func:`design_bt`'s link
    (a tuple of int32 vectors; bus-invert designs add their invert lines)."""
    return tuple(
        wire_toggles(*encode(lane_flits(in_send_order(packets, d.ordering, d.k)[0], lanes), d.codec,
                             d.partition))
        for d in designs
    )


@partial(jax.jit, static_argnames=("ordering", "k", "lanes"))
def send(packets: jax.Array, ordering: str, k: int | None, lanes: int,
         weights: jax.Array | None = None):
    """(order, wire image) of a link that sends the (P, N) packets on
    ``lanes`` lanes: the :func:`transmit_order` and the (T, lanes) image,
    flit-major.  Paired ``weights`` ride in the inputs' order on as many
    lanes again, beside them."""
    riders = () if weights is None else (weights,)
    sent = in_send_order(packets, ordering, k, *riders)
    image = jnp.concatenate([lane_flits(x, lanes) for x in sent])
    return transmit_order(packets, ordering, k), image.T


@partial(jax.jit, static_argnames=("ordering", "k", "lanes"))
def paired_bt(inputs: jax.Array, weights: jax.Array, ordering: str,
              k: int | None, lanes: int):
    """(input BT, weight BT) of the paired framing: each flit carries
    ``lanes`` input bytes beside ``lanes`` weight bytes, and the input's
    sort order moves both (an input and its weight stay together)."""
    xs, ws = in_send_order(inputs, ordering, k, weights)
    return jnp.stack([toggles(lane_flits(xs, lanes)),
                      toggles(lane_flits(ws, lanes))])


@partial(jax.jit, static_argnames=("block", "dtype"))
def quantize_blocks(x: jax.Array, block: int = 256, dtype=jnp.float32):
    """Blockwise symmetric int8 codes and float32 scales of a flat vector.

    ``dtype`` is the arithmetic's precision: float32 is the reference,
    bfloat16 the benchmark's control."""
    xb = x.reshape(-1, block).astype(dtype)
    scale = jnp.max(jnp.abs(xb), axis=1) / 127
    safe = jnp.where(scale > 0, scale, 1)
    q = jnp.clip(jnp.round(xb / safe[:, None]), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale.astype(jnp.float32)
