"""Traffic adapters: real workload tensors -> NoC flows.

Workload families from the existing layers, mapped onto fabric
traffic patterns (DESIGN.md §9):

  * **Conv platform** (paper §IV-B, ``benchmarks/lenet_workload.py``): the
    allocation unit scatters im2col patch packets from a memory router to
    the PE routers (unicast each), with the convolution kernel bytes riding
    the paired weight lanes — the paper's 16-PE platform laid out on a
    mesh.
  * **Decode weight streams** (``repro.serve`` / ``repro.traffic``): a
    weight matrix's int8 HBM image is one long byte stream multicast from
    the memory-controller router to a row of PEs — the weight-broadcast
    traffic that dominates decode.
  * **Gradient all-reduce** (``repro.optim``): the int8 gradient wire image
    sharded over the routers of a ring schedule, each shard hopping to the
    next router — one step of a ring reduce-scatter, the ICI collective
    pattern of DESIGN.md §5 on the modeled fabric.
  * **Expert-parallel dispatch** (``repro.models.moe``): one MoE layer's
    all-to-all — every chip sends its tokens' rows to the chips holding
    their experts, one flow per (source chip, expert).
  * **Fleet decode** (DESIGN.md §17, the ``fleet_noc`` benchmark):
    multi-tenant decode weight broadcast — users x layers x shards
    multicast flows on a large grid, tenants pinned to rows, shards to
    column groups; the merge-heavy traffic the contention model prices.

Adapters only build ``TrafficFlow``s; ordering/packing/measuring stay in
:mod:`repro.noc.simulate`.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import _obs_hooks as _obs
from repro.link import LinkSpec, tensor_flit_stream
from repro.traffic.ordering import int8_view

from .simulate import TrafficFlow
from .topology import Topology

__all__ = [
    "packetize",
    "conv_platform_flows",
    "decode_weight_flows",
    "fleet_decode_flows",
    "ring_allreduce_flows",
    "moe_dispatch_flows",
]


def _wire_bytes(x: jax.Array) -> jax.Array:
    """A tensor's int8 wire image as flat uint8 bytes.

    int8/uint8 inputs ARE already their wire image (e.g. captured streams
    from ``repro.obs.capture``) and pass through untouched — re-quantizing
    them would rescale the bytes and distort the measured distribution.
    """
    if x.dtype in (jnp.dtype(jnp.uint8), jnp.dtype(jnp.int8)):
        return jnp.ravel(x).astype(jnp.uint8)
    return jnp.ravel(int8_view(x)).astype(jnp.uint8)


def packetize(data: jax.Array, elems: int) -> jax.Array:
    """Flatten a byte tensor and shape it into (P, elems) packets (trimmed
    to whole packets; a NoC flow transmits complete packets only)."""
    pkts = tensor_flit_stream(jnp.ravel(data).astype(jnp.uint8), elems)
    if pkts.shape[0] == 0:
        raise ValueError(
            f"payload of {data.size} bytes is smaller than one "
            f"{elems}-byte packet"
        )
    return pkts


def conv_platform_flows(
    patches: jax.Array,
    kernel_bytes: jax.Array,
    topo: Topology,
    src: int,
    pe_routers: Sequence[int],
    spec: LinkSpec = LinkSpec(),
) -> list[TrafficFlow]:
    """Scatter conv input packets to PE routers, kernels on the weight lanes.

    ``patches`` is the (num_patches, window) im2col byte matrix of one
    image; ``kernel_bytes`` one output channel's flattened kernel.  Packets
    are dealt round-robin over ``pe_routers``; when the spec has weight
    lanes each packet pairs with the cyclically-tiled kernel bytes (the
    repeated-kernel stream of ``benchmarks/lenet_workload.py``).
    """
    topo.coords(src)  # validates the router id
    pkts = packetize(patches, spec.elems_per_packet)
    p = pkts.shape[0]
    if spec.weight_lanes:
        wrep = jnp.tile(
            jnp.ravel(kernel_bytes).astype(jnp.uint8),
            (p * spec.weight_elems_per_packet) // kernel_bytes.size + 1,
        )[: p * spec.weight_elems_per_packet].reshape(p, -1)
    flows = []
    for i, pe in enumerate(pe_routers):
        sel = jnp.arange(i, p, len(pe_routers))
        if sel.shape[0] == 0:
            continue
        flows.append(
            TrafficFlow(
                name=f"conv/pe{pe}",
                src=src,
                dsts=(pe,),
                inputs=jnp.take(pkts, sel, axis=0),
                weights=(
                    jnp.take(wrep, sel, axis=0) if spec.weight_lanes else None
                ),
            )
        )
    return flows


def decode_weight_flows(
    weight: jax.Array,
    topo: Topology,
    src: int,
    dsts: Sequence[int],
    spec: LinkSpec = LinkSpec(),
    max_packets: int | None = None,
) -> list[TrafficFlow]:
    """Multicast a weight matrix's int8 HBM stream to a set of PE routers.

    The matrix is quantized to its int8 wire image (``repro.traffic``), the
    row-major byte stream is packetized, and ONE multicast flow carries it
    down the XY tree — each tree link transmits a single copy, which is the
    bandwidth argument for weight broadcast.  Input-only specs model the
    dedicated weight-distribution channel.
    """
    if spec.weight_lanes:
        raise ValueError(
            "decode weight streams are a one-sided broadcast; use an "
            "input-only spec (weight_lanes=0)"
        )
    topo.coords(src)  # validates the router id
    pkts = packetize(_wire_bytes(weight), spec.elems_per_packet)
    if max_packets is not None:
        pkts = pkts[:max_packets]
    return [
        TrafficFlow(
            name="decode/weights",
            src=src,
            dsts=tuple(dsts),
            inputs=pkts,
        )
    ]


def fleet_decode_flows(
    weights: jax.Array,
    topo: Topology,
    *,
    users: int,
    layers: int,
    shards: int,
    spec: LinkSpec = LinkSpec(input_lanes=16, weight_lanes=0),
    packets_per_flow: int = 2,
) -> list[TrafficFlow]:
    """Multi-tenant decode weight traffic: users x layers x shards flows.

    The fleet-serving pattern behind the ROADMAP's 16x16 north star (and
    the ``fleet_noc`` benchmark): tenant ``u`` is pinned to grid row
    ``u % rows`` — its memory-controller router sits at column 0 and its
    PEs are the remaining routers of the row.  For every decode layer
    ``l``, weight shard ``s`` multicasts from the tenant's memory router
    to the ``s``-th contiguous group of the row's PE columns (the
    tensor-parallel shard placement), so flows of co-located tenants and
    of every layer merge on the row's column-0 egress links — exactly the
    merge-point contention ``noc.latency`` prices.

    Payloads are deterministic strided slices of ``weights``'s int8 wire
    image (tiled if the tensor is smaller than one flow), so every flow
    carries distinct but reproducible bytes.  They are host arrays: one
    gather on the device cuts every slice, and one transfer brings them
    over for ``FlowBatch`` to stack.
    """
    if spec.weight_lanes:
        raise ValueError(
            "fleet decode traffic is a one-sided broadcast; use an "
            "input-only spec (weight_lanes=0)"
        )
    if users < 1 or layers < 1 or shards < 1:
        raise ValueError(
            f"need users/layers/shards >= 1, got {users}/{layers}/{shards}"
        )
    if packets_per_flow < 1:
        raise ValueError(f"packets_per_flow must be >= 1, got {packets_per_flow}")
    pe_cols = topo.cols - 1
    if pe_cols < shards:
        raise ValueError(
            f"{shards} shards need {shards} PE columns; a {topo.rows}x"
            f"{topo.cols} grid has {pe_cols} (column 0 is the memory router)"
        )
    need = packets_per_flow * spec.elems_per_packet
    n = users * layers * shards
    with _obs.span("fleet.route", users=users, layers=layers, shards=shards,
                   flows=n, bytes=n * need):
        data = _wire_bytes(weights)
        if int(data.size) < need:
            data = jnp.tile(data, -(-need // int(data.size)))
        span = int(data.size) - need  # highest valid slice start
        # flow fi = (u * layers + layer) * shards + s starts at a coprime
        # stride, which walks the wire image without re-slicing the same
        # window for co-located tenants
        offs = np.arange(n, dtype=np.int64) * 7919 % (span + 1)
        index = (offs[:, None] + np.arange(need)).astype(np.int32)
        staged = np.asarray(jnp.take(data, jnp.asarray(index)))
    flows = []
    for u in range(users):
        row = u % topo.rows
        mem = topo.router(row, 0)
        for layer in range(layers):
            for s in range(shards):
                lo = s * pe_cols // shards
                hi = (s + 1) * pe_cols // shards
                dsts = tuple(
                    topo.router(row, 1 + c) for c in range(lo, hi)
                )
                fi = (u * layers + layer) * shards + s
                flows.append(
                    TrafficFlow(
                        name=f"u{u}/l{layer}/s{s}",
                        src=mem,
                        dsts=dsts,
                        inputs=staged[fi].reshape(
                            packets_per_flow, spec.elems_per_packet
                        ),
                    )
                )
    return flows


def ring_allreduce_flows(
    grad: jax.Array,
    topo: Topology,
    routers: Sequence[int] | None = None,
    spec: LinkSpec = LinkSpec(),
) -> list[TrafficFlow]:
    """One ring reduce-scatter step of a gradient's int8 wire image.

    The flat gradient quantizes to int8 (the ``repro.optim`` compressed
    wire format), shards evenly over ``routers`` (default: every router, in
    id order — on a ring topology that is the physical cycle), and shard i
    flows from router i to its cyclic successor.  Repeating with rotated
    shards would model the remaining R-1 steps; one step already exercises
    every inter-router hop with distinct payloads.
    """
    order = tuple(routers) if routers is not None else tuple(
        range(topo.num_routers)
    )
    if len(order) < 2:
        raise ValueError("ring all-reduce needs >= 2 routers")
    if spec.weight_lanes:
        raise ValueError("gradient traffic is one-sided; use weight_lanes=0")
    pkts = packetize(_wire_bytes(grad), spec.elems_per_packet)
    shard = max(pkts.shape[0] // len(order), 1)
    flows = []
    for i, r in enumerate(order):
        lo = min(i * shard, pkts.shape[0])
        hi = pkts.shape[0] if i == len(order) - 1 else min(lo + shard, pkts.shape[0])
        if hi <= lo:
            continue
        flows.append(
            TrafficFlow(
                name=f"allreduce/shard{i}",
                src=r,
                dsts=(order[(i + 1) % len(order)],),
                inputs=pkts[lo:hi],
            )
        )
    return flows


@partial(jax.jit, static_argnames=("per_chip", "experts"))
def _dispatch_rows(wire, expert_ids, *, per_chip: int, experts: int):
    """The (token, choice) rows grouped by (source chip, expert), token
    order inside each group, and each group's row count."""
    t, k = expert_ids.shape
    src = jnp.arange(t, dtype=jnp.int32) // per_chip
    key = (src[:, None] * experts + expert_ids.astype(jnp.int32)).reshape(-1)
    order = jnp.argsort(key, stable=True)
    counts = jnp.bincount(key, length=(t // per_chip) * experts)
    return jnp.take(wire, order // k, axis=0), counts


def moe_dispatch_flows(
    rows: jax.Array,
    expert_ids: jax.Array,
    topo: Topology,
    *,
    num_experts: int,
    chips: Sequence[int] | None = None,
    spec: LinkSpec = LinkSpec(input_lanes=16, weight_lanes=0),
) -> list[TrafficFlow]:
    """Expert-parallel dispatch: the all-to-all of one MoE layer.

    ``rows`` is the layer's (T, d) token rows (their int8 wire image, or a
    float tensor quantized by ``int8_view``), ``expert_ids`` the (T, k)
    experts each token is routed to (``repro.obs.MoeRoute``).  ``chips``
    are the routers of the expert-parallel group in chip order (default:
    every router); the tokens are split evenly over them in order (each
    chip's data-parallel share) and chip ``c`` holds experts
    ``c*E/C ... (c+1)*E/C - 1``.

    One unicast flow per (source chip, expert) carries that chip's tokens
    routed to the expert, in token order, whole tokens of ``d / elems``
    packets each; flows are injected source-major, expert-minor.  A flow
    whose expert lives on its source chip never enters the fabric and is
    left out (the ``moe.load`` event counts them).  Tokens inside a flow
    are an unordered set, the permutation freedom the paper's sorting
    unit exploits (``sort_at`` in ``simulate_noc``).

    The payloads are host arrays: one gather on the device groups every
    row, and one transfer brings them over for ``FlowBatch`` to stack.
    """
    if spec.weight_lanes:
        raise ValueError("dispatch traffic is one-sided; use weight_lanes=0")
    if rows.ndim != 2 or expert_ids.ndim != 2 or (
            rows.shape[0] != expert_ids.shape[0]):
        raise ValueError(
            f"need (tokens, d_model) rows and (tokens, top_k) expert ids, "
            f"got {tuple(rows.shape)} and {tuple(expert_ids.shape)}"
        )
    t, k = (int(n) for n in expert_ids.shape)
    elems = spec.elems_per_packet
    width = int(rows.shape[1])
    if width % elems:
        raise ValueError(
            f"a {width}-byte token row is not whole {elems}-byte packets"
        )
    routers = tuple(range(topo.num_routers)) if chips is None else tuple(chips)
    for r in routers:
        topo.coords(r)  # validates the router id
    c = len(routers)
    if not c or t % c or num_experts % c:
        raise ValueError(
            f"{t} tokens and {num_experts} experts must split evenly over "
            f"{c} chips"
        )
    per_chip, held = t // c, num_experts // c
    with _obs.span("moe.route", chips=c, experts=num_experts, tokens=t,
                   top_k=k):
        wire = _wire_bytes(rows).reshape(t, width)
        grouped, counts = _dispatch_rows(
            wire, jnp.asarray(expert_ids), per_chip=per_chip,
            experts=num_experts)
        grouped = np.asarray(grouped)
        counts = np.asarray(counts).reshape(c, num_experts)
    flows, start, local = [], 0, 0
    for src in range(c):
        for e in range(num_experts):
            n = int(counts[src, e])
            seg = grouped[start:start + n]
            start += n
            if not n:
                continue
            if e // held == src:
                local += 1
                continue
            flows.append(
                TrafficFlow(
                    name=f"moe/chip{src}/expert{e}",
                    src=routers[src],
                    dsts=(routers[e // held],),
                    inputs=seg.reshape(n * width // elems, elems),
                )
            )
    per_expert = counts.sum(axis=0)
    _obs.event(
        "moe.load", chips=c, experts=num_experts,
        tokens_per_expert_max=int(per_expert.max()),
        tokens_per_expert_mean=float(per_expert.mean()),
        flows=len(flows), local_flows=local,
        fabric_bytes=sum(int(f.inputs.size) for f in flows),
    )
    return flows
