"""NoC-level simulation: flows in, per-link BT/energy accounting out.

The single-link story (``repro.link``) models one wire; this module models
the fabric.  Traffic is injected as ``TrafficFlow``s (packet payloads with
a source router and one or more destinations), expanded along deterministic
XY/ring routes into per-link flit streams, and measured with ONE batched
Pallas launch (``repro.kernels.bt_count_links``: links x flits x byte-lanes
on the grid) instead of one ``bt_count`` launch per link.

Where the sorting unit sits is the modeled design choice (DESIGN.md §9):

  * ``sort_at='source'`` — one PSU per injection port (the paper's §V
    proposal lifted to a NoC): packets are element-sorted once, the wire
    image is fixed at the source, and every hop of the route re-uses the
    same ordered stream.  Intermediate routers need no sorting hardware;
    the BT advantage rides along the whole path.
  * ``sort_at='hop'``   — a PSU (plus a packet-granularity transmission
    scheduler) at every router egress: each link element-sorts per packet
    *and* reorders the transmission sequence of the packets queued on that
    link by popcount bucket (the scheme of Chen et al., arXiv:2509.00500).
    Per-packet element sorting is idempotent, so the extra leverage is
    exactly at flow-merge points — packets from different flows interleave
    in bucket order instead of arrival order.

Element ordering reuses the registered ``repro.link`` stages (the KEY /
ENCODE / PACK registries and ``assemble_stream``), so a ``LinkSpec`` means
the same thing on a NoC link as on the paper's point-to-point link.  That
includes the wire-codec stage (DESIGN.md §11): a spec naming a
``repro.codec`` codec puts one encoder at every active link's egress — the
measured streams are the coded wire images and each link's invert-line
transitions ride along as ``LinkStats.bt_aux``, so fabric-level
coding-vs-ordering comparisons are net of overhead.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import _obs_hooks as _obs
from repro.codec.schemes import codec_by_name, invert_line_transitions
from repro.kernels import bt_count_links
from repro.link import ENCODE_STAGES, LinkSpec, make_order, row_bucket_order
from repro.link.framing import assemble_stream

from .fabric import FabricStreams, FlowBatch, expand_fabric, validate_flow
from .latency import FabricLatency, NocLatencyModel, fabric_latency
from .power import NocPowerModel
from .routing import compile_fabric, hop_count, multicast_links
from .topology import Topology

__all__ = [
    "TrafficFlow",
    "LinkStats",
    "LinkStreams",
    "NocReport",
    "expand_link_streams",
    "fabric_to_link_streams",
    "stack_link_streams",
    "simulate_noc",
]


@dataclasses.dataclass(frozen=True)
class TrafficFlow:
    """One traffic injection: packets from a source router to destination(s).

    ``inputs`` is (P, elems_per_packet) bytes; ``weights`` (optional) is the
    paired weight payload per the ``LinkSpec`` framing.  More than one
    destination means tree multicast along the deterministic routes.
    """

    name: str
    src: int
    dsts: tuple[int, ...]
    inputs: jax.Array
    weights: jax.Array | None = None

    def __post_init__(self) -> None:
        if not self.dsts:
            raise ValueError(f"flow {self.name!r} has no destinations")


@dataclasses.dataclass(frozen=True)
class LinkStats:
    """BT / energy accounting of one directed link's traffic."""

    link: int  # topology link id
    src: int
    dst: int
    num_flits: int
    bt_input: int
    bt_weight: int
    energy_pj: float
    bt_aux: int = 0  # invert-line transitions (wire-codec overhead)

    @property
    def total_bt(self) -> int:
        return self.bt_input + self.bt_weight

    @property
    def gross_bt(self) -> int:
        """Data BT plus the codec's invert-line transitions."""
        return self.total_bt + self.bt_aux

    @property
    def bt_per_flit(self) -> float:
        return self.total_bt / max(self.num_flits, 1)


class LinkStreams(NamedTuple):
    """Per-link wire streams, stacked for the batched BT kernel.

    ``streams`` is (L, T_max, lanes) uint8; links shorter than T_max are
    padded with copies of their last flit (BT-neutral), ``lengths`` keeps
    the real flit counts.  When the spec names a wire codec, ``streams``
    is the *coded* wire image, ``aux_bt`` carries each link's invert-line
    transitions (all zeros otherwise), and ``inverts`` keeps the raw
    (T_link, npart) invert-line states per link (``None`` when the codec
    adds no wires) — the wire-level activity path needs the actual line
    levels, not just their transition total.
    """

    link_ids: tuple[int, ...]
    streams: jax.Array
    lengths: tuple[int, ...]
    aux_bt: tuple[int, ...] = ()
    inverts: tuple = ()


@dataclasses.dataclass(frozen=True)
class NocReport:
    """Fabric-level accounting: per-link stats plus flow path info."""

    name: str
    topology: str
    sort_at: str
    key: str
    links: tuple[LinkStats, ...]
    flow_hops: tuple[tuple[str, int], ...]  # (flow name, max hops to a dst)
    total_links: int  # links in the topology (active or not)
    # wire-level activity (DESIGN.md §15) — populated only when the run was
    # measured with ``activity_windows=``.  One (num_windows, wires) toggle
    # tensor and one (wires,) time-at-1 vector per active link, wires =
    # wire_lanes*8 data bits + the codec's invert lines; consumed
    # duck-typed by ``repro.obs.activity.profiles_from_noc`` (noc never
    # imports repro.obs).
    activity_window: int = 0
    wire_lanes: int = 0
    wire_toggles: tuple = ()
    wire_ones: tuple = ()
    # contention-model results (DESIGN.md §17) — populated only when the
    # run was simulated with ``latency=``
    latency: FabricLatency | None = None

    @property
    def active_links(self) -> int:
        return len(self.links)

    @property
    def total_bt(self) -> int:
        return sum(s.total_bt for s in self.links)

    @property
    def total_aux_bt(self) -> int:
        """Fabric-wide invert-line transitions (wire-codec overhead)."""
        return sum(s.bt_aux for s in self.links)

    @property
    def gross_bt(self) -> int:
        return self.total_bt + self.total_aux_bt

    @property
    def total_flit_hops(self) -> int:
        """Flits summed over links — each hop retransmits the payload."""
        return sum(s.num_flits for s in self.links)

    @property
    def energy_pj(self) -> float:
        return sum(s.energy_pj for s in self.links)

    @property
    def max_hops(self) -> int:
        return max((h for _, h in self.flow_hops), default=0)

    def reduction_vs(self, base: "NocReport") -> float:
        """Fabric-level BT reduction relative to a baseline run (fraction,
        scored on ``gross_bt`` so coded fabrics are net of overhead)."""
        return 1.0 - self.gross_bt / max(base.gross_bt, 1e-9)


# flow validation lives with the batched path now; the legacy reference
# loop below shares it
_validate_flow = validate_flow


def _packet_perm(
    xi: jax.Array, wi: jax.Array | None, spec: LinkSpec
) -> jax.Array:
    """Per-hop transmission order of the packets queued on one link: stable
    counting sort by the popcount bucket of each packet's full wire image
    (ACC granularity = W+1 levels, APP = k)."""
    rows = xi if wi is None else jnp.concatenate([xi, wi], axis=-1)
    levels = spec.k if spec.key == "app" else spec.width + 1
    return row_bucket_order(
        rows, levels, width=spec.width, descending=spec.descending
    )


def expand_link_streams(
    topo: Topology,
    flows: Sequence[TrafficFlow],
    spec: LinkSpec = LinkSpec(),
    *,
    sort_at: str = "source",
) -> LinkStreams:
    """Expand flows into the per-link wire streams of the whole fabric.

    Element ordering (the spec's KEY stage) is applied per packet at the
    source; ``sort_at='hop'`` additionally re-orders each link's packet
    queue by popcount bucket.  All ordering/packing is plain jnp (the
    registered ``repro.link`` stages); the Pallas work of a NoC run is the
    single batched BT launch in :func:`simulate_noc`.

    Compatibility wrapper: since the fleet-scale refactor (DESIGN.md §17)
    this delegates to the batched fabric pipeline (``noc.fabric``) and
    re-expands its distinct-queue streams into the legacy per-link view —
    bit-exact vs :func:`_expand_link_streams_reference` (asserted in
    ``tests/test_fabric.py``).  New code should keep the
    :class:`~repro.noc.fabric.FabricStreams` form instead: it measures Q
    distinct queues, not L links.
    """
    plan = compile_fabric(topo, [(f.src, f.dsts) for f in flows])
    batch = FlowBatch.from_flows(flows, spec)
    fs = expand_fabric(plan, batch, spec, sort_at=sort_at)
    return fabric_to_link_streams(fs)


def fabric_to_link_streams(fs: FabricStreams) -> LinkStreams:
    """Per-link view of a fabric expansion: one gather of the distinct-queue
    streams per the plan's link->queue table.  Invert-line states stay
    device arrays, trimmed to each link's real flit count (the legacy
    ``LinkStreams.inverts`` contract); only the scalar aux counts sync to
    host here, once for the whole fabric."""
    plan = fs.plan
    if not plan.link_ids:
        lanes = int(fs.streams.shape[-1])
        return LinkStreams((), jnp.zeros((0, 1, lanes), jnp.uint8), ())
    lq = jnp.asarray(plan.link_queue, jnp.int32)
    lengths = fs.link_lengths()
    stacked = jnp.take(fs.streams, lq, axis=0)[:, : max(max(lengths), 1)]
    if fs.aux_bt is None:
        aux = (0,) * len(plan.link_ids)
        inverts: tuple = ()
    else:
        aux_q = np.asarray(fs.aux_bt).astype(int).tolist()
        aux = tuple(aux_q[qi] for qi in plan.link_queue)
        if fs.inverts is None:
            inverts = (None,) * len(plan.link_ids)
        else:
            inverts = tuple(
                fs.inverts[qi, : lengths[i]]
                for i, qi in enumerate(plan.link_queue)
            )
    return LinkStreams(plan.link_ids, stacked, lengths, aux, inverts)


def _expand_link_streams_reference(
    topo: Topology,
    flows: Sequence[TrafficFlow],
    spec: LinkSpec,
    *,
    sort_at: str,
) -> LinkStreams:
    """The original per-flow expansion loop, kept verbatim as the pinned
    bit-exactness reference for the batched fabric pipeline (DESIGN.md
    §17) — O(flows + links) traced host ops, so never use it at fleet
    scale.  ``tests/test_fabric.py`` asserts the batched path reproduces
    its streams / lengths / aux counts / invert states byte for byte on
    every test fabric."""
    encode = ENCODE_STAGES[spec.encode]
    # per-flow: encoded payloads + element order, computed ONCE at the source
    per_flow = []
    for flow in flows:
        _validate_flow(flow, spec)
        xi = encode(flow.inputs).astype(jnp.uint8)
        wi = (
            encode(flow.weights).astype(jnp.uint8)
            if flow.weights is not None
            else None
        )
        order = make_order(
            spec.key,
            xi,
            lanes=spec.input_lanes,
            width=spec.width,
            k=spec.k,
            descending=spec.descending,
        )
        links = multicast_links(topo, flow.src, flow.dsts)
        per_flow.append((xi, wi, order, links))

    # per-link: concatenate the queued segments in injection order
    segments: dict[int, list[int]] = {}
    for fi, (_, _, _, links) in enumerate(per_flow):
        for lid in links:
            segments.setdefault(lid, []).append(fi)

    link_ids = sorted(segments)
    # links with the same queued-flow composition carry byte-identical
    # streams (every link of a unicast route, every tree link of a
    # multicast) — assemble each distinct queue once
    assembled: dict[tuple[int, ...], tuple[jax.Array, int, object]] = {}
    streams: list[jax.Array] = []
    aux_bts: list[int] = []
    inverts: list = []
    for lid in link_ids:
        idxs = tuple(segments[lid])
        entry = assembled.get(idxs)
        if entry is None:
            xi = jnp.concatenate([per_flow[i][0] for i in idxs], axis=0)
            wis = [per_flow[i][1] for i in idxs]
            wi = None if wis[0] is None else jnp.concatenate(wis, axis=0)
            order = jnp.concatenate([per_flow[i][2] for i in idxs], axis=0)
            if sort_at == "hop" and len(xi) > 1:
                perm = _packet_perm(xi, wi, spec)
                xi = jnp.take(xi, perm, axis=0)
                wi = None if wi is None else jnp.take(wi, perm, axis=0)
                order = jnp.take(order, perm, axis=0)
            stream = assemble_stream(xi, wi, spec, order, spec.pack)
            aux, inv = 0, None
            if spec.codec != "none":
                # each link's egress encoder codes its own queue; the
                # batched kernel then measures the coded wire directly.
                # invert-line state stays on device — the activity path
                # trims/materializes it only when asked to
                coded = codec_by_name(spec.codec).encode(stream)
                stream = coded.wire
                aux = int(invert_line_transitions(coded.invert))
                inv = coded.invert
            entry = assembled[idxs] = (stream, aux, inv)
        streams.append(entry[0])
        aux_bts.append(entry[1])
        inverts.append(entry[2])
    stacked, lengths = stack_link_streams(streams, spec.bytes_per_flit)
    return LinkStreams(
        tuple(link_ids), stacked, lengths, tuple(aux_bts), tuple(inverts)
    )


def stack_link_streams(
    streams: Sequence[jax.Array], lanes: int
) -> tuple[jax.Array, tuple[int, ...]]:
    """Stack jagged (T_l, lanes) streams to (L, T_max, lanes) uint8.

    Shorter streams are padded with copies of their last flit and the real
    flit counts are returned alongside.  Since the unified kernel masks
    everything past each link's length (DESIGN.md §12), the padding value
    is no longer load-bearing — a repeated flit merely keeps the padded
    tensor self-consistent for callers that inspect it.
    """
    if not streams:
        return jnp.zeros((0, 1, lanes), jnp.uint8), ()
    lengths = tuple(int(s.shape[0]) for s in streams)
    t_max = max(lengths)
    padded = [
        s if s.shape[0] == t_max else jnp.pad(
            s, ((0, t_max - s.shape[0]), (0, 0)), mode="edge"
        )
        for s in streams
    ]
    return jnp.stack(padded).astype(jnp.uint8), lengths


def simulate_noc(
    topo: Topology,
    flows: Sequence[TrafficFlow],
    spec: LinkSpec = LinkSpec(),
    *,
    sort_at: str = "source",
    power: NocPowerModel | None = None,
    interpret: bool | None = None,
    backend: str | None = None,
    chunk_rows: int | None = None,
    activity_windows: int | None = None,
    latency: NocLatencyModel | None = None,
    name: str = "noc",
) -> NocReport:
    """Run the fabric: expand flows to link streams, measure every link.

    The expansion is the batched fabric pipeline (DESIGN.md §17): routing
    compiled once into a ``FabricPlan``, payloads stacked into a
    ``FlowBatch``, and every distinct link queue assembled/coded in
    vmapped stages — then ONE ``bt_count_links`` launch measures the
    whole fabric (links sharing a queue composition carry byte-identical
    streams, so each distinct queue is measured once).  Per-link energies
    roll up through ``NocPowerModel`` (wire switching + router flit
    overhead per hop).  ``backend`` selects the kernel execution path
    (pallas | compiled | interpret, DESIGN.md §13); ``chunk_rows`` streams
    the flit-row axis in fixed-size chunks for fabrics whose stacked link
    tensor would not fit in memory at once.  ``activity_windows`` (a flit
    count) additionally measures per-wire × per-time-window switching
    activity on every link (DESIGN.md §15): the report gains
    ``wire_toggles`` / ``wire_ones`` and each link fires a
    ``link.activity`` probe event.  ``latency`` (a ``NocLatencyModel``)
    additionally evaluates the hop-contention model over the plan's queue
    tables — the report gains per-link/per-flow ``FabricLatency`` rows and
    contended links fire ``noc.contend`` probe events.
    """
    power = power if power is not None else NocPowerModel()
    with _obs.span(
        "noc.simulate",
        topology=f"{topo.kind}{topo.rows}x{topo.cols}",
        sort_at=sort_at, key=spec.key, flows=len(flows), name=name,
    ):
        report = _simulate_noc(
            topo, flows, spec, sort_at=sort_at, power=power,
            interpret=interpret, backend=backend, chunk_rows=chunk_rows,
            activity_windows=activity_windows, latency=latency, name=name,
        )
    if _obs.active():
        # per-link egress telemetry (the rows behind repro.obs.report)
        for s in report.links:
            _obs.event(
                "noc.link", link=s.link, src=s.src, dst=s.dst,
                num_flits=s.num_flits, bt_input=s.bt_input,
                bt_weight=s.bt_weight, bt_aux=s.bt_aux,
                energy_pj=s.energy_pj,
            )
        for i, s in enumerate(report.links if report.activity_window else ()):
            pw = report.wire_toggles[i].sum(axis=0)
            hot = int(np.lexsort((np.arange(len(pw)), -pw))[0])
            _obs.event(
                "link.activity", link=s.link, src=s.src, dst=s.dst,
                window_flits=report.activity_window,
                num_windows=-(-s.num_flits // report.activity_window),
                data_lanes=report.wire_lanes,
                toggles_total=int(pw.sum()),
                per_wire=[int(v) for v in pw],
                hot_wire=hot, hot_wire_toggles=int(pw[hot]),
            )
    return report


def _simulate_noc(
    topo: Topology,
    flows: Sequence[TrafficFlow],
    spec: LinkSpec,
    *,
    sort_at: str,
    power: NocPowerModel,
    interpret: bool | None,
    backend: str | None,
    chunk_rows: int | None,
    activity_windows: int | None,
    latency: NocLatencyModel | None,
    name: str,
) -> NocReport:
    with _obs.span("noc.plan", flows=len(flows)):
        plan = compile_fabric(topo, [(f.src, f.dsts) for f in flows])
    with _obs.span("noc.stack", flows=len(flows)):
        batch = FlowBatch.from_flows(flows, spec)
    fs = expand_fabric(plan, batch, spec, sort_at=sort_at)
    extra_wires = 0
    if spec.codec != "none":
        extra_wires = codec_by_name(spec.codec).extra_wires(spec.bytes_per_flit)
    stats: list[LinkStats] = []
    wire_toggles: tuple = ()
    wire_ones: tuple = ()
    if plan.link_ids:
        # ONE launch over the Q distinct queues; per-link rows are table
        # lookups (dedup'd links carry byte-identical streams)
        # activity windows count from the real flits: the longest queue
        streams = fs.streams if activity_windows is None else (
            fs.streams[:, : max(max(fs.lengths), 1)])
        with _obs.span("noc.measure", queues=plan.num_queues):
            out = bt_count_links(
                streams,
                input_lanes=spec.input_lanes,
                lengths=fs.lengths,
                interpret=interpret,
                backend=backend,
                chunk_rows=chunk_rows,
                activity_windows=activity_windows,
            )
            if activity_windows is not None:
                qtog, qone = _queue_wire_activity(
                    out, fs.lengths, fs.inverts, activity_windows, extra_wires
                )
                wire_toggles = tuple(qtog[qi] for qi in plan.link_queue)
                wire_ones = tuple(qone[qi] for qi in plan.link_queue)
                bt = np.asarray(out.bt)
            else:
                bt = np.asarray(out)
            bt_rows = bt.astype(int).tolist()
            aux_q = (
                [0] * plan.num_queues
                if fs.aux_bt is None
                else np.asarray(fs.aux_bt).astype(int).tolist()
            )
        with _obs.span("noc.rollup", links=len(plan.link_ids)):
            table = topo.link_table
            for lid, qi in zip(plan.link_ids, plan.link_queue):
                length = fs.lengths[qi]
                bi, bw = bt_rows[qi]
                aux = aux_q[qi]
                u, v = int(table[lid, 0]), int(table[lid, 1])
                stats.append(
                    LinkStats(
                        link=lid,
                        src=u,
                        dst=v,
                        num_flits=length,
                        bt_input=bi,
                        bt_weight=bw,
                        # same coded-wire accounting as the point-to-point
                        # path: invert lines switch and widen this hop too
                        energy_pj=power.coded_hop_energy_pj(
                            bi + bw, aux, length,
                            8 * spec.bytes_per_flit, extra_wires,
                        ),
                        bt_aux=aux,
                    )
                )
    fabric_lat = None
    if latency is not None:
        fabric_lat = fabric_latency(
            plan,
            [c * spec.flits_per_packet for c in batch.counts],
            latency,
        )
    flow_hops = tuple(
        (f.name, max(hop_count(topo, f.src, d) for d in f.dsts)) for f in flows
    )
    return NocReport(
        name=name,
        topology=f"{topo.kind}{topo.rows}x{topo.cols}",
        sort_at=sort_at,
        key=spec.key,
        links=tuple(stats),
        flow_hops=flow_hops,
        total_links=topo.num_links,
        activity_window=activity_windows or 0,
        wire_lanes=spec.bytes_per_flit if activity_windows else 0,
        wire_toggles=wire_toggles,
        wire_ones=wire_ones,
        latency=fabric_lat,
    )


def _queue_wire_activity(
    out, lengths: tuple[int, ...], inverts, window: int, extra_wires: int
) -> tuple[list, list]:
    """Per-queue full-wire activity: the kernel's data-wire tensors widened
    with the codec invert lines' toggles/ones.  The invert recurrence was
    already paid on device in the batched expansion; the (Q, T, npart)
    line-state tensor crosses to host ONCE here and only window bucketing
    happens per queue, in numpy."""
    tog = np.asarray(out.toggles).astype(np.int64)  # (Q, NW, lanes*8)
    one = np.asarray(out.ones).astype(np.int64)  # (Q, lanes*8)
    nw = tog.shape[1]
    inv_all = None if inverts is None else np.asarray(inverts, np.int64)
    wire_toggles, wire_ones = [], []
    for i, length in enumerate(lengths):
        aux_t = np.zeros((nw, extra_wires), np.int64)
        aux_o = np.zeros(extra_wires, np.int64)
        if inv_all is not None and length >= 1:
            iv = inv_all[i, :length]
            aux_o[: iv.shape[1]] = iv.sum(axis=0)
            if length >= 2:
                flips = (iv[1:] != iv[:-1]).astype(np.int64)
                # boundary into row t lands in window t // window — the
                # same global indexing as the kernel's data wires
                widx = np.arange(1, length) // window
                np.add.at(aux_t[:, : iv.shape[1]], widx, flips)
        wire_toggles.append(np.concatenate([tog[i], aux_t], axis=1))
        wire_ones.append(np.concatenate([one[i], aux_o]))
    return wire_toggles, wire_ones
