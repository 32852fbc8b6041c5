"""Batched device-side fabric expansion (DESIGN.md §17).

The legacy expansion (`simulate._expand_link_streams_reference`) walks the
flows in a Python loop — one encode + one sort-order launch per flow, one
concatenate/assemble/codec chain per distinct link queue — O(flows + links)
traced host round-trips before the single batched BT launch.  This module
replaces that walk with three batched steps over the routing tables a
:class:`~repro.noc.routing.FabricPlan` compiled once:

  1. :class:`FlowBatch` — every flow's packets stacked back to back into
     ONE device-resident (P, elems) tensor (zero-padded to a power of two
     packets, per-flow packet counts kept statically);
  2. :func:`expand_fabric` — encode + per-packet send order applied to
     ALL flows in one call each (one stable sort of (key, position) per
     packet), flows gathered into distinct-queue rows by one (Q, P_q)
     index table, the hop-sort packet permutation applied as a
     masked batched counting sort, per-queue flit assembly vmapped over the
     registered ``repro.link`` stages, and the wire codec vmapped across
     queues (bus-invert's scan included) — invert-line state stays on
     device until the activity path consumes it;
  3. one ``bt_count_links`` launch (the §12 multi-axis core) measures every
     distinct queue; per-link numbers are a table lookup, because links
     with the same queued-flow composition carry byte-identical streams.

Bit-exactness vs the legacy loop is the subsystem contract, asserted per
trimmed stream / aux count / invert state in ``tests/test_fabric.py`` on
every existing test fabric.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import _obs_hooks as _obs
from repro.codec.schemes import codec_by_name
from repro.core.sorting import counting_sort_indices
from repro.link import ENCODE_STAGES, LinkSpec, order_packets
from repro.link.framing import assemble_stream
from repro.link.stages import row_bucket_keys

from .routing import FabricPlan

__all__ = [
    "FlowBatch",
    "FabricStreams",
    "expand_fabric",
    "validate_flow",
]


def validate_flow(flow, spec: LinkSpec) -> None:
    """Payload/framing consistency of one flow against the link spec."""
    if flow.inputs.ndim != 2 or flow.inputs.shape[-1] != spec.elems_per_packet:
        raise ValueError(
            f"flow {flow.name!r}: payload {tuple(flow.inputs.shape)} != "
            f"(P, {spec.elems_per_packet}) for this spec"
        )
    if flow.inputs.shape[0] == 0:
        raise ValueError(f"flow {flow.name!r}: zero packets")
    if spec.weight_lanes and flow.weights is None:
        raise ValueError(
            f"flow {flow.name!r}: spec has weight lanes but no weight payload"
        )
    if flow.weights is not None:
        if not spec.weight_lanes:
            raise ValueError(
                f"flow {flow.name!r}: weight payload on an input-only spec"
            )
        if flow.weights.shape != (
            flow.inputs.shape[0],
            spec.weight_elems_per_packet,
        ):
            raise ValueError(
                f"flow {flow.name!r}: weight payload "
                f"{tuple(flow.weights.shape)} != "
                f"(P, {spec.weight_elems_per_packet})"
            )


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


class FlowBatch(NamedTuple):
    """Every flow's packet payloads as one device-resident batch.

    ``inputs`` is (P', elems) uint8: the flows' packets back to back in
    flow order, the total rounded up to a power of two packets (the pad
    packets are zeros and never reach a measured wire: the queue tables
    index real packets only); ``weights`` rides along for paired
    framings.  ``counts`` keeps each flow's packet count statically; flow
    ``f`` starts at packet ``starts[f]``.
    """

    inputs: jax.Array
    weights: Optional[jax.Array]
    counts: tuple[int, ...]

    @property
    def num_flows(self) -> int:
        return len(self.counts)

    @property
    def starts(self) -> tuple[int, ...]:
        return tuple(int(s) for s in np.cumsum((0,) + self.counts[:-1]))

    @classmethod
    def from_flows(cls, flows: Sequence, spec: LinkSpec) -> "FlowBatch":
        """Validate and stack flow payloads (one host staging pass, one
        device transfer per side — not one per flow)."""
        flows = tuple(flows)
        for flow in flows:
            validate_flow(flow, spec)
        counts = tuple(int(f.inputs.shape[0]) for f in flows)
        # the total padded to a power of two: traffic whose packet count
        # varies (one MoE layer to the next) reuses the expansion's
        # compiled programs
        total = _pow2(max(sum(counts), 1))

        def stack(parts, elems):
            out = np.zeros((total, elems), np.uint8)
            if parts:
                out[: sum(counts)] = np.concatenate(
                    [np.asarray(x, np.uint8) for x in parts])
            return jnp.asarray(out)

        xs = stack([f.inputs for f in flows], spec.elems_per_packet)
        ws = None
        if spec.weight_lanes and flows:
            ws = stack([f.weights for f in flows],
                       spec.weight_elems_per_packet)
        return cls(xs, ws, counts)


class FabricStreams(NamedTuple):
    """The fabric's distinct-queue wire streams, ready for ONE BT launch.

    ``streams`` is (Q, T, bytes_per_flit) uint8 — one row per distinct
    link queue (T covers the longest queue, rounded up to a power of two
    packets), padded past each queue's real flit count with copies of its
    last flit (the same self-consistent padding the legacy stacker used;
    the kernel masks past ``lengths`` either way).  ``aux_bt`` / ``inverts``
    carry the wire codec's invert-line transition counts and raw line
    states per queue — device arrays until a consumer materializes them
    (``None`` for codecs with no extra wires).  Per-link views are
    ``plan.link_queue`` lookups.
    """

    plan: FabricPlan
    streams: jax.Array
    lengths: tuple[int, ...]
    aux_bt: Optional[jax.Array] = None
    inverts: Optional[jax.Array] = None

    @property
    def num_queues(self) -> int:
        return len(self.lengths)

    def link_lengths(self) -> tuple[int, ...]:
        """Real flit counts in per-active-link order."""
        return tuple(self.lengths[qi] for qi in self.plan.link_queue)


def _queue_gather_table(
    plan: FabricPlan, batch: FlowBatch
) -> tuple[np.ndarray, tuple[int, ...]]:
    """(Q, P_q) batch packet indices per distinct queue + real counts.

    Queue slot j of queue q maps to the batch index of the j-th packet in
    injection order (the legacy concatenation order); pad
    slots point at index 0 and are masked everywhere downstream.  P_q is
    the longest queue rounded up to a power of two, so that fabrics whose
    longest queue varies reuse the compiled expansion and measurement.
    """
    counts, starts = batch.counts, batch.starts
    qcounts = tuple(
        sum(counts[f] for f in q) for q in plan.queues
    )
    qmax = max(qcounts, default=0)
    table = np.zeros((len(plan.queues), _pow2(max(qmax, 1))), np.int64)
    for qi, q in enumerate(plan.queues):
        parts = [
            np.arange(starts[f], starts[f] + counts[f], dtype=np.int64)
            for f in q
        ]
        if parts:
            idx = np.concatenate(parts)
            table[qi, : idx.shape[0]] = idx
    return table, qcounts


def _hop_perm_masked(
    rows: jax.Array,
    qcounts: Sequence[int],
    levels: int,
    *,
    width: int,
    descending: bool,
) -> jax.Array:
    """Batched, jagged-queue version of the per-hop packet permutation.

    Real rows get the same popcount-bucket keys (and descending flip) as
    ``simulate``'s legacy ``row_bucket_order`` call; pad rows get one extra
    bucket past everything, so the stable counting sort emits the real
    packets in exactly the legacy order followed by the pads.
    """
    keys = row_bucket_keys(rows, levels, width=width)  # (Q, P)
    if descending:
        keys = (levels - 1) - keys
    p = rows.shape[1]
    mask = jnp.arange(p)[None, :] < jnp.asarray(qcounts, jnp.int32)[:, None]
    keys = jnp.where(mask, keys, levels)
    return counting_sort_indices(keys, levels + 1)


def _validate_expansion(spec: LinkSpec, sort_at: str) -> None:
    if sort_at not in ("source", "hop"):
        raise ValueError(f"sort_at must be 'source' or 'hop', got {sort_at!r}")
    if spec.key == "row_bucket":
        raise ValueError(
            "NoC flows carry packets, which use the packet-granularity key "
            "stages ('none', 'column_major', 'acc', 'app'); 'row_bucket' is "
            "a row-stream stage (TxPipeline.measure_rows)"
        )


def expand_fabric(
    plan: FabricPlan,
    batch: FlowBatch,
    spec: LinkSpec = LinkSpec(),
    *,
    sort_at: str = "source",
) -> FabricStreams:
    """Expand a whole fabric's flows into distinct-queue wire streams.

    Every step is batched over all flows / queues at once; the only Python
    iteration is the O(queues) index-table build.  Bit-exact vs the legacy
    per-flow loop by construction (same stages, same orders, same
    injection-order concatenation — asserted in ``tests/test_fabric.py``).
    """
    _validate_expansion(spec, sort_at)
    if batch.num_flows != plan.num_flows:
        raise ValueError(
            f"batch carries {batch.num_flows} flows but the plan routed "
            f"{plan.num_flows}"
        )
    with _obs.span(
        "noc.expand",
        topology=f"{plan.topo.kind}{plan.topo.rows}x{plan.topo.cols}",
        sort_at=sort_at, flows=plan.num_flows, queues=plan.num_queues,
    ):
        return _expand_fabric(plan, batch, spec, sort_at)


def _expand_fabric(
    plan: FabricPlan, batch: FlowBatch, spec: LinkSpec, sort_at: str
) -> FabricStreams:
    nq = plan.num_queues
    if nq == 0 or batch.num_flows == 0:
        return FabricStreams(
            plan, jnp.zeros((nq, 1, spec.bytes_per_flit), jnp.uint8),
            (0,) * nq,
        )
    table, qcounts = _queue_gather_table(plan, batch)
    lengths = tuple(c * spec.flits_per_packet for c in qcounts)
    streams, aux, inverts = _expand_device(
        batch.inputs, batch.weights, jnp.asarray(table),
        jnp.asarray(qcounts, jnp.int32), spec=spec, sort_at=sort_at,
    )
    return FabricStreams(plan, streams, lengths, aux, inverts)


@partial(jax.jit, static_argnames=("spec", "sort_at"))
def _expand_device(inputs, weights, gather, qcounts, *, spec: LinkSpec,
                   sort_at: str):
    """The expansion's device work as one program: (streams, aux BT or
    None, invert-line states or None) of the (Q, Pq) queue gather table,
    each queue's real packet count in ``qcounts``."""
    nq = gather.shape[0]
    encode = ENCODE_STAGES[spec.encode]
    xi = encode(inputs).astype(jnp.uint8)  # (P', E)
    wi = encode(weights).astype(jnp.uint8) if weights is not None else None
    # every packet of every flow put in send order ONCE, at the source (the
    # per-flow legacy order, as one stable sort); the input order moves the
    # weight half along only for the symmetric framing, as in
    # assemble_stream
    sym = wi is not None and wi.shape[-1] == xi.shape[-1]
    xi, ws = order_packets(
        spec.key,
        xi,
        wi if sym else None,
        lanes=spec.input_lanes,
        width=spec.width,
        k=spec.k,
        descending=spec.descending,
    )
    wi = ws if sym else wi
    qx = jnp.take(xi, gather, axis=0)
    qw = None if wi is None else jnp.take(wi, gather, axis=0)
    if sort_at == "hop":
        rows = qx if qw is None else jnp.concatenate([qx, qw], axis=-1)
        levels = spec.k if spec.key == "app" else spec.width + 1
        perm = _hop_perm_masked(
            rows, qcounts, levels,
            width=spec.width, descending=spec.descending,
        )
        qx = jnp.take_along_axis(qx, perm[..., None], axis=1)
        if qw is not None:
            qw = jnp.take_along_axis(qw, perm[..., None], axis=1)
    # per-queue flit assembly of the sent packets, vmapped over the queues;
    # kept apart from the gathers, whose fusion with the lane packing takes
    # the TPU compiler over a minute at fabric sizes
    qx, qw = jax.lax.optimization_barrier((qx, qw))
    if qw is None:
        streams = jax.vmap(
            lambda x: assemble_stream(x, None, spec, None, spec.pack)
        )(qx)
    else:
        streams = jax.vmap(
            lambda x, w: assemble_stream(x, w, spec, None, spec.pack)
        )(qx, qw)
    real = qcounts * spec.flits_per_packet  # each queue's real flit rows
    t = streams.shape[1]
    aux = inverts = None
    if spec.codec != "none":
        codec = codec_by_name(spec.codec)
        coded = jax.vmap(codec.encode)(streams)
        streams = coded.wire.astype(jnp.uint8)
        if coded.invert is not None:
            inverts = coded.invert
            inside = jnp.arange(1, t)[None, :, None] < real[:, None, None]
            flips = (inverts[:, 1:] != inverts[:, :-1]) & inside
            aux = flips.sum(axis=(1, 2)).astype(jnp.int32)
        else:
            aux = jnp.zeros((nq,), jnp.int32)
    # pad rows become copies of each queue's last real flit — the same
    # self-consistent padding the legacy stacker emitted (codec state was
    # already computed on the real region only, which comes first)
    last = jnp.take_along_axis(
        streams, jnp.maximum(real - 1, 0)[:, None, None], axis=1)
    streams = jnp.where(
        jnp.arange(t, dtype=jnp.int32)[None, :, None] < real[:, None, None],
        streams, last)
    return streams, aux, inverts
