"""`TxPipeline` — the staged transmit path, fused on its hot path.

One object owns the paper's whole dataflow (popcount -> bucket ->
counting-sort -> reorder -> pack -> measure), configured by a single
``LinkSpec``.  Two execution paths produce bit-identical results:

  * **fused** (default when applicable): one Pallas launch per packet block
    (``repro.kernels.psu_stream``) runs sort + reorder + flit-pack +
    BT-accumulate without the stream ever leaving VMEM.  Applicable for
    'acc'/'app' keys with 'row'/'lane' packing and a symmetric (or absent)
    weight side.
  * **staged** (fallback + reference): the registered stages composed with
    the ``repro.core.sorting`` counting sort and the ``bt_count`` kernel —
    a sort launch, a host gather, and one BT launch per lane half.  Used by
    the data-independent strategies ('none', 'column_major'), the 'col'
    stream layout, asymmetric framings, and row streams.

Row streams (weight matrices traversed row-wise — the TPU traffic
adaptation, DESIGN.md §3.3) go through ``measure_rows``/``transmit_rows``
with the 'row_bucket' key stage.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro import _obs_hooks as _obs
from repro.core.bt import BTReport
from repro.kernels import bt_count, psu_stream

from .framing import _validate_paired, assemble_stream
from .power import LinkPowerModel
from .spec import LinkSpec
from .stages import ENCODE_STAGES, PACK_STAGES, make_order, row_bucket_order

__all__ = ["TxPipeline", "TxResult", "LinkReport"]


@dataclasses.dataclass(frozen=True)
class TxResult:
    """What one transmit produces: the permutation, the wire image, the BT."""

    order: jax.Array  # (P, N) int32 (or (R,) for row streams)
    rank: Optional[jax.Array]  # (P, N) int32; None on the staged path
    stream: jax.Array  # (T, lanes) uint8 wire rows (codec-coded if any)
    bt_input: jax.Array  # int32: input-side bit transitions
    bt_weight: jax.Array  # int32: weight-side bit transitions
    fused: bool  # produced by the single-launch kernel?
    invert: Optional[jax.Array] = None  # (T, P) uint8 bus-invert lines
    bt_aux: jax.Array | int = 0  # int32: invert-line transitions


@dataclasses.dataclass(frozen=True)
class LinkReport:
    """BT / energy accounting of one measured stream (Table-I columns +
    the Fig. 6/7 energy model)."""

    name: str
    num_flits: int
    input_bt: int
    weight_bt: int
    fused: bool = False
    energy_pj: float = 0.0
    aux_bt: int = 0  # invert-line transitions (codec overhead)
    extra_wires: int = 0  # invert lines added beside the data lanes

    @property
    def total_bt(self) -> int:
        return self.input_bt + self.weight_bt

    @property
    def gross_bt(self) -> int:
        """Data BT plus the codec's own invert-line transitions — the
        number every codec comparison is scored on (net of overhead)."""
        return self.total_bt + self.aux_bt

    @property
    def input_bt_per_flit(self) -> float:
        return self.input_bt / max(self.num_flits, 1)

    @property
    def weight_bt_per_flit(self) -> float:
        return self.weight_bt / max(self.num_flits, 1)

    @property
    def overall_bt_per_flit(self) -> float:
        return self.total_bt / max(self.num_flits, 1)

    def reduction_vs(self, base: "LinkReport") -> float:
        """Overall BT reduction relative to a baseline report (fraction).

        Scored on ``gross_bt``, so coded streams are credited net of their
        invert-line overhead (identical to the data-only ratio when neither
        report carries a codec)."""
        return 1.0 - self.gross_bt / max(base.gross_bt, 1e-9)

    def to_bt_report(self) -> BTReport:
        """Legacy ``repro.core.bt.BTReport`` view (Table-I columns)."""
        return BTReport(
            jnp.float32(self.input_bt_per_flit),
            jnp.float32(self.weight_bt_per_flit),
            jnp.float32(self.overall_bt_per_flit),
        )


class TxPipeline:
    """Staged TX pipeline over one link, configured by a ``LinkSpec``.

    Args:
      spec: framing + stage selection.
      power: energy model for ``LinkReport.energy_pj`` (default paper model).
      fused: force (True) or forbid (False) the fused kernel; None = use it
        whenever the spec allows.
      interpret: legacy backend override (True = the Pallas interpreter,
        False = the compiled kernel, None = the platform default backend).
      backend: kernel backend override ('pallas' | 'compiled' |
        'interpret', DESIGN.md §13); wins over ``interpret``.
      block_packets: packets per fused-kernel grid step.
    """

    def __init__(
        self,
        spec: LinkSpec = LinkSpec(),
        *,
        power: LinkPowerModel | None = None,
        fused: bool | None = None,
        interpret: bool | None = None,
        backend: str | None = None,
        block_packets: int = 64,
    ) -> None:
        self.spec = spec
        self.power = power if power is not None else LinkPowerModel()
        self._fused = fused
        self._interpret = interpret
        self._backend = backend
        self._block_packets = block_packets

    # ---------------------------------------------------------------- stages
    def encode(self, values: jax.Array) -> jax.Array:
        """The wire byte image of ``values`` under the encode stage."""
        return ENCODE_STAGES[self.spec.encode](values)

    def order(self, inputs: jax.Array) -> jax.Array:
        """Per-packet transmit permutation (derived from encoded inputs)."""
        s = self.spec
        return make_order(
            s.key,
            self.encode(inputs),
            lanes=s.input_lanes,
            width=s.width,
            k=s.k,
            descending=s.descending,
        )

    def _fusable(self, weights: jax.Array | None) -> bool:
        s = self.spec
        # a wire codec recodes the assembled stream AFTER packing, so its
        # BT cannot come out of the fused sort+pack+measure kernel; coded
        # specs take the staged path (the single-launch multi-codec hot
        # path is repro.kernels.bt_count_codecs)
        return (
            s.key in ("acc", "app")
            and s.pack in ("lane", "row")
            and s.codec == "none"
            and (weights is None or s.symmetric)
        )

    def _code_wire(
        self, stream: jax.Array
    ) -> tuple[jax.Array, Optional[jax.Array], jax.Array]:
        """Apply the spec's wire codec: (wire, invert lines, aux BT)."""
        # deferred import: repro.codec registers into repro.link on import
        from repro.codec.schemes import codec_by_name, invert_line_transitions

        coded = codec_by_name(self.spec.codec).encode(stream)
        return coded.wire, coded.invert, invert_line_transitions(coded.invert)

    # ------------------------------------------------------------- packet TX
    def run(
        self, inputs: jax.Array, weights: jax.Array | None = None
    ) -> TxResult:
        """Transmit P packets: returns permutation, wire stream and BT.

        ``inputs`` is (P, elems_per_packet); ``weights`` (optional) is
        (P, elems_per_packet) for the symmetric paired framing or
        (P, weight_elems_per_packet) for asymmetric links (framed unordered,
        see DESIGN.md §1).
        """
        s = self.spec
        if weights is not None:
            _validate_paired(inputs, weights, s)
        elif inputs.shape[-1] != s.elems_per_packet:
            raise ValueError(
                f"packet payload {inputs.shape[-1]} != "
                f"flits*input_lanes = {s.elems_per_packet}"
            )
        fused = self._fused if self._fused is not None else self._fusable(weights)
        if fused and not self._fusable(weights):
            raise ValueError(
                f"spec (key={s.key!r}, pack={s.pack!r}, codec={s.codec!r}, "
                f"symmetric={s.symmetric}) cannot run fused"
            )
        with _obs.span(
            "link.tx", path="fused" if fused else "staged", key=s.key,
            codec=s.codec, packets=int(inputs.shape[0]),
        ):
            with _obs.span("link.stage", stage="encode"):
                xi = self.encode(inputs)
                wi = self.encode(weights) if weights is not None else None
            if fused:
                res = psu_stream(
                    xi,
                    wi,
                    width=s.width,
                    k=None if s.key == "acc" else s.k,
                    descending=s.descending,
                    input_lanes=s.input_lanes,
                    weight_lanes=s.weight_lanes if wi is not None else None,
                    pack=s.pack,
                    block_packets=self._block_packets,
                    interpret=self._interpret,
                    backend=self._backend,
                )
                return TxResult(
                    res.order, res.rank, res.stream, res.bt_input,
                    res.bt_weight, True,
                )
            with _obs.span("link.stage", stage="order"):
                order = make_order(
                    s.key, xi, lanes=s.input_lanes, width=s.width, k=s.k,
                    descending=s.descending,
                )
            with _obs.span("link.stage", stage="assemble"):
                stream = assemble_stream(xi, wi, s, order, s.pack)
            invert, bt_aux = None, jnp.int32(0)
            if s.codec != "none":
                with _obs.span("link.stage", stage="codec"):
                    stream, invert, bt_aux = self._code_wire(stream)
            with _obs.span("link.stage", stage="bt"):
                bt_i = bt_count(
                    stream[:, : s.input_lanes], interpret=self._interpret,
                    backend=self._backend,
                )
                if wi is not None and s.weight_lanes:
                    bt_w = bt_count(
                        stream[:, s.input_lanes :], interpret=self._interpret,
                        backend=self._backend,
                    )
                else:
                    bt_w = jnp.int32(0)
            return TxResult(
                order, None, stream, bt_i, bt_w, False, invert, bt_aux
            )

    def transmit(
        self, inputs: jax.Array, weights: jax.Array | None = None
    ) -> jax.Array:
        """The (T, lanes) uint8 wire image of the packets."""
        return self.run(inputs, weights).stream

    def measure(
        self,
        inputs: jax.Array,
        weights: jax.Array | None = None,
        name: str = "stream",
    ) -> LinkReport:
        """BT / energy report for transmitting the packets under this spec.

        Coded specs report their invert-line transitions and added wires,
        and the energy model charges both (``coded_link_energy_pj``) — the
        BT win is net of the codec's own overhead."""
        res = self.run(inputs, weights)
        num_flits, lanes = (int(d) for d in res.stream.shape)
        with _obs.span("link.readback"):
            bt_i, bt_w = int(res.bt_input), int(res.bt_weight)
            aux = int(res.bt_aux)
        wires = self._extra_wires(lanes)
        energy = self.power.coded_link_energy_pj(
            bt_i + bt_w, aux, num_flits, 8 * lanes, wires
        )
        _obs.event(
            "link.report", name=name, bt_input=bt_i, bt_weight=bt_w,
            aux_bt=aux, num_flits=num_flits, energy_pj=energy,
        )
        return LinkReport(
            name,
            num_flits,
            bt_i,
            bt_w,
            fused=res.fused,
            energy_pj=energy,
            aux_bt=aux,
            extra_wires=wires,
        )

    def _extra_wires(self, lanes: int) -> int:
        """Invert lines the spec's codec adds beside ``lanes`` byte lanes.

        ``lanes`` is the ACTUAL width of the assembled stream — an
        input-only run of a paired spec codes only the input half, so the
        codec framing (and the wire/energy accounting) must follow the
        stream, not ``bytes_per_flit``."""
        if self.spec.codec == "none":
            return 0
        from repro.codec.schemes import codec_by_name

        return codec_by_name(self.spec.codec).extra_wires(lanes)

    # --------------------------------------------------------------- row TX
    def row_order(self, rows: jax.Array) -> jax.Array:
        """Transmit order of whole rows of an (R, B) byte matrix under this
        spec's key stage ('none' or 'row_bucket', DESIGN.md §3.3)."""
        s = self.spec
        if s.key == "none":
            return jnp.arange(rows.shape[0], dtype=jnp.int32)
        if s.key != "row_bucket":
            raise ValueError(
                f"row streams use key 'none' or 'row_bucket', got {s.key!r}"
            )
        return row_bucket_order(rows, s.k, width=s.width, descending=s.descending)

    def _row_wire(self, rows: jax.Array) -> tuple[jax.Array, jax.Array]:
        """(wire stream, aux BT) of an (R, B) byte-row stream."""
        enc = self.encode(rows)
        ordered = jnp.take(enc, self.row_order(enc), axis=0)
        stream = PACK_STAGES[self.spec.pack].stream(
            ordered, self.spec.bytes_per_flit
        ).astype(jnp.uint8)
        if self.spec.codec == "none":
            return stream, jnp.int32(0)
        wire, _, bt_aux = self._code_wire(stream)
        return wire, bt_aux

    def transmit_rows(self, rows: jax.Array) -> jax.Array:
        """Wire image of an (R, B) byte-row stream (weight matrix traffic,
        DESIGN.md §3.3): encode, order whole rows by popcount bucket, lay
        out with the pack stage ('row' = HBM-natural, 'col' = interleaved),
        then apply the wire codec (if any)."""
        return self._row_wire(rows)[0]

    def measure_rows(self, rows: jax.Array, name: str = "rows") -> LinkReport:
        """BT / energy report for streaming ``rows`` under this spec."""
        stream, bt_aux = self._row_wire(rows)
        bt = bt_count(
            stream, interpret=self._interpret, backend=self._backend
        )
        with _obs.span("link.readback"):
            aux, bt = int(bt_aux), int(bt)
        num_flits, lanes = (int(d) for d in stream.shape)
        wires = self._extra_wires(lanes)
        energy = self.power.coded_link_energy_pj(
            bt, aux, num_flits, 8 * lanes, wires
        )
        _obs.event(
            "link.report", name=name, bt_input=bt, bt_weight=0,
            aux_bt=aux, num_flits=num_flits, energy_pj=energy,
        )
        return LinkReport(
            name,
            num_flits,
            bt,
            0,
            fused=False,
            energy_pj=energy,
            aux_bt=aux,
            extra_wires=wires,
        )
