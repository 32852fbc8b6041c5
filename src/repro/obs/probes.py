"""The probe layer: routes hook firings into registries, tracers and the
JAX profiler (DESIGN.md §14).

Production modules call ``repro._obs_hooks.span/event`` at fixed probe
points; while at least one :func:`collect`, :func:`tracing` or
:func:`profiling` context is active this module's sink is installed into
the hook slot and every firing fans out to all active consumers.  The
probe vocabulary:

  =================  =====  ==============================================
  kind               form   fired by
  =================  =====  ==============================================
  kernel.dispatch    span   every public kernel entry point in
                            ``repro.kernels.ops`` (resolved backend,
                            shapes, grid blocks, pallas launches)
  link.tx            span   ``link.TxPipeline.run`` (fused or staged)
  link.stage         span   each stage inside ``TxPipeline.run``: encode
                            (both paths), order/assemble/codec/bt (staged
                            path)
  link.readback      span   the host reads that end ``TxPipeline.measure``
                            / ``measure_rows`` (each waits on the device)
  link.report        event  ``TxPipeline.measure``/``measure_rows`` —
                            per-stream BT/energy totals
  noc.expand         span   ``noc.expand_link_streams``
  noc.simulate       span   ``noc.simulate_noc``
  noc.link           event  one per measured NoC link (the per-link BT
                            telemetry behind ``repro.obs.report``)
  noc.contend        event  one per contended link (>= 2 merged flows) of
                            a ``noc.latency`` contention-model evaluation
  link.activity      event  one per link measured with wire-level
                            activity (``activity_windows=``) — per-wire
                            toggle telemetry (DESIGN.md §15)
  dse.measure        span   each per-width multi-axis launch in
                            ``dse.evaluate_grid``
  dse.readback       span   each host read of a launch's results in
                            ``dse.evaluate_grid`` (BT table, toggles)
  dse.link           event  one per measurement link of a DSE grid launch
  dse.point          event  one per evaluated design point
  codec.stream       event  per-stream totals in ``codec.compare_streams``
  capture.stream     event  one per stream recorded by a traffic-capture
                            session (``repro.obs.capture``) — bytes per
                            scenario/stream
  bench.module       span   ``benchmarks/run.py --trace`` around each
                            module run
  =================  =====  ==============================================

Span firings become Chrome trace spans on every active tracer, a
``<kind>.calls`` counter (labeled by the kind's identity keys) on every
active registry, and, under :func:`profiling`, a
``jax.profiler.TraceAnnotation`` named by the kind, on the profiler's clock
beside the device's events.  Event firings become instant trace events
plus the per-kind counters below; the profiler consumer ignores them.
Unknown kinds still count (``<kind>.calls``) so new probe points degrade
gracefully.
"""

from __future__ import annotations

from contextlib import contextmanager

from jax.profiler import TraceAnnotation

from repro import _obs_hooks

from .activity import wire_name
from .metrics import Registry
from .trace import Tracer

__all__ = [
    "PROBE_KINDS",
    "collect",
    "tracing",
    "profiling",
    "active_registries",
    "active_tracers",
]

# the canonical probe vocabulary — kind -> form.  This dict IS the source
# of truth the DESIGN.md §14 table must mirror (a guard test parses the
# table and fails on drift), so adding a probe point means updating both.
PROBE_KINDS: dict[str, str] = {
    "kernel.dispatch": "span",
    "link.tx": "span",
    "link.stage": "span",
    "link.readback": "span",
    "link.report": "event",
    "link.activity": "event",
    "noc.expand": "span",
    "noc.simulate": "span",
    "noc.link": "event",
    "noc.contend": "event",
    "dse.measure": "span",
    "dse.readback": "span",
    "dse.link": "event",
    "dse.point": "event",
    "codec.stream": "event",
    "capture.stream": "event",
    "bench.module": "span",
}

# label keys lifted from span payloads into metric series identity and
# profiler annotation arguments — everything else stays trace-only
# (unbounded-cardinality values like shapes must never become label sets)
_SPAN_LABELS: dict[str, tuple[str, ...]] = {
    "kernel.dispatch": ("entry", "backend"),
    "link.tx": ("path", "key", "codec"),
    "link.stage": ("stage",),
    "noc.expand": ("topology", "sort_at"),
    "noc.simulate": ("topology", "sort_at"),
    "dse.measure": ("width",),
    "dse.readback": ("width",),
    "bench.module": ("module",),
}


def _labels(kind: str, data: dict) -> dict:
    keys = _SPAN_LABELS.get(kind, ())
    return {k: data[k] for k in keys if k in data}


def _record_span(reg: Registry, kind: str, data: dict) -> None:
    labels = _labels(kind, data)
    reg.counter(f"{kind}.calls", **labels).inc()
    if kind == "kernel.dispatch":
        reg.counter(
            "kernel.pallas_launches", **_labels(kind, data)
        ).inc(data.get("pallas_launches", 0))


def _record_event(reg: Registry, kind: str, data: dict) -> None:
    if kind == "noc.link":
        lab = {
            "link": data["link"], "src": data["src"], "dst": data["dst"],
        }
        reg.counter("noc.link.bt", side="input", **lab).inc(data["bt_input"])
        reg.counter("noc.link.bt", side="weight", **lab).inc(data["bt_weight"])
        reg.counter("noc.link.bt", side="aux", **lab).inc(data["bt_aux"])
        reg.counter("noc.link.flits", **lab).inc(data["num_flits"])
        reg.counter("noc.link.energy_pj", **lab).inc(data["energy_pj"])
    elif kind == "noc.contend":
        lab = {
            "link": data["link"], "src": data["src"], "dst": data["dst"],
        }
        reg.counter("noc.contend.flows", **lab).inc(data["flows"])
        reg.counter("noc.contend.wait_cycles", **lab).inc(
            data["wait_cycles"]
        )
    elif kind == "link.report":
        lab = {"stream": data["name"]}
        reg.counter("link.bt", side="input", **lab).inc(data["bt_input"])
        reg.counter("link.bt", side="weight", **lab).inc(data["bt_weight"])
        reg.counter("link.bt", side="aux", **lab).inc(data["aux_bt"])
        reg.counter("link.flits", **lab).inc(data["num_flits"])
        reg.counter("link.energy_pj", **lab).inc(data["energy_pj"])
    elif kind == "link.activity":
        lab = {
            "link": data["link"], "src": data["src"], "dst": data["dst"],
        }
        reg.counter("link.activity.toggles", **lab).inc(
            data["toggles_total"]
        )
        reg.counter("link.activity.windows", **lab).inc(
            data["num_windows"]
        )
        reg.counter(
            "link.activity.hot_wire_toggles",
            wire=wire_name(data["hot_wire"], data["data_lanes"]),
            **lab,
        ).inc(data["hot_wire_toggles"])
        # per-wire distribution as a histogram (bounded series count —
        # wire *values* stream through one series per link, never one
        # series per wire)
        hist = reg.histogram("link.activity.wire_toggles", **lab)
        for v in data["per_wire"]:
            hist.observe(v)
    elif kind == "dse.link":
        lab = {"link": data["link"], "width": data["width"]}
        reg.counter("dse.link.bt", **lab).inc(data["bt"])
        reg.counter("dse.link.packets", **lab).inc(data["packets"])
    elif kind == "dse.point":
        reg.counter("dse.points", width=data["width"]).inc()
        reg.histogram("dse.point.bt_reduction").observe(data["bt_reduction"])
    elif kind == "codec.stream":
        reg.counter(
            "codec.stream.bt", workload=data["workload"],
            stream=data["stream"],
        ).inc(data["bt"])
    elif kind == "capture.stream":
        lab = {"scenario": data["scenario"], "stream": data["stream"]}
        reg.counter("capture.bytes", **lab).inc(data["bytes"])
        reg.counter("capture.streams", **lab).inc()
    else:  # unknown kinds still count — new probes degrade gracefully
        reg.counter(f"{kind}.calls", **_labels(kind, data)).inc()


class _SpanCtx:
    """One probe span fanned out to every active tracer, registry and, when
    profiling, the profiler's trace."""

    __slots__ = ("_sink", "_kind", "_data", "_ends", "_note")

    def __init__(self, sink: "_Sink", kind: str, data: dict) -> None:
        self._sink, self._kind, self._data = sink, kind, data

    def __enter__(self):
        self._ends = [
            t.begin(self._kind, args=self._data) for t in self._sink.tracers
        ]
        self._note = None
        if self._sink.profiling:
            self._note = TraceAnnotation(
                self._kind, **_labels(self._kind, self._data)
            )
            self._note.__enter__()
        return self

    def __exit__(self, *exc):
        if self._note is not None:
            self._note.__exit__(*exc)
        for end in self._ends:
            end()
        for reg in self._sink.registries:
            _record_span(reg, self._kind, self._data)
        return False


class _Sink:
    """The multiplexer installed into ``repro._obs_hooks.SINK``."""

    def __init__(self) -> None:
        self.registries: list[Registry] = []
        self.tracers: list[Tracer] = []
        self.profiling = 0  # depth of open profiling() scopes

    @property
    def collecting(self) -> bool:
        """A registry or tracer is active (``_obs_hooks.active()``)."""
        return bool(self.registries or self.tracers)

    def span(self, kind: str, data: dict) -> _SpanCtx:
        return _SpanCtx(self, kind, data)

    def event(self, kind: str, data: dict) -> None:
        for t in self.tracers:
            t.instant(kind, args=data)
        for reg in self.registries:
            _record_event(reg, kind, data)


_SINK = _Sink()


def _refresh() -> None:
    _obs_hooks.SINK = (
        _SINK if (_SINK.collecting or _SINK.profiling) else None
    )


def active_registries() -> tuple[Registry, ...]:
    return tuple(_SINK.registries)


def active_tracers() -> tuple[Tracer, ...]:
    return tuple(_SINK.tracers)


@contextmanager
def collect(registry: Registry | None = None):
    """Activate metrics collection for the with-body; yields the registry.

    Nested ``collect()`` scopes all receive every probe firing (each scope
    sees its own totals).  Entering the first scope is what installs the
    sink — before that, probes are a ``None`` test and nothing else.
    """
    reg = Registry() if registry is None else registry
    _SINK.registries.append(reg)
    _refresh()
    try:
        yield reg
    finally:
        _SINK.registries.remove(reg)
        _refresh()


@contextmanager
def tracing(tracer: Tracer | None = None):
    """Activate span tracing for the with-body; yields the tracer."""
    tr = Tracer() if tracer is None else tracer
    _SINK.tracers.append(tr)
    _refresh()
    try:
        yield tr
    finally:
        _SINK.tracers.remove(tr)
        _refresh()


@contextmanager
def profiling():
    """Write every probe span into the JAX profiler's trace for the
    with-body.

    Each span enters a ``jax.profiler.TraceAnnotation`` named by its kind,
    with the kind's identity labels as arguments, so a profiler session
    (``jax.profiler.start_trace``/``trace``) holds the program's host spans
    on the device events' clock.  Events are not recorded, and
    ``_obs_hooks.active()`` stays False under this consumer alone.
    Re-entrant; leaving the last scope (also by an exception) empties the
    hook slot again when nothing else collects."""
    _SINK.profiling += 1
    _refresh()
    try:
        yield
    finally:
        _SINK.profiling -= 1
        _refresh()
