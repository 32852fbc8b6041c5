"""``perfbench.spans``: the program's probe spans in a profiler trace.

On the CPU: a trace taken under ``repro.obs.profiling()`` holds the spans
under the names the reduction reads, and the names the readers key on
(span kinds, entry programs) are the program's own, so that a rename fails
here instead of silencing a number.  On two traces recorded on one TPU v5e
with program spans (three full-size ``lenet-conv-paper`` reports, two
reports of a tiny decode grid): the reduction against counts made from the
events another way."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from perfbench import spans, trace
from repro import obs
from repro.dse import DesignPoint, Workload, evaluate_grid
from repro.kernels import bt_count_axes, psu_stream, quantize_egress
from repro.link import LinkSpec, TxPipeline

POINTS = (DesignPoint(ordering="none", k=None),
          DesignPoint(ordering="acc", k=None),
          DesignPoint(ordering="app", k=4, codec="bus_invert"))


def _report(pipes, x, workload):
    """Two measures (fused and staged) and one grid with activity."""
    out = [p.measure(x) for p in pipes]
    out.append(evaluate_grid(POINTS, workload, activity_windows=4))
    return out


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.integers(0, 255, (16, 64), dtype=np.uint8))
    pipes = [TxPipeline(LinkSpec(width_bits=128, input_lanes=16,
                                 weight_lanes=0, key=key))
             for key in ("acc", "none")]
    workload = Workload("w", (x, x[:8]), 16)
    plain = _report(pipes, x, workload)  # compiles every program
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        with obs.profiling(), TraceAnnotation(trace.WINDOW):
            profiled = []
            for _ in range(2):
                with TraceAnnotation(trace.REPORT):
                    profiled.append(_report(pipes, x, workload))
    finally:
        jax.profiler.stop_trace()
    return trace._load(trace.find_xspace(d)), plain, profiled


def test_profiled_trace_holds_the_program_spans(cpu_trace):
    data, plain, profiled = cpu_trace
    assert all(p == plain for p in profiled)  # spans change no result
    window, events = spans.window_thread(data)
    reports = spans.traced_reports(window, events)
    assert len(reports) == 2
    kinds = spans.kind_totals(events, reports)
    calls = {k: c for k, (_, c) in kinds.items()}
    # per report: 2 transmits, 2 measure reads, 2 grid reads (BT table and
    # toggles of the one width), 1 grid launch; encode on both paths and
    # order / assemble / bt on the staged one
    assert calls["link.tx"] == 4
    assert calls["link.readback"] == 4
    assert calls["dse.readback"] == 4
    assert calls["dse.measure"] == 2
    assert calls["link.stage"] == 2 * (2 + 3)
    assert calls["kernel.dispatch"] >= 6
    assert set(kinds) <= set(obs.PROBE_KINDS)
    for secs, _ in kinds.values():
        assert 0 < secs < (window[1] - window[0]) * 1e-9
    # the annotation's name is the kind alone: its labels are arguments
    names = {ev.name for plane in data.planes for line in plane.lines
             for ev in line.events}
    assert {"link.tx", "link.readback", "dse.readback"} <= names
    assert not [n for n in names if n.startswith("link.") and "#" in n]


def test_spans_per_report():
    # two reports of 10 ns; a readback nested in a readback counts once
    reports = [(0, 10), (20, 30)]
    events = [(0, 10, trace.REPORT), (20, 30, trace.REPORT),
              (1, 5, "link.tx"), (2, 4, "kernel.dispatch"),
              (6, 8, "link.readback"), (6, 7, "link.readback"),
              (21, 29, "link.tx"), (22, 23, "dse.readback"),
              (12, 14, "link.readback"),  # between reports: not counted
              (25, 26, spans.BUILD), (3, 4, "host.stack"),
              (1, 9, "link.measure")]  # the benchmark's own annotations
    kinds = spans.kind_totals(events, reports)
    assert {k: (round(s * 1e9), c) for k, (s, c) in kinds.items()} == {
        "dse.readback": (1, 1), "kernel.dispatch": (2, 1),
        "link.readback": (2, 2), "link.tx": (12, 2)}
    sp = spans.Spans(reports=2, kinds=kinds, gaps=[], builds=[],
                     axes_outside_kernel_s=None)
    assert sp.metrics() == {
        "tx_host_ms": pytest.approx(6e-6), "readback_ms": pytest.approx(1.5e-6),
        "readbacks_per_report": 1.5, "axes_outside_kernel_ms": None}
    empty = spans.Spans(reports=2, kinds={}, gaps=[], builds=[],
                        axes_outside_kernel_s=None)
    assert set(empty.metrics().values()) == {None}


def test_read_names_are_the_programs_own():
    """The span kinds read here are probe kinds, and the public entry
    points still run as the programs the readers key on."""
    for kind in spans.TX_SPANS + spans.READBACK_SPANS:
        assert obs.PROBE_KINDS[kind] == "span"
    x = jnp.zeros((1, 64, 64), jnp.uint8)
    cfg = (DesignPoint(ordering="none", k=None).codec_variant,)
    programs = {
        trace.AXES_PROGRAM: lambda: bt_count_axes(
            x, configs=cfg, input_lanes=16),
        trace.QUANTIZE_PROGRAM: lambda: quantize_egress(
            jnp.zeros(512, jnp.float32)),
        trace.PSU_PROGRAM: lambda: psu_stream(x[0], width=8, input_lanes=16),
    }
    for name, call in programs.items():
        # a top-level jitted call runs as the program jit_<name>
        eqns = jax.make_jaxpr(call)().eqns
        assert name in {e.params.get("name") for e in eqns}, name


# ------------------------------------- traces recorded on one TPU v5e

PAPER = os.path.join(os.path.dirname(__file__), "data",
                     "paper_spans_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def paper():
    return trace._load(PAPER), trace.reduce(PAPER), spans.reduce(PAPER)


def test_paper_spans_per_report(paper):
    """Three full-size reports of ``lenet-conv-paper`` traced with the
    program's spans: every report reads back 11 times (9 measures, the
    grid's BT table and toggles), and each span kind's seconds are the
    sum of its spans, which do not nest in their own kind here."""
    data, _, sp = paper
    window, events = spans.window_thread(data)
    reports = spans.traced_reports(window, events)
    assert sp.reports == len(reports) == 3
    for s, e in reports:
        reads = [x for x in events if s <= x[0] < e
                 and x[2] in spans.READBACK_SPANS]
        assert len(reads) == 11
    for kind in ("link.tx", "link.readback", "dse.readback"):
        inside = [(s, e) for s, e, k in events if k == kind
                  and any(rs <= s < re for rs, re in reports)]
        assert sp.kinds[kind] == (
            pytest.approx(sum(e - s for s, e in inside) * 1e-9), len(inside))
    m = sp.metrics()
    assert m["readbacks_per_report"] == 11.0
    report_ms = 1e3 * sum(e - s for s, e in reports) * 1e-9 / 3
    assert 0 < m["tx_host_ms"] + m["readback_ms"] < report_ms
    assert sp.builds == []  # a warm cell builds nothing in the window


def test_paper_idle_is_named_by_program_spans(paper):
    """The gaps that trace.reduce names fall mostly under program spans,
    and the longest single gaps are among its idle seconds."""
    _, red, sp = paper
    idle = sum(red.gaps.values())
    named = sum(v for k, v in red.gaps.items() if spans.is_program_span(k))
    assert named >= 0.75 * idle
    assert 0 < len(sp.gaps) <= spans.TOP_GAPS
    secs = [g[0] for g in sp.gaps]
    assert secs == sorted(secs, reverse=True) and sum(secs) < idle
    assert all(0 <= at < 3 and spans.is_program_span(name)
               for _, at, name in sp.gaps)


TINY_GRID = os.path.join(os.path.dirname(__file__), "data",
                         "tiny_grid_spans_v5e.xplane.pb.gz")


def test_decode_spans_per_report():
    """Two reports of a tiny decode grid (3 layers at widths 128/256,
    256-packet chunks) traced with the program's spans: each dispatches
    7 quantizer entries and 1 measurement entry, and reads nothing back
    through the link or grid paths."""
    sp = spans.reduce(TINY_GRID)
    assert sp.reports == 2
    assert sp.kinds["kernel.dispatch"][1] == 2 * 8
    assert set(sp.kinds) == {"kernel.dispatch"}
    m = sp.metrics()
    assert m["tx_host_ms"] is m["readback_ms"] is None
    assert m["readbacks_per_report"] is None
    assert m["axes_outside_kernel_ms"] > 0


@pytest.mark.parametrize("path", [PAPER, TINY_GRID], ids=["paper", "decode"])
def test_axes_time_outside_the_kernel(path):
    """Program time of the ``_bt_count_axes`` programs less their Pallas
    kernel time, counted here from the events themselves."""
    data, red, sp = trace._load(path), trace.reduce(path), spans.reduce(path)
    w0, w1 = spans.window_thread(data)[0]
    (plane,) = [p for p in data.planes if trace.DEVICE_PLANE.match(p.name)]
    lines = {line.name: line for line in plane.lines}
    programs = sum(
        min(int(m.end_ns), w1) - max(int(m.start_ns), w0)
        for m in lines["XLA Modules"].events
        if m.name.startswith(f"jit_{trace.AXES_PROGRAM}(")
        and int(m.end_ns) > w0 and int(m.start_ns) < w1) * 1e-9
    want = programs - red.kernel_seconds(trace.AXES_PROGRAM)
    assert sp.axes_outside_kernel_s == pytest.approx(want, rel=1e-6)
    assert 0 < want < programs
