"""``perfbench.trace`` on a trace recorded on one TPU v5e.

The recording (gzipped ``.xplane.pb``) holds two reports of a tiny decode
grid and two of the paper's LeNet flow, inside one ``perfbench.window``
annotation.  Each number is checked against a count made another way."""

import os

import pytest

from perfbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "two_cells_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    return trace._load(DATA), trace.reduce(DATA)


def _device_lines(data):
    (plane,) = [p for p in data.planes if trace.DEVICE_PLANE.match(p.name)]
    return {line.name: line for line in plane.lines}


def _window(data):
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.WINDOW:
                    return int(ev.start_ns), int(ev.end_ns)
    raise AssertionError("no window")


def test_busy_union_and_idle_share(recorded):
    data, red = recorded
    w0, w1 = _window(data)
    assert red.window_s == pytest.approx((w1 - w0) * 1e-9)
    # sweep over interval edges, counting the operations and programs
    # running
    edges = []
    lines = _device_lines(data)
    for ev in list(lines["XLA Ops"].events) + list(lines["XLA Modules"].events):
        s, e = max(int(ev.start_ns), w0), min(int(ev.end_ns), w1)
        if e > s:
            edges += [(s, 1), (e, -1)]
    busy, depth, last = 0, 0, None
    for t, d in sorted(edges):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert red.busy_s == pytest.approx(busy * 1e-9, abs=1e-12)
    assert 0 < red.busy_s < red.window_s
    idle = 1 - red.busy_s / red.window_s
    assert 0.8 < idle < 1.0  # two tiny cells: the host drives, the chip waits
    assert sum(red.gaps.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)
    assert "link.measure" in red.gaps


def test_kernel_time_by_program_and_program_count(recorded):
    data, red = recorded
    w0, w1 = _window(data)
    lines = _device_lines(data)
    modules = [(int(m.start_ns), int(m.end_ns), m.name)
               for m in lines["XLA Modules"].events
               if int(m.end_ns) > w0 and int(m.start_ns) < w1]
    assert red.programs == len(modules) == 109
    # two reports of each cell, each with its programs
    assert red.reports == 4
    assert sum(red.report_programs) == 109 and min(red.report_programs) > 0
    want = {}
    for ev in lines["XLA Ops"].events:
        if trace.KERNEL not in ev.name:
            continue
        s = max(int(ev.start_ns), w0)
        e = min(int(ev.end_ns), w1)
        if e <= s:
            continue
        owner = [n for ms, me, n in modules if ms <= s < me]
        prog = trace.PROGRAM.match(owner[0]).group(1)
        want[prog] = want.get(prog, 0) + (e - s) * 1e-9
    assert set(red.kernels) == set(want) == {
        "_quantize_egress", "_bt_count_axes", "_bt_count", "_psu_stream"}
    for prog, secs in want.items():
        assert red.kernel_seconds(prog) == pytest.approx(secs)
    assert red.kernel_seconds("_no_such_program") == 0.0
    out = red.breakdown()
    assert set(out) == {"device_ops", "idle_gaps"}
    assert 0 < len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["device_ops"][0][1] >= out["device_ops"][-1][1]


def test_union_and_innermost_span():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    spans = [(0, 100, "a.report"), (10, 20, "b.inner"), (30, 90, "c.inner"),
             (40, 50, "d.deeper")]
    assert trace._innermost(spans, [5, 15, 25, 45, 60, 95, 150]) == [
        "a.report", "b.inner", "a.report", "d.deeper", "c.inner",
        "a.report", "(no annotation)"]


def test_programs_per_report_and_a_trace_that_lost_events():
    reports = [(0, 10), (20, 30), (40, 50)]
    # 12 is nearer report 0, 17 nearer report 1; -5 and 60 lie outside
    # every report and go to the first and the last
    assert trace.programs_per_report(reports, [-5, 1, 12, 17, 25, 45, 60]
                                     ) == [3, 2, 2]
    assert trace.programs_per_report(reports, [1, 2, 21, 22, 41, 42],
                                     chips=2) == [1, 1, 1]
    with pytest.raises(trace.TraceIncomplete, match="1 of 3"):
        trace.programs_per_report(reports, [1, 5, 22, 31])


def test_readers_divide_by_the_traced_reports(recorded):
    from perfbench import harness

    _, red = recorded
    rec = harness.RunRecord(setup_s=1.0, window_s=20.0,
                            latencies_s=[0.1] * 200,
                            events_per_report=1, work={}, trace=red)
    read = harness.load_reader
    assert read("programs_per_report")(rec) == pytest.approx(109 / 4)
    assert read("programs_per_report.host")(rec) == pytest.approx(109 / 4)
    assert read("axes_kernel_ms")(rec) == pytest.approx(
        1e3 * red.kernel_seconds(trace.AXES_PROGRAM) / 4)
