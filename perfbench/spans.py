#!/usr/bin/env python3
"""The program's probe spans in a cell's profiler trace.

    python3 perfbench/spans.py --workload <name> --seed <n> [--seconds <s>]
                               [--reports <k>] [--keep <file.xplane.pb.gz>]

Run from the root of a checkout, on the chip the cell names.  After the
cell's set-up it runs three segments of reports back to back:

  1. ``k`` reports traced as ``run.py --trace 1`` traces them (the
     profiler on, the benchmark's annotations only);
  2. ``k`` reports traced with the program's probe spans as well
     (``repro.obs.profiling()``: ``link.tx``, ``link.readback``,
     ``dse.readback``, ``kernel.dispatch``, ... on the profiler's clock);
  3. reports untraced, for ``--seconds``.

It prints on standard error, for each traced segment, the reduction that
``perfbench/trace.py`` makes and the per-layer metrics of ``BENCHMARK.json``
read from it, and for segment 2 the seconds and calls of each span kind
per report, the five longest single idle gaps (report index, innermost
span open at the gap's middle), the innermost span open at each program
built inside the window, and the host-dispatch numbers these spans give
(``tx_host_ms``, ``readback_ms``, ``readbacks_per_report``) beside the
chunk fold's device time (``axes_outside_kernel_ms``).  The last line of
standard output is the same as one JSON object, with the median report
latency of each segment.  ``--keep`` writes segment 2's trace, gzipped.

The reduction (:func:`reduce`) reads the ``.xplane.pb`` with
``jax.profiler.ProfileData`` alone; the span kinds are those of
``repro.obs.PROBE_KINDS``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import gzip
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time

if __package__ in (None, ""):  # run as a script: the checkout's packages
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

from perfbench import harness, trace  # noqa: E402
from repro import obs  # noqa: E402

BUILD = "perfbench.build"  # marks each program JAX builds while tracing
TX_SPANS = ("link.tx",)
READBACK_SPANS = ("link.readback", "dse.readback")
TOP_GAPS = 5


def is_program_span(kind: str) -> bool:
    """A span the program fires (``repro.obs.PROBE_KINDS``), not one of the
    benchmark's own annotations (``link.measure``, ``host.readback``)."""
    return obs.PROBE_KINDS.get(kind) == "span"


def window_thread(data) -> tuple[tuple[int, int], list[tuple[int, int, str]]]:
    """The traced window and every annotation on the host thread that
    holds it, as (start ns, end ns, kind), sorted."""
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            # an annotation's labels are event stats, not part of its name
            events = [(int(ev.start_ns), int(ev.end_ns), ev.name)
                      for ev in line.events]
            for s, e, name in events:
                if name == trace.WINDOW:
                    return (s, e), sorted(events)
    raise RuntimeError(f"no {trace.WINDOW!r} annotation in the trace")


def traced_reports(window, events) -> list[tuple[int, int]]:
    w0, w1 = window
    return [(s, e) for s, e, name in events
            if name == trace.REPORT and w0 <= s and e <= w1]


def kind_totals(events, reports) -> dict[str, tuple[float, int]]:
    """For each program span kind: the seconds inside the traced reports
    covered by at least one span of that kind (a span nested in one of its
    own kind is not counted twice), and the calls that start in a report."""
    firsts = [s for s, _ in reports]

    def report_of(t: int) -> int:
        i = bisect.bisect_right(firsts, t) - 1
        return i if i >= 0 and t < reports[i][1] else -1

    by_kind: dict[str, list[tuple[int, int]]] = collections.defaultdict(list)
    calls: dict[str, int] = collections.Counter()
    for s, e, kind in events:
        if not is_program_span(kind):
            continue
        i = report_of(s)
        if i < 0:
            continue
        calls[kind] += 1
        by_kind[kind].append((s, min(e, reports[i][1])))
    return {kind: (sum(e - s for s, e in trace._union(iv)) * 1e-9,
                   calls[kind])
            for kind, iv in sorted(by_kind.items())}


@dataclasses.dataclass
class Spans:
    """Segment 2's trace, reduced.

    ``kinds`` maps each program span kind to (seconds, calls) inside the
    traced reports; ``gaps`` lists the longest single idle gaps of the
    device as (seconds, report index or -1 between reports, innermost span
    open at the gap's middle); ``builds`` names the innermost span open at
    each program built inside the window; ``axes_outside_kernel_s`` is the
    device time of the ``_bt_count_axes`` programs outside their Pallas
    kernel events (``None`` when the trace holds none of them)."""

    reports: int
    kinds: dict[str, tuple[float, int]]
    gaps: list[tuple[float, int, str]]
    builds: list[str]
    axes_outside_kernel_s: float | None

    def _sum(self, kinds, field: int) -> float | None:
        found = [self.kinds[k][field] for k in kinds if k in self.kinds]
        return sum(found) if found else None

    def metrics(self) -> dict[str, float | None]:
        """The host-dispatch and fold numbers per traced report."""
        n = self.reports
        tx = self._sum(TX_SPANS, 0)
        rb = self._sum(READBACK_SPANS, 0)
        rb_calls = self._sum(READBACK_SPANS, 1)
        fold = self.axes_outside_kernel_s
        return {
            "tx_host_ms": None if tx is None else 1e3 * tx / n,
            "readback_ms": None if rb is None else 1e3 * rb / n,
            "readbacks_per_report": None if rb_calls is None else rb_calls / n,
            "axes_outside_kernel_ms": None if fold is None else 1e3 * fold / n,
        }


def reduce(path: str) -> Spans:
    """Reduce the trace at ``path`` (``.xplane.pb``, or gzipped ``.gz``).

    Busy time is what ``trace.reduce`` takes: the union of the device's
    operation and program intervals inside the window."""
    data = trace._load(path)
    window, events = window_thread(data)
    reports = traced_reports(window, events)
    if not reports:
        raise RuntimeError(f"no {trace.REPORT!r} annotation inside the window")
    w0, w1 = window
    # annotations, as trace.reduce takes them: the runtime's own events
    # on the thread (``PjitFunction(...)``, ``np.asarray(...)``) are not
    spans = [x for x in events if x[2] != BUILD and "." in x[2]
             and not re.search(r"[( ]", x[2])]
    firsts = [s for s, _ in reports]
    gaps = []
    fold_ns, axes_programs, n = 0, 0, 0
    for plane in data.planes:
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        n += 1
        lines = {line.name: line for line in plane.lines}
        modules, intervals = [], []
        for ev in lines["XLA Modules"].events if "XLA Modules" in lines else ():
            s, e = max(int(ev.start_ns), w0), min(int(ev.end_ns), w1)
            if e <= s:
                continue
            m = trace.PROGRAM.match(ev.name)
            modules.append((s, e, m.group(1) if m else ev.name))
            intervals.append((s, e))
        modules.sort()
        starts = [m[0] for m in modules]
        kernels: dict[int, list[tuple[int, int]]] = collections.defaultdict(list)
        for ev in lines["XLA Ops"].events if "XLA Ops" in lines else ():
            s, e = max(int(ev.start_ns), w0), min(int(ev.end_ns), w1)
            if e <= s:
                continue
            intervals.append((s, e))
            if trace.KERNEL in ev.name:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < modules[i][1]:
                    kernels[i].append((s, min(e, modules[i][1])))
        for i, (s, e, prog) in enumerate(modules):
            if prog == trace.AXES_PROGRAM:
                axes_programs += 1
                inside = trace._union(kernels.get(i, []))
                fold_ns += (e - s) - sum(ke - ks for ks, ke in inside)
        busy = trace._union(intervals)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle = [(gs, ge) for gs, ge in zip(edges[0::2], edges[1::2])
                if ge > gs]
        longest = sorted(idle, key=lambda g: g[0] - g[1])[:TOP_GAPS]
        longest.sort()
        mids = [(gs + ge) // 2 for gs, ge in longest]
        for (gs, ge), mid, name in zip(longest, mids,
                                       trace._innermost(spans, mids)):
            i = bisect.bisect_right(firsts, mid) - 1
            at = i if i >= 0 and mid < reports[i][1] else -1
            gaps.append(((ge - gs) * 1e-9, at, name))
    if not n:
        raise RuntimeError(f"no TPU device plane in {path}")
    builds = [s for s, _, name in events if name == BUILD and w0 <= s <= w1]
    return Spans(
        reports=len(reports),
        kinds=kind_totals(events, reports),
        gaps=sorted(gaps, key=lambda g: -g[0])[:TOP_GAPS],
        builds=trace._innermost(spans, builds),
        axes_outside_kernel_s=fold_ns * 1e-9 / n if axes_programs else None,
    )


def mark_builds() -> None:
    """From now on, put a zero-length ``perfbench.build`` annotation on the
    profiler's clock wherever JAX builds a program (a no-op while no
    profiler session runs)."""
    import jax

    def on_event(event: str, **kwargs) -> None:
        if event == harness.CompileCounter.BUILT:
            with jax.profiler.TraceAnnotation(BUILD):
                pass

    jax.monitoring.register_event_listener(on_event)


def traced_segment(cell, reports: int, first: int, span, *, program_spans,
                   keep: str | None = None):
    """``reports`` reports from ``first`` on under the profiler, as
    ``harness.measure`` traces them, with the program's spans when
    ``program_spans``; (latencies, trace.Reduced, Spans)."""
    import contextlib

    import jax

    consumer = obs.profiling() if program_spans else contextlib.nullcontext()
    with tempfile.TemporaryDirectory(prefix="perfbench-spans-") as d:
        jax.profiler.start_trace(d, profiler_options=harness._trace_options())
        try:
            with consumer:
                lat, _ = harness.run_window(cell, float("inf"), span,
                                            first=first, limit=reports)
        finally:
            jax.profiler.stop_trace()
        path = trace.find_xspace(d)
        if keep:
            with open(path, "rb") as src, gzip.open(keep, "wb") as dst:
                shutil.copyfileobj(src, dst)
        return lat, trace.reduce(path), reduce(path)


def _per_layer(bench, workload, record) -> dict:
    out = {}
    for m in harness.cell_metrics(bench, workload, traced=True):
        value = harness.load_reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = value
    return out


def _median_ms(lat) -> float | None:
    return 1e3 * statistics.median(lat) if lat else None


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--reports", type=int, default=0,
                    help="reports per traced segment (default: the "
                         "traffic's traced_reports)")
    ap.add_argument("--keep", help="write segment 2's trace here, gzipped")
    args = ap.parse_args(argv)
    started = time.time()
    harness.enable_compile_cache()
    bench = harness.load_benchmark()
    try:
        entry, config, traffic = harness.find_cell(bench, args.workload)
        device = harness.check_device(int(entry["chips"]))
    except harness.BenchError as e:
        print(f"perfbench.spans: {e}", file=sys.stderr)
        return 2
    k = args.reports or traffic.get("traced_reports", harness.TRACED_REPORTS)
    span = harness.span_factory(True)
    driver = harness.load_driver(traffic)
    peaks = harness.load_peaks(device["kind"])
    mark_builds()
    cell = driver.setup(config, traffic, harness.derive_seed(args.seed), span)
    setup_s = time.time() - started

    out = {"workload": args.workload, "seed": args.seed, "reports": k,
           "setup_s": setup_s, "device": device}
    for seg, program_spans in (("annotations", False), ("program_spans", True)):
        lat, red, sp = traced_segment(
            cell, k, k if program_spans else 0, span,
            program_spans=program_spans,
            keep=args.keep if program_spans else None)
        record = harness.RunRecord(setup_s, sum(lat), lat,
                                   cell.events_per_report, cell.work, red,
                                   peaks)
        out[seg] = {
            "median_ms": _median_ms(lat),
            "idle_pct": 100.0 * (1.0 - red.busy_s / red.window_s),
            "programs": [min(red.report_programs), max(red.report_programs)],
            "per_layer": _per_layer(bench, args.workload, record),
            "breakdown": red.breakdown(),
            "spans": sp.metrics(),
            "kinds": {kd: [s / sp.reports * 1e3, c / sp.reports]
                      for kd, (s, c) in sp.kinds.items()},
            "gaps": sp.gaps,
            "builds": sp.builds,
        }
    rest, _ = harness.run_window(cell, args.seconds, span, first=2 * k)
    out["untraced"] = {"median_ms": _median_ms(rest), "reports": len(rest)}

    for seg in ("annotations", "program_spans"):
        o = out[seg]
        print(f"{seg}: {k} reports, median {o['median_ms']:.3f} ms, idle "
              f"{o['idle_pct']:.2f} %, programs per report {o['programs']}",
              file=sys.stderr)
        print(f"  per-layer {o['per_layer']}", file=sys.stderr)
        print(f"  idle by annotation {o['breakdown']['idle_gaps']}",
              file=sys.stderr)
        for kd, (ms, calls) in o["kinds"].items():
            print(f"  span {kd}: {ms:.3f} ms, {calls:g} calls per report",
                  file=sys.stderr)
        for secs, at, name in o["gaps"]:
            print(f"  gap {1e3 * secs:.3f} ms in report {at} under {name}",
                  file=sys.stderr)
        print(f"  built in the window: {len(o['builds'])} {o['builds']}",
              file=sys.stderr)
        print(f"  {o['spans']}", file=sys.stderr)
    print(f"untraced: {len(rest)} reports, median "
          f"{out['untraced']['median_ms']} ms", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
