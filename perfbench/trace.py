"""Reduce a profiler trace to the benchmark's device numbers.

Read with ``jax.profiler.ProfileData`` alone.  On a TPU the trace holds one
plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event
per operation the chip ran (named by its HLO text) and whose line
``XLA Modules`` has one event per program executed (``jit_<fn>(<id>)``).
The host plane ``/host:CPU`` holds the benchmark's own annotations, among
them ``perfbench.window`` around the traced reports and ``perfbench.report``
around each of them.  All events share
one clock in the trace; the device's timestamps are converted to it by
the profiler, so a device event may appear shifted by a fraction of a
millisecond against the host's.

A Pallas kernel is an operation whose HLO is a ``tpu_custom_call``; which
kernel it is follows from the program it runs in, e.g. the measurement
kernel inside ``jit__bt_count_axes``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import os
import re

WINDOW = "perfbench.window"
REPORT = "perfbench.report"
KERNEL = 'custom_call_target="tpu_custom_call"'
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
PROGRAM = re.compile(r"^jit_(.*)\(\d+\)$")

# the entry programs whose Pallas kernels the readers time
AXES_PROGRAM = "_bt_count_axes"
QUANTIZE_PROGRAM = "_quantize_egress"
PSU_PROGRAM = "_psu_stream"


def find_xspace(log_dir: str) -> str:
    """The one ``.xplane.pb`` a profiler session wrote under ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {found}")
    return found[0]


def _load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


class TraceIncomplete(RuntimeError):
    """The trace holds no device program for some traced report: the
    profiler lost events, and no number read from it can be trusted."""


def _short(hlo: str) -> str:
    """An HLO instruction's name, without its text (``%fusion.3 = ...``)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Reduced:
    """One traced window, reduced.

    ``busy_s`` is the union of the intervals inside the window in which the
    device ran an operation or a program (a program's interval covers its
    DMA waits and loop control, which no operation event does), averaged
    over chips; ``reports`` counts the report annotations in the window;
    ``programs`` counts program executions in the window over all chips,
    and ``report_programs`` those that start in each report (a program
    starting between two reports goes to the nearer one); ``kernels`` maps each entry program to the
    device seconds of the Pallas kernels it ran; ``ops`` maps
    ``<program>:<instruction>`` to device seconds; ``gaps`` maps the
    innermost benchmark annotation open at an idle gap's middle to the
    gap's seconds."""

    window_s: float
    busy_s: float
    reports: int
    programs: int
    report_programs: list[int]
    kernels: dict[str, float]
    ops: dict[str, float]
    gaps: dict[str, float]

    def kernel_seconds(self, program: str) -> float:
        return self.kernels.get(program, 0.0)

    def breakdown(self, top: int = 10) -> dict:
        def largest(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]

        return {"device_ops": largest(self.ops),
                "idle_gaps": largest(self.gaps)}


def reduce(path: str) -> Reduced:
    """Reduce the trace at ``path`` (``.xplane.pb``, or gzipped ``.gz``).

    Idle gaps are attributed to the benchmark's annotations on the thread
    that holds the window: names with a dot and no parenthesis or space
    (``link.measure``), which nest, being ``with`` blocks of one thread.

    Raises :class:`TraceIncomplete` when a report inside the window holds
    no device program: the profiler dropped the events from there on."""
    data = _load(path)
    host_spans: list[tuple[int, int, str]] = []
    window = None
    devices = []
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                events = [(int(ev.start_ns), int(ev.end_ns), ev.name)
                          for ev in line.events]
                for s, e, name in events:
                    if name == WINDOW:
                        window = (s, e)
                        host_spans = [x for x in events
                                      if "." in x[2]
                                      and not re.search(r"[( ]", x[2])]
        elif DEVICE_PLANE.match(plane.name):
            devices.append(plane)
    if window is None:
        raise RuntimeError(f"no {WINDOW!r} annotation in {path}")
    if not devices:
        raise RuntimeError(f"no TPU device plane in {path}")
    w0, w1 = window
    reports = sorted((s, e) for s, e, name in host_spans
                     if name == REPORT and w0 <= s and e <= w1)
    if not reports:
        raise RuntimeError(f"no {REPORT!r} annotation inside the window")

    busy_ns = 0
    programs = 0
    starts_all: list[int] = []
    kernels: dict[str, float] = collections.Counter()
    ops: dict[str, float] = collections.Counter()
    gaps: dict[str, float] = collections.Counter()
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        modules = []
        intervals = []
        for ev in lines["XLA Modules"].events if "XLA Modules" in lines else ():
            s, e = int(ev.start_ns), int(ev.end_ns)
            if e <= w0 or s >= w1:
                continue
            programs += 1
            m = PROGRAM.match(ev.name)
            modules.append((s, e, m.group(1) if m else ev.name))
            intervals.append((max(s, w0), min(e, w1)))
        modules.sort()
        starts = [m[0] for m in modules]
        for ev in lines["XLA Ops"].events if "XLA Ops" in lines else ():
            s, e = int(ev.start_ns), int(ev.end_ns)
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            intervals.append((s, e))
            prog = _enclosing(modules, starts, s)
            secs = (e - s) * 1e-9
            ops[f"{prog}:{_short(ev.name)}"] += secs
            if KERNEL in ev.name:
                kernels[prog] += secs
        busy = _union(intervals)
        busy_ns += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle = [(gs, ge) for gs, ge in zip(edges[0::2], edges[1::2]) if ge > gs]
        names = _innermost(host_spans, [(gs + ge) // 2 for gs, ge in idle])
        for (gs, ge), name in zip(idle, names):
            gaps[name] += (ge - gs) * 1e-9
        starts_all += starts
    n = len(devices)
    report_programs = programs_per_report(reports, starts_all, n)
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_ns * 1e-9 / n,
        reports=len(reports),
        programs=programs,
        report_programs=report_programs,
        kernels=dict(kernels),
        ops={k: v / n for k, v in ops.items()},
        gaps={k: v / n for k, v in gaps.items()},
    )


def programs_per_report(reports: list[tuple[int, int]], starts: list[int],
                        chips: int = 1) -> list[int]:
    """Programs per report, from the programs' start times on ``chips``
    chips: each goes to the report that holds its start, or the nearer
    one.  Raises :class:`TraceIncomplete` when some report has none."""
    counts = [0] * len(reports)
    firsts = [s for s, _ in reports]
    for t in starts:
        i = max(bisect.bisect_right(firsts, t) - 1, 0)
        if (t >= reports[i][1] and i + 1 < len(reports)
                and reports[i + 1][0] - t < t - reports[i][1]):
            i += 1
        counts[i] += 1
    empty = [i for i, c in enumerate(counts) if c < chips]
    if empty:
        raise TraceIncomplete(
            f"{len(empty)} of {len(reports)} traced reports hold no device "
            f"program, the first is report {empty[0]}: the trace lost events"
        )
    return [c // chips for c in counts]


def _enclosing(modules, starts, t: int) -> str:
    """The program whose execution contains time ``t`` (or ``?``)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return "?"


def _innermost(spans: list[tuple[int, int, str]], points: list[int]
               ) -> list[str]:
    """For each time in ascending ``points``, the innermost of the nested
    ``spans`` open then."""
    spans = sorted(spans)
    out, stack, i = [], [], 0
    for t in points:
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out.append(stack[-1][2] if stack else "(no annotation)")
    return out
