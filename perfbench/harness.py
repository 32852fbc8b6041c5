"""The benchmark harness: one run of one cell, driven by ``BENCHMARK.json``.

A run finds its cell's files by name and by nothing else:

  * ``configs/<config>.json``: the configuration's sizes (its ``file``);
  * ``traffic/<traffic>.json``: the traffic mix's parameters, with the
    name of the driver that generates it;
  * ``drivers/<driver>.py``: set-up, one report and the comparison with
    ``reference.py`` for one path of the program;
  * ``metrics/<metric>.py``: one reader per metric, from the run's host
    timings, its work counts and its reduced device trace.

Set-up (weights, traffic, warm-up of every shape the window uses) counts
toward ``setup_s``.  The window then runs reports back to back, one at a
time (a closed loop with one client), for ``--seconds``; every report
blocks until its numbers are on the host.  After the window the device
memory peak is read, the program's state is dropped and the driver
compares what the window produced with the reference.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time
from types import ModuleType
from typing import Callable, NamedTuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")
TRACED_REPORTS = 8  # reports a traced run traces, unless the traffic says


class Compared(NamedTuple):
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class RunRecord:
    """What a metric reader may read about one run."""

    setup_s: float
    window_s: float
    latencies_s: list[float]
    events_per_report: int
    work: dict
    trace: object | None = None  # perfbench.trace.Reduced, traced runs only
    peaks: dict | None = None

    @property
    def reports(self) -> int:
        return len(self.latencies_s)


class BenchError(RuntimeError):
    """The run cannot be made as asked; nothing is printed to stdout."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(root: str, *parts: str) -> dict:
    path = os.path.join(root, "perfbench", *parts)
    if not os.path.isfile(path):
        raise BenchError(f"missing {os.path.relpath(path, root)}")
    with open(path) as f:
        return json.load(f)


def _load_module(root: str, kind: str, name: str) -> ModuleType:
    path = os.path.join(root, "perfbench", kind, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchError(f"missing {os.path.relpath(path, root)}")
    mod_name = f"perfbench.{kind}." + name.replace("-", "_").replace(".", "__")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str, root: str = ROOT
              ) -> tuple[dict, dict, dict]:
    """(workload entry, configuration, traffic parameters) of a cell."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(
            f"unknown workload {workload!r}; choose from {sorted(cells)}"
        )
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[cell["config"]]["file"])) as f:
        config = json.load(f)
    traffic = _load_json(root, "traffic", f"{cell['traffic']}.json")
    return cell, config, traffic


def load_driver(traffic: dict, root: str = ROOT) -> ModuleType:
    return _load_module(root, "drivers", traffic["driver"])


def load_reader(metric: str, root: str = ROOT
                ) -> Callable[[RunRecord], float | None]:
    return _load_module(root, "metrics", metric).read


def cell_metrics(bench: dict, workload: str, traced: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: the end-to-end ones
    untraced, the per-layer ones traced; an entry with ``workloads``
    applies to those cells only."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def load_peaks(kind: str, root: str = ROOT) -> dict:
    table = _load_json(root, "peaks.json")
    if kind not in table["devices"]:
        raise BenchError(
            f"no published peaks for device kind {kind!r} in peaks.json"
        )
    return table["devices"][kind]


def derive_seed(seed: int) -> int:
    """A 31-bit seed for every generator, from any whole number."""
    entropy = seed & ((1 << 64) - 1)  # negatives wrap, as in two's complement
    return int(np.random.SeedSequence(entropy).generate_state(1)[0] & 0x7FFFFFFF)


def span_factory(traced: bool):
    """A context manager per host step: a profiler annotation when traced,
    nothing otherwise."""
    if not traced:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def check_device(chips: int) -> dict:
    """The chip the cell needs, on the Pallas backend; never the CPU."""
    import jax

    from repro.kernels import BACKEND_ENV_VAR, default_backend

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    env = os.environ.get(BACKEND_ENV_VAR, "")
    if env not in ("", "pallas") or default_backend() != "pallas":
        raise BenchError(
            f"kernel backend is {default_backend()!r}, not 'pallas'"
        )
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()
    ]
    return int(max(peaks))


class CompileCounter:
    """Counts, from its creation on, the programs JAX builds (``built``)
    and those of them it compiled, not finding them in the persistent
    cache (``compiled``)."""

    BUILT = "/jax/compilation_cache/compile_requests_use_cache"
    COMPILED = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.built = self.compiled = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == self.BUILT:
            self.built += 1
        elif event == self.COMPILED:
            self.compiled += 1

    def __str__(self) -> str:
        return f"{self.built} built, {self.compiled} compiled"


def run_window(cell, seconds: float, span, *, first: int = 0,
               limit: int | None = None) -> tuple[list[float], float]:
    """Reports ``first``, ``first + 1``, ... back to back for ``seconds``,
    or until ``limit`` of them are done; (latencies, window seconds).
    The window closes when the last report that started in it ends."""
    lat = []
    with span("perfbench.window"):
        t0 = time.perf_counter()
        now = t0
        while now - t0 < seconds and (limit is None or len(lat) < limit):
            with span("perfbench.report"):
                cell.report(first + len(lat))
            end = time.perf_counter()
            lat.append(end - now)
            now = end
    return lat, now - t0


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache``; every program is
    kept, however fast it compiled, so a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python frames would swamp the host
    opts.host_tracer_level = 2
    return opts


def measure(cell, seconds: float, traced: int, span):
    """The window: (latencies, window_s, reduced trace or None).

    A traced run (``traced`` > 0) has the profiler on for its first
    ``traced`` reports only, so that the trace holds every event of them
    (a long trace loses its later events), and runs the rest of the window
    untraced."""
    if not traced:
        lat, window_s = run_window(cell, seconds, span)
        return lat, window_s, None
    import jax

    from perfbench import trace

    with tempfile.TemporaryDirectory(prefix="perfbench-trace-") as d:
        jax.profiler.start_trace(d, profiler_options=_trace_options())
        try:
            lat, window_s = run_window(cell, seconds, span, limit=traced)
        finally:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
        t1 = time.perf_counter()
        path = trace.find_xspace(d)
        try:
            reduced = trace.reduce(path)
        except trace.TraceIncomplete as e:
            raise BenchError(str(e)) from e
        print(f"trace: {len(lat)} reports, {os.path.getsize(path)} bytes, "
              f"written in {t1 - t0:.1f} s, reduced in "
              f"{time.perf_counter() - t1:.1f} s; device programs per "
              f"report {min(reduced.report_programs)} to "
              f"{max(reduced.report_programs)}", file=sys.stderr, flush=True)
    rest, rest_s = run_window(cell, seconds - window_s, span, first=len(lat))
    return lat + rest, window_s + rest_s, reduced


def result_line(correct, attempted, failed, metrics, device, compared,
                breakdown=None) -> str:
    out = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {
        c.name: {"value": c.value, "limit": c.limit} for c in compared
    }
    return json.dumps(out)


def execute(args, started: float, *, require_chip: bool = True,
            root: str = ROOT) -> str:
    """One run; returns the result line.  ``require_chip=False`` is for
    the tests, which drive the rest of a run on the CPU."""
    bench = load_benchmark(root)
    cell_entry, config, traffic = find_cell(bench, args.workload, root)
    traced = bool(args.trace)
    chosen = cell_metrics(bench, args.workload, traced)
    import jax

    if require_chip:
        device = check_device(int(cell_entry["chips"]))
    else:
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(jax.devices())}
    span = span_factory(traced)
    driver = load_driver(traffic, root)
    readers = {m["name"]: load_reader(m["name"], root) for m in chosen}
    peaks = load_peaks(device["kind"], root) if traced and require_chip else None

    compiles = CompileCounter()
    cell = driver.setup(config, traffic, derive_seed(args.seed), span)
    setup_s = time.time() - started
    in_setup = str(compiles)
    before = compiles.built
    lat, window_s, reduced = measure(
        cell, args.seconds,
        traffic.get("traced_reports", TRACED_REPORTS) if traced else 0, span)
    in_window = compiles.built - before
    device["memory_peak_bytes"] = memory_peak_bytes() if require_chip else 0
    record = RunRecord(setup_s, window_s, lat, cell.events_per_report,
                       cell.work, reduced, peaks)

    metrics = {}
    for m in chosen:
        value = readers[m["name"]](record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = None
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()

    compared, failed = cell.check(np.random.default_rng(derive_seed(args.seed) + 1))
    for line in cell.notes() + [
            f"programs in set-up: {in_setup}; built in the window: "
            f"{in_window}"]:
        print(line, file=sys.stderr, flush=True)
    correct = all(c.ok for c in compared)
    for c in compared:
        print(f"compare {c.name}: {c.value} (limit {c.limit})",
              file=sys.stderr, flush=True)
    return result_line(correct, len(lat), failed, metrics, device, compared,
                       breakdown)
