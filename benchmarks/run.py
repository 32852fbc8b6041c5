"""Report CLI — one module per paper table/figure plus the beyond-paper
traffic reports.  Prints a ``name,derived`` CSV of simulated statistics
(BT, area, power, latency, launch counts); it times nothing.  Speed is
measured on the chip by ``perfbench/`` alone.

  table1_bt        -> paper Table I   (BT per flit, 4 orderings, 2 data models)
  fig5_area        -> paper Fig. 5    (area breakdown, 4 designs, 2 sizes)
  fig7_power       -> paper Fig. 6/7  (link-related + PE power reductions)
  lenet_workload   -> paper §IV-B     (conv+pool platform, PSU in the loop)
  arch_bt          -> paper §V future work (transformer traffic BT)
  noc_bt           -> §V NoC fabric   (per-link BT across topologies/hops)
  dse_sweep        -> design-space Pareto fronts (area x BT x latency)
  codec_bt         -> ordering vs coding vs composed (repro.codec tables)
  model_traffic    -> captured real-model streams: per-scenario BT/power
                      campaign + trained-weight recalibration (§16)
  fleet_noc        -> fleet-scale serving fabric (§17): one-launch pin,
                      BT + contention latency on a 16x16 mesh of
                      multi-tenant decode flows

Usage: ``python -m benchmarks.run [--trace] [--activity] [module ...]``
runs the named modules in registry order (no names = all); ``--list``
prints the valid names.  Set REPRO_BENCH_TINY=1 to run each module at its
smoke-test shape (a module's optional ``TINY_KWARGS`` dict) — the CI
crash gate.

``--trace`` activates ``repro.obs`` around each module and writes a
Chrome/Perfetto-loadable ``TRACE_<module>.json`` to the current
directory: one top-level ``bench.module`` span per run with every probe
span (kernel dispatches, link stages, NoC/DSE launches) nested inside by
timestamp.  Its ``metadata`` carries the run's provenance (git SHA, ISO
timestamp, jax version, resolved kernel backend, shapes) and the trace's
span coverage of the module's run.  Load it at https://ui.perfetto.dev or
chrome://tracing.

``--activity`` (or REPRO_BENCH_ACTIVITY=1) turns on wire-level
switching-activity measurement in the modules that support it
(``noc_bt``, ``codec_bt``, DESIGN.md §15): hottest-wire report rows plus
an ``ACTIVITY_<module>.saif`` (standard backward SAIF for EDA power
flows) and ``ACTIVITY_<module>_wires.csv`` per-wire heatmap in the
current directory.
"""

from __future__ import annotations

import datetime
import importlib
import os
import subprocess
import sys
import time

# The registry: ``--list`` order and run order.
MODULES = (
    "table1_bt",
    "fig5_area",
    "fig7_power",
    "lenet_workload",
    "arch_bt",
    "noc_bt",
    "dse_sweep",
    "codec_bt",
    "model_traffic",
    "fleet_noc",
)


def _git_sha() -> str:
    """The repo HEAD the report was made at ('unknown' off-git)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if out.returncode == 0 and out.stdout.strip():
            return out.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        pass
    return "unknown"


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for a CLI run.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself); otherwise the cache lives at the fixed path
    ``<repo>/.jax_cache``, so every run from this checkout finds what an
    earlier one compiled.  Entry points call this; importing ``repro``
    never does (the tests run without a cache).  Returns the directory.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main() -> None:
    args = sys.argv[1:]
    emit_trace = "--trace" in args
    if "--activity" in args:
        # modules read the env (same pattern as REPRO_BENCH_TINY), so the
        # flag and the variable are interchangeable
        os.environ["REPRO_BENCH_ACTIVITY"] = "1"
    args = [a for a in args if a not in ("--trace", "--activity")]
    if "--list" in args:
        for name in MODULES:
            print(name)
        return
    names = dict.fromkeys(args)  # dedup, keep request order for the error
    unknown = [a for a in names if a not in MODULES]
    if unknown:
        listed = ", ".join(repr(a) for a in unknown)
        raise SystemExit(
            f"unknown benchmark module{'s' if len(unknown) > 1 else ''} "
            f"{listed}; valid names: {', '.join(MODULES)}"
        )

    import jax

    from repro.kernels import default_backend

    enable_compilation_cache()
    tiny = os.environ.get("REPRO_BENCH_TINY", "") not in ("", "0")
    git_sha = _git_sha() if emit_trace else None
    print("name,derived")
    failures = 0
    for name in MODULES:
        if names and name not in names:
            continue
        mod = importlib.import_module(f".{name}", __package__)
        kwargs = getattr(mod, "TINY_KWARGS", {}) if tiny else {}
        try:
            if emit_trace:
                from repro import _obs_hooks, obs

                tracer = obs.Tracer(process_name=f"bench.{name}")
                t0 = time.monotonic()
                with obs.tracing(tracer), obs.collect():
                    with _obs_hooks.span("bench.module", module=name):
                        rows = mod.run(**kwargs)
                dt = time.monotonic() - t0
            else:
                rows = mod.run(**kwargs)
        except Exception as e:  # keep the harness running; report the failure
            print(f'{name},"FAILED: {type(e).__name__}: {e}"')
            failures += 1
            continue
        for rname, derived in rows:
            print(f'{rname},"{derived}"')
        if emit_trace:
            # the bench.module span wraps the whole run, so its duration
            # over the run's wall time is the trace's span coverage (the
            # DESIGN.md §14 >=95% target; the remainder is harness I/O)
            coverage = min(
                1.0, tracer.span_seconds("bench.module") / max(dt, 1e-9)
            )
            tracer.write(f"TRACE_{name}.json", metadata={
                "module": name,
                "backend": default_backend(),
                "platform": jax.default_backend(),
                "tiny": tiny,
                "kwargs": kwargs,
                "git_sha": git_sha,
                "timestamp": datetime.datetime.now(
                    datetime.timezone.utc
                ).isoformat(timespec="seconds"),
                "jax_version": jax.__version__,
                "span_coverage": round(coverage, 4),
            })
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
