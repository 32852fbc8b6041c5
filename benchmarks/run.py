"""Benchmark harness — one module per paper table/figure plus the
beyond-paper traffic and roofline reports.  Prints ``name,us_per_call,
derived`` CSV (the harness contract).

  table1_bt        -> paper Table I   (BT per flit, 4 orderings, 2 data models)
  fig5_area        -> paper Fig. 5    (area breakdown, 4 designs, 2 sizes)
  fig7_power       -> paper Fig. 6/7  (link-related + PE power reductions)
  lenet_workload   -> paper §IV-B     (conv+pool platform, PSU in the loop)
  arch_bt          -> paper §V future work (transformer traffic BT)
  noc_bt           -> §V NoC fabric   (per-link BT across topologies/hops)
  dse_sweep        -> design-space Pareto fronts (area x BT x latency)
  codec_bt         -> ordering vs coding vs composed (repro.codec tables)
  kernel_bench     -> kernel microbenchmarks (per-backend wall rows)
  roofline_report  -> deliverable (g) tables from the dry-run records
  model_traffic    -> captured real-model streams: per-scenario BT/power
                      campaign + trained-weight recalibration (§16)
  fleet_noc        -> fleet-scale serving fabric (§17): batched expansion
                      vs legacy loop, one-launch pin, BT + contention
                      latency on a 16x16 mesh of multi-tenant decode flows

Usage: ``python -m benchmarks.run [--json] [--trace] [--activity]
[module ...]`` runs
the named modules in registry order (no names = all); ``--list`` prints
the valid names.  Set REPRO_BENCH_TINY=1 to run each module at its
smoke-test shape (a module's optional ``TINY_KWARGS`` dict) — the CI
benchmark smoke step.

``--json`` additionally writes one ``BENCH_<module>.json`` per module run
to the current directory: the CSV rows plus the resolved kernel backend
(DESIGN.md §13), the jax platform, the run kwargs (the shapes), the
module wall time, and run provenance (git SHA, ISO timestamp, jax
version).  CI uploads these as the persistent wall-clock trajectory and
``benchmarks.check_bench`` gates on them against the committed baseline
under ``benchmarks/trajectory/``.

``--trace`` activates ``repro.obs`` around each module and writes a
Chrome/Perfetto-loadable ``TRACE_<module>.json`` next to the bench JSON:
one top-level ``bench.module`` span per run with every probe span
(kernel dispatches, link stages, NoC/DSE launches) nested inside by
timestamp, plus the trace's span coverage of the module wall time in its
``metadata``.  Load it at https://ui.perfetto.dev or chrome://tracing.

``--activity`` (or REPRO_BENCH_ACTIVITY=1) turns on wire-level
switching-activity measurement in the modules that support it
(``noc_bt``, ``codec_bt``, DESIGN.md §15): hottest-wire report rows plus
an ``ACTIVITY_<module>.saif`` (standard backward SAIF for EDA power
flows) and ``ACTIVITY_<module>_wires.csv`` per-wire heatmap next to the
bench JSON.  CI's bench-smoke step uploads both with the trajectory.
"""

from __future__ import annotations

import datetime
import importlib
import json
import os
import subprocess
import sys
import time

# The registry: ``--list`` order, run order, and the set of JSON artifacts
# ``benchmarks.check_bench`` requires.
MODULES = (
    "table1_bt",
    "fig5_area",
    "fig7_power",
    "lenet_workload",
    "arch_bt",
    "noc_bt",
    "dse_sweep",
    "codec_bt",
    "kernel_bench",
    "roofline_report",
    "model_traffic",
    "fleet_noc",
)


def _write_json(name: str, payload: dict) -> None:
    with open(f"BENCH_{name}.json", "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def _git_sha() -> str:
    """The repo HEAD the numbers were measured at ('unknown' off-git)."""
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
    emit_json = "--json" in args
    emit_trace = "--trace" in args
    if "--activity" in args:
        # modules read the env (same pattern as REPRO_BENCH_TINY), so the
        # flag and the variable are interchangeable
        os.environ["REPRO_BENCH_ACTIVITY"] = "1"
    args = [a for a in args if a not in ("--json", "--trace", "--activity")]
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
    git_sha = _git_sha()
    print("name,us_per_call,derived")
    failures = 0
    for name in MODULES:
        if names and name not in names:
            continue
        mod = importlib.import_module(f".{name}", __package__)
        kwargs = getattr(mod, "TINY_KWARGS", {}) if tiny else {}
        meta = {
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
        }
        tracer = None
        t0 = time.monotonic()
        try:
            if emit_trace:
                from repro import _obs_hooks, obs

                tracer = obs.Tracer(process_name=f"bench.{name}")
                with obs.tracing(tracer), obs.collect():
                    with _obs_hooks.span("bench.module", module=name):
                        rows = mod.run(**kwargs)
            else:
                rows = mod.run(**kwargs)
        except Exception as e:  # keep the harness running; report the failure
            msg = f"FAILED: {type(e).__name__}: {e}"
            print(f"{name},0,{msg}")
            failures += 1
            if emit_json:
                _write_json(name, {
                    **meta,
                    "wall_s": round(time.monotonic() - t0, 3),
                    "failed": msg,
                    "rows": [],
                })
            continue
        dt = time.monotonic() - t0
        for rname, us, derived in rows:
            print(f'{rname},{us:.2f},"{derived}"')
        if emit_json:
            _write_json(name, {
                **meta,
                "wall_s": round(dt, 3),
                "rows": [
                    {"name": r, "us_per_call": round(us, 2), "derived": d}
                    for r, us, d in rows
                ],
            })
        if tracer is not None:
            # the bench.module span wraps the whole run, so its duration
            # over the module wall time is the trace's span coverage (the
            # DESIGN.md §14 >=95% target; the remainder is harness I/O)
            coverage = min(
                1.0, tracer.span_seconds("bench.module") / max(dt, 1e-9)
            )
            tracer.write(f"TRACE_{name}.json", metadata={
                **meta,
                "wall_s": round(dt, 3),
                "span_coverage": round(coverage, 4),
            })
        print(f"# {name} done in {dt:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
