"""The repro.obs observability subsystem (DESIGN.md §14).

The load-bearing claim is ZERO cost when disabled: production modules
import only ``repro._obs_hooks`` (a None test per probe, fired outside
any traced computation), so every kernel entry point's traced jaxpr is
byte-identical whether ``repro.obs`` is absent from the process, imported
but inactive, or actively collecting.  The rest pins the probe
vocabulary, the metrics JSON round-trip, the per-link report against
``NocReport``, the Chrome trace schema, and the report CLI's
``--trace`` artifact.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import _obs_hooks, obs
from repro.kernels import CodecVariant, bt_count, bt_count_axes
from repro.link import LinkSpec, TxPipeline
from repro.noc import TrafficFlow, simulate_noc
from repro.noc.topology import mesh

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CFG = (CodecVariant("none", None, False, "none", None),
        CodecVariant("acc", None, False, "bus_invert", 4))


def _packets(p=8, elems=32, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 255, (p, elems), dtype=np.uint8))


def _input_spec():
    return LinkSpec(width_bits=64, input_lanes=8, weight_lanes=0)


def _jaxprs():
    """Traced-jaxpr strings of the probed public entry points."""
    x = _packets()
    pipe = TxPipeline(_input_spec(), interpret=True)
    return {
        "bt_count": str(jax.make_jaxpr(
            lambda a: bt_count(a, interpret=True))(x)),
        "bt_count_axes": str(jax.make_jaxpr(
            lambda a: bt_count_axes(
                a[None], None, configs=_CFG, width=8, input_lanes=8,
                interpret=True,
            ))(x)),
        "tx_run": str(jax.make_jaxpr(
            lambda a: pipe.run(a).bt_input)(x)),
    }


# --------------------------------------------- zero cost when disabled


def test_jaxpr_identical_with_obs_absent_vs_imported():
    """In a fresh process: production imports never pull in repro.obs,
    and importing + activating it leaves every traced jaxpr
    byte-identical (the tentpole claim)."""
    script = """
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import CodecVariant, bt_count, bt_count_axes
from repro.link import LinkSpec, TxPipeline

assert "repro.obs" not in sys.modules, "production code imported repro.obs"

x = jnp.asarray(
    np.random.default_rng(0).integers(0, 255, (8, 32), dtype=np.uint8)
)
cfg = (CodecVariant("none", None, False, "none", None),
       CodecVariant("acc", None, False, "bus_invert", 4))
pipe = TxPipeline(
    LinkSpec(width_bits=64, input_lanes=8, weight_lanes=0), interpret=True
)

def jaxprs():
    return {
        "bt_count": str(jax.make_jaxpr(
            lambda a: bt_count(a, interpret=True))(x)),
        "bt_count_axes": str(jax.make_jaxpr(
            lambda a: bt_count_axes(
                a[None], None, configs=cfg, width=8, input_lanes=8,
                interpret=True,
            ))(x)),
        "tx_run": str(jax.make_jaxpr(lambda a: pipe.run(a).bt_input)(x)),
    }

before = jaxprs()
assert "repro.obs" not in sys.modules, "tracing imported repro.obs"
from repro import obs
mid = jaxprs()
with obs.collect(), obs.tracing():
    after = jaxprs()
assert before == mid, "importing repro.obs changed a jaxpr"
assert before == after, "activating repro.obs changed a jaxpr"
print("JAXPR-IDENTITY-OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO, "src"), _REPO]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=_REPO, timeout=600,
    )
    assert out.returncode == 0, out.stderr
    assert "JAXPR-IDENTITY-OK" in out.stdout


def test_jaxpr_identical_inactive_vs_collecting():
    before = _jaxprs()
    with obs.collect(), obs.tracing():
        during = _jaxprs()
    after = _jaxprs()
    assert before == during == after


def test_hooks_inactive_by_default():
    assert _obs_hooks.SINK is None or obs.active_registries()
    with obs.collect():
        assert _obs_hooks.active()
        assert _obs_hooks.SINK is not None
    assert not _obs_hooks.active()
    # the null span is a no-op context manager
    with _obs_hooks.span("kernel.dispatch", entry="x"):
        pass
    _obs_hooks.event("noc.link", link=0)  # swallowed


# ------------------------------------------------ the profiler consumer


def test_jaxpr_identical_under_profiling(tmp_path):
    """Spans entered as profiler annotations, in a live profiler session,
    leave every traced jaxpr byte-identical."""
    before = _jaxprs()
    with jax.profiler.trace(str(tmp_path)), obs.profiling():
        during = _jaxprs()
    assert before == during == _jaxprs()


def test_profiling_alone_is_not_active():
    """The profiler consumer installs the sink but keeps ``active()``
    False, so the telemetry it guards stays off; spans still count on a
    registry collecting beside it, and no span is timed on the host."""
    x = _packets()
    with obs.profiling():
        assert _obs_hooks.SINK is not None
        assert not _obs_hooks.active()
        _obs_hooks.event("noc.link", link=0)  # no collector: swallowed
        with obs.collect() as reg:
            assert _obs_hooks.active()
            TxPipeline(_input_spec(), interpret=True).measure(x)
        assert not _obs_hooks.active()
    assert _obs_hooks.SINK is None
    assert reg.value("link.readback.calls") == 1
    assert reg.value("link.stage.calls", stage="encode") == 1
    assert reg.to_dict()["histograms"] == []


def test_profiling_nests_and_restores_the_slot():
    with obs.profiling():
        with obs.profiling():
            assert _obs_hooks.SINK is not None
        assert _obs_hooks.SINK is not None
    assert _obs_hooks.SINK is None
    with pytest.raises(RuntimeError, match="inside a span"):
        with obs.profiling(), obs.profiling():
            with _obs_hooks.span("link.tx", path="fused"):
                raise RuntimeError("inside a span")
    assert _obs_hooks.SINK is None
    with obs.collect():
        with pytest.raises(RuntimeError):
            with obs.profiling():
                raise RuntimeError
        assert _obs_hooks.SINK is not None and _obs_hooks.active()
    assert _obs_hooks.SINK is None


# --------------------------------------------------- probe vocabulary


def test_kernel_dispatch_counters():
    x = _packets()
    with obs.collect() as reg:
        bt_count(x, backend="interpret")
        bt_count(x, backend="interpret")
        bt_count(x, backend="compiled")
    assert reg.value(
        "kernel.dispatch.calls", entry="bt_count", backend="interpret") == 2
    assert reg.value(
        "kernel.dispatch.calls", entry="bt_count", backend="compiled") == 1
    # pallas launch accounting: interpret dispatches launch, compiled don't
    assert reg.value(
        "kernel.pallas_launches", entry="bt_count", backend="interpret") == 2
    assert reg.value(
        "kernel.pallas_launches", entry="bt_count", backend="compiled") == 0


def test_link_pipeline_probes_and_report_counters():
    x = _packets()
    pipe = TxPipeline(_input_spec(), interpret=True)
    with obs.collect() as reg:
        rep = pipe.measure(x, name="s0")
    assert reg.value("link.tx.calls", path="fused", key="acc",
                     codec="none") == 1
    assert reg.value("link.bt", side="input", stream="s0") == rep.input_bt
    assert reg.value("link.flits", stream="s0") == rep.num_flits
    # staged path fires the stage spans
    staged = TxPipeline(
        LinkSpec(width_bits=64, input_lanes=8, weight_lanes=0,
                 key="column_major"),
        interpret=True,
    )
    with obs.collect() as reg2:
        staged.measure(x, name="s1")
    assert reg2.value("link.tx.calls", path="staged", key="column_major",
                      codec="none") == 1
    for stage in ("order", "assemble", "bt"):
        assert reg2.value("link.stage.calls", stage=stage) == 1


def test_nested_collect_scopes_both_see_firings():
    x = _packets()
    with obs.collect() as outer:
        bt_count(x, backend="interpret")
        with obs.collect() as inner:
            bt_count(x, backend="interpret")
    assert outer.value("kernel.dispatch.calls", entry="bt_count",
                       backend="interpret") == 2
    assert inner.value("kernel.dispatch.calls", entry="bt_count",
                       backend="interpret") == 1


# ------------------------------------------- NoC per-link report layer


def _noc_run():
    x = _packets(elems=_input_spec().elems_per_packet, seed=3)
    flows = [TrafficFlow("f0", 0, (3,), x), TrafficFlow("f1", 1, (2,), x)]
    with obs.collect() as reg:
        rep = simulate_noc(
            mesh(2, 2), flows, _input_spec(), interpret=True
        )
    return reg, rep


def test_noc_link_counters_match_report():
    reg, rep = _noc_run()
    table = obs.link_table(reg)
    assert len(table) == rep.active_links
    by_id = {s.link: s for s in rep.links}
    for row in table:
        s = by_id[row["link"]]
        assert (row["src"], row["dst"]) == (s.src, s.dst)
        assert row["bt_input"] == s.bt_input
        assert row["bt_weight"] == s.bt_weight
        assert row["aux_bt"] == s.bt_aux
        assert row["gross_bt"] == s.gross_bt
        assert row["num_flits"] == s.num_flits
        assert row["energy_pj"] == pytest.approx(s.energy_pj, abs=0.01)
    assert sum(r["gross_bt"] for r in table) == rep.gross_bt


def test_noc_host_phase_spans_under_profiling(monkeypatch):
    """A profiled ``simulate_noc`` enters one profiler annotation per host
    phase, nested in ``noc.simulate``: planning, stacking, the expansion,
    the measurement and its read, and the per-link roll-up, in order."""
    from repro.obs import probes

    entered = []

    class Recorder:
        def __init__(self, name, **labels):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(probes, "TraceAnnotation", Recorder)
    x = _packets(elems=_input_spec().elems_per_packet, seed=3)
    flows = [TrafficFlow("f0", 0, (3,), x), TrafficFlow("f1", 1, (2,), x)]
    with obs.profiling():
        simulate_noc(mesh(2, 2), flows, _input_spec(), backend="compiled")
    noc = [k for k in entered if k.startswith("noc.")]
    assert noc == ["noc.simulate", "noc.plan", "noc.stack", "noc.expand",
                   "noc.measure", "noc.rollup"]
    assert "kernel.dispatch" in entered


def test_top_links_ordering_and_format(tmp_path):
    reg, rep = _noc_run()
    top = obs.top_links(reg, 2)
    assert len(top) == min(2, rep.active_links)
    gross = [r["gross_bt"] for r in obs.link_table(reg)]
    assert top[0]["gross_bt"] == max(gross)
    assert [r["gross_bt"] for r in top] == sorted(
        [r["gross_bt"] for r in top], reverse=True
    )
    text = obs.format_links(top)
    assert "gross BT" in text and str(top[0]["gross_bt"]) in text
    # heatmap CSV artifact: header + one row per link
    path = tmp_path / "links.csv"
    rows = obs.write_links_csv(str(path), reg)
    lines = path.read_text().strip().splitlines()
    assert lines[0].split(",") == list(obs.report.LINK_FIELDS)
    assert len(lines) == 1 + len(rows)


def test_metrics_json_round_trip(tmp_path):
    reg, _ = _noc_run()
    path = tmp_path / "metrics.json"
    doc = obs.write_metrics_json(str(path), reg)
    assert doc["links"] == obs.link_table(reg)
    reg2 = obs.read_metrics_json(str(path))
    assert reg2.to_dict() == reg.to_dict()
    assert obs.link_table(reg2) == obs.link_table(reg)


# ------------------------------------------------------- trace schema


def test_tracer_chrome_schema(tmp_path):
    x = _packets()
    tracer = obs.Tracer()
    with obs.tracing(tracer):
        with _obs_hooks.span("bench.module", module="demo"):
            bt_count(x, backend="interpret")
        _obs_hooks.event("noc.link", link=0, shape=(2, 3))
    doc = tracer.to_chrome(metadata={"git_sha": "abc"})
    json.dumps(doc)  # JSON-safe throughout (tuples coerced)
    assert doc["metadata"] == {"git_sha": "abc"}
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert {"bench.module", "kernel.dispatch"} <= names
    for e in spans:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                "args"} <= set(e)
        assert e["dur"] >= 0
    outer = next(e for e in spans if e["name"] == "bench.module")
    inner = next(e for e in spans if e["name"] == "kernel.dispatch")
    # nested purely by timestamp containment
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0
    assert tracer.span_seconds("bench.module") >= tracer.span_seconds(
        "kernel.dispatch"
    )
    instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert instants and instants[0]["args"]["shape"] == [2, 3]
    out = tracer.write(str(tmp_path / "t.json"))
    assert json.load(open(tmp_path / "t.json")) == out


# ------------------------------------------- bench --trace end to end


@pytest.mark.slow
def test_bench_trace_artifact(tmp_path):
    """One tiny dse_sweep run under --trace: the TRACE json is
    Chrome-loadable, carries the run's provenance in its metadata, and
    covers >=95% of the module's run with spans (the DESIGN.md §14
    acceptance bar)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(_REPO, "src"), _REPO])
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["REPRO_BENCH_TINY"] = "1"
    env["REPRO_DSE_ARTIFACT"] = str(tmp_path / "dse_front.json")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--trace", "dse_sweep"],
        capture_output=True, text=True, env=env, cwd=str(tmp_path),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr
    lines = out.stdout.splitlines()
    assert lines[0] == "name,derived"
    assert any(line.startswith("dse/obs/") for line in lines[1:])

    trace = json.load(open(tmp_path / "TRACE_dse_sweep.json"))
    assert isinstance(trace["traceEvents"], list) and trace["traceEvents"]
    meta = trace["metadata"]
    for field in ("git_sha", "timestamp", "jax_version"):
        assert meta.get(field), f"missing provenance field {field!r}"
    assert "T" in meta["timestamp"]  # ISO-8601
    assert meta["module"] == "dse_sweep"
    assert meta["span_coverage"] >= 0.95
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {"bench.module", "kernel.dispatch", "dse.measure"} <= {
        e["name"] for e in spans
    }
    outer = next(e for e in spans if e["name"] == "bench.module")
    assert outer["dur"] / 1e6 >= 0.95 * sum(
        e["dur"] for e in spans if e["name"] == "dse.measure"
    ) / 1e6


# ------------------------------- wire-activity report layer (§15)


def _noc_activity_sim(seed=3):
    x = _packets(elems=_input_spec().elems_per_packet, seed=seed)
    flows = [TrafficFlow("f0", 0, (3,), x), TrafficFlow("f1", 1, (2,), x)]
    return simulate_noc(
        mesh(2, 2), flows, _input_spec(), interpret=True,
        activity_windows=4,
    )


def _noc_activity_run(seed=3):
    with obs.collect() as reg:
        rep = _noc_activity_sim(seed=seed)
    return reg, rep


def test_activity_counters_match_noc_report():
    reg, rep = _noc_activity_run()
    table = obs.activity_table(reg)
    assert len(table) == rep.active_links
    profs = obs.profiles_from_noc(rep)
    by_id = {s.link: (s, p) for s, p in zip(rep.links, profs)}
    for row in table:
        s, p = by_id[row["link"]]
        assert (row["src"], row["dst"]) == (s.src, s.dst)
        assert row["toggles"] == s.gross_bt == p.gross_bt
        assert row["windows"] == p.num_windows
        assert row["wire_max"] == int(p.per_wire.max())
        hot_name, hot_tog = p.hottest_wires(1)[0]
        assert (row["hot_wire"], row["hot_wire_toggles"]) == (
            hot_name, hot_tog
        )
    # top_wires descends by toggles and agrees with the table rows
    top = obs.top_wires(reg, 3)
    assert [r["toggles"] for r in top] == sorted(
        [r["toggles"] for r in top], reverse=True
    )
    assert top[0]["toggles"] == max(r["hot_wire_toggles"] for r in table)


def test_report_tables_empty_registry():
    reg = obs.Registry()
    assert obs.link_table(reg) == []
    assert obs.activity_table(reg) == []
    assert obs.top_links(reg) == []
    assert obs.top_wires(reg) == []
    doc = obs.metrics_dict(reg)
    assert doc["links"] == []
    assert "activity" not in doc  # absent, not empty — PR 7 artifacts
    # byte-identical for runs without wire activity


def test_report_csvs_empty_registry(tmp_path):
    reg = obs.Registry()
    links = tmp_path / "links.csv"
    act = tmp_path / "activity.csv"
    assert obs.write_links_csv(str(links), reg) == []
    assert obs.write_activity_csv(str(act), reg) == []
    # header-only CSVs, parseable with the documented field lists
    assert links.read_text().strip().split(",") == list(
        obs.report.LINK_FIELDS
    )
    assert act.read_text().strip().split(",") == list(
        obs.report.ACTIVITY_FIELDS
    )


def test_activity_accumulates_across_runs():
    """A link seen by two simulate_noc runs inside one collect scope
    reports its total activity — same accumulation rule as link_table."""
    reg1, rep = _noc_activity_run()
    single = obs.activity_table(reg1)
    with obs.collect() as reg2:
        _noc_activity_sim()
        _noc_activity_sim()
    double = obs.activity_table(reg2)
    assert len(double) == len(single)
    for a, b in zip(single, double):
        assert (a["link"], a["src"], a["dst"]) == (
            b["link"], b["src"], b["dst"]
        )
        assert b["toggles"] == 2 * a["toggles"]
        assert b["windows"] == 2 * a["windows"]
        assert b["wire_max"] == a["wire_max"]  # histogram max, not a sum
        # the hot-wire counter is keyed by wire name, so the same wire
        # winning both runs accumulates like every other counter
        assert b["hot_wire"] == a["hot_wire"]
        assert b["hot_wire_toggles"] == 2 * a["hot_wire_toggles"]
    doc = obs.metrics_dict(reg2)
    assert doc["activity"] == double


def test_link_table_missing_energy_counter():
    """A registry populated without the energy counter (older artifact,
    partial collection) still renders: energy reads as 0, not a crash."""
    reg = obs.Registry()
    lab = {"link": 7, "src": 0, "dst": 1}
    reg.counter("noc.link.bt", side="input", **lab).inc(30)
    reg.counter("noc.link.bt", side="weight", **lab).inc(12)
    reg.counter("noc.link.flits", **lab).inc(6)
    (row,) = obs.link_table(reg)
    assert row["gross_bt"] == 42 and row["aux_bt"] == 0
    assert row["energy_pj"] == 0
    assert row["bt_per_flit"] == 7.0
    assert obs.top_links(reg) == [row]


def test_probe_kinds_match_design_table():
    """DESIGN.md §14's vocabulary table and obs.PROBE_KINDS must not
    drift — adding a probe point means updating both."""
    import re

    text = open(os.path.join(_REPO, "DESIGN.md")).read()
    documented = {
        m.group(1): m.group(2)
        for m in re.finditer(
            r"^\| `([a-z]+\.[a-z_]+)`\s*\| (span|event)\s*\|", text, re.M
        )
    }
    assert documented == obs.PROBE_KINDS
