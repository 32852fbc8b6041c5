"""The harness on the CPU: cells, metrics and readers found by name, the
result line, a cell added as data alone, the runs that must fail, and the
faults the comparison must catch."""

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from perfbench import harness

ROOT = harness.ROOT
TINY_DECODER = {
    "hidden_size": 128, "num_attention_heads": 2, "num_key_value_heads": 1,
    "intermediate_size": 256, "num_hidden_layers": 3,
    "initializer_range": 0.02, "torch_dtype": "bfloat16",
}


def test_every_cell_and_metric_is_found_by_name():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        entry, config, traffic = harness.find_cell(bench, cell["name"])
        assert entry is cell and config and traffic["driver"]
        assert callable(harness.load_driver(traffic).setup)
        for traced in (False, True):
            for m in harness.cell_metrics(bench, cell["name"], traced):
                assert callable(harness.load_reader(m["name"]))
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    for name in names:
        assert os.path.isfile(os.path.join(ROOT, "perfbench", "metrics",
                                           f"{name}.py"))
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.load_peaks("TPU v9 imaginary")
    with pytest.raises(harness.BenchError):
        harness.find_cell(bench, "no-such-cell")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout with two tiny cells, one metric and one configuration
    added as files and entries only: no file of the harness is edited."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = harness.load_benchmark()
    with open(root / "perfbench" / "configs" / "tiny-decoder.json", "w") as f:
        json.dump(TINY_DECODER, f)
    lenet = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "lenet5-conv-paper.json")))
    with open(root / "perfbench" / "configs" / "tiny-lenet.json", "w") as f:
        json.dump({**lenet, "train_steps": 10}, f)
    grid = json.load(open(os.path.join(
        ROOT, "perfbench", "traffic", "decode-grid.json")))
    with open(root / "perfbench" / "traffic" / "tiny-grid.json", "w") as f:
        json.dump({**grid, "chunk_packets": 256}, f)
    paper = json.load(open(os.path.join(
        ROOT, "perfbench", "traffic", "paper-conv.json")))
    with open(root / "perfbench" / "traffic" / "tiny-paper.json", "w") as f:
        json.dump({**paper, "captures": 2}, f)
    with open(root / "perfbench" / "metrics" / "reports_done.py", "w") as f:
        f.write("def read(run):\n    return run.reports\n")
    bench["configs"] += [
        {"name": "tiny-decoder", "source": "test",
         "file": "perfbench/configs/tiny-decoder.json", "reduced": [],
         "why": "test"},
        {"name": "tiny-lenet", "source": "test",
         "file": "perfbench/configs/tiny-lenet.json", "reduced": [],
         "why": "test"},
    ]
    bench["workloads"] += [
        {"name": "tiny-grid", "config": "tiny-decoder",
         "traffic": "tiny-grid", "chips": 1, "why": "test"},
        {"name": "tiny-paper", "config": "tiny-lenet",
         "traffic": "tiny-paper", "chips": 1, "why": "test"},
    ]
    # a cell joins the metrics of the cell it resembles by entries alone
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", [])
        for old, new in (("internlm2-decode-grid", "tiny-grid"),
                         ("lenet-conv-paper", "tiny-paper")):
            if old in cells:
                cells.append(new)
    bench["end_to_end"].append(
        {"name": "reports_done", "unit": "count", "better": "higher",
         "bound": 0.01, "source": "host_clock", "workloads": ["tiny-grid"]})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(root)


def _run(root, workload, trace=0, seconds=0.5):
    args = argparse.Namespace(workload=workload, seed=2**31 + 12345,
                              seconds=seconds, trace=trace)
    line = harness.execute(args, time.time(), require_chip=False, root=root)
    return json.loads(line)


def test_added_cell_runs_and_reports_its_metrics(tiny_root):
    out = _run(tiny_root, "tiny-grid")
    assert list(out)[:5] == list(harness.RESULT_KEYS)
    assert list(out)[-1] == "compared"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"flit_rate", "setup_s", "reports_done"}
    assert out["metrics"]["reports_done"]["value"] == out["attempted"]
    assert out["metrics"]["flit_rate"]["unit"] == "Mflit/s"
    assert set(out["compared"]) == {
        "codes_differ", "scales_differ", "bt_entries_differ"}
    assert all(c["limit"] == 0 for c in out["compared"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])


def test_paper_cell_reports_its_tail(tiny_root):
    out = _run(tiny_root, "tiny-paper")
    assert out["correct"] is True
    assert set(out["metrics"]) == {"flit_rate.host", "report_p95_ms",
                                   "setup_s"}
    assert set(out["compared"]) == {"values_differ", "sent_entries_differ"}


def _alter_answer(bt_count_axes):
    def broken(*a, **kw):
        out = bt_count_axes(*a, **kw)
        return out.at[..., 0, 0].add(1)
    return broken


def _half_batch(bt_count_axes):
    def broken(inputs, *a, **kw):
        return bt_count_axes(inputs[:, : inputs.shape[1] // 2], *a, **kw)
    return broken


def _alter_code(quantize_egress):
    def broken(x, *a, **kw):
        q, s, n = quantize_egress(x, *a, **kw)
        return q.at[0].add(1), s, n
    return broken


@pytest.mark.parametrize("fault,target", [
    (_alter_answer, "bt_count_axes"),
    (_half_batch, "bt_count_axes"),
    (_alter_code, "quantize_egress"),
])
def test_grid_faults_come_out_incorrect(tiny_root, monkeypatch, fault,
                                        target):
    import repro.kernels

    monkeypatch.setattr(repro.kernels, target,
                        fault(getattr(repro.kernels, target)))
    out = _run(tiny_root, "tiny-grid")
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["compared"].values())


def _paper_alter_answer(monkeypatch):
    import repro.link

    measure = repro.link.TxPipeline.measure

    def broken(self, *a, **kw):
        r = measure(self, *a, **kw)
        return type(r)(**{**r.__dict__, "input_bt": r.input_bt + 1})
    monkeypatch.setattr(repro.link.TxPipeline, "measure", broken)


def _paper_half_batch(monkeypatch):
    import repro.dse

    evaluate_grid = repro.dse.evaluate_grid

    def broken(points, workload, **kw):
        half = workload._replace(streams=tuple(
            s[: max(1, s.shape[0] // 2)] for s in workload.streams))
        return evaluate_grid(points, half, **kw)
    monkeypatch.setattr(repro.dse, "evaluate_grid", broken)


def _paper_alter_stream(monkeypatch):
    import repro.link

    run = repro.link.TxPipeline.run

    def broken(self, *a, **kw):
        r = run(self, *a, **kw)
        return dataclasses.replace(r, stream=r.stream.at[-1, 0].add(1))
    monkeypatch.setattr(repro.link.TxPipeline, "run", broken)


@pytest.mark.parametrize("fault,number", [
    (_paper_alter_answer, "values_differ"),
    (_paper_half_batch, "values_differ"),
    (_paper_alter_stream, "sent_entries_differ"),
])
def test_paper_faults_come_out_incorrect(tiny_root, monkeypatch, fault,
                                         number):
    fault(monkeypatch)
    out = _run(tiny_root, "tiny-paper")
    assert out["correct"] is False
    assert out["compared"][number]["value"] > 0


def _command(cwd, extra_env=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(extra_env or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "internlm2-decode-grid", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_fails_without_a_chip():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_command_fails_without_the_program(tmp_path):
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = _command(str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_derive_seed_takes_any_whole_number():
    seeds = {harness.derive_seed(s) for s in (0, 1, 2**31 + 5, 2**40, -3)}
    assert len(seeds) == 5 and all(0 <= s < 2**31 for s in seeds)


def test_span_factory_is_silent_untraced():
    with harness.span_factory(False)("x") as s:
        assert s is None
    assert isinstance(harness.span_factory(False)("y"),
                      contextlib.nullcontext)


def test_metric_readers_from_a_record():
    rec = harness.RunRecord(setup_s=12.5, window_s=2.0,
                            latencies_s=[0.5, 0.5, 0.5, 0.5],
                            events_per_report=1_000_000, work={})
    read = {m: harness.load_reader(m) for m in
            ("setup_s", "flit_rate", "report_p95_ms")}
    assert read["setup_s"](rec) == 12.5
    assert read["flit_rate"](rec) == pytest.approx(2.0)
    assert read["report_p95_ms"](rec) == pytest.approx(500.0)
    assert harness.load_reader("flit_rate.host")(rec) == pytest.approx(2.0)
    for m in harness.load_benchmark()["per_layer"]:
        assert harness.load_reader(m["name"])(rec) is None  # untraced


class _Counting:
    """A cell whose reports take no time and are only counted."""

    def __init__(self):
        self.done = []

    def report(self, i):
        self.done.append(i)
        time.sleep(0.001)


def _fake_profiler(monkeypatch, reduced):
    import jax

    from perfbench import trace

    traced = {}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: traced.setdefault("dir", d))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(trace, "find_xspace", lambda d: __file__)
    monkeypatch.setattr(trace, "reduce", reduced)
    return traced


def test_traced_run_traces_its_first_reports_then_runs_on(monkeypatch):
    from perfbench import trace

    cell = _Counting()
    seen = []

    def reduced(path):
        seen.append(len(cell.done))
        return trace.Reduced(
            window_s=1.0, busy_s=0.5, reports=3, programs=6,
            report_programs=[2, 2, 2], kernels={}, ops={}, gaps={})

    _fake_profiler(monkeypatch, reduced)
    span = harness.span_factory(False)
    lat, window_s, red = harness.measure(cell, 0.2, 3, span)
    assert seen == [3]  # the trace ended after the third report
    assert len(lat) == len(cell.done) > 3  # and the window went on
    assert cell.done == list(range(len(cell.done)))
    assert red.reports == 3 and window_s >= 0.2


def test_traced_run_that_lost_events_prints_nothing(monkeypatch):
    from perfbench import trace

    def reduced(path):
        raise trace.TraceIncomplete("1 of 3 traced reports hold no program")

    _fake_profiler(monkeypatch, reduced)
    with pytest.raises(harness.BenchError, match="hold no program"):
        harness.measure(_Counting(), 0.1, 3, harness.span_factory(False))
