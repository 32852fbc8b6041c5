"""Each driver's report at a tiny size on the CPU (``compiled`` backend)
matches ``perfbench.reference``, and each control fails the comparison."""

import contextlib
import json
import os

import numpy as np
import pytest

from perfbench.drivers import noc_fleet, paper_conv, weight_grid

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_DECODER = {
    "hidden_size": 128, "num_attention_heads": 2, "num_key_value_heads": 1,
    "intermediate_size": 256, "num_hidden_layers": 3,
    "initializer_range": 0.02, "torch_dtype": "bfloat16",
}


def _traffic(name, **changes):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return {**json.load(f), **changes}


def _span(name):
    return contextlib.nullcontext()


def test_weight_grid_matches_reference_and_control_fails():
    cell = weight_grid.setup(
        TINY_DECODER, _traffic("decode-grid", chunk_packets=256), 7, _span)
    for i in range(4):
        cell.report(i)
    compared, failed = cell.check(np.random.default_rng(0))
    assert [c.name for c in compared] == [
        "codes_differ", "scales_differ", "bt_entries_differ"]
    assert all(c.value == 0 for c in compared) and failed == 0
    assert len(cell.weights) == 2  # the other layers were dropped
    control, c_failed = cell.control()
    # bfloat16 arithmetic on bfloat16 weights can keep the scales: the
    # codes and the BT it prices give it away
    values = {c.name: c.value for c in control}
    assert values["codes_differ"] > 0 and values["bt_entries_differ"] > 0
    assert c_failed == 2


def test_paper_conv_matches_reference_and_control_fails():
    config = json.load(open(os.path.join(
        HERE, "configs", "lenet5-conv-paper.json")))
    config["train_steps"] = 10
    cell = paper_conv.setup(config, _traffic("paper-conv", captures=2), 7,
                            _span)
    # the issue's count: 128 + 39 packets apart, 256 paired, 167 in the grid
    assert cell.events_per_report == 7748
    for i in range(3):
        cell.report(i)
    compared, failed = cell.check(None)
    assert [(c.name, c.value) for c in compared] == [
        ("values_differ", 0), ("sent_entries_differ", 0)]
    assert failed == 0
    control, c_failed = cell.control()
    # the control's numbers and its orders and images, for each capture
    assert all(c.value > c.limit for c in control) and c_failed == 4
    assert any(line.startswith("paper acc:") for line in cell.notes())


@pytest.mark.parametrize("orderings", [["none", "acc", "app"]])
def test_noc_fleet_matches_reference(orderings):
    traffic = _traffic("fleet16", rows=4, cols=4, users=4, layers=2,
                       shards=2, orderings=orderings)
    cell = noc_fleet.setup(TINY_DECODER, traffic, 3, _span)
    for i in range(2):
        cell.report(i)
    compared, failed = cell.check(np.random.default_rng(1))
    assert [(c.name, c.value) for c in compared] == [("links_differ", 0)]
    assert failed == 0
    assert cell.events_per_report == 3 * 4 * 2 * 2 * 2 * 4


def test_fleet_routes_are_xy():
    # column first, then row, on a 4x4 mesh: 1 -> 14 is (0,1) -> (3,2)
    assert noc_fleet.xy_links(4, 1, 14) == [(1, 2), (2, 6), (6, 10), (10, 14)]
