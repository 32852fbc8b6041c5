"""Ordering strategies + link framing + BT accounting (paper Table I setup)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.core import (
    LinkConfig,
    LinkPowerModel,
    bit_transitions,
    bt_report,
    make_order,
    measure,
    order_packets,
    pack_to_flits,
    paired_stream,
)


def rand_packets(p, n, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 256, (p, n), dtype=np.uint8))


def test_order_insensitivity_of_mac():
    """THE enabling property (paper §I): reordering (input, weight) pairs
    does not change the accumulated dot product — exactly, in integers."""
    inp = rand_packets(5, 32, 1)
    wgt = rand_packets(5, 32, 2)
    for strat in ("none", "column_major", "acc", "app"):
        oi, ow = order_packets(strat, inp, wgt, lanes=8)
        before = (inp.astype(jnp.int32) * wgt.astype(jnp.int32)).sum(-1)
        after = (oi.astype(jnp.int32) * ow.astype(jnp.int32)).sum(-1)
        np.testing.assert_array_equal(np.asarray(before), np.asarray(after))


@given(st.integers(0, 2**32 - 1))
def test_orders_are_permutations(seed):
    inp = rand_packets(3, 32, seed)
    for strat in ("none", "column_major", "acc", "app"):
        order = np.asarray(make_order(strat, inp, lanes=8))
        for row in order:
            assert sorted(row.tolist()) == list(range(32))


def test_column_major_is_transpose():
    inp = jnp.arange(32, dtype=jnp.uint8)[None]
    order = np.asarray(make_order("column_major", inp, lanes=8))[0]
    want = np.arange(32).reshape(4, 8).T.reshape(-1)
    np.testing.assert_array_equal(order, want)


def test_bit_transitions_manual():
    s = jnp.asarray([[0x00, 0xFF], [0xFF, 0xFF], [0x0F, 0xF0]], jnp.uint8)
    # boundaries: (00->FF: 8) + (FF->FF: 0) = 8 ; (FF->FF:0)+(FF->F0:... )
    # lane0: 00->FF (8), FF->0F (4+4? 0xFF^0x0F=0xF0 ->4). lane1: FF->FF(0), FF->F0 (0x0F ->4)
    assert int(bit_transitions(s)) == 8 + 4 + 0 + 4


def test_pack_lane_vs_row():
    v = jnp.arange(32, dtype=jnp.uint8)[None]
    row = np.asarray(pack_to_flits(v, 8, "row"))[0]
    lane = np.asarray(pack_to_flits(v, 8, "lane"))[0]
    np.testing.assert_array_equal(row[0], np.arange(8))
    np.testing.assert_array_equal(lane[:, 0], np.arange(4))  # lane-contiguous


def test_paired_stream_shape_and_split():
    cfg = LinkConfig()
    inp, wgt = rand_packets(10, 32, 3), rand_packets(10, 32, 4)
    s = paired_stream(inp, wgt, cfg, "acc")
    assert s.shape == (40, 16)  # 10 packets x 4 flits x 16 bytes
    rep = bt_report(s, cfg.input_lanes)
    assert float(rep.overall_bt_per_flit) == pytest.approx(
        float(rep.input_bt_per_flit) + float(rep.weight_bt_per_flit)
    )


def test_acc_reduces_bt_on_uniform_paired_traffic():
    """Even on uniform random bytes, popcount ordering with lane packing
    reduces input-side BT (the E[HD | same popcount] = 3.5 < 4 effect —
    see benchmarks/datagen.py)."""
    inp, wgt = rand_packets(2000, 32, 5), rand_packets(2000, 32, 6)
    base = measure(inp, wgt, strategy="none")
    acc = measure(inp, wgt, strategy="acc")
    app = measure(inp, wgt, strategy="app")
    assert float(acc.input_bt_per_flit) < float(base.input_bt_per_flit) * 0.95
    assert float(app.input_bt_per_flit) < float(base.input_bt_per_flit) * 0.95
    # weights move with inputs -> weight side statistically unchanged
    assert abs(float(acc.weight_bt_per_flit) - float(base.weight_bt_per_flit)) < 1.0
    # APP retains most of ACC's reduction (paper: 95.5 %; uniform data is the
    # worst case for APP -- require >= 70 %)
    red_acc = 1 - float(acc.input_bt_per_flit) / float(base.input_bt_per_flit)
    red_app = 1 - float(app.input_bt_per_flit) / float(base.input_bt_per_flit)
    assert red_app > 0.7 * red_acc


def test_paired_stream_asymmetric_lanes():
    """Regression: input_lanes != weight_lanes used to crash in the flit
    concatenate (different flit counts per side).  The weight side now
    carries flits*weight_lanes bytes per packet, framed per flit."""
    cfg = LinkConfig(input_lanes=12, weight_lanes=4)
    assert cfg.elems_per_packet == 48 and cfg.weight_elems_per_packet == 16
    inp = rand_packets(10, 48, 7)
    wgt = rand_packets(10, 16, 8)
    s = paired_stream(inp, wgt, cfg, "acc", pack="row")
    assert s.shape == (40, 16)  # 10 packets x 4 flits x 16 bytes
    # per-flit split: first 12 lanes input bytes, last 4 weight bytes —
    # weight side framed natively (no input-derived permutation applies)
    w_half = np.asarray(s)[:, 12:].reshape(10, -1)
    np.testing.assert_array_equal(w_half, np.asarray(wgt))
    rep = bt_report(s, cfg.input_lanes)
    assert float(rep.overall_bt_per_flit) > 0


def test_paired_stream_asymmetric_wrong_payload_raises():
    cfg = LinkConfig(input_lanes=12, weight_lanes=4)
    inp, wgt = rand_packets(4, 48, 1), rand_packets(4, 48, 2)
    with pytest.raises(ValueError, match="weight payload"):
        paired_stream(inp, wgt, cfg, "none")


def test_power_model_transfer():
    m = LinkPowerModel()
    # calibrated to the paper's ACC point: 20.42 % BT -> 18.27 % power
    assert m.power_reduction(0.2042) == pytest.approx(0.1827, abs=1e-4)
    assert m.link_energy_pj(1000, 10) > m.link_energy_pj(500, 10)
