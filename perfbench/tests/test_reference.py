"""The benchmark's references and work counts, on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference as ref
from perfbench import work


@pytest.mark.parametrize("lanes,partition", [(16, 4), (16, None), (8, 2)])
def test_bus_invert_parity_is_the_recurrence(lanes, partition):
    rng = np.random.default_rng(lanes + (partition or 0))
    data = rng.integers(0, 256, (300, lanes))
    # ties and repeats: every distance, including exactly half a partition
    data[100:140] = data[99]
    data[140:160] = data[140:160] ^ 0x0F
    image = jnp.asarray(data.T)  # (lanes, T)
    wire_s, inv_s = ref.bus_invert_scan(image, partition)
    wire_p, inv_p = ref.bus_invert_parity(image, partition)
    np.testing.assert_array_equal(np.asarray(wire_p), np.asarray(wire_s))
    np.testing.assert_array_equal(np.asarray(inv_p), np.asarray(inv_s))


def test_stream_bt_by_hand():
    # two 4-byte packets on 2 lanes: lane-major packing, flits of 2 bytes
    pk = jnp.asarray([[0x00, 0x01, 0xFF, 0x0F], [0x03, 0x00, 0x00, 0x00]])
    rows = ref.lane_flits(pk, 2)
    np.testing.assert_array_equal(
        np.asarray(rows).T,
        [[0x00, 0xFF], [0x01, 0x0F], [0x03, 0x00], [0x00, 0x00]])
    bt = np.asarray(ref.stream_bt(pk, (ref.Design(),), 2))
    # 1+4, then 1+4, then 2+0
    assert bt.tolist() == [[12, 0]]
    # ACC sends each packet's bytes in ascending '1'-bit count, stably
    order = np.asarray(ref.transmit_order(pk, "acc", None))
    assert order.tolist() == [[0, 1, 3, 2], [1, 2, 3, 0]]


def test_quantize_blocks_control_differs():
    x = 0.02 * jax.random.normal(jax.random.key(0), (4096,), jnp.float32)
    q, s = ref.quantize_blocks(x)
    qc, sc = ref.quantize_blocks(x, 256, jnp.bfloat16)
    assert q.dtype == jnp.int8 and s.shape == (16,)
    np.testing.assert_allclose(np.asarray(s), np.abs(np.asarray(x)).reshape(16, -1).max(1) / 127, rtol=1e-6)
    assert int(jnp.sum(q != qc)) > 0


def test_decode_grid_work_counts():
    values = 62_914_560  # one internlm2-1.8b layer's seven projections
    assert values // 64 == 983_040
    assert work.flit_rows(values, 64, 16) == 3_932_160
    assert work.stream_events(values, 64, 16, 6) == 23_592_960
    assert values // 64 == 15 * 65_536  # the cell's 15 chunks
    assert work.axes_wire_bytes(values, 1, 6) == 62_914_560 + 72
    with pytest.raises(ValueError):
        work.flit_rows(64, 64, 12)
