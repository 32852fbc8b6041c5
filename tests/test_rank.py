"""The PSU's comparison-free counting-sort rank (``psu._rank_from_keys``).

The kernels compute each element's stable counting-sort address from
per-bucket one-hots, an exact prefix product and the histogram's prefix
sum, as the hardware does (DESIGN.md §2).  These cases pin it, bit for
bit, against two independent formulas: the pairwise comparison that the
kernels used before (kept here only as an oracle) and
``repro.core.sorting.counting_sort_ranks``.  Each case then runs through
``psu_sort`` (the sort kernel), ``psu_stream`` (the multi-axis block's
emitted rank) and ``bt_count_axes`` (the BT it prices), on the compiled
backend and the Pallas interpreter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bucket_map, counting_sort_ranks, popcount
from repro.kernels import CodecVariant, bt_count_axes, psu_sort, psu_stream
from repro.kernels.psu import _rank_from_keys
from repro.kernels.ref import bt_codecs_ref

WIDTH = 8
PACKETS = 12  # not a block multiple: a padded tail block
BLOCK = 8

# bytes of each popcount 0..8
_BY_POPCOUNT = [
    np.array([v for v in range(256) if bin(v).count("1") == c], np.uint8)
    for c in range(WIDTH + 1)
]

# (id, N, APP k or None for ACC, descending, byte pattern)
CASES = [
    ("random-n8", 8, None, False, "random"),
    ("random-n32", 32, None, False, "random"),
    ("random-n64", 64, None, False, "random"),
    ("all-equal-n64", 64, None, False, "equal"),
    ("every-bucket-n32", 32, None, False, "every"),
    ("descending-n64", 64, None, True, "every"),
    ("app-k1-n32", 32, 1, False, "random"),
    ("app-k9-n64", 64, WIDTH + 1, False, "every"),
    ("app-k4-descending-n8", 8, 4, True, "random"),
]


def _packets(n, pattern, seed):
    rng = np.random.default_rng(seed)
    if pattern == "random":
        return rng.integers(0, 256, (PACKETS, n), dtype=np.uint8)
    if pattern == "equal":  # every element has popcount 3
        return rng.choice(_BY_POPCOUNT[3], (PACKETS, n))
    # every popcount at least once per packet, shuffled, the rest random
    counts = np.concatenate(
        [np.tile(np.arange(WIDTH + 1), (PACKETS, 1)),
         rng.integers(0, WIDTH + 1, (PACKETS, n - WIDTH - 1))], axis=1
    )
    counts = rng.permuted(counts, axis=1)
    return np.vectorize(lambda c: rng.choice(_BY_POPCOUNT[c]))(counts).astype(
        np.uint8
    )


def _keys(x, k, descending):
    keys = popcount(jnp.asarray(x), WIDTH)
    nb = WIDTH + 1
    if k is not None:
        keys, nb = bucket_map(keys, WIDTH, k), k
    if descending:
        keys = (nb - 1) - keys
    return keys.astype(jnp.int32), nb


def _compare_rank(key):
    """The pairwise formula: #{j: key_j < key_i} + #{j < i: key_j == key_i}."""
    key = np.asarray(key)
    n = key.shape[-1]
    ki, kj = key[..., :, None], key[..., None, :]
    earlier = np.arange(n)[None, :] < np.arange(n)[:, None]
    return ((kj < ki) | ((kj == ki) & earlier)).sum(axis=-1).astype(np.int32)


@pytest.fixture(params=CASES, ids=[c[0] for c in CASES])
def case(request):
    name, n, k, descending, pattern = request.param
    x = _packets(n, pattern, seed=n + (k or 0) + 100 * descending)
    key, nb = _keys(x, k, descending)
    want = _compare_rank(key)
    np.testing.assert_array_equal(
        np.asarray(counting_sort_ranks(key, nb)), want
    )
    if pattern == "every":  # the case uses every bucket of its key
        assert all(len(np.unique(row)) == nb for row in np.asarray(key))
    if pattern == "equal":
        assert (np.asarray(key) == np.asarray(key)[0, 0]).all()
    return x, k, descending, key, nb, want


def test_rank_matches_comparison_and_counting_sort(case):
    _, _, _, key, nb, want = case
    got = jax.jit(_rank_from_keys, static_argnums=1)(key, nb)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("backend", ["compiled", "interpret"])
def test_psu_sort_rank(case, backend):
    x, k, descending, _, _, want = case
    order, rank = psu_sort(
        jnp.asarray(x), k=k, descending=descending, block_packets=BLOCK,
        backend=backend,
    )
    np.testing.assert_array_equal(np.asarray(rank), want)
    np.testing.assert_array_equal(
        np.asarray(order), np.argsort(want, axis=-1)
    )


@pytest.mark.parametrize("backend", ["compiled", "interpret"])
def test_psu_stream_rank(case, backend):
    x, k, descending, _, _, want = case
    res = psu_stream(
        jnp.asarray(x), k=k, descending=descending, input_lanes=8,
        block_packets=BLOCK, backend=backend,
    )
    np.testing.assert_array_equal(np.asarray(res.rank), want)


@pytest.mark.parametrize("backend", ["compiled", "interpret"])
def test_bt_count_axes_sorted_bt(case, backend):
    x, k, descending, _, _, _ = case
    key = "acc" if k is None else "app"
    configs = (
        CodecVariant(key, k, descending),
        CodecVariant(key, k, descending, "bus_invert"),
    )
    got = bt_count_axes(
        jnp.asarray(x)[None], configs=configs, input_lanes=8,
        block_packets=BLOCK, backend=backend,
    )
    want = bt_codecs_ref(jnp.asarray(x), None, configs, input_lanes=8)
    np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(want))
