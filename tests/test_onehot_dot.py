"""The one-pass MXU form of the kernels' exact 0/1 contractions.

Every product in the BT kernel's block math (``kernels/axes.py``) and the
PSU's rank (``kernels/psu.py``) contracts small integers with a 0/1
matrix through ``psu._onehot_dot``.  Where the caller's static bounds on
both operands are at most 256, every value is exact in bfloat16 and the
product takes one bf16 pass with f32 accumulation; larger bounds keep the
multi-pass f32 HIGHEST product (DESIGN.md §13).  These cases pin:

  * exactness against integer numpy at the extremes (bytes 0 and 255,
    all-255 payloads under a full permutation, positions N - 1, row sums
    over the largest blocks);
  * the decode grid's six designs bit-exact against ``kernels/ref.py`` on
    payloads biased toward 0x00 and 0xFF, compiled and interpreted;
  * which sites engage: traced at the decode grid's shapes, only the
    bus-invert row sums (bound 8 x rows) keep HIGHEST; at N = 512 the
    emitted position row does too.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.kernels import CodecVariant, bt_count_axes
from repro.kernels.axes import _axes_block, max_partitions
from repro.kernels.psu import _onehot_dot
from repro.kernels.ref import bt_codecs_ref

DECODE_DESIGNS = (
    CodecVariant("none"),
    CodecVariant("acc"),
    CodecVariant("app", 4),
    CodecVariant("acc", codec="gray"),
    CodecVariant("acc", codec="transition"),
    CodecVariant("app", 4, codec="bus_invert", partition=4),
)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's own
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _precisions(jaxpr):
    """Every dot_general of a jaxpr as (precision, lhs shape, rhs shape);
    its operands are always f32 (a bf16 x bf16 dot does not run on the
    CPU)."""
    out = []
    for eqn in _eqns(jaxpr):
        if eqn.primitive is lax.dot_general_p:
            assert [v.aval.dtype for v in eqn.invars] == [jnp.float32] * 2
            prec = eqn.params["precision"]
            prec = prec[0] if isinstance(prec, tuple) else prec
            out.append((
                prec or lax.Precision.DEFAULT,
                tuple(eqn.invars[0].aval.shape),
                tuple(eqn.invars[1].aval.shape),
            ))
    return out


def _bf16_roundings(jaxpr):
    return sum(
        eqn.primitive.name == "convert_element_type"
        and eqn.params["new_dtype"] == jnp.bfloat16
        for eqn in _eqns(jaxpr)
    )


def _perm_onehot(rng, bp, n):
    rank = np.stack([rng.permutation(n) for _ in range(bp)])
    return rank, rank[:, :, None] == np.arange(n)[None, None, :]


def _bytes_0_255(rng):  # (a, sel, dims, batch, bounds, integer reference)
    a = rng.choice(np.array([0, 255]), (64, 64))
    sel = rng.integers(0, 2, (64, 16)).astype(bool)
    return a, sel, ((1,), (0,)), ((), ()), (255, 1), a @ sel


def _all_255_permuted(rng):
    a = np.full((64, 2, 64), 255)
    _, perm = _perm_onehot(rng, 64, 64)
    return (a, perm, ((2,), (1,)), ((0,), (0,)), (255, 1),
            np.einsum("bmn,bnk->bmk", a, perm.astype(np.int64)))


def _positions(n):
    def case(rng):
        a = np.broadcast_to(np.arange(n), (8, 1, n))
        rank, perm = _perm_onehot(rng, 8, n)
        want = np.einsum("bmn,bnk->bmk", a, perm.astype(np.int64))
        # the permuted iota is the inverse permutation: `order`
        np.testing.assert_array_equal(want[:, 0], np.argsort(rank, axis=1))
        return a, perm, ((2,), (1,)), ((0,), (0,)), (n - 1, 1), want
    return case


def _row_sum(rows):
    def case(rng):  # 0/1 flags summed over a block's rows (all ones too)
        e = rng.integers(0, 2, (rows - 1, 4))
        e[:, 0] = 1
        ones = np.ones((rows - 1, 1), np.int64)
        return e, ones, ((0,), (0,)), ((), ()), (1, 1), e.T @ ones
    return case


ONE_PASS_CASES = {
    "bytes-0-255": _bytes_0_255,
    "all-255-full-permutation": _all_255_permuted,
    **{f"positions-n{n}": _positions(n) for n in (8, 32, 64, 256)},
    # the links kernel's default block, and eight times it
    **{f"row-sum-{r}-rows": _row_sum(r) for r in (512, 4096)},
}


@pytest.mark.parametrize("name", ONE_PASS_CASES)
def test_one_pass_exact_at_extremes(name):
    rng = np.random.default_rng(len(name))
    a, b, dims, batch, bounds, want = ONE_PASS_CASES[name](rng)
    dot = functools.partial(_onehot_dot, dims=dims, batch=batch, bounds=bounds)
    a, b = jnp.asarray(a, jnp.int32), jnp.asarray(b)
    (prec, _, _), = _precisions(jax.make_jaxpr(dot)(a, b).jaxpr)
    assert prec == lax.Precision.DEFAULT  # the one-pass path engages
    got = np.asarray(jax.jit(dot)(a, b))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


def test_bound_above_256_keeps_highest_and_is_exact():
    # a bus-invert group sum: per-lane flips (<= 8) summed over 1023 rows
    rng = np.random.default_rng(3)
    grp = (np.arange(16)[:, None] // 4 == np.arange(4)[None, :])
    flips = rng.integers(0, 9, (1023, 16)).sum(axis=0, keepdims=True)
    dot = functools.partial(
        _onehot_dot, dims=((0,), (1,)), bounds=(1, 8 * 1023)
    )
    args = (jnp.asarray(grp), jnp.asarray(flips, jnp.int32))
    (prec, _, _), = _precisions(jax.make_jaxpr(dot)(*args).jaxpr)
    assert prec == lax.Precision.HIGHEST
    np.testing.assert_array_equal(
        np.asarray(jax.jit(dot)(*args)), grp.T.astype(np.int64) @ flips.T
    )


def test_sum_that_can_reach_2_pow_24_is_refused():
    with pytest.raises(ValueError, match="inexact contraction"):
        _onehot_dot(
            jnp.ones((1, 1 << 16), jnp.int32), jnp.ones((1 << 16, 1), bool),
            ((1,), (0,)), bounds=(256, 1),
        )


# ------------------------------ the decode grid's designs, edge-heavy bytes


@pytest.mark.parametrize("backend", ["compiled", "interpret"])
def test_decode_grid_designs_bit_exact_on_edge_bytes(backend):
    rng = np.random.default_rng(16)
    p, n = 100, 64  # not a block multiple: a padded tail block
    x = rng.integers(0, 256, (p, n))
    pick = rng.random((p, n))
    x = np.where(pick < 0.4, 0xFF, np.where(pick < 0.8, 0x00, x))
    x = jnp.asarray(x.astype(np.uint8))
    got = bt_count_axes(
        x[None], configs=DECODE_DESIGNS, input_lanes=16, block_packets=64,
        backend=backend,
    )
    want = bt_codecs_ref(x, None, DECODE_DESIGNS, input_lanes=16)
    np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(want))


# ---------------------------------------------- which products take one pass


def _block_jaxpr(configs, n, lanes, emit_stream=False, bp=64):
    block = functools.partial(
        _axes_block, configs=configs, width=8, input_lanes=lanes,
        weight_lanes=0, split_lanes=lanes, pack="lane",
        pmax=max_partitions(configs, lanes), emit_stream=emit_stream,
    )
    x = jnp.zeros((bp, n), jnp.int32)
    return jax.make_jaxpr(block)(x, x, jnp.int32(bp)).jaxpr


def test_decode_grid_block_takes_one_pass_but_the_row_sums():
    lanes, n, bp = 16, 64, 64
    rows = bp * n // lanes
    jaxpr = _block_jaxpr(DECODE_DESIGNS, n, lanes, bp=bp)
    prods = _precisions(jaxpr)
    highest = [p for p in prods if p[0] == lax.Precision.HIGHEST]
    one_pass = [p for p in prods if p[0] == lax.Precision.DEFAULT]
    assert len(highest) + len(one_pass) == len(prods)
    # bus_invert(partition 4): 4 groups; input and weight side sums, for
    # each of the two entry branches: the only bound above 256 (8 x rows)
    assert [p[1:] for p in highest] == [((lanes, 4), (1, lanes))] * 4
    shapes = [p[1:] for p in one_pass]
    # the reorder of both sorted orderings (acc, app k=4)
    assert shapes.count(((bp, 1, n), (bp, n, n))) == 2
    # the lane packing of all three orderings (none included)
    assert shapes.count(((bp, n), (n, n))) == 3
    # the rank's triangular count: 9 ACC buckets, 4 APP buckets
    assert ((9 * bp, n), (n, n)) in shapes and ((4 * bp, n), (n, n)) in shapes
    # bus-invert selectors: popcounts to groups, flips and states to lanes,
    # the invert line's boundary count
    assert ((rows - 1, lanes), (lanes, 4)) in shapes
    assert shapes.count(((rows - 1, 4), (lanes, 4))) == 2
    assert shapes.count(((rows, 4), (lanes, 4))) == 2
    assert shapes.count(((rows - 1, 4), (rows - 1, 1))) == 2
    assert len(one_pass) == 2 + 3 + 2 + 1 + 2 + 2 + 2
    # the operands above 0/1 are rounded through bfloat16: the payloads of
    # the two reorders and the three packings, and the popcounts
    assert _bf16_roundings(jaxpr) == 2 + 3 + 1


@pytest.mark.parametrize("n", [256, 512])
def test_emitted_positions_take_one_pass_up_to_n_256(n):
    prods = _precisions(
        _block_jaxpr((CodecVariant("acc"),), n, 8, emit_stream=True, bp=8)
    )
    reorder = [p for p in prods if p[1] == (8, 2, n)]
    want = lax.Precision.DEFAULT if n <= 257 else lax.Precision.HIGHEST
    assert [p[0] for p in reorder] == [want]
    # the rank and the packing stay one pass at any N
    others = [p[0] for p in prods if p[1] != (8, 2, n)]
    assert others and set(others) == {lax.Precision.DEFAULT}
