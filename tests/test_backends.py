"""Backend-dispatch equivalence (DESIGN.md §13).

The compiled jnp backend is the CPU/GPU production path and the Pallas
interpreter is the validation switch; every public kernel entry point must
be bit-exact between the two — across the full ordering x codec grid,
width 4/8, jagged links and non-block-multiple P — and the chunked
streaming / sharded-link paths must reproduce the plain launch exactly
(the bus-invert carry threads across chunk edges).  Also pins the
resolution order: explicit ``backend=`` > legacy ``interpret=`` >
``force_default_backend`` > ``REPRO_KERNEL_BACKEND`` > platform default.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    BACKEND_ENV_VAR,
    BACKENDS,
    CodecVariant,
    Variant,
    bt_count,
    bt_count_axes,
    bt_count_axes_sharded,
    bt_count_codecs,
    bt_count_links,
    bt_count_variants,
    default_backend,
    force_default_backend,
    pallas_launch_count,
    psu_sort,
    psu_stream,
    quantize_egress,
    resolve_backend,
)


def _stack_jagged(arrays):
    """(P_l, N) packet queues -> zero-padded (L, P_max, N) + valid tuple."""
    valid = tuple(a.shape[0] for a in arrays)
    pmax = max(valid)
    return (
        jnp.stack(
            [jnp.pad(a, ((0, pmax - a.shape[0]), (0, 0))) for a in arrays]
        ),
        valid,
    )


def _grid_configs(width):
    orderings = [("none", None, False), ("column_major", None, False),
                 ("acc", None, False), ("acc", None, True)]
    orderings += [("app", k, False) for k in (2, 4, 8) if k <= width + 1]
    codecs = [("none", None), ("gray", None), ("transition", None),
              ("bus_invert", None), ("bus_invert", 4)]
    return tuple(
        CodecVariant(key, k, desc, scheme, part)
        for key, k, desc in orderings
        for scheme, part in codecs
    )


def _jagged_case(width, seed):
    rng = np.random.default_rng(seed)
    hi = 2**width if width < 8 else 256
    ps = [37, 16, 53]  # non-block-multiple, all-different link lengths
    xs = [jnp.asarray(rng.integers(0, hi, (p, 32), dtype=np.uint8))
          for p in ps]
    ws = [jnp.asarray(rng.integers(0, 256, (p, 32), dtype=np.uint8))
          for p in ps]
    x, valid = _stack_jagged(xs)
    w, _ = _stack_jagged(ws)
    return x, w, valid


# ------------------------------------------ compiled == interpret, per entry


@pytest.mark.parametrize("width", [4, 8])
def test_bt_count_axes_backends_bit_exact(width):
    """Acceptance: the full ordering x codec grid on jagged links at a
    non-block-multiple P, compiled vs interpret, every cell equal."""
    x, w, valid = _jagged_case(width, seed=width)
    kw = dict(valid=valid, configs=_grid_configs(width), width=width,
              input_lanes=8, block_packets=16)
    got = np.asarray(bt_count_axes(x, w, backend="compiled", **kw))
    ref = np.asarray(bt_count_axes(x, w, backend="interpret", **kw))
    np.testing.assert_array_equal(got, ref)


def test_psu_entry_points_backends_bit_exact():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(0, 256, (50, 32), dtype=np.uint8))
    w = jnp.asarray(rng.integers(0, 256, (50, 32), dtype=np.uint8))
    for kw in ({"k": None}, {"k": 4, "descending": True}):
        oc, rc = psu_sort(x, backend="compiled", **kw)
        oi, ri = psu_sort(x, backend="interpret", **kw)
        np.testing.assert_array_equal(np.asarray(oc), np.asarray(oi))
        np.testing.assert_array_equal(np.asarray(rc), np.asarray(ri))
        sc = psu_stream(x, w, block_packets=16, **kw, backend="compiled")
        si = psu_stream(x, w, block_packets=16, **kw, backend="interpret")
        for fc, fi in zip(sc, si):
            np.testing.assert_array_equal(np.asarray(fc), np.asarray(fi))


def test_scalar_entry_points_backends_bit_exact():
    rng = np.random.default_rng(5)
    s = jnp.asarray(rng.integers(0, 256, (77, 16), dtype=np.uint8))
    assert int(bt_count(s, backend="compiled")) == int(
        bt_count(s, backend="interpret")
    )
    g = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))
    qc = quantize_egress(g, backend="compiled")
    qi = quantize_egress(g, backend="interpret")
    for a, b in zip(qc, qi):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_variant_and_codec_entry_points_backends_bit_exact():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.integers(0, 256, (41, 32), dtype=np.uint8))
    w = jnp.asarray(rng.integers(0, 256, (41, 32), dtype=np.uint8))
    variants = (Variant("none"), Variant("acc"), Variant("app", 4, True))
    np.testing.assert_array_equal(
        np.asarray(bt_count_variants(x, w, variants=variants,
                                     block_packets=16, backend="compiled")),
        np.asarray(bt_count_variants(x, w, variants=variants,
                                     block_packets=16, backend="interpret")),
    )
    configs = _grid_configs(8)[::3]
    np.testing.assert_array_equal(
        np.asarray(bt_count_codecs(x, w, configs=configs, block_packets=16,
                                   backend="compiled")),
        np.asarray(bt_count_codecs(x, w, configs=configs, block_packets=16,
                                   backend="interpret")),
    )
    s = jnp.asarray(rng.integers(0, 256, (3, 29, 16), dtype=np.uint8))
    np.testing.assert_array_equal(
        np.asarray(bt_count_links(s, input_lanes=8, lengths=(29, 11, 2),
                                  block_rows=8, backend="compiled")),
        np.asarray(bt_count_links(s, input_lanes=8, lengths=(29, 11, 2),
                                  block_rows=8, backend="interpret")),
    )


# --------------------------------------------------- chunked-streaming carry


def test_chunked_streaming_carries_state_across_chunk_edges():
    """The lax.scan streaming path must thread the inter-block fold carry
    (bus-invert wire state + edge flits) across chunk boundaries: any
    chunk size reproduces the single-launch totals exactly, on both
    backends.  Stateful codecs make a dropped carry visible immediately —
    a cold bus-invert restart at a chunk edge flips invert decisions."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.integers(0, 256, (150, 32), dtype=np.uint8))[None]
    configs = (
        CodecVariant("acc"),
        CodecVariant("none", codec="bus_invert"),
        CodecVariant("app", 4, codec="bus_invert", partition=4),
        CodecVariant("acc", codec="transition"),
        CodecVariant("none", codec="gray"),
    )
    kw = dict(configs=configs, input_lanes=8, block_packets=16)
    whole = np.asarray(bt_count_axes(x, None, backend="compiled", **kw))
    assert whole[0, 1, 2] > 0  # the invert line actually switches
    for chunk in (16, 32, 48, 96):  # incl. non-divisors of P=150
        for be in ("compiled", "interpret"):
            got = np.asarray(
                bt_count_axes(x, None, backend=be, chunk_packets=chunk, **kw)
            )
            np.testing.assert_array_equal(got, whole, err_msg=f"{be}/{chunk}")


def test_chunked_links_matches_unchunked():
    rng = np.random.default_rng(13)
    s = jnp.asarray(rng.integers(0, 256, (4, 700, 16), dtype=np.uint8))
    lengths = (700, 333, 2, 0)
    whole = np.asarray(bt_count_links(s, input_lanes=8, lengths=lengths))
    got = np.asarray(
        bt_count_links(s, input_lanes=8, lengths=lengths, chunk_rows=256,
                       backend="compiled")
    )
    np.testing.assert_array_equal(got, whole)


# ------------------------------------------------------- sharded link axis


def test_sharded_axes_matches_unsharded_on_one_device():
    """`bt_count_axes_sharded` (shard_map over the link axis + psum) is a
    layout change, not a math change: on however many devices are present
    (1 in CI) it reproduces the unsharded table, including the link-count
    padding it adds to fill the device mesh."""
    x, w, valid = _jagged_case(8, seed=17)
    kw = dict(valid=valid, configs=_grid_configs(8)[:6], input_lanes=8,
              block_packets=16)
    np.testing.assert_array_equal(
        np.asarray(bt_count_axes_sharded(x, w, **kw)),
        np.asarray(bt_count_axes(x, w, **kw)),
    )


# ------------------------------------------------------- resolution order


def test_backend_resolution_order(monkeypatch):
    monkeypatch.delenv(BACKEND_ENV_VAR, raising=False)
    platform_default = default_backend()
    assert platform_default in BACKENDS
    if jax.default_backend() != "tpu":
        assert platform_default == "compiled"
    # env var beats the platform default, read at call time
    monkeypatch.setenv(BACKEND_ENV_VAR, "interpret")
    assert default_backend() == "interpret"
    assert resolve_backend(None, None) == "interpret"
    # a force context beats the env var
    with force_default_backend("compiled"):
        assert default_backend() == "compiled"
    assert default_backend() == "interpret"
    # the legacy interpret= bool beats the default; backend= beats all
    assert resolve_backend(None, False) == "pallas"
    assert resolve_backend(None, True) == "interpret"
    assert resolve_backend("compiled", True) == "compiled"
    # junk is rejected loudly, never silently mapped
    monkeypatch.setenv(BACKEND_ENV_VAR, "turbo")
    with pytest.raises(ValueError, match="turbo"):
        default_backend()
    with pytest.raises(ValueError, match="backend="):
        resolve_backend("turbo", None)


def test_env_var_selects_execution_path(monkeypatch):
    """The env override changes which path actually runs (not just a
    label): results stay bit-exact and the launch-count trace still pins
    the pallas path under a compiled default."""
    rng = np.random.default_rng(19)
    s = jnp.asarray(rng.integers(0, 256, (40, 8), dtype=np.uint8))
    monkeypatch.setenv(BACKEND_ENV_VAR, "compiled")
    a = int(bt_count(s))
    monkeypatch.setenv(BACKEND_ENV_VAR, "interpret")
    b = int(bt_count(s))
    assert a == b
    # launch counts remain the cross-backend invariant: the counter traces
    # the pallas path even when the session default is compiled
    monkeypatch.setenv(BACKEND_ENV_VAR, "compiled")
    assert pallas_launch_count(bt_count, s) == 1
    assert pallas_launch_count(lambda v: bt_count(v, backend="compiled"), s) == 0


def test_quantizer_pads_rows_to_whole_steps():
    """Block rows that are not a multiple of the grid step are zero-padded
    (not stepped one row at a time) and trimmed: bit-exact with compiled."""
    from repro.kernels.quantize import (
        quantize_egress_compiled,
        quantize_egress_pallas,
    )

    rng = np.random.default_rng(23)
    x = jnp.asarray(rng.normal(size=(77 * 64,)).astype(np.float32))
    got = quantize_egress_pallas(x, block=64, rows_per_step=32, interpret=True)
    # jitted, as the entry point runs it (XLA may divide by a constant as
    # a reciprocal product, so eager and jitted scales differ in an ulp)
    ref = jax.jit(quantize_egress_compiled, static_argnames="block")(
        x, block=64
    )
    assert got[0].shape == (77 * 64,) and got[1].shape == (77,)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="multiple of 32"):
        quantize_egress_pallas(x, block=64, rows_per_step=20)


@pytest.mark.skipif(jax.default_backend() == "tpu", reason="needs no TPU")
def test_pallas_kernels_never_fall_back_to_the_interpreter():
    """Off-TPU a ``*_pallas`` call without ``interpret=True`` is refused;
    it does not quietly run the interpreter in place of the chip."""
    from repro.kernels.btcount import bt_count_pallas

    s = jnp.zeros((40, 8), jnp.uint8)
    with pytest.raises(Exception):
        jax.block_until_ready(bt_count_pallas(s))
    assert int(bt_count_pallas(s, interpret=True)) == 0


def test_enable_compilation_cache_paths(monkeypatch):
    """CLI runs cache compiles in $JAX_COMPILATION_CACHE_DIR when set, else
    at the fixed ``<repo>/.jax_cache``; importing repro sets neither."""
    import pathlib
    import sys

    repo = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(repo))
    from benchmarks.run import enable_compilation_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compilation_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == "/elsewhere/cache"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compilation_cache() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
