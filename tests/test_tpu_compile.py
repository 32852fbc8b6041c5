"""Compile the main-path Pallas kernels for a TPU v5e, with no chip attached.

Each test lowers one public entry point on the real Mosaic path
(``backend="pallas"``) at the shapes ``chip_smoke.py`` launches, for a
described v5e chip, and checks that the compiled program holds the kernel
(``tpu_custom_call``).  Nothing runs: a compile that passes shows the chip's
compiler accepts the kernels (tiling, scans, relayouts, VMEM), not that
they are fast or right — the bit-exactness tests cover the results.

The topology is described only inside the module fixture (never at import
or collection), so every test worker collects the same tests and only the
worker running this file loads the TPU compiler.  JAX's persistent
compilation cache is turned off around these tests: a TPU executable
compiled without a chip cannot be read back here.
"""

import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402
from benchmarks.model_traffic import _POINTS  # noqa: E402
from repro.kernels import (  # noqa: E402
    bt_count,
    bt_count_axes,
    bt_count_axes_sharded,
    bt_count_links,
    psu_sort,
    psu_stream,
    quantize_egress,
)

# shapes of the chip_smoke.py launches
FLEET_QUEUES = (64, 512, 16)  # fleet_noc's 16x16 mesh: 64 distinct queues
LENET_PAIRED = (256, 32)  # 8192 captured input bytes, paper framing
LENET_SEPARATE = (128, chip_smoke.ELEMS)
LENET_STREAMS = 3  # conv1 / conv2 / inputs workload streams
LONG_VALUES = sum(a * b for a, b in chip_smoke.qwen3_layer_shapes().values())


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs under /tmp
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_fleet_links_compile(one_chip):
    _compile(
        one_chip,
        lambda s, n: bt_count_links(
            s, input_lanes=16, lengths=n, backend="pallas"
        ),
        (FLEET_QUEUES, jnp.uint8),
        (FLEET_QUEUES[:1], jnp.int32),
    )


@pytest.mark.parametrize("k", [None, 4], ids=["acc", "app4"])
@pytest.mark.parametrize("framing", ["paired", "separate"])
def test_psu_stream_compile(one_chip, framing, k):
    if framing == "paired":
        fn = lambda x, w: psu_stream(x, w, k=k, backend="pallas")  # noqa: E731
        shapes = [(LENET_PAIRED, jnp.uint8)] * 2
    else:
        fn = lambda x: psu_stream(  # noqa: E731
            x, k=k, input_lanes=chip_smoke.LANES, backend="pallas"
        )
        shapes = [(LENET_SEPARATE, jnp.uint8)]
    _compile(one_chip, fn, *shapes)


def test_grid_activity_compile(one_chip):
    configs = tuple(dict.fromkeys(p.codec_variant for p in _POINTS))
    _compile(
        one_chip,
        lambda x, v: bt_count_axes(
            x, valid=v, configs=configs, input_lanes=chip_smoke.LANES,
            activity_windows=chip_smoke.ACTIVITY_WINDOWS, backend="pallas",
        ),
        ((LENET_STREAMS,) + LENET_SEPARATE, jnp.uint8),
        ((LENET_STREAMS,), jnp.int32),
    )


def test_chunked_grid_compile(one_chip):
    packets = LONG_VALUES // chip_smoke.ELEMS
    _compile(
        one_chip,
        lambda x: bt_count_axes(
            x, configs=chip_smoke.LONG_CONFIGS,
            input_lanes=chip_smoke.LANES,
            chunk_packets=chip_smoke.CHUNK_PACKETS, backend="pallas",
        ),
        ((1, packets, chip_smoke.ELEMS), jnp.uint8),
    )


def test_psu_sort_compile(one_chip):
    _compile(
        one_chip,
        lambda x: psu_sort(x, k=4, backend="pallas"),
        (LENET_SEPARATE, jnp.uint8),
    )


def test_bt_count_compile(one_chip):
    rows = LENET_PAIRED[0] * LENET_PAIRED[1] // 8  # 'none' paired input side
    _compile(
        one_chip,
        lambda s: bt_count(s, backend="pallas"),
        ((rows, 8), jnp.uint8),
    )


@pytest.mark.parametrize(
    "size", sorted({a * b for a, b in chip_smoke.qwen3_layer_shapes().values()})
)
def test_quantize_compile(one_chip, size):
    _compile(
        one_chip,
        lambda x: quantize_egress(x, backend="pallas"),
        ((size,), jnp.float32),
    )


@pytest.mark.parametrize(
    "kernel, fn, shape",
    [
        ("bt_count_kernel", lambda s: bt_count(s, backend="pallas"),
         (513, 8)),
        ("quantize_kernel", lambda x: quantize_egress(x, backend="pallas"),
         (1 << 16,)),
        ("psu_sort_kernel", lambda x: psu_sort(x, k=4, backend="pallas"),
         LENET_SEPARATE),
        ("bt_axes_kernel", lambda x: psu_stream(
            x, input_lanes=chip_smoke.LANES, backend="pallas"),
         LENET_SEPARATE),
    ],
    ids=["bt_count", "quantize", "psu_sort", "bt_axes"],
)
def test_kernel_names(one_chip, kernel, fn, shape):
    """Each Pallas kernel compiles to a custom call named by its ``name=``,
    the operation's name in a device trace, whatever its entry is called."""
    dtype = jnp.float32 if kernel == "quantize_kernel" else jnp.uint8
    text = _compile(one_chip, fn, (shape, dtype)).as_text()
    names = re.findall(
        r"%([\w.-]+) = .*custom_call_target=\"tpu_custom_call\"", text
    )
    assert names, text
    for name in names:
        assert re.fullmatch(rf"{kernel}(\.\d+)?", name), name


def test_sharded_links_compile_four_chips(topo):
    """The --four-chips phase: fleet queues sharded over a 2x2 v5e host."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devices = list(topo.devices)
    replicated = NamedSharding(Mesh(np.asarray(devices), ("links",)),
                               PartitionSpec())
    compiled = _compile(
        replicated,
        lambda s, n: bt_count_axes_sharded(
            s, valid=n, configs=chip_smoke.LONG_CONFIGS, input_lanes=16,
            pack="row", block_packets=512, backend="pallas", devices=devices,
        ),
        (FLEET_QUEUES, jnp.uint8),
        (FLEET_QUEUES[:1], jnp.int32),
    )
    assert "all-reduce" in compiled.as_text()
