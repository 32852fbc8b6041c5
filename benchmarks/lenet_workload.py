"""§IV-B reproduction: LeNet conv1+pool workload through the PSU platform.

16 PEs compute the first convolution (6 kernels, 5x5) and 2x2 mean-pool of
LeNet-5 on synthetic MNIST-like images.  The allocation unit runs the fused
TX pipeline (``repro.link.TxPipeline``: one Pallas launch sorts, reorders,
packs and measures each packet block), the transmitting units permute
(input, weight) pairs, and we verify the CONVOLUTION OUTPUT is unchanged by
the reordering (order-insensitive accumulation) while link BT drops — the
end-to-end statement of the paper.

The same LeNet streams then route through ``repro.codec.compare``, so the
conv scenario reports ordering-alone, coding-alone and ordering∘coding
side by side (net of invert-line overhead, one ``bt_count_codecs`` launch
per stream — DESIGN.md §11).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.codec import compare_streams
from repro.kernels import Variant
from repro.link import LinkSpec, TxPipeline

from .datagen import im2col, synth_images

KERNEL = 5
N_CH = 6
ELEMS, LANES = 64, 16  # validated Table-I framing on the input link

TINY_KWARGS = {"n_images": 1}  # CI smoke (REPRO_BENCH_TINY=1)


def conv_pool_reference(img: np.ndarray, kernels: np.ndarray):
    patches = im2col(img, KERNEL).astype(np.int64)  # (P, 25)
    conv = patches @ kernels.astype(np.int64).T  # (P, 6)
    hw = img.shape[0] - KERNEL + 1
    conv = conv.reshape(hw, hw, N_CH)
    pooled = conv[: hw // 2 * 2, : hw // 2 * 2].reshape(hw // 2, 2, hw // 2, 2, N_CH)
    return pooled.mean((1, 3))


def _pipes() -> dict[str, TxPipeline]:
    spec = LinkSpec(
        width_bits=8 * LANES,
        flits_per_packet=ELEMS // LANES,
        input_lanes=LANES,
        weight_lanes=0,
    )
    return {
        name: TxPipeline(dataclasses.replace(spec, key=name))
        for name in ("none", "acc", "app")
    }


def run(n_images: int = 6) -> list[tuple[str, str]]:
    rng = np.random.default_rng(0)
    imgs = synth_images(n_images, seed=7)
    kernels = rng.integers(0, 256, (N_CH, KERNEL * KERNEL), dtype=np.uint8)
    pipes = _pipes()

    rows = []
    total_bt = {"none": 0, "acc": 0, "app": 0}
    in_streams, w_streams = [], []
    for img in imgs:
        patches = im2col(img, KERNEL)  # (P, 25) uint8
        w_stream = np.broadcast_to(kernels[0], patches.shape)  # channel-0 link
        flat_i = patches.reshape(-1)
        flat_w = np.ascontiguousarray(w_stream).reshape(-1)
        p = flat_i.size // ELEMS
        x = jnp.asarray(flat_i[: p * ELEMS].reshape(p, ELEMS))
        w = jnp.asarray(flat_w[: p * ELEMS].reshape(p, ELEMS))
        in_streams.append(x)
        w_streams.append(w)
        res = {name: pipes[name].run(x) for name in ("acc", "app")}
        total_bt["none"] += int(pipes["none"].run(x).bt_input)
        for name, r in res.items():
            total_bt[name] += int(r.bt_input)
            # order-insensitivity: per-packet MAC identical (exact, ints)
            oi = jnp.take_along_axis(x, r.order, axis=-1)
            ow = jnp.take_along_axis(w, r.order, axis=-1)
            macs0 = (x.astype(jnp.int32) * w.astype(jnp.int32)).sum(-1)
            macs1 = (oi.astype(jnp.int32) * ow.astype(jnp.int32)).sum(-1)
            assert bool(jnp.all(macs0 == macs1))

        # full conv+pool output sanity (reference path)
        out = conv_pool_reference(img, kernels)
        assert np.isfinite(out).all()

    for name in ("acc", "app"):
        red = 100 * (1 - total_bt[name] / total_bt["none"])
        rows.append((
            f"lenet/{name}",
            f"bt={total_bt[name]} base={total_bt['none']} red={red:.2f}% "
            f"(paper link-BT red: acc 20.42% app 19.50%)",
        ))

    # --- ordering vs coding vs composed on the same LeNet streams ---
    # (repro.codec.compare: one bt_count_codecs launch per stream; both
    # links of the conv scenario — patch packets and kernel bytes — summed)
    table = compare_streams(
        in_streams + w_streams,
        LANES,
        orderings=("none", Variant("acc"), Variant("app", 4)),
        codecs=("none", "bus_invert4"),
        workload="lenet",
    )
    for r in table:
        rows.append((
            f"lenet/compare/{r.label}",
            f"data_bt={r.data_bt} aux_bt={r.aux_bt} wires=+{r.extra_wires} "
            f"net_red={100 * r.bt_reduction:.2f}%",
        ))
    return rows
