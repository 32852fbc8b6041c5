"""Synthetic traffic generators shared by the paper-reproduction benches.

Two data models, both needed:

  * ``uniform``  — the paper's literal "random inputs and weights": iid
    uniform bytes.  Analytically, popcount ordering's gain is bounded here
    by E[HD | same popcount] = 3.5 bits/byte vs 4.0 unordered (~12.5 % on
    the ordered side).
  * ``conv``     — LeNet-like conv traffic: spatially-correlated synthetic
    images streamed as im2col patches with a repeated quantized kernel.
    This reproduces the paper's Table-I magnitudes (their workload is the
    first two LeNet layers).
"""

from __future__ import annotations

import numpy as np


def uniform_pairs(packets: int, elems: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    inp = rng.integers(0, 256, (packets, elems), dtype=np.uint8)
    wgt = rng.integers(0, 256, (packets, elems), dtype=np.uint8)
    return inp, wgt


def synth_images(n: int, hw: int = 32, sparsity: float = 0.55, smooth: int = 2,
                 seed: int = 0) -> np.ndarray:
    """MNIST-like 8-bit images: smoothed noise thresholded to sparse strokes."""
    rng = np.random.default_rng(seed)
    imgs = rng.normal(size=(n, hw, hw))
    for _ in range(smooth):
        imgs = (imgs + np.roll(imgs, 1, 1) + np.roll(imgs, -1, 1)
                + np.roll(imgs, 1, 2) + np.roll(imgs, -1, 2)) / 5
    thr = np.quantile(imgs, sparsity, axis=(1, 2), keepdims=True)
    v = np.clip(imgs - thr, 0, None)
    v = v / (v.max(axis=(1, 2), keepdims=True) + 1e-9) * 255
    return v.astype(np.uint8)


def im2col(img: np.ndarray, k: int = 5) -> np.ndarray:
    out = img.shape[0] - k + 1
    return np.lib.stride_tricks.sliding_window_view(img, (k, k)).reshape(
        out * out, k * k
    )


def _pad_to_packets(stream: np.ndarray, elems: int, lanes: int) -> np.ndarray:
    """Round a flat byte stream up to whole packets without dropping bytes.

    The final partial packet is completed by cycling the stream's last
    ``lanes`` bytes — i.e. the period-``lanes`` extension
    ``padded[o] = padded[o - lanes]``, which is phase-correct at any tail
    offset: since packet offsets are flit-aligned (``elems`` is a multiple
    of ``lanes``), every fully-padded flit equals its predecessor and the
    only boundary transitions left are the final real bytes' own — the
    repeated-flit convention of the repro.kernels padding/masking contract
    (under per-packet sorting the tail packet additionally pays its own
    intra-packet transitions).  Streams already a whole number of packets
    are returned unchanged.
    """
    pad = (-stream.size) % elems
    if not pad:
        return stream
    tail = stream[-min(lanes, stream.size):]
    return np.concatenate([stream, np.resize(tail, pad)])


def conv_streams(n_images: int = 24, kernel: int = 5, elems: int = 64,
                 seed: int = 42, column_major: bool = False,
                 channels: int = 6, lanes: int = 16):
    """(input_packets, weight_packets) for one PE's link of the paper's
    conv platform.  Inputs are im2col patches streamed patch-major
    (``column_major=False``, the non-optimized order) or position-major
    (``column_major=True`` — the paper's column-major layout: all patches'
    values at kernel position 0, then position 1, ...); weights follow the
    same traversal.

    The weight stream cycles the layer's ``channels`` output-channel
    kernels across the patch sequence (LeNet: 6 in conv1, 16 in conv2) —
    the PE allocation's round-robin over output channels.  The pre-fix
    model broadcast ONE kernel into every packet, which collapsed
    weight-side ordering gains and under-reduced the overall numbers
    (DESIGN.md §10's honest-calibration note records the recalibration).

    Streams whose byte count is not a whole number of ``elems`` packets
    are padded — never truncated — by cycling the last ``lanes``-byte flit
    into the final packet (see :func:`_pad_to_packets`).
    """
    rng = np.random.default_rng(seed)
    imgs = synth_images(n_images, seed=seed)
    k2 = kernel * kernel
    kerns = (rng.normal(size=(channels, k2)) * 60 + 128).clip(0, 255).astype(
        np.uint8
    )
    inps, wgts = [], []
    for im in imgs:
        patches = im2col(im, kernel)  # (P, 25)
        wmat = kerns[np.arange(len(patches)) % channels]  # cycle channels
        if column_major:
            inps.append(patches.T.reshape(-1))
            wgts.append(wmat.T.reshape(-1))
        else:
            inps.append(patches.reshape(-1))
            wgts.append(wmat.reshape(-1))
    inp_stream = _pad_to_packets(np.concatenate(inps), elems, lanes)
    wgt_stream = _pad_to_packets(np.concatenate(wgts), elems, lanes)
    return (
        inp_stream.reshape(-1, elems),
        wgt_stream.reshape(-1, elems),
    )
