"""Table I reproduction: BT per 128-bit flit under four orderings.

Paper values (for reference, 100k packets of paired random data):
  non-optimized 63.072 | column-major 54.011 (-14.37 %) |
  ACC 50.346 (-20.18 %) | APP 50.896 (-19.31 %)

We report both data models (see datagen.py): the paper's reductions are
reproduced on the conv-traffic model; uniform iid bytes show the analytic
~5 % ceiling for paired framing (derivation in datagen.py).

All measurements run through ``repro.link.TxPipeline``; ACC/APP take the
fused single-launch kernel path, 'none'/'column_major' the staged path
(bit-identical, see tests/test_psu_stream.py).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.link import LinkSpec, TxPipeline

from .datagen import conv_streams, uniform_pairs

PAPER = {
    "none": (63.072, 0.0),
    "column_major": (54.011, 14.366),
    "acc": (50.346, 20.177),
    "app": (50.896, 19.305),
}
# input-side BT/flit from Table I (the stream the PSU actually orders);
# the weight-stream generation is underspecified in the paper, so the input
# side is the calibration target.
# The conv weight stream cycles the layer's 6 output-channel kernels
# (DESIGN.md §10 recalibration: overall ACC 14.2 % / APP 12.7 % vs the
# paper's 20.42 % / 19.50 % — reported side by side, never substituted).
PAPER_INPUT = {"none": 31.035, "column_major": 26.004, "acc": 22.333, "app": 22.887}

STRATS = ("none", "column_major", "acc", "app")

TINY_KWARGS = {"packets": 512, "conv_images": 2}  # CI smoke (REPRO_BENCH_TINY=1)


def _input_only_spec(strat: str, elems: int, lanes: int = 16, k: int = 4) -> LinkSpec:
    """Spec for one PE's input-side link: all lanes carry input bytes."""
    return LinkSpec(
        width_bits=8 * lanes,
        flits_per_packet=elems // lanes,
        input_lanes=lanes,
        weight_lanes=0,
        key=strat,
        k=k,
    )


def _measure_separate(vals, strat, lanes=16, k=4):
    x = jnp.asarray(vals)
    pipe = TxPipeline(_input_only_spec(strat, x.shape[-1], lanes, k))
    return pipe.measure(x).overall_bt_per_flit


def run(packets: int = 20000, conv_images: int = 24) -> list[tuple[str, str]]:
    rows = []

    # --- paired uniform framing (paper's literal setup) ---
    inp, wgt = uniform_pairs(packets, LinkSpec().elems_per_packet)
    inp, wgt = jnp.asarray(inp), jnp.asarray(wgt)
    base = TxPipeline(LinkSpec(key="none")).measure(inp, wgt)
    for strat in STRATS:
        r = TxPipeline(LinkSpec(key=strat)).measure(inp, wgt)
        red = r.reduction_vs(base) * 100
        rows.append((
            f"table1/uniform/{strat}",
            f"bt_per_flit={r.overall_bt_per_flit:.3f} red={red:.2f}% "
            f"fused={int(r.fused)} "
            f"paper_bt={PAPER[strat][0]} paper_red={PAPER[strat][1]}%",
        ))

    # --- conv-traffic model (reproduces the paper's magnitudes) ---
    inp, wgt = conv_streams(n_images=conv_images)
    inp_cm, wgt_cm = conv_streams(n_images=conv_images, column_major=True)
    base_i = _measure_separate(inp, "none")
    base_w = _measure_separate(wgt, "none")
    for strat in STRATS:
        if strat == "column_major":
            # the paper's column-major is a LAYOUT of the im2col traversal
            # (position-major), not a per-packet permutation
            bi = _measure_separate(inp_cm, "none")
            bw = _measure_separate(wgt_cm, "none")
        else:
            bi = _measure_separate(inp, strat)
            bw = _measure_separate(wgt, strat)
        red = 100 * (1 - (bi + bw) / (base_i + base_w))
        in_red = 100 * (1 - bi / base_i)
        paper_in_red = 100 * (1 - PAPER_INPUT[strat] / PAPER_INPUT["none"])
        rows.append((
            f"table1/conv/{strat}",
            f"in={bi:.3f} (paper {PAPER_INPUT[strat]}) wt={bw:.3f} "
            f"overall_red={red:.2f}% input_red={in_red:.2f}% "
            f"(paper input_red={paper_in_red:.2f}%)",
        ))

    # APP retention of ACC's reduction (paper: 95.5 %)
    acc_i, app_i = _measure_separate(inp, "acc"), _measure_separate(inp, "app")
    acc_w, app_w = _measure_separate(wgt, "acc"), _measure_separate(wgt, "app")
    red_acc = 1 - (acc_i + acc_w) / (base_i + base_w)
    red_app = 1 - (app_i + app_w) / (base_i + base_w)
    rows.append((
        "table1/conv/app_retention",
        f"app/acc={100 * red_app / red_acc:.1f}% (paper 95.5%)",
    ))

    # beyond-paper: bucket-count sweep (pairs with the fig5 area k-sweep to
    # complete the area/BT trade-off curve the paper fixes at k=4)
    for k in (2, 4, 8):
        bi = _measure_separate(inp, "app", k=k)
        rows.append((
            f"table1/conv/k_sweep/k{k}",
            f"input_bt={bi:.3f} input_red={100 * (1 - bi / base_i):.2f}% "
            f"(acc={100 * (1 - acc_i / base_i):.2f}%)",
        ))
    return rows
