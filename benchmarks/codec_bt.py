"""Ordering vs coding vs ordering∘coding — the codec comparison bench.

The paper reduces link BT purely by popcount ordering; classic link
*coding* (bus-invert, gray, transition signaling; cf. Li et al.,
arXiv:2002.05293) is the standard alternative, and the NoC follow-up
(arXiv:2509.00500) frames the two as composable.  This bench scores the
three-way on the repo's traffic families:

  * **conv**      — the calibrated §IV-B conv streams (input + paired
    weight links, ``datagen.conv_streams``);
  * **decode**    — a weight matrix's int8 HBM broadcast image;
  * **allreduce** — an int8 gradient wire image;

every (ordering, codec) pair measured net of invert-line overhead by ONE
``bt_count_codecs`` launch per stream (``repro.codec.compare``).

Artifact: the full comparison table as CSV (``REPRO_CODEC_ARTIFACT``
overrides the path; CI uploads it with the bench-smoke artifacts).

With ``--activity`` (or REPRO_BENCH_ACTIVITY=1) the conv stream's full
(ordering x codec) grid is additionally measured wire-resolved
(``bt_count_codecs(..., activity_windows=)``, DESIGN.md §15): each
config's hottest wire becomes a report row and all configs export as
``ACTIVITY_codec_bt.saif`` + the ``ACTIVITY_codec_bt_wires.csv``
per-wire heatmap.
"""

from __future__ import annotations

import csv
import os

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.codec import codec_by_name, compare_streams, demo_workloads
from repro.kernels import CodecVariant, Variant, bt_count_codecs

from .datagen import conv_streams

TINY_KWARGS = {
    "conv_images": 1,
    "codecs": ("none", "bus_invert4"),
    "demo_images": 1,
}

_LANES = 16

_CSV_FIELDS = (
    "workload",
    "ordering",
    "codec",
    "data_bt",
    "aux_bt",
    "num_flits",
    "extra_wires",
    "bt_reduction",
    "power_reduction",
    "energy_pj",
)

_ORDERINGS = ("none", Variant("acc"), Variant("app", 4))


def _write_csv(path: str, rows) -> None:
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w", newline="") as f:
        writer = csv.DictWriter(f, fieldnames=_CSV_FIELDS)
        writer.writeheader()
        writer.writerows(
            {k: getattr(r, k) for k in _CSV_FIELDS} for r in rows
        )


def run(
    conv_images: int = 6,
    codecs: tuple[str, ...] = ("none", "bus_invert", "bus_invert4", "transition"),
    demo_images: int = 4,
) -> list[tuple[str, str]]:
    rows: list[tuple[str, str]] = []
    inp, wgt = conv_streams(n_images=conv_images)
    demo = demo_workloads(images=demo_images)
    workloads = {
        "conv": (jnp.asarray(inp), jnp.asarray(wgt)),
        "decode": demo["decode"],
        "allreduce": demo["allreduce"],
    }

    all_rows = []
    with obs.collect() as reg:  # codec.stream probe: per-stream baselines
        for name, streams in workloads.items():
            table = compare_streams(
                streams, _LANES, orderings=_ORDERINGS, codecs=codecs,
                workload=name,
            )
            all_rows.extend(table)
            for r in table:
                rows.append((
                    f"codec/{name}/{r.label}",
                    f"data_bt={r.data_bt} aux_bt={r.aux_bt} "
                    f"wires=+{r.extra_wires} "
                    f"net_red={100 * r.bt_reduction:.2f}% "
                    f"power_red={100 * r.power_reduction:.2f}%",
                ))

    # --- obs telemetry: per-stream baseline breakdown of each workload ---
    for s in reg.series("codec.stream.bt"):
        lab = dict(s.labels)
        rows.append((
            f"codec/obs/stream/{lab['stream']}",
            f"baseline_bt={int(s.value)} (unordered uncoded wire, "
            f"one bt_count_codecs launch per stream)",
        ))

    # --- wire-resolved activity of the conv grid (--activity, §15) ---
    if os.environ.get("REPRO_BENCH_ACTIVITY", "") not in ("", "0"):
        configs = tuple(
            CodecVariant(
                key=o.key if isinstance(o, Variant) else o,
                k=o.k if isinstance(o, Variant) else None,
                descending=o.descending if isinstance(o, Variant) else False,
                codec=codec_by_name(c).scheme,
                partition=codec_by_name(c).partition,
            )
            for o in _ORDERINGS
            for c in codecs
        )
        x = workloads["conv"][0]
        window = 32
        act = bt_count_codecs(
            x, None, configs=configs, input_lanes=_LANES,
            activity_windows=window,
        )
        p, n = x.shape
        duration = p * (n // _LANES)
        bt = np.asarray(act.bt, dtype=np.int64)
        profs = []
        for ci, cfg in enumerate(configs):
            label = f"{cfg.key}+{cfg.codec}" + (
                f"{cfg.partition}" if cfg.partition else ""
            )
            prof = obs.profile_from_arrays(
                label, act.toggles[ci], act.ones[ci],
                window_flits=window, duration_flits=duration,
                data_lanes=_LANES,
            )
            prof.check(int(bt[ci].sum()))  # per-wire sum == gross BT
            profs.append(prof)
            hot = prof.hottest_wires(1)[0]
            rows.append((
                f"codec/hot_wire/{label}",
                f"wire={hot[0]} toggles={hot[1]} "
                f"rate={hot[1] / max(duration - 1, 1):.3f} "
                f"tail={hot[1] / max(prof.per_wire.mean(), 1e-9):.2f}x_mean",
            ))
        obs.write_saif("ACTIVITY_codec_bt.saif", profs, design="codec_bt")
        obs.write_wires_csv("ACTIVITY_codec_bt_wires.csv", profs)
        rows.append((
            "codec/activity/artifact",
            f"SAIF + wire heatmap for {len(profs)} configs x "
            f"{profs[0].num_wires} wires (window={window} flits) -> "
            "ACTIVITY_codec_bt.saif",
        ))

    # --- machine-readable artifact ---
    path = os.environ.get("REPRO_CODEC_ARTIFACT", "codec_compare.csv")
    _write_csv(path, all_rows)
    rows.append((
        "codec/artifact",
        f"comparison CSV -> {path} ({len(all_rows)} rows over "
        f"{len(workloads)} workloads)",
    ))
    return rows
