"""NoC BT benchmark: the sorting unit inside a multi-router fabric.

Three report groups (DESIGN.md §9, §14):

  * **topology x ordering** — fabric-total BT / energy for conv-platform
    traffic on a mesh and a ring, under sort-at-source and sort-at-every-
    hop, precise (ACC) vs approximate (APP) vs unsorted.
  * **hottest links** — per-link BT telemetry of the mesh acc/source
    fabric via the ``repro.obs`` ``noc.link`` probe: the top-3 links by
    gross BT as report rows, and (with ``REPRO_NOC_LINKS_ARTIFACT=path``)
    the full per-link heatmap CSV.  With ``--activity`` (or
    REPRO_BENCH_ACTIVITY=1) the same run is measured wire-resolved
    (DESIGN.md §15): top-3 hottest *wires* as report rows plus
    ``ACTIVITY_noc_bt.saif`` and the ``ACTIVITY_noc_bt_wires.csv``
    per-wire heatmap.
  * **hop sweep** — one unicast flow at increasing XY distance: with
    sort-at-source, every extra hop retransmits the *already ordered*
    stream, so the absolute BT saving scales linearly with hop count and
    the relative reduction is preserved end-to-end.
"""

from __future__ import annotations

import os
import sys
from contextlib import nullcontext

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.link import LinkSpec
from repro.noc import (
    TrafficFlow,
    conv_platform_flows,
    hop_count,
    mesh,
    ring,
    simulate_noc,
)

from .datagen import im2col, synth_images

TINY_KWARGS = {"n_images": 1, "max_hops": 2}

# (key, sort_at) design points; 'none'/'source' is the baseline fabric
DESIGNS = [
    ("none", "source"),
    ("acc", "source"),
    ("app", "source"),
    ("acc", "hop"),
    ("none", "hop"),
]


def _conv_flows(topo, src, pes, spec, n_images):
    rng = np.random.default_rng(0)
    imgs = synth_images(n_images, seed=7)
    kernel = rng.integers(0, 256, (25,), dtype=np.uint8)
    flows = []
    for img in imgs:
        flows.extend(
            conv_platform_flows(
                jnp.asarray(im2col(img, 5)), jnp.asarray(kernel),
                topo, src, pes, spec,
            )
        )
    return flows


def run(n_images: int = 3, max_hops: int = 6) -> list[tuple[str, str]]:
    rows = []

    # --- topology x ordering: conv-platform traffic ---
    fabrics = [
        (mesh(4, 4), 0, [r for r in range(16) if r % 4]),  # PEs off col 0
        (ring(8), 0, list(range(1, 8))),
    ]
    hot_reg = None  # per-link telemetry of the mesh acc/source fabric
    hot_rep = None  # its report (carries wire activity under --activity)
    activity = os.environ.get("REPRO_BENCH_ACTIVITY", "") not in ("", "0")
    for topo, src, pes in fabrics:
        tname = f"{topo.kind}{topo.rows}x{topo.cols}"
        # flows depend only on the framing, not the key/sort_at
        flows = _conv_flows(topo, src, pes, LinkSpec(), n_images)
        base = None
        for key, sort_at in DESIGNS:
            spec = LinkSpec(key=key)
            # collect per-link telemetry on the paper-default mesh fabric
            # (the repro.obs noc.link probe feeds the hottest-link rows)
            watch = tname.startswith("mesh") and (key, sort_at) == (
                "acc", "source",
            )
            with obs.collect() if watch else nullcontext() as reg:
                rep = simulate_noc(
                    topo, flows, spec, sort_at=sort_at,
                    activity_windows=32 if watch and activity else None,
                )
            if watch:
                hot_reg, hot_rep = reg, rep
            if base is None:
                base = rep
            rows.append((
                f"noc/{tname}/{key}-{sort_at}",
                f"bt={rep.total_bt} red={100 * rep.reduction_vs(base):.2f}% "
                f"links={rep.active_links}/{rep.total_links} "
                f"flit_hops={rep.total_flit_hops} E={rep.energy_pj / 1e3:.1f}nJ",
            ))

    # --- hottest links: per-link BT telemetry of the mesh acc/source run ---
    if hot_reg is not None:
        for rank, r in enumerate(obs.top_links(hot_reg, 3), 1):
            rows.append((
                f"noc/hot_link/{rank}",
                f"link={r['link']} route={r['src']}->{r['dst']} "
                f"gross_bt={r['gross_bt']} flits={r['num_flits']} "
                f"bt_per_flit={r['bt_per_flit']:.2f} "
                f"E={r['energy_pj']:.1f}pJ",
            ))
        artifact = os.environ.get("REPRO_NOC_LINKS_ARTIFACT")
        if artifact:  # the per-link heatmap CSV (README quickstart)
            obs.write_links_csv(artifact, hot_reg)

    # --- hottest wires: wire-resolved telemetry of the same run (§15) ---
    if activity and hot_rep is not None and hot_rep.activity_window:
        profs = obs.profiles_from_noc(hot_rep)
        for p, s in zip(profs, hot_rep.links):
            p.check(s.gross_bt)  # sum(per-wire) == gross BT, every link
        for rank, r in enumerate(obs.top_wires(hot_reg, 3), 1):
            rows.append((
                f"noc/hot_wire/{rank}",
                f"link={r['link']} route={r['src']}->{r['dst']} "
                f"wire={r['wire']} toggles={r['toggles']}",
            ))
        obs.write_saif("ACTIVITY_noc_bt.saif", profs, design="noc_bt")
        obs.write_wires_csv("ACTIVITY_noc_bt_wires.csv", profs)
        rows.append((
            "noc/activity/artifact",
            f"SAIF + wire heatmap for {len(profs)} links x "
            f"{profs[0].num_wires} wires (window="
            f"{hot_rep.activity_window} flits) -> ACTIVITY_noc_bt.saif",
        ))

    # --- hop sweep: source-sorted advantage is preserved across hops ---
    topo = mesh(4, 4)
    rng = np.random.default_rng(1)
    img = synth_images(1, seed=11)[0]
    pkts = jnp.asarray(im2col(img, 5).reshape(-1)[: 96 * 32].reshape(96, 32))
    wgts = jnp.asarray(
        rng.integers(0, 256, pkts.shape, dtype=np.uint8)
    )
    # XY distances 1..max_hops from router 0, capped at the 4x4 mesh
    # diameter (say so rather than silently covering less than asked)
    diameter = (topo.rows - 1) + (topo.cols - 1)
    if max_hops > diameter:
        print(
            f"# noc_bt: hop sweep capped at the mesh diameter "
            f"({max_hops} requested, {diameter} possible)",
            file=sys.stderr,
        )
        max_hops = diameter
    dests = [
        topo.router(max(0, h - (topo.cols - 1)), min(h, topo.cols - 1))
        for h in range(1, max_hops + 1)
    ]
    for dst in dests:
        h = hop_count(topo, 0, dst)
        flow = [TrafficFlow("sweep", 0, (dst,), pkts, wgts)]
        per_key = {}
        for key in ("none", "acc"):
            rep = simulate_noc(topo, flow, LinkSpec(key=key), sort_at="source")
            per_key[key] = rep
        red = 100 * per_key["acc"].reduction_vs(per_key["none"])
        rows.append((
            f"noc/hops{h}",
            f"bt_none={per_key['none'].total_bt} "
            f"bt_acc={per_key['acc'].total_bt} red={red:.2f}% "
            "(per-hop reduction preserved)",
        ))
    return rows
