"""Fleet-scale NoC serving benchmark (DESIGN.md §17).

The ROADMAP's north star: multi-tenant decode traffic (users x layers x
shards multicast flows from ``noc.adapters.fleet_decode_flows``) on a
16x16 mesh, expanded by the batched fabric pipeline and measured by ONE
``bt_count_links`` launch over the fabric's distinct link queues.  Report
groups:

  * **scale** — flows / active links / distinct queues of the compiled
    ``FabricPlan``, plus the one-launch pin read from the traced jaxpr
    (``repro.kernels.pallas_launch_count``).
  * **ordering** — fabric BT / energy for unsorted vs ACC vs APP source
    sorting at fleet scale — the paper's link-power argument at the
    scale where it pays.
  * **latency** — the wormhole/contention model (``noc.latency``) over
    the same plan: max / mean flow latency, contended links, aggregate
    queueing; per-flit-count only, so one evaluation serves every
    ordering.
  * **hot links** — the top links by gross BT with their contention
    (merged flows, wait cycles) alongside — BT hot-spots and merge
    hot-spots are the same links in this traffic, which is the point of
    putting both models on one plan.

With ``REPRO_FLEET_ARTIFACT=path`` the full per-link latency/BT table is
written as the CSV heatmap CI uploads.
"""

from __future__ import annotations

import csv
import os

import jax
import numpy as np

from repro.kernels import bt_count_links, pallas_launch_count
from repro.link import LinkSpec
from repro.noc import (
    FlowBatch,
    NocLatencyModel,
    compile_fabric,
    expand_fabric,
    fabric_latency,
    fleet_decode_flows,
    mesh,
    simulate_noc,
)

TINY_KWARGS = {"users": 4, "layers": 4, "shards": 2, "rows": 8, "cols": 8}

_ORDERINGS = ("none", "acc", "app")


def _spec(key: str) -> LinkSpec:
    # one-sided weight-broadcast framing: all 16 flit bytes carry payload
    return LinkSpec(input_lanes=16, weight_lanes=0, key=key)


def run(
    users: int = 16,
    layers: int = 16,
    shards: int = 4,
    rows: int = 16,
    cols: int = 16,
) -> list[tuple[str, str]]:
    out: list[tuple[str, str]] = []
    topo = mesh(rows, cols)
    spec = _spec("acc")
    weights = np.random.default_rng(0).integers(
        0, 256, (1 << 16,), dtype=np.uint8
    )
    flows = fleet_decode_flows(
        jax.numpy.asarray(weights), topo,
        users=users, layers=layers, shards=shards, spec=spec,
    )
    plan = compile_fabric(topo, [(f.src, f.dsts) for f in flows])
    batch = FlowBatch.from_flows(flows, spec)
    out.append((
        "fleet/scale",
        f"mesh{rows}x{cols} flows={len(flows)} "
        f"active_links={plan.active_links}/{topo.num_links} "
        f"queues={plan.num_queues} packets={sum(batch.counts)}",
    ))

    # --- the one-launch pin: whole fabric, one bt_count_links launch ---
    fs = expand_fabric(plan, batch, spec, sort_at="source")
    launches = pallas_launch_count(
        lambda s: bt_count_links(
            s, input_lanes=spec.input_lanes, lengths=fs.lengths
        ),
        fs.streams,
    )
    out.append((
        "fleet/launches",
        f"bt_count_links launches={launches} for {plan.num_queues} queues "
        f"/ {plan.active_links} links (one per key width)",
    ))

    # --- ordering: fabric BT / energy at fleet scale ---
    reports = {}
    for key in _ORDERINGS:
        rep = simulate_noc(topo, flows, _spec(key), sort_at="source")
        reports[key] = rep
        base = reports[_ORDERINGS[0]]
        out.append((
            f"fleet/{key}",
            f"bt={rep.total_bt} red={100 * rep.reduction_vs(base):.2f}% "
            f"flit_hops={rep.total_flit_hops} E={rep.energy_pj / 1e3:.1f}nJ",
        ))

    # --- latency: wormhole + merge contention over the same plan ---
    lat = fabric_latency(
        plan,
        [c * spec.flits_per_packet for c in batch.counts],
        NocLatencyModel(),
    )
    out.append((
        "fleet/latency",
        f"max={lat.max_latency_ns:.0f}ns mean={lat.mean_latency_ns:.0f}ns "
        f"contended={lat.contended_links}/{len(lat.links)} "
        f"wait={lat.total_wait_cycles}cyc",
    ))

    # --- hot links: BT hot-spots with their contention alongside ---
    acc = reports["acc"]
    by_link = {l.link: l for l in lat.links}
    hot = sorted(acc.links, key=lambda s: -s.gross_bt)[:3]
    for rank, s in enumerate(hot, 1):
        c = by_link[s.link]
        out.append((
            f"fleet/hot_link/{rank}",
            f"link={s.link} route={s.src}->{s.dst} gross_bt={s.gross_bt} "
            f"flits={s.num_flits} flows={c.flows} wait={c.wait_cycles}cyc "
            f"drain={c.drain_ns:.0f}ns E={s.energy_pj:.1f}pJ",
        ))

    artifact = os.environ.get("REPRO_FLEET_ARTIFACT")
    if artifact:  # the per-link latency/BT heatmap CSV CI uploads
        parent = os.path.dirname(artifact)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(artifact, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow([
                "link", "src", "dst", "flits", "flows", "bt_input",
                "bt_aux", "energy_pj", "wait_cycles", "busy_ns", "drain_ns",
            ])
            for s in acc.links:
                c = by_link[s.link]
                w.writerow([
                    s.link, s.src, s.dst, s.num_flits, c.flows, s.bt_input,
                    s.bt_aux, round(s.energy_pj, 3), c.wait_cycles,
                    round(c.busy_ns, 3), round(c.drain_ns, 3),
                ])
        out.append((
            "fleet/artifact",
            f"per-link latency/BT heatmap ({len(acc.links)} links) -> "
            f"{artifact}",
        ))
    return out
