"""Fig. 5 reproduction: area breakdown of four sorting-unit designs.

Absolute um^2 are modeled (no EDA flow; DESIGN.md §6) but anchored so the
paper's APP points and reduction percentages hold exactly; Bitonic/CSN use a
gate-level comparator-network model.
"""

from __future__ import annotations

from repro.core import bitonic_area, csn_area, psu_area

PAPER = {("app", 25): 2193.0, ("app", 49): 6928.0, "overall_reduction": 35.4}

TINY_KWARGS = {"ns": (25,)}  # CI smoke (REPRO_BENCH_TINY=1): one sort width


def run(ns: tuple[int, ...] = (25, 49)) -> list[tuple[str, str]]:
    rows = []
    for n in ns:
        designs = {
            "bitonic": bitonic_area(n),
            "csn": csn_area(n),
            "acc_psu": psu_area(n),
            "app_psu": psu_area(n, k=4),
        }
        for name, a in designs.items():
            rows.append((
                f"fig5/N{n}/{name}",
                f"popcount={a.popcount:.0f}um2 sort={a.sort:.0f}um2 "
                f"total={a.total:.0f}um2",
            ))
        acc, app = designs["acc_psu"], designs["app_psu"]
        rows.append((
            f"fig5/N{n}/reductions",
            f"overall={100 * (1 - app.total / acc.total):.1f}% "
            f"popcount={100 * (1 - app.popcount / acc.popcount):.1f}% "
            f"sort={100 * (1 - app.sort / acc.sort):.1f}% "
            f"(paper@N25: 35.4/24.9/36.7)",
        ))
    # k-sweep beyond the paper (k=4 fixed there): the area leg of the
    # repro.dse trade-off curve (dse_sweep joins it with measured BT)
    from repro.dse import k_sweep

    for pt in k_sweep(n=25, width=8, ks=(2, 4, 8),
                      include_baseline=False, include_precise=False):
        a = pt.area()
        rows.append((f"fig5/k_sweep/k{pt.k}", f"total={a.total:.0f}um2"))

    # timing model at the paper's 500 MHz target (latency scaling argument)
    from repro.core import bitonic_timing, psu_timing

    for n in ns:
        acc, app, bit = psu_timing(n), psu_timing(n, k=4), bitonic_timing(n)
        rows.append((
            f"fig5/timing/N{n}",
            f"acc={acc.sort_time_ns(n):.0f}ns app={app.sort_time_ns(n):.0f}ns "
            f"bitonic_latency={bit.latency_cycles}cyc vs psu "
            f"{acc.latency_cycles}cyc (O(1) in N)",
        ))
    return rows
