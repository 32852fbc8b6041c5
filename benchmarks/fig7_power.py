"""Fig. 6/7 reproduction: link-related and PE-level power reductions.

Power model (DESIGN.md §6): link-related power reduction = transfer_factor x
BT reduction (transfer_factor calibrated on the paper's ACC point); PE-level
reduction = link_share x link-related reduction, with link_share calibrated
from the paper's Fig. 6 (ACC: 18.27 % link -> 4.98 % PE => share ~ 0.273).
BT reductions come from the measured conv-traffic model (table1 bench).
"""

from __future__ import annotations

from repro.link import LinkPowerModel

from .table1_bt import _measure_separate
from .datagen import conv_streams

PAPER = {
    "acc": {"bt": 20.42, "link_power": 18.27, "pe_power": 4.98},
    "app": {"bt": 19.50, "link_power": 16.48, "pe_power": 4.58},
}
LINK_SHARE = 4.98 / 18.27  # PE-level share of link-related power (Fig. 6)

TINY_KWARGS = {"conv_images": 2}  # CI smoke (REPRO_BENCH_TINY=1)


def run(conv_images: int = 24) -> list[tuple[str, str]]:
    model = LinkPowerModel()
    inp, wgt = conv_streams(n_images=conv_images)
    base = _measure_separate(inp, "none") + _measure_separate(wgt, "none")
    rows = []
    for strat in ("acc", "app"):
        bt = _measure_separate(inp, strat) + _measure_separate(wgt, strat)
        bt_red = 1 - bt / base
        link_red = model.power_reduction(bt_red)
        pe_red = LINK_SHARE * link_red * 100
        p = PAPER[strat]
        rows.append((
            f"fig7/{strat}",
            f"bt_red={bt_red * 100:.2f}% (paper {p['bt']}%) "
            f"link_power_red={link_red * 100:.2f}% (paper {p['link_power']}%) "
            f"pe_power_red={pe_red:.2f}% (paper {p['pe_power']}%)",
        ))
    # sorting-unit power overhead ratio (paper: APP 1.43 mW vs ACC 2.28 mW,
    # -37.3 %): modeled as proportional to the area model, via the
    # repro.dse design-point API (the one home of the sweep logic)
    from repro.dse import DesignPoint, area_reduction

    app_red = area_reduction(DesignPoint(n=25, width=8, k=4, ordering="app"))
    rows.append((
        "fig7/psu_power_overhead",
        f"app/acc area ratio={1 - app_red:.3f} -> overhead "
        f"reduction={100 * app_red:.1f}% (paper 37.3% "
        "power, 35.4% area)",
    ))
    return rows
