"""Real-model traffic campaign: captured streams through the BT stack.

Every other bench module measures synthetic streams
(``benchmarks/datagen.py``).  This one drives the model zoo itself under
``repro.obs.capture`` (DESIGN.md §16) and profiles the *captured* int8
traffic — four real scenarios:

  * **lenet_conv**       — a LeNet trained in-repo (``repro.models.lenet``,
    checkpointed via ``repro.checkpoint`` so CI restores instead of
    retraining): trained conv kernels + task inputs, the honest version of
    the paper's Table-I conv setup.
  * **serve_decode**     — ``serve.generate`` on a smoke transformer: the
    multicast decode weight stream plus per-token KV bytes.
  * **train_allreduce**  — one eager train step: the gradient tree, i.e.
    the ring all-reduce payload.
  * **moe_dispatch**     — the MoE model's forward under capture: each
    expert layer's input rows and routing, dispatched as the
    expert-parallel all-to-all (one flow per source chip and expert).

Each scenario's captured workload runs through ``dse.evaluate_grid``
(baseline / ACC / APP k=4 / APP+bus-invert composed, wire-resolved) and
through ``noc.simulate`` on a fabric via the matching ``noc.adapters``
flow builder, with per-link telemetry collected by ``repro.obs``.  The
campaign lands as ``SCENARIOS_model_traffic.csv`` / ``.json`` artifacts
(``repro.obs.report.scenario_table``) in the current directory, and the
trained-weight recalibration rows report captured overall reductions SIDE
BY SIDE with the §10 synthetic numbers and the paper's — never
substituted.
"""

from __future__ import annotations

import os

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.dse import DesignPoint, evaluate_grid
from repro.link import LinkSpec
from repro.noc import (
    conv_platform_flows,
    decode_weight_flows,
    mesh,
    moe_dispatch_flows,
    ring,
    ring_allreduce_flows,
    simulate_noc,
)

from .datagen import im2col, synth_images
from .table1_bt import _input_only_spec, _measure_separate

TINY_KWARGS = {"lenet_steps": 40, "new_tokens": 2, "seq": 16}

# the §10 calibration state this campaign recalibrates (percent overall
# reduction on the synthetic conv streams; benchmarks/table1_bt.py) and
# the paper's reported numbers — always shown side by side
SYNTHETIC_OVERALL = {"acc": 14.21, "app": 12.66}
PAPER_OVERALL = {"acc": 20.42, "app": 19.50}

# smoke-config archetypes behind the serve/train/moe scenarios
SERVE_ARCH = "qwen3-4b"
TRAIN_ARCH = "internlm2-1.8b"
MOE_ARCH = "qwen3-moe-30b-a3b"

ELEMS = 64  # 4 flits x 16 input lanes per measured packet
LANES = 16

# the campaign's design points, in report order
_POINTS = (
    DesignPoint(ordering="none", k=None),
    DesignPoint(ordering="acc", k=None),
    DesignPoint(ordering="app", k=4),
    DesignPoint(ordering="app", k=4, codec="bus_invert"),
)


def _evaluate(sess: obs.CaptureSession, scenario: str, windows: int):
    wl = sess.workload(scenario, elems=ELEMS, lanes=LANES)
    return wl, evaluate_grid(_POINTS, wl, activity_windows=windows)


def _record(scenario, sess, evals, noc_red=None, hot_link=None):
    """One obs.report scenario record from the campaign measurements."""
    base, acc, app, comp = evals
    streams = sess.get(scenario)
    rec = {
        "scenario": scenario,
        "streams": len(streams),
        "num_bytes": sum(s.num_bytes for s in streams),
        "num_flits": base.num_flits,
        "bt_base": base.total_bt,
        "red_acc": acc.bt_reduction,
        "red_app": app.bt_reduction,
        "red_composed": comp.bt_reduction,
        "energy_base_pj": base.energy_pj,
        "energy_app_pj": app.energy_pj,
    }
    if app.hot_wire is not None:
        rec["hot_wire"] = obs.wire_name(app.hot_wire, LANES)
    if noc_red is not None:
        rec["noc_red_acc"] = noc_red
    if hot_link is not None:
        rec["hot_link"] = (
            f"{hot_link['src']}->{hot_link['dst']}"
        )
    return rec


def _noc_run(topo, flows, spec):
    """(acc-vs-none fabric reduction, hottest link record) of one flow set."""
    import dataclasses

    base = simulate_noc(
        topo, flows, dataclasses.replace(spec, key="none"), sort_at="source"
    )
    with obs.collect() as reg:
        rep = simulate_noc(
            topo, flows, dataclasses.replace(spec, key="acc"),
            sort_at="source",
        )
    top = obs.top_links(reg, 1)
    return rep.reduction_vs(base), (top[0] if top else None), rep


def run(
    lenet_steps: int = 300,
    batch: int = 2,
    prompt: int = 8,
    new_tokens: int = 4,
    seq: int = 32,
    activity_windows: int = 32,
) -> list[tuple[str, str]]:
    from repro.configs import smoke_config
    from repro.models import lenet

    rows = []
    records = []
    io_spec = _input_only_spec("none", ELEMS, LANES)

    # ---- lenet_conv: train (or restore) the real model, capture, measure
    ckpt_dir = os.environ.get("REPRO_LENET_CKPT", ".lenet_ckpt")
    params, info = lenet.train_lenet(steps=lenet_steps, ckpt_dir=ckpt_dir)
    rows.append((
        "model/lenet/train",
        f"steps={info['steps']} final_loss={info['final_loss']:.4f} "
        f"restored={int(info['restored'])} ckpt={ckpt_dir}",
    ))
    sessions = {"lenet_conv": obs.capture_lenet_conv(params=params)}

    # ---- serve_decode / train_allreduce / moe_dispatch: eager captures
    sessions["serve_decode"] = obs.capture_serve_decode(
        smoke_config(SERVE_ARCH), batch=batch, prompt=prompt,
        new_tokens=new_tokens,
    )
    sessions["train_allreduce"] = obs.capture_train_step(
        smoke_config(TRAIN_ARCH), batch=batch, seq=seq
    )
    sessions["moe_dispatch"] = obs.capture_moe_dispatch(
        smoke_config(MOE_ARCH), batch=batch, seq=seq
    )
    rows.append((
        "model/capture",
        "scenarios=4 streams="
        + " ".join(
            f"{k}:{len(s.streams)}" for k, s in sorted(sessions.items())
        ),
    ))

    # ---- per-scenario NoC runs on captured bytes (adapters + telemetry)
    noc_results = {}
    m44, r8 = mesh(4, 4), ring(8)

    w = sessions["serve_decode"].scenario_bytes("serve_decode", ["weights"])
    noc_results["serve_decode"] = _noc_run(
        m44,
        decode_weight_flows(
            jnp.asarray(w.view(np.int8)), m44, 0, (1, 2, 3), io_spec
        ),
        io_spec,
    )

    g = sessions["train_allreduce"].scenario_bytes("train_allreduce")
    noc_results["train_allreduce"] = _noc_run(
        r8, ring_allreduce_flows(jnp.asarray(g.view(np.int8)), r8, spec=io_spec),
        io_spec,
    )

    # the first expert layer's all-to-all over 8 chips of the mesh (the
    # smoke model's 8 experts, one a chip)
    route = sessions["moe_dispatch"].routes[0]
    moe_cfg = smoke_config(MOE_ARCH)
    noc_results["moe_dispatch"] = _noc_run(
        m44,
        moe_dispatch_flows(
            route.x, route.expert_ids, m44,
            num_experts=moe_cfg.moe.num_experts, chips=tuple(range(8)),
            spec=io_spec,
        ),
        io_spec,
    )

    # conv platform: REAL trained kernel bytes on the weight lanes, im2col
    # patches of the task images on the input lanes (paper §IV-B framing)
    kernel = sessions["lenet_conv"].scenario_bytes("lenet_conv", ["conv1"])
    patches = jnp.asarray(im2col(synth_images(1, seed=7)[0], 5))
    noc_results["lenet_conv"] = _noc_run(
        m44,
        conv_platform_flows(
            patches, jnp.asarray(kernel), m44, 0,
            [r for r in range(16) if r % 4], LinkSpec(),
        ),
        LinkSpec(),
    )

    # ---- per-scenario DSE grid over the captured workloads
    for scenario in sorted(sessions):
        sess = sessions[scenario]
        wl, evals = _evaluate(sess, scenario, activity_windows)
        noc_red, hot_link, _ = noc_results[scenario]
        records.append(
            _record(scenario, sess, evals, float(noc_red), hot_link)
        )
        base, acc, app, comp = evals
        rows.append((
            f"model/{scenario}/bt",
            f"streams={len(wl.streams)} flits={wl.num_flits} "
            f"bt_base={base.total_bt} red_acc={100 * acc.bt_reduction:.2f}% "
            f"red_app={100 * app.bt_reduction:.2f}% "
            f"red_composed={100 * comp.bt_reduction:.2f}% "
            f"E_app={app.energy_pj / 1e3:.1f}nJ",
        ))
        rows.append((
            f"model/{scenario}/noc",
            f"fabric_red_acc={100 * noc_red:.2f}% hot_link="
            + (
                f"{hot_link['src']}->{hot_link['dst']} "
                f"gross_bt={hot_link['gross_bt']}"
                if hot_link else "-"
            ),
        ))

    # ---- recalibration: trained-weight overall reductions, side by side
    # with the §10 synthetic numbers (table1_bt separate-stream framing:
    # captured task inputs on one link, captured trained weights on the
    # other; overall = 1 - (bi+bw)/(base_i+base_w))
    lsess = sessions["lenet_conv"]
    inp = np.asarray(
        lsess.packets("lenet_conv", ELEMS, names=["inputs"])
    )
    wgt = np.asarray(
        lsess.packets("lenet_conv", ELEMS, names=["conv1", "conv2"])
    )
    base_i = _measure_separate(inp, "none")
    base_w = _measure_separate(wgt, "none")
    recal = {}
    for strat, key in (("acc", "acc"), ("app", "app")):
        bi = _measure_separate(inp, key)
        bw = _measure_separate(wgt, key)
        red = 100 * (1 - (bi + bw) / (base_i + base_w))
        recal[strat] = {
            "captured_red": round(float(red), 2),
            "synthetic_red": SYNTHETIC_OVERALL[strat],
            "paper_red": PAPER_OVERALL[strat],
        }
        rows.append((
            f"model/recalib/{strat}",
            f"captured_red={red:.2f}% "
            f"synthetic_red={SYNTHETIC_OVERALL[strat]}% "
            f"paper_red={PAPER_OVERALL[strat]}% (trained LeNet streams)",
        ))

    # ---- the campaign artifacts (CSV table + JSON with recalibration)
    csv_path = "SCENARIOS_model_traffic.csv"
    json_path = "SCENARIOS_model_traffic.json"
    obs.write_scenarios_csv(csv_path, records)
    obs.write_scenarios_json(
        json_path, records, meta={"recalibration": recal},
    )
    rows.append((
        "model/artifact",
        f"{len(records)} scenario records -> {csv_path} + {json_path}",
    ))
    return rows
