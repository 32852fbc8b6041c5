"""Beyond-paper: BT accounting on transformer traffic (the paper's §V
future work — 'extend the analysis to ResNets and Transformers').

Streams measured per architecture (smoke-scale weights, full-scale rules):
  * MLP weight stream (decode-dominant HBM traffic), two's-complement vs
    sign-magnitude, row/col layouts, ACC/APP row ordering;
  * MoE dispatch buffers (token sets per expert are unordered — the cleanest
    order-insensitivity in the zoo): token popcount-bucket ordering;
  * gradient egress int8 image with the weight-derived static permutation.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.kernels import bt_count
from repro.link import LinkSpec, TxPipeline, tensor_flit_stream
from repro.models import init_params
from repro.traffic import egress_permutation, int8_view, stream_bt_report

ARCHS = ["internlm2-1.8b", "qwen3-moe-30b-a3b", "mamba2-370m"]


def _structured_weight(rng, ff, d):
    """Trained-net-like weights: per-row lognormal scale structure."""
    return jnp.asarray(rng.normal(size=(ff, d)) * rng.lognormal(0, 1.0, (ff, 1)))


def run() -> list[tuple[str, str]]:
    rows = []
    rng = np.random.default_rng(0)

    # 1. weight streams: encoding x layout x ordering
    w = _structured_weight(rng, 1024, 256)
    for sm in (False, True):
        for layout in ("row", "col"):
            for strat in ("none", "acc", "app"):
                rep = stream_bt_report("w", w, strat, sign_magnitude=sm, layout=layout)
                rows.append((
                    f"arch_bt/weights/sm={int(sm)}/{layout}/{strat}",
                    f"bt/flit={rep.bt_ordered / rep.num_flits:.2f} "
                    f"red_vs_unordered={rep.reduction * 100:.2f}%",
                ))

    # 2. per-arch MLP weight-stream totals (iid-init weights: the honest
    #    negative control — near-zero ordering gain at row granularity)
    for arch in ARCHS:
        cfg = smoke_config(arch)
        params = init_params(cfg, jax.random.key(0))
        layer = jax.tree.map(lambda x: x[0], params["layers"])
        tensor = (
            layer["mlp"]["down"] if "mlp" in layer
            else layer["moe"]["down"].reshape(-1, cfg.d_model) if "moe" in layer
            else layer["ssd"]["out_proj"]
        )
        rep = stream_bt_report(arch, tensor, "app", sign_magnitude=True, layout="col")
        rows.append((
            f"arch_bt/{arch}/mlp_stream",
            f"bt_base={rep.bt_none} bt_app={rep.bt_ordered} "
            f"red={rep.reduction * 100:.2f}% (iid-init rows: expected ~0)",
        ))

    # 3. MoE dispatch buffer ordering: activations have token-norm structure
    #    (token rows are an unordered set -> row-bucket TX pipeline applies)
    toks = jnp.asarray(
        rng.normal(size=(256, 128)) * rng.lognormal(0, 0.8, (256, 1))
    )
    t8 = int8_view(toks)
    dispatch_spec = LinkSpec(
        flits_per_packet=1, input_lanes=16, weight_lanes=0,
        key="row_bucket", encode="sign_magnitude", pack="row", k=4,
    )
    base = TxPipeline(
        dataclasses.replace(dispatch_spec, key="none")
    ).measure_rows(t8, "moe_dispatch")
    ordered = TxPipeline(dispatch_spec).measure_rows(t8, "moe_dispatch")
    rows.append((
        "arch_bt/moe_dispatch/app",
        f"bt_base={base.total_bt} bt_ordered={ordered.total_bt} "
        f"red={100 * (1 - ordered.total_bt / base.total_bt):.2f}%",
    ))

    # 4. gradient egress image with weight-derived static permutation
    wflat = int8_view(jnp.asarray(rng.normal(size=(64 * 1024,))))
    perm, _ = egress_permutation(wflat, packet=64)
    g = int8_view(jnp.asarray(rng.normal(size=(64 * 1024,))))
    base = int(bt_count(tensor_flit_stream(g)))
    permuted = int(bt_count(tensor_flit_stream(g[jnp.asarray(perm)])))
    rows.append((
        "arch_bt/grad_egress/static_perm",
        f"bt_base={base} bt_perm={permuted} red={100 * (1 - permuted / base):.2f}% "
        "(uncorrelated grads: expected ~0 — recorded as the honest negative "
        "result; value-dependent per-step sorting would desynchronise the "
        "reduction, see DESIGN.md §8)",
    ))
    return rows
