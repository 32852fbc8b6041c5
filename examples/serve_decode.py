"""Serving example: batched prefill + decode with the paper's technique on
the serving data path.

Before serving, the contraction axes of every layer are popcount-ordered
(`apply_weight_ordering`) — a numeric no-op verified here by comparing the
generated tokens — and the modeled HBM weight-stream BT saving is reported
via the ``repro.link`` row-stream TX pipeline, with sign-magnitude recoding
(the beyond-paper encoding win).

    PYTHONPATH=src python examples/serve_decode.py

REPRO_BENCH_TINY=1 (the CI examples-smoke contract) caps the batch and
token counts and keeps the smoke config regardless of --full.
"""

import argparse
import dataclasses
import os

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.link import LinkSpec, TxPipeline
from repro.models import init_params
from repro.serve import generate
from repro.traffic import apply_weight_ordering, int8_view


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--full", action="store_true",
                    help="serve a ~100M config instead of the smoke config")
    args = ap.parse_args()

    if os.environ.get("REPRO_BENCH_TINY", "") not in ("", "0"):
        args.full = False
        args.batch = min(args.batch, 2)
        args.prompt_len = min(args.prompt_len, 8)
        args.new_tokens = min(args.new_tokens, 4)

    if args.full:
        cfg = get_config("internlm2-1.8b", n_layers=8, d_model=512, n_heads=8,
                         n_kv_heads=4, d_ff=2048, tie_embeddings=True,
                         attn_impl="dense", param_dtype="bfloat16")
    else:
        cfg = smoke_config("internlm2-1.8b")
    params = init_params(cfg, jax.random.key(0))
    ordered = apply_weight_ordering(params, cfg, "app")

    prompts = jax.random.randint(
        jax.random.key(1), (args.batch, args.prompt_len), 0, cfg.vocab
    )
    out_base = generate(params, cfg, prompts, args.new_tokens)
    out_ord = generate(ordered, cfg, prompts, args.new_tokens)
    same = np.array_equal(np.asarray(out_base.tokens), np.asarray(out_ord.tokens))
    print(f"generated {args.batch}x{args.new_tokens} tokens; "
          f"ordering-invariant: {same}")
    assert same

    print("\nmodeled decode weight-stream BT (per layer-0 tensor):")
    down = int8_view(params["layers"]["mlp"]["down"][0])  # (ff, d) wire image
    spec = LinkSpec(flits_per_packet=1, input_lanes=16, weight_lanes=0,
                    pack="col", k=4)
    for sm in (False, True):
        for strat in ("none", "app"):
            rep = TxPipeline(dataclasses.replace(
                spec,
                key="none" if strat == "none" else "row_bucket",
                encode="sign_magnitude" if sm else "identity",
            )).measure_rows(down, "mlp.down")
            print(f"  sign_magnitude={sm!s:5s} order={strat:4s} "
                  f"BT/flit={rep.overall_bt_per_flit:6.2f}")
    print("(sign-magnitude recoding ~halves BT; ordering adds a few % on "
          "magnitude-structured rows — benchmarks/arch_bt.py)")


if __name__ == "__main__":
    main()
