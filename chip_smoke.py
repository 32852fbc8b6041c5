#!/usr/bin/env python3
"""Chip smoke: run the link-power simulator's main path once on a TPU.

    python chip_smoke.py [--seed N] [--four-chips]

With no option it needs one TPU chip and runs these phases in this one
process, on the ``pallas`` kernel backend (no interpreter, no CPU
fallback):

  device  jax version and devices; the platform must be ``tpu`` and the
          kernel backend ``pallas``.
  paper   the in-repo LeNet trained from ``--seed`` (never restored from a
          checkpoint), its conv traffic captured and measured through
          ``TxPipeline`` (none / acc / app; the fused kernel for acc and
          app) and ``dse.evaluate_grid`` with activity windows.  The ACC
          and APP reductions print beside the paper's.
  fleet   ``simulate_noc`` on ``benchmarks/fleet_noc.py``'s default
          fabric: a 16x16 mesh carrying 16 users x 16 layers x 4 shards
          = 1024 decode flows, for none / acc / app source sorting.
  long    one qwen3-4b decoder layer's projection weights at the
          published widths (about 101 M values) drawn from ``--seed``,
          quantized tensor by tensor by the Pallas int8 quantizer and
          measured as one byte stream by the chunked ordering x codec
          grid of ``bt_count_axes``.

Every integer result is checked for equality against the ``compiled``
backend on the same chip, and against ``repro.kernels.ref`` where a
reference exists (whole streams, or a prefix of the long one).  Each phase
prints one line with its outcome, its cold wall time (compilation
included) and one warm wall time: smoke readings, not benchmark metrics.

``--four-chips`` runs only the multi-chip phase: the fleet's link queues
through ``bt_count_axes_sharded`` over four devices, compared bit for bit
with ``bt_count_axes`` on one device.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failure raises before it is printed and the exit code is non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(REPO, "src"), REPO]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from benchmarks.run import enable_compilation_cache  # noqa: E402
from repro.kernels import (  # noqa: E402
    BACKEND_ENV_VAR,
    CodecVariant,
    default_backend,
    force_default_backend,
)

ORDERINGS = ("none", "acc", "app")
PAPER_RED = {"acc": 20.42, "app": 19.50}  # paper Table I overall reductions
ELEMS, LANES = 64, 16  # measured framing: 4 flits x 16 input lanes
ACTIVITY_WINDOWS = 32  # flit rows per activity window (model_traffic's)
# the long stream's ordering x codec grid, one launch per chunk
LONG_CONFIGS = (
    CodecVariant("none"),
    CodecVariant("acc"),
    CodecVariant("app", 4),
    CodecVariant("acc", codec="gray"),
    CodecVariant("acc", codec="transition"),
    CodecVariant("app", 4, codec="bus_invert", partition=4),
)
CHUNK_PACKETS = 65536  # 4 MiB of int8 bytes per chunk
REF_PACKETS = 4096  # long-stream prefix checked against kernels/ref.py
FLEET = {"users": 16, "layers": 16, "shards": 4, "rows": 16, "cols": 16}


def qwen3_layer_shapes() -> dict[str, tuple[int, int]]:
    """The seven projection matrices of one qwen3-4b decoder layer at the
    published widths (``repro.configs.qwen3_4b``)."""
    from repro.configs.qwen3_4b import CONFIG as c

    q, kv = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
    return {
        "wq": (c.d_model, q),
        "wk": (c.d_model, kv),
        "wv": (c.d_model, kv),
        "wo": (q, c.d_model),
        "w_gate": (c.d_model, c.d_ff),
        "w_up": (c.d_model, c.d_ff),
        "w_down": (c.d_ff, c.d_model),
    }


def _timed(fn):
    """(result, seconds) of one call, waited for on the device."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _run_phase(name: str, work, check) -> None:
    """Run ``work`` cold and warm, then ``check`` its result; print one
    line.  ``check`` raises on any mismatch and returns a summary."""
    cold_out, cold = _timed(work)
    warm_out, warm = _timed(work)
    _require(_same(cold_out, warm_out), f"{name}: warm run != cold run")
    summary = check(warm_out)
    print(
        f"phase {name}: ok cold_s={cold:.3f} warm_s={warm:.3f} {summary}",
        flush=True,
    )


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _same(a, b) -> bool:
    """Exact equality of two results: same tree, equal leaves."""
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    return ta == tb and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _red(bt: int, base: int) -> float:
    return 100.0 * (1.0 - bt / max(base, 1))


# --------------------------------------------------------------------------


def device_phase(expect: int) -> dict:
    devs = jax.devices()
    print(f"jax {jax.__version__} devices={devs}", flush=True)
    dev = devs[0]
    print(f"device_kind={dev.device_kind!r}", flush=True)
    _require(dev.platform == "tpu", f"no TPU: platform is {dev.platform!r}")
    _require(len(devs) >= expect, f"need {expect} chips, found {len(devs)}")
    env = os.environ.get(BACKEND_ENV_VAR, "")
    _require(
        env in ("", "pallas"), f"${BACKEND_ENV_VAR}={env!r} hides the kernel"
    )
    _require(
        default_backend() == "pallas",
        f"default kernel backend is {default_backend()!r}",
    )
    print("phase device: ok backend=pallas", flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def paper_phase(seed: int) -> None:
    from benchmarks.model_traffic import _POINTS
    from benchmarks.table1_bt import _input_only_spec
    from repro import obs
    from repro.dse import evaluate_grid
    from repro.kernels.ref import bt_codecs_ref, psu_stream_ref
    from repro.link import LinkSpec, TxPipeline
    from repro.models import lenet

    params, _ = lenet.train_lenet(steps=300, seed=seed, ckpt_dir=None)
    sess = obs.capture_lenet_conv(params=params, seed=seed)
    # separate-stream framing (16 input lanes, the recalibration framing)
    sep = {
        "inputs": jnp.asarray(sess.packets("lenet_conv", ELEMS,
                                           names=["inputs"])),
        "weights": jnp.asarray(sess.packets("lenet_conv", ELEMS,
                                            names=["conv1", "conv2"])),
    }
    # the paper's paired framing: 8 input + 8 weight lanes per flit
    pspec = LinkSpec()
    pin = jnp.asarray(sess.packets("lenet_conv", pspec.elems_per_packet,
                                   names=["inputs"]))
    kbytes = sess.scenario_bytes("lenet_conv", ["conv1", "conv2"])
    pwt = jnp.asarray(np.resize(kbytes, pin.size).reshape(pin.shape))
    wl = sess.workload("lenet_conv", elems=ELEMS, lanes=LANES)

    def work():
        out = {}
        for side, x in sep.items():
            for k in ORDERINGS:
                r = TxPipeline(_input_only_spec(k, ELEMS, LANES)).measure(x)
                out[f"{side}/{k}"] = (r.input_bt, r.weight_bt, int(r.fused))
        for k in ORDERINGS:
            r = TxPipeline(LinkSpec(key=k)).measure(pin, pwt)
            out[f"paired/{k}"] = (r.input_bt, r.weight_bt, int(r.fused))
        evals = evaluate_grid(_POINTS, wl, activity_windows=ACTIVITY_WINDOWS)
        out["grid"] = [(e.total_bt, e.aux_bt, e.per_wire_bt) for e in evals]
        return out

    def check(out):
        with force_default_backend("compiled"):
            _require(_same(work(), out), "paper: pallas != compiled")
        for k in ("acc", "app"):
            _require(out[f"paired/{k}"][2] == 1, f"paper: {k} not fused")
            kk = None if k == "acc" else pspec.k
            fused = TxPipeline(LinkSpec(key=k)).run(pin, pwt)
            ref = psu_stream_ref(pin, pwt, k=kk, input_lanes=8)
            _require(
                _same((fused.order, fused.stream, fused.bt_input,
                       fused.bt_weight), (ref[0], ref[2], ref[3], ref[4])),
                f"paper: fused {k} != kernels/ref.py",
            )
        for pt, (total, aux, _) in zip(_POINTS, out["grid"]):
            ref = sum(
                np.asarray(bt_codecs_ref(s, None, (pt.codec_variant,),
                                         input_lanes=LANES))[0]
                for s in wl.streams
            )
            _require((int(ref[0] + ref[1]), int(ref[2])) == (total, aux),
                     f"paper: grid point {pt.label} != kernels/ref.py")
        reds = {}
        for k in ("acc", "app"):
            bt = sum(sum(out[f"{s}/{k}"][:2]) for s in sep)
            base = sum(sum(out[f"{s}/none"][:2]) for s in sep)
            reds[k] = _red(bt, base)
        paired = {
            k: _red(sum(out[f"paired/{k}"][:2]), sum(out["paired/none"][:2]))
            for k in ("acc", "app")
        }
        return (
            f"packets={int(sep['inputs'].shape[0])}+"
            f"{int(sep['weights'].shape[0])} "
            f"acc_red={reds['acc']:.2f}% (paper {PAPER_RED['acc']}%) "
            f"app_red={reds['app']:.2f}% (paper {PAPER_RED['app']}%) "
            f"paired_acc={paired['acc']:.2f}% paired_app={paired['app']:.2f}% "
            f"grid_points={len(out['grid'])} == compiled == ref"
        )

    _run_phase("paper", work, check)


def _fleet(seed: int):
    """(topology, flows, spec factory) of fleet_noc's default fabric."""
    from repro.link import LinkSpec
    from repro.noc import fleet_decode_flows, mesh

    topo = mesh(FLEET["rows"], FLEET["cols"])
    weights = np.random.default_rng(seed).integers(
        0, 256, (1 << 16,), dtype=np.uint8
    )

    def spec(key):  # one-sided weight broadcast: 16 payload lanes per flit
        return LinkSpec(input_lanes=16, weight_lanes=0, key=key)

    flows = fleet_decode_flows(
        jnp.asarray(weights), topo, users=FLEET["users"],
        layers=FLEET["layers"], shards=FLEET["shards"], spec=spec("acc"),
    )
    return topo, flows, spec


def _fleet_queues(topo, flows, spec):
    """The fleet's distinct link queues: (L, T, lanes) streams + lengths."""
    from repro.noc import FlowBatch, compile_fabric, expand_fabric

    plan = compile_fabric(topo, [(f.src, f.dsts) for f in flows])
    batch = FlowBatch.from_flows(flows, spec)
    fs = expand_fabric(plan, batch, spec, sort_at="source")
    return fs.streams, fs.lengths


def fleet_phase(seed: int) -> None:
    from repro.kernels import bt_count_links
    from repro.kernels.ref import bt_count_ref
    from repro.noc import simulate_noc

    topo, flows, spec = _fleet(seed)

    def work():
        return {
            k: [
                (s.link, s.num_flits, s.bt_input, s.bt_weight, s.bt_aux)
                for s in simulate_noc(topo, flows, spec(k),
                                      sort_at="source").links
            ]
            for k in ORDERINGS
        }

    def check(out):
        with force_default_backend("compiled"):
            _require(_same(work(), out), "fleet: pallas != compiled")
        streams, lengths = _fleet_queues(topo, flows, spec("acc"))
        got = np.asarray(bt_count_links(streams, input_lanes=16,
                                        lengths=lengths))
        lens = np.asarray(lengths)
        for q in range(streams.shape[0]):
            ref = int(bt_count_ref(streams[q, : int(lens[q])]))
            _require(int(got[q].sum()) == ref,
                     f"fleet: queue {q} != kernels/ref.py")
        total = {k: sum(s[2] + s[3] for s in out[k]) for k in ORDERINGS}
        return (
            f"flows={len(flows)} links={len(out['none'])} "
            f"queues={int(streams.shape[0])}x{int(streams.shape[1])} "
            f"bt_none={total['none']} "
            f"acc_red={_red(total['acc'], total['none']):.2f}% "
            f"app_red={_red(total['app'], total['none']):.2f}% "
            "== compiled == ref"
        )

    _run_phase("fleet", work, check)


def long_phase(seed: int) -> None:
    from repro.kernels import bt_count_axes, quantize_egress
    from repro.kernels.ref import bt_codecs_ref

    shapes = qwen3_layer_shapes()
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    weights = jax.block_until_ready([
        (0.02 * jax.random.normal(kk, shp, jnp.float32)).reshape(-1)
        for kk, shp in zip(keys, shapes.values())
    ])
    nvalues = sum(int(w.size) for w in weights)

    def quantize():  # per tensor, as a deployment quantizes its weights
        qs, ss = zip(*(quantize_egress(w)[:2] for w in weights))
        return jnp.concatenate(qs), jnp.concatenate(ss)

    def measure(q):
        packets = lax.bitcast_convert_type(q, jnp.uint8).reshape(1, -1, ELEMS)
        return bt_count_axes(packets, configs=LONG_CONFIGS, input_lanes=LANES,
                             chunk_packets=CHUNK_PACKETS)

    def work():
        q, scales = quantize()
        return q, scales, measure(q)

    def check(out):
        q, scales, bt = out
        with force_default_backend("compiled"):
            q_c, s_c = quantize()
            _require(bool(jnp.array_equal(q_c, q)),
                     "long: int8 codes pallas != compiled")
            _require(bool(jnp.array_equal(s_c, scales)),
                     "long: scales pallas != compiled")
            _require(_same(measure(q), bt), "long: BT pallas != compiled")
        prefix = lax.bitcast_convert_type(
            q[: REF_PACKETS * ELEMS], jnp.uint8
        ).reshape(REF_PACKETS, ELEMS)
        ref = bt_codecs_ref(prefix, None, LONG_CONFIGS, input_lanes=LANES)
        got = bt_count_axes(prefix[None], configs=LONG_CONFIGS,
                            input_lanes=LANES)[0]
        _require(_same(got, ref), "long: prefix BT != kernels/ref.py")
        bt = np.asarray(bt)[0]
        base = int(bt[0].sum())
        reds = " ".join(
            f"{c.key}{c.k or ''}+{c.codec}={_red(int(r.sum()), base):.2f}%"
            for c, r in zip(LONG_CONFIGS[1:], bt[1:])
        )
        return (
            f"values={nvalues} packets={nvalues // ELEMS} "
            f"bt_none={base} {reds} == compiled; prefix == ref"
        )

    _run_phase("long", work, check)


def four_chip_phase(seed: int) -> None:
    from repro.kernels import bt_count_axes, bt_count_axes_sharded

    topo, flows, spec = _fleet(seed)
    streams, lengths = _fleet_queues(topo, flows, spec("acc"))
    kw = dict(valid=lengths, configs=LONG_CONFIGS, input_lanes=16,
              pack="row", block_packets=512)
    devices = jax.devices()[:4]

    def work():
        return bt_count_axes_sharded(streams, devices=devices, **kw)

    def check(out):
        single = bt_count_axes(streams, **kw)
        _require(_same(single, out), "four-chips: sharded != one device")
        return (
            f"devices={len(devices)} links={int(streams.shape[0])} "
            f"configs={len(LONG_CONFIGS)} bt_total={int(np.asarray(out).sum())}"
            " sharded == one device"
        )

    _run_phase("four_chips", work, check)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded link-axis phase on 4 chips")
    args = ap.parse_args(argv)
    enable_compilation_cache()
    device = device_phase(4 if args.four_chips else 1)
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        paper_phase(args.seed)
        fleet_phase(args.seed)
        long_phase(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
