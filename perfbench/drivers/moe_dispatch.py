"""Expert-parallel prefill dispatch: one MoE layer's all-to-all on a fabric.

Set-up draws the configuration's weights (normal(0, initializer_range) in
its type, norms 1, no output head) and the token ids (i.i.d. Zipf over a
seeded permutation of the vocabulary, the configuration's
``prefill_tokens_per_chip`` for each chip of the ``rows`` x ``cols``
slice), runs the model's forward once under ``repro.obs.capture`` and keeps, for every MoE layer, the captured rows,
routing and router probabilities on the device; the weights are dropped.
Every report shape is then warmed, one report per layer.

Report ``i`` takes MoE layer ``i mod layers``: the rows' int8 wire image
(``repro.kernels.quantize_egress``, blocks of ``quant_block``), the
expert-parallel flows (``repro.noc.moe_dispatch_flows``: chip ``c`` sends
to the chip of each expert its tokens routed there) and one
``repro.noc.simulate_noc`` per ordering, sorting at the source.  The
report ends when every link's numbers are on the host.  Its events are
the orderings times the flit rows of the flows that cross the fabric,
counted from the captured routing.

The check compares, exactly, every link's flits and BT of every report
of one layer drawn from the seed with a reference that re-quantizes the
captured rows (``reference.quantize_blocks``), builds the flows from the
captured expert ids, routes them XY and queues them in injection order;
and the model: the captured MoE inputs and router probabilities of every
layer against ``reference_deepseek_v2`` in float32 on the same seed and
tokens, within the limits below, with the top-k sets equal wherever the
reference's k-th and (k+1)-th probabilities lie further apart than
``TOPK_MARGIN``, and every token's picks a top-k of its own captured
probabilities.
"""

from __future__ import annotations

import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import reference as ref
from perfbench import reference_deepseek_v2 as ref_model
from perfbench import work
from perfbench.drivers.noc_fleet import xy_links
from perfbench.harness import Compared
from repro.configs.deepseek_v2_lite import from_hf
from repro.kernels import quantize_egress
from repro.link import LinkSpec
from repro.models import param_shapes
from repro.noc import compile_fabric, mesh, moe_dispatch_flows, simulate_noc
from repro.obs import capture_moe_dispatch

# The model comparison's limits, for bfloat16 compute against the float32
# reference, from readings at the cell's own size on one TPU v5e over 13
# seeds (PERF.md section 6): the MoE inputs' largest relative Frobenius
# error over the 4 expert layers reads 1.26-1.33 % in bfloat16 and 2.654 %
# with the inputs in float8 e4m3 (the control); the limit lies between,
# about 1.4 times from each.
ROWS_REL_ERR = 0.019
# The router probabilities' largest absolute error reads 0.0067-0.0099 in
# bfloat16 and 0.0138-0.0174 in the control, which fails by its rows; the
# limit lies between, nearer the control, since this reading is a maximum
# over 2 M probabilities and its tail is long.
PROBS_ABS_ERR = 0.013
# Top-k sets are compared where the reference's k-th probability beats the
# (k+1)-th by more than twice what the probabilities may move.  Everywhere
# else the picks must be a top-k of the program's own probabilities
# (``picks_not_topk``, exactly): with those within PROBS_ABS_ERR of the
# reference, no pick then lies more than TOPK_MARGIN below the
# reference's k-th probability, so only near-ties may swap.
TOPK_MARGIN = 2 * PROBS_ABS_ERR


@partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, *, shape, std: float, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make_params(key, *, cfg, std: float):
    """The model's parameters but the output head: normal(0, std) in the
    configuration's parameter type, norms 1.  One draw per leaf, each its
    own program, so that only one leaf's float32 temporaries exist at a
    time."""
    shapes = dict(param_shapes(cfg))
    shapes.pop("head", None)
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        if "norm" in jax.tree_util.keystr(path):
            out.append(jnp.ones(leaf.shape, leaf.dtype))
        else:
            out.append(jax.block_until_ready(_normal(
                k, shape=leaf.shape, std=std, dtype=jnp.dtype(leaf.dtype))))
    return jax.tree_util.tree_unflatten(tree, out)


def zipf_tokens(seed: int, shape: tuple[int, ...], vocab: int,
                exponent: float) -> np.ndarray:
    """I.i.d. token ids with Zipf frequencies over a seeded permutation of
    the vocabulary."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(vocab)
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -exponent
    return perm[rng.choice(vocab, size=shape, p=p / p.sum())].astype(np.int32)


def dispatch_pairs(ids: np.ndarray, chips: int, experts: int):
    """(source chip, expert, tokens) of every non-empty flow, in injection
    order (source-major, expert-minor), with ``tokens`` the source chip's
    token indices routed to the expert, in order."""
    t = ids.shape[0]
    per, held = t // chips, experts // chips
    out = []
    for src in range(chips):
        part = ids[src * per:(src + 1) * per]
        for e in range(experts):
            tok = src * per + np.nonzero((part == e).any(axis=1))[0]
            if tok.size:
                out.append((src, e, tok))
    return [(s, e, tok, e // held) for s, e, tok in out]


def _popcount8(x: np.ndarray) -> np.ndarray:
    return np.unpackbits(x.astype(np.uint8)[..., None], axis=-1).sum(
        axis=-1, dtype=np.int64)


@partial(jax.jit, static_argnames=("ordering", "k"))
def _send_order(packets, ordering: str, k):
    """(P, N) packets with each one's elements in the order sent."""
    return ref.in_send_order(packets, ordering, k)[0]


def reference_links(codes: np.ndarray, ids: np.ndarray, p: dict,
                    ordering: str, k: int) -> dict:
    """{(src, dst): (flits, BT)} of every active link, from the rows'
    (T, d) uint8 codes and the (T, k) expert ids.

    With the sorting unit at the source, each packet leaves its chip with
    one wire image, so a link's BT is its packets' own transitions plus
    one transition count at each seam between consecutive packets of its
    queue (``test_moe_dispatch.py`` pins this against ``stream_bt`` of
    the concatenated queue)."""
    t, d = codes.shape
    elems, lanes = p["elems"], p["lanes"]
    f = elems // lanes
    per_row = d // elems
    packets = jnp.asarray(codes.reshape(t * per_row, elems))
    sent = np.asarray(_send_order(
        packets, ordering, k if ordering == "app" else None))
    # (P, F, lanes): element j of a packet rides lane j // F in its flit
    # j % F, the layout of ``reference.lane_flits`` (built on the host:
    # the TPU compiler takes minutes over that gather at this size)
    image = sent.reshape(-1, lanes, f).transpose(0, 2, 1)
    inner = _popcount8(image[:, 1:] ^ image[:, :-1]).sum(axis=(1, 2))
    first, last = image[:, 0], image[:, -1]
    chips = p["rows"] * p["cols"]
    queues: dict[tuple[int, int], list[np.ndarray]] = {}
    for src, _, tok, dst in dispatch_pairs(ids, chips, p["experts"]):
        if src == dst:
            continue
        pk = (tok[:, None] * per_row + np.arange(per_row)).reshape(-1)
        for hop in xy_links(p["cols"], src, dst):
            queues.setdefault(hop, []).append(pk)
    out = {}
    for hop, parts in queues.items():
        seq = np.concatenate(parts)
        seams = _popcount8(last[seq[:-1]] ^ first[seq[1:]]).sum()
        out[hop] = (seq.size * f, int(inner[seq].sum() + seams))
    return out


class MoeDispatch:
    def __init__(self, config: dict, traffic: dict, seed: int, span):
        self.span = span
        self.p = p = dict(traffic)
        self.config = config
        self.seed = seed
        dtype = config["torch_dtype"]
        self.cfg = from_hf(config, dtype=dtype, param_dtype=dtype)
        self.topo = mesh(p["rows"], p["cols"])
        self.chips = p["rows"] * p["cols"]
        self.experts = p["experts"] = config["n_routed_experts"]
        self.k = p["app_k"]
        self.orderings = tuple(p["orderings"])
        self.specs = {
            key: LinkSpec(width_bits=8 * p["lanes"],
                          flits_per_packet=p["elems"] // p["lanes"],
                          input_lanes=p["lanes"], weight_lanes=0,
                          key=key, k=self.k)
            for key in self.orderings
        }
        self.tokens = zipf_tokens(
            seed, (self.chips, config["prefill_tokens_per_chip"]),
            config["vocab_size"], p["zipf_exponent"])
        t0 = time.perf_counter()
        with span("model.capture"):
            params = make_params(jax.random.key(seed), cfg=self.cfg,
                                 std=config["initializer_range"])
            routes = capture_moe_dispatch(
                self.cfg, params=params, tokens=jnp.asarray(self.tokens)
            ).routes
            self.routes = jax.block_until_ready(
                [(r.x, r.expert_ids, r.probs) for r in routes])
            del params, routes
        _progress(f"weights and capture {time.perf_counter() - t0:.1f} s")
        self.layers = len(self.routes)
        ids = [np.asarray(r[1]) for r in self.routes]
        self.layer_events = [self._events(i) for i in ids]
        self.layer_bytes = [self._links_bytes(i) for i in ids]
        self.load = [self._load(i) for i in ids]
        self.records: list[tuple[int, dict]] = []
        self._reference = None
        self.topk_covered = (0, 0)  # tokens whose top-k sets are compared
        for layer in range(self.layers):
            self._produce(layer)  # compiles every program a report runs
            _progress(f"layer {layer} warmed at {time.perf_counter() - t0:.1f} s")

    # ---- work counts, from the captured routing
    def _fabric_pairs(self, ids):
        return [x for x in dispatch_pairs(ids, self.chips, self.experts)
                if x[0] != x[3]]

    def _events(self, ids) -> int:
        rows = sum(tok.size for *_, tok, _ in self._fabric_pairs(ids))
        d = self.cfg.d_model
        return len(self.orderings) * work.flit_rows(
            rows * d, self.p["elems"], self.p["lanes"])

    def _links_bytes(self, ids) -> int:
        """Least HBM bytes of a report's ``bt_count_links`` launches: every
        distinct queue's uint8 flit rows read once and its (2,) int32 BT
        row written once, per ordering."""
        pairs = self._fabric_pairs(ids)
        plan = compile_fabric(self.topo, [(s, (d,)) for s, _, _, d in pairs])
        f = self.p["elems"] // self.p["lanes"]
        per_row = self.cfg.d_model // self.p["elems"]
        flits = [sum(pairs[i][2].size * per_row * f for i in q)
                 for q in plan.queues]
        return len(self.orderings) * (sum(flits) * self.p["lanes"]
                                      + 8 * len(flits))

    def _load(self, ids) -> tuple[int, float, int]:
        """Tokens of the busiest expert, the mean, fabric flows."""
        per_expert = np.bincount(ids.reshape(-1), minlength=self.experts)
        return (int(per_expert.max()), float(per_expert.mean()),
                len(self._fabric_pairs(ids)))

    @property
    def events_per_report(self) -> float:
        done = [self.layer_events[layer] for layer, _ in self.records]
        return sum(done) / max(len(done), 1)

    @property
    def work(self) -> dict:
        # traced reports cycle the layers evenly (traced_reports is a
        # multiple of the layer count), so the mean over layers is theirs
        return {"links_wire_bytes": sum(self.layer_bytes) / self.layers}

    # ---- a report
    def codes(self, layer: int) -> jax.Array:
        x = self.routes[layer][0]
        q, _, _ = quantize_egress(x.reshape(-1), self.p["quant_block"])
        return q[:x.size].reshape(x.shape)

    def _produce(self, layer: int) -> dict:
        with self.span("kernels.quantize"):
            codes = self.codes(layer)
        with self.span("noc.flows"):
            flows = moe_dispatch_flows(
                codes, self.routes[layer][1], self.topo,
                num_experts=self.experts, spec=self.specs[self.orderings[0]])
        out = {}
        for key in self.orderings:
            with self.span("noc.simulate"):
                rep = simulate_noc(self.topo, flows, self.specs[key],
                                   sort_at=self.p["sort_at"],
                                   chunk_rows=self.p["chunk_rows"])
            out[key] = {(s.src, s.dst): (s.num_flits, s.bt_input + s.bt_weight)
                        for s in rep.links}
        return out

    def report(self, i: int) -> None:
        layer = i % self.layers
        self.records.append((layer, self._produce(layer)))

    # ---- the comparison
    def check(self, rng) -> tuple[list[Compared], int]:
        done = sorted({layer for layer, _ in self.records})
        layer = int(rng.choice(done))
        t0 = time.perf_counter()
        links, wrong = self.compare_links(layer)
        _progress(f"links of layer {layer} checked in "
                  f"{time.perf_counter() - t0:.1f} s")
        model, bad_layers = self.compare_model(self.program_route())
        covered, total = self.topk_covered
        _progress(f"model checked at {time.perf_counter() - t0:.1f} s; "
                  f"top-k sets compared on {covered} of {total} tokens")
        return [Compared("links_differ", links, 0)] + model, wrong + bad_layers

    def compare_links(self, layer: int) -> tuple[int, int]:
        """Link numbers that differ over every report of ``layer``, and the
        reports in which any does."""
        x, ids, _ = self.routes[layer]
        codes = np.asarray(ref.quantize_blocks(
            x.reshape(-1), self.p["quant_block"])[0]).view(np.uint8)
        codes = codes.reshape(x.shape)
        ids = np.asarray(ids)
        expect = {key: reference_links(codes, ids, self.p, key, self.k)
                  for key in self.orderings}
        links = wrong = 0
        for l, got in self.records:
            if l != layer:
                continue
            bad = sum(
                len(set(got[key]) ^ set(expect[key]))
                + sum(got[key][h] != v for h, v in expect[key].items()
                      if h in got[key])
                for key in self.orderings
            )
            links += bad
            wrong += bad > 0
        return links, wrong

    def program_route(self) -> list[tuple[np.ndarray, ...]]:
        return [(np.asarray(x, np.float32), np.asarray(i), np.asarray(pr))
                for x, i, pr in self.routes]

    def reference(self) -> tuple[dict, list]:
        """The float32 reference forward on this run's seed and tokens,
        and each MoE layer's router weights (computed once)."""
        if self._reference is None:
            params = make_params(jax.random.key(self.seed), cfg=self.cfg,
                                 std=self.config["initializer_range"])
            want = ref_model.forward(params, self.config,
                                     jnp.asarray(self.tokens))
            routers = [params["layers"]["moe"]["router"][i]
                       for i in range(self.layers)]
            self._reference = (want, routers)
        return self._reference

    def compare_model(self, got) -> tuple[list[Compared], int]:
        """``got``'s (rows, expert ids, probabilities) of every MoE layer
        against the float32 reference: the largest relative row error and
        absolute probability error over the layers, the tokens whose top-k
        sets differ where the reference's margin exceeds ``TOPK_MARGIN``,
        the tokens whose picks are not a top-k of their own probabilities,
        and the layers outside any limit."""
        want, _ = self.reference()
        return self._score(got, want["moe"])

    def _score(self, got, want) -> tuple[list[Compared], int]:
        rows = probs = 0.0
        flips = stray = bad = covered = total = 0
        for (x, ids, pr), w in zip(got, want):
            wx, wp = np.asarray(w["x"]), np.asarray(w["probs"])
            r = float(np.linalg.norm(x - wx) / np.linalg.norm(wx))
            e = float(np.abs(pr - wp).max())
            clear = np.asarray(w["margin"]) > TOPK_MARGIN
            same = (np.sort(ids, -1) == np.sort(np.asarray(w["expert_ids"]),
                                                -1)).all(-1)
            f = int((clear & ~same).sum())
            # the picks must be a top-k of the program's own probabilities
            rest = pr.copy()
            np.put_along_axis(rest, ids, -np.inf, -1)
            n = int((rest.max(-1) > np.take_along_axis(pr, ids, -1).min(-1)
                     ).sum())
            rows, probs = max(rows, r), max(probs, e)
            flips, stray = flips + f, stray + n
            covered, total = covered + int(clear.sum()), total + clear.size
            bad += r > ROWS_REL_ERR or e > PROBS_ABS_ERR or f > 0 or n > 0
        self.topk_covered = (covered, total)
        return [Compared("rows_rel_err", rows, ROWS_REL_ERR),
                Compared("probs_abs_err", probs, PROBS_ABS_ERR),
                Compared("topk_flips", flips, 0),
                Compared("picks_not_topk", stray, 0)], bad

    def control(self) -> tuple[list[Compared], int]:
        """The reference with its MoE inputs in float8 (e4m3), one step of
        precision below bfloat16, put in the program's place: rows cast
        down and back, probabilities and top-k recomputed from them."""
        want, routers = self.reference()
        got = []
        with jax.default_matmul_precision("highest"):
            for w, router in zip(want["moe"], routers):
                x = jnp.asarray(w["x"]).astype(jnp.float8_e4m3fn).astype(
                    jnp.float32)
                pr = jax.nn.softmax(x @ router.astype(jnp.float32), -1)
                ids = jax.lax.top_k(pr, self.cfg.moe.top_k)[1]
                got.append((np.asarray(x), np.asarray(ids), np.asarray(pr)))
        return self._score(got, want["moe"])

    def notes(self) -> list[str]:
        lines = [f"layer {l}: tokens per expert max {mx} mean {mean:.1f}, "
                 f"{n} fabric flows, {e} events"
                 for l, ((mx, mean, n), e) in enumerate(
                     zip(self.load, self.layer_events))]
        covered, total = self.topk_covered
        if total:
            lines.append(f"top-k sets compared on {covered} of {total} "
                         f"tokens ({100 * covered / total:.1f}%)")
        if self.records:
            layer, got = self.records[-1]
            total = {k: sum(bt for _, bt in got[k].values()) for k in got}
            base = max(total.get("none", 0), 1)
            lines.append(f"layer {layer}: " + " ".join(
                f"{k}: bt={v} ({100 * (1 - v / base):.2f}%)"
                for k, v in total.items()))
        return lines


def _progress(what: str) -> None:
    print(f"moe_dispatch: {what}", file=sys.stderr, flush=True)


def setup(config: dict, traffic: dict, seed: int, span) -> MoeDispatch:
    return MoeDispatch(config, traffic, seed, span)
