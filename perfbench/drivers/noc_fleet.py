"""Multi-tenant decode fleet: a decoder layer's weights over a NoC mesh.

Set-up draws the configuration's layer weights as ``weight_grid`` does.
Report ``i`` quantizes layer ``i mod layers`` tensor by tensor, builds the
fleet's flows from those int8 bytes (``repro.noc.fleet_decode_flows``:
users x layers x shards weight-slice multicasts from each tenant's memory
router to its row's PE columns) and runs ``repro.noc.simulate_noc`` once
per ordering, sorting at the source.  The report ends when every link's
numbers are on the host.

The check rebuilds each checked report's flows from the reference's own
quantized bytes, routes them (XY, column first; a multicast crosses each
link of its tree once), queues each link's flows in injection order and
compares every link's flits and BT with the program's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import reference as ref
from perfbench import work
from perfbench.drivers import weight_grid
from perfbench.harness import Compared
from repro.kernels import quantize_egress
from repro.link import LinkSpec
from repro.noc import fleet_decode_flows, mesh, simulate_noc

STRIDE = 7919  # flow f's payload starts at (f * STRIDE) mod (span + 1)


def fleet_flows(data: np.ndarray, rows: int, cols: int, users: int,
                layers: int, shards: int, need: int):
    """(src, dsts, byte offset) of every flow, in injection order."""
    span = data.size - need
    pe_cols = cols - 1
    out = []
    for u in range(users):
        row = u % rows
        for layer in range(layers):
            for s in range(shards):
                lo, hi = s * pe_cols // shards, (s + 1) * pe_cols // shards
                dsts = tuple(row * cols + 1 + c for c in range(lo, hi))
                fi = (u * layers + layer) * shards + s
                off = 0 if span == 0 else (fi * STRIDE) % (span + 1)
                out.append((row * cols, dsts, off))
    return out


def xy_links(cols: int, src: int, dst: int) -> list[tuple[int, int]]:
    """Directed (router, router) hops of the mesh's XY route."""
    r, c = divmod(src, cols)
    dr, dc = divmod(dst, cols)
    hops, here = [], src
    while c != dc:
        c += 1 if dc > c else -1
        hops.append((here, r * cols + c))
        here = r * cols + c
    while r != dr:
        r += 1 if dr > r else -1
        hops.append((here, r * cols + c))
        here = r * cols + c
    return hops


def reference_links(data: np.ndarray, p: dict, key: str, k: int) -> dict:
    """{(src, dst): (flits, BT)} of every active link, from the bytes."""
    need = p["packets_per_flow"] * p["elems"]
    if data.size < need:
        data = np.tile(data, -(-need // data.size))
    queues: dict[tuple[int, int], list[np.ndarray]] = {}
    for src, dsts, off in fleet_flows(data, p["rows"], p["cols"], p["users"],
                                      p["layers"], p["shards"], need):
        tree = dict.fromkeys(h for d in dsts for h in xy_links(p["cols"], src, d))
        pk = data[off:off + need].reshape(p["packets_per_flow"], p["elems"])
        for hop in tree:
            queues.setdefault(hop, []).append(pk)
    design = ref.Design(key, k if key == "app" else None)
    out = {}
    for hop, pkts in queues.items():
        stream = np.concatenate(pkts)
        bt = np.asarray(ref.stream_bt(stream, (design,), p["lanes"]))[0, 0]
        out[hop] = (stream.shape[0] * (p["elems"] // p["lanes"]), int(bt))
    return out


class NocFleet:
    def __init__(self, config: dict, traffic: dict, seed: int, span):
        self.span = span
        self.p = dict(traffic)
        self.layers = config["num_hidden_layers"]
        self.block = traffic["block"]
        sizes = tuple(r * c for _, (r, c) in weight_grid.layer_shapes(config))
        self.weights = jax.block_until_ready(weight_grid.make_weights(
            jax.random.key(seed), config["initializer_range"],
            sizes=sizes, layers=self.layers,
            dtype=jnp.dtype(config["torch_dtype"])))
        self.topo = mesh(traffic["rows"], traffic["cols"])
        self.k = traffic["app_k"]
        self.orderings = tuple(traffic["orderings"])
        self.specs = {
            key: LinkSpec(width_bits=8 * traffic["lanes"],
                          flits_per_packet=traffic["elems"] // traffic["lanes"],
                          input_lanes=traffic["lanes"], weight_lanes=0,
                          key=key, k=self.k)
            for key in self.orderings
        }
        flows = traffic["users"] * traffic["layers"] * traffic["shards"]
        self.events_per_report = len(self.orderings) * work.flit_rows(
            flows * traffic["packets_per_flow"] * traffic["elems"],
            traffic["elems"], traffic["lanes"])
        self.work = {}
        self.records: list[tuple[int, dict]] = []
        self._produce(0)  # compiles every program a report runs

    def _bytes(self, layer: int, quantize) -> jax.Array:
        outs = [quantize(w) for w in self.weights[layer]]
        return jnp.concatenate([q for q, *_ in outs]).view(jnp.uint8)

    def _produce(self, layer: int) -> dict:
        p = self.p
        with self.span("kernels.quantize"):
            data = self._bytes(layer, lambda w: quantize_egress(w, self.block))
        with self.span("noc.flows"):
            flows = fleet_decode_flows(
                data, self.topo, users=p["users"], layers=p["layers"],
                shards=p["shards"], spec=self.specs[self.orderings[0]],
                packets_per_flow=p["packets_per_flow"])
        out = {}
        for key in self.orderings:
            with self.span("noc.simulate"):
                rep = simulate_noc(self.topo, flows, self.specs[key],
                                   sort_at=p["sort_at"])
            out[key] = {(s.src, s.dst): (s.num_flits, s.bt_input + s.bt_weight)
                        for s in rep.links}
        return out

    def report(self, i: int) -> None:
        layer = i % self.layers
        self.records.append((layer, self._produce(layer)))

    def check(self, rng) -> tuple[list[Compared], int]:
        done = sorted({layer for layer, _ in self.records})
        sample = rng.choice(done, min(self.p["check_layers"], len(done)),
                            replace=False).tolist()
        self.weights = {l: self.weights[l] for l in sample}
        links = wrong = 0
        for l in sample:
            data = np.asarray(self._bytes(
                l, lambda w: ref.quantize_blocks(w, self.block)))
            expect = {key: reference_links(data, self.p, key, self.k)
                      for key in self.orderings}
            for layer, got in self.records:
                if layer != l:
                    continue
                bad = sum(
                    len(set(got[key]) ^ set(expect[key]))
                    + sum(got[key][h] != v for h, v in expect[key].items()
                          if h in got[key])
                    for key in self.orderings
                )
                links += bad
                wrong += bad > 0
        return [Compared("links_differ", links, 0)], wrong

    def notes(self) -> list[str]:
        if not self.records:
            return []
        got = self.records[-1][1]
        total = {k: sum(bt for _, bt in got[k].values()) for k in got}
        base = max(total.get("none", 0), 1)
        return [" ".join(f"{k}: bt={v} ({100 * (1 - v / base):.2f}%)"
                         for k, v in total.items())]


def setup(config: dict, traffic: dict, seed: int, span) -> NocFleet:
    return NocFleet(config, traffic, seed, span)
