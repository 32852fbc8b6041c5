"""The paper's conv platform: captured LeNet-5 conv traffic, Table I style.

Set-up trains the in-repo LeNet-5 from the seed and captures its conv
traffic (the trained kernels and an input batch) for a fixed number of
image draws, each drawn from the seed.  Report ``i`` takes capture
``i mod captures`` and runs what a user of the paper's flow runs:

  * ``TxPipeline.measure`` on the input and weight streams framed apart
    (64-byte packets on 16 lanes), once per ordering;
  * ``TxPipeline.measure`` on the paper's paired framing (8 input lanes
    beside 8 weight lanes), once per ordering;
  * ``dse.evaluate_grid`` over the design points with activity windows.

The check compares every report's numbers (flits, BT per side, invert-line
BT, BT per wire) with ``perfbench.reference`` on the same capture, and for
every capture the order and wire image each measure sends.  Only the raw
captured bytes come from the program: the benchmark frames them itself.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import reference as ref
from perfbench.harness import Compared
from repro import obs
from repro.dse import DesignPoint, Workload, evaluate_grid
from repro.link import LinkSpec, TxPipeline
from repro.models import lenet

PAPER = {"acc": 20.42, "app": 19.50}  # Table I overall reductions (%)
SCENARIO = "lenet_conv"  # the capture's scenario name
WEIGHTS = ("conv1", "conv2")  # the weight streams, in the order sent


def _separate_spec(key: str, elems: int, lanes: int, k: int) -> LinkSpec:
    """One PE's input-only link: every lane carries the stream's bytes."""
    return LinkSpec(width_bits=8 * lanes, flits_per_packet=elems // lanes,
                    input_lanes=lanes, weight_lanes=0, key=key, k=k)


def _codec(name: str | None) -> tuple[str, int | None]:
    """(scheme, partition) of a codec name: ``bus_invert<n>`` has one
    invert line per n lanes, plain ``bus_invert`` one per flit."""
    if name is None:
        return "none", None
    if name.startswith("bus_invert"):
        return "bus_invert", int(name[len("bus_invert"):] or 0) or None
    return name, None


def frame(data: np.ndarray, elems: int) -> jax.Array:
    """A byte stream as whole ``elems``-byte packets; the tail that fills
    no packet is left out."""
    p = data.size // elems
    return jnp.asarray(data[: p * elems].reshape(p, elems))


class Capture:
    """One image draw's traffic in every framing the report measures,
    framed here from the raw bytes the capture recorded."""

    def __init__(self, sess, elems: int, lanes: int, paired_elems: int):
        raw = {s.name: np.asarray(s.data, np.uint8)
               for s in sess.get(SCENARIO)}
        weights = np.concatenate([raw[n] for n in WEIGHTS])
        self.separate = {"inputs": frame(raw["inputs"], elems),
                         "weights": frame(weights, elems)}
        pin = frame(raw["inputs"], paired_elems)
        self.paired = (pin, jnp.asarray(
            np.resize(weights, pin.size).reshape(pin.shape)))
        # every captured stream on a link of its own, as Table I measures
        self.workload = Workload(
            SCENARIO, tuple(frame(d, elems) for d in raw.values()), lanes)


class PaperConv:
    def __init__(self, config: dict, traffic: dict, seed: int, span):
        self.span = span
        self.elems, self.lanes = traffic["elems"], traffic["lanes"]
        self.paired_lanes = traffic["paired_lanes"]
        self.k = traffic["app_k"]
        self.orderings = tuple(traffic["orderings"])
        self.points = tuple(DesignPoint(**p) for p in traffic["grid"])
        self.windows = traffic["activity_windows"]
        self.flits = self.elems // self.lanes
        rng = np.random.default_rng(seed)
        params, _ = lenet.train_lenet(
            steps=config["train_steps"], batch=config["train_batch"],
            seed=seed, ckpt_dir=None)
        draws = rng.integers(0, 2**31 - 1, traffic["captures"])
        self.captures = [
            Capture(obs.capture_lenet_conv(params=params, seed=int(s)),
                    self.elems, self.lanes, self.flits * self.paired_lanes)
            for s in draws
        ]
        self.separate = {
            k: TxPipeline(_separate_spec(k, self.elems, self.lanes, self.k))
            for k in self.orderings
        }
        paired = LinkSpec(input_lanes=self.paired_lanes,
                          weight_lanes=self.paired_lanes,
                          width_bits=16 * self.paired_lanes,
                          flits_per_packet=self.flits, k=self.k)
        self.paired = {
            k: TxPipeline(dataclasses.replace(paired, key=k))
            for k in self.orderings
        }
        c0 = self.captures[0]
        sep_rows = sum(int(x.shape[0]) * self.flits
                       for x in c0.separate.values())
        paired_rows = int(c0.paired[0].shape[0]) * self.flits
        self.events_per_report = (
            (sep_rows + paired_rows) * len(self.orderings)
            + c0.workload.num_flits * len(self.points)
        )
        self.work = {}
        self.records: list[tuple[int, np.ndarray]] = []
        self._produce(c0)  # compiles every program a report runs

    def _produce(self, cap: Capture) -> np.ndarray:
        out = []
        for side in ("inputs", "weights"):
            for k in self.orderings:
                with self.span("link.measure"):
                    r = self.separate[k].measure(cap.separate[side])
                out += [r.num_flits, r.input_bt, r.weight_bt, r.aux_bt]
        for k in self.orderings:
            with self.span("link.measure"):
                r = self.paired[k].measure(*cap.paired)
            out += [r.num_flits, r.input_bt, r.weight_bt, r.aux_bt]
        with self.span("dse.evaluate_grid"):
            evals = evaluate_grid(self.points, cap.workload,
                                  activity_windows=self.windows)
        for e in evals:
            out += [e.num_flits, e.total_bt, e.aux_bt, *e.per_wire_bt]
        return np.asarray(out, np.int64)

    def report(self, i: int) -> None:
        c = i % len(self.captures)
        self.records.append((c, self._produce(self.captures[c])))

    def expected(self, cap: Capture, mask: int = 0xFF) -> np.ndarray:
        """The reference's numbers for one capture, laid out as
        ``_produce``'s; ``mask`` keeps only some bits of every byte."""
        out = []
        for side in ("inputs", "weights"):
            x = cap.separate[side] & mask
            for k in self.orderings:
                d = ref.Design(k, self.k if k == "app" else None)
                data, aux = np.asarray(ref.stream_bt(x, (d,), self.lanes))[0]
                out += [int(x.shape[0]) * self.flits, data, 0, aux]
        pin, pwt = (a & mask for a in cap.paired)
        for k in self.orderings:
            bi, bw = np.asarray(ref.paired_bt(
                pin, pwt, k, self.k if k == "app" else None,
                self.paired_lanes))
            out += [int(pin.shape[0]) * self.flits, bi, bw, 0]
        for p in self.points:
            d = ref.Design(p.ordering, p.k, *_codec(p.codec))
            streams = [s & mask for s in cap.workload.streams]
            bt = sum(np.asarray(ref.stream_bt(s, (d,), self.lanes), np.int64)[0]
                     for s in streams)
            wires = sum(np.asarray(ref.stream_wire_bt(s, (d,), self.lanes)[0],
                                   np.int64) for s in streams)
            flits = sum(int(s.shape[0]) * self.flits for s in streams)
            out += [flits, bt[0], bt[1], *wires]
        return np.asarray(out, np.int64)

    def sent(self, cap: Capture) -> list[jax.Array]:
        """Order and wire image of each of a report's measures, from the
        same pipelines on the same capture (``TxPipeline.run``, which
        ``measure`` runs and reduces to its BT)."""
        out = []
        for side in ("inputs", "weights"):
            for k in self.orderings:
                r = self.separate[k].run(cap.separate[side])
                out += [r.order, r.stream]
        for k in self.orderings:
            r = self.paired[k].run(*cap.paired)
            out += [r.order, r.stream]
        return out

    def expected_sent(self, cap: Capture, mask: int = 0xFF
                      ) -> list[jax.Array]:
        """The reference's :meth:`sent`; ``mask`` as in :meth:`expected`."""
        out = []
        for side in ("inputs", "weights"):
            for k in self.orderings:
                out += ref.send(cap.separate[side] & mask, k,
                                self.k if k == "app" else None, self.lanes)
        pin, pwt = (a & mask for a in cap.paired)
        for k in self.orderings:
            out += ref.send(pin, k, self.k if k == "app" else None,
                            self.paired_lanes, pwt)
        return out

    def check(self, rng) -> tuple[list[Compared], int]:
        del rng  # every report and every capture is compared
        return self._compare(
            self.records, [self.sent(cap) for cap in self.captures])

    def control(self) -> tuple[list[Compared], int]:
        """The reference on int4 wire bytes (the low nibble of every byte
        dropped), put in the program's place."""
        recs = [(c, self.expected(cap, 0xF0))
                for c, cap in enumerate(self.captures)]
        return self._compare(
            recs, [self.expected_sent(cap, 0xF0) for cap in self.captures])

    def _compare(self, records, sent):
        """``records`` are (capture, numbers) of the reports; ``sent`` the
        orders and wire images of every capture.  Failed: reports whose
        numbers differ, and captures whose orders or images do."""
        expect = {}
        values = wrong = 0
        for c, got in records:
            if c not in expect:
                expect[c] = self.expected(self.captures[c])
            bad = int(np.sum(got != expect[c])) if got.shape == expect[c].shape \
                else got.size
            values += bad
            wrong += bad > 0
        entries = 0
        for cap, got in zip(self.captures, sent):
            bad = sum(
                int(jnp.sum(g != e)) if g.shape == e.shape else int(g.size)
                for g, e in zip(got, self.expected_sent(cap)))
            entries += bad
            wrong += bad > 0
        return [Compared("values_differ", values, 0),
                Compared("sent_entries_differ", entries, 0)], wrong

    def notes(self) -> list[str]:
        if not self.records:
            return []
        got = self.records[-1][1]
        per = 4
        sep = got[: 2 * len(self.orderings) * per].reshape(2, -1, per)
        bt = {k: int(sep[:, j, 1:3].sum()) for j, k in enumerate(self.orderings)}
        if "none" not in bt:
            return []
        lines = []
        for k, paper in PAPER.items():
            if k in bt:
                red = 100.0 * (1 - bt[k] / max(bt["none"], 1))
                lines.append(f"paper {k}: reduction {red:.2f}% (paper "
                             f"{paper}%, error {red - paper:+.2f} pp)")
        return lines


def setup(config: dict, traffic: dict, seed: int, span) -> PaperConv:
    return PaperConv(config, traffic, seed, span)
