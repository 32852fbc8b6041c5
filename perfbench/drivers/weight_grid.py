"""Decode-weight grid: a decoder layer's weight egress under a design grid.

Set-up draws every layer's projection weights on the device, in the
configuration's type, in one jitted call from the seed.  Report ``i`` takes layer ``i mod layers``: the int8
quantizer runs tensor by tensor (``repro.kernels.quantize_egress``), the
layer's codes become one byte stream, and one chunked
``repro.kernels.bt_count_axes`` launch measures it under every design
point.  The report ends when the BT table is on the host.

The check compares, for layers drawn from the seed among those the window
reported, the codes and scales of the layer's last report and the BT table
of every report of it with ``perfbench.reference``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench import reference as ref
from perfbench import work
from perfbench.harness import Compared
from repro.kernels import CodecVariant, bt_count_axes, quantize_egress


def layer_shapes(config: dict) -> list[tuple[str, tuple[int, int]]]:
    """The seven projection matrices of one decoder layer, (in, out)."""
    h = config["hidden_size"]
    heads = config["num_attention_heads"]
    hd = config.get("head_dim") or h // heads
    q, kv = heads * hd, config["num_key_value_heads"] * hd
    f = config["intermediate_size"]
    return [("wq", (h, q)), ("wk", (h, kv)), ("wv", (h, kv)), ("wo", (q, h)),
            ("w1", (h, f)), ("w3", (h, f)), ("w2", (f, h))]


def designs(traffic: dict) -> tuple[ref.Design, ...]:
    return tuple(ref.Design(**d) for d in traffic["designs"])


def codec_variants(ds) -> tuple[CodecVariant, ...]:
    return tuple(
        CodecVariant(d.ordering, d.k, False, d.codec, d.partition) for d in ds
    )


@partial(jax.jit, static_argnames=("sizes", "layers", "dtype"))
def make_weights(key, std, *, sizes: tuple[int, ...], layers: int, dtype):
    """Every layer's flat tensors, normal(0, std) in ``dtype``, in one
    call."""
    keys = jax.random.split(key, layers * len(sizes))
    flat = [
        (std * jax.random.normal(k, (n,), jnp.float32)).astype(dtype)
        for k, n in zip(keys, sizes * layers)
    ]
    return tuple(
        tuple(flat[i * len(sizes):(i + 1) * len(sizes)]) for i in range(layers)
    )


@partial(jax.jit, static_argnames=("elems",))
def stack_codes(codes, scales, *, elems: int):
    """A layer's per-tensor codes as one (1, P, elems) byte stream, and its
    scales as one vector."""
    stream = lax.bitcast_convert_type(jnp.concatenate(codes), jnp.uint8)
    return stream.reshape(1, -1, elems), jnp.concatenate(scales)


class WeightGrid:
    def __init__(self, config: dict, traffic: dict, seed: int, span):
        self.span = span
        self.elems = traffic["elems"]
        self.lanes = traffic["lanes"]
        self.block = traffic["block"]
        self.chunk_packets = traffic["chunk_packets"]
        self.check_layers = traffic["check_layers"]
        self.layers = config["num_hidden_layers"]
        self.designs = designs(traffic)
        self.configs = codec_variants(self.designs)
        sizes = tuple(r * c for _, (r, c) in layer_shapes(config))
        for n in sizes:
            if n % self.block or n % self.elems:
                raise ValueError(
                    f"a {n}-value tensor is not whole {self.block}-value "
                    f"blocks and {self.elems}-byte packets"
                )
        self.values = sum(sizes)
        self.weights = jax.block_until_ready(make_weights(
            jax.random.key(seed), config["initializer_range"],
            sizes=sizes, layers=self.layers,
            dtype=jnp.dtype(config["torch_dtype"]),
        ))
        self.events_per_report = work.stream_events(
            self.values, self.elems, self.lanes, len(self.designs))
        self.work = {"axes_wire_bytes": work.axes_wire_bytes(
            self.values, 1, len(self.designs))}
        self.bts: list[tuple[int, np.ndarray]] = []
        self.kept: dict[int, tuple[jax.Array, jax.Array]] = {}
        self._produce(0)  # compiles every program a report runs

    def _produce(self, layer: int):
        with self.span("kernels.quantize"):
            outs = [quantize_egress(w, self.block) for w in self.weights[layer]]
        with self.span("host.stack"):
            stream, scales = stack_codes(
                tuple(q for q, _, _ in outs), tuple(s for _, s, _ in outs),
                elems=self.elems,
            )
        with self.span("kernels.axes"):
            bt = bt_count_axes(
                stream, configs=self.configs, input_lanes=self.lanes,
                chunk_packets=self.chunk_packets,
            )
        with self.span("host.readback"):
            table = np.asarray(bt)[0]
        return stream, scales, table

    def report(self, i: int) -> None:
        layer = i % self.layers
        stream, scales, table = self._produce(layer)
        self.bts.append((layer, table))
        self.kept[layer] = (stream, scales)

    def check(self, rng) -> tuple[list[Compared], int]:
        """Draw the layers to check, drop every other layer's state, and
        compare."""
        done = sorted({layer for layer, _ in self.bts})
        sample = sorted(rng.choice(done, min(self.check_layers, len(done)),
                                   replace=False).tolist())
        self.weights = {l: self.weights[l] for l in sample}
        self.kept = {l: self.kept[l] for l in sample}
        produced = {
            l: (*self.kept[l], [t for layer, t in self.bts if layer == l])
            for l in sample
        }
        return compare(self, produced)

    def control(self) -> tuple[list[Compared], int]:
        """The reference in bfloat16 put in the program's place, on the
        layers :meth:`check` kept."""
        produced = {}
        for l in self.weights:
            stream, scales = self.reference_codes(l, jnp.bfloat16)
            table = np.asarray(ref.stream_bt(stream[0], self.designs,
                                             self.lanes))
            produced[l] = (stream, scales, [self.as_table(table)])
        return compare(self, produced)

    def reference_codes(self, layer: int, dtype):
        outs = [ref.quantize_blocks(w, self.block, dtype)
                for w in self.weights[layer]]
        return stack_codes(tuple(q for q, _ in outs),
                           tuple(s for _, s in outs), elems=self.elems)

    @staticmethod
    def as_table(data_aux: np.ndarray) -> np.ndarray:
        """(D, 2) data/aux BT as the program's (D, 3) input/weight/aux."""
        d = data_aux.astype(np.int64)
        return np.stack([d[:, 0], np.zeros_like(d[:, 0]), d[:, 1]], axis=1)

    def notes(self) -> list[str]:
        if not self.bts:
            return []
        table = self.bts[-1][1].astype(np.int64)
        gross = table.sum(axis=1)
        reds = " ".join(
            f"{d.ordering}{d.k or ''}+{d.codec}="
            f"{100.0 * (1 - g / max(gross[0], 1)):.2f}%"
            for d, g in zip(self.designs[1:], gross[1:])
        )
        return [f"layer {self.bts[-1][0]}: bt_none={gross[0]} {reds}"]


def compare(cell: WeightGrid, produced: dict):
    """Codes, scales and BT tables of ``produced`` against the reference.

    ``produced`` maps a layer to (stream, scales, [BT tables]).  Returns
    the compared numbers and the number of BT tables that differ."""
    codes = scales = entries = tables_wrong = 0
    for layer, (stream, sc, tables) in produced.items():
        r_stream, r_scales = cell.reference_codes(layer, jnp.float32)
        expect = cell.as_table(np.asarray(
            ref.stream_bt(r_stream[0], cell.designs, cell.lanes)))
        codes += int(jnp.sum(stream != r_stream))
        scales += int(jnp.sum(sc != r_scales))
        for t in tables:
            wrong = int(np.sum(np.asarray(t, np.int64) != expect))
            entries += wrong
            tables_wrong += wrong > 0
    return [
        Compared("codes_differ", codes, 0),
        Compared("scales_differ", scales, 0),
        Compared("bt_entries_differ", entries, 0),
    ], tables_wrong


def setup(config: dict, traffic: dict, seed: int, span) -> WeightGrid:
    return WeightGrid(config, traffic, seed, span)
