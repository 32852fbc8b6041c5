"""The expert-parallel dispatch cell at a tiny size on the CPU: the flows
against a plain builder, the seam-sum reference against ``stream_bt`` of
each concatenated queue, the driver's report against the reference, and
one planted fault each in the bytes, the routing and the precision."""

import contextlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference as ref
from perfbench.drivers import moe_dispatch
from perfbench.drivers.noc_fleet import xy_links
from repro.link import LinkSpec
from repro.noc import mesh, moe_dispatch_flows

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "deepseek-v2-lite-ep8-prefill.json")) as f:
    CONFIG = json.load(f)
# every mechanism at tiny widths: 2 heads, 16 experts (2 a chip), top-6,
# 128-value token rows (one quantizer block, two packets), 3 layers
TINY = {
    **CONFIG,
    "hidden_size": 128, "intermediate_size": 96, "kv_lora_rank": 32,
    "moe_intermediate_size": 32, "n_routed_experts": 16,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "num_hidden_layers": 3, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "vocab_size": 512, "prefill_tokens_per_chip": 16,
    "rope_scaling": {**CONFIG["rope_scaling"],
                     "original_max_position_embeddings": 64},
}
with open(os.path.join(HERE, "traffic", "ep8-dispatch.json")) as f:
    TRAFFIC = json.load(f)
SPEC = LinkSpec(width_bits=128, flits_per_packet=4, input_lanes=16,
                weight_lanes=0, key="acc", k=4)


def _span(name):
    return contextlib.nullcontext()


def _routing(seed, t=64, k=6, experts=16):
    rng = np.random.default_rng(seed)
    ids = np.stack([rng.choice(experts, k, replace=False) for _ in range(t)])
    rows = rng.integers(-127, 128, (t, 128), dtype=np.int8)
    return rows, ids.astype(np.int32)


@pytest.mark.parametrize("seed", [0, 1])
def test_flows_match_plain_builder(seed):
    rows, ids = _routing(seed)
    topo = mesh(2, 4)
    flows = moe_dispatch_flows(jnp.asarray(rows), jnp.asarray(ids), topo,
                               num_experts=16, spec=SPEC)
    want = []
    for src in range(8):  # 8 tokens a chip, 2 experts a chip
        for e in range(16):
            if e // 2 == src:
                continue
            tok = [t for t in range(src * 8, src * 8 + 8) if e in ids[t]]
            if tok:
                want.append((src, e // 2, rows[tok].view(np.uint8)))
    assert len(flows) == len(want)
    for f, (src, dst, data) in zip(flows, want):
        assert (f.src, f.dsts) == (src, (dst,))
        np.testing.assert_array_equal(np.asarray(f.inputs),
                                      data.reshape(-1, 64))


def test_flows_reject_uneven_splits_and_partial_packets():
    rows, ids = _routing(0)
    topo = mesh(2, 4)
    with pytest.raises(ValueError, match="split evenly"):
        moe_dispatch_flows(rows, ids, topo, num_experts=12, spec=SPEC)
    with pytest.raises(ValueError, match="whole 64-byte packets"):
        moe_dispatch_flows(rows[:, :96], ids, topo, num_experts=16, spec=SPEC)
    with pytest.raises(ValueError, match="weight_lanes=0"):
        moe_dispatch_flows(rows, ids, topo, num_experts=16,
                           spec=LinkSpec(key="acc"))


@pytest.mark.parametrize("ordering", ["none", "acc", "app"])
def test_seam_sum_matches_concatenated_queues(ordering):
    """``reference_links`` prices a link as its packets' own transitions
    plus the seams between them; ``stream_bt`` of the link's whole queue,
    concatenated in injection order, says the same."""
    rows, ids = _routing(2)
    p = {**TRAFFIC, "experts": 16}
    got = moe_dispatch.reference_links(rows.view(np.uint8), ids, p, ordering, 4)
    queues = {}
    for src, _, tok, dst in moe_dispatch.dispatch_pairs(ids, 8, 16):
        if src != dst:
            for hop in xy_links(4, src, dst):
                queues.setdefault(hop, []).append(rows[tok].view(np.uint8))
    assert set(got) == set(queues)
    design = (ref.Design(ordering, 4 if ordering == "app" else None),)
    for hop, parts in queues.items():
        stream = np.concatenate(parts).reshape(-1, 64)
        bt = int(np.asarray(ref.stream_bt(jnp.asarray(stream), design, 16))[0, 0])
        assert got[hop] == (stream.shape[0] * 4, bt)


@pytest.fixture(scope="module")
def cell():
    c = moe_dispatch.setup(TINY, TRAFFIC, 12345, _span)
    for i in range(3):
        c.report(i)
    return c


def test_driver_matches_reference(cell):
    compared, failed = cell.check(np.random.default_rng(0))
    values = {c.name: c.value for c in compared}
    assert values["links_differ"] == 0 and values["topk_flips"] == 0
    assert values["picks_not_topk"] == 0
    assert all(c.ok for c in compared) and failed == 0
    # events from the captured routing: 3 orderings x the fabric rows'
    # flit rows, averaged over the reports made (layers 0, 1, 0)
    ev = [3 * 4 * 2 * sum(tok.size for s, _, tok, d in
                          moe_dispatch.dispatch_pairs(np.asarray(ids), 8, 16)
                          if s != d)
          for _, ids, _ in cell.routes]
    assert cell.events_per_report == (2 * ev[0] + ev[1]) / 3
    assert cell.work["links_wire_bytes"] > 0
    assert any("tokens per expert" in line for line in cell.notes())


def test_control_fails(cell):
    """MoE inputs in float8 e4m3 fail the model comparison."""
    control, failed = cell.control()
    assert not all(c.ok for c in control) and failed > 0


def test_planted_byte_fault_fails(monkeypatch):
    real = moe_dispatch.quantize_egress

    def flipped(x, block):
        q, s, n = real(x, block)
        return q.at[5].set(q[5] ^ 0x10), s, n

    monkeypatch.setattr(moe_dispatch, "quantize_egress", flipped)
    bad = moe_dispatch.setup(TINY, TRAFFIC, 12345, _span)
    bad.report(0)
    compared, failed = bad.check(np.random.default_rng(0))
    assert [c.name for c in compared if not c.ok] == ["links_differ"]
    assert failed == 1


def test_planted_routing_fault_fails(monkeypatch):
    real = moe_dispatch.moe_dispatch_flows

    def misrouted(rows, ids, *a, **kw):
        return real(rows, jnp.roll(ids, 1, axis=0), *a, **kw)

    monkeypatch.setattr(moe_dispatch, "moe_dispatch_flows", misrouted)
    bad = moe_dispatch.setup(TINY, TRAFFIC, 12345, _span)
    bad.report(0)
    compared, failed = bad.compare_links(0)
    assert compared > 0 and failed == 1


def test_planted_topk_fault_in_last_layer_fails(cell):
    """A wrong top-k in the model's last expert layer (each pick one
    expert id off), which no later layer's rows can show, fails the
    model comparison: the picks are not a top-k of the layer's
    probabilities."""
    got = cell.program_route()
    x, ids, pr = got[-1]
    got[-1] = (x, (ids + 1) % pr.shape[-1], pr)
    compared, failed = cell.compare_model(got)
    assert "picks_not_topk" in [c.name for c in compared if not c.ok]
    assert failed == 1


def test_planted_precision_fault_fails(cell):
    """The program's MoE inputs cut to float8 before they are compared."""
    got = [(np.asarray(jnp.asarray(x).astype(jnp.float8_e4m3fn), np.float32),
            ids, pr) for x, ids, pr in cell.program_route()]
    compared, failed = cell.compare_model(got)
    assert not all(c.ok for c in compared) and failed > 0
