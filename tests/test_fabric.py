"""The batched fabric pipeline (DESIGN.md §17): plan compilation, the
device-side expansion's bit-exactness against the legacy per-flow loop,
the one-launch fleet pin, and the contention-latency model."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import bt_count_links, pallas_launch_count
from repro.link import LinkSpec
from repro.noc import (
    FabricLatency,
    FlowBatch,
    NocLatencyModel,
    TrafficFlow,
    compile_fabric,
    expand_fabric,
    fabric_latency,
    fabric_to_link_streams,
    fleet_decode_flows,
    hop_count,
    mesh,
    ring,
    route_latency_cycles,
    route_latency_ns,
    simulate_noc,
    torus,
)
from repro.noc.simulate import (
    _expand_link_streams_reference,
    expand_link_streams,
)


def _pk(p, seed=0, elems=32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 256, (p, elems), dtype=np.uint8))


def _flows(topo, n=4, seed=0):
    """Multi-tenant-ish endpoints: unicasts + multicasts, shared prefixes."""
    far = topo.num_routers - 1
    mid = topo.num_routers // 2
    specs = [
        (0, (far,)),
        (0, (mid, far)),  # shares the flow-0 prefix -> queue merge
        (1, (far,)),
        (mid, (0, 1, far)),
    ][:n]
    return [
        TrafficFlow(
            f"f{i}", src, dsts,
            _pk(3 + 2 * i, seed + 2 * i), _pk(3 + 2 * i, seed + 2 * i + 1),
        )
        for i, (src, dsts) in enumerate(specs)
    ]


# ------------------------------------------------------------ FabricPlan


def test_fabric_plan_tables_match_legacy_queue_semantics():
    topo = mesh(4, 4)
    flows = _flows(topo)
    plan = compile_fabric(topo, [(f.src, f.dsts) for f in flows])
    assert plan.num_flows == len(flows)
    assert plan.link_ids == tuple(sorted(plan.link_ids))  # ascending scan
    assert len(plan.link_queue) == plan.active_links
    # every queue holds flow indices in injection order, and every link's
    # queue is the set of flows whose multicast tree crosses it
    for lid, qi in zip(plan.link_ids, plan.link_queue):
        q = plan.queues[qi]
        assert list(q) == sorted(q)  # injection order == flow index order
        assert q == tuple(
            fi for fi, links in enumerate(plan.flow_links) if lid in links
        )
        assert plan.queue_of(lid) == q
    # distinct compositions are deduplicated: flows 0 and 1 share a path
    # prefix, so at least one queue serves several physical links
    assert plan.num_queues < plan.active_links
    counts = [plan.link_queue.count(qi) for qi in range(plan.num_queues)]
    assert max(counts) >= 2
    # endpoints survive normalization (the latency model walks them)
    assert plan.endpoints == tuple(
        (f.src, tuple(f.dsts)) for f in flows
    )


def test_flow_batch_stacks_flows_back_to_back():
    """Flows of 3, 5, 7 and 9 packets fill 24 rows of a 32-row batch in
    flow order: no flow is padded to the longest."""
    flows = _flows(ring(6))
    batch = FlowBatch.from_flows(flows, LinkSpec())
    assert batch.counts == (3, 5, 7, 9) and batch.starts == (0, 3, 8, 15)
    assert batch.inputs.shape == (32, 32) == batch.weights.shape
    want = np.concatenate([np.asarray(f.inputs) for f in flows])
    np.testing.assert_array_equal(np.asarray(batch.inputs[:24]), want)
    assert not np.asarray(batch.inputs[24:]).any()


def test_fabric_plan_rejects_batch_size_mismatch():
    topo = ring(6)
    flows = _flows(topo, n=2)
    plan = compile_fabric(topo, [(f.src, f.dsts) for f in flows])
    batch = FlowBatch.from_flows(flows[:1], LinkSpec())
    with pytest.raises(ValueError, match="1 flows"):
        expand_fabric(plan, batch, LinkSpec())


# ------------------------------------- bit-exactness vs the legacy loop


@pytest.mark.parametrize("topo", [mesh(4, 4), torus(3, 4), ring(8)],
                         ids=["mesh4x4", "torus3x4", "ring8"])
@pytest.mark.parametrize("key,sort_at,codec", [
    ("none", "source", "none"),
    ("acc", "source", "none"),
    ("acc", "hop", "none"),
    ("app", "hop", "none"),
    ("acc", "source", "bus_invert"),
    ("none", "hop", "bus_invert"),
])
def test_batched_expansion_bit_exact_vs_reference(topo, key, sort_at, codec):
    spec = LinkSpec(key=key, codec=codec)
    flows = _flows(topo, seed=17)
    got = expand_link_streams(topo, flows, spec, sort_at=sort_at)
    ref = _expand_link_streams_reference(topo, flows, spec, sort_at=sort_at)
    assert got.link_ids == ref.link_ids
    assert got.lengths == ref.lengths
    assert got.aux_bt == ref.aux_bt
    # full padded tensors: edge-padding must reproduce too, the BT kernel
    # reads the pad region even though lengths mask it out of the totals
    np.testing.assert_array_equal(
        np.asarray(got.streams), np.asarray(ref.streams)
    )
    for gi, ri in zip(got.inverts, ref.inverts):
        assert (gi is None) == (ri is None)
        if gi is not None:
            np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))


def test_expansion_handles_empty_flow_set():
    topo = mesh(3, 3)
    got = expand_link_streams(topo, [], LinkSpec())
    assert got.link_ids == () and got.streams.shape[0] == 0


# ------------------------------------------------- the fleet-scale pins


def test_fleet_fabric_one_launch_per_key_width():
    """The acceptance fleet: 16x16 mesh, >= 1024 multi-tenant decode
    flows, whole-fabric measurement traces to ONE pallas launch."""
    topo = mesh(16, 16)
    spec = LinkSpec(input_lanes=16, weight_lanes=0)
    data = _pk(1, seed=3, elems=4096).reshape(-1)
    flows = fleet_decode_flows(
        data, topo, users=16, layers=16, shards=4, spec=spec
    )
    assert len(flows) == 1024
    plan = compile_fabric(topo, [(f.src, f.dsts) for f in flows])
    batch = FlowBatch.from_flows(flows, spec)
    fs = expand_fabric(plan, batch, spec, sort_at="source")
    assert fs.num_queues == plan.num_queues < plan.active_links
    assert pallas_launch_count(
        lambda s: bt_count_links(
            s, input_lanes=spec.input_lanes, lengths=fs.lengths
        ),
        fs.streams,
    ) == 1
    # queue -> link fan-out keeps the legacy per-link report shape
    ls = fabric_to_link_streams(fs)
    assert ls.link_ids == plan.link_ids
    assert len(ls.lengths) == plan.active_links


def test_fleet_decode_flows_shapes_and_validation():
    topo = mesh(4, 5)
    spec = LinkSpec(input_lanes=16, weight_lanes=0)
    data = _pk(2, seed=9, elems=256).reshape(-1)
    flows = fleet_decode_flows(
        data, topo, users=3, layers=2, shards=2, spec=spec
    )
    assert len(flows) == 3 * 2 * 2
    assert flows[0].name == "u0/l0/s0"
    for f in flows:
        assert f.weights is None
        assert f.inputs.shape == (2, spec.flits_per_packet * 16)
        # memory-column source, PE-column destinations
        assert topo.coords(f.src)[1] == 0
        assert all(topo.coords(d)[1] >= 1 for d in f.dsts)
    with pytest.raises(ValueError, match="weight"):
        fleet_decode_flows(data, topo, users=1, layers=1, shards=1,
                           spec=LinkSpec())  # weight-lane spec
    with pytest.raises(ValueError, match="shards"):
        fleet_decode_flows(data, topo, users=1, layers=1, shards=9,
                           spec=spec)  # > PE columns


def _sliced_payloads(data, need, flows):
    """The per-flow definition of the fleet payloads: the wire image
    (tiled when shorter than one flow) cut at ``(fi * 7919) mod (span +
    1)``, one slice per flow."""
    if data.size < need:
        data = np.tile(data, -(-need // data.size))
    span = data.size - need
    offs = [0 if span == 0 else (fi * 7919) % (span + 1)
            for fi in range(flows)]
    return data, offs


@pytest.mark.parametrize("scale", [5.3, 0.7, 1.0],
                         ids=["strided", "tiled", "span0"])
def test_fleet_decode_flows_payloads_are_strided_slices(scale):
    """Every flow carries its strided slice of the wire image, byte for
    byte, as a host view of one staged array (not a device slice)."""
    topo = mesh(4, 5)
    spec = LinkSpec(input_lanes=16, weight_lanes=0)
    need = 2 * spec.elems_per_packet
    data = np.random.default_rng(11).integers(
        0, 256, int(scale * need), dtype=np.uint8)
    flows = fleet_decode_flows(
        jnp.asarray(data), topo, users=3, layers=2, shards=2, spec=spec)
    tiled, offs = _sliced_payloads(data, need, len(flows))
    assert (len(set(offs)) > 1) == (scale != 1.0)
    for f, off in zip(flows, offs):
        np.testing.assert_array_equal(
            f.inputs, tiled[off:off + need].reshape(2, -1))
    staged = flows[0].inputs.base
    assert isinstance(staged, np.ndarray)
    assert staged.shape == (len(flows), need)
    for f in flows:
        assert isinstance(f.inputs, np.ndarray) and f.inputs.base is staged
        assert np.shares_memory(f.inputs, staged)


@pytest.mark.parametrize("key", ["none", "acc", "app"])
def test_fleet_staged_flows_match_device_slices(key):
    """The staged payloads price every link exactly as the same flows cut
    one device slice each."""
    topo = mesh(4, 5)
    spec = LinkSpec(input_lanes=16, weight_lanes=0, key=key)
    need = 2 * spec.elems_per_packet
    data = np.random.default_rng(5).integers(
        0, 256, 40 * need + 7, dtype=np.uint8)
    staged = fleet_decode_flows(
        jnp.asarray(data), topo, users=3, layers=2, shards=2, spec=spec)
    wire, offs = _sliced_payloads(data, need, len(staged))
    wire = jnp.asarray(wire)
    sliced = [
        TrafficFlow(f.name, f.src, f.dsts,
                    wire[off:off + need].reshape(2, -1))
        for f, off in zip(staged, offs)
    ]
    got = simulate_noc(topo, staged, spec, sort_at="source")
    want = simulate_noc(topo, sliced, spec, sort_at="source")
    assert got.links == want.links and got.total_bt > 0


# ------------------------------------------------- the contention model


def test_route_latency_pins():
    m = NocLatencyModel()  # 500 MHz, 3-cycle router, 1-cycle link
    assert m.cycle_ns == pytest.approx(2.0)
    assert route_latency_cycles(0, 10, m) == 0
    assert route_latency_cycles(3, 0, m) == 0
    # head: 3 hops x (3+1), body: 7 flits pipeline behind
    assert route_latency_cycles(3, 8, m) == 12 + 7
    assert route_latency_ns(3, 8, m) == pytest.approx(38.0)
    with pytest.raises(ValueError):
        NocLatencyModel(clock_ghz=0.0)
    with pytest.raises(ValueError):
        NocLatencyModel(link_cycles=0)


def test_fabric_latency_injection_order_contention():
    # two flows merging on the same 1x4-mesh row: f0 injects first, f1
    # waits f0's full serialization at every shared link
    topo = mesh(1, 4)
    plan = compile_fabric(topo, [(0, (3,)), (1, (3,))])
    lat = fabric_latency(plan, [4, 4], NocLatencyModel())
    assert isinstance(lat, FabricLatency)
    by_link = {l.link: l for l in lat.links}
    l01 = by_link[topo.link_id(0, 1)]
    l12 = by_link[topo.link_id(1, 2)]
    assert l01.flows == 1 and l01.wait_cycles == 0
    # merged link: f1 queues behind f0's 4 flits (link_cycles=1)
    assert l12.flows == 2 and l12.wait_cycles == 4
    f0, f1 = lat.flows
    assert f0.hops == 3 and f0.wait_cycles == 0
    assert f0.cycles == route_latency_cycles(3, 4)
    # f1: 2 hops + 4-cycle wait at each of its 2 shared links
    assert f1.hops == 2 and f1.wait_cycles == 8
    assert f1.cycles == route_latency_cycles(2, 4) + 8
    assert lat.max_latency_ns == pytest.approx(2.0 * f1.cycles)
    assert lat.contended_links == 2
    with pytest.raises(ValueError, match="flit counts"):
        fabric_latency(plan, [4])


def test_contend_probe_and_simulate_latency_report():
    topo = mesh(1, 4)
    flows = [
        TrafficFlow("a", 0, (3,), _pk(2, 1), _pk(2, 2)),
        TrafficFlow("b", 1, (3,), _pk(2, 3), _pk(2, 4)),
    ]
    with obs.collect() as reg:
        rep = simulate_noc(
            topo, flows, LinkSpec(), latency=NocLatencyModel()
        )
    assert rep.latency is not None
    assert rep.latency.contended_links == 2
    # one noc.contend event per contended link, labeled by route
    lab = {"link": topo.link_id(1, 2), "src": 1, "dst": 2}
    assert reg.value("noc.contend.flows", **lab) == 2
    assert reg.value("noc.contend.wait_cycles", **lab) == 8  # 2pk x 4 flits
    # without latency= the report stays latency-free (and fires nothing)
    rep2 = simulate_noc(topo, flows, LinkSpec())
    assert rep2.latency is None
