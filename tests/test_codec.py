"""repro.codec + the single-launch multi-codec BT kernel.

Load-bearing claims:

  * every registered codec is a true encode/decode pair —
    ``decode(encode(x)) == x`` on arbitrary flit streams;
  * ``bt_count_codecs`` is bit-exact per (codec, ordering) config against
    the sequential ``kernels/ref.py`` composition (order -> gather -> pack
    -> codec-encode -> BT) across every codec x ordering (none / acc /
    app k in {2,4,8}) x width 4/8 x non-block-multiple P, in ONE launch;
  * ``codec.compare`` reports ordering-alone, coding-alone and composed
    reductions net of invert-line overhead on the conv workload.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.codec import (
    CODECS,
    codec_by_name,
    codec_overhead,
    compare_streams,
    demo_workloads,
    format_table,
    invert_line_transitions,
    kernel_config,
    make_bus_invert,
)
from repro.core.area import PSUArea, codec_area
from repro.core.coding import (
    gray_decode_bytes,
    gray_encode_bytes,
    sign_magnitude_decode_bytes,
    sign_magnitude_encode_bytes,
)
from repro.core.popcount import popcount
from repro.kernels import CodecVariant, bt_count_codecs, pallas_launch_count
from repro.kernels.ref import bt_codecs_ref
from repro.link import LinkPowerModel, LinkSpec, TxPipeline


# ------------------------------------------------------------- the schemes


@pytest.mark.parametrize("name", sorted(CODECS))
@pytest.mark.parametrize("shape", [(1, 16), (2, 16), (37, 16), (64, 8)])
def test_decode_encode_identity(name, shape):
    """The subsystem contract: decode∘encode == identity, every codec."""
    rng = np.random.default_rng(hash((name, shape)) % 2**31)
    s = jnp.asarray(rng.integers(0, 256, shape, dtype=np.uint8))
    codec = CODECS[name]
    coded = codec.encode(s)
    assert (np.asarray(codec.decode(coded)) == np.asarray(s)).all()


def test_byte_maps_bijective_over_all_bytes():
    b = jnp.arange(256, dtype=jnp.uint8)
    for enc, dec in (
        (gray_encode_bytes, gray_decode_bytes),
        (sign_magnitude_encode_bytes, sign_magnitude_decode_bytes),
    ):
        e = np.asarray(enc(b))
        assert len(set(e.tolist())) == 256  # bijection
        assert (np.asarray(dec(jnp.asarray(e))) == np.arange(256)).all()


def test_bus_invert_matches_naive_sequential_and_bounds_hd():
    """The lax.scan encoder equals the textbook per-flit decision, and the
    coded wire never moves more than half the partition bits."""
    rng = np.random.default_rng(7)
    s = rng.integers(0, 256, (50, 8), dtype=np.uint8)
    for partition in (None, 4, 2):
        codec = make_bus_invert(partition)
        wire, inv = codec.encode(jnp.asarray(s))
        wire, inv = np.asarray(wire), np.asarray(inv)
        pw = 8 if partition is None else partition
        npart = 8 // pw
        # naive python re-implementation, partition by partition
        prev = s[0].reshape(npart, pw).astype(np.uint8)
        exp_wire = [s[0]]
        exp_inv = [np.zeros(npart, int)]
        for t in range(1, 50):
            d = s[t].reshape(npart, pw)
            row_w, row_v = [], []
            for part in range(npart):
                hd = int(
                    np.asarray(popcount(jnp.asarray(d[part] ^ prev[part]), 8)).sum()
                )
                v = int(2 * hd > 8 * pw)
                row_w.append(d[part] ^ (0xFF * v))
                row_v.append(v)
            prev = np.stack(row_w).astype(np.uint8)
            exp_wire.append(prev.reshape(-1))
            exp_inv.append(np.array(row_v))
        assert (wire == np.stack(exp_wire)).all()
        assert (inv == np.stack(exp_inv)).all()
        # the bus-invert guarantee, per partition
        wi = wire.reshape(50, npart, pw)
        hd = np.asarray(popcount(jnp.asarray(wi[1:] ^ wi[:-1]), 8)).sum(-1)
        assert hd.max() <= 8 * pw // 2


def test_transition_bt_equals_data_popcount():
    rng = np.random.default_rng(9)
    s = jnp.asarray(rng.integers(0, 256, (40, 16), dtype=np.uint8))
    wire = CODECS["transition"].encode(s).wire
    flips = popcount(
        jnp.bitwise_xor(wire[1:].astype(jnp.int32), wire[:-1].astype(jnp.int32)), 8
    )
    assert int(flips.sum()) == int(popcount(s[1:].astype(jnp.int32), 8).sum())


def test_codec_registry_unknown_name_lists_registered():
    with pytest.raises(ValueError, match="registered codecs"):
        codec_by_name("hamming")


# ------------------------------------------------- the single-launch kernel


def _grid_configs(width):
    orderings = [("none", None, False), ("acc", None, False),
                 ("acc", None, True)]
    orderings += [("app", k, False) for k in (2, 4, 8) if k <= width + 1]
    codecs = [("none", None), ("gray", None), ("sign_magnitude", None),
              ("transition", None), ("bus_invert", None), ("bus_invert", 4)]
    return tuple(
        CodecVariant(key, k, desc, scheme, part)
        for key, k, desc in orderings
        for scheme, part in codecs
    )


@pytest.mark.parametrize("width", [4, 8])
@pytest.mark.parametrize("p", [65, 7])  # non-block-multiple packet counts
def test_codec_kernel_matches_reference(width, p):
    """Acceptance: ONE launch covers every codec x ordering (none / acc /
    app k in {2,4,8}) x width 4/8 x non-block-multiple P, each config
    bit-exact (data sides AND invert lines) vs the ref.py composition."""
    rng = np.random.default_rng(hash((width, p)) % 2**31)
    hi = 2**width if width < 8 else 256
    x = jnp.asarray(rng.integers(0, hi, (p, 32), dtype=np.uint8))
    w = jnp.asarray(rng.integers(0, 256, (p, 32), dtype=np.uint8))
    configs = _grid_configs(width)
    got = np.asarray(
        bt_count_codecs(
            x, w, configs=configs, width=width, input_lanes=8,
            block_packets=16,
        )
    )
    ref = np.asarray(
        bt_codecs_ref(
            x, w, configs, width=width, input_lanes=8, weight_lanes=8
        )
    )
    np.testing.assert_array_equal(got, ref)


def test_codec_kernel_input_only_row_pack_and_single_launch():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(0, 256, (33, 48), dtype=np.uint8))
    configs = (
        CodecVariant("none"),
        CodecVariant("acc", codec="transition"),
        CodecVariant("app", 4, codec="bus_invert", partition=4),
    )
    for pack in ("lane", "row"):
        got = np.asarray(
            bt_count_codecs(
                x, None, configs=configs, input_lanes=16, pack=pack,
                block_packets=8,
            )
        )
        ref = np.asarray(
            bt_codecs_ref(
                x, None, configs, input_lanes=16, weight_lanes=0, pack=pack
            )
        )
        np.testing.assert_array_equal(got, ref)
        assert (got[:, 1] == 0).all()  # no weight side
    # the whole grid is ONE pallas launch in the traced jaxpr
    launches = pallas_launch_count(
        lambda s: bt_count_codecs(
            s, None, configs=configs, input_lanes=16, block_packets=8
        ),
        x,
    )
    assert launches == 1


def test_codec_kernel_validation():
    x = jnp.zeros((4, 16), jnp.uint8)
    with pytest.raises(ValueError):  # unknown scheme
        bt_count_codecs(x, configs=(CodecVariant(codec="bogus"),))
    with pytest.raises(ValueError):  # partition without bus_invert
        bt_count_codecs(x, configs=(CodecVariant(codec="gray", partition=4),))
    with pytest.raises(ValueError):  # partition not dividing the flit
        bt_count_codecs(
            x, configs=(CodecVariant(codec="bus_invert", partition=3),),
            input_lanes=8,
        )
    with pytest.raises(ValueError):  # ordering contract still enforced
        bt_count_codecs(x, configs=(CodecVariant("app", None),))


# -------------------------------------------------- link-layer integration


def test_tx_pipeline_coded_path_matches_kernel():
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.integers(0, 256, (20, 32), dtype=np.uint8))
    w = jnp.asarray(rng.integers(0, 256, (20, 32), dtype=np.uint8))
    spec = LinkSpec(key="app", codec="bus_invert4")
    rep = TxPipeline(spec).measure(x, w)
    got = np.asarray(
        bt_count_codecs(
            x, w, configs=(kernel_config(spec),), input_lanes=8
        )
    )[0]
    assert (rep.input_bt, rep.weight_bt, rep.aux_bt) == tuple(got.tolist())
    assert not rep.fused and rep.extra_wires == 4
    assert rep.gross_bt == rep.total_bt + rep.aux_bt
    # reduction is scored net of the invert lines
    base = TxPipeline(LinkSpec(key="none")).measure(x, w)
    assert rep.reduction_vs(base) == pytest.approx(
        1 - rep.gross_bt / base.total_bt
    )


def test_input_only_coded_link_frames_codec_on_actual_stream():
    """An input-only run of a paired spec assembles an input_lanes-wide
    stream; the codec partitions (and the wire/energy accounting) must
    follow that actual width, not bytes_per_flit."""
    rng = np.random.default_rng(19)
    x = jnp.asarray(rng.integers(0, 256, (10, 32), dtype=np.uint8))
    pipe = TxPipeline(LinkSpec(key="none", codec="bus_invert4"))
    res = pipe.run(x)  # default spec: 8 input + 8 weight lanes, no weights
    assert res.stream.shape[-1] == 8  # input half only
    assert res.invert.shape[-1] == 2  # 8 lanes / 4-lane partitions
    rep = pipe.measure(x)
    assert rep.extra_wires == 2
    m = LinkPowerModel()
    assert rep.energy_pj == pytest.approx(
        m.coded_link_energy_pj(rep.total_bt, rep.aux_bt, rep.num_flits, 64, 2)
    )


def test_link_spec_codec_validation_lists_names():
    with pytest.raises(ValueError, match="bus_invert"):
        LinkSpec(codec="bogus")
    with pytest.raises(ValueError):
        TxPipeline(LinkSpec(key="acc", codec="bus_invert"), fused=True).run(
            jnp.zeros((4, 32), jnp.uint8)
        )


def test_stage_registry_errors_list_registered_names():
    """Satellite: unknown stage-name errors enumerate the registry, like
    benchmarks/run.py does for unknown module names."""
    from repro.link import pack_to_flits

    for field, known in (
        ("key", "acc"),
        ("encode", "sign_magnitude"),
        ("pack", "lane"),
    ):
        with pytest.raises(ValueError, match=known):
            LinkSpec(**{field: "bogus"})
    with pytest.raises(ValueError, match="row"):
        pack_to_flits(jnp.zeros((2, 16), jnp.uint8), 8, "bogus")


def test_gray_is_an_encode_stage():
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.integers(0, 256, (8, 32), dtype=np.uint8))
    pipe = TxPipeline(LinkSpec(key="acc", encode="gray"))
    assert (
        np.asarray(pipe.encode(x)) == np.asarray(gray_encode_bytes(x))
    ).all()
    pipe.measure(x)  # end to end through the fused path


# ------------------------------------------------------ compare + overhead


@pytest.fixture(scope="module")
def conv_rows():
    streams = demo_workloads(images=2)["conv"]
    return compare_streams(
        streams, 16,
        orderings=("none", ("acc", None, False), ("app", 4, False)),
        codecs=("none", "bus_invert4"),
        workload="conv",
    )


def test_compare_reports_ordering_coding_and_composed(conv_rows):
    """Acceptance: bus-invert-alone, ordering-alone and composed BT
    reductions, net of invert-line overhead, on the conv workload."""
    by_label = {r.label: r for r in conv_rows}
    base = by_label["none"]
    coding = by_label["none+bus_invert4"]
    ordering = by_label["acc"]
    composed = by_label["acc+bus_invert4"]
    assert base.bt_reduction == 0.0 and base.aux_bt == 0
    # bus-invert fires on unordered conv traffic and pays for its lines
    assert coding.aux_bt > 0 and coding.extra_wires == 4
    assert coding.bt_reduction == pytest.approx(
        1 - coding.gross_bt / base.gross_bt
    )
    assert 0 < coding.bt_reduction < ordering.bt_reduction
    # composing coding on top of ordering still wins net of overhead
    assert composed.bt_reduction > ordering.bt_reduction
    assert composed.bt_reduction > coding.bt_reduction
    format_table(conv_rows)  # renders


def test_compare_all_pairs_one_launch_per_stream(conv_rows):
    # 3 orderings x 2 codecs = 6 pairs, baseline included in the grid
    assert len(conv_rows) == 6
    assert len({(r.ordering, r.codec) for r in conv_rows}) == 6


def test_overhead_accounting():
    ov = codec_overhead("bus_invert4", 16)
    assert ov.extra_wires == 4 and ov.data_wires == 128
    assert ov.wire_overhead == pytest.approx(4 / 128)
    assert ov.encoder_area_um2 == pytest.approx(codec_area("bus_invert", 16, 4))
    assert codec_overhead("gray", 16).extra_wires == 0
    assert codec_area("none", 16) == 0.0
    # PSUArea folds the encoder into the total
    a = PSUArea(100.0, 200.0, codec=50.0)
    assert a.total == 350.0
    # the energy model charges aux transitions and the widened floor
    m = LinkPowerModel()
    assert m.coded_link_energy_pj(1000, 0, 64, 128, 0) == pytest.approx(
        m.link_energy_pj(1000, 64)
    )
    coded = m.coded_link_energy_pj(1000, 50, 64, 128, 4)
    assert coded == pytest.approx(
        m.energy_per_transition_pj * 1050
        + m.static_flit_energy_pj * (1 + 4 / 128) * 64
    )


# --------------------------------------------------------- noc + dse axes


def test_noc_links_carry_coded_wire_and_aux():
    import dataclasses

    from repro.noc import TrafficFlow, mesh, simulate_noc
    from repro.noc.simulate import expand_link_streams

    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.integers(0, 256, (24, 64), dtype=np.uint8))
    topo = mesh(2, 2)
    spec = LinkSpec(
        width_bits=128, flits_per_packet=4, input_lanes=16, weight_lanes=0,
        key="acc", codec="bus_invert4",
    )
    flows = [TrafficFlow("f", 0, (3,), x)]
    plain = expand_link_streams(
        topo, flows, dataclasses.replace(spec, codec="none")
    )
    coded = expand_link_streams(topo, flows, spec)
    assert coded.link_ids == plain.link_ids
    codec = CODECS["bus_invert4"]
    for i, length in enumerate(plain.lengths):
        ref = codec.encode(plain.streams[i][:length])
        assert (
            np.asarray(coded.streams[i][:length]) == np.asarray(ref.wire)
        ).all()
        assert coded.aux_bt[i] == int(invert_line_transitions(ref.invert))
    rep = simulate_noc(topo, flows, spec)
    assert rep.total_aux_bt == sum(coded.aux_bt)
    assert rep.gross_bt == rep.total_bt + rep.total_aux_bt
    base = simulate_noc(topo, flows, dataclasses.replace(spec, key="none",
                                                         codec="none"))
    assert 0 < rep.reduction_vs(base) < 1


def test_design_point_codec_axis():
    from repro.dse import DesignPoint, expand_grid

    with pytest.raises(ValueError, match="registered codecs"):
        DesignPoint(codec="bogus")
    pt = DesignPoint(ordering="acc", k=None, codec="bus_invert4")
    assert pt.label == "acc+bus_invert4@N25"
    cv = pt.codec_variant
    assert cv.codec == "bus_invert" and cv.partition == 4
    grid = expand_grid(
        ks=(4,), orderings=("none", "acc"), codecs=(None, "bus_invert4")
    )
    assert [p.label for p in grid] == [
        "none@N25", "none+bus_invert4@N25", "acc@N25", "acc+bus_invert4@N25",
    ]


def test_evaluate_grid_codec_points_net_of_overhead():
    from repro.dse import DesignPoint, Workload, evaluate_grid, point_record

    rng = np.random.default_rng(23)
    stream = jnp.asarray(rng.integers(0, 256, (40, 64), dtype=np.uint8))
    workload = Workload("rand", (stream,), lanes=16)
    pts = (
        DesignPoint(ordering="acc", k=None),
        DesignPoint(ordering="acc", k=None, codec="bus_invert4"),
    )
    plain, coded = evaluate_grid(pts, workload)
    assert plain.aux_bt == 0 and plain.extra_wires == 0
    assert plain.area.codec == 0.0
    assert coded.extra_wires == 4
    assert coded.area.codec == pytest.approx(codec_area("bus_invert", 16, 4))
    assert coded.area_um2 == plain.area_um2 + coded.area.codec
    # the coded point's reduction is charged its invert-line transitions
    base = plain.total_bt / (1 - plain.bt_reduction)
    assert coded.bt_reduction == pytest.approx(
        1 - (coded.total_bt + coded.aux_bt) / base
    )
    rec = point_record(coded)
    assert rec["codec"] == "bus_invert4"
    assert rec["aux_bt"] == coded.aux_bt and rec["extra_wires"] == 4
