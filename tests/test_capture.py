"""Real-model traffic capture (``repro.obs.capture``, DESIGN.md §16).

The load-bearing claim mirrors ``tests/test_obs.py``: ZERO cost when off.
Model-zoo hot paths carry tap sites (``repro._obs_hooks.tap`` — a None
test while no capture is active), and the installed tap performs no jax
operation on tracer payloads, so every model-zoo traced jaxpr is
byte-identical whether capture is absent from the process, imported but
inactive, or actively recording (subprocess- and in-process-pinned).

The rest pins capture determinism, the save/load replay round-trip, BT
consistency between captured packets and ``stream_bt_report`` (same wire
image, same totals), the clear flit-divisibility error, the per-config
smoke (every ``repro.configs`` arch flows through capture), the trained
LeNet (learns + checkpoints + restores), and the MoE dispatch adapter.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import _obs_hooks, obs
from repro.configs import ARCH_NAMES, smoke_config
from repro.link import LinkSpec, TxPipeline
from repro.models import init_cache, init_params
from repro.models.moe import init_moe, moe_block
from repro.noc import mesh, moe_dispatch_flows, simulate_noc
from repro.optim import AdamWConfig
from repro.optim import init as opt_init
from repro.train import make_train_step

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dense_cfg():
    return smoke_config("qwen3-4b")


def _model_jaxprs(cfg):
    """Traced-jaxpr strings of the tapped model-zoo entry points."""
    from repro.models import decode_step
    from repro.models.lenet import init_lenet, lenet_forward
    from repro.obs.capture import train_batch

    key = jax.random.key(0)
    params = init_params(cfg, key)
    cache = init_cache(cfg, 2, 8)
    tok = jnp.zeros((2, 1), jnp.int32)
    step = make_train_step(cfg, AdamWConfig(warmup_steps=1, total_steps=10))
    opt = opt_init(params)
    batch = train_batch(cfg, 2, 8)
    lparams = init_lenet(key)
    imgs = jnp.zeros((2, 32, 32, 1), jnp.float32)
    return {
        "decode_step": str(jax.make_jaxpr(
            lambda p, c, t: decode_step(p, cfg, c, t))(params, cache, tok)),
        "train_step": str(jax.make_jaxpr(step)(params, opt, batch)),
        "lenet": str(jax.make_jaxpr(lenet_forward)(lparams, imgs)),
    }


# --------------------------------------------- zero cost when disabled


def test_model_jaxprs_identical_with_capture_absent_vs_active():
    """In a fresh process: serve/train/models never import repro.obs, and
    installing + activating capture leaves every model-zoo traced jaxpr
    byte-identical (the tentpole claim; capture therefore adds zero
    launches to any measured path)."""
    script = """
import sys

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.models import decode_step, forward, init_cache, init_params
from repro.models.lenet import init_lenet, lenet_forward
from repro.models.moe import init_moe, moe_block
from repro.optim import AdamWConfig, init as opt_init
from repro.serve.loop import generate
from repro.train import make_train_step

assert "repro.obs" not in sys.modules, "production code imported repro.obs"

cfg = smoke_config("qwen3-4b")
mcfg = smoke_config("qwen3-moe-30b-a3b")
key = jax.random.key(0)
params = init_params(cfg, key)
cache = init_cache(cfg, 2, 8)
tok = jnp.zeros((2, 1), jnp.int32)
step = make_train_step(cfg, AdamWConfig(warmup_steps=1, total_steps=10))
opt = opt_init(params)
batch = {
    "tokens": jnp.zeros((2, 8), jnp.int32),
    "labels": jnp.zeros((2, 8), jnp.int32),
}
mparams = init_moe(key, mcfg)
mx = jnp.zeros((2, 8, mcfg.d_model), jnp.dtype(mcfg.dtype))
dcfg = smoke_config("deepseek-v2-lite")
dparams = init_params(dcfg, key)
lparams = init_lenet(key)
imgs = jnp.zeros((2, 32, 32, 1), jnp.float32)

def jaxprs():
    return {
        "decode_step": str(jax.make_jaxpr(
            lambda p, c, t: decode_step(p, cfg, c, t))(params, cache, tok)),
        "train_step": str(jax.make_jaxpr(step)(params, opt, batch)),
        "moe_block": str(jax.make_jaxpr(
            lambda p, x: moe_block(p, x, mcfg))(mparams, mx)),
        "moe_forward": str(jax.make_jaxpr(
            lambda p, t: forward(p, dcfg, tokens=t))(
                dparams, batch["tokens"])),
        "lenet": str(jax.make_jaxpr(lenet_forward)(lparams, imgs)),
    }

before = jaxprs()
assert "repro.obs" not in sys.modules, "tracing imported repro.obs"
from repro import obs
mid = jaxprs()
with obs.capture() as sess:
    active = jaxprs()
assert before == mid, "importing repro.obs changed a model jaxpr"
assert before == active, "active capture changed a model jaxpr"
# the traced firings carried tracers and were dropped whole
assert sess.streams == [], "capture recorded tracer payloads"
print("CAPTURE-JAXPR-IDENTITY-OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO, "src"), _REPO]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=_REPO, timeout=600,
    )
    assert out.returncode == 0, out.stderr
    assert "CAPTURE-JAXPR-IDENTITY-OK" in out.stdout


def test_jaxpr_identity_in_process():
    """Same identity in this process: inactive vs installed vs recording."""
    cfg = _dense_cfg()
    assert _obs_hooks.TAP is None
    before = _model_jaxprs(cfg)
    with obs.capture() as sess:
        assert _obs_hooks.TAP is not None
        assert _obs_hooks.capturing()
        active = _model_jaxprs(cfg)
    assert _obs_hooks.TAP is None
    assert not _obs_hooks.capturing()
    assert before == active
    # every in-trace firing carried tracers and was dropped whole
    assert sess.streams == []


def test_tap_site_is_noop_without_capture():
    """A tap firing with no capture active records nowhere and returns."""
    _obs_hooks.tap("serve.weights", params={"w": jnp.ones((2, 2))})


# --------------------------------------------- recording real traffic


def test_capture_serve_decode_records_and_is_deterministic():
    cfg = _dense_cfg()
    a = obs.capture_serve_decode(cfg, batch=2, prompt=8, new_tokens=2)
    b = obs.capture_serve_decode(cfg, batch=2, prompt=8, new_tokens=2)
    assert a.scenarios() == ("serve_decode",)
    names = [s.name for s in a.streams]
    assert names == ["weights", "kv", "kv"]
    assert all(s.num_bytes > 0 for s in a.streams)
    assert all(s.data.dtype == np.uint8 for s in a.streams)
    # same model, same seed -> byte-identical capture (replay determinism)
    assert len(a.streams) == len(b.streams)
    for sa, sb in zip(a.streams, b.streams):
        np.testing.assert_array_equal(sa.data, sb.data)


def test_capture_train_and_moe_drivers():
    grads = obs.capture_train_step(_dense_cfg(), batch=2, seq=8)
    (g,) = grads.get("train_allreduce")
    assert g.kind == "train.grads" and g.num_bytes > 0

    mcfg = smoke_config("qwen3-moe-30b-a3b")
    moe = obs.capture_moe_dispatch(mcfg, batch=2, seq=8)
    # one stream and one route per expert layer of the forward
    streams = moe.get("moe_dispatch")
    assert [e.name for e in streams] == ["x"] * mcfg.n_layers
    assert all(e.source_shape == (16, mcfg.d_model) for e in streams)
    assert [r.layer for r in moe.routes] == list(range(mcfg.n_layers))
    assert moe.routes[0].expert_ids.shape == (16, mcfg.moe.top_k)
    with pytest.raises(ValueError, match="MoE"):
        obs.capture_moe_dispatch(_dense_cfg())


def test_capture_fires_probe_events():
    """Each recorded stream fires a capture.stream event: byte counters per
    scenario/stream land on active registries."""
    cfg = _dense_cfg()
    with obs.collect() as reg:
        sess = obs.capture_train_step(cfg, batch=2, seq=8)
    (g,) = sess.get("train_allreduce")
    assert reg.value(
        "capture.bytes", scenario="train_allreduce", stream="grads"
    ) == g.num_bytes
    assert reg.value(
        "capture.streams", scenario="train_allreduce", stream="grads"
    ) == 1


def test_nested_capture_sessions_both_record():
    with obs.capture() as outer:
        with obs.capture() as inner:
            obs.capture_train_step(_dense_cfg(), batch=2, seq=8)
    assert len(outer.streams) == len(inner.streams) == 1
    np.testing.assert_array_equal(outer.streams[0].data, inner.streams[0].data)


# --------------------------------------------- replay round-trip


def test_save_load_session_roundtrip(tmp_path):
    sess = obs.capture_train_step(_dense_cfg(), batch=2, seq=8)
    path = str(tmp_path / "capture.npz")
    obs.save_session(path, sess)
    back = obs.load_session(path)
    assert len(back.streams) == len(sess.streams)
    for sa, sb in zip(sess.streams, back.streams):
        assert (sa.scenario, sa.name, sa.kind) == (sb.scenario, sb.name, sb.kind)
        assert sa.source_shape == sb.source_shape
        assert sa.meta == sb.meta
        np.testing.assert_array_equal(sa.data, sb.data)
    # replayed workload measures identically
    wa = sess.workload("train_allreduce", elems=64)
    wb = back.workload("train_allreduce", elems=64)
    spec = LinkSpec(
        width_bits=128, flits_per_packet=4, input_lanes=16, weight_lanes=0,
        key="acc",
    )
    for a, b in zip(wa.streams, wb.streams):
        ra = TxPipeline(spec).measure(a)
        rb = TxPipeline(spec).measure(b)
        assert ra.overall_bt_per_flit == rb.overall_bt_per_flit


# --------------------------------------------- BT consistency


def test_capture_bt_matches_stream_bt_report():
    """The captured wire image is THE wire image: measuring a captured
    tensor's packets (row-pack framing) gives byte-identical baseline BT
    to ``repro.traffic.stream_bt_report`` on the original tensor."""
    from repro.traffic.ordering import stream_bt_report

    rng = np.random.default_rng(3)
    t = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))
    sess = obs.CaptureSession()
    sess.add("manual", "w", t)
    pkts = sess.packets("manual", 64)
    spec = LinkSpec(
        width_bits=128, flits_per_packet=4, input_lanes=16, weight_lanes=0,
        key="none", pack="row",
    )
    m = TxPipeline(spec).measure(pkts)
    rep = stream_bt_report("w", t, strategy="acc", lanes=16, layout="row")
    assert m.num_flits == rep.num_flits
    assert int(round(m.overall_bt_per_flit * m.num_flits)) == rep.bt_none


def test_workload_bt_sums_over_streams():
    """Workload streams are measured independently (no seam transitions),
    so a scenario's total BT is exactly the sum of its per-stream BT —
    the sum-over-scenarios consistency behind the campaign tables."""
    from repro.dse import DesignPoint, evaluate_grid

    rng = np.random.default_rng(5)
    sess = obs.CaptureSession()
    for i, shape in enumerate([(4, 64), (6, 64)]):
        sess.add("manual", f"s{i}", jnp.asarray(
            rng.normal(size=shape).astype(np.float32)
        ))
    wl = sess.workload("manual", elems=64)
    (ev,) = evaluate_grid([DesignPoint(ordering="none", k=None)], wl)
    spec = LinkSpec(
        width_bits=128, flits_per_packet=4, input_lanes=16, weight_lanes=0,
        key="none",
    )
    per_stream = sum(
        int(round(
            TxPipeline(spec).measure(s).overall_bt_per_flit
            * 4 * int(s.shape[0])
        ))
        for s in wl.streams
    )
    assert ev.total_bt == per_stream


# --------------------------------------------- clear divisibility errors


def test_flit_divisibility_error_is_clear():
    sess = obs.CaptureSession()
    sess.add("manual", "odd", jnp.ones((10, 10), jnp.float32))  # 100 bytes
    with pytest.raises(ValueError, match="my-config.*not.*divisible"):
        sess.packets("manual", 64, owner="my-config", strict=True)
    # non-strict trims to whole packets instead
    assert sess.packets("manual", 64).shape == (1, 64)
    with pytest.raises(ValueError, match="smaller than one"):
        sess.packets("manual", 128, owner="my-config")
    with pytest.raises(ValueError, match="no captured streams"):
        sess.workload("nothing")


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_every_config_flows_through_capture(arch):
    """The satellite fix for the dead-weight model zoo: every one of the
    ~10 real configs drives a captured train step; non-flit-divisible
    shapes fail with the clear ValueError naming the config, never a
    shape crash."""
    cfg = smoke_config(arch)
    sess = obs.capture_train_step(cfg, batch=2, seq=8)
    streams = sess.get("train_allreduce")
    assert streams and all(s.num_bytes > 0 for s in streams)
    try:
        wl = sess.workload("train_allreduce", elems=64, owner=arch, strict=True)
    except ValueError as e:
        assert arch in str(e) and "divisible" in str(e)
        wl = sess.workload("train_allreduce", elems=64, owner=arch)
    assert wl.num_flits > 0


# --------------------------------------------- trained LeNet


def test_lenet_trains_and_checkpoints(tmp_path):
    from repro.models import lenet

    params, info = lenet.train_lenet(
        steps=30, batch=32, ckpt_dir=str(tmp_path)
    )
    assert info["restored"] is False
    # the synthetic task is learnable: well under chance cross-entropy
    assert info["final_loss"] < 1.0
    restored, info2 = lenet.train_lenet(
        steps=30, batch=32, ckpt_dir=str(tmp_path)
    )
    assert info2["restored"] is True and info2["steps"] == 30
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lenet_capture_streams():
    sess = obs.capture_lenet_conv(steps=5, batch=16)
    names = {s.name for s in sess.get("lenet_conv")}
    assert names == {"conv1", "conv2", "inputs"}
    conv2 = sess.get("lenet_conv", "conv2")[0]
    assert conv2.source_shape == (5, 5, 6, 16)
    assert conv2.num_bytes == 5 * 5 * 6 * 16


# --------------------------------------------- MoE dispatch adapter


def test_moe_dispatch_flows_adapter():
    """A captured route as the expert-parallel all-to-all: every chip of
    the group sends, each flow is one (source chip, expert) unicast of
    whole token rows, and a flow to the chip's own expert stays off the
    fabric."""
    mcfg = smoke_config("qwen3-moe-30b-a3b")
    sess = obs.capture_moe_dispatch(mcfg, batch=2, seq=8)
    route = sess.routes[0]
    topo = mesh(4, 4)
    chips = (0, 1, 2, 3, 4, 5, 6, 7)  # 8 experts, one a chip
    spec = LinkSpec(
        width_bits=128, flits_per_packet=4, input_lanes=16, weight_lanes=0,
        key="acc",
    )
    codes = jnp.asarray(
        sess.get("moe_dispatch", "x")[0].data.view(np.int8).reshape(16, 64)
    )
    with obs.collect() as reg:
        flows = moe_dispatch_flows(codes, route.expert_ids, topo,
                                   num_experts=8, chips=chips, spec=spec)
    ids = np.asarray(route.expert_ids)
    pairs = {(t // 2, int(e)) for t in range(16) for e in ids[t]}
    local = sum(s == e for s, e in pairs)
    assert len(flows) == len(pairs) - local
    assert all(len(f.dsts) == 1 and f.src != f.dsts[0] for f in flows)
    assert sum(f.inputs.shape[0] for f in flows) == (
        sum(int(e) != t // 2 for t in range(16) for e in ids[t]))
    lab = {"chips": 8, "experts": 8}
    assert reg.value("moe.load.flows", **lab) == len(flows)
    assert reg.value("moe.load.local_flows", **lab) == local
    assert reg.value("moe.route.calls", **lab) == 1
    rep = simulate_noc(topo, flows, spec, sort_at="source")
    assert rep.total_bt > 0
    with pytest.raises(ValueError, match="tokens, d_model"):
        moe_dispatch_flows(codes[0], route.expert_ids, topo, num_experts=8,
                           chips=chips, spec=spec)
    with pytest.raises(ValueError, match="weight_lanes=0"):
        moe_dispatch_flows(codes, route.expert_ids, topo, num_experts=8,
                           chips=chips, spec=LinkSpec(key="acc"))


def test_adapter_int8_passthrough():
    """int8/uint8 adapter inputs ARE their wire image: the flows carry the
    same bytes, not a re-quantized (rescaled) copy."""
    from repro.noc.adapters import _wire_bytes

    b = np.arange(-60, 68, dtype=np.int8)  # amax < 127: int8_view would rescale
    out = np.asarray(_wire_bytes(jnp.asarray(b)))
    np.testing.assert_array_equal(out, b.view(np.uint8))


# --------------------------------------------- probe vocabulary


def test_capture_kind_in_probe_vocabulary():
    assert obs.PROBE_KINDS["capture.stream"] == "event"
    assert set(obs.TAP_SCENARIOS) == {
        "serve.weights", "serve.kv", "train.grads", "moe.dispatch",
        "lenet.conv",
    }
