"""DeepSeek-V2-Lite at smoke size against the plain float32 reference
(``repro.models.reference_deepseek_v2``), and the ``moe.dispatch`` capture
from the normal forward.

The program runs the published equations through ``transformer.forward``:
latent attention with yarn RoPE, a leading dense layer, softmax routing
with un-renormalized greedy top-k, shared experts and the dropless grouped
dispatch.  In float32 on the CPU its matrix products are exact float32, so
it must agree with the reference to float32 rounding; in bfloat16 to what
bfloat16 rounding leaves.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import _obs_hooks, obs
from repro.configs import get_config
from repro.configs.deepseek_v2_lite import PUBLISHED, from_hf
from repro.models import forward, init_params
from repro.models import reference_deepseek_v2 as ref
from repro.models.mla import softmax_scale, yarn_inv_freq

# the published config at smoke widths: every mechanism kept (yarn factor
# 40 with a short original context, so the ramp lies inside the 4 rope
# frequencies), 8 experts of which 3 per token, 1 dense + 2 expert layers
SMOKE_HF = {
    **PUBLISHED,
    "hidden_size": 64, "intermediate_size": 96, "kv_lora_rank": 16,
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 3, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab_size": 256,
    "rope_scaling": {**PUBLISHED["rope_scaling"],
                     "original_max_position_embeddings": 64},
}


def _setup(dtype, seed=0, b=2, s=24):
    cfg = from_hf(SMOKE_HF, dtype=dtype, attn_impl="dense")
    params = init_params(cfg, jax.random.key(seed))
    tok = jax.random.randint(jax.random.key(seed + 1), (b, s), 0, cfg.vocab)
    return cfg, params, tok


def _captured(cfg, params, tok):
    sess = obs.capture_moe_dispatch(cfg, params=params, tokens=tok)
    return sess.routes


def test_published_config():
    c = get_config("deepseek-v2-lite")
    assert (c.n_layers, c.d_model, c.n_heads, c.vocab) == (27, 2048, 16, 102400)
    assert (c.first_k_dense, c.d_ff, c.scale_embeddings) == (1, 10944, False)
    a = c.mla
    assert (a.kv_lora_rank, a.qk_nope_head_dim, a.qk_rope_head_dim,
            a.v_head_dim) == (512, 128, 64, 128)
    m = c.moe
    assert (m.num_experts, m.top_k, m.d_ff_expert, m.num_shared_experts,
            m.norm_topk_prob, m.dropless) == (64, 6, 1408, 2, False, True)
    # 15.7 B parameters, as published
    assert round(c.param_count() / 1e9, 1) == 15.7
    # the softmax scale: 192^-0.5 m^2, m = 0.1 * 0.707 * ln 40 + 1
    m_ = 0.1 * 0.707 * np.log(40) + 1
    assert softmax_scale(a) == pytest.approx(192 ** -0.5 * m_ * m_)


def test_yarn_frequencies_match_reference():
    for hf in (PUBLISHED, SMOKE_HF):
        cfg = from_hf(hf)
        np.testing.assert_array_equal(
            yarn_inv_freq(cfg.mla, cfg.rope_theta), ref.rope_inv_freq(hf))
    # the ramp: the highest frequencies kept, the lowest divided by 40
    inv = ref.rope_inv_freq(PUBLISHED)
    base = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:5], base[:5], rtol=1e-6)
    np.testing.assert_allclose(inv[-5:], base[-5:] / 40, rtol=1e-6)


@pytest.mark.parametrize("seed", [0, 1])
def test_forward_matches_float32_reference(seed):
    """Float32 program against the float32 reference: the same equations,
    so the MoE inputs, router probabilities and final hidden state agree
    to float32 rounding (rtol 1e-4 leaves room for summation order over
    3 layers), and the top-k sets are the same."""
    cfg, params, tok = _setup("float32", seed)
    routes = _captured(cfg, params, tok)
    h, _ = forward(params, cfg, tokens=tok)
    want = ref.forward(params, SMOKE_HF, tok)
    assert [r.layer for r in routes] == [1, 2]
    for r, w in zip(routes, want["moe"]):
        np.testing.assert_allclose(r.x, w["x"], rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(r.probs, w["probs"], rtol=1e-4, atol=1e-6)
        np.testing.assert_array_equal(np.sort(r.expert_ids, -1),
                                      np.sort(w["expert_ids"], -1))
    final = ref.rms_norm(want["hidden"], params["final_norm"],
                         SMOKE_HF["rms_norm_eps"])
    np.testing.assert_allclose(h, final, rtol=1e-4, atol=1e-4)


def test_bfloat16_forward_within_rounding():
    """In bfloat16 the MoE inputs are within 3 % of the float32 reference
    (relative Frobenius norm; 1.1-1.9 % measured over three seeds: bfloat16
    keeps 8 mantissa bits, 0.4 % per rounding, and the residual stream,
    attention probabilities and every product are rounded over two
    layers) and the router probabilities within 0.03 absolute (such an
    error moves logits of unit spread by a few hundredths, which moves a
    probability p by a few per cent of p; 0.016 measured)."""
    cfg, params, tok = _setup("bfloat16")
    routes = _captured(cfg, params, tok)
    want = ref.forward(params, SMOKE_HF, tok)
    for r, w in zip(routes, want["moe"]):
        assert r.x.dtype == jnp.bfloat16
        x = np.asarray(r.x, np.float32)
        err = np.linalg.norm(x - w["x"]) / np.linalg.norm(w["x"])
        assert err < 3e-2, err
        assert np.abs(np.asarray(r.probs) - w["probs"]).max() < 3e-2


def test_dense_then_moe_and_unnormalized_topk():
    """Layer 0 is dense (no route), the expert layers route greedily over
    all experts, and the kept weights are the raw softmax probabilities
    (they sum to less than 1)."""
    cfg, params, tok = _setup("float32")
    assert params["dense_layers"]["mlp"]["gate"].shape == (1, 64, 96)
    assert params["layers"]["moe"]["gate"].shape == (2, 8, 64, 32)
    (r, _) = _captured(cfg, params, tok)
    top = np.take_along_axis(np.asarray(r.probs),
                             np.asarray(r.expert_ids), -1)
    assert (top.sum(-1) < 1).all()
    np.testing.assert_array_equal(
        top, -np.sort(-np.asarray(r.probs), -1)[:, :3])


def test_grouped_dispatch_is_dropless():
    """Every token reaches all of its experts: the grouped product equals
    an explicit per-token sum over its k experts plus the shared ones."""
    from repro.models.moe import moe_block

    cfg, params, _ = _setup("float32")
    lp = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    # every token routed to the same experts: the worst case for a buffer
    x = jnp.tile(jax.random.normal(jax.random.key(3), (1, 1, 64)), (2, 16, 1))
    y, _, route = moe_block(lp, x, cfg, return_route=True)
    xf = np.asarray(x.reshape(-1, 64), np.float64)

    def swiglu(v, g, u, d):
        g, u, d = (np.asarray(a, np.float64) for a in (g, u, d))
        z = v @ g
        return ((z / (1 + np.exp(-z))) * (v @ u)) @ d

    want = swiglu(xf, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    probs = np.asarray(route["probs"], np.float64)
    for t in range(xf.shape[0]):
        for e in np.asarray(route["expert_ids"][t]):
            want[t] += probs[t, e] * swiglu(xf[t], lp["gate"][e], lp["up"][e],
                                            lp["down"][e])
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 64), want,
                               rtol=1e-4, atol=1e-5)


def test_capture_fires_from_normal_forward_and_jaxpr_unchanged():
    """The tap fires once per expert layer from ``forward`` itself, the
    captured pass computes what the scanned pass computes, and the traced
    jaxpr is the same with capture absent and active."""
    cfg, params, tok = _setup("bfloat16")

    def jaxpr():
        return str(jax.make_jaxpr(lambda p, t: forward(p, cfg, tokens=t))(
            params, tok))

    before = jaxpr()
    with obs.capture() as sess:
        assert _obs_hooks.capturing()
        during = jaxpr()
        h_cap, aux_cap = forward(params, cfg, tokens=tok)
    assert before == during == jaxpr()
    assert len(sess.routes) == 2
    assert [s.name for s in sess.get("moe_dispatch")] == ["x", "x"]
    assert sess.get("moe_dispatch")[0].meta == {"layer": 1, "experts": 8,
                                                "top_k": 3}
    h, aux = forward(params, cfg, tokens=tok)
    np.testing.assert_allclose(np.asarray(h_cap, np.float32),
                               np.asarray(h, np.float32), atol=2e-2)
    assert float(aux_cap) == pytest.approx(float(aux), rel=1e-3)
    # under jit the tap sees tracers and records nothing
    with obs.capture() as sess2:
        jax.jit(lambda p, t: forward(p, cfg, tokens=t))(params, tok)
    assert sess2.routes == [] and sess2.streams == []


def test_reference_copies_are_identical():
    """``perfbench/reference_deepseek_v2.py`` is this reference, byte for
    byte, so the benchmark's comparison is the one tested here."""
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(ref.__file__) as a, open(os.path.join(
            here, "perfbench", "reference_deepseek_v2.py")) as b:
        assert a.read() == b.read()
