"""Per-architecture smoke tests (assignment deliverable f): reduced configs
of the same family, one forward/train step on CPU, output shapes + no NaNs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, arch_shapes, get_config, smoke_config
from repro.models import encdec_forward, forward, init_params, lm_loss, unembed
from repro.optim import AdamWConfig
from repro.optim import init as opt_init
from repro.train import make_train_step

KEY = jax.random.key(0)


def _batch(cfg, b=2, s=16):
    tok = jax.random.randint(KEY, (b, s), 0, cfg.vocab)
    lab = jax.random.randint(KEY, (b, s), 0, cfg.vocab)
    batch = {"tokens": tok, "labels": lab}
    if cfg.family in ("encdec", "audio"):
        batch["frames"] = jax.random.normal(KEY, (b, 8, cfg.d_model), jnp.float32)
    elif cfg.family == "vlm":
        batch["patches"] = jax.random.normal(KEY, (b, cfg.n_frontend_tokens, cfg.d_model))
        batch["labels"] = jnp.pad(
            lab, ((0, 0), (cfg.n_frontend_tokens, 0)), constant_values=-100
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_forward_shapes_and_finite(arch):
    cfg = smoke_config(arch)
    params = init_params(cfg, KEY)
    batch = _batch(cfg)
    if cfg.family in ("encdec", "audio"):
        h, aux = encdec_forward(params, cfg, batch["frames"], batch["tokens"])
        want_s = batch["tokens"].shape[1]
    elif cfg.family == "vlm":
        h, aux = forward(params, cfg, tokens=batch["tokens"], inputs_embeds=batch["patches"])
        want_s = batch["tokens"].shape[1] + cfg.n_frontend_tokens
    else:
        h, aux = forward(params, cfg, tokens=batch["tokens"])
        want_s = batch["tokens"].shape[1]
    assert h.shape == (2, want_s, cfg.d_model)
    logits = unembed(params, cfg, h)
    assert logits.shape == (2, want_s, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    loss = lm_loss(params, cfg, h, batch["labels"])
    assert np.isfinite(float(loss)) and float(loss) > 0


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_one_train_step(arch):
    cfg = smoke_config(arch)
    params = init_params(cfg, KEY)
    opt = opt_init(params)
    step = jax.jit(make_train_step(cfg, AdamWConfig(warmup_steps=1, total_steps=10)))
    p2, o2, metrics = step(params, opt, _batch(cfg))
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    # params actually moved
    moved = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2))
    )
    assert moved
    assert np.isfinite(
        np.asarray(jax.tree.leaves(p2)[0], np.float32)
    ).all()


def test_exact_assigned_configs_match_table():
    """Spot-check the full configs against the assignment table."""
    g = get_config("gemma-7b")
    assert (g.n_layers, g.d_model, g.n_heads, g.n_kv_heads) == (28, 3072, 16, 16)
    assert (g.head_dim, g.d_ff, g.vocab, g.act) == (256, 24576, 256000, "geglu")
    q = get_config("qwen3-moe-30b-a3b")
    assert (q.moe.num_experts, q.moe.top_k, q.moe.d_ff_expert) == (128, 8, 768)
    assert (q.n_layers, q.d_model, q.n_kv_heads, q.vocab) == (48, 2048, 4, 151936)
    m = get_config("mamba2-370m")
    assert (m.n_layers, m.d_model, m.vocab, m.ssm.d_state) == (48, 1024, 50280, 128)
    z = get_config("zamba2-1.2b")
    assert (z.n_layers, z.d_model, z.vocab, z.ssm.d_state) == (38, 2048, 32000, 64)
    i = get_config("internvl2-26b")
    assert (i.n_layers, i.d_model, i.n_heads, i.n_kv_heads, i.d_ff, i.vocab) == (
        48, 6144, 48, 8, 16384, 92553)
    w = get_config("whisper-medium")
    assert (w.n_layers, w.n_enc_layers, w.d_model, w.vocab) == (24, 24, 1024, 51865)
    gr = get_config("granite-moe-3b-a800m")
    assert (gr.moe.num_experts, gr.moe.top_k, gr.moe.padded_experts) == (40, 8, 48)


def test_shape_assignment():
    """long_500k runs for SSM/hybrid only; all archs get the other three."""
    for arch in ARCH_NAMES:
        shapes = arch_shapes(arch)
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(shapes)
        fam = get_config(arch).family
        assert ("long_500k" in shapes) == (fam in ("ssm", "hybrid"))
    # 44 nominal cells (11 archs) minus 9 documented long_500k skips
    total = sum(len(arch_shapes(a)) for a in ARCH_NAMES)
    assert total == 35
