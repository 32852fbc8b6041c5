"""Data pipeline, optimizer, compression, checkpointing, fault tolerance."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticLMDataset
from repro.optim import (
    AdamWConfig,
    CompressionConfig,
    compressed_psum,
    global_norm,
    init,
    lr_schedule,
    update,
)

KEY = jax.random.key(0)


# ---------------- data pipeline ----------------


def test_data_determinism_and_sharding():
    cfg = DataConfig(vocab=101, seq_len=16, global_batch=8, seed=5)
    ds = SyntheticLMDataset(cfg)
    g1, g2 = ds.global_batch(3), ds.global_batch(3)
    np.testing.assert_array_equal(g1["tokens"], g2["tokens"])
    # shards tile the global batch exactly, for any shard count
    for ns in (1, 2, 4, 8):
        parts = [ds.shard_batch(3, s, ns)["tokens"] for s in range(ns)]
        np.testing.assert_array_equal(np.concatenate(parts), g1["tokens"])
    # labels are next-token shifted
    row = ds._row(3, 0)
    np.testing.assert_array_equal(g1["tokens"][0], row[:-1])
    np.testing.assert_array_equal(g1["labels"][0], row[1:])


def test_data_steps_differ():
    ds = SyntheticLMDataset(DataConfig(vocab=101, seq_len=16, global_batch=2))
    assert not np.array_equal(ds.global_batch(0)["tokens"], ds.global_batch(1)["tokens"])


# ---------------- optimizer ----------------


def test_adamw_minimizes_quadratic():
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = init(params)
    cfg = AdamWConfig(peak_lr=0.1, warmup_steps=5, total_steps=300, weight_decay=0.0)
    for _ in range(300):
        grads = {"w": 2 * (params["w"] - target)}
        params, state, _ = update(cfg, grads, state, params)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target), atol=1e-2)


def test_grad_clipping():
    params = {"w": jnp.zeros(4)}
    state = init(params)
    cfg = AdamWConfig(clip_norm=1.0, warmup_steps=0, weight_decay=0.0)
    _, _, metrics = update(cfg, {"w": jnp.full(4, 1e6)}, state, params)
    assert float(metrics["grad_norm"]) > 1e6  # reported pre-clip


def test_lr_schedule_shape():
    cfg = AdamWConfig(peak_lr=1.0, warmup_steps=10, total_steps=110, min_lr_ratio=0.1)
    lr = lr_schedule(cfg)
    assert float(lr(jnp.int32(0))) == 0.0
    assert float(lr(jnp.int32(10))) == pytest.approx(1.0)
    assert float(lr(jnp.int32(110))) == pytest.approx(0.1, abs=1e-6)
    assert float(lr(jnp.int32(60))) < 1.0


def test_global_norm():
    t = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
    assert float(global_norm(t)) == pytest.approx(5.0)


# ---------------- compressed all-reduce (shard_map, 1-device axis) --------


def _run_compressed(mode, g, err, perm=None, inv=None):
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    cfg = CompressionConfig(mode=mode, block=64,
                            use_egress_ordering=perm is not None)

    @jax.jit
    def f(g, err):
        return jax.shard_map(
            lambda g, e: compressed_psum(g, e, cfg, ("data",), perm, inv),
            mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
            out_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
        )(g, err)

    return f(g, err)


def test_int8_ef_error_feedback_converges():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(256,)).astype(np.float32))
    err = jnp.zeros_like(g)
    # EF property: accumulated compressed sum -> accumulated true sum
    acc_comp = jnp.zeros_like(g)
    for _ in range(50):
        out, err = _run_compressed("int8_ef", g, err)
        acc_comp = acc_comp + out
    rel = float(jnp.linalg.norm(acc_comp - 50 * g) / jnp.linalg.norm(50 * g))
    assert rel < 0.01, rel


def test_int8_ef_single_step_bounded_error():
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
    out, err = _run_compressed("int8_ef", g, jnp.zeros_like(g))
    np.testing.assert_allclose(np.asarray(out + err), np.asarray(g), rtol=1e-5, atol=1e-6)


def test_bf16_mode():
    g = jnp.asarray(np.random.default_rng(2).normal(size=(64,)).astype(np.float32))
    out, _ = _run_compressed("bf16", g, jnp.zeros_like(g))
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), rtol=1e-2)


def test_ordered_egress_is_transparent():
    from repro.traffic import egress_permutation, int8_view

    rng = np.random.default_rng(3)
    w = int8_view(jnp.asarray(rng.normal(size=(256,))))
    perm, inv = egress_permutation(w, packet=64)
    g = jnp.asarray(rng.normal(size=(256,)).astype(np.float32))
    base, _ = _run_compressed("int8_ef", g, jnp.zeros_like(g))
    ordered, _ = _run_compressed("int8_ef", g, jnp.zeros_like(g),
                                 jnp.asarray(perm), jnp.asarray(inv))
    np.testing.assert_allclose(np.asarray(base), np.asarray(ordered), rtol=1e-6)


# ---------------- checkpointing ----------------


def test_checkpoint_roundtrip(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": np.arange(10, dtype=np.float32), "b": {"c": np.ones((2, 3))}}
    m.save(1, tree, extra={"data_step": 1})
    m.save(2, tree, extra={"data_step": 2})
    got, extra, step = m.restore(tree)
    assert step == 2 and extra["data_step"] == 2
    np.testing.assert_array_equal(got["a"], tree["a"])


def test_checkpoint_gc_keeps_n(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2)
    for s in range(5):
        m.save(s, {"x": np.zeros(1)})
    assert m.all_steps() == [3, 4]


def test_checkpoint_corruption_fallback(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=5)
    tree = {"x": np.arange(4, dtype=np.float32)}
    m.save(1, tree)
    m.save(2, {"x": np.arange(4, dtype=np.float32) * 2})
    # corrupt the newest
    with open(os.path.join(str(tmp_path), "step_0000000002", "arrays.npz"), "r+b") as f:
        f.seek(200)
        f.write(b"\xde\xad\xbe\xef")
    got, _, step = m.restore(tree)
    assert step == 1
    np.testing.assert_array_equal(got["x"], tree["x"])


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    m = CheckpointManager(str(tmp_path))
    m.save(1, {"x": np.zeros((2, 2))})
    with pytest.raises(FileNotFoundError):
        m.restore({"x": np.zeros((3, 3))})


def test_restart_equivalence_bitwise(tmp_path):
    """Full fault-tolerance test: preempt mid-run, resume, final params must
    be BITWISE identical to the uninterrupted run."""
    from repro.configs import smoke_config
    from repro.train import SimulatedPreemption, TrainLoopConfig, train

    cfg = smoke_config("internlm2-1.8b")
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4, seed=3)
    ocfg = AdamWConfig(peak_lr=1e-3, warmup_steps=2, total_steps=20)
    r1 = train(cfg, dcfg, ocfg, TrainLoopConfig(
        steps=8, checkpoint_every=3, checkpoint_dir=str(tmp_path / "a"), log_every=100))
    with pytest.raises(SimulatedPreemption):
        train(cfg, dcfg, ocfg, TrainLoopConfig(
            steps=8, checkpoint_every=3, checkpoint_dir=str(tmp_path / "b"),
            log_every=100, fail_at_step=5))
    r2 = train(cfg, dcfg, ocfg, TrainLoopConfig(
        steps=8, checkpoint_every=3, checkpoint_dir=str(tmp_path / "b"), log_every=100))
    for a, b in zip(jax.tree.leaves(r1["params"]), jax.tree.leaves(r2["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_elastic_restore_resharded(tmp_path):
    """Save -> restore with device_put onto a (degenerate) new sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint import restore_resharded

    m = CheckpointManager(str(tmp_path))
    tree = {"w": np.arange(16, dtype=np.float32).reshape(4, 4)}
    m.save(1, tree)
    got, _, _ = m.restore(tree)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    sh = {"w": NamedSharding(mesh, P())}
    placed = restore_resharded(got, sh)
    np.testing.assert_array_equal(np.asarray(placed["w"]), tree["w"])
