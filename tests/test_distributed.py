"""Multi-device distribution tests (subprocess: needs its own XLA device
flag, which must not leak into this process)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """One real train step on a 2x4 mesh == the same step unsharded."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import smoke_config
        from repro.models import init_params
        from repro.optim import AdamWConfig, init as opt_init
        from repro.train import make_train_step
        from repro.launch.sharding import params_shardings, opt_shardings, batch_shardings

        cfg = smoke_config("internlm2-1.8b", d_model=64, n_heads=4, n_kv_heads=4)
        params = init_params(cfg, jax.random.key(0))
        opt = opt_init(params)
        batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.key(2), (8, 32), 0, cfg.vocab)}
        step = make_train_step(cfg, AdamWConfig(total_steps=10))
        # single device
        p1, o1, m1 = jax.jit(step)(params, opt, batch)

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        p_sh = params_shardings(cfg, mesh, jax.eval_shape(lambda: params))
        o_sh = opt_shardings(cfg, mesh, jax.eval_shape(lambda: opt), jax.eval_shape(lambda: params))
        b_sh = batch_shardings(cfg, mesh, {k: jax.eval_shape(lambda v=v: v) for k, v in batch.items()})
        with mesh:
            p2, o2, m2 = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh))(params, opt, batch)
        err = abs(float(m1["loss"]) - float(m2["loss"]))
        assert err < 5e-3, err  # bf16 forward, shard-order-dependent sums
        d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        assert d < 5e-3, d
        print("SHARDED_OK", err, d)
    """)
    assert "SHARDED_OK" in out


@pytest.mark.slow
def test_explicit_compressed_dp_matches_psum():
    """shard_map int8-EF compressed all-reduce across 8 real devices sums
    gradients equivalently to plain psum (within quantization error)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.optim import CompressionConfig, compressed_psum

        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(8, 512)).astype(np.float32))
        err = jnp.zeros((8, 512), jnp.float32)
        cfg = CompressionConfig(mode="int8_ef", block=64)
        def f(g, e):
            out, ne = compressed_psum(g[0], e[0], cfg, ("data",))
            return out[None], ne[None]
        with mesh:
            out, _ = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=(P("data"), P("data")),
                out_specs=(P("data"), P("data"))))(g, err)
        want = np.asarray(g).sum(0)
        got = np.asarray(out)[0]
        rel = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert rel < 0.02, rel
        print("COMPRESS_OK", rel)
    """)
    assert "COMPRESS_OK" in out
