"""ZeRO-1 optimizer-state sharding rules."""

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs import get_config
from repro.launch.sharding import opt_shardings, params_shardings
from repro.models import param_shapes
from repro.optim import init as opt_init

MESH = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def test_zero1_shards_mv_over_data():
    cfg = get_config("internlm2-1.8b", zero1=True)
    ps = param_shapes(cfg)
    os_shapes = jax.eval_shape(opt_init, ps)
    sh = opt_shardings(cfg, MESH, os_shapes, ps)
    p_sh = params_shardings(cfg, MESH, ps)
    # every m/v leaf with a free divisible axis gains a "data" placement the
    # param sharding does not have
    n_data = sum("data" in str(s.spec) for s in jax.tree.leaves(sh.m))
    n_data_params = sum("data" in str(s.spec) for s in jax.tree.leaves(p_sh))
    assert n_data > 0
    assert n_data_params == 0  # params keep pure-TP sharding
    # step stays replicated
    assert sh.step.spec == jax.sharding.PartitionSpec()


def test_zero1_off_mirrors_params():
    cfg = get_config("internlm2-1.8b")
    ps = param_shapes(cfg)
    os_shapes = jax.eval_shape(opt_init, ps)
    sh = opt_shardings(cfg, MESH, os_shapes, ps)
    p_sh = params_shardings(cfg, MESH, ps)
    for a, b in zip(jax.tree.leaves(sh.m), jax.tree.leaves(p_sh)):
        assert a.spec == b.spec
