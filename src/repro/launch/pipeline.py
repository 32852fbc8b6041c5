"""GPipe-style pipeline parallelism over a "pipe" mesh axis.

Completes the parallelism menu (DP/TP/EP/SP/FSDP/ZeRO-1 + PP).  For the
assigned model sizes TP x DP always fits (DESIGN.md §5), so PP ships as a
first-class *option* rather than a default: stages hold contiguous layer
blocks, microbatches stream through ``lax.ppermute`` inside ``shard_map``,
and jax AD differentiates through the permutes (reverse schedule) for
training.

Schedule: plain GPipe fill-drain — T = n_micro + stages - 1 ticks; at tick t
stage s processes microbatch (t - s).  Bubble fraction = (S-1)/(T), the
standard GPipe trade-off; activations for AD are kept per tick (GPipe
re-materialisation would wrap ``stage_fn`` in jax.checkpoint, composable via
cfg.remat).

Numerical equivalence with the unpipelined stack is tested on a 4-device
mesh in tests/test_pipeline.py.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


Carry = jax.Array


def pipeline_apply(
    stage_fn: Callable[[dict, jax.Array], jax.Array],
    stage_params: dict,  # leaves stacked (n_stages, ...) — one slice/stage
    x_micro: jax.Array,  # (n_micro, mb, ...) microbatched input
    mesh: Mesh,
    axis: str = "pipe",
) -> jax.Array:
    """Run ``stage_fn`` as a pipeline over ``mesh[axis]``.

    Returns the stage-(S-1) outputs re-assembled as (n_micro, mb, ...).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def per_device(params_local, x_local):
        # params_local: this stage's slice (leading stage axis of size 1)
        params_me = jax.tree.map(lambda p: p[0], params_local)
        # x_local: full microbatch stream only meaningful on stage 0
        # (shard_map replicates it; non-zero stages ignore their copy)
        sid = lax.axis_index(axis)
        zero = jnp.zeros_like(x_local[0])
        fwd = [(i, i + 1) for i in range(n_stages - 1)]

        carry = zero
        outs = []
        for t in range(ticks):
            inject = x_local[t] if t < n_micro else zero
            h_in = jnp.where(sid == 0, inject, carry)
            h_out = stage_fn(params_me, h_in)
            # keep the last-stage output for microbatch (t - (S-1))
            if t >= n_stages - 1:
                outs.append(h_out)
            carry = lax.ppermute(h_out, axis, fwd)
        # (n_micro, mb, ...) valid on the LAST stage; broadcast via ppermute
        # ring so every device returns the same tensor (replicated out-spec)
        result = jnp.stack(outs)
        last = n_stages - 1
        # bring last stage's result to all: sum of masked psum
        mine = jnp.where(sid == last, result, jnp.zeros_like(result))
        return lax.psum(mine, axis)

    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=(P(axis), P()), out_specs=P()
    )
    return fn(stage_params, x_micro)


def stack_stages(layer_params: dict, n_stages: int) -> dict:
    """Reshape (L, ...) layer-stacked params into (n_stages, L/n_stages, ...)."""
    def r(x):
        l = x.shape[0]
        if l % n_stages:
            raise ValueError(f"{l} layers not divisible by {n_stages} stages")
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])

    return jax.tree.map(r, layer_params)


def make_pipe_mesh(n_stages: int) -> Mesh:
    import numpy as np

    return Mesh(np.array(jax.devices()[:n_stages]), ("pipe",))
