"""Train-step construction: loss, gradients, optimizer update, microbatching.

``make_train_step`` returns a pure function
``(params, opt_state, batch) -> (params, opt_state, metrics)`` suitable for
``jax.jit`` with donated arguments, on any mesh (shardings supplied by
``repro.launch``) or none (CPU smoke tests).

Gradient accumulation (``microbatches > 1``) lax.scans over batch slices,
trading activation memory for steps.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro import _obs_hooks
from repro.models import encdec_forward, forward, lm_loss
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, OptState, update

Params = Any
Batch = Dict[str, jax.Array]


def make_loss_fn(cfg: ModelConfig) -> Callable[[Params, Batch], jax.Array]:
    fam = cfg.family

    def loss_fn(params: Params, batch: Batch) -> jax.Array:
        if fam in ("encdec", "audio"):
            h, aux = encdec_forward(params, cfg, batch["frames"], batch["tokens"])
        elif fam == "vlm":
            h, aux = forward(
                params, cfg, tokens=batch["tokens"], inputs_embeds=batch["patches"]
            )
        else:
            h, aux = forward(params, cfg, tokens=batch["tokens"])
        return lm_loss(params, cfg, h, batch["labels"]) + aux

    return loss_fn


def make_train_step(
    cfg: ModelConfig, opt_cfg: AdamWConfig, microbatches: int = 1
) -> Callable[[Params, OptState, Batch], tuple[Params, OptState, dict]]:
    loss_fn = make_loss_fn(cfg)
    grad_fn = jax.value_and_grad(loss_fn)

    def train_step(params: Params, opt_state: OptState, batch: Batch):
        if microbatches <= 1:
            loss, grads = grad_fn(params, batch)
        else:
            # STRIDED microbatch split (row i -> microbatch i mod mb): the
            # minor-factor reshape keeps every microbatch shard-local on the
            # data axis, so scan's xs-slicing needs no resharding (contiguous
            # splits crossed shard boundaries: measured 2x flops + permutes)
            def reshape_mb(x):
                r = x.reshape(x.shape[0] // microbatches, microbatches,
                              *x.shape[1:])
                return jnp.moveaxis(r, 1, 0)

            def body(carry, mbatch):
                acc, loss_acc = carry
                l, g = grad_fn(params, mbatch)
                acc = jax.tree.map(jnp.add, acc, g)
                return (acc, loss_acc + l), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (grads, loss), _ = lax.scan(
                body,
                (zeros, jnp.float32(0.0)),
                jax.tree.map(reshape_mb, batch),
                unroll=not cfg.scan_layers,  # unrolled for XLA cost analysis
            )
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
        # traffic tap: the gradient tree is exactly the ring all-reduce
        # payload.  Under jit grads are tracers and the tap drops the
        # firing whole (jaxpr-identical); eager callers record real bytes.
        _obs_hooks.tap("train.grads", grads=grads)
        new_params, new_opt, metrics = update(opt_cfg, grads, opt_state, params)
        return new_params, new_opt, {"loss": loss, **metrics}

    return train_step
