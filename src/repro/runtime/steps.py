"""The train step builder: the jit boundary of training.

``build_train_step`` returns a step that is jittable with donated state.  It
works both concrete (examples, tests) and abstract (``ShapeDtypeStruct``
inputs) — nothing here allocates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.transformer import Model
from repro.optim.adamw import AdamW

__all__ = ["build_train_step"]


def build_train_step(model: Model, opt: AdamW, *, accum_steps: int = 1,
                     grad_shardings=None):
    """(params, opt_state, batch, extra) -> (params, opt_state, metrics).

    ``batch`` = (tokens, labels) with shape (B, S); grad accumulation splits
    B into ``accum_steps`` microbatches scanned sequentially (overlaps the
    per-microbatch DP reduction with compute under XLA's scheduler).

    ``grad_shardings``: optional pytree of NamedSharding matching params —
    gradients are constrained to the param layout right out of backward,
    which keeps the (param-sized, f32) cotangents from materializing
    replicated (a 16x memory regression observed without it).
    """

    def loss_fn(params, tokens, labels, extra):
        return model.loss(params, tokens, labels, extra=extra)

    def constrain_grads(grads):
        if grad_shardings is None:
            return grads
        return jax.tree.map(jax.lax.with_sharding_constraint, grads,
                            grad_shardings)

    def step(params, opt_state, batch, extra=None):
        tokens, labels = batch
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(loss_fn)(
                params, tokens, labels, extra)
            grads = constrain_grads(grads)
        else:
            b = tokens.shape[0]
            mb = b // accum_steps
            tk = tokens.reshape(accum_steps, mb, -1)
            lb = labels.reshape(accum_steps, mb, -1)
            ex = (None if extra is None else jax.tree.map(
                lambda a: a.reshape(accum_steps, mb, *a.shape[1:]), extra))

            def body(carry, xs):
                acc, lsum = carry
                t, l, e = xs
                loss_i, g_i = jax.value_and_grad(loss_fn)(
                    params, t, l, e)
                g_i = constrain_grads(g_i)
                acc = jax.tree.map(jnp.add, acc, g_i)
                return (acc, lsum + loss_i), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, lsum), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)), (tk, lb, ex))
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            loss = lsum / accum_steps

        new_params, new_opt, gnorm = opt.apply(params, grads, opt_state)
        metrics = {"loss": loss.astype(jnp.float32),
                   "grad_norm": gnorm.astype(jnp.float32)}
        return new_params, new_opt, metrics

    return step
