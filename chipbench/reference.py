"""The model-independent parts of the plain training reference: float32
``jax.numpy``, no kernels, nothing of the program.

A model's own equations live in ``chipbench/models/<model>.py``; its
``loss_and_grad`` is ``loss_and_grad_of`` over its worker loss. Input
rows come straight from the whole feature table by global node id (no
partition, cache or pull), and the loss is the mean negative
log-likelihood over a batch's seed nodes (``seed_nll``). Data
parallelism over ``P`` workers is the mean of the workers' losses and
gradients (``chipbench.check``), and the optimizer is AdamW with
decoupled weight decay.

``dtype=float32`` runs every matrix product at ``precision="highest"``;
``dtype=bfloat16`` is the control, the same arithmetic in the next
precision below the one the configuration states (see ``check.py``).
Shapes are padded to fixed bounds with masks, so one compiled program
serves every seed of a cell.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

Params = Dict[str, List[Dict[str, jax.Array]]]


def dot(a, b, dtype):
    """``a @ b``, at ``precision="highest"`` in float32."""
    if dtype == jnp.float32:
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.dot(a, b)


def seed_nll(h, labels, seed_mask, dtype):
    """Mean NLL of the output rows ``h`` over the seeds: rows ``[0, B)``
    hold the logits of the ``B`` (masked) seeds."""
    logits = h[:labels.shape[0]]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    sm = seed_mask.astype(dtype)
    return jnp.sum(nll * sm) / jnp.maximum(jnp.sum(sm), 1)


def loss_and_grad_of(worker_loss: Callable) -> Callable:
    """-> a model's jitted ``loss_and_grad(params, table, rows, edges,
    labels, seed_mask, *, dtype=float32)``: (loss, grads) of one
    worker-step, in float32, where ``worker_loss(params, x, edges,
    labels, seed_mask, dtype)`` is the model's loss over input rows
    ``x``. ``rows`` are the global ids of the input rows (-1 padded),
    read from ``table``."""

    @partial(jax.jit, static_argnames=("dtype",))
    def loss_and_grad(params, table, rows, edges, labels, seed_mask, *,
                      dtype=jnp.float32):
        x = jnp.where((rows >= 0)[:, None], table[jnp.maximum(rows, 0)], 0)

        def f(p):
            return worker_loss(p, x, edges, labels, seed_mask, dtype)

        if dtype == jnp.float32:
            with jax.default_matmul_precision("highest"):
                loss, g = jax.value_and_grad(f)(params)
        else:
            cast = jax.tree.map(lambda a: a.astype(dtype), params)
            loss, g = jax.value_and_grad(f)(cast)
        return (loss.astype(jnp.float32),
                jax.tree.map(lambda a: a.astype(jnp.float32), g))

    return loss_and_grad


def adamw_init(params: Params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"step": jnp.zeros((), jnp.int32), "mu": zeros,
            "nu": jax.tree.map(jnp.zeros_like, params)}


@partial(jax.jit, static_argnames=("hp",))
def adamw_steps(params, state, grads, zero_steps, *, hp):
    """One AdamW update with ``grads``, then ``zero_steps`` updates with
    zero gradients (the fully masked steps that pad an epoch).
    ``hp`` = (lr, b1, b2, eps, weight_decay)."""
    lr, b1, b2, eps, wd = hp

    def update(carry, g):
        p, s = carry
        t = s["step"] + 1
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, s["mu"], g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, s["nu"], g)
        c1 = 1 - b1 ** t.astype(jnp.float32)
        c2 = 1 - b2 ** t.astype(jnp.float32)
        p = jax.tree.map(
            lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * w), p, mu, nu)
        return p, {"step": t, "mu": mu, "nu": nu}

    params, state = update((params, state), grads)
    zero = jax.tree.map(jnp.zeros_like, grads)
    return jax.lax.fori_loop(0, zero_steps,
                             lambda _, c: update(c, zero), (params, state))
