"""Plain GraphSAGE training reference: float32 ``jax.numpy``, no kernels.

It follows the published layer equations and nothing of the program:
input rows come straight from the whole feature table by global node id
(no partition, cache or pull), each layer takes the mean of its valid
in-edges' source rows and computes ``h @ w_self + agg @ w_neigh + b``,
with ReLU between layers, and the loss is the mean negative
log-likelihood over a batch's seed nodes. Data parallelism over ``P``
workers is the mean of the workers' losses and gradients, and the
optimizer is AdamW with decoupled weight decay.

``dtype=float32`` runs every matrix product at ``precision="highest"``;
``dtype=bfloat16`` is the control, the same arithmetic in the next
precision below the one the configuration states (see ``check.py``).
Shapes are padded to fixed bounds with masks, so one compiled program
serves every seed of a cell.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence

import numpy as np
import jax
import jax.numpy as jnp

Params = Dict[str, List[Dict[str, jax.Array]]]

#: the model this reference implements (a configuration's ``model``)
MODEL = "sage"
LEAVES = ("w_self", "w_neigh", "b")


def init_params(dims: Sequence[int], seed: int) -> Params:
    """GraphSAGE weights on the device in one jitted call from ``seed``
    (any non-negative integer): uniform in +-1/sqrt(d_in), zero bias."""
    lo = np.uint32(seed & 0xFFFFFFFF)
    hi = np.uint32((seed >> 32) & 0xFFFFFFFF)
    return _init(tuple(dims), lo, hi)


@partial(jax.jit, static_argnums=0)
def _init(dims, lo, hi):
    key = jax.random.fold_in(jax.random.key(lo), hi)
    layers = []
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        key, k1, k2 = jax.random.split(key, 3)
        s = 1.0 / np.sqrt(d_in)
        layers.append({
            "w_self": jax.random.uniform(k1, (d_in, d_out), jnp.float32,
                                         -s, s),
            "w_neigh": jax.random.uniform(k2, (d_in, d_out), jnp.float32,
                                          -s, s),
            "b": jnp.zeros((d_out,), jnp.float32)})
    return {"layers": layers}


def _dot(a, b, dtype):
    if dtype == jnp.float32:
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.dot(a, b)


def worker_loss(params: Params, x, edges, labels, seed_mask, dtype):
    """One worker's mean NLL over its seeds. ``x`` (M, d) input rows;
    ``edges`` per layer ``(src, dst, mask)`` indices into the layer's
    rows, padded edges masked; seeds are rows ``[0, B)`` of the output."""
    h = x.astype(dtype)
    M = h.shape[0]
    layers = params["layers"]
    for l, (layer, (src, dst, mask)) in enumerate(zip(layers, edges)):
        w = mask.astype(dtype)
        summed = jax.ops.segment_sum(h[src] * w[:, None], dst,
                                     num_segments=M)
        count = jax.ops.segment_sum(w, dst, num_segments=M)
        agg = summed / jnp.maximum(count, 1)[:, None]
        h = (_dot(h, layer["w_self"].astype(dtype), dtype)
             + _dot(agg, layer["w_neigh"].astype(dtype), dtype)
             + layer["b"].astype(dtype))
        if l < len(layers) - 1:
            h = jax.nn.relu(h)
    logits = h[:labels.shape[0]]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    sm = seed_mask.astype(dtype)
    return jnp.sum(nll * sm) / jnp.maximum(jnp.sum(sm), 1)


@partial(jax.jit, static_argnames=("dtype",))
def loss_and_grad(params, table, rows, edges, labels, seed_mask, *,
                  dtype=jnp.float32):
    """-> (loss, grads) of one worker-step, in float32. ``rows`` are the
    global ids of the input rows (-1 padded), read from ``table``."""
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
