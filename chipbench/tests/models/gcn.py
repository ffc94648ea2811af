"""GCN with the renormalisation trick (Kipf and Welling, arXiv
1609.02907) as the program's ``gcn`` kind computes it, for the test
that a second model plugs into the harness by files alone.

Layer ``l`` maps rows of width ``d[l]`` to ``d[l+1]``: each dst row
takes the mean of its valid in-edges' source rows,
``agg = mean_{(u->v) valid} h[u]``, and computes
``0.5 * (h[v] + agg) @ w + b``, with ReLU between layers. The loss is
the mean negative log-likelihood over a batch's seed nodes.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import numpy as np
import jax
import jax.numpy as jnp

from chipbench import reference


def dims(config: Dict[str, Any]) -> List[int]:
    return ([config["feat_dim"]]
            + [config["hidden_dim"]] * (config["num_layers"] - 1)
            + [config["num_classes"]])


def gnn_config(config: Dict[str, Any]):
    from repro.models import GNNConfig

    return GNNConfig(kind="gcn", in_dim=config["feat_dim"],
                     hidden_dim=config["hidden_dim"],
                     num_classes=config["num_classes"],
                     num_layers=config["num_layers"])


def init_params(config: Dict[str, Any], seed: int) -> reference.Params:
    lo = np.uint32(seed & 0xFFFFFFFF)
    hi = np.uint32((seed >> 32) & 0xFFFFFFFF)
    return _init(tuple(dims(config)), lo, hi)


@partial(jax.jit, static_argnums=0)
def _init(dims, lo, hi):
    key = jax.random.fold_in(jax.random.key(lo), hi)
    layers = []
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        key, k = jax.random.split(key)
        s = 1.0 / np.sqrt(d_in)
        layers.append({
            "w": jax.random.uniform(k, (d_in, d_out), jnp.float32, -s, s),
            "b": jnp.zeros((d_out,), jnp.float32)})
    return {"layers": layers}


def worker_loss(params, x, edges, labels, seed_mask, dtype):
    h = x.astype(dtype)
    M = h.shape[0]
    layers = params["layers"]
    for l, (layer, (src, dst, mask)) in enumerate(zip(layers, edges)):
        w = mask.astype(dtype)
        summed = jax.ops.segment_sum(h[src] * w[:, None], dst,
                                     num_segments=M)
        count = jax.ops.segment_sum(w, dst, num_segments=M)
        agg = summed / jnp.maximum(count, 1)[:, None]
        h = (reference.dot(0.5 * (h + agg), layer["w"].astype(dtype), dtype)
             + layer["b"].astype(dtype))
        if l < len(layers) - 1:
            h = jax.nn.relu(h)
    return reference.seed_nll(h, labels, seed_mask, dtype)


loss_and_grad = reference.loss_and_grad_of(worker_loss)


def epoch_flops(config: Dict[str, Any], flat) -> float:
    """Per layer nd[l] * 2*d[l]*d[l+1] (one product) + e[l] * d[l] (the
    mean's adds) + nd[l] * d[l] (the self row's add); backward twice
    forward."""
    d = dims(config)
    flops = 0.0
    for l in range(len(d) - 1):
        nd = float(flat.num_dst[l].sum())
        edges = float(np.count_nonzero(flat.edge_mask[l]))
        flops += nd * 2 * d[l] * d[l + 1] + (edges + nd) * d[l]
    return 3.0 * flops
