"""GraphSAGE (Hamilton et al., arXiv 1706.02216) with mean aggregation:
the plain reference, its FLOP count and the program's configuration.

Layer ``l`` maps rows of width ``d[l]`` to ``d[l+1]`` (``dims``): each
dst row takes the mean of its valid in-edges' source rows,
``agg = mean_{(u->v) valid} h[u]``, and computes
``h[v] @ w_self + agg @ w_neigh + b``, with ReLU between layers. The
loss is the mean negative log-likelihood over a batch's seed nodes.

The harness finds this module by the configuration's ``model``
(``harness.load_model`` states what a model module gives). Only
``gnn_config`` reads the program, for its configuration type.
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
    """The program's ``GNNConfig`` of this configuration."""
    from repro.models import GNNConfig

    return GNNConfig(kind="sage", in_dim=config["feat_dim"],
                     hidden_dim=config["hidden_dim"],
                     num_classes=config["num_classes"],
                     num_layers=config["num_layers"])


def init_params(config: Dict[str, Any], seed: int) -> reference.Params:
    """Weights on the device in one jitted call from ``seed`` (any
    non-negative integer): uniform in +-1/sqrt(d_in), zero bias, with
    the program's leaf names."""
    lo = np.uint32(seed & 0xFFFFFFFF)
    hi = np.uint32((seed >> 32) & 0xFFFFFFFF)
    return _init(tuple(dims(config)), lo, hi)


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


def worker_loss(params: reference.Params, x, edges, labels, seed_mask,
                dtype):
    """One worker's mean NLL over its seeds. ``x`` (M, d) input rows;
    ``edges`` per layer ``(src, dst, mask)`` indices into the layer's
    rows, padded edges masked; seeds are rows ``[0, B)`` of the output."""
    dot = reference.dot
    h = x.astype(dtype)
    M = h.shape[0]
    layers = params["layers"]
    for l, (layer, (src, dst, mask)) in enumerate(zip(layers, edges)):
        w = mask.astype(dtype)
        summed = jax.ops.segment_sum(h[src] * w[:, None], dst,
                                     num_segments=M)
        count = jax.ops.segment_sum(w, dst, num_segments=M)
        agg = summed / jnp.maximum(count, 1)[:, None]
        h = (dot(h, layer["w_self"].astype(dtype), dtype)
             + dot(agg, layer["w_neigh"].astype(dtype), dtype)
             + layer["b"].astype(dtype))
        if l < len(layers) - 1:
            h = jax.nn.relu(h)
    return reference.seed_nll(h, labels, seed_mask, dtype)


loss_and_grad = reference.loss_and_grad_of(worker_loss)


def epoch_flops(config: Dict[str, Any], flat) -> float:
    """Forward-plus-backward FLOPs of one worker-epoch (a ``FlatEpoch``),
    from the schedule and not from how the program computes them: with
    ``nd[l]`` the dst rows of layer ``l`` and ``e[l]`` its valid edges,
    forward is sum_l nd[l] * 2*d[l]*d[l+1] * 2 (the self and neighbour
    products) + e[l] * d[l] (the mean's adds); backward is twice
    forward."""
    d = dims(config)
    flops = 0.0
    for l in range(len(d) - 1):
        nd = flat.num_dst[l].astype(np.float64)
        edges = float(np.count_nonzero(flat.edge_mask[l]))
        flops += float(nd.sum()) * 2 * d[l] * d[l + 1] * 2
        flops += edges * d[l]
    return 3.0 * flops
