"""GAT, the graph attention network (Veličković et al., ICLR 2018,
arXiv 1710.10903), as the OGB ogbn-products leaderboard entry "GAT
(NeighborSampling)" runs it (PyTorch Geometric's
``examples/ogbn_products_gat.py``): the plain reference, its FLOP count
and the program's configuration.

Layer ``l`` has ``H`` heads of width ``C`` (``hidden_dim``; the number
of classes on the last layer) and reads rows of width ``d[l]``
(``feat_dim``, then ``H * hidden_dim``). With ``z = h @ lin`` split into
heads ``z[u] = (z[u, 1], ..., z[u, H])``::

    a_src[u, k] = z[u, k] . att_src[k]     a_dst[v, k] = z[v, k] . att_dst[k]
    e[u->v, k]  = leaky_relu(a_src[u, k] + a_dst[v, k], 0.2)
    alpha[u->v, k] = softmax of e[., k] over v's valid in-edges and one
                     self-loop v->v
    out[v, k]   = sum_u alpha[u->v, k] * z[u, k]
    h'[v] = concat_k out[v, k] + bias + h[v] @ skip_w + skip_b, then ELU

where the last layer takes the mean over its heads for the concatenation
and has no ELU. ``lin`` has no bias and serves both ends of an edge, as
PyG's ``GATConv`` with one ``in_channels``; the skip is the example's
``Lin`` on the dst rows. The loss is the mean negative log-likelihood
over a batch's seed nodes. Here the softmax runs over the edge list with
segment operations (max, exp, sum) over each dst row's edges and its
self-loop, on every row of the padded buffer.

Departures from the published training, each stated in the
configuration's ``assumed`` where it is a setting:

* dropout 0 (published 0.5 after each ELU), since the check is
  deterministic;
* neighbours are sampled with replacement by the system's sampler (PyG's
  ``NeighborSampler`` samples without);
* AdamW with weight decay 0, which is Adam.

The harness finds this module by the configuration's ``model``
(``harness.load_model`` states what a model module gives). Only
``gnn_config`` reads the program, for its configuration type.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from chipbench import reference

#: the attention LeakyReLU's negative slope (the paper's, PyG's default)
SLOPE = 0.2


def layers(config: Dict[str, Any]) -> List[Tuple[int, int, int, bool]]:
    """Per layer (d_in, heads, head width, concatenated)."""
    if "heads" not in config:
        raise ValueError(
            f"model 'gat': no head count ('heads') in configuration "
            f"{config.get('name')!r}; the GAT of chipbench/models/gat.py "
            f"is heads x hidden_dim wide")
    H, L = config["heads"], config["num_layers"]
    hid = config["hidden_dim"]
    return [(config["feat_dim"] if l == 0 else H * hid, H,
             config["num_classes"] if l == L - 1 else hid, l < L - 1)
            for l in range(L)]


def gnn_config(config: Dict[str, Any]):
    """The program's ``GNNConfig`` of this configuration, its matrix
    products at the precision the configuration states (``precision``
    ``matmul``; absent or "default", the backend's default)."""
    from repro.models import GNNConfig

    layers(config)
    matmul = config.get("precision", {}).get("matmul", "default")
    return GNNConfig(kind="gat", in_dim=config["feat_dim"],
                     hidden_dim=config["hidden_dim"],
                     num_classes=config["num_classes"],
                     num_layers=config["num_layers"],
                     fanouts=tuple(config["fanouts"]),
                     heads=config["heads"],
                     precision=None if matmul == "default" else matmul)


def init_params(config: Dict[str, Any], seed: int) -> reference.Params:
    """Weights on the device in one jitted call from ``seed`` (any
    non-negative integer), with the program's leaf names: ``lin`` and
    ``skip_w`` uniform in +-1/sqrt(d_in), ``att_src``/``att_dst``
    Glorot-uniform over (H, C), zero biases."""
    lo = np.uint32(seed & 0xFFFFFFFF)
    hi = np.uint32((seed >> 32) & 0xFFFFFFFF)
    return _init(tuple(layers(config)), lo, hi)


@partial(jax.jit, static_argnums=0)
def _init(shapes, lo, hi):
    key = jax.random.fold_in(jax.random.key(lo), hi)
    out = []
    for d_in, H, C, concat in shapes:
        key, k1, k2, k3, k4 = jax.random.split(key, 5)
        d_out = H * C if concat else C
        s, a = 1.0 / np.sqrt(d_in), np.sqrt(6.0 / (H + C))
        u = jax.random.uniform
        out.append({
            "lin": u(k1, (d_in, H * C), jnp.float32, -s, s),
            "att_src": u(k2, (H, C), jnp.float32, -a, a),
            "att_dst": u(k3, (H, C), jnp.float32, -a, a),
            "bias": jnp.zeros((d_out,), jnp.float32),
            "skip_w": u(k4, (d_in, d_out), jnp.float32, -s, s),
            "skip_b": jnp.zeros((d_out,), jnp.float32)})
    return {"layers": out}


def worker_loss(params: reference.Params, x, edges, labels, seed_mask,
                dtype):
    """One worker's mean NLL over its seeds. ``x`` (M, d) input rows;
    ``edges`` per layer ``(src, dst, mask)`` indices into the layer's
    rows, padded edges masked; seeds are rows ``[0, B)`` of the output.
    Every one of the ``M`` rows gets its self-loop, added as a term of
    its own (``e_self``, ``p_self``) beside the edge list."""
    dot = reference.dot
    h = x.astype(dtype)
    M = h.shape[0]
    L = len(params["layers"])
    for l, (layer, (src, dst, mask)) in enumerate(zip(params["layers"],
                                                      edges)):
        H, C = layer["att_src"].shape
        z = dot(h, layer["lin"].astype(dtype), dtype).reshape(M, H, C)
        a_src = jnp.sum(z * layer["att_src"].astype(dtype), axis=-1)
        a_dst = jnp.sum(z * layer["att_dst"].astype(dtype), axis=-1)
        e = jax.nn.leaky_relu(a_src[src] + a_dst[dst], SLOPE)
        e = jnp.where(mask[:, None], e, -jnp.inf)
        e_self = jax.nn.leaky_relu(a_src + a_dst, SLOPE)
        top = jax.lax.stop_gradient(jnp.maximum(
            jax.ops.segment_max(e, dst, num_segments=M), e_self))
        p = jnp.where(mask[:, None], jnp.exp(e - top[dst]), 0)
        p_self = jnp.exp(e_self - top)
        den = jax.ops.segment_sum(p, dst, num_segments=M) + p_self
        out = (jax.ops.segment_sum(p[..., None] * z[src], dst,
                                   num_segments=M)
               + p_self[..., None] * z) / den[..., None]
        out = (out.reshape(M, H * C) if l < L - 1
               else jnp.mean(out, axis=1))
        h = (out + layer["bias"].astype(dtype)
             + dot(h, layer["skip_w"].astype(dtype), dtype)
             + layer["skip_b"].astype(dtype))
        if l < L - 1:
            h = jax.nn.elu(h)
    return reference.seed_nll(h, labels, seed_mask, dtype)


loss_and_grad = reference.loss_and_grad_of(worker_loss)


def epoch_flops(config: Dict[str, Any], flat) -> float:
    """Forward-plus-backward FLOPs of one worker-epoch (a ``FlatEpoch``),
    from the schedule and not from how the program computes them. Layer
    ``l`` reads ``r[l]`` rows (the valid input rows for layer 0, the
    previous layer's dst rows after that) and writes ``nd[l]`` dst rows
    over ``e[l]`` valid edges; with ``W = H * C`` its forward is
    ``r[l] * 2*d[l]*W`` (the projection) ``+ (r[l] + nd[l]) * 2*W`` (the
    two score products) ``+ (e[l] + nd[l]) * (2*H + 2*W)`` (per edge and
    self-loop: the score add, the exp and the weighted sum)
    ``+ nd[l] * 2*d[l]*d_out`` (the skip); backward is twice forward."""
    flops = 0.0
    rows = float(flat.m_counts.sum())
    for l, (d_in, H, C, concat) in enumerate(layers(config)):
        W, d_out = H * C, (H * C if concat else C)
        nd = float(flat.num_dst[l].astype(np.float64).sum())
        edges = float(np.count_nonzero(flat.edge_mask[l]))
        flops += (rows * 2 * d_in * W + (rows + nd) * 2 * W
                  + (edges + nd) * (2 * H + 2 * W) + nd * 2 * d_in * d_out)
        rows = nd
    return 3.0 * flops
