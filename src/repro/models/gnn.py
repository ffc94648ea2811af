"""GraphSAGE + GCN in pure JAX over padded MFG blocks (paper §2.3 models).

The forward consumes the static-shape ``CollatedBatch`` layout: a padded
input-node feature matrix ``h`` of shape (m_max, d) whose *dst prefix*
property (dst nodes of every layer are a prefix of its src nodes, and the
final seeds are ``h[:batch_size]``) lets all layers update the same
buffer.

Aggregation dispatches per ``GNNConfig.agg_backend``: the default
``"segment"`` is masked ``segment_sum`` over the padded edge lists (the
oracle and CPU path); ``"pallas"`` / ``"pallas_interpret"`` run the fused
``kernels/gather_agg`` Pallas kernel, which exploits the deterministic
sampler's dst-major fan-out-regular edge layout (every dst owns exactly
``fanout`` contiguous edges, so the padded tail starts on a row boundary
and aggregates to zero) -- ``cfg.fanouts`` must then carry the per-layer
fan-outs. The kernel path has a custom VJP, so ``loss_fn`` grads work on
every backend.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.schedule import CollatedBatch
from repro.kernels.gather_agg.ops import gather_agg


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    kind: str                 # "sage" | "gcn"
    in_dim: int
    hidden_dim: int
    num_classes: int
    num_layers: int
    dropout: float = 0.0      # (dry-run/CPU benches run deterministic)
    #: per-layer sampler fan-outs (input->output); required by the
    #: pallas aggregation backends (dst-major regular layout contract)
    fanouts: Optional[Tuple[int, ...]] = None
    #: "segment" (jnp segment_sum oracle) | "pallas" (fused gather_agg
    #: kernel) | "pallas_interpret" (kernel body interpreted on CPU)
    agg_backend: str = "segment"

    def __post_init__(self):
        if self.agg_backend not in ("segment", "pallas",
                                    "pallas_interpret"):
            raise ValueError(f"unknown agg_backend {self.agg_backend!r}")
        if self.agg_backend != "segment":
            if self.fanouts is None:
                raise ValueError(
                    "pallas aggregation needs cfg.fanouts (the dst-major "
                    "fan-out-regular layout contract)")
            if len(self.fanouts) < self.num_layers:
                raise ValueError(
                    f"cfg.fanouts has {len(self.fanouts)} entries for "
                    f"{self.num_layers} layers")


def init_params(cfg: GNNConfig, key: jax.Array) -> Dict[str, Any]:
    dims = ([cfg.in_dim] + [cfg.hidden_dim] * (cfg.num_layers - 1)
            + [cfg.num_classes])
    params: Dict[str, Any] = {"layers": []}
    for l in range(cfg.num_layers):
        key, k1, k2 = jax.random.split(key, 3)
        d_in, d_out = dims[l], dims[l + 1]
        scale = 1.0 / np.sqrt(d_in)
        if cfg.kind == "sage":
            layer = {
                "w_self": jax.random.uniform(k1, (d_in, d_out), jnp.float32,
                                             -scale, scale),
                "w_neigh": jax.random.uniform(k2, (d_in, d_out), jnp.float32,
                                              -scale, scale),
                "b": jnp.zeros((d_out,), jnp.float32),
            }
        elif cfg.kind == "gcn":
            layer = {
                "w": jax.random.uniform(k1, (d_in, d_out), jnp.float32,
                                        -scale, scale),
                "b": jnp.zeros((d_out,), jnp.float32),
            }
        else:
            raise ValueError(cfg.kind)
        params["layers"].append(layer)
    return params


def aggregate_mean(h: jnp.ndarray, edge_src: jnp.ndarray,
                   edge_dst: jnp.ndarray, edge_mask: jnp.ndarray,
                   num_segments: int) -> jnp.ndarray:
    """Masked mean of src features into dst slots (the paper's AGG).
    The jnp oracle; ``_aggregate`` may dispatch to the fused Pallas
    kernel instead."""
    msg = h[edge_src] * edge_mask[:, None].astype(h.dtype)
    summed = jax.ops.segment_sum(msg, edge_dst, num_segments=num_segments)
    cnt = jax.ops.segment_sum(edge_mask.astype(h.dtype), edge_dst,
                              num_segments=num_segments)
    return summed / jnp.maximum(cnt, 1.0)[:, None]


def _aggregate(cfg: GNNConfig, layer: int, h: jnp.ndarray,
               edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
               edge_mask: jnp.ndarray, m: int) -> jnp.ndarray:
    """Backend switch for the AGG: fused ``gather_agg`` when the config
    opts in AND the padded edge list honours the fan-out-regular
    contract (edge count divisible by the layer fan-out; the sampler's
    dst-major layout with replacement guarantees it), else the
    ``segment_sum`` oracle. Kernel output covers the dst prefix rows
    only -- the tail up to ``m`` is zero on both paths (padded dst rows
    are fully masked). Its ops carry the ``aggregate`` scope."""
    fo = cfg.fanouts[layer] if cfg.fanouts else 0
    E = edge_src.shape[0]
    with jax.named_scope("aggregate"):
        if cfg.agg_backend != "segment" and fo > 0 and E % fo == 0:
            nd = E // fo
            agg = gather_agg(h, edge_src, edge_mask, nd=nd, fanout=fo,
                             use_kernel=True,
                             interpret=cfg.agg_backend == "pallas_interpret")
            if nd < m:
                agg = jnp.concatenate(
                    [agg, jnp.zeros((m - nd, h.shape[1]), agg.dtype)])
            return agg[:m]
        return aggregate_mean(h, edge_src, edge_dst, edge_mask, m)


def forward(cfg: GNNConfig, params: Dict[str, Any],
            features: jnp.ndarray,
            edge_src: Sequence[jnp.ndarray], edge_dst: Sequence[jnp.ndarray],
            edge_mask: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """-> logits for the whole padded node array; seeds are the prefix."""
    h = features
    m = features.shape[0]
    for l, layer in enumerate(params["layers"]):
        agg = _aggregate(cfg, l, h, edge_src[l], edge_dst[l],
                         edge_mask[l], m)
        if cfg.kind == "sage":
            h_new = h @ layer["w_self"] + agg @ layer["w_neigh"] + layer["b"]
        else:  # gcn: mean over {self} U neighbors (renormalisation trick)
            h_new = 0.5 * (h + agg) @ layer["w"] + layer["b"]
        if l < cfg.num_layers - 1:
            h_new = jax.nn.relu(h_new)
        h = h_new
    return h


def loss_fn(cfg: GNNConfig, params, features, edge_src, edge_dst, edge_mask,
            labels, seed_mask):
    logits = forward(cfg, params, features, edge_src, edge_dst, edge_mask)
    B = labels.shape[0]
    lg = logits[:B]
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                               axis=-1)[:, 0]
    w = seed_mask.astype(jnp.float32)
    loss = jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
    acc = jnp.sum((jnp.argmax(lg, -1) == labels) * w) / jnp.maximum(
        jnp.sum(w), 1.0)
    return loss, acc


def make_train_step(cfg: GNNConfig, optimizer):
    """-> jit'd (params, opt_state, batch_dict) -> (params, opt_state, aux)."""

    @jax.jit
    def step(params, opt_state, batch):
        def lf(p):
            return loss_fn(cfg, p, batch["features"], batch["edge_src"],
                           batch["edge_dst"], batch["edge_mask"],
                           batch["labels"], batch["seed_mask"])
        (loss, acc), grads = jax.value_and_grad(lf, has_aux=True)(params)
        params2, opt_state2 = optimizer.update(grads, opt_state, params)
        return params2, opt_state2, {"loss": loss, "acc": acc}

    return step


def batch_to_device(cb: CollatedBatch, features: np.ndarray) -> Dict[str, Any]:
    return {
        "features": jnp.asarray(features),
        "edge_src": [jnp.asarray(e) for e in cb.edge_src],
        "edge_dst": [jnp.asarray(e) for e in cb.edge_dst],
        "edge_mask": [jnp.asarray(e) for e in cb.edge_mask],
        "labels": jnp.asarray(cb.labels),
        "seed_mask": jnp.asarray(cb.seed_mask),
    }
