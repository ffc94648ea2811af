"""GraphSAGE, GCN and GAT in pure JAX over padded MFG blocks (paper §2.3
models; GAT: Veličković et al., arXiv 1710.10903).

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

``gat`` (``_gat_forward``, DESIGN.md §3.1) runs on the jnp path only and
relies on that same layout: each dst row's softmax runs over its
``fanout`` edges, reshaped to ``(nd, fanout)``, plus its self-loop, and
each layer computes only its dst prefix.
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
    kind: str                 # "sage" | "gcn" | "gat"
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
    #: attention heads (``gat`` only): hidden layers are
    #: ``heads * hidden_dim`` wide, the last averages its heads
    heads: int = 1
    #: precision of the matrix products, by ``jax.lax.Precision``'s
    #: names ("default", "high", "highest"); None leaves the backend's
    #: default (one bfloat16 pass for float32 on a TPU)
    precision: Optional[str] = None

    def __post_init__(self):
        if self.precision not in (None, "default", "high", "highest"):
            raise ValueError(f"unknown precision {self.precision!r}")
        if self.kind == "gat":
            if self.agg_backend != "segment":
                raise ValueError(
                    f"kind 'gat' runs on agg_backend 'segment' only: the "
                    f"Pallas gather_agg kernel has no attention "
                    f"(got {self.agg_backend!r})")
            if self.fanouts is None or len(self.fanouts) < self.num_layers:
                raise ValueError(
                    "kind 'gat' needs cfg.fanouts, one per layer: its "
                    "softmax runs over each dst row's fan-out edges")
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
        elif cfg.kind == "gat":
            layer = _gat_layer_params(cfg, l, k1)
        else:
            raise ValueError(cfg.kind)
        params["layers"].append(layer)
    return params


def _gat_layer_params(cfg: GNNConfig, l: int, key: jax.Array
                      ) -> Dict[str, jax.Array]:
    """Layer ``l`` of a GAT, leaves named as PyG's ``GATConv`` plus the
    linear skip: ``lin`` (d_in, H*C), ``att_src``/``att_dst`` (H, C),
    ``bias`` (H*C, or C where the heads are averaged), ``skip_w``,
    ``skip_b``."""
    H, last = cfg.heads, l == cfg.num_layers - 1
    C = cfg.num_classes if last else cfg.hidden_dim
    d_in = cfg.in_dim if l == 0 else H * cfg.hidden_dim
    d_out = C if last else H * C
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s, a = 1.0 / np.sqrt(d_in), np.sqrt(6.0 / (H + C))
    return {
        "lin": jax.random.uniform(k1, (d_in, H * C), jnp.float32, -s, s),
        "att_src": jax.random.uniform(k2, (H, C), jnp.float32, -a, a),
        "att_dst": jax.random.uniform(k3, (H, C), jnp.float32, -a, a),
        "bias": jnp.zeros((d_out,), jnp.float32),
        "skip_w": jax.random.uniform(k4, (d_in, d_out), jnp.float32, -s, s),
        "skip_b": jnp.zeros((d_out,), jnp.float32),
    }


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


def _matmul(cfg: GNNConfig, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a @ b`` at ``cfg.precision``; its transposes in the backward
    keep that precision."""
    return jnp.matmul(a, b, precision=cfg.precision)


def forward(cfg: GNNConfig, params: Dict[str, Any],
            features: jnp.ndarray,
            edge_src: Sequence[jnp.ndarray], edge_dst: Sequence[jnp.ndarray],
            edge_mask: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """-> logits for the whole padded node array; seeds are the prefix."""
    if cfg.kind == "gat":
        return _gat_forward(cfg, params, features, edge_src, edge_mask)
    h = features
    m = features.shape[0]
    for l, layer in enumerate(params["layers"]):
        agg = _aggregate(cfg, l, h, edge_src[l], edge_dst[l],
                         edge_mask[l], m)
        if cfg.kind == "sage":
            h_new = (_matmul(cfg, h, layer["w_self"])
                     + _matmul(cfg, agg, layer["w_neigh"]) + layer["b"])
        else:  # gcn: mean over {self} U neighbors (renormalisation trick)
            h_new = _matmul(cfg, 0.5 * (h + agg), layer["w"]) + layer["b"]
        if l < cfg.num_layers - 1:
            h_new = jax.nn.relu(h_new)
        h = h_new
    return h


#: GAT's attention LeakyReLU slope (the paper's and PyG's)
GAT_SLOPE = 0.2


def _gat_layer(cfg: GNNConfig, l: int, layer: Dict[str, jax.Array],
               h: jnp.ndarray, edge_src: jnp.ndarray,
               edge_mask: jnp.ndarray) -> jnp.ndarray:
    """One ``GATConv`` plus skip over the rows ``h`` of layer ``l``'s
    sources -> its ``nd = E / fanout`` dst rows (the dst prefix). Edge
    ``k`` belongs to dst row ``k // fanout`` (the sampler's dst-major,
    fan-out-regular layout; the padded tail is masked), so the softmax
    runs over a ``(nd, fanout)`` reshape plus each row's self-loop,
    which keeps every row's softmax non-empty."""
    fo, E = cfg.fanouts[l], edge_src.shape[0]
    if E % fo:
        raise ValueError(f"gat layer {l}: {E} padded edges is no multiple "
                         f"of its fan-out {fo}")
    nd, (H, C) = E // fo, layer["att_src"].shape
    z = _matmul(cfg, h, layer["lin"]).reshape(h.shape[0], H, C)
    src, mask = edge_src.reshape(nd, fo), edge_mask.reshape(nd, fo)
    with jax.named_scope("aggregate"):
        with jax.named_scope("attention"):
            a_src = jnp.sum(z * layer["att_src"], axis=-1)        # (M, H)
            a_dst = jnp.sum(z[:nd] * layer["att_dst"], axis=-1)   # (nd, H)
            e = jax.nn.leaky_relu(a_src[src] + a_dst[:, None], GAT_SLOPE)
            e = jnp.where(mask[..., None], e, -jnp.inf)
            e_self = jax.nn.leaky_relu(a_src[:nd] + a_dst, GAT_SLOPE)
            top = jax.lax.stop_gradient(
                jnp.maximum(jnp.max(e, axis=1), e_self))
            p = jnp.exp(e - top[:, None])                  # masked: 0
            p_self = jnp.exp(e_self - top)
            denom = jnp.sum(p, axis=1) + p_self
        out = (jnp.sum(p[..., None] * z[src], axis=1)
               + p_self[..., None] * z[:nd]) / denom[..., None]
    last = l == cfg.num_layers - 1
    out = out.mean(axis=1) if last else out.reshape(nd, H * C)
    out = (out + layer["bias"] + _matmul(cfg, h[:nd], layer["skip_w"])
           + layer["skip_b"])
    return out if last else jax.nn.elu(out)


def _gat_forward(cfg: GNNConfig, params: Dict[str, Any],
                 features: jnp.ndarray, edge_src: Sequence[jnp.ndarray],
                 edge_mask: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """GAT logits over the padded node array: each layer computes only
    its dst prefix, and the last layer's rows are padded with zeros to
    the ``m`` input rows (no seed lies past the dst prefix)."""
    h, m = features, features.shape[0]
    for l, layer in enumerate(params["layers"]):
        h = _gat_layer(cfg, l, layer, h, edge_src[l], edge_mask[l])
    return jnp.pad(h, ((0, m - h.shape[0]), (0, 0)))


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
