"""GraphSAGE, GCN and GAT in pure JAX over padded MFG blocks (paper §2.3
models; GAT: Veličković et al., arXiv 1710.10903).

The forward consumes the static-shape ``CollatedBatch`` layout: a padded
input-node feature matrix ``h`` of shape (m_max, d) whose *dst prefix*
property (dst nodes of every layer are a prefix of its src nodes, and the
final seeds are ``h[:batch_size]``) lets each layer compute only the
leading rows the next one reads.

The sampler's edges are dst-major and fan-out-regular: every dst row owns
exactly ``fanout`` contiguous edges, zero-degree and padded edges are
masked, so edge ``e`` belongs to dst row ``e // fanout``. When
``cfg.fanouts`` carries the per-layer fan-outs, SAGE/GCN's mean is a
gather, a masked sum over an ``(E // fanout, fanout)`` reshape and a
divide (``fanout_mean``), over the dst prefix only: no
scatter in the forward, and each layer updates its ``E // fanout`` dst
rows. Without fan-outs it is a masked ``segment_sum`` into every row, the
oracle for any edge list. ``agg_backend`` ``"pallas"`` /
``"pallas_interpret"`` run the fused ``kernels/gather_agg`` Pallas kernel
on the same layout instead (custom VJP, so ``loss_fn`` grads work on
every backend).

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
    #: per-layer sampler fan-outs (input->output): the dst-major regular
    #: layout contract, which the jnp mean's reshape path, GAT and the
    #: pallas aggregation backends rely on
    fanouts: Optional[Tuple[int, ...]] = None
    #: "segment" (jnp: the reshape mean where ``fanouts`` is known, else
    #: the segment_sum oracle) | "pallas" (fused gather_agg kernel) |
    #: "pallas_interpret" (kernel body interpreted on CPU)
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
    """Masked mean of src features into dst slots (the paper's AGG) by
    ``segment_sum``, for any edge list: the oracle, and ``_aggregate``'s
    path where the fan-outs are unknown."""
    msg = h[edge_src] * edge_mask[:, None].astype(h.dtype)
    summed = jax.ops.segment_sum(msg, edge_dst, num_segments=num_segments)
    cnt = jax.ops.segment_sum(edge_mask.astype(h.dtype), edge_dst,
                              num_segments=num_segments)
    return summed / jnp.maximum(cnt, 1.0)[:, None]


def fanout_mean(h: jnp.ndarray, edge_src: jnp.ndarray,
                edge_mask: jnp.ndarray, nd: int, fanout: int) -> jnp.ndarray:
    """Masked mean over the dst-major, fan-out-regular layout: edge ``e``
    of the ``nd * fanout`` belongs to dst row ``e // fanout``. h (m, d)
    -> (nd, d), a gather and a sum over the fan-out axis, no scatter.

    The rows are gathered fan-out-major, into ``(fanout, n8, d)`` with
    the dst rows padded (masked) to ``n8``, a multiple of 8: the flat
    gather's output then takes that shape without a copy on a TPU's
    (8, 128) tiles, and the sum over the fan-out adds whole ``(n8, d)``
    slabs. Gathered dst-major, as ``(nd, fanout, d)``, a fan-out that is
    no multiple of 8 (25) makes the compiler copy every gathered row
    into a padded layout before the sum."""
    n8 = -(-nd // 8) * 8
    pad = ((0, n8 - nd), (0, 0))
    src = jnp.pad(edge_src.reshape(nd, fanout), pad).T
    msk = jnp.pad(edge_mask.reshape(nd, fanout), pad).T.astype(h.dtype)
    s = (h[src] * msk[..., None]).sum(axis=0)     # (n8, d)
    cnt = jnp.maximum(msk.sum(axis=0), 1.0)
    return (s / cnt[:, None])[:nd]


def _reshape_rows(cfg: GNNConfig, layer: int, E: int, rows: int) -> int:
    """Dst rows of layer ``layer``'s mean on the reshape path:
    ``E // fanout`` of its ``E`` padded edges, at most its ``rows``
    source rows (the dst rows are a prefix of them). Valid edges are
    ``num_dst * fanout <= E``, so the edges past ``nd * fanout`` are
    padding. 0 where the path does not apply: fan-out unknown, a Pallas
    backend, or fewer edges than one fan-out."""
    if cfg.agg_backend != "segment" or not cfg.fanouts:
        return 0
    return min(E // cfg.fanouts[layer], rows)


def scatter_free_layers(cfg: GNNConfig, edge_max: Sequence[int]) -> int:
    """Layers whose forward aggregation is a gather and a reduce over
    the fan-out axis, with no scatter, at padded edge counts
    ``edge_max``: every GAT layer, and each SAGE/GCN layer on the
    reshape path."""
    if cfg.kind == "gat":
        return cfg.num_layers
    return sum(_reshape_rows(cfg, l, E, E) > 0
               for l, E in enumerate(edge_max[:cfg.num_layers]))


def _aggregate(cfg: GNNConfig, layer: int, h: jnp.ndarray,
               edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
               edge_mask: jnp.ndarray) -> jnp.ndarray:
    """The AGG of layer ``layer`` -> the mean for the layer's leading
    rows: its ``nd`` dst rows on the reshape path (``_reshape_rows``),
    all ``m`` rows of ``h`` on the others (``segment_sum``, or the fused
    ``gather_agg`` where the config opts in AND the edge count divides
    by the fan-out), whose rows past the dst prefix are zero (padded dst
    rows are fully masked). Its ops carry the ``aggregate`` scope."""
    m, E = h.shape[0], edge_src.shape[0]
    fo = cfg.fanouts[layer] if cfg.fanouts else 0
    nd = _reshape_rows(cfg, layer, E, m)
    with jax.named_scope("aggregate"):
        if nd:
            return fanout_mean(h, edge_src[:nd * fo], edge_mask[:nd * fo],
                               nd, fo)
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
    """-> logits for the whole padded node array; seeds are the prefix.
    Each layer updates the rows its aggregation covers (the dst prefix
    on the reshape path), and the last layer's rows are padded with
    zeros to the ``m`` input rows. A layer on the reshape path, and
    every GAT layer, reads no ``edge_dst``: it may be ``None`` there."""
    if cfg.kind == "gat":
        return _gat_forward(cfg, params, features, edge_src, edge_mask)
    h = features
    m = features.shape[0]
    for l, layer in enumerate(params["layers"]):
        agg = _aggregate(cfg, l, h, edge_src[l], edge_dst[l],
                         edge_mask[l])
        nd = agg.shape[0]
        hd = h if nd == h.shape[0] else h[:nd]
        if cfg.kind == "sage":
            h_new = (_matmul(cfg, hd, layer["w_self"])
                     + _matmul(cfg, agg, layer["w_neigh"]) + layer["b"])
        else:  # gcn: mean over {self} U neighbors (renormalisation trick)
            h_new = _matmul(cfg, 0.5 * (hd + agg), layer["w"]) + layer["b"]
        if l < cfg.num_layers - 1:
            h_new = jax.nn.relu(h_new)
        h = h_new
    if h.shape[0] < m:
        h = jnp.pad(h, ((0, m - h.shape[0]), (0, 0)))
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
