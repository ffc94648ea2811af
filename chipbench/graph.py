"""The benchmark's own graph generator: the yardstick's data.

A copy of the program's power-law generator (``repro.graph.generate``,
``DatasetSpec`` and ``make_powerlaw_graph``) with its keyed Philox
stream (``repro.graph.sampler.rng_from``), kept here so that a change to
the program cannot change the graphs the benchmark trains on. A
configuration's graph is one fixed dataset, made from the ``graph_seed``
in its file, as ogbn-products is one fixed graph.

Generation model: nodes fall into clusters; each node draws a
heavy-tailed in-degree; in-neighbours come with probability ``p_intra``
from the node's own cluster, else from the whole graph, in both cases
weighted by a Zipf popularity over nodes. Labels follow the cluster and
features are a class centre plus noise.
"""
from __future__ import annotations

import dataclasses
import hashlib
import struct
from typing import Dict

import numpy as np


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    name: str
    num_nodes: int
    avg_degree: float
    feat_dim: int
    num_classes: int
    num_clusters: int
    zipf_a: float            # popularity exponent (p ~ rank^-a)
    p_intra: float           # probability an edge stays inside the cluster
    train_frac: float


def derive_seed(s0: int, *fields: int) -> int:
    """H(s0, fields...) -> uint64, H = BLAKE2b-8."""
    h = hashlib.blake2b(digest_size=8)
    h.update(struct.pack("<q", s0))
    for f in fields:
        h.update(struct.pack("<q", int(f)))
    return struct.unpack("<Q", h.digest())[0]


def rng_from(s0: int, *fields: int) -> np.random.Generator:
    return np.random.default_rng(np.random.Philox(derive_seed(s0, *fields)))


def _zipf_weights(n: int, a: float, rng: np.random.Generator) -> np.ndarray:
    """Popularity ~ rank^-a, randomly permuted over node ids."""
    w = np.arange(1, n + 1, dtype=np.float64) ** (-a)
    rng.shuffle(w)
    return w / w.sum()


def make_graph(spec: DatasetSpec, seed: int) -> Dict[str, np.ndarray]:
    """-> the graph as arrays: in-CSR ``indptr``/``indices`` (messages
    flow from ``indices[indptr[v]:indptr[v+1]]`` into ``v``),
    ``features`` (n, d) float32, ``labels`` (n,) int32 and
    ``train_mask`` (n,) bool."""
    rng = rng_from(seed)
    n = spec.num_nodes
    clusters = rng.integers(0, spec.num_clusters, size=n).astype(np.int32)
    popularity = _zipf_weights(n, spec.zipf_a, rng)
    deg = np.maximum(
        1, rng.lognormal(mean=np.log(spec.avg_degree) - 0.5, sigma=1.0,
                         size=n)).astype(np.int64)
    deg = np.minimum(deg, n - 1)

    dst = np.repeat(np.arange(n, dtype=np.int64), deg)
    total = int(dst.shape[0])
    intra = rng.random(total) < spec.p_intra
    src = np.empty(total, dtype=np.int64)
    n_inter = int((~intra).sum())
    src[~intra] = rng.choice(n, size=n_inter, p=popularity)
    dst_cluster = clusters[dst]
    for c in range(spec.num_clusters):
        members = np.flatnonzero(clusters == c)
        if members.size == 0:
            continue
        sel = np.flatnonzero(intra & (dst_cluster == c))
        if sel.size == 0:
            continue
        w = popularity[members]
        src[sel] = members[rng.choice(members.size, size=sel.size,
                                      p=w / w.sum())]
    self_loop = src == dst
    src[self_loop] = (dst[self_loop] + 1 + rng.integers(
        0, n - 2, size=int(self_loop.sum()))) % n

    labels = (clusters % spec.num_classes).astype(np.int32)
    centers = rng.normal(0.0, 1.0, size=(spec.num_classes, spec.feat_dim))
    features = (centers[labels] +
                rng.normal(0.0, 2.0, size=(n, spec.feat_dim))
                ).astype(np.float32)
    train_mask = rng.random(n) < spec.train_frac

    order = np.argsort(dst, kind="stable")
    counts = np.bincount(dst[order], minlength=n)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return {"indptr": indptr, "indices": src[order].astype(np.int32),
            "features": features, "labels": labels,
            "train_mask": train_mask}
