"""Scan-pipelined RapidGNN epoch on an SPMD mesh -- flat ``("data",)``
or hierarchical ``("dcn", "data")`` (two-tier pulls, DESIGN.md §6.7).

This is Alg. 1's prefetcher/trainer overlap expressed INSIDE the compiled
step program (DESIGN.md §6.3): a ``jax.lax.scan`` over the S steps of an
epoch whose body (a) issues the all_to_all residual-miss pull for step
i+1 and (b) trains on step i's already-pulled features. Both live in one
dataflow graph with no dependency between them, so the collective hides
behind the train step's compute -- the device analogue of the host-side
``core.prefetch.Prefetcher`` thread, with the bounded queue replaced by a
1-step software pipeline carried through the scan.

Per-step feature assembly is the SINGLE-PASS fused path
(``kernels/assemble``): local-shard gather, C_s binary-search merge and
pulled-residual overlay resolved per row with one output materialization
(DESIGN.md §3), shared by both epoch programs so rapid-vs-baseline
comparisons assemble features identically. The legacy three-stage chain
(``cache_lookup`` then local overlay) survives as the ``"staged"``
backend / interpret-mode oracle.

Host-side companions (all numpy, computed offline from the deterministic
schedule): ``DeviceView`` relabels the partitioned graph into contiguous
per-worker slot ranges so ownership is ``id // n_per``; ``epoch_k_max``
computes the exact static lane bound; ``collate_device_epoch`` packs a
whole epoch into (S, P, ...) arrays in one VECTORIZED pass (single
``g2d`` gather over the schedule compiler's FlatEpoch streams, one
stamp-table membership pass per worker, batched lane packing,
threaded slice copies into the padded slabs -- DESIGN.md §6.6; the
per-(step, worker) loop survives as ``collate_device_epoch_loop``, the
parity/bench reference); ``stack_caches`` stacks the per-worker hot
sets C_s.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from jax.flatten_util import ravel_pytree

from repro.core.schedule import EpochSchedule, collate
from repro.graph.partition import PartitionedGraph
from repro.kernels.assemble.ops import assemble_features, source_views
from repro.kernels.cache_lookup.ops import to_device_ids
from repro.models.gnn import GNNConfig, loss_fn
from repro.dist.feature_a2a import (build_pull_plan, pack_pull_lanes,
                                    pack_pull_lanes_two_tier, pull_shard,
                                    pull_shard_two_tier)

#: pull-plan keys of the collated epoch dict, per topology tier layout
PULL_KEYS_FLAT = ("send_ids", "send_pos", "send_mask")
PULL_KEYS_HIER = ("intra_ids", "intra_pos", "intra_mask",
                  "inter_ids", "inter_pos", "inter_mask")

#: int64 cache padding; survives the int32 canonicalisation cast exactly
#: and matches the ``cache_lookup`` device sentinel.
CACHE_PAD = int(2 ** 31 - 1)


@dataclasses.dataclass
class DeviceCache:
    """One worker's hot set C_s in DEVICE id space, sorted for searchsorted."""
    ids: np.ndarray      # (k,) int64 device ids, sorted unique
    feats: np.ndarray    # (k, d) float32


@dataclasses.dataclass
class DeviceView:
    """Device relabeling of a PartitionedGraph.

    Partitions own arbitrary global-id sets; the device path needs
    ownership decidable by arithmetic (``owner = id // n_per``) so the
    pull can turn an id into (owner, slot) with no lookup table on
    device. ``build`` assigns worker p's nodes the dense device ids
    ``p * n_per + [0..|V_p|)`` with ``n_per = max_p |V_p|`` (tail slots
    of smaller partitions are zero rows, never referenced).
    """
    num_parts: int
    n_per: int
    table: np.ndarray      # (P, n_per, d) float32, partition-sharded rows
    offsets: np.ndarray    # (P, 1) int32   first device slot per worker
    g2d: np.ndarray        # (n,) int64     global id -> device id
    features: np.ndarray   # (n, d)         global table (host ref, not copied)

    @staticmethod
    def build(pg: PartitionedGraph) -> "DeviceView":
        g = pg.graph
        P_ = pg.num_parts
        n_per = int(max(ln.shape[0] for ln in pg.local_nodes))
        table = np.zeros((P_, n_per, g.feat_dim), np.float32)
        g2d = np.empty(g.num_nodes, np.int64)
        for p, loc in enumerate(pg.local_nodes):
            table[p, : loc.shape[0]] = g.features[loc]
            g2d[loc] = p * n_per + np.arange(loc.shape[0], dtype=np.int64)
        offsets = (np.arange(P_, dtype=np.int32) * n_per)[:, None]
        return DeviceView(num_parts=P_, n_per=n_per, table=table,
                          offsets=offsets, g2d=g2d, features=g.features)

    @property
    def owner_d(self) -> np.ndarray:
        """(P*n_per,) device-id -> owner, for build_pull_plan."""
        return np.repeat(np.arange(self.num_parts, dtype=np.int32),
                         self.n_per)

    def remap_cache(self, cache_ids_global: np.ndarray) -> DeviceCache:
        """Global hot-set ids (schedule output) -> sorted device cache."""
        dev = self.g2d[cache_ids_global]
        order = np.argsort(dev)
        return DeviceCache(
            ids=dev[order],
            feats=self.features[cache_ids_global[order]].astype(np.float32))


def _batch_miss(es_batch, cache: DeviceCache, dv: DeviceView, worker: int):
    """-> (dev_ids (m,), miss_mask (m,)) for one sampled batch."""
    dev = dv.g2d[es_batch.input_nodes]
    remote = (dev // dv.n_per) != worker
    miss = remote & ~np.isin(dev, cache.ids, assume_unique=False)
    return dev, miss


def _epoch_flat(es_list: Sequence[EpochSchedule], dv: DeviceView
                ) -> Optional[Dict[str, np.ndarray]]:
    """Splice the P workers' FlatEpoch payloads into one worker-major
    batch stream with ONE ``g2d`` gather (the vectorized staging spine,
    DESIGN.md §6.6). Since the schedule compiler already stores each
    worker-epoch flat (CSR offsets, no per-batch objects), this is P
    concatenations -- the per-(worker, batch) rec loop is gone.

    -> dict: the per-worker ``flats`` plus per-batch ``step``/``worker``
    /``m_counts``/``starts`` (element offsets) and the per-element
    ``dev`` device ids; None for an epoch with no batches at all.
    Per-element batch/column coordinates are NOT materialized here --
    ``_miss_coords`` derives them lazily for just the miss subset.
    """
    flats = [es.flat for es in es_list]
    nbs = np.fromiter((f.num_batches for f in flats), np.int64,
                      len(flats))
    n = int(nbs.sum())
    if n == 0:
        return None
    step = np.concatenate([np.arange(nb, dtype=np.int64) for nb in nbs])
    worker = np.repeat(np.arange(len(flats), dtype=np.int64), nbs)
    m_counts = np.concatenate([f.m_counts for f in flats])
    dev = dv.g2d[np.concatenate([f.input_nodes for f in flats])]
    starts = np.zeros(n + 1, np.int64)
    np.cumsum(m_counts, out=starts[1:])
    return {"flats": flats, "step": step, "worker": worker,
            "m_counts": m_counts, "dev": dev, "starts": starts}


def _miss_coords(flat: Dict[str, np.ndarray], miss: np.ndarray):
    """(batch ordinal, buffer row) of each missed element, derived from
    the element offsets -- a binary search over the (n_batches,) starts
    vector on just the miss subset instead of materializing full
    per-element repeat/arange coordinate arrays."""
    idx = np.flatnonzero(miss)
    eb = np.searchsorted(flat["starts"], idx, side="right") - 1
    return eb, idx - flat["starts"][eb]


#: device-id spaces up to this many slots use the O(1) stamp-table
#: membership test (int32 stamp array = 4 bytes/slot host scratch);
#: larger spaces fall back to per-worker binary search
STAMP_TABLE_MAX_SLOTS = 1 << 26


def _classify_misses(flat: Dict[str, np.ndarray],
                     caches: Sequence[DeviceCache], dv: DeviceView):
    """Residual-miss classification for a whole epoch in one vectorized
    pass per worker (replacing the S x P per-batch ``np.isin`` calls,
    each of which re-sorted the hot set).

    The flattened element stream is worker-major, so each worker's
    elements are one contiguous slice. Membership in that worker's hot
    set is an O(1) probe of a slot-indexed STAMP table (``stamp[id] ==
    w``; workers stamp in ascending order, so later overwrites never
    corrupt earlier queries and the table needs no clearing) -- for id
    spaces too large for the 4 B/slot scratch it degrades to one
    vectorized binary search per worker against its cache-resident
    (n_hot,) key vector. Remoteness is two compares against the
    worker's slot range, not a division.

    -> (miss mask aligned with ``flat['dev']``, owners of just the
    missed elements).
    """
    dev = flat["dev"]
    miss = np.zeros(dev.shape, bool)
    wk, mc = flat["worker"], flat["m_counts"]
    n_slots = dv.num_parts * dv.n_per
    stamp = (np.full(n_slots, -1, np.int32)
             if n_slots <= STAMP_TABLE_MAX_SLOTS else None)
    lo = 0
    for w, cache in enumerate(caches):
        span = int(mc[wk == w].sum())
        sl = slice(lo, lo + span)
        lo += span
        if span == 0:
            continue
        d = dev[sl]
        base = w * dv.n_per
        rem = (d < base) | (d >= base + dv.n_per)
        if cache.ids.shape[0] == 0 or not rem.any():
            miss[sl] = rem
            continue
        q = d[rem]
        m = rem.copy()
        if stamp is not None:
            stamp[cache.ids] = w
            m[rem] = stamp[q] != w
        else:
            pos = np.minimum(np.searchsorted(cache.ids, q),
                             cache.ids.shape[0] - 1)
            m[rem] = cache.ids[pos] != q
        miss[sl] = m
    return miss, dev[miss] // dv.n_per


def epoch_k_max(es_list: Sequence[EpochSchedule],
                caches: Sequence[DeviceCache], dv: DeviceView) -> int:
    """Exact static per-owner lane bound over all (worker, step) pairs,
    computed in one vectorized pass over the whole epoch (bincount over
    (batch, owner) group keys -- no per-batch loop).

    Pad bounds (m_max / edge maxima) are NOT recomputed here -- callers
    precompute them once via ``WorkerSchedule.pad_bounds()`` (the
    multi-epoch runner maxes this over every epoch's caches so all
    epochs share one compiled program). Workers with fewer batches
    simply contribute fewer (worker, step) pairs."""
    flat = _epoch_flat(es_list, dv)
    if flat is None:
        return 1
    miss, owner_miss = _classify_misses(flat, caches, dv)
    if owner_miss.size == 0:
        return 1
    P_ = len(es_list)
    eb, _ = _miss_coords(flat, miss)
    return max(1, int(np.bincount(eb * P_ + owner_miss).max()))


def epoch_k_max_split(es_list: Sequence[EpochSchedule],
                      caches: Sequence[DeviceCache], dv: DeviceView,
                      topo) -> tuple:
    """Exact static lane bounds for the TWO-TIER plan: ``(k_max_intra,
    k_max_inter)`` over all (worker, step) pairs of the epoch, split by
    whether the missed id's owner shares the requesting worker's host
    (same vectorized bincount pass as ``epoch_k_max``, one group key
    per tier). Both bounds floor at 1 so degenerate tiers (single-host
    epochs, all-local epochs) still compile static shapes."""
    flat = _epoch_flat(es_list, dv)
    if flat is None:
        return 1, 1
    miss, owner_miss = _classify_misses(flat, caches, dv)
    if owner_miss.size == 0:
        return 1, 1
    P_ = len(es_list)
    D = topo.devices_per_host
    eb, _ = _miss_coords(flat, miss)
    req = flat["worker"][eb]
    same = topo.same_host(owner_miss, req)
    k_i = k_x = 1
    if same.any():
        k_i = int(np.bincount(
            eb[same] * D + topo.local_of(owner_miss[same])).max())
    if (~same).any():
        k_x = int(np.bincount(eb[~same] * P_ + owner_miss[~same]).max())
    return max(1, k_i), max(1, k_x)


def _alloc_epoch(P_: int, S: int, batch_size: int, m_max: int,
                 edge_max: Sequence[int], k_max: int, topology=None,
                 k_max_inter: Optional[int] = None,
                 with_edge_dst: bool = True
                 ) -> Dict[str, np.ndarray]:
    """Empty (S, P, ...) device-layout epoch: every step fully masked.
    With a hierarchical ``topology`` the pull lanes split into the
    two-tier layout -- intra (S, P, D, k_max) + inter (S, P, P,
    k_max_inter) -- instead of the flat send_* (S, P, P, k_max).
    ``with_edge_dst=False``: ``edge_dst`` is ``None`` per layer."""
    out = {
        "input_nodes": np.full((S, P_, m_max), -1, np.int64),
        "labels": np.zeros((S, P_, batch_size), np.int32),
        "seed_mask": np.zeros((S, P_, batch_size), bool),
        "edge_src": [np.zeros((S, P_, e), np.int32) for e in edge_max],
        "edge_dst": [np.zeros((S, P_, e), np.int32) if with_edge_dst
                     else None for e in edge_max],
        "edge_mask": [np.zeros((S, P_, e), bool) for e in edge_max],
    }
    if topology is not None and topology.is_hierarchical:
        D = topology.devices_per_host
        k_x = k_max_inter if k_max_inter is not None else k_max
        out["intra_ids"] = np.zeros((S, P_, D, k_max), np.int32)
        out["intra_pos"] = np.zeros((S, P_, D, k_max), np.int32)
        out["intra_mask"] = np.zeros((S, P_, D, k_max), bool)
        out["inter_ids"] = np.zeros((S, P_, P_, k_x), np.int32)
        out["inter_pos"] = np.zeros((S, P_, P_, k_x), np.int32)
        out["inter_mask"] = np.zeros((S, P_, P_, k_x), bool)
    else:
        out["send_ids"] = np.zeros((S, P_, P_, k_max), np.int32)
        out["send_pos"] = np.zeros((S, P_, P_, k_max), np.int32)
        out["send_mask"] = np.zeros((S, P_, P_, k_max), bool)
    return out


def _check_num_steps(es_list: Sequence[EpochSchedule], S: int) -> None:
    over = [w for w, es in enumerate(es_list) if es.num_batches > S]
    if over:
        raise ValueError(
            f"workers {over} have more batches than num_steps={S}; "
            f"pass num_steps >= max worker batch count "
            f"(dropping steps would corrupt miss accounting)")


#: threads that copy a collated epoch's rows (``_submit_fills``)
FILL_THREADS = 4


def _copy_rows(slab, stream, starts, lo: int, hi: int) -> None:
    """Rows ``lo..hi-1`` of the ``(S, K)`` slab: row ``i`` takes
    ``stream[starts[i]:starts[i + 1]]`` as its prefix."""
    bounds = starts[lo:hi + 1].tolist()
    for i, (a, b) in enumerate(zip(bounds[:-1], bounds[1:]), lo):
        slab[i, :b - a] = stream[a:b]


def _submit_fills(pool: ThreadPoolExecutor, fills) -> list:
    """Submit each ``(slab, stream, starts)``'s row copies to ``pool``,
    its rows split into ``FILL_THREADS`` shares -> the futures."""
    futs = []
    for slab, stream, starts in fills:
        cuts = np.linspace(0, starts.shape[0] - 1,
                           FILL_THREADS + 1).astype(int)
        futs += [pool.submit(_copy_rows, slab, stream, starts, a, b)
                 for a, b in zip(cuts[:-1], cuts[1:]) if b > a]
    return futs


def collate_device_epoch(es_list: Sequence[EpochSchedule],
                         caches: Sequence[DeviceCache], dv: DeviceView,
                         labels: np.ndarray, batch_size: int, m_max: int,
                         edge_max: Sequence[int], k_max: int,
                         num_steps: int, topology=None,
                         k_max_inter: Optional[int] = None,
                         with_edge_dst: bool = True,
                         stats: Optional[Dict[str, int]] = None
                         ) -> Dict[str, np.ndarray]:
    """Pack an epoch into the (S, P, ...) device layout -- VECTORIZED.

    Per (step, worker): the padded collated batch (ids remapped to
    device space, -1 padded) plus the residual-miss PullPlan lanes.
    Layout matches launch/dryrun_gnn.specs exactly, batch-for-batch
    identical to ``collate_device_epoch_loop`` (the per-(step, worker)
    reference this path is parity-tested against).

    The per-element work stages in a handful of whole-epoch numpy ops
    instead of S x P small ones (DESIGN.md §6.6): one ``g2d`` gather
    over every input node, one label gather over every seed, one
    stamp-table membership pass per worker for miss classification
    (``_classify_misses``, replacing S x P ``np.isin`` re-sorts), one
    sort-based lane packing (``pack_pull_lanes``) replacing S x P
    ``build_pull_plan`` calls, and -- now that the schedule compiler
    stores each worker-epoch as a FlatEpoch -- one slice copy per
    (batch, output array) from the worker's flat stream into its
    padded slab, on ``FILL_THREADS`` threads (``_submit_fills``). This is
    what keeps the host's double-buffer staging ahead of the device.

    ``m_max``/``edge_max``/``k_max``/``num_steps`` are precomputed
    bounds -- the multi-epoch runner passes GLOBAL (all-epoch, all-
    worker) values so every epoch collates to identical shapes and one
    XLA compilation. A worker with fewer than ``num_steps`` batches
    (uneven train-node partitions, possibly zero batches) gets fully
    masked empty steps for the tail: ids -1, all masks False, so it
    still participates in every collective but trains on nothing.
    Raises when a worker has MORE batches than ``num_steps`` (silent
    truncation would corrupt the fetch accounting).

    With a hierarchical ``topology`` the pull lanes come out two-tier
    (``intra_*``/``inter_*`` via ``pack_pull_lanes_two_tier``, bounds
    ``k_max``/``k_max_inter``) instead of flat ``send_*`` -- everything
    else (batches, labels, edges) is layout-identical.

    ``with_edge_dst=False`` leaves ``edge_dst`` out (``None`` per
    layer), for a program whose aggregation reads the dst rows off the
    fan-out-regular layout (``models.gnn.scatter_free_layers``). A
    ``stats`` dict receives the epoch's ``valid_rows`` (input rows with
    a node) and ``local_rows`` (those the requesting worker's shard
    holds), counted on the flat streams.
    """
    P_ = len(es_list)
    S = num_steps
    _check_num_steps(es_list, S)
    out = _alloc_epoch(P_, S, batch_size, m_max, edge_max, k_max,
                       topology=topology, k_max_inter=k_max_inter,
                       with_edge_dst=with_edge_dst)
    if stats is not None:
        stats.update(valid_rows=0, local_rows=0)
    flat = _epoch_flat(es_list, dv)
    if flat is None:
        return out
    flats, dev = flat["flats"], flat["dev"]

    # ragged padded fills: each batch's valid prefix is one contiguous
    # slice of its worker's flat stream (CSR offsets), copied into its
    # row of the pre-padded slab; the padded tails keep _alloc_epoch's
    # fill. The copies release the GIL, so they run on a few threads,
    # a share of every array's rows each, while this thread packs the
    # pull lanes and counts the rows from the flat streams
    fills = []
    lo = 0
    for w, f in enumerate(flats):
        if f.num_batches == 0:
            continue    # fully masked worker; may carry 0 layer info
        span = int(f.input_starts[-1])
        fills.append((out["input_nodes"][:, w], dev[lo:lo + span],
                      f.input_starts))
        lo += span
        fills.append((out["labels"][:, w], labels[f.seeds],
                      f.seed_starts))
        fills.append((out["seed_mask"][:, w],
                      np.ones(f.seeds.shape[0], bool), f.seed_starts))
        for l in range(len(edge_max)):
            for key, stream in (("edge_src", f.edge_src[l]),
                                ("edge_dst", f.edge_dst[l]),
                                ("edge_mask", f.edge_mask[l])):
                if out[key][l] is not None:
                    fills.append((out[key][l][:, w], stream,
                                  f.edge_starts[l]))
    with ThreadPoolExecutor(FILL_THREADS) as pool:
        copies = _submit_fills(pool, fills)
        out.update(_pull_lanes(flat, caches, dv, S, P_, k_max, topology,
                               k_max_inter))
        if stats is not None:
            stats["valid_rows"] = int(dev.shape[0])
            stats["local_rows"] = _local_rows(flat, dv)
        for c in copies:
            c.result()
    return out


def _pull_lanes(flat, caches: Sequence[DeviceCache], dv: DeviceView,
                S: int, P_: int, k_max: int, topology,
                k_max_inter: Optional[int]) -> Dict[str, np.ndarray]:
    """The epoch's residual-miss pull lanes, (S, P, ...): one
    classification + one batched packing, flat ``send_*`` or, on a
    hierarchical ``topology``, two-tier ``intra_*``/``inter_*``."""
    dev = flat["dev"]
    row = flat["step"] * P_ + flat["worker"]    # batch -> flat (step, w)
    miss, owner_miss = _classify_misses(flat, caches, dv)
    eb, col = _miss_coords(flat, miss)
    # assume_unique: the sampler dedupes input_nodes per batch, so no
    # (group, id, pos) duplicates can exist
    if topology is not None and topology.is_hierarchical:
        D = topology.devices_per_host
        k_x = k_max_inter if k_max_inter is not None else k_max
        intra, inter = pack_pull_lanes_two_tier(
            dev[miss], col, row[eb], owner_miss, flat["worker"][eb],
            S * P_, topology, k_max, k_x, assume_unique=True)
        return {"intra_ids": intra[0].reshape(S, P_, D, k_max),
                "intra_pos": intra[1].reshape(S, P_, D, k_max),
                "intra_mask": intra[2].reshape(S, P_, D, k_max),
                "inter_ids": inter[0].reshape(S, P_, P_, k_x),
                "inter_pos": inter[1].reshape(S, P_, P_, k_x),
                "inter_mask": inter[2].reshape(S, P_, P_, k_x)}
    sids, spos, smask, _ = pack_pull_lanes(
        dev[miss], col, row[eb], owner_miss, S * P_, P_, k_max,
        assume_unique=True)
    return {"send_ids": sids.reshape(S, P_, P_, k_max),
            "send_pos": spos.reshape(S, P_, P_, k_max),
            "send_mask": smask.reshape(S, P_, P_, k_max)}


def _local_rows(flat, dv: DeviceView) -> int:
    """Input rows of the epoch that their own worker's shard holds:
    ``base <= id < base + n_per`` against the requesting worker's
    base, over the flat (unpadded) streams."""
    dev, wk, mc = flat["dev"], flat["worker"], flat["m_counts"]
    n, lo = 0, 0
    for w in range(len(flat["flats"])):
        span = int(mc[wk == w].sum())
        d = dev[lo:lo + span]
        lo += span
        base = w * dv.n_per
        n += int(np.count_nonzero((d >= base) & (d < base + dv.n_per)))
    return n


def collate_device_epoch_loop(es_list: Sequence[EpochSchedule],
                              caches: Sequence[DeviceCache],
                              dv: DeviceView, labels: np.ndarray,
                              batch_size: int, m_max: int,
                              edge_max: Sequence[int], k_max: int,
                              num_steps: int) -> Dict[str, np.ndarray]:
    """Per-(step, worker) reference collation: one ``collate`` +
    ``build_pull_plan`` call per batch. Kept as the oracle the
    vectorized ``collate_device_epoch`` is parity-tested and benchmarked
    against (``benchmarks/assemble.py``)."""
    P_ = len(es_list)
    S = num_steps
    L = len(edge_max)
    _check_num_steps(es_list, S)
    out = _alloc_epoch(P_, S, batch_size, m_max, edge_max, k_max)
    owner_d = dv.owner_d
    for w, es in enumerate(es_list):
        for i in range(len(es.batches)):
            b = es.batches[i]
            cb = collate(b, labels, batch_size, m_max, edge_max)
            dev, miss = _batch_miss(b, caches[w], dv, w)
            m = b.num_input_nodes
            out["input_nodes"][i, w, :m] = dev
            out["labels"][i, w] = cb.labels
            out["seed_mask"][i, w] = cb.seed_mask
            plan = build_pull_plan(dev[miss].astype(np.int32),
                                   np.flatnonzero(miss).astype(np.int32),
                                   owner_d, P_, k_max)
            out["send_ids"][i, w] = plan.send_ids
            out["send_pos"][i, w] = plan.send_pos
            out["send_mask"][i, w] = plan.send_mask
            for l in range(L):
                out["edge_src"][l][i, w] = cb.edge_src[l]
                out["edge_dst"][l][i, w] = cb.edge_dst[l]
                out["edge_mask"][l][i, w] = cb.edge_mask[l]
    return out


def stack_caches(caches: Sequence[DeviceCache], dv: DeviceView,
                 n_hot: int):
    """Stack per-worker hot sets into (P, n_hot) ids + (P, n_hot, d) rows.

    Ids stay sorted with CACHE_PAD tail padding (the device sentinel), so
    the binary-search ``cache_lookup`` works shard-locally unchanged.
    Raises when a cache exceeds ``n_hot``: the collation already routed
    those ids through C_s, so dropping them here would silently train on
    zero feature rows (same contract as build_pull_plan's overflow).
    """
    P_ = len(caches)
    d = dv.table.shape[-1]
    cids = np.full((P_, n_hot), CACHE_PAD, np.int64)
    cfeats = np.zeros((P_, n_hot, d), np.float32)
    for w, c in enumerate(caches):
        k = c.ids.shape[0]
        if k > n_hot:
            raise ValueError(
                f"worker {w} hot set has {k} ids > n_hot={n_hot}; "
                f"truncating would serve zero rows for ids the pull "
                f"plans treat as cache hits")
        cids[w, :k] = c.ids
        cfeats[w, :k] = c.feats
    return cids, cfeats


def prefetch_stream(send: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Roll the per-step pull plans one step forward (step i's scan body
    pulls step i+1's misses) and fully MASK the final element: the roll
    wraps step 0's plan to the last scan step, whose pull is discarded,
    so shipping its real lanes would be a wasted fetch. The masked
    element keeps the collective shape-static (the all_to_all still
    runs) but requests only zero lanes -- fetch accounting is unchanged
    because lane counts come from the un-rolled host arrays.

    send: dict of (S, ...) arrays -- the flat ``send_*`` triplet or the
    two-tier ``intra_*``/``inter_*`` sextet; keys ending in ``mask``
    are AND-masked, the rest zeroed on the dead final element.
    """
    S = next(iter(send.values())).shape[0]
    out = {}
    for key, a in send.items():
        rolled = jnp.roll(a, -1, axis=0)
        live = (jnp.arange(S) < S - 1).reshape((S,) + (1,) * (a.ndim - 1))
        out[key] = (rolled & live if key.endswith("mask")
                    else jnp.where(live, rolled, 0))
    return out


def _pull(tbl, send, base, m_max: int, hier: bool, axis, width: int):
    """One step's residual-miss pull under the ``pull`` scope: the flat
    ``all_to_all`` exchange, or the two-tier one on a hierarchical
    topology, into a buffer ``width`` lanes wide. Shared by both epoch
    programs."""
    with jax.named_scope("pull"):
        if hier:
            return pull_shard_two_tier(tbl, send, base, m_max,
                                       world_axes=axis, width=width)
        return pull_shard(tbl, send["send_ids"], send["send_pos"],
                          send["send_mask"], base, m_max, width=width)


def _sources(tbl, cfeats, backend: str):
    """The epoch's table and hot-set rows as the assembly reads them, and
    the width of the pulled buffer (``source_views``), under the
    ``assemble`` scope; built once per epoch, outside the step loop."""
    with jax.named_scope("assemble"):
        return source_views(tbl, cfeats, backend)


def _assemble(tbl, base, cids32, cfeats, ids, pulled, backend: str,
              interpret: bool, d: int):
    """One step's feature assembly (``kernels/assemble``) under the
    ``assemble`` scope, cut to the model's ``d`` features. Shared by
    both epoch programs."""
    with jax.named_scope("assemble"):
        return assemble_features(tbl, base, cids32, cfeats,
                                 to_device_ids(ids), pulled,
                                 backend=backend, interpret=interpret
                                 )[:, :d]


def _pmean_train_step(cfg: GNNConfig, opt, params, opt_state, feats, x,
                      axis="data"):
    """Shared scan-body tail for both epoch programs: batch loss/grad,
    pmean over the full worker ``axis`` (``"data"`` flat, ``("dcn",
    "data")`` hierarchical -- the same all-group AllReduce, so params
    stay replicated and curves stay bit-comparable), optimizer update.
    -> (params, opt_state, loss, acc).

    Scopes: the loss under ``forward`` (its gradient's ops then carry
    ``transpose(jvp(forward))``), the all-reduce under
    ``grad_allreduce``, the update under ``optimizer``.

    The AllReduce runs on ONE flat buffer. With per-leaf all-reduces the
    two epoch programs drifted apart by rounding on four TPU chips; with
    the flat buffer they agree bit for bit. The likely cause, not
    confirmed from the HLO, is that XLA combined the per-leaf reductions
    differently in the two programs."""

    def lf(p):
        with jax.named_scope("forward"):
            return loss_fn(cfg, p, feats, x["edge_src"], x["edge_dst"],
                           x["edge_mask"], x["labels"], x["seed_mask"])

    (loss, acc), grads = jax.value_and_grad(lf, has_aux=True)(params)
    with jax.named_scope("grad_allreduce"):
        flat, unravel = ravel_pytree((grads, loss, acc))
        grads, loss, acc = unravel(jax.lax.pmean(flat, axis))
    with jax.named_scope("optimizer"):
        p2, o2 = opt.update(grads, opt_state, params)
    return p2, o2, loss, acc


def make_pipelined_epoch(cfg: GNNConfig, opt, mesh, m_max: int,
                         assemble_backend: str = "auto",
                         assemble_interpret: bool = False,
                         topology=None):
    """-> epoch_fn(params, opt_state, table, offsets, cache_ids,
    cache_feats, batches) running S pipelined steps on the mesh.

    Per scan step (DESIGN.md §6.3): pull step i+1's residual misses
    (carried to the next iteration) while training on step i's features,
    assembled by the fused single-pass kernel (local shard > cache C_s >
    pulled residuals resolved per row, one output materialization --
    ``kernels/assemble``, backend selected by ``assemble_backend``);
    grads are pmean'd over the full worker axis so params stay
    replicated. Returns (params, opt_state, losses (S,), accs (S,)).

    A hierarchical ``topology`` switches the pull to the TWO-TIER
    exchange (``pull_shard_two_tier``: intra-host lanes over the ici
    axis, cross-host lanes over the flattened (dcn, data) pair) and the
    worker axis to ``("dcn", "data")`` -- bit-equal curves, cheaper
    same-host wires (DESIGN.md §6.7).
    """
    hier = topology is not None and topology.is_hierarchical
    ax = topology.worker_axes if topology is not None else "data"
    pull_keys = PULL_KEYS_HIER if hier else PULL_KEYS_FLAT

    def epoch_fn(params, opt_state, table, offsets, cache_ids,
                 cache_feats, batches):

        def device_epoch(params, opt_state, tbl, offs, cids, cfeats, bt):
            tbl = tbl[0]                          # (n_per, d) my shard
            base = offs.reshape(-1)[0]
            with jax.named_scope("assemble"):
                cids32 = to_device_ids(cids[0])   # (n_hot,) sorted int32
            cfe = cfeats[0]
            bt = jax.tree.map(lambda a: a[:, 0], bt)   # drop worker dim

            tsrc, csrc, width = _sources(tbl, cfe, assemble_backend)

            def pull(send):
                return _pull(tbl, send, base, m_max, hier, ax, width)

            send = {k: bt[k] for k in pull_keys}
            # prefetch stream: step i's body pulls step i+1's misses; the
            # wrapped final element is fully masked (its pull would be
            # discarded), so no real lanes ride the wasted wrap fetch
            with jax.named_scope("pull"):
                next_send = prefetch_stream(send)
                first = jax.tree.map(lambda a: a[0], send)
            xs = {
                "input_nodes": bt["input_nodes"],
                "labels": bt["labels"],
                "seed_mask": bt["seed_mask"],
                "edge_src": bt["edge_src"],
                "edge_dst": bt["edge_dst"],
                "edge_mask": bt["edge_mask"],
                "next_send": next_send,
            }
            pulled0 = pull(first)

            def step(carry, x):
                params, opt_state, pulled = carry
                nxt = pull(x["next_send"])        # overlap: no dep on train
                feats = _assemble(tsrc, base, cids32, csrc,
                                  x["input_nodes"], pulled,
                                  assemble_backend, assemble_interpret,
                                  cfg.in_dim)
                p2, o2, loss, acc = _pmean_train_step(
                    cfg, opt, params, opt_state, feats, x, axis=ax)
                return (p2, o2, nxt), (loss, acc)

            (params, opt_state, _), (losses, accs) = jax.lax.scan(
                step, (params, opt_state, pulled0), xs)
            return params, opt_state, losses, accs

        return shard_map(
            device_epoch, mesh=mesh,
            in_specs=(P(), P(), P(ax), P(ax), P(ax),
                      P(ax), P(None, ax)),
            out_specs=(P(), P(), P(), P()), check_vma=False,
        )(params, opt_state, table, offsets, cache_ids, cache_feats,
          batches)

    return epoch_fn


def make_ondemand_epoch(cfg: GNNConfig, opt, mesh, m_max: int,
                        assemble_backend: str = "auto",
                        assemble_interpret: bool = False,
                        topology=None):
    """-> epoch_fn(params, opt_state, table, offsets, batches): the
    DGL-style on-demand baseline as a NON-overlapped scan.

    Same mesh, same pull-plan wire format, same train step and the SAME
    fused assembly path as ``make_pipelined_epoch`` (cache-less:
    ``assemble_features`` with no C_s, so local shard > pulled) -- the
    rapid-vs-baseline comparison assembles features identically. But no
    software pipeline: step i's all_to_all pull feeds step i's own
    features, so the collective sits on the trainer's critical path
    every step. This is the device analogue of
    ``core.runtime.BaselineRunner``, making device rapid-vs-baseline
    step time directly measurable (DESIGN.md §6.5). Collate its batches
    with EMPTY caches so every remote id rides the pull lanes. A
    hierarchical ``topology`` switches pulls to the two-tier exchange,
    same as ``make_pipelined_epoch``.
    """
    hier = topology is not None and topology.is_hierarchical
    ax = topology.worker_axes if topology is not None else "data"
    pull_keys = PULL_KEYS_HIER if hier else PULL_KEYS_FLAT

    def epoch_fn(params, opt_state, table, offsets, batches):

        def device_epoch(params, opt_state, tbl, offs, bt):
            tbl = tbl[0]                          # (n_per, d) my shard
            base = offs.reshape(-1)[0]
            bt = jax.tree.map(lambda a: a[:, 0], bt)   # drop worker dim
            tsrc, _, width = _sources(tbl, None, assemble_backend)

            def step(carry, x):
                params, opt_state = carry
                # pull THIS step's remote rows: the train step below
                # depends on it, so nothing overlaps (on-demand fetch)
                pulled = _pull(tbl, x, base, m_max, hier, ax, width)
                feats = _assemble(tsrc, base, None, None, x["input_nodes"],
                                  pulled, assemble_backend,
                                  assemble_interpret, cfg.in_dim)
                p2, o2, loss, acc = _pmean_train_step(
                    cfg, opt, params, opt_state, feats, x, axis=ax)
                return (p2, o2), (loss, acc)

            xs = {k: bt[k] for k in
                  ("input_nodes", "labels", "seed_mask", "edge_src",
                   "edge_dst", "edge_mask") + pull_keys}
            (params, opt_state), (losses, accs) = jax.lax.scan(
                step, (params, opt_state), xs)
            return params, opt_state, losses, accs

        return shard_map(
            device_epoch, mesh=mesh,
            in_specs=(P(), P(), P(ax), P(ax), P(None, ax)),
            out_specs=(P(), P(), P(), P()), check_vma=False,
        )(params, opt_state, table, offsets, batches)

    return epoch_fn


def empty_caches(num_parts: int, feat_dim: int) -> List[DeviceCache]:
    """Per-worker EMPTY hot sets: the no-cache (baseline) collation key.
    ``_batch_miss`` then routes every remote id through the pull lanes."""
    return [DeviceCache(ids=np.zeros(0, np.int64),
                        feats=np.zeros((0, feat_dim), np.float32))
            for _ in range(num_parts)]
