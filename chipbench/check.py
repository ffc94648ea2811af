"""How ``correct`` is decided: the timed path against the plain reference.

The check reads the program's own training, at the cell's sizes,
through the runner and compiled epoch program the window drives. Set-up
trains two check epochs from the seed before the window: epoch A holds
each worker's batch 0 and epoch B its batches 1 and 2, both padded with
fully masked steps to the cell's steps per epoch, as the runner pads any
short epoch. A masked step has a zero gradient, so AdamW's first moment
after epoch A is the first gradient times a known factor, and the
reference replays the masked steps' updates exactly.

Five numbers are compared, each against a limit in the cell's file:

``loss.1``, ``loss.2``, ``loss.3``
            each real step's loss, as its relative gap. Step 1 runs before
            any update, so its gap is the forward pass's alone; AdamW's
            first steps move every weight by about the learning rate in
            the sign of its gradient, so rounding that flips the sign of
            a tiny gradient shows in steps 2 and 3;
``grad``    the first gradient as the optimizer got it (pmean'd over
            workers), as the worst leaf's gap of norms over the larger of
            that leaf's reference norm and the median leaf's;
``update``  the parameters' change after epoch B, by the same measure,
            over the leaves whose reference gradient is at least a
            thousandth of the median leaf's (a leaf below that moves
            under Adam by rounding alone).

A sixth, ``blocks``, holds the sampled blocks themselves to the graph,
since the reference is fed the program's own rows and edges: the count
of faults ``block_faults`` finds in every batch of every scheduled
epoch, partial last batches with them. It is exact, with the limit 0.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from chipbench import reference

#: real batches trained before the window: one in epoch A, two in B
CHECK_BATCHES = ((0, 1), (1, 3))
NUMBERS = ("loss.1", "loss.2", "loss.3", "grad", "update", "blocks")
#: leaves whose reference gradient norm is under this share of the median
#: leaf's are left out of ``update``
QUIET_LEAF = 1e-3


def leaf_norms(tree) -> Dict[str, float]:
    """{"layer<l>.<leaf>": L2 norm} of a parameter-shaped tree, over each
    layer's own leaves."""
    return {f"layer{l}.{k}": float(np.linalg.norm(
        np.asarray(layer[k], np.float64)))
        for l, layer in enumerate(tree["layers"]) for k in sorted(layer)}


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep: Optional[Sequence[str]] = None) -> float:
    med = float(np.median(list(ref.values())))
    keys = list(ref) if keep is None else keep
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def tree_sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """-> {number: value}; ``prog``/``ref`` hold ``losses`` (3,),
    ``grad1`` and ``delta`` trees."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    g_ref = leaf_norms(ref["grad1"])
    med = float(np.median(list(g_ref.values())))
    moving = [k for k, v in g_ref.items() if v >= QUIET_LEAF * med]
    out = {f"loss.{j + 1}": float(v)
           for j, v in enumerate(np.abs(lp - lr) / np.abs(lr))}
    out.update({
        "grad": worst_leaf_gap(leaf_norms(prog["grad1"]), g_ref),
        "update": worst_leaf_gap(leaf_norms(prog["delta"]),
                                 leaf_norms(ref["delta"]), moving),
    })
    return {k: (v if np.isfinite(v) else float("inf"))
            for k, v in out.items()}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(v <= limits[k] for k, v in numbers.items())


def grad_from_first_moment(mu, b1: float, steps: int):
    """The first gradient from AdamW's first moment after one real step
    and ``steps - 1`` zero-gradient steps: mu = (1-b1) b1^(steps-1) g,
    with b1 and 1-b1 rounded to float32 as the optimizer uses them."""
    f = (float(np.float32(1 - b1))
         * float(np.float32(b1)) ** (steps - 1))
    return jax.tree.map(lambda m: np.asarray(m, np.float64) / f, mu)


# -- the sampled blocks against the graph ---------------------------------

def block_faults(arrays: Dict[str, np.ndarray], epochs: Sequence[Sequence],
                 fanouts: Sequence[int]) -> int:
    """Faults in the blocks the runner trains on, read against the
    generator's graph ``arrays`` (``chipbench.graph.make_graph``).
    ``epochs[e][w]`` is worker ``w``'s ``FlatEpoch`` of epoch ``e``.
    Sound blocks: an epoch's seeds over all workers are the train nodes,
    each once; a batch's rows are distinct and its output rows are its
    seeds; each layer's dst rows are a prefix of its src rows, and each
    dst row has ``fanout`` sampled in-edges, each an edge of the graph
    into it, or masked self-loops where it has no in-edge. -> the count
    of seeds, batches and edges at fault: 0 when sound."""
    indptr, indices = arrays["indptr"], arrays["indices"]
    n = indptr.shape[0] - 1
    deg = np.diff(indptr)
    edges = np.sort(np.repeat(np.arange(n, dtype=np.int64), deg) * n
                    + indices)
    train = np.flatnonzero(arrays["train_mask"])
    bad = 0
    for flats in epochs:
        seeds = np.sort(np.concatenate([f.seeds for f in flats]))
        bad += (abs(seeds.shape[0] - train.shape[0])
                + int(np.count_nonzero(np.isin(seeds, train, invert=True)))
                + int(np.count_nonzero(seeds[1:] == seeds[:-1])))
        for f in flats:
            bad += _flat_faults(f, edges, deg, fanouts)
    return bad


def _flat_faults(f, edges: np.ndarray, deg: np.ndarray,
                 fanouts: Sequence[int]) -> int:
    n = deg.shape[0]
    nb = f.seed_starts.shape[0] - 1
    m, B = np.diff(f.input_starts), np.diff(f.seed_starts)
    head = f.input_starts[:-1]
    rows = np.sort(np.repeat(np.arange(nb), m) * n + f.input_nodes)
    bad = int(np.count_nonzero(rows[1:] == rows[:-1]))
    first = np.repeat(head, B) + (np.arange(B.sum())
                                  - np.repeat(f.seed_starts[:-1], B))
    bad += int(np.count_nonzero(f.input_nodes[first] != f.seeds))
    bad += int(np.count_nonzero(f.num_dst[len(fanouts) - 1] != B))
    num_src = m
    for l, fan in enumerate(fanouts):
        nd = f.num_dst[l]
        E = np.diff(f.edge_starts[l])
        bad += int(np.count_nonzero((nd > num_src) | (E != nd * fan)))
        eb = np.repeat(np.arange(nb), E)
        src = f.edge_src[l].astype(np.int64)
        dst = f.edge_dst[l].astype(np.int64)
        ok = (src >= 0) & (src < num_src[eb]) & (dst >= 0) & (dst < nd[eb])
        bad += int(np.count_nonzero(~ok))
        src, dst, eb = src[ok], dst[ok], eb[ok]
        mask = f.edge_mask[l][ok]
        per_dst = np.bincount(np.cumsum(nd)[eb] - nd[eb] + dst,
                              minlength=int(nd.sum()))
        bad += int(np.count_nonzero(per_dst != fan))
        gs = f.input_nodes[head[eb] + src]
        gd = f.input_nodes[head[eb] + dst]
        key = np.sort(gd[mask] * n + gs[mask])   # sorted: a fast search
        at = np.minimum(np.searchsorted(edges, key), edges.shape[0] - 1)
        bad += int(np.count_nonzero(edges[at] != key))
        bad += int(np.count_nonzero(~mask & ((deg[gd] != 0) | (gs != gd))))
        num_src = nd
    return bad


# -- the reference side ----------------------------------------------------

def _bucket(n: int, floor: int) -> int:
    """A padded size: the cell's floor, else n rounded up to 1024."""
    return floor if n <= floor else -(-n // 1024) * 1024


def pack_steps(flats: Sequence, labels: np.ndarray, batch_size: int,
               m_floor: int, e_floor: Sequence[int]) -> List[List[dict]]:
    """The check's real worker-steps as padded host arrays:
    ``steps[j][w]`` = rows (global ids, -1 padded), per-layer edges
    (src, dst, mask), labels and seed mask of worker ``w``'s ``j``-th
    real batch (batch ``j`` of its epoch-0 schedule)."""
    steps = []
    for j in range(sum(b - a for a, b in CHECK_BATCHES)):
        per_worker = []
        for flat in flats:
            b = flat.batch(j)
            M = _bucket(b.input_nodes.shape[0], m_floor)
            rows = np.full(M, -1, np.int32)
            rows[:b.input_nodes.shape[0]] = b.input_nodes
            edges = []
            for blk, ef in zip(b.blocks, e_floor):
                E = _bucket(blk.edge_src.shape[0], ef)
                src, dst = np.zeros(E, np.int32), np.zeros(E, np.int32)
                mask = np.zeros(E, bool)
                n = blk.edge_src.shape[0]
                src[:n], dst[:n], mask[:n] = (blk.edge_src, blk.edge_dst,
                                              blk.edge_mask)
                edges.append((src, dst, mask))
            lab = np.zeros(batch_size, np.int32)
            sm = np.zeros(batch_size, bool)
            lab[:b.seeds.shape[0]] = labels[b.seeds]
            sm[:b.seeds.shape[0]] = True
            per_worker.append({"rows": rows, "edges": edges,
                               "labels": lab, "seed_mask": sm})
        steps.append(per_worker)
    return steps


def reference_readings(steps: List[List[dict]], table: jax.Array,
                       params0, hp: tuple, steps_per_epoch: int,
                       loss_and_grad: Callable, dtype=jnp.float32,
                       update=reference.adamw_steps) -> dict:
    """Replay the check on the reference: real step 1, the rest of epoch
    A masked, real steps 2 and 3, the rest of epoch B masked.
    ``hp`` = (lr, b1, b2, eps, weight_decay); ``loss_and_grad`` is the
    model's (``chipbench/models/<model>.py``), ``update`` the optimizer
    (``reference.adamw_steps``'s signature)."""
    S = steps_per_epoch
    zero_after = (S - 1, 0, S - 2)
    params = params0
    state = reference.adamw_init(params0)
    losses, grad1 = [], None
    for j, per_worker in enumerate(steps):
        outs = [loss_and_grad(
            params, table, w["rows"], w["edges"], w["labels"],
            w["seed_mask"], dtype=dtype) for w in per_worker]
        loss = sum(o[0] for o in outs) / len(outs)
        grads = jax.tree.map(lambda *g: sum(g) / len(g),
                             *[o[1] for o in outs])
        if j == 0:
            grad1 = grads
        params, state = update(params, state, grads, zero_after[j], hp=hp)
        losses.append(float(loss))
    return {"losses": losses, "grad1": jax.device_get(grad1),
            "delta": tree_sub(jax.device_get(params),
                              jax.device_get(params0))}
