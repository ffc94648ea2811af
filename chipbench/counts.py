"""The work a step needs, counted from the schedule and not from how the
program does it: padded rows, rows past a layer's dst prefix and
recomputed work never count, so a later kernel or layout is measured
against the same work.

Per worker-batch, with ``nd[l]`` the dst rows of layer ``l``, ``e[l]``
its valid edges and ``d[l] -> d[l+1]`` its widths:

* FLOPs forward = sum_l nd[l] * 2*d[l]*d[l+1] * 2 (the self and neighbour
  products) + e[l] * d[l] (the mean aggregation's adds); forward plus
  backward is three times forward.
* assemble bytes = m * (2 * d[0] * 4 + 4): every valid input row read
  once and written once in float32, plus its int32 query id.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np


def epoch_counts(flat, dims: Sequence[int]) -> Dict[str, float]:
    """-> {"flops", "assemble_bytes", "seeds", "rows"} summed over the
    batches of one worker-epoch (a ``FlatEpoch``)."""
    flops = 0.0
    for l in range(len(dims) - 1):
        nd = flat.num_dst[l].astype(np.float64)
        edges = float(np.count_nonzero(flat.edge_mask[l]))
        flops += float(nd.sum()) * 2 * dims[l] * dims[l + 1] * 2
        flops += edges * dims[l]
    rows = float(flat.m_counts.sum())
    return {"flops": 3.0 * flops,
            "assemble_bytes": rows * (2 * dims[0] * 4 + 4),
            "seeds": float(flat.seeds.shape[0]),
            "rows": rows}


def add(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {k: a.get(k, 0.0) + v for k, v in b.items()}
