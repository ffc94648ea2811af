"""The work a step needs, counted from the schedule and not from how the
program does it: padded rows, rows past a layer's dst prefix and
recomputed work never count, so a later kernel or layout is measured
against the same work.

Per worker-epoch:

* FLOPs: forward plus backward, by the model's own count
  (``epoch_flops`` of ``chipbench/models/<model>.py``).
* assemble bytes = m * (2 * d[0] * 4 + 4): every valid input row read
  once and written once in float32, plus its int32 query id.
"""
from __future__ import annotations

from typing import Callable, Dict


def epoch_counts(flat, feat_dim: int,
                 flops: Callable[[object], float]) -> Dict[str, float]:
    """-> {"flops", "assemble_bytes", "seeds", "rows"} summed over the
    batches of one worker-epoch (a ``FlatEpoch``); ``flops(flat)`` is
    the model's count for it."""
    rows = float(flat.m_counts.sum())
    return {"flops": flops(flat),
            "assemble_bytes": rows * (2 * feat_dim * 4 + 4),
            "seeds": float(flat.seeds.shape[0]),
            "rows": rows}


def add(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {k: a.get(k, 0.0) + v for k, v in b.items()}
