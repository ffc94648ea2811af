"""Reduce a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. Each chip is a plane named
``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event per executed
HLO op, named by its HLO text (``%fusion.146 = f32[...] fusion(...)``).
Control-flow ops (a ``while`` that runs a scan) enclose the ops of their
body, so busy time is the union of the innermost ops' intervals. Host
spans (``jax.profiler.TraceAnnotation``) sit on the ``/host:CPU`` plane
on the same clock; the window is the span named ``WINDOW``.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW = "chipbench.window"
TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def line_events(plane, keep_line) -> List[Event]:
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for line in plane.lines if keep_line(line.name)
            for ev in line.events]


def innermost(events: Iterable[Event]) -> List[Event]:
    """Drop every event that encloses another: what is left ran on the
    device itself rather than as the frame of a loop or call."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(evs)
    stack: List[int] = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][2] and (
                s, e) != (evs[stack[-1]][1], evs[stack[-1]][2]):
            parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(evs, parent) if not p]


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union_ns(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float, str]]:
    """Idle intervals of [lo, hi] outside the events, each with the name
    of the op before it."""
    out, last_end, last_name = [], lo, "window start"
    for n, s, e in sorted(events, key=lambda x: x[1]):
        if s > last_end:
            out.append((last_end, s, last_name))
        if e >= last_end:
            last_end, last_name = e, n
    if hi > last_end:
        out.append((last_end, hi, last_name))
    return out


def short(name: str) -> str:
    """``%fusion.146 = f32[..] fusion(..)`` -> ``fusion.146``."""
    return name.split(" = ", 1)[0].lstrip("%")


class Reduction:
    """The traced window of one run, per chip.

    ``chips[i]`` holds chip i's innermost op events clipped to the
    window; ``host`` the host spans inside it."""

    def __init__(self, chips: List[List[Event]], host: List[Event],
                 lo: float, hi: float):
        self.chips, self.host, self.lo, self.hi = chips, host, lo, hi

    @classmethod
    def from_profile(cls, pd, plane_re=TPU_PLANE,
                     ops_line=lambda name: name == OPS_LINE
                     ) -> "Reduction":
        """``plane_re`` picks the chips' planes and ``ops_line`` (a
        predicate on line names) their op lines; the tests point them at
        the host plane of a CPU trace."""
        host = [ev for p in pd.planes if p.name == HOST_PLANE
                for ev in line_events(p, lambda _: True)]
        spans = [ev for ev in host if ev[0] == WINDOW]
        if len(spans) != 1:
            raise RuntimeError(f"expected one {WINDOW!r} span, found "
                               f"{len(spans)}")
        _, lo, hi = spans[0]
        planes = sorted((p for p in pd.planes if plane_re.match(p.name)),
                        key=lambda p: p.name)
        chips = [clip(innermost(line_events(p, ops_line)), lo, hi)
                 for p in planes]
        chips = [c for c in chips if c]
        return cls(chips, clip([h for h in host if h[0] != WINDOW],
                               lo, hi), lo, hi)

    @classmethod
    def from_dir(cls, trace_dir: str, **kw) -> "Reduction":
        import jax
        return cls.from_profile(
            jax.profiler.ProfileData.from_file(find_xplane(trace_dir)),
            **kw)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_s(self) -> List[float]:
        """Per chip: seconds in which an op ran."""
        return [union_ns(c) * 1e-9 for c in self.chips]

    def idle_share(self) -> List[float]:
        return [1.0 - b / self.window_s for b in self.busy_s()]

    def op_seconds(self, pattern: str) -> List[float]:
        """Per chip: summed seconds of the ops whose HLO text matches."""
        rx = re.compile(pattern)
        return [sum(e - s for n, s, e in c if rx.search(n)) * 1e-9
                for c in self.chips]

    def top_ops(self, k: int = 10) -> List[List]:
        """The ops that took most device time, seconds averaged over chips."""
        tot: Dict[str, float] = {}
        for c in self.chips:
            for n, s, e in c:
                tot[short(n)] = tot.get(short(n), 0.0) + (e - s) * 1e-9
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / len(self.chips)] for n, v in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Chip 0's ``k`` longest idle gaps, each named by the innermost
        host span that covers its middle (``host: no span`` where none
        does) and the device op before it."""
        longest = sorted(gaps(self.chips[0], self.lo, self.hi),
                         key=lambda g: g[0] - g[1])[:k]
        out = []
        for s, e, prev in longest:
            mid = (s + e) / 2
            cover = [h for h in self.host if h[1] <= mid <= h[2]]
            host = (min(cover, key=lambda h: h[2] - h[1])[0] if cover
                    else "host: no span")
            out.append([f"{host} | after {short(prev)}", (e - s) * 1e-9])
        return out
