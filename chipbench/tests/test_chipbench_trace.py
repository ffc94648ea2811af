"""The trace reduction, on synthetic events and on a small trace that
the JAX profiler recorded on the CPU (``data/cpu_window.xplane.pb``:
three calls of a jitted function inside the ``chipbench.window`` span).
On the CPU the ops run on the host plane's ``tf_XLA*`` thread lines."""
import os
import re

import numpy as np
import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "cpu_window.xplane.pb")


def cpu_ops(line_name: str) -> bool:
    return line_name.startswith("tf_XLA")


@pytest.fixture(scope="module")
def profile():
    import jax
    return jax.profiler.ProfileData.from_file(DATA)


@pytest.fixture(scope="module")
def red(profile):
    return trace.Reduction.from_profile(
        profile, plane_re=re.compile(r"^/host:CPU$"), ops_line=cpu_ops)


def raw(profile):
    """(window, op events) read without the reduction's helpers."""
    window, ops = None, []
    for p in profile.planes:
        for line in p.lines:
            for ev in line.events:
                iv = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == trace.WINDOW:
                    window = iv[1:]
                elif cpu_ops(line.name):
                    ops.append(iv)
    return window, ops


def brute_innermost(events):
    return [a for a in events
            if not any(b is not a and a[1] <= b[1] and b[2] <= a[2]
                       and (b[1], b[2]) != (a[1], a[2]) for b in events)]


def brute_busy(events, lo, hi):
    lo_i = int(lo)
    grid = np.zeros(int(hi) - lo_i + 1, bool)
    for _, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            grid[int(s) - lo_i:int(e) - lo_i] = True
    return grid.sum()


def test_innermost_drops_enclosing_events():
    evs = [("while", 0, 100), ("a", 10, 20), ("b", 30, 40),
           ("c", 90, 120), ("d", 95, 100)]
    kept = trace.innermost(evs)
    assert sorted(n for n, _, _ in kept) == ["a", "b", "d"]


def test_union_gaps_and_clip():
    evs = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40)]
    assert trace.union_ns(evs) == 30
    assert trace.gaps(evs, -5, 50) == [(-5, 0, "window start"),
                                       (20, 30, "b"), (40, 50, "c")]
    assert trace.clip(evs, 8, 35) == [("a", 8, 10), ("b", 8, 20),
                                      ("c", 30, 35)]
    assert trace.short("%fusion.146 = f32[4] fusion(x)") == "fusion.146"


def test_window_is_the_annotated_span(profile, red):
    (lo, hi), _ = raw(profile)
    assert red.window_s == pytest.approx((hi - lo) * 1e-9)
    assert len(red.chips) == 1


def test_busy_and_idle_match_a_brute_force_union(profile, red):
    (lo, hi), ops = raw(profile)
    want = brute_busy(brute_innermost(ops), lo, hi) * 1e-9
    assert red.busy_s()[0] == pytest.approx(want, abs=2e-9)
    assert 0 < red.busy_s()[0] < red.window_s
    assert red.idle_share()[0] == pytest.approx(
        1 - want / red.window_s, abs=1e-6)


@pytest.mark.parametrize("pattern", [r"^end: dot_general", r"tanh",
                                     r"sort"])
def test_op_seconds_sums_the_matching_events(profile, red, pattern):
    (lo, hi), ops = raw(profile)
    inner = brute_innermost(ops)
    want = sum(min(e, hi) - max(s, lo) for n, s, e in inner
               if re.search(pattern, n) and e > lo and s < hi) * 1e-9
    assert want > 0
    assert red.op_seconds(pattern)[0] == pytest.approx(want, abs=1e-12)


def test_breakdown_lists_are_sorted_and_short(red):
    ops, gaps = red.top_ops(), red.idle_gaps()
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert [v for _, v in gaps] == sorted((v for _, v in gaps),
                                          reverse=True)
    assert sum(v for _, v in gaps) <= red.window_s - red.busy_s()[0] + 1e-9
