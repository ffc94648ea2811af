"""Split a traced window by the program's own names: device time by
``jax.named_scope`` path, chip idle by ``rapidgnn.*`` host span.

  python -m chipbench.scopes --workload sage-products.p1 --seed 7 \\
      --seconds 10

runs one cell as ``python -m chipbench.run ... --trace 1`` does, with
the epoch program's optimized HLO dumped beside the trace (and no
persistent compile cache, so the program compiles and is dumped), then
prints the run's result line and, as the last line of standard output,
one JSON line: per-step device ms by top-level scope, the window's
chip idle by span, and the useful-row share of the window's epochs.

A TPU trace names each op by its HLO text alone (``%fusion.150 = ...``,
no ``op_name``), so an op's scope comes from the compiled module, where
each instruction carries ``metadata={op_name="jit(epoch_fn)/while/body/
closed_call/forward/..."}``; instruction names are unique in a module.
Host spans keep the line (thread) they were recorded on: the
dispatching thread is the one that holds ``chipbench.window``.
"""
from __future__ import annotations

import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace

SPAN_PREFIX = "rapidgnn."
#: the epoch program's jit, as the HLO dump names its module
MODULE = "jit_epoch_fn"
NO_SPAN = "no span"

#: top-level scopes of the step program and the ``op_name`` path
#: segment that marks each; backward ops carry the transposed forward
TOP_SCOPES = (("pull", "pull"), ("assemble", "assemble"),
              ("backward", "transpose(jvp(forward))"),
              ("forward", "jvp(forward)"), ("forward", "forward"),
              ("grad_allreduce", "grad_allreduce"),
              ("optimizer", "optimizer"))

_COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(")
_INST = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')

Op = Tuple[str, float, float]          # (op_name, start_ns, end_ns)
Span = Tuple[str, int, float, float]   # (name, line, start_ns, end_ns)


def instructions(hlo_text: str) -> List[Tuple[str, str, str, str]]:
    """-> (computation, instruction, opcode, op_name) of every
    instruction of a compiled module's text (``op_name`` "" where the
    instruction has none)."""
    out, comp = [], ""
    for line in hlo_text.splitlines():
        c = _COMP.match(line)
        if c and line.rstrip().endswith("{"):
            comp = c.group(2)
            continue
        m = _INST.match(line)
        if m:
            n = _OP_NAME.search(line)
            out.append((comp, m.group(1), m.group(2),
                        n.group(1) if n else ""))
    return out


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name``."""
    return {i: n for _, i, _, n in instructions(hlo_text)}


def top_scope(op_name: str) -> Optional[str]:
    """The top-level scope an ``op_name`` path lies in, or None."""
    segs = op_name.split("/")
    for seg in segs:
        for scope, mark in TOP_SCOPES:
            if seg == mark:
                return scope
    return None


def read_dump(dump_dir: str, module: str = MODULE) -> str:
    """The newest optimized-HLO text of ``module`` in an XLA dump."""
    paths = glob.glob(os.path.join(
        dump_dir, f"*{module}*after_optimizations.txt"))
    if not paths:
        raise RuntimeError(f"no optimized HLO of {module} under {dump_dir}")
    with open(max(paths, key=os.path.getmtime)) as f:
        return f.read()


class ScopedTrace:
    """The traced window with each chip's innermost ops named by their
    ``op_name`` and the ``rapidgnn.*`` host spans with their line."""

    def __init__(self, chips: List[List[Op]], spans: List[Span],
                 dispatch: int, lo: float, hi: float,
                 unnamed_s: float = 0.0):
        self.chips, self.spans, self.dispatch = chips, spans, dispatch
        self.lo, self.hi = lo, hi
        #: device seconds (all chips) of ops the module text did not name
        self.unnamed_s = unnamed_s

    @classmethod
    def from_profile(cls, pd, names: Dict[str, str],
                     plane_re=trace.TPU_PLANE,
                     ops_line=lambda n: n == trace.OPS_LINE
                     ) -> "ScopedTrace":
        spans, window = [], []
        for p in pd.planes:
            if p.name != trace.HOST_PLANE:
                continue
            for k, line in enumerate(p.lines):
                for ev in line.events:
                    iv = (ev.name, k, ev.start_ns,
                          ev.start_ns + ev.duration_ns)
                    if ev.name == trace.WINDOW:
                        window.append(iv)
                    elif ev.name.startswith(SPAN_PREFIX):
                        spans.append(iv)
        if len(window) != 1:
            raise RuntimeError(f"expected one {trace.WINDOW!r} span, "
                               f"found {len(window)}")
        _, dispatch, lo, hi = window[0]
        chips, unnamed = [], 0.0
        for p in sorted((p for p in pd.planes if plane_re.match(p.name)),
                        key=lambda p: p.name):
            ops = trace.clip(trace.innermost(trace.line_events(p, ops_line)),
                             lo, hi)
            if ops:
                chips.append([(names.get(trace.short(n), ""), s, e)
                              for n, s, e in ops])
                unnamed += sum(e - s for n, s, e in ops
                               if trace.short(n) not in names) * 1e-9
        spans = [(n, k, max(s, lo), min(e, hi)) for n, k, s, e in spans
                 if e > lo and s < hi]
        return cls(chips, spans, dispatch, lo, hi, unnamed)

    def busy_s(self) -> List[float]:
        return [trace.union_ns(c) * 1e-9 for c in self.chips]

    def scope_seconds(self, include: str,
                      exclude: Optional[str] = None) -> List[float]:
        """Per chip: seconds of the ops whose ``op_name`` matches the
        regex ``include`` and not ``exclude``."""
        inc = re.compile(include)
        exc = re.compile(exclude) if exclude else None
        return [sum(e - s for n, s, e in c
                    if inc.search(n) and not (exc and exc.search(n))) * 1e-9
                for c in self.chips]

    def by_top_scope(self) -> Dict[str, float]:
        """Seconds per top-level scope (``unscoped`` for the rest), mean
        over chips; every op counts once, so they sum to the busy time
        of ops that do not overlap."""
        tot: Dict[str, float] = {}
        for c in self.chips:
            for n, s, e in c:
                k = top_scope(n) or "unscoped"
                tot[k] = tot.get(k, 0.0) + (e - s) * 1e-9
        return {k: v / len(self.chips) for k, v in tot.items()}

    def idle_by_span(self) -> Dict[str, float]:
        """Each chip's idle seconds split over the innermost
        ``rapidgnn.*`` span of the dispatching thread that covers each
        instant, ``no span`` where none does; mean over chips."""
        mine = [(n, s, e) for n, k, s, e in self.spans
                if k == self.dispatch]
        tot: Dict[str, float] = {}
        for c in self.chips:
            for gs, ge, _ in trace.gaps(c, self.lo, self.hi):
                for name, s, e in split_by_spans(gs, ge, mine):
                    tot[name] = tot.get(name, 0.0) + (e - s) * 1e-9
        return {k: v / len(self.chips) for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])}


def split_by_spans(lo: float, hi: float, spans: Sequence[trace.Event]
                   ) -> List[Tuple[str, float, float]]:
    """[lo, hi] cut at the spans' edges, each piece named by the
    shortest (innermost, for nested spans of one thread) span that
    covers it, ``no span`` where none does."""
    cuts = sorted({lo, hi} | {x for _, s, e in spans for x in (s, e)
                              if lo < x < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        cover = [sp for sp in spans if sp[1] <= a and b <= sp[2]]
        name = (min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover
                else NO_SPAN)
        out.append((name, a, b))
    return out


def breakdown(st: ScopedTrace, steps: int) -> Dict[str, object]:
    """The numbers ``main`` prints: ms per step by top-level scope and
    for ``aggregate`` (forward and backward), the scoped share of busy
    time, and the idle by span in seconds over the window."""
    busy = st.busy_s()
    per = 1e3 / steps
    agg = st.scope_seconds(r"(^|/)aggregate(/|$)")
    scopes = st.by_top_scope()
    return {
        "busy_ms_per_step": sum(busy) / len(busy) * per,
        "scope_ms_per_step": {k: v * per for k, v in sorted(
            scopes.items(), key=lambda kv: -kv[1])},
        "aggregate_ms_per_step": sum(agg) / len(agg) * per,
        "scoped_share_of_busy": (sum(v for k, v in scopes.items()
                                     if k != "unscoped")
                                 / (sum(busy) / len(busy))),
        "unnamed_op_s": st.unnamed_s,
        "idle_by_span_s": st.idle_by_span(),
    }


def run_scoped(cell, seed: int, seconds: float, device: Dict[str, object],
               t0: float, dump_dir: str, log=print
               ) -> Tuple[Dict[str, object], Dict[str, object]]:
    """``harness.run_cell`` with ``trace`` on, and the same trace read
    by ``ScopedTrace`` against the epoch program's HLO in ``dump_dir``.
    -> (the run's result, ``breakdown`` plus the window's
    ``valid_rows`` and ``padded_rows``)."""
    import jax

    from chipbench import harness

    seen: Dict[str, object] = {"calls": []}
    build, from_dir = harness.build, trace.Reduction.from_dir

    def build_and_keep(*a, **kw):
        s = build(*a, **kw)
        run = s.runner.run

        def run_and_keep(*ra, **rkw):
            reps = run(*ra, **rkw)
            seen["calls"].append(reps)
            return reps
        s.runner.run = run_and_keep
        return s

    def reduce_both(trace_dir, **kw):
        try:
            names = op_names(read_dump(dump_dir))
        except RuntimeError as e:      # scopes unread; spans still are
            log(f"chipbench.scopes: {e}")
            names = {}
        seen["scoped"] = ScopedTrace.from_profile(
            jax.profiler.ProfileData.from_file(trace.find_xplane(trace_dir)),
            names)
        return from_dir(trace_dir, **kw)

    harness.build, trace.Reduction.from_dir = build_and_keep, reduce_both
    try:
        result = harness.run_cell(cell, seed, seconds, True, device, t0,
                                  log=log)
    finally:
        harness.build, trace.Reduction.from_dir = build, from_dir
    window = seen["calls"][-1]
    out = breakdown(seen["scoped"], sum(r.steps for r in window))
    out["valid_rows"] = sum(getattr(r, "valid_rows", 0) for r in window)
    out["padded_rows"] = sum(getattr(r, "padded_rows", 0) for r in window)
    return result, out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    dump = tempfile.mkdtemp(prefix="chipbench-hlo-")
    # read when XLA starts, so before JAX does
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"), f"--xla_dump_to={dump}",
        "--xla_dump_hlo_as_text", f"--xla_dump_hlo_module_re={MODULE}"]))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from chipbench import harness, run

    # a program loaded from a compile cache would not be dumped
    jax.config.update("jax_enable_compilation_cache", False)

    def log(msg: str) -> None:
        print(f"[{time.perf_counter() - t0:8.3f}s] {msg}", file=sys.stderr,
              flush=True)

    bench = harness.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell = harness.load_cell(bench, args.workload, run.ROOT)
    try:
        device = run.require_chips(jax, cell.chips)
    except RuntimeError as e:
        log(f"chipbench.scopes: {e}")
        return 3
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    result, out = run_scoped(cell, args.seed, args.seconds, device, t0,
                             dump, log=log)
    shutil.rmtree(dump, ignore_errors=True)
    print(json.dumps(result))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
