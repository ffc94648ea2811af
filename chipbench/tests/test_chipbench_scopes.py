"""The program's names, and the reduction that reads them.

The epoch programs (``make_pipelined_epoch``, ``make_ondemand_epoch``)
compiled at the tiny cell's size, on one worker and on four virtual CPU
devices: every op the program itself computes lies in a top-level scope.
The runner's ``rapidgnn.*`` host spans and its row counters, from a
profiled two-epoch run on the CPU. ``chipbench.scopes`` on synthetic
events and on that run's trace, and the new reader on traces that have
nothing for it."""
import json
import os
import re
import subprocess
import sys
from typing import List, Tuple

import numpy as np
import pytest

from chipbench import counts, harness, scopes, trace
from chipbench.tests import _scoped, _tiny

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "cpu_window.xplane.pb")

#: opcodes that move, name or hold data and compute nothing
PLUMBING = {"parameter", "constant", "get-tuple-element", "tuple",
            "bitcast", "copy", "while", "broadcast"}
#: ops of the loop and of shard_map themselves (the scan's counter and
#: bound, its per-step input slices and stacked outputs, constant
#: fills), and ops XLA made that carry no ``op_name``
MACHINERY = re.compile(
    r"^$|/(while|shard_map)(/body|/cond)?(/closed_call)?"
    r"(/(add|lt|dynamic_slice|dynamic_update_slice|broadcast)(\.\d+)?)?$")

SPANS = ("stage", "stage.schedule", "stage.caches", "stage.collate",
         "stage.to_device", "stage.stack_caches", "stage.wait",
         "epoch.dispatch", "epoch.readback", "epoch.report")
CHILDREN = ("stage.schedule", "stage.caches", "stage.collate",
            "stage.to_device", "stage.stack_caches")

COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(")
CALLS = re.compile(r"\b(condition|body)=%?([\w.\-]+)")


def executed(hlo_text: str) -> List[Tuple[str, str, str]]:
    """-> (instruction, opcode, op_name) of the instructions that run as
    ops of their own: the entry computation's and, recursively, those of
    the bodies and conditions of its ``while`` loops (not the insides of
    fusions or reducers)."""
    entry, loops = None, {}
    for line in hlo_text.splitlines():
        c = COMP.match(line)
        if c and c.group(1):
            entry = c.group(2)
    for line in hlo_text.splitlines():
        c = COMP.match(line)
        if c and line.rstrip().endswith("{"):
            comp = c.group(2)
        elif " while(" in line:
            loops.setdefault(comp, []).extend(
                m.group(2) for m in CALLS.finditer(line))
    keep, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp not in keep:
            keep.add(comp)
            todo.extend(loops.get(comp, []))
    return [(i, o, n) for c, i, o, n in scopes.instructions(hlo_text)
            if c in keep]


@pytest.fixture(scope="module")
def programs():
    """workers -> {"pipelined": hlo text, "ondemand": hlo text}."""
    out = {1: _scoped.compiled_programs(1)}
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    run = subprocess.run(
        [sys.executable, "-m", "chipbench.tests._scoped", "4"],
        cwd=_tiny.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    out[4] = json.loads(run.stdout.strip().splitlines()[-1])
    return out


@pytest.mark.parametrize("program", ["pipelined", "ondemand"])
@pytest.mark.parametrize("workers", [1, 4])
def test_every_compute_op_lies_in_a_top_level_scope(programs, workers,
                                                    program):
    text = programs[workers][program]
    ops = executed(text)
    assert len(ops) > 50
    bare = [(i, o, n) for i, o, n in ops
            if o not in PLUMBING and not MACHINERY.search(n)
            and scopes.top_scope(n) is None]
    assert bare == []
    found = {scopes.top_scope(n) for _, _, n in ops}
    want = {"assemble", "forward", "backward", "grad_allreduce",
            "optimizer"}
    if workers > 1:     # one worker's pull can fuse into other ops
        want.add("pull")
    assert want <= found


@pytest.mark.parametrize("program", ["pipelined", "ondemand"])
@pytest.mark.parametrize("workers", [1, 4])
def test_backward_ops_are_transposed_and_aggregate_is_in_both(
        programs, workers, program):
    names = [n for _, _, _, n in scopes.instructions(
        programs[workers][program])]
    assert any("/transpose(jvp(forward))/" in n for n in names)
    assert any(re.search(r"/jvp\(forward\)/(.*/)?aggregate/", n)
               and "transpose(" not in n for n in names)
    assert any(re.search(r"/transpose\(jvp\(forward\)\)/(.*/)?aggregate/", n)
               for n in names)


def test_top_scope_reads_the_path():
    base = "jit(epoch_fn)/shard_map/while/body/closed_call/"
    assert scopes.top_scope(base + "jvp(forward)/aggregate/mul") == \
        "forward"
    assert scopes.top_scope(
        base + "transpose(jvp(forward))/aggregate/scatter-add") == \
        "backward"
    assert scopes.top_scope(base + "assemble/jit(assemble_features)/x") \
        == "assemble"
    assert scopes.top_scope("jit(epoch_fn)/pull/slice") == "pull"
    assert scopes.top_scope(base + "optimizer/sqrt") == "optimizer"
    assert scopes.top_scope(base + "grad_allreduce/psum") == \
        "grad_allreduce"
    assert scopes.top_scope("jit(epoch_fn)/while/body/add") is None


# -- the reduction on synthetic events ------------------------------------

def synthetic():
    """One chip, window [0, 100]: ops at [0, 10] (forward), [20, 30]
    (backward), [30, 40] (optimizer), [70, 80] (unscoped). Dispatching
    thread (line 0): ``epoch.readback`` [5, 25], ``stage.wait``
    [25, 50] holding ``stage`` [30, 45]; the staging thread's (line 1)
    ``stage`` [0, 100] must not count."""
    f = "jit(epoch_fn)/while/body/closed_call/"
    ops = [(f + "jvp(forward)/aggregate/mul", 0, 10),
           (f + "transpose(jvp(forward))/aggregate/scatter-add", 20, 30),
           (f + "optimizer/sqrt", 30, 40),
           ("jit(epoch_fn)/while/body/add", 70, 80)]
    spans = [("rapidgnn.epoch.readback", 0, 5, 25),
             ("rapidgnn.stage.wait", 0, 25, 50),
             ("rapidgnn.stage", 0, 30, 45),
             ("rapidgnn.stage", 1, 0, 100)]
    return scopes.ScopedTrace([ops], spans, dispatch=0, lo=0, hi=100)


def test_scope_seconds_includes_and_excludes():
    st = synthetic()
    assert st.scope_seconds("forward", r"transpose\(") == \
        pytest.approx([10e-9])
    assert st.scope_seconds(r"transpose\(jvp\(forward\)\)") == \
        pytest.approx([10e-9])
    assert st.scope_seconds("aggregate") == pytest.approx([20e-9])
    assert st.scope_seconds("optimizer|grad_allreduce") == \
        pytest.approx([10e-9])
    tops = st.by_top_scope()
    assert tops == pytest.approx({"forward": 10e-9, "backward": 10e-9,
                                  "optimizer": 10e-9, "unscoped": 10e-9})
    assert sum(tops.values()) == pytest.approx(st.busy_s()[0])


def test_idle_by_span_splits_a_gap_that_straddles_spans():
    # idle: [10, 20] (readback), [40, 70] (stage [40, 45], stage.wait
    # [45, 50], no span [50, 70]) and [80, 100] (no span)
    got = synthetic().idle_by_span()
    assert got == pytest.approx({"rapidgnn.epoch.readback": 10e-9,
                                 "rapidgnn.stage": 5e-9,
                                 "rapidgnn.stage.wait": 5e-9,
                                 scopes.NO_SPAN: 40e-9})
    assert sum(got.values()) == pytest.approx(
        100e-9 - synthetic().busy_s()[0])


def test_split_by_spans_takes_the_innermost():
    spans = [("outer", 0, 100), ("inner", 40, 60)]
    assert scopes.split_by_spans(30, 70, spans) == [
        ("outer", 30, 40), ("inner", 40, 60), ("outer", 60, 70)]
    assert scopes.split_by_spans(100, 120, spans) == [
        (scopes.NO_SPAN, 100, 120)]


def test_executed_keeps_entry_and_loop_bodies_only():
    text = "\n".join([
        "%fused (p: f32[4]) -> f32[4] {",
        '  ROOT %m = f32[4] multiply(%p, %p), metadata={op_name="a/mul"}',
        "}",
        "%body (t: (s32[])) -> (s32[]) {",
        '  %f = f32[4] fusion(%x), kind=kLoop, calls=%fused, '
        'metadata={op_name="a/while/body/forward/mul"}',
        "}",
        "%cond (t: (s32[])) -> pred[] {",
        '  ROOT %lt = pred[] compare(%a, %b), direction=LT, '
        'metadata={op_name="a/while/cond/lt"}',
        "}",
        "ENTRY %main (x: f32[4]) -> f32[4] {",
        "  %w = (s32[]) while(%t), condition=%cond, body=%body",
        "}"])
    assert sorted(i for i, _, _ in executed(text)) == \
        ["f", "lt", "w"]
    assert scopes.op_names(text)["m"] == "a/mul"


# -- the runner's spans and counters --------------------------------------

@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Two epochs of the tiny cell's runner inside ``chipbench.window``
    under the profiler, and again with the profiler off."""
    import jax

    s = harness.build(_tiny.tiny_cell(1), 2 ** 36 + 5, 2,
                      log=lambda m: None)
    runner, texts = s.runner, []
    fn = runner._fn

    def keep(*args):
        if not texts:
            texts.append(fn.lower(*args).compile().as_text())
        return fn(*args)
    runner._fn = keep
    off = runner.run(stop_epoch=2)
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            on = runner.run(stop_epoch=2)
    pd = jax.profiler.ProfileData.from_file(trace.find_xplane(d))
    return {"setup": s, "off": off, "on": on, "profile": pd,
            "text": texts[0]}


def host_spans(pd):
    """(name, line, start, end) of every ``rapidgnn.*`` span, and the
    line that holds the window."""
    spans, main = [], None
    for p in pd.planes:
        if p.name == trace.HOST_PLANE:
            for k, line in enumerate(p.lines):
                for ev in line.events:
                    if ev.name == trace.WINDOW:
                        main = k
                    if ev.name.startswith(scopes.SPAN_PREFIX):
                        spans.append((ev.name, k, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return spans, main


def test_every_span_is_recorded(profiled):
    spans, main = host_spans(profiled["profile"])
    assert {n for n, _, _, _ in spans} == {"rapidgnn." + s for s in SPANS}
    for n, k, _, _ in spans:
        if n.startswith("rapidgnn.epoch.") or n == "rapidgnn.stage.wait":
            assert k == main, n


def test_staging_children_nest_in_a_stage_off_the_main_thread(profiled):
    spans, main = host_spans(profiled["profile"])
    stages = [sp for sp in spans if sp[0] == "rapidgnn.stage"]
    for n, k, s, e in spans:
        if n[len("rapidgnn."):] in CHILDREN:
            assert any(k == sk and ss <= s and e <= se
                       for _, sk, ss, se in stages), (n, k)
    background = {k for _, k, _, _ in stages} - {main}
    assert background
    for child in CHILDREN:
        assert any(n == "rapidgnn." + child and k in background
                   for n, k, _, _ in spans), child


def test_losses_are_bit_equal_with_the_profiler_on(profiled):
    for a, b in zip(profiled["off"], profiled["on"]):
        np.testing.assert_array_equal(a.losses, b.losses)
        np.testing.assert_array_equal(a.accs, b.accs)


def test_valid_rows_count_the_schedule_rows(profiled):
    s = profiled["setup"]
    runner = s.runner
    for rep in profiled["on"]:
        want = sum(counts.epoch_counts(
            ws.epoch(rep.epoch).flat, s.cell.config["feat_dim"],
            lambda flat: 0.0)["rows"] for ws in runner.schedules)
        assert rep.valid_rows == want
        assert rep.padded_rows == runner.num_steps * runner.P * runner.m_max
        assert 0 < rep.valid_rows < rep.padded_rows
        d = rep.to_dict()
        assert (d["valid_rows"], d["padded_rows"]) == (rep.valid_rows,
                                                       rep.padded_rows)


def without_end_markers(pd):
    """``pd`` with the XLA CPU runtime's ``end: <op>`` events left out.
    That runtime records each op's end as a short event inside the op's
    own, so ``trace.innermost`` would keep the marker, which no HLO
    instruction names, and drop the op: a scope's ops then survive only
    where timing splits a pair, and a scope of short ops can vanish from
    the reading. A TPU's ``XLA Ops`` line holds the ops alone."""
    from types import SimpleNamespace as NS

    return NS(planes=[NS(name=p.name, lines=[
        NS(name=line.name, events=[ev for ev in line.events
                                   if not ev.name.startswith("end: ")])
        for line in p.lines]) for p in pd.planes])


def test_scoped_trace_reads_the_cpu_run(profiled):
    """On the CPU the ops run on the host plane's ``tf_XLA*`` lines and
    are named by their HLO instruction names."""
    st = scopes.ScopedTrace.from_profile(
        without_end_markers(profiled["profile"]),
        scopes.op_names(profiled["text"]),
        plane_re=re.compile(r"^/host:CPU$"),
        ops_line=lambda n: n.startswith("tf_XLA"))
    tops = st.by_top_scope()
    assert {"assemble", "forward", "backward", "optimizer"} <= set(tops)
    idle = st.idle_by_span()
    assert any(k.startswith("rapidgnn.") for k in idle)
    assert sum(idle.values()) == pytest.approx(
        (st.hi - st.lo) * 1e-9 - st.busy_s()[0], rel=1e-6)


# -- the new reader -------------------------------------------------------

def read_boundary_idle(data):
    return harness.load_reader("epoch.boundary_idle_ms")(data)


def test_boundary_idle_is_none_without_spans_or_trace():
    import jax

    red = trace.Reduction.from_profile(
        jax.profiler.ProfileData.from_file(DATA),
        plane_re=re.compile(r"^/host:CPU$"),
        ops_line=lambda n: n.startswith("tf_XLA"))
    window = {"epochs": 3, "steps": 9}
    assert read_boundary_idle(harness.RunData(
        chips=1, peaks={}, window=window, trace=red)) is None
    assert read_boundary_idle(harness.RunData(
        chips=1, peaks={}, window=window)) is None


def test_boundary_idle_reads_idle_inside_epoch_spans():
    # chip idle [10, 20] and [40, 100]; epoch spans [5, 25] and
    # [60, 70]: 10 + 10 ns over 2 epochs
    red = trace.Reduction(
        [[("%a = f32[] add()", 0, 10), ("%b = f32[] add()", 20, 40)]],
        [("rapidgnn.epoch.readback", 5, 25),
         ("rapidgnn.epoch.dispatch", 60, 70),
         ("rapidgnn.stage", 30, 90)], 0, 100)
    got = read_boundary_idle(harness.RunData(
        chips=1, peaks={}, window={"epochs": 2}, trace=red))
    assert got == pytest.approx(10e-6)
