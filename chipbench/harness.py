"""One cell's run: set-up, the check epochs, the timed window, the check.

What the window drives is the program's own training loop,
``repro.dist.DeviceRapidGNNRunner.run`` over ``make_pipelined_epoch``
(paper Alg. 1: steady cache C_s, C_sec and pull plans staged on a
background thread, a prefetched ``all_to_all`` pull), in this process,
on the cell's chips. The scenario is built as the program's device cells
build it: ``partition_graph``, ``KHopSampler``, ``build_schedule``
(numpy, eager), ``DeviceView``, the model's ``GNNConfig`` and AdamW.
The model is the module ``models/<model>.py``, found by the
configuration's ``model``: the weights, the program's configuration,
the FLOP count and the reference come from it. The graph comes from
``chipbench.graph``.

The runner walks every epoch of its schedules when it is built and
stages its first epoch synchronously on every ``run()`` call. So each
worker gets one ``WorkerSchedule`` view over ``epochs`` distinct
scheduled epochs: the two check epochs (``check.CHECK_BATCHES``), then
the distinct epochs, which the view repeats for the window. The runner
is built over the view before the repeats are appended, so its bound
walk covers only distinct epochs, and one ``run()`` call then covers
the whole window: whole epochs of the runner's own loop, with its own
overlap and one synchronous stage at its start, as a user's job has.
Static shapes are the cell's fixed floors (``cells/<cell>.json``), so
every seed runs the same compiled program and only a checkout's first
run compiles.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from chipbench import check, counts
from chipbench import trace as trace_mod
from chipbench.graph import DatasetSpec, make_graph

HERE = os.path.dirname(os.path.abspath(__file__))


# -- loading ---------------------------------------------------------------

def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with the files it names."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    bounds: Dict[str, Any]      # cells/<name>.json: pad floors, limits
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_cell(bench: Dict[str, Any], name: str, root: str) -> Cell:
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(os.path.join(root, conf["file"])),
        traffic=load_json(os.path.join(HERE, "traffic",
                                       w["traffic"] + ".json")),
        bounds=load_json(os.path.join(HERE, "cells", name + ".json")),
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str) -> Callable:
    """``metrics/<name>.py``'s ``read(run) -> float | None``."""
    return _load(os.path.join(HERE, "metrics", metric + ".py"),
                 "chipbench_metric_" + metric.replace(".", "_")).read


#: where a configuration's ``model`` is looked up, as ``<model>.py``
MODELS = os.path.join(HERE, "models")


def load_model(model: str):
    """The module of a configuration's ``model``: ``init_params(config,
    seed)``, ``loss_and_grad``, ``gnn_config(config)`` and
    ``epoch_flops(config, flat)`` (see ``models/``). Loaded once per
    file, so its jitted functions keep their compiled programs."""
    path = os.path.join(MODELS, model + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"model {model!r}: no {path}")
    return _model_at(path)


@functools.lru_cache(maxsize=None)
def _model_at(path: str):
    name = os.path.splitext(os.path.basename(path))[0]
    return _load(path, "chipbench_model_" + name.replace(".", "_"))


def peaks_for(kind: str) -> Dict[str, Any]:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device {kind!r} is not in chipbench/peaks.json")
    return table[kind]


# -- the scenario ----------------------------------------------------------

def dataset_spec(config: Dict[str, Any]) -> DatasetSpec:
    return DatasetSpec(**{f.name: config[f.name]
                          for f in dataclasses.fields(DatasetSpec)})


def build_graph(config: Dict[str, Any]):
    """-> (the program's ``Graph``, the generator's arrays it wraps);
    the check reads the arrays, not the program's object."""
    from repro.graph import Graph

    a = make_graph(dataset_spec(config), config["graph_seed"])
    g = Graph(indptr=a["indptr"], indices=a["indices"],
              features=a["features"], labels=a["labels"],
              num_classes=config["num_classes"])
    g.train_mask = a["train_mask"]
    return g, a


def _subset(es, lo: int, hi: int):
    """Epoch ``es`` cut to its batches [lo, hi), same hot set."""
    from repro.core.schedule import EpochSchedule
    from repro.graph import FlatEpoch

    f = es.flat
    sub = FlatEpoch.from_batches(f.to_batches()[lo:hi], epoch=f.epoch,
                                 worker=f.worker, num_layers=f.num_layers)
    return EpochSchedule(epoch=es.epoch, flat=sub,
                         remote_ids=es.remote_ids,
                         remote_freq=es.remote_freq,
                         cache_ids=es.cache_ids,
                         m_max=int(sub.m_counts.max()))


def build_views(schedules, floor: Dict[str, Any]):
    """One ``WorkerSchedule`` per worker over [check A, check B, epoch 0,
    ..., epoch E-1], with pad bounds at least the cell's floors."""
    from repro.core.schedule import WorkerSchedule, merge_pad_bounds

    m, em = merge_pad_bounds(schedules)
    meta = (max(m, floor["m_max"]),
            [max(a, b) for a, b in zip(em, floor["edge_max"])])
    views = []
    for ws in schedules:
        es0 = ws.epoch(0)
        epochs = [_subset(es0, lo, hi) for lo, hi in check.CHECK_BATCHES]
        epochs += [ws.epoch(e) for e in range(len(ws.epochs))]
        views.append(WorkerSchedule(
            worker=ws.worker, s0=ws.s0, n_hot=ws.n_hot, epochs=epochs,
            epoch_meta=[meta] * len(epochs)))
    return views


def extend_views(runner, views, distinct: int, window_epochs: int) -> int:
    """Append ``window_epochs`` repeats of the distinct epochs (which sit
    at view index 2 on) and let the runner run them; -> first window
    index. The repeats are the same objects, so nothing is rebuilt."""
    start = len(views[0].epochs)
    for v in views:
        for i in range(window_epochs):
            e = 2 + (start - 2 + i) % distinct
            v.epochs.append(v.epochs[e])
            v.epoch_meta.append(v.epoch_meta[e])
    runner.num_epochs = len(views[0].epochs)
    return start


# -- one run ---------------------------------------------------------------

@dataclasses.dataclass
class RunData:
    """What the per-layer readers see (``metrics/<name>.py``)."""
    chips: int
    peaks: Dict[str, Any]
    window: Dict[str, Any]
    trace: Any = None            # chipbench.trace.Reduction, traced runs


@dataclasses.dataclass
class Setup:
    """A built cell: the program's runner over the schedule views, with
    what the check and the counts need beside it."""
    cell: Cell
    seed: int
    graph: Any
    arrays: Dict[str, np.ndarray]   # chipbench.graph.make_graph's
    partition: Any
    schedules: List[Any]
    views: List[Any]
    runner: Any
    hp: tuple                    # AdamW (lr, b1, b2, eps, weight_decay)
    model: Any                   # the configuration's model module


def build(cell: Cell, seed: int, epochs: int,
          log: Callable[[str], None] = print, graph=None, arrays=None,
          partition=None) -> Setup:
    """Graph, partition, ``epochs`` scheduled epochs per worker from
    ``seed``, the views and the runner, as a device cell builds them.
    ``graph``/``arrays``/``partition`` reuse an earlier setup's (they
    do not depend on the seed)."""
    from repro.core import build_schedule
    from repro.dist import DeviceRapidGNNRunner, DeviceView, Topology
    from repro.graph import KHopSampler, partition_graph
    from repro.train import AdamW

    conf, traffic, floor = cell.config, cell.traffic, cell.bounds["pad_floor"]
    P, batch = traffic["workers"], traffic["batch_size"]
    opt = conf["optimizer"]
    model = load_model(conf["model"])
    g, arrays = build_graph(conf) if graph is None else (graph, arrays)
    pg = (partition_graph(g, P, traffic["partition"]) if partition is None
          else partition)
    sampler = KHopSampler(g, fanouts=conf["fanouts"], batch_size=batch)
    schedules = [build_schedule(sampler, pg, worker=w, s0=seed,
                                num_epochs=epochs, n_hot=conf["n_hot"])
                 for w in range(P)]
    views = build_views(schedules, floor)
    topo = Topology.flat(P)
    runner = DeviceRapidGNNRunner(
        views, DeviceView.build(pg), model.gnn_config(conf),
        AdamW(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
              weight_decay=opt["weight_decay"]),
        topo.make_mesh(), batch, g.labels, seed=seed, topology=topo)
    # a fixed lane bound too, so the collated shapes never follow the seed
    runner.k_max = max(runner.k_max, floor["k_max"])
    log(f"static shapes: m_max={runner.m_max} "
        f"edge_max={list(runner.edge_max)} k_max={runner.k_max} "
        f"num_steps={runner.num_steps} n_hot={runner.n_hot} workers={P}")
    hp = (opt["lr"], opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"])
    return Setup(cell=cell, seed=seed, graph=g, arrays=arrays,
                 partition=pg, schedules=schedules, views=views,
                 runner=runner, hp=hp, model=model)


def train_check(s: Setup) -> Dict[str, Any]:
    """Train check epochs A and B through the runner from the seed's
    weights. -> the program's readings (``check.compare``'s ``prog``)
    and the initial weights; the runner keeps the trained state.
    The first call compiles the epoch program."""
    import jax

    runner = s.runner
    p0 = s.model.init_params(s.cell.config, s.seed)
    p0_host = jax.device_get(p0)
    rep_a = runner.run(params=p0, start_epoch=0, stop_epoch=1)
    grad1 = check.grad_from_first_moment(
        jax.device_get(runner.opt_state.mu), s.hp[1], runner.num_steps)
    rep_b = runner.run(params=runner.params, opt_state=runner.opt_state,
                       start_epoch=1, stop_epoch=2)
    return {"losses": [float(rep_a[0].losses[0])]
            + [float(x) for x in rep_b[0].losses[:2]],
            "grad1": grad1,
            "delta": check.tree_sub(jax.device_get(runner.params),
                                    p0_host),
            "params0": p0_host}


def check_steps(s: Setup) -> List[List[dict]]:
    """The check's real worker-steps, padded for the reference."""
    floor = s.cell.bounds["pad_floor"]
    return check.pack_steps([ws.epoch(0).flat for ws in s.schedules],
                            s.graph.labels,
                            s.cell.traffic["batch_size"], floor["m_max"],
                            floor["edge_max"])


def steps_per_epoch(s: Setup) -> int:
    """Steps of a (padded) epoch, from the schedule: its most batches."""
    return max(ws.epoch(e).num_batches for ws in s.schedules
               for e in range(len(ws.epochs)))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device: Dict[str, Any], t0: float,
             log: Callable[[str], None] = print) -> Dict[str, Any]:
    """Set up, train the check epochs, run the window, check, report.
    ``device`` is the platform record (``platform``, ``kind``,
    ``count``); ``t0`` the process's start on ``time.perf_counter``."""
    import jax

    E = cell.traffic["epochs"]
    s = build(cell, seed, E, log=log)
    runner, views, schedules, g, hp, model = (
        s.runner, s.views, s.schedules, s.graph, s.hp, s.model)
    P, S = cell.traffic["workers"], runner.num_steps
    prog = train_check(s)

    # one warm epoch sizes the window: steady epochs take the longer of
    # the device epoch and the staging of the next one
    staged = runner.stage_time_s
    rep_w = runner.run(params=runner.params, opt_state=runner.opt_state,
                       start_epoch=2, stop_epoch=3)
    epoch_s = max(rep_w[0].wall_time_s, runner.stage_time_s - staged)
    n = max(2, math.ceil(seconds / epoch_s))
    start = extend_views(runner, views, E, n)
    per_epoch = [{} for _ in range(E)]
    flops = functools.partial(model.epoch_flops, cell.config)
    for ws in schedules:
        for e in range(E):
            per_epoch[e] = counts.add(per_epoch[e], counts.epoch_counts(
                ws.epoch(e).flat, cell.config["feat_dim"], flops))
    work: Dict[str, float] = {}
    for i in range(n):
        work = counts.add(work, per_epoch[(start - 2 + i) % E])
    setup_s = time.perf_counter() - t0
    log(f"setup_s={setup_s} warm_epoch_s={rep_w[0].wall_time_s} "
        f"stage_s={runner.stage_time_s - staged} window_epochs={n}")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if trace else None
    staged, exposed = runner.stage_time_s, runner.exposed_stage_s
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
            tw = time.perf_counter()
            reps = runner.run(params=runner.params,
                              opt_state=runner.opt_state,
                              start_epoch=start, stop_epoch=start + n)
            jax.block_until_ready((runner.params, runner.opt_state))
            wall = time.perf_counter() - tw
    finally:
        if trace:
            jax.profiler.stop_trace()
    devices = list(runner.mesh.devices.flat)
    peak = max_peak_bytes(devices)
    stage_s = runner.stage_time_s - staged
    losses = np.concatenate([r.losses for r in reps])
    window = {
        "epochs": n, "steps": n * S, "wall_s": wall, "work": work,
        "stage_s": stage_s,
        # the background waits, plus the call's synchronous first stage
        "exposed_stage_s": (runner.exposed_stage_s - exposed
                            + stage_s - sum(r.stage_s for r in reps)),
    }
    log(f"window: epochs={n} steps={n * S} wall_s={wall} "
        f"seeds={work['seeds']} stage_s={stage_s} "
        f"exposed_stage_s={window['exposed_stage_s']} peak_bytes={peak}")
    log("epoch walls, stages and exposed stages (s): "
        + " ".join(f"{r.wall_time_s:.4f}/{r.stage_s:.4f}/"
                   f"{r.exposed_stage_s:.4f}" for r in reps))
    steps, S_ref = check_steps(s), steps_per_epoch(s)
    blocks = check.block_faults(
        s.arrays, [[ws.epoch(e).flat for ws in schedules] for e in range(E)],
        cell.config["fanouts"])
    del s, runner, views, schedules, reps, rep_w
    gc.collect()

    # the check, after the window and the peak reading
    table = jax.device_put(g.features, devices[0])
    ref = check.reference_readings(
        steps, table, jax.device_put(prog["params0"], devices[0]),
        hp, S_ref, model.loss_and_grad)
    numbers = dict(check.compare(prog, ref), blocks=blocks)
    failed = int(np.count_nonzero(~np.isfinite(losses)))
    limits = cell.bounds["limits"]
    correct = check.judge(numbers, limits) and failed == 0
    log(f"program losses={prog['losses']} reference losses={ref['losses']}")

    measured = {"seed_nodes_per_s": work["seeds"] / wall,
                "peak_hbm_bytes": peak, "setup_s": setup_s}
    dev = dict(device, memory_peak_bytes=peak)
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": n * S, "failed": failed}
    if trace:
        red = trace_mod.Reduction.from_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        for i, idle in enumerate(red.idle_share()):
            log(f"chip {i}: idle share {idle} of {red.window_s} s")
        data = RunData(chips=P, peaks=peaks_for(device["kind"]),
                       window=window, trace=red)
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = red.busy_s()
        dev["busy_s"] = sum(busy) / len(busy)
        dev["window_s"] = red.window_s
        result["metrics"] = metrics
        result["device"] = dev
        result["breakdown"] = {"device_ops": red.top_ops(),
                               "idle_gaps": red.idle_gaps()}
    else:
        result["metrics"] = {m["name"]: {"value": measured[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = dev
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in check.NUMBERS}
    return result


def max_peak_bytes(devices) -> Optional[int]:
    """The fullest chip's ``peak_bytes_in_use``; None where the backend
    keeps no memory statistics (the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return None if None in peaks else max(peaks)
