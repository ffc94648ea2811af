"""Microbenchmark: the vectorized epoch-at-once schedule compiler vs the
per-batch oracle (ISSUE 5 / DESIGN.md §2.1).

Three sections; the first two at a 64- and a 256-worker partition point:

  * sampler -- ``KHopSampler.sample_epoch_batched`` vs the per-batch
    ``sample_epoch`` loop AND the device compiler port
    (``sample_epoch_batched_device``, DESIGN.md §2.2), asserting
    bit-exact batch parity before any timing.
  * build   -- one end-to-end worker-epoch build (sampling + remote
    frequency counting + deterministic hot-set selection; the loop
    variant additionally pays ``FlatEpoch.from_batches`` packing, which
    IS its pipeline -- the canonical schedule payload is flat), all
    three compilers.
  * overlap -- the device runner's train-overlapped next-epoch builds
    on LAZY schedules (one emulated device; the parent process must
    stay single-device): total staging wall vs the slice left EXPOSED
    on the critical path after training hides the rest, with lazy-vs-
    eager loss-curve parity asserted before timing.

Device-compiler caveat (recorded honestly, PR 5 precedent): on a
single-CPU host the device columns lose to numpy -- XLA's comparison
sort vs numpy's radix sort on one core. The port's case is the
accelerator + staging-thread overlap, not single-core CPU throughput.

Per-worker train mass follows the assemble-bench convention of
paper-proportioned shapes: ogbn-papers100M has ~1.2 M train nodes, so a
P-worker cluster hands each worker ~1.2M/P seeds (capped at
``MAX_TRAIN`` to keep the loop reference affordable; the sim partitions
themselves are far smaller than papers100M's, so the seed stream is
drawn graph-wide -- schedule-build cost depends on the stream size and
the graph, not on who owns the seeds). Loop/batched iterations are
INTERLEAVED and min-of-N so machine drift cancels out of the ratio.

Emits ``artifacts/BENCH_schedule.json`` and CSV rows for
``benchmarks.run``; any batched-vs-loop divergence raises
``RuntimeError("... parity FAILED")``, which fails the section and the
CI bench job (same pattern as the campaign section).
"""
from __future__ import annotations

import json
import os
import time
from typing import List

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HEADER = "section,case,variant,ms_per_worker_epoch,speedup_vs_loop,identical"

#: workers sampled per partition point (timing every one of 256 loop
#: builds would dominate the bench job for no extra signal)
SAMPLE_WORKERS = 3
#: papers100M train mass and the per-worker cap keeping the loop
#: reference affordable
PAPER_TRAIN, MAX_TRAIN = 1_200_000, 2_400


def _time_pair(fn_a, fn_b, iters: int = 5):
    """Interleaved min-of-iters (ms, ms): A/B alternate call-for-call so
    scheduler/thermal drift hits both variants equally."""
    fn_a()
    fn_b()
    ta, tb = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn_a()
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        fn_b()
        tb.append(time.perf_counter() - t0)
    return min(ta) * 1e3, min(tb) * 1e3


def _batches_equal(flat, loop_batches) -> bool:
    if flat.num_batches != len(loop_batches):
        return False
    for br, bn in zip(loop_batches, flat.to_batches()):
        if not (np.array_equal(br.seeds, bn.seeds)
                and np.array_equal(br.input_nodes, bn.input_nodes)):
            return False
        for x, y in zip(br.blocks, bn.blocks):
            if not ((x.num_src, x.num_dst) == (y.num_src, y.num_dst)
                    and np.array_equal(x.edge_src, y.edge_src)
                    and np.array_equal(x.edge_dst, y.edge_dst)
                    and np.array_equal(x.edge_mask, y.edge_mask)):
                return False
    return True


def _epochs_equal(a, b) -> bool:
    return (a.m_max == b.m_max
            and np.array_equal(a.remote_ids, b.remote_ids)
            and np.array_equal(a.remote_freq, b.remote_freq)
            and np.array_equal(a.cache_ids, b.cache_ids)
            and np.array_equal(a.flat.input_nodes, b.flat.input_nodes)
            and np.array_equal(a.flat.seeds, b.flat.seeds))


def bench_schedule_build(workers=(64, 256),
                         dataset: str = "ogbn_products_sim",
                         batch_size: int = 100, fanouts=(25, 10),
                         n_hot: int = 4096, s0: int = 42):
    from repro.graph import load_dataset, partition_graph, KHopSampler
    from repro.core.schedule import _build_epoch

    from repro.graph.device_sampler import sample_epoch_batched_device

    g = load_dataset(dataset)
    rng = np.random.default_rng(s0)
    rows, recs = [], []
    for P_ in workers:
        pg = partition_graph(g, P_, "metis")
        sampler = KHopSampler(g, fanouts=list(fanouts),
                              batch_size=batch_size)
        n_train = min(PAPER_TRAIN // P_, MAX_TRAIN)
        t_samp = {"loop": 0.0, "batched": 0.0, "device": 0.0}
        t_build = {"loop": 0.0, "batched": 0.0, "device": 0.0}
        parity = dev_parity = True
        for w in range(SAMPLE_WORKERS):
            train = rng.choice(g.num_nodes, size=n_train, replace=False)
            batched_flat = sampler.sample_epoch_batched(s0, w, 0, train)
            parity &= _batches_equal(batched_flat,
                                     sampler.sample_epoch(s0, w, 0, train))
            dev_parity &= _batches_equal(
                batched_flat,
                sample_epoch_batched_device(sampler, s0, w, 0,
                                            train).to_batches())
            eb = _build_epoch(sampler, pg, w, s0, 0, train, n_hot,
                              compiler="batched")
            parity &= _epochs_equal(
                _build_epoch(sampler, pg, w, s0, 0, train, n_hot,
                             compiler="loop"), eb)
            dev_parity &= _epochs_equal(
                eb, _build_epoch(sampler, pg, w, s0, 0, train, n_hot,
                                 compiler="device"))
            tl, tb = _time_pair(
                lambda: sampler.sample_epoch(s0, w, 0, train),
                lambda: sampler.sample_epoch_batched(s0, w, 0, train))
            t_samp["loop"] += tl
            t_samp["batched"] += tb
            _, td = _time_pair(
                lambda: sampler.sample_epoch_batched(s0, w, 0, train),
                lambda: sample_epoch_batched_device(sampler, s0, w, 0,
                                                    train))
            t_samp["device"] += td
            tl, tb = _time_pair(
                lambda: _build_epoch(sampler, pg, w, s0, 0, train,
                                     n_hot, compiler="loop"),
                lambda: _build_epoch(sampler, pg, w, s0, 0, train,
                                     n_hot, compiler="batched"))
            t_build["loop"] += tl
            t_build["batched"] += tb
            _, td = _time_pair(
                lambda: _build_epoch(sampler, pg, w, s0, 0, train,
                                     n_hot, compiler="batched"),
                lambda: _build_epoch(sampler, pg, w, s0, 0, train,
                                     n_hot, compiler="device"))
            t_build["device"] += td
        rec = {"workers": P_, "dataset": dataset,
               "batch_size": batch_size, "fanouts": list(fanouts),
               "train_per_worker": n_train,
               "batches_per_worker": -(-n_train // batch_size),
               "parity": bool(parity),
               "device_parity": bool(dev_parity)}
        for sec, t in (("sampler", t_samp), ("build", t_build)):
            for variant in ("loop", "batched", "device"):
                ms = t[variant] / SAMPLE_WORKERS
                sp = t["loop"] / max(t[variant], 1e-9)
                ok = parity if variant != "device" else dev_parity
                rows.append(f"{sec},P{P_}_b{batch_size}_n{n_train},"
                            f"{variant},{ms:.2f},{sp:.2f}x,{ok}")
                rec[f"{sec}_{variant}_ms"] = round(ms, 3)
            rec[f"{sec}_speedup"] = round(
                t["loop"] / max(t["batched"], 1e-9), 2)
            rec[f"{sec}_device_speedup"] = round(
                t["loop"] / max(t["device"], 1e-9), 2)
        recs.append(rec)
    return rows, recs


def bench_overlapped_runner(dataset: str = "ogbn_products_sim",
                            batch_size: int = 100, fanouts=(25, 10),
                            n_hot: int = 4096, epochs: int = 3,
                            s0: int = 42):
    """Train-overlapped next-epoch builds through the device runner on
    ONE emulated device (the bench process must stay single-device):
    lazy device-resident schedules are rebuilt + collated by the
    background staging thread while the device trains, so the metric
    pair is the TOTAL staging wall vs the slice left EXPOSED after
    training completes. Lazy-vs-eager loss parity is asserted first."""
    import jax

    from repro.graph import load_dataset, partition_graph, KHopSampler
    from repro.core import build_schedule
    from repro.dist import DeviceRapidGNNRunner, DeviceView, make_mesh
    from repro.models import GNNConfig
    from repro.train import AdamW

    P_ = 1
    if jax.device_count() < P_:
        raise RuntimeError("no device for the overlap section")
    g = load_dataset(dataset)
    n_train = min(PAPER_TRAIN // 64, MAX_TRAIN)     # 64-worker seed mass
    rng = np.random.default_rng(s0)
    mask = np.zeros(g.num_nodes, bool)
    mask[rng.choice(g.num_nodes, size=n_train, replace=False)] = True
    g.train_mask = mask                 # bound the per-epoch seed stream
    pg = partition_graph(g, P_, "metis")
    sampler = KHopSampler(g, fanouts=list(fanouts),
                          batch_size=batch_size)
    dv = DeviceView.build(pg)
    mesh = make_mesh((P_,), ("data",))
    cfg = GNNConfig(kind="sage", in_dim=g.feat_dim, hidden_dim=32,
                    num_classes=g.num_classes, num_layers=len(fanouts))

    def runners():
        out = []
        for lazy in (False, True):
            schedules = [build_schedule(sampler, pg, worker=w, s0=s0,
                                        num_epochs=epochs, n_hot=n_hot,
                                        lazy=lazy)
                         for w in range(P_)]
            out.append(DeviceRapidGNNRunner(
                schedules, dv, cfg, AdamW(lr=3e-3), mesh, batch_size,
                g.labels, seed=s0))
        return out

    eager, lazy = runners()
    rep_e = eager.run()
    rep_l = lazy.run()
    if not np.array_equal(np.concatenate([r.losses for r in rep_e]),
                          np.concatenate([r.losses for r in rep_l])):
        return ["overlap,P1,lazy,nan,nan,False"], {
            "parity": False}
    staged = [r for r in rep_l if r.stage_s > 0.0]
    stage_ms = 1e3 * sum(r.stage_s for r in staged) / max(len(staged), 1)
    exposed_ms = 1e3 * sum(r.exposed_stage_s for r in staged) \
        / max(len(staged), 1)
    hidden_ratio = stage_ms / max(exposed_ms, 1e-6)
    train_ms = 1e3 * sum(r.wall_time_s for r in rep_l[1:]) \
        / max(len(rep_l) - 1, 1)
    case = f"P{P_}_b{batch_size}_n{n_train}"
    rows = [
        f"overlap,{case},staged_wall,{stage_ms:.2f},-,True",
        f"overlap,{case},exposed_wall,{exposed_ms:.2f},"
        f"{hidden_ratio:.1f}x,True",
    ]
    rec = {"workers": P_, "dataset": dataset, "batch_size": batch_size,
           "fanouts": list(fanouts), "train_nodes": n_train,
           "epochs": epochs, "parity": True,
           "train_ms_per_epoch": round(train_ms, 3),
           "stage_ms_per_epoch": round(stage_ms, 3),
           "exposed_ms_per_epoch": round(exposed_ms, 3),
           "hidden_ratio": round(hidden_ratio, 2),
           "trace_count": int(lazy.trace_count)}
    return rows, rec


def run() -> List[str]:
    rows = [HEADER]
    b_rows, recs = bench_schedule_build()
    rows += b_rows
    o_rows, o_rec = bench_overlapped_runner()
    rows += o_rows
    art = os.path.join(ROOT, "artifacts")
    os.makedirs(art, exist_ok=True)
    with open(os.path.join(art, "BENCH_schedule.json"), "w") as f:
        json.dump({"schedule_build": recs,
                   "overlapped_runner": o_rec}, f, indent=1)
    if not all(r["parity"] for r in recs):
        raise RuntimeError("batched-vs-loop schedule parity FAILED")
    if not all(r["device_parity"] for r in recs):
        raise RuntimeError("device-vs-batched schedule parity FAILED")
    if not o_rec["parity"]:
        raise RuntimeError("overlapped-runner loss parity FAILED")
    best = max(recs, key=lambda r: r["workers"])
    rows.append(f"summary,build_P{best['workers']},batched,"
                f"{best['build_batched_ms']},{best['build_speedup']}x,"
                f"{best['parity']}")
    rows.append(f"summary,overlap_P1,exposed_wall,"
                f"{o_rec['exposed_ms_per_epoch']},"
                f"{o_rec['hidden_ratio']}x,{o_rec['parity']}")
    return rows


def main() -> None:
    for row in run():
        print(row)


if __name__ == "__main__":
    main()
