"""Multi-device checks, run in a subprocess with 4 host devices
(tests/test_distributed.py sets XLA_FLAGS before python starts)."""
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp


def check_pull_features():
    from repro.dist import make_mesh, build_pull_plan, pull_features
    P_, n_per, d, m_max, k_max = 4, 16, 8, 12, 6
    mesh = make_mesh((4,), ("data",))
    rng = np.random.default_rng(0)
    table_global = rng.normal(size=(P_ * n_per, d)).astype(np.float32)
    owner = np.repeat(np.arange(P_), n_per)
    plans, want = [], []
    for w in range(P_):
        ids = rng.choice(P_ * n_per, size=m_max, replace=False)
        pos = np.arange(m_max)
        plans.append(build_pull_plan(ids.astype(np.int32),
                                     pos.astype(np.int32), owner, P_,
                                     k_max))
        exp = np.zeros((m_max, d), np.float32)
        exp[pos] = table_global[ids]
        want.append(exp)
    with mesh:
        out = pull_features(
            mesh, jnp.asarray(table_global.reshape(P_, n_per, d)),
            jnp.asarray(np.stack([p.send_ids for p in plans])),
            jnp.asarray(np.stack([p.send_pos for p in plans])),
            jnp.asarray(np.stack([p.send_mask for p in plans])),
            jnp.asarray((np.arange(P_) * n_per).astype(np.int32)), m_max)
    np.testing.assert_allclose(np.asarray(out), np.stack(want), rtol=1e-6)
    print("pull_features OK")


def check_pipelined_gnn_epoch():
    from repro.graph import load_dataset, partition_graph, KHopSampler
    from repro.core import build_schedule
    from repro.core.schedule import epoch_edge_maxima
    from repro.dist import (make_mesh, DeviceView, epoch_k_max,
                            collate_device_epoch, stack_caches,
                            make_pipelined_epoch)
    from repro.models import GNNConfig, init_params
    from repro.train import AdamW

    P_, n_hot, B = 4, 64, 16
    g = load_dataset("tiny")
    pg = partition_graph(g, P_, "greedy")
    sampler = KHopSampler(g, fanouts=[5, 5], batch_size=B)
    schedules = [build_schedule(sampler, pg, worker=w, s0=7,
                                num_epochs=1, n_hot=n_hot)
                 for w in range(P_)]
    dv = DeviceView.build(pg)
    es_list = [ws.epoch(0) for ws in schedules]
    m_max = max(es.m_max for es in es_list)
    edge_max = None
    for es in es_list:
        em = epoch_edge_maxima(es)
        edge_max = em if edge_max is None else [max(a, b) for a, b
                                                in zip(edge_max, em)]
    caches = [dv.remap_cache(es.cache_ids) for es in es_list]
    S = max(es.num_batches for es in es_list)
    k_max = epoch_k_max(es_list, caches, dv)
    batches = collate_device_epoch(es_list, caches, dv, g.labels, B,
                                   m_max, edge_max, k_max, S)
    cids, cfeats = stack_caches(caches, dv, n_hot)

    mesh = make_mesh((P_,), ("data",))
    cfg = GNNConfig(kind="sage", in_dim=g.feat_dim, hidden_dim=32,
                    num_classes=g.num_classes, num_layers=2)
    params = init_params(cfg, jax.random.key(0))
    opt = AdamW(lr=3e-3)
    epoch_fn = make_pipelined_epoch(cfg, opt, mesh, m_max)
    with mesh:
        _, _, losses, _ = epoch_fn(
            params, opt.init(params), jnp.asarray(dv.table),
            jnp.asarray(dv.offsets), jnp.asarray(cids),
            jnp.asarray(cfeats), jax.tree.map(jnp.asarray, batches))
        losses = np.asarray(losses)
    assert not np.isnan(losses).any()
    assert losses[-1] < losses[0]
    print("pipelined_gnn_epoch OK")


def _runner_setup(P_=4, B=16, epochs=3, n_hot=64, uneven=False):
    from repro.dist import make_mesh

    if uneven:
        from _uneven import build_uneven_case
        g, pg, schedules, dv = build_uneven_case(P_=P_, B=B, epochs=epochs,
                                                 n_hot=n_hot)
    else:
        from repro.graph import load_dataset, partition_graph, KHopSampler
        from repro.core import build_schedule
        from repro.dist import DeviceView

        g = load_dataset("tiny")
        pg = partition_graph(g, P_, "greedy")
        sampler = KHopSampler(g, fanouts=[5, 5], batch_size=B)
        schedules = [build_schedule(sampler, pg, worker=w, s0=7,
                                    num_epochs=epochs, n_hot=n_hot)
                     for w in range(P_)]
        dv = DeviceView.build(pg)
    mesh = make_mesh((P_,), ("data",))
    return g, pg, schedules, dv, mesh


def _make_runner(cls, g, schedules, dv, mesh, B, **kw):
    from repro.models import GNNConfig
    from repro.train import AdamW
    cfg = GNNConfig(kind="sage", in_dim=g.feat_dim, hidden_dim=32,
                    num_classes=g.num_classes, num_layers=2)
    return cls(schedules, dv, cfg, AdamW(lr=3e-3), mesh, B, g.labels,
               **kw)


def check_device_runner():
    """Multi-epoch double-buffer runner: one compilation, host-parity
    miss accounting, C_sec swap shrinking epoch-1 pull lanes, and
    rapid == baseline training curves (identical schedule)."""
    from repro.dist import (DeviceRapidGNNRunner, DeviceBaselineRunner,
                            assert_host_parity, collate_device_epoch,
                            epoch_k_max)

    B, epochs = 16, 3
    g, pg, schedules, dv, mesh = _runner_setup(B=B, epochs=epochs)
    runner = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B)
    reports = runner.run()
    assert len(reports) == epochs
    assert runner.trace_count == 1, \
        f"expected ONE XLA trace across {epochs} epochs, got " \
        f"{runner.trace_count}"
    # ... and one executable: epoch 0's inputs are placed like the
    # outputs later epochs are fed, so nothing compiles a second time
    assert runner._fn._cache_size() == 1
    losses = np.concatenate([r.losses for r in reports])
    assert not np.isnan(losses).any()
    assert reports[-1].losses[-1] < reports[0].losses[0]

    # per-(epoch, worker) residual-miss lanes == host-sim cache_misses
    assert_host_parity(schedules, pg, B, reports)
    # every valid row is assembled from exactly one source, and each
    # pulled row rode one lane
    for r in reports:
        assert r.local_rows + r.cache_rows + r.pulled_rows == r.valid_rows
        assert r.pulled_rows == r.total_miss_lanes
        assert min(r.local_rows, r.cache_rows, r.pulled_rows) > 0

    # double-buffer effect: epoch 1 collated against the SWAPPED-in
    # C_sec beats the no-swap counterfactual (stuck on epoch 0's C_s)
    caches0 = [dv.remap_cache(ws.epoch(0).cache_ids) for ws in schedules]
    es1 = [ws.epoch(1) for ws in schedules]
    k_stale = max(runner.k_max, epoch_k_max(es1, caches0, dv))
    stale = collate_device_epoch(es1, caches0, dv, g.labels, B,
                                 runner.m_max, runner.edge_max, k_stale,
                                 runner.num_steps)
    stale_lanes = int(stale["send_mask"].sum())
    assert reports[1].total_miss_lanes < stale_lanes, \
        f"swap did not shrink epoch-1 pull lanes: " \
        f"{reports[1].total_miss_lanes} vs stale {stale_lanes}"

    baseline = _make_runner(DeviceBaselineRunner, g, schedules, dv, mesh, B)
    rep_b = baseline.run()
    assert baseline.trace_count == 1
    # no cache: every remote id rides the lanes, so never fewer
    for r, b in zip(reports, rep_b):
        assert b.total_miss_lanes >= r.total_miss_lanes
        assert (b.local_rows, b.cache_rows, b.pulled_rows) == (
            r.local_rows, 0, r.cache_rows + r.pulled_rows)
    # identical schedule + exact feature paths => identical curves
    np.testing.assert_allclose(
        np.concatenate([r.losses for r in reports]),
        np.concatenate([r.losses for r in rep_b]), rtol=1e-4, atol=1e-5)
    print("device_runner OK")


def check_uneven_workers():
    """Workers with fewer/zero batches get fully masked empty steps and
    still match host-sim accounting (pre-fix: IndexError in
    collate_device_epoch / epoch_edge_maxima)."""
    from repro.dist import DeviceRapidGNNRunner, assert_host_parity

    B, epochs = 16, 2
    g, pg, schedules, dv, mesh = _runner_setup(B=B, epochs=epochs,
                                               uneven=True)
    assert schedules[2].epoch(0).num_batches == 0
    assert schedules[3].epoch(0).num_batches < \
        schedules[0].epoch(0).num_batches
    runner = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B)
    reports = runner.run()
    assert runner.trace_count == 1
    for r in reports:
        assert not np.isnan(r.losses).any()
        assert r.miss_lanes[2] == 0         # no batches -> no pulls
    assert_host_parity(schedules, pg, B, reports)
    print("uneven_workers OK")


def check_determinism():
    """Same seed => bit-identical staged pull plans, cache ids, and loss
    curves across two COMPLETELY FRESH device-runner builds (graph,
    schedules, DeviceView, mesh, runner all rebuilt) -- the device half
    of the end-to-end determinism property (host half:
    tests/test_eval_campaign.py)."""
    from repro.dist import DeviceRapidGNNRunner

    B, epochs = 16, 2
    runs = []
    for _ in range(2):
        g, pg, schedules, dv, mesh = _runner_setup(B=B, epochs=epochs)
        runner = _make_runner(DeviceRapidGNNRunner, g, schedules, dv,
                              mesh, B)
        staged0 = runner._stage(0)
        reports = runner.run()
        cids = [ws.epoch(e).cache_ids.copy()
                for ws in schedules for e in range(epochs)]
        runs.append((staged0, reports, cids))
    (sa, ra, ca), (sb, rb, cb) = runs
    for x, y in zip(ca, cb):
        np.testing.assert_array_equal(x, y)
    for key in ("send_ids", "send_pos", "send_mask", "input_nodes",
                "labels", "seed_mask"):
        np.testing.assert_array_equal(np.asarray(sa["batches"][key]),
                                      np.asarray(sb["batches"][key]),
                                      err_msg=key)
    np.testing.assert_array_equal(np.asarray(sa["cids"]),
                                  np.asarray(sb["cids"]))
    np.testing.assert_array_equal(
        np.concatenate([r.losses for r in ra]),
        np.concatenate([r.losses for r in rb]))
    np.testing.assert_array_equal(np.stack([r.miss_lanes for r in ra]),
                                  np.stack([r.miss_lanes for r in rb]))
    print("determinism OK")


def check_checkpoint_resume():
    """train/checkpoint.py round trip THROUGH the device runner: run
    epochs [0, 2), save params+opt state at the boundary, restore into a
    FRESH runner, run [2, 3) -- the stitched loss curve must equal an
    uninterrupted 3-epoch run's exactly (float32 survives the npz round
    trip losslessly; the epoch window shares the one compiled program)."""
    import tempfile

    from repro.dist import DeviceRapidGNNRunner
    from repro.models.gnn import init_params
    from repro.train import (save_checkpoint, load_checkpoint,
                             checkpoint_step)

    B, epochs = 16, 3
    g, pg, schedules, dv, mesh = _runner_setup(B=B, epochs=epochs)
    full = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B)
    rep_full = full.run()

    r1 = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B)
    rep_head = r1.run(stop_epoch=2)
    assert len(rep_head) == 2
    r2 = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B)
    with tempfile.TemporaryDirectory() as td:
        pdir = os.path.join(td, "params")
        odir = os.path.join(td, "opt")
        save_checkpoint(pdir, r1.params, step=2)
        save_checkpoint(odir, r1.opt_state, step=2)
        assert checkpoint_step(pdir) == 2
        like_p = init_params(r2.cfg, jax.random.key(r2.seed))
        params = load_checkpoint(pdir, like_p)
        opt_state = load_checkpoint(odir, r2.opt.init(like_p))
    rep_tail = r2.run(params=params, opt_state=opt_state, start_epoch=2)
    assert len(rep_tail) == 1 and rep_tail[0].epoch == 2
    resumed = np.concatenate([r.losses for r in rep_head + rep_tail])
    uninterrupted = np.concatenate([r.losses for r in rep_full])
    np.testing.assert_array_equal(
        resumed, uninterrupted,
        err_msg="resumed loss curve diverges from uninterrupted run")
    # miss accounting unaffected by the restart
    np.testing.assert_array_equal(
        np.stack([r.miss_lanes for r in rep_head + rep_tail]),
        np.stack([r.miss_lanes for r in rep_full]))
    print("checkpoint_resume OK")


def _assert_epoch_bit_equal(a, b):
    """EpochSchedule bit-equality over every payload + hot-set array."""
    assert a.m_max == b.m_max
    np.testing.assert_array_equal(a.cache_ids, b.cache_ids)
    np.testing.assert_array_equal(a.remote_ids, b.remote_ids)
    np.testing.assert_array_equal(a.remote_freq, b.remote_freq)
    fa, fb = a.flat, b.flat
    for f in ("seeds", "seed_starts", "input_nodes", "input_starts",
              "num_dst"):
        np.testing.assert_array_equal(getattr(fa, f), getattr(fb, f),
                                      err_msg=f)
    assert fa.num_layers == fb.num_layers
    for l in range(fa.num_layers):
        for f in ("edge_src", "edge_dst", "edge_mask", "edge_starts"):
            np.testing.assert_array_equal(getattr(fa, f)[l],
                                          getattr(fb, f)[l],
                                          err_msg=f"{f}[{l}]")


def check_overlapped_staging():
    """Train-overlapped next-epoch builds: a LAZY (device-resident)
    schedule under the DEVICE compiler is rebuilt by the runner's
    background staging thread while the previous epoch trains. The
    staged-ahead epochs must be bit-consistent with a cold eager
    (numpy-batched) build, the loss curve must match the eager runner
    exactly, and the one-compilation invariant must survive the thread
    (staging never traces)."""
    from repro.core import build_schedule
    from repro.dist import DeviceRapidGNNRunner, DeviceView, make_mesh
    from repro.graph import KHopSampler, load_dataset, partition_graph

    P_, B, epochs, n_hot = 4, 16, 3, 64
    g = load_dataset("tiny")
    pg = partition_graph(g, P_, "greedy")
    sampler = KHopSampler(g, fanouts=[5, 5], batch_size=B)
    eager = [build_schedule(sampler, pg, worker=w, s0=7,
                            num_epochs=epochs, n_hot=n_hot)
             for w in range(P_)]
    lazy = [build_schedule(sampler, pg, worker=w, s0=7,
                           num_epochs=epochs, n_hot=n_hot,
                           compiler="device", lazy=True)
            for w in range(P_)]
    for ws in lazy:
        assert all(e is None for e in ws.epochs)    # payloads dropped
        assert ws.spill_dir is None                 # and never spilled

    dv = DeviceView.build(pg)
    mesh = make_mesh((P_,), ("data",))
    run_e = _make_runner(DeviceRapidGNNRunner, g, eager, dv, mesh, B)
    rep_e = run_e.run()
    run_l = _make_runner(DeviceRapidGNNRunner, g, lazy, dv, mesh, B)
    rep_l = run_l.run()

    assert run_l.trace_count == 1, \
        f"background staging retriggered tracing: {run_l.trace_count}"
    # staged-ahead device-compiled epochs == cold numpy-batched builds
    for we, wl in zip(eager, lazy):
        for e in range(epochs):
            _assert_epoch_bit_equal(we.epoch(e), wl.epoch(e))
    np.testing.assert_array_equal(
        np.concatenate([r.losses for r in rep_e]),
        np.concatenate([r.losses for r in rep_l]),
        err_msg="lazy-schedule loss curve diverges from eager")
    np.testing.assert_array_equal(
        np.stack([r.miss_lanes for r in rep_e]),
        np.stack([r.miss_lanes for r in rep_l]))

    # overlap accounting: every staged epoch recorded a build wall, the
    # final epoch stages nothing, and the exposed slice never exceeds it
    assert run_l.stage_time_s > 0.0
    assert 0.0 <= run_l.exposed_stage_s <= run_l.stage_time_s + 1e-6
    assert all(r.stage_s > 0.0 for r in rep_l[:-1])
    assert rep_l[-1].stage_s == 0.0 and rep_l[-1].exposed_stage_s == 0.0
    print(f"overlap staging wall {run_l.stage_time_s * 1e3:.1f} ms, "
          f"exposed {run_l.exposed_stage_s * 1e3:.1f} ms")
    print("overlapped_staging OK")


def check_fault_recovery():
    """Device staging fault sites (DESIGN.md §10): every tolerated fault
    recovers to a BIT-equal loss curve, persistent faults surface the
    typed ``StagingError``, and a lost staged cache degrades exactly one
    epoch to uncached without touching any other epoch's accounting."""
    from repro.dist import DeviceRapidGNNRunner
    from repro.dist.runner import StagingError
    from repro.fault import active_plan, plan_from_profile

    B, epochs = 16, 3
    g, pg, schedules, dv, mesh = _runner_setup(B=B, epochs=epochs)
    clean = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B)
    rep_clean = clean.run()
    oracle = np.concatenate([r.losses for r in rep_clean])

    # stage-flaky: transient background-staging death -> one supervised
    # eager rebuild, zero degradation, bit-equal curve, ONE compilation
    r = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B)
    plan = plan_from_profile("stage-flaky", seed=3)
    with active_plan(plan):
        rep = r.run()
    assert plan.total_fires() >= 1, "stage-flaky plan never fired"
    assert r.stage_retries >= 1
    assert r.trace_count == 1
    assert sum(x.degraded for x in rep) == 0
    np.testing.assert_array_equal(
        np.concatenate([x.losses for x in rep]), oracle,
        err_msg="transient staging fault broke loss bit-equality")

    # stage-dead: staging fails on EVERY attempt -> typed StagingError
    # after the bounded retry budget, never a hang or raw thread error
    r = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B)
    try:
        with active_plan(plan_from_profile("stage-dead", seed=3)):
            r.run()
    except StagingError:
        pass
    else:
        raise AssertionError(
            "persistent staging failure must raise StagingError")

    # stage-deadline: staging thread hangs past the deadline -> overrun
    # counted, eager rebuild on the critical path, still bit-equal
    r = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B,
                     stage_deadline_s=0.05)
    plan = plan_from_profile("stage-deadline", seed=3)
    with active_plan(plan):
        rep = r.run()
    assert plan.fires("stage", "hang") >= 1
    assert r.deadline_overruns >= 1
    assert r.trace_count == 1
    np.testing.assert_array_equal(
        np.concatenate([x.losses for x in rep]), oracle,
        err_msg="deadline-overrun recovery broke loss bit-equality")

    # cache-loss: epoch 1's staged C_s dropped -> that epoch recollates
    # UNCACHED (graceful degrade, counted in the report); features come
    # from the same table either way so the curve stays bit-equal, and
    # the wider-k recollation may cost at most one extra trace
    r = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B)
    plan = plan_from_profile("cache-loss", seed=3)
    with active_plan(plan):
        rep = r.run()
    assert plan.fires("stage_cache", "drop") == 1
    assert r.degraded_epochs == 1
    assert rep[1].degraded == 1 and rep[1].degrade_reason == "cache_lost"
    assert sum(x.degraded for x in rep) == 1
    assert 1 <= r.trace_count <= 2
    # uncached epoch pulls strictly more lanes; others match clean
    assert rep[1].total_miss_lanes > rep_clean[1].total_miss_lanes
    for e in (0, 2):
        np.testing.assert_array_equal(rep[e].miss_lanes,
                                      rep_clean[e].miss_lanes)
    np.testing.assert_array_equal(
        np.concatenate([x.losses for x in rep]), oracle,
        err_msg="uncached degraded epoch broke loss bit-equality")
    print("fault_recovery OK")


def check_crash_resume():
    """Kill-and-resume bit parity: periodic atomic run-state checkpoints
    + an injected crash at an epoch boundary; resuming from LATEST must
    reproduce the uninterrupted curve bit-for-bit. Also drills a crash
    INSIDE the checkpoint commit: LATEST must keep naming the previous
    complete step."""
    import tempfile

    from repro.dist import DeviceRapidGNNRunner
    from repro.fault import InjectedCrash, active_plan, plan_from_profile
    from repro.models.gnn import init_params
    from repro.train import latest_step, load_run_state

    B, epochs = 16, 3
    g, pg, schedules, dv, mesh = _runner_setup(B=B, epochs=epochs)
    full = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B)
    rep_full = full.run()
    uninterrupted = np.concatenate([r.losses for r in rep_full])

    with tempfile.TemporaryDirectory() as td:
        r1 = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B,
                          checkpoint_dir=td, checkpoint_every=1)
        try:
            with active_plan(plan_from_profile("run-crash", seed=5)):
                r1.run()
        except InjectedCrash:
            pass
        else:
            raise AssertionError("run-crash plan must kill the run")
        step = latest_step(td)
        assert step == 2, f"expected LATEST=2 after epoch-2 crash, {step}"

        r2 = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B)
        like_p = init_params(r2.cfg, jax.random.key(r2.seed))
        like = {"params": like_p, "opt": r2.opt.init(like_p)}
        state, step = load_run_state(td, like)
        rep_tail = r2.run(params=state["params"],
                          opt_state=state["opt"], start_epoch=step)
        assert len(rep_tail) == epochs - step
        resumed = np.concatenate([r.losses for r in rep_tail])
        np.testing.assert_array_equal(
            resumed,
            np.concatenate([r.losses for r in rep_full[step:]]),
            err_msg="crash-resumed loss curve diverges bit-wise")

    # crash BETWEEN the arrays commit and the manifest commit of step 2:
    # LATEST stays on step 1, which must restore bit-intact
    with tempfile.TemporaryDirectory() as td:
        r3 = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B,
                          checkpoint_dir=td, checkpoint_every=1)
        try:
            with active_plan(plan_from_profile("ckpt-crash", seed=5)):
                r3.run()
        except InjectedCrash:
            pass
        else:
            raise AssertionError("ckpt-crash plan must kill the commit")
        assert latest_step(td) == 1
        like_p = init_params(r3.cfg, jax.random.key(r3.seed))
        like = {"params": like_p, "opt": r3.opt.init(like_p)}
        state, step = load_run_state(td, like)
        assert step == 1
    print("crash_resume OK")


def check_topology_two_tier():
    """Hierarchical 2-host x 4-device topology end to end (needs 8
    emulated devices): the two-tier runner must (a) keep trace_count 1,
    (b) produce loss curves BIT-equal to the flat-mesh runner on the
    identical schedule (the two-tier exchange + tuple-axis pmean are
    the same math on the same values), (c) split every epoch's miss
    lanes so intra + inter == the flat lane counts elementwise with
    both tiers non-degenerate, and (d) pass host parity."""
    from repro.dist import (DeviceRapidGNNRunner, Topology,
                            assert_host_parity)

    P_, B, epochs = 8, 16, 3
    if jax.device_count() < P_:
        # graceful under the default 4-device harness ("all" mode); the
        # dedicated pytest lane runs this check with 8 devices and
        # asserts the OK line, so a skip can never mask a failure there
        print(f"topology_two_tier SKIPPED (needs {P_} devices, "
              f"have {jax.device_count()})")
        return
    g, pg, schedules, dv, mesh = _runner_setup(P_=P_, B=B, epochs=epochs)
    flat = _make_runner(DeviceRapidGNNRunner, g, schedules, dv, mesh, B)
    rep_f = flat.run()
    assert flat.trace_count == 1

    topo = Topology.hierarchical(2, 4)
    hier = _make_runner(DeviceRapidGNNRunner, g, schedules, dv,
                        topo.make_mesh(), B, topology=topo)
    rep_h = hier.run()
    assert hier.trace_count == 1, \
        f"hierarchical runner traced {hier.trace_count}x"

    # bit-equal curves: same schedule, same values, same full-group
    # collectives -- only the wires differ
    np.testing.assert_array_equal(
        np.concatenate([r.losses for r in rep_f]),
        np.concatenate([r.losses for r in rep_h]),
        err_msg="two-tier loss curve diverges from flat mesh")

    intra_total = inter_total = 0
    for rf, rh in zip(rep_f, rep_h):
        np.testing.assert_array_equal(
            rh.intra_lanes + rh.inter_lanes, rf.miss_lanes,
            err_msg=f"epoch {rf.epoch}: tier split does not sum to the "
                    f"flat lane counts")
        np.testing.assert_array_equal(rh.miss_lanes, rf.miss_lanes)
        intra_total += int(rh.intra_lanes.sum())
        inter_total += int(rh.inter_lanes.sum())
    assert intra_total > 0 and inter_total > 0, \
        f"degenerate tier split: intra={intra_total} inter={inter_total}"
    # per-tier wire rows decompose the padded total
    for rh in rep_h:
        assert rh.intra_wire_rows + rh.inter_wire_rows == rh.wire_rows

    assert_host_parity(schedules, pg, B, rep_h)
    print(f"topology intra_lanes={intra_total} inter_lanes={inter_total}")
    print("topology_two_tier OK")


def check_serve_gnn():
    """Serving lane on 4 emulated devices: one service per worker over
    the SAME partitioned graph, each serving the same request streams.
    Per-worker responses must be bit-equal to that worker's own oracle
    through the tier ladder (uncached -> fresh), and a flaky-pull plan
    must recover bit-equal -- worker-keyed Philox streams mean workers
    sample DIFFERENT subgraphs, so cross-worker equality is not
    expected and not asserted."""
    from repro.fault import active_plan, plan_from_profile
    from repro.graph import load_dataset, partition_graph, KHopSampler
    from repro.graph.sampler import rng_from
    from repro.models import GNNConfig, init_params
    from repro.serve.gnn import GNNInferenceService

    assert jax.device_count() == 4
    g = load_dataset("tiny", seed=0)
    pg = partition_graph(g, 4, "greedy")
    sampler = KHopSampler(g, fanouts=[3, 3], batch_size=4)
    cfg = GNNConfig(kind="sage", in_dim=g.feat_dim, hidden_dim=16,
                    num_classes=g.num_classes, num_layers=2)
    params = init_params(cfg, jax.random.key(0))
    rng = rng_from(13, 0xD157)
    streams = [rng.integers(0, g.num_nodes, size=int(k))
               for k in rng.integers(1, 5, size=6)]

    def serve_round(svc, batch):
        pendings = [svc.submit(s) for s in batch]
        served = 0
        while served < len(pendings):
            served += svc.step(timeout=0.1)
        return [p.result(timeout=5.0) for p in pendings]

    for w in range(4):
        svc = GNNInferenceService(pg, sampler, cfg, params, s0=13,
                                  worker=w, n_hot=32,
                                  default_timeout_s=30.0)
        try:
            for r in serve_round(svc, streams[:3]):      # uncached
                np.testing.assert_array_equal(
                    r.logits, svc.oracle(streams[r.rid], r.rid))
            svc.warmer.warm_now()
            plan = plan_from_profile("serve-pull-flaky", seed=w)
            with active_plan(plan):                      # fresh + faults
                for r in serve_round(svc, streams[3:]):
                    np.testing.assert_array_equal(
                        r.logits, svc.oracle(streams[r.rid], r.rid))
            assert svc.trace_count == 1, svc.trace_count
        finally:
            svc.close()
    print("serve_gnn OK")


def check_moe_expert_parallel():
    from repro.dist import make_mesh
    from repro.models.transformer.common import ArchConfig
    from repro.models.transformer.moe import init_moe_params, moe_apply
    cfg = ArchConfig(name="t", d_model=32, moe=True, num_experts=4,
                     top_k=2, moe_d_ff=16, capacity_factor=4.0,
                     dtype="float32")
    params = init_moe_params(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 8, 32))
    ref = moe_apply(params, x, cfg, mesh=None)
    mesh = make_mesh((2, 2), ("data", "model"))
    with mesh:
        out = moe_apply(params, x, cfg, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    print("moe_expert_parallel OK")


def check_sharded_decode_attention():
    from repro.dist import make_mesh
    from repro.serve.attention import sharded_decode_attention
    from repro.models.transformer.attention import decode_attention
    rng = np.random.default_rng(3)
    B, S, H, kvH, dh = 4, 64, 8, 2, 16
    q = jnp.asarray(rng.normal(size=(B, 1, H, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, kvH, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, kvH, dh)).astype(np.float32))
    ln = jnp.asarray([10, 33, 64, 50], jnp.int32)
    ref = decode_attention(q, k, v, ln)
    mesh = make_mesh((2, 2), ("data", "model"))
    with mesh:
        out = sharded_decode_attention(mesh, q, k, v, ln)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    print("sharded_decode_attention OK")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    checks = {"pull": check_pull_features,
              "epoch": check_pipelined_gnn_epoch,
              "runner": check_device_runner,
              "uneven": check_uneven_workers,
              "determinism": check_determinism,
              "checkpoint": check_checkpoint_resume,
              "overlap": check_overlapped_staging,
              "fault": check_fault_recovery,
              "crashresume": check_crash_resume,
              "topology": check_topology_two_tier,
              "serve": check_serve_gnn,
              "moe": check_moe_expert_parallel,
              "decode": check_sharded_decode_attention}
    if which == "all":
        for fn in checks.values():
            fn()
    else:
        checks[which]()
    print("ALL DIST CHECKS OK")
