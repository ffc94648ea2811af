"""Multi-epoch device runners: Alg. 1's epoch loop on the SPMD mesh.

``DeviceRapidGNNRunner`` drives N epochs through ``make_pipelined_epoch``
with the paper's double-buffer protocol (DESIGN.md §6.5): while epoch e
trains on device against C_s, a BACKGROUND staging thread builds epoch
e+1 -- the next epoch's schedule itself when the ``WorkerSchedule`` is
lazy/device-resident (the train-overlapped next-epoch build, DESIGN.md
§2.2), then its C_sec (``remap_cache`` + ``stack_caches``) and pull
plans through the VECTORIZED ``collate_device_epoch`` (DESIGN.md §6.6;
whole-epoch numpy, no per-(step, worker) loop, so staging keeps up with
the device at 256+ workers). The main thread blocks only on the device
epoch; whatever staging wall is left AFTER training completes is the
EXPOSED staging wall (``exposed_stage_s``, near zero when training
dominates), and the staged buffers swap in at the epoch boundary
(Alg. 1 l.18) -- the device analogue of
``core.prefetch.SecondaryCacheBuilder``.

Every epoch is collated to GLOBAL static bounds: ``WorkerSchedule.
pad_bounds()`` merged across workers, one ``k_max`` maxed over every
epoch's caches, and ``num_steps`` = the max worker batch count (short
workers get fully masked empty steps). All N epochs therefore run ONE
compiled program -- ``trace_count`` stays 1.

``DeviceBaselineRunner`` is the same loop over ``make_ondemand_epoch``
with EMPTY caches: no C_s, no software pipeline, every remote id pulled
on the critical path -- the DGL-style on-demand path, so device
rapid-vs-baseline step time is measurable on the same mesh.

The epoch program's configuration comes from the schedule as well: where
every batch of every (worker, epoch) has exactly ``num_dst * F`` edges in
a layer, for one ``F`` per layer, the runner hands the program those
fan-outs (``epoch_fanouts``), so SAGE/GCN's mean takes the
scatter-free reshape path (``models/gnn.py``) and the stage leaves the
unread ``edge_dst`` out; an irregular or empty schedule keeps the
configuration as given, and its ``segment_sum`` mean.

``assert_host_parity`` checks the device runner's per-epoch residual-miss
lane counts against the host-sim ``RapidGNNRunner``'s ``cache_misses``
batch-exact on the identical schedule (DESIGN.md §7).

Host spans: the epoch loop and the staging thread mark their phases
with ``jax.profiler.TraceAnnotation`` spans named ``rapidgnn.*`` (main
thread: ``epoch.dispatch``, ``epoch.readback``, ``stage.wait``,
``epoch.report``; every ``_stage`` call: ``stage`` with its children
``stage.schedule``, ``stage.caches``, ``stage.collate``,
``stage.to_device`` and ``stage.stack_caches``). They land on the
profiler's clock beside the device ops when a trace is running, and
cost a few microseconds each when none is.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.schedule import (EpochSchedule, WorkerSchedule,
                                 merge_pad_bounds)
from repro.fault.inject import TransientFault, fault_point
from repro.models.gnn import GNNConfig, init_params, scatter_free_layers
from repro.dist.gnn_step import (DeviceCache, DeviceView,
                                 collate_device_epoch, empty_caches,
                                 epoch_k_max, epoch_k_max_split,
                                 make_ondemand_epoch,
                                 make_pipelined_epoch, stack_caches)
from repro.dist.topology import Topology
from repro.train.checkpoint import save_run_state


def _span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span ``rapidgnn.<name>`` on the profiler's clock."""
    return jax.profiler.TraceAnnotation("rapidgnn." + name)


def epoch_fanouts(es: EpochSchedule) -> Optional[List[int]]:
    """Per layer, the fan-out ``F`` with exactly ``num_dst * F`` edges in
    every batch of the epoch (0 where no batch has a dst row), or None
    where some layer has no such ``F``."""
    flat = es.flat
    out = []
    for l in range(flat.num_layers):
        nd = flat.num_dst[l]
        ne = np.diff(flat.edge_starts[l])
        live = np.flatnonzero(nd > 0)
        fo = int(ne[live[0]] // nd[live[0]]) if live.size else 0
        if (live.size and fo < 1) or not np.array_equal(ne, nd * fo):
            return None
        out.append(fo)
    return out


def _merge_fanouts(a: Optional[List[int]], b: Optional[List[int]]
                  ) -> Optional[List[int]]:
    """Two ``epoch_fanouts`` results -> the one fan-out per layer they
    agree on (0 where neither saw a dst row), or None."""
    if a is None or b is None or len(a) != len(b):
        return None
    if any(x and y and x != y for x, y in zip(a, b)):
        return None
    return [x or y for x, y in zip(a, b)]


def _program_config(cfg: GNNConfig, fanouts: Optional[List[int]]
                     ) -> GNNConfig:
    """The epoch program's configuration: ``cfg`` with the fan-outs a
    regular schedule showed (``_merge_fanouts`` over every (worker,
    epoch)) where it has none, ``cfg`` itself where the schedule is
    irregular or a layer saw no dst row. A configuration that states
    fan-outs must state the schedule's."""
    if fanouts is None or not all(fanouts):
        return cfg
    fanouts = tuple(fanouts)
    if cfg.fanouts is None:
        return dataclasses.replace(cfg, fanouts=fanouts)
    if tuple(cfg.fanouts[:len(fanouts)]) != fanouts:
        raise ValueError(f"cfg.fanouts {tuple(cfg.fanouts)} disagree with "
                         f"the schedule's fan-outs {fanouts}")
    return cfg


class StagingError(RuntimeError):
    """Epoch staging failed persistently (retry budget exhausted or a
    non-transient error); the original failure rides as ``__cause__``."""


@dataclasses.dataclass
class DeviceEpochReport:
    """Per-epoch accounting from one device runner epoch."""
    epoch: int
    steps: int                  # scan length (global, padded)
    miss_lanes: np.ndarray      # (P,) residual-miss pull lanes per worker
    wire_rows: int              # padded rows the a2a actually moves
    losses: np.ndarray          # (S,) pmean'd per step
    accs: np.ndarray            # (S,)
    wall_time_s: float
    #: host wall of staging the NEXT epoch (schedule build if lazy +
    #: collation + C_sec), overlapped with this epoch's training ...
    stage_s: float = 0.0
    #: ... and the slice of it left exposed after training finished
    #: (what a synchronous stage would add to the critical path is
    #: ``stage_s``; the overlap hides ``stage_s - exposed_stage_s``).
    exposed_stage_s: float = 0.0
    #: 1 when this epoch ran in a degraded mode (e.g. staged cache lost
    #: -> uncached baseline-style epoch), with the reason alongside
    degraded: int = 0
    degrade_reason: str = ""
    #: staging retries spent producing THIS epoch's buffers
    stage_retries: int = 0
    #: two-tier split of ``miss_lanes`` on a hierarchical topology:
    #: same-host lanes (cheap ici wire) vs cross-host lanes (DCN wire);
    #: ``intra + inter == miss_lanes`` elementwise (flat: intra =
    #: miss_lanes, inter = 0 -- every peer counts as same-host)
    intra_lanes: Optional[np.ndarray] = None    # (P,)
    inter_lanes: Optional[np.ndarray] = None    # (P,)
    #: padded-row split of ``wire_rows`` by tier (flat: all intra)
    intra_wire_rows: int = 0
    inter_wire_rows: int = 0
    #: rows of the collated ``input_nodes`` that hold a real input node,
    #: and all of its rows (S * P * m_max): the share of the assemble
    #: select pass's rows that assemble a real row
    valid_rows: int = 0
    padded_rows: int = 0
    #: ``valid_rows`` by the source the assembly copies them from: the
    #: worker's own shard, its hot set C_s, or the pull (one lane each)
    local_rows: int = 0
    cache_rows: int = 0
    pulled_rows: int = 0
    #: layers whose aggregation the epoch program computes as a gather
    #: and a reduce over the fan-out axis, with no scatter in the forward
    scatter_free_layers: int = 0

    @property
    def total_miss_lanes(self) -> int:
        return int(self.miss_lanes.sum())

    def payload_bytes(self, feat_dim: int, itemsize: int = 4) -> int:
        """True feature bytes requested (== host-sim remote_bytes)."""
        return self.total_miss_lanes * feat_dim * itemsize

    def request_bytes(self, itemsize: int = 4) -> int:
        """Id bytes shipped on the a2a REQUEST legs (the padded int32 id
        matrices of every pull this epoch) -- the previously
        unaccounted half of the wire (DESIGN.md §6.7)."""
        return int(self.wire_rows) * itemsize

    def to_dict(self) -> Dict[str, Any]:
        """JSON-ready export: ``repro.eval.cells.device_cell_result``
        stores these per-epoch records on the campaign ``CellResult``
        (the ``epoch_metrics`` field of ``BENCH_paper.json``)."""
        intra = (self.miss_lanes if self.intra_lanes is None
                 else self.intra_lanes)
        inter = (np.zeros_like(self.miss_lanes)
                 if self.inter_lanes is None else self.inter_lanes)
        return {"epoch": self.epoch, "steps": self.steps,
                "miss_lanes": [int(x) for x in self.miss_lanes],
                "wire_rows": int(self.wire_rows),
                "intra_lanes": [int(x) for x in intra],
                "inter_lanes": [int(x) for x in inter],
                "intra_wire_rows": int(self.intra_wire_rows),
                "inter_wire_rows": int(self.inter_wire_rows),
                "valid_rows": int(self.valid_rows),
                "padded_rows": int(self.padded_rows),
                "local_rows": int(self.local_rows),
                "cache_rows": int(self.cache_rows),
                "pulled_rows": int(self.pulled_rows),
                "scatter_free_layers": int(self.scatter_free_layers),
                "losses": [float(x) for x in self.losses],
                "accs": [float(x) for x in self.accs],
                "wall_time_s": float(self.wall_time_s),
                "stage_s": float(self.stage_s),
                "exposed_stage_s": float(self.exposed_stage_s),
                "degraded": int(self.degraded),
                "degrade_reason": self.degrade_reason,
                "stage_retries": int(self.stage_retries)}


class _DeviceRunnerBase:
    """Shared epoch-loop machinery; subclasses pick program + caches."""

    uses_cache = True
    pulls_beyond_steps = 0      # a2a pulls per epoch in excess of S steps

    def __init__(self, schedules: Sequence[WorkerSchedule], dv: DeviceView,
                 cfg: GNNConfig, opt, mesh, batch_size: int,
                 labels: np.ndarray, seed: int = 0,
                 assemble_backend: str = "auto", *,
                 stage_deadline_s: Optional[float] = None,
                 max_stage_retries: int = 2,
                 stage_retry_base_s: float = 0.01,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 1,
                 topology: Optional[Topology] = None):
        self.assemble_backend = assemble_backend
        # supervision knobs (DESIGN.md §10): a deadline on the overlapped
        # stage future, a bounded retry budget for transient stage
        # failures, and optional periodic atomic run-state checkpoints
        self.stage_deadline_s = stage_deadline_s
        self.max_stage_retries = max_stage_retries
        self.stage_retry_base_s = stage_retry_base_s
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        self.stage_retries = 0
        self.degraded_epochs = 0
        self.deadline_overruns = 0
        self.recovery_wall_s = 0.0
        self.schedules = list(schedules)
        self.P = len(self.schedules)
        if mesh.devices.size != self.P:
            raise ValueError(f"{self.P} schedules for a "
                             f"{mesh.devices.size}-device mesh")
        self.topo = topology if topology is not None \
            else Topology.flat(self.P)
        if self.topo.num_workers != self.P:
            raise ValueError(
                f"topology {self.topo.describe()} describes "
                f"{self.topo.num_workers} workers, runner has {self.P}")
        if self.topo.is_hierarchical and tuple(mesh.axis_names) != \
                ("dcn", "data"):
            raise ValueError(
                f"hierarchical topology needs a ('dcn', 'data') mesh, "
                f"got axes {tuple(mesh.axis_names)}")
        n_epochs = {len(ws.epochs) for ws in self.schedules}
        if len(n_epochs) != 1:
            raise ValueError(f"workers disagree on epoch count: {n_epochs}")
        self.num_epochs = n_epochs.pop()
        self.dv = dv
        self.cfg = cfg
        self.opt = opt
        self.mesh = mesh
        self.batch_size = batch_size
        self.labels = labels
        self.seed = seed

        # global static bounds: pad_bounds merged across workers, steps /
        # lane bound maxed over every (worker, epoch) -- the
        # one-compilation key (per-epoch bounds would retrigger tracing).
        # One pass loads each (worker, epoch) once (spilled schedules
        # load here and once more when the epoch is staged). Only the
        # bound SCALARS are retained: cache feature rows are rebuilt per
        # staged epoch so at most two epochs' C_s/C_sec are live at once
        # (the paper's 2*n_hot*d memory bound, not E*n_hot*d).
        self.m_max, self.edge_max = merge_pad_bounds(self.schedules)
        self.n_hot = max(1, max(ws.n_hot for ws in self.schedules))
        # hierarchical: k_max bounds the INTRA tier, k_max_inter the
        # cross-host DCN tier; flat: k_max is the single-tier bound and
        # k_max_inter stays 1 (unused)
        self.num_steps, self.k_max, self.k_max_inter = 0, 1, 1
        fanouts: Optional[List[int]] = [0] * cfg.num_layers
        for e in range(self.num_epochs):
            es_list = [ws.epoch(e) for ws in self.schedules]
            for es in es_list:
                if es.num_batches:
                    fanouts = _merge_fanouts(fanouts, epoch_fanouts(es))
            # ids-only cache view: the lane bound never touches feats
            ids_only = self._caches_for(es_list, ids_only=True)
            self.num_steps = max(self.num_steps,
                                 max(es.num_batches for es in es_list))
            if self.topo.is_hierarchical:
                k_i, k_x = epoch_k_max_split(es_list, ids_only, self.dv,
                                             self.topo)
                self.k_max = max(self.k_max, k_i)
                self.k_max_inter = max(self.k_max_inter, k_x)
            else:
                self.k_max = max(self.k_max,
                                 epoch_k_max(es_list, ids_only, self.dv))
        self.cfg = _program_config(cfg, fanouts)
        self.scatter_free_layers = scatter_free_layers(self.cfg,
                                                       self.edge_max)
        # a program that reads every layer's dst rows off the regular
        # layout never reads edge_dst: the stage neither builds nor
        # ships it
        self.with_edge_dst = self.scatter_free_layers < cfg.num_layers

        self.trace_count = 0
        self._fn = jax.jit(self._counted(self._make_epoch_fn()))
        self.params: Optional[Any] = None
        self.opt_state: Optional[Any] = None
        self.stage_time_s = 0.0     # host-side staging wall (cumulative)
        self.exposed_stage_s = 0.0  # slice of it NOT hidden by training

    def _caches_for(self, es_list, ids_only: bool = False
                    ) -> List[DeviceCache]:
        d = self.dv.table.shape[-1]
        if not self.uses_cache:
            return empty_caches(self.P, d)
        if ids_only:
            return [DeviceCache(ids=np.sort(self.dv.g2d[es.cache_ids]),
                                feats=np.zeros((0, d), np.float32))
                    for es in es_list]
        return [self.dv.remap_cache(es.cache_ids) for es in es_list]

    def _counted(self, fn):
        # keeps ``fn``'s name, so the program is ``jit_epoch_fn`` in
        # HLO dumps and profiles
        @functools.wraps(fn)
        def wrapped(*args):
            self.trace_count += 1   # fires once per XLA trace, not per call
            return fn(*args)
        return wrapped

    # -- per-epoch staging (the host half of the double buffer) ---------

    def _stage(self, e: int, attempt: int = 0) -> Dict[str, Any]:
        fault_point("stage", attempt=attempt, epoch=e)
        with _span("stage"):
            t0 = time.perf_counter()
            out = self._stage_inner(e)
            dt = time.perf_counter() - t0
        self.stage_time_s += dt
        out["stage_s"] = dt
        return out

    def _collate_and_account(self, es_list, caches, k_max: int,
                             k_max_inter: int) -> Dict[str, Any]:
        """Collate one epoch and derive its per-tier lane/wire
        accounting: true per-requesting-worker lane counts from the
        masks, padded wire rows from the static shapes. On a flat
        topology the whole exchange counts as the intra tier (every
        peer is same-host); hierarchical splits by tier, and the tiers
        sum to exactly what the flat plan would count -- the byte-sum
        identity ``verify`` pins (DESIGN.md §6.7)."""
        counted: Dict[str, int] = {}
        with _span("stage.collate"):
            batches = collate_device_epoch(
                es_list, caches, self.dv, self.labels, self.batch_size,
                self.m_max, self.edge_max, k_max, self.num_steps,
                topology=self.topo, k_max_inter=k_max_inter,
                with_edge_dst=self.with_edge_dst, stats=counted)
        rows = batches["input_nodes"]
        # padded rows the program's all_to_alls move: the pipelined epoch
        # issues one extra pull (the pre-scan pulled0; its final wrap pull
        # is part of the S in-scan pulls), the on-demand epoch exactly S
        pulls = self.num_steps + self.pulls_beyond_steps
        if self.topo.is_hierarchical:
            intra = batches["intra_mask"].sum(axis=(0, 2, 3)) \
                .astype(np.int64)
            inter = batches["inter_mask"].sum(axis=(0, 2, 3)) \
                .astype(np.int64)
            _, P_, D, k_i = batches["intra_mask"].shape
            k_x = batches["inter_mask"].shape[-1]
            wire_intra = pulls * P_ * D * k_i
            wire_inter = pulls * P_ * P_ * k_x
        else:
            intra = batches["send_mask"].sum(axis=(0, 2, 3)) \
                .astype(np.int64)
            inter = np.zeros_like(intra)
            _, P_, _, k = batches["send_mask"].shape
            wire_intra = pulls * P_ * P_ * k
            wire_inter = 0
        with _span("stage.to_device"):
            dev = jax.tree.map(jnp.asarray, batches)
        # rows by assembly source: the ownership test (the collation's
        # ``local_rows``), one pull lane per pulled row, the hot set the
        # rest
        valid, local = counted["valid_rows"], counted["local_rows"]
        pulled = int((intra + inter).sum())
        return {
            "batches": dev,
            "valid_rows": valid,
            "padded_rows": int(rows.size),
            "local_rows": local,
            "cache_rows": valid - local - pulled,
            "pulled_rows": pulled,
            "lanes": intra + inter,
            "intra_lanes": intra,
            "inter_lanes": inter,
            "wire_rows": wire_intra + wire_inter,
            "intra_wire_rows": wire_intra,
            "inter_wire_rows": wire_inter,
        }

    def _stage_inner(self, e: int) -> Dict[str, Any]:
        with _span("stage.schedule"):
            es_list = [ws.epoch(e) for ws in self.schedules]
        with _span("stage.caches"):
            caches = self._caches_for(es_list)
        staged = self._collate_and_account(es_list, caches, self.k_max,
                                           self.k_max_inter)
        if self.uses_cache:
            # the staged C_s can be LOST (fault plane): the epoch then
            # degrades to an uncached rebuild instead of failing the run
            if fault_point("stage_cache", epoch=e):
                staged["cache_lost"] = True
            else:
                with _span("stage.stack_caches"):
                    cids, cfeats = stack_caches(caches, self.dv,
                                                self.n_hot)
                    staged["cids"] = jnp.asarray(cids)
                    staged["cfeats"] = jnp.asarray(cfeats)
        return staged

    def _stage_supervised(self, e: int, start_attempt: int = 0
                          ) -> Tuple[Dict[str, Any], int]:
        """Stage epoch ``e`` with a bounded transient-retry budget.

        Returns ``(staged, retries_used)``. Staging is deterministic
        given ``(schedule, e)``, so a retried or eagerly-rebuilt stage is
        bit-identical to the one the background thread would have built.
        """
        err: Optional[BaseException] = None
        for i in range(self.max_stage_retries + 1):
            if i:
                time.sleep(self.stage_retry_base_s * 2 ** (i - 1))
                self.stage_retries += 1
            try:
                return self._stage(e, attempt=start_attempt + i), i
            except TransientFault as exc:
                err = exc
        raise StagingError(f"staging epoch {e} failed after "
                           f"{self.max_stage_retries} retries") from err

    def _await_stage(self, fut, e: int) -> Tuple[Dict[str, Any], int]:
        """Collect the overlapped stage of epoch ``e``; on deadline
        overrun or a dead staging thread, rebuild EAGERLY on the critical
        path (counted in ``recovery_wall_s``) -- graceful degradation,
        never a different schedule."""
        try:
            return fut.result(timeout=self.stage_deadline_s), 0
        except FuturesTimeout:
            self.deadline_overruns += 1
        except Exception:
            pass    # dead stage thread: the eager rebuild retries fresh
        t0 = time.perf_counter()
        # start_attempt=1: the background attempt 0 already fired, so a
        # transient fault keyed to attempt 0 clears here deterministically
        staged, retries = self._stage_supervised(e, start_attempt=1)
        self.recovery_wall_s += time.perf_counter() - t0
        self.stage_retries += 1
        return staged, retries + 1

    def _degrade_uncached(self, e: int) -> Dict[str, Any]:
        """Rebuild epoch ``e`` with EMPTY caches after the staged C_s was
        lost: every remote id goes through the pull pipeline for this one
        epoch (baseline-style, counted as degraded). The lane bound may
        grow past the cached ``k_max``, which costs at most ONE extra XLA
        trace for the degraded epoch; feature values are unchanged, so
        the loss curve still matches the clean run bit-for-bit."""
        es_list = [ws.epoch(e) for ws in self.schedules]
        d = self.dv.table.shape[-1]
        caches = empty_caches(self.P, d)
        if self.topo.is_hierarchical:
            k_i, k_x = epoch_k_max_split(es_list, caches, self.dv,
                                         self.topo)
            k = max(self.k_max, k_i)
            kx = max(self.k_max_inter, k_x)
        else:
            k = max(self.k_max, epoch_k_max(es_list, caches, self.dv))
            kx = self.k_max_inter
        staged = self._collate_and_account(es_list, caches, k, kx)
        cids, cfeats = stack_caches(caches, self.dv, self.n_hot)
        staged["cids"] = jnp.asarray(cids)
        staged["cfeats"] = jnp.asarray(cfeats)
        staged["stage_s"] = 0.0
        return staged

    # -- the epoch loop --------------------------------------------------

    def run(self, params=None, opt_state=None, start_epoch: int = 0,
            stop_epoch: Optional[int] = None) -> List[DeviceEpochReport]:
        """Drive epochs ``[start_epoch, stop_epoch)`` (defaults: all).

        The window exists for checkpoint resume: run ``[0, k)``, save
        ``self.params``/``self.opt_state``, then a FRESH runner restored
        from the checkpoint runs ``[k, N)`` -- static bounds are global,
        so both windows share one compiled program and the concatenated
        loss curve matches an uninterrupted run bit-for-bit."""
        if stop_epoch is None:
            stop_epoch = self.num_epochs
        if not 0 <= start_epoch < stop_epoch <= self.num_epochs:
            raise ValueError(f"bad epoch window [{start_epoch}, "
                             f"{stop_epoch}) for {self.num_epochs} epochs")
        if params is None:
            params = init_params(self.cfg, jax.random.key(self.seed))
        if opt_state is None:
            opt_state = self.opt.init(params)
        # place the carried state where the epoch program returns it
        # (replicated over the mesh), so epoch 0 and every later epoch
        # present the same input shardings: one trace, one compile
        replicated = NamedSharding(self.mesh, PartitionSpec())
        params, opt_state = jax.device_put((params, opt_state), replicated)
        table = jnp.asarray(self.dv.table)
        offsets = jnp.asarray(self.dv.offsets)
        reports: List[DeviceEpochReport] = []
        # bootstrap C_s (Alg. 1 l.4), supervised: transient stage faults
        # retry in place instead of killing the run
        staged, pending_retries = self._stage_supervised(start_epoch)
        with self.mesh, ThreadPoolExecutor(max_workers=1) as pool:
            for e in range(start_epoch, stop_epoch):
                t0 = time.perf_counter()
                degraded, reason = 0, ""
                if self.uses_cache and staged.get("cache_lost"):
                    # staged cache lost: run e UNCACHED (one degraded
                    # epoch, Alg. 1 degenerating to the baseline path)
                    t_rec = time.perf_counter()
                    staged = self._degrade_uncached(e)
                    self.recovery_wall_s += time.perf_counter() - t_rec
                    self.degraded_epochs += 1
                    degraded, reason = 1, "cache_lost"
                with _span("epoch.dispatch"):
                    params, opt_state, losses, accs = self._run_epoch(
                        params, opt_state, table, offsets, staged)
                # dispatch is async: a background thread stages epoch
                # e+1 (lazy schedule build + C_sec + plans) WHILE the
                # device trains epoch e. numpy/XLA release the GIL, so
                # the two genuinely overlap even single-host ...
                fut = (pool.submit(self._stage, e + 1, 0)
                       if e + 1 < stop_epoch else None)
                with _span("epoch.readback"):
                    losses = np.asarray(losses)  # block on the device epoch
                    accs = np.asarray(accs)
                t_done = time.perf_counter()
                with _span("stage.wait"):
                    nxt, nxt_retries = ((None, 0) if fut is None
                                        else self._await_stage(fut, e + 1))
                exposed = (time.perf_counter() - t_done
                           if fut is not None else 0.0)
                self.exposed_stage_s += exposed
                with _span("epoch.report"):
                    reports.append(DeviceEpochReport(
                        epoch=e, steps=self.num_steps,
                        miss_lanes=staged["lanes"],
                        wire_rows=staged["wire_rows"],
                        intra_lanes=staged.get("intra_lanes"),
                        inter_lanes=staged.get("inter_lanes"),
                        intra_wire_rows=staged.get("intra_wire_rows", 0),
                        inter_wire_rows=staged.get("inter_wire_rows", 0),
                        valid_rows=staged["valid_rows"],
                        padded_rows=staged["padded_rows"],
                        local_rows=staged["local_rows"],
                        cache_rows=staged["cache_rows"],
                        pulled_rows=staged["pulled_rows"],
                        scatter_free_layers=self.scatter_free_layers,
                        losses=losses, accs=accs,
                        wall_time_s=time.perf_counter() - t0,
                        stage_s=(nxt["stage_s"] if nxt is not None
                                 else 0.0),
                        exposed_stage_s=exposed,
                        degraded=degraded, degrade_reason=reason,
                        stage_retries=pending_retries))
                    self.params, self.opt_state = params, opt_state
                    if (self.checkpoint_dir is not None
                            and (e + 1) % self.checkpoint_every == 0):
                        # atomic run-state commit; the crash probe AFTER it
                        # models dying between epochs -- resume picks up from
                        # LATEST and the stitched loss curve is bit-equal
                        save_run_state(self.checkpoint_dir,
                                       {"params": params, "opt": opt_state},
                                       step=e + 1)
                        fault_point("run_crash", epoch=e + 1)
                staged, pending_retries = nxt, nxt_retries
        self.params, self.opt_state = params, opt_state
        return reports

    # subclass hooks ------------------------------------------------------

    def _make_epoch_fn(self):
        raise NotImplementedError

    def _run_epoch(self, params, opt_state, table, offsets, staged):
        raise NotImplementedError


class DeviceRapidGNNRunner(_DeviceRunnerBase):
    """Paper Alg. 1 on the mesh: C_s/C_sec double buffer + pipelined pull."""

    uses_cache = True
    pulls_beyond_steps = 1      # the pre-scan pulled0 priming the pipeline

    def _make_epoch_fn(self):
        return make_pipelined_epoch(self.cfg, self.opt, self.mesh,
                                    self.m_max,
                                    assemble_backend=self.assemble_backend,
                                    topology=self.topo)

    def _run_epoch(self, params, opt_state, table, offsets, staged):
        return self._fn(params, opt_state, table, offsets, staged["cids"],
                        staged["cfeats"], staged["batches"])


class DeviceBaselineRunner(_DeviceRunnerBase):
    """DGL-style on-demand path: no cache, pull on the critical path."""

    uses_cache = False

    def _make_epoch_fn(self):
        return make_ondemand_epoch(self.cfg, self.opt, self.mesh,
                                   self.m_max,
                                   assemble_backend=self.assemble_backend,
                                   topology=self.topo)

    def _run_epoch(self, params, opt_state, table, offsets, staged):
        return self._fn(params, opt_state, table, offsets,
                        staged["batches"])


def host_miss_matrix(schedules: Sequence[WorkerSchedule], pg,
                     batch_size: int) -> np.ndarray:
    """(E, P) host-sim ``cache_misses`` per (epoch, worker): every worker
    run through ``core.runtime.RapidGNNRunner`` on the same schedule."""
    from repro.core.fetch import ShardedFeatureStore
    from repro.core.metrics import NetworkModel
    from repro.core.runtime import RapidGNNRunner

    E = len(schedules[0].epochs)
    out = np.zeros((E, len(schedules)), np.int64)
    for w, ws in enumerate(schedules):
        store = ShardedFeatureStore(pg, worker=w,
                                    net=NetworkModel(enabled=False))
        m = RapidGNNRunner(ws, store, batch_size=batch_size).run()
        out[:, w] = [em.cache_misses for em in m.epochs]
    return out


def assert_host_parity(schedules: Sequence[WorkerSchedule], pg,
                       batch_size: int,
                       reports: Sequence[DeviceEpochReport]) -> np.ndarray:
    """Device residual-miss lanes == host-sim cache_misses, per (epoch,
    worker). The two paths count the SAME miss sets from independent code
    (numpy searchsorted vs pull-plan lanes), so equality pins the device
    fetch accounting to the paper's (DESIGN.md §7). Returns the matrix."""
    host = host_miss_matrix(schedules, pg, batch_size)
    dev = np.stack([r.miss_lanes for r in reports])
    np.testing.assert_array_equal(
        dev, host,
        err_msg="device pull-lane counts diverge from host cache_misses")
    return host
