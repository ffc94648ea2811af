"""Chip smoke test: RapidGNN's device training path on a TPU.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # the four-chip phase only

One chip: the paper's GraphSAGE configuration
(``configs/rapidgnn_paper.sage("ogbn_products_sim", 1000)``: fan-outs
(25, 10), hidden 256, batch 1000, n_hot 4096, d=100, 47 classes) on a
P=1 mesh for three epochs through ``DeviceRapidGNNRunner`` (epoch 0 pays
the compile), then the same schedule through ``DeviceBaselineRunner``.
The runners are built and driven the way a campaign device cell drives
them (``repro.eval.cells``). Four chips: the same configuration on a flat
``(4,)`` mesh, rapid against baseline on one schedule, with the device
pull-lane counts held to the host simulation's cache misses.

Checks: finite, falling losses; one trace and one executable per runner;
rapid and baseline loss curves within rtol 1e-4 / atol 1e-5; the fused
``assemble`` kernel bit-equal to the jnp reference on one step's inputs.
Earlier lines report the device, the kernel backends each path resolved
to, the static bounds, compile seconds, warm epoch wall and peak device
bytes. The last line is one JSON object, printed only when every check
passed; without a TPU the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.rapidgnn_paper import sage  # noqa: E402
from repro.eval.cells import (_build_device_scenario,  # noqa: E402
                              build_device_runner, device_cell_result)
from repro.eval.spec import CellSpec  # noqa: E402

#: the JAX monitoring event that times each backend compile (or cache load)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: rapid-vs-baseline loss agreement, as tests/_dist_checks.py holds it
LOSS_RTOL, LOSS_ATOL = 1e-4, 1e-5

#: epochs per runner on the chip; epoch 0 pays the compile
EPOCHS = 3


class NoTPUError(RuntimeError):
    """JAX found no TPU, or fewer TPU chips than the phase needs."""


def require_tpu(chips: int = 1) -> Dict[str, object]:
    """The platform gate: -> the device as JAX reports it, or raise."""
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise NoTPUError(f"no TPU: JAX's default backend is {platform!r}; "
                         f"chip_smoke.py measures nothing off the chip")
    if len(devs) < chips:
        raise NoTPUError(f"need {chips} TPU chips, JAX sees {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def smoke_spec(size: str, workers: int, epochs: int,
               system: str = "rapidgnn") -> CellSpec:
    """The device cell this script drives. ``paper`` is the paper's
    GraphSAGE config at full width; ``tiny`` is a CPU-sized stand-in
    for checking control flow."""
    if size == "paper":
        c = sage("ogbn_products_sim", 1000, workers=workers, epochs=epochs)
        return CellSpec(backend="device", system=system, dataset=c.dataset,
                        batch_size=c.batch_size, workers=workers,
                        n_hot=c.n_hot, epochs=epochs, seed=c.s0,
                        fanouts=c.fanouts, partition=c.partition,
                        hidden=c.hidden_dim)
    if size == "tiny":
        return CellSpec(backend="device", system=system, dataset="tiny",
                        batch_size=16, workers=workers, n_hot=64,
                        epochs=epochs, seed=42, fanouts=(5, 5),
                        partition="greedy", hidden=32)
    raise ValueError(f"unknown size {size!r}")


class Checks:
    """Collects failed checks so every diagnostic line still prints."""

    def __init__(self):
        self.failed: List[str] = []

    def __call__(self, ok: bool, what: str) -> bool:
        print(f"check {'PASS' if ok else 'FAIL'}: {what}", flush=True)
        if not ok:
            self.failed.append(what)
        return ok


class CompileClock:
    """Counts and times backend compiles while active."""

    def __init__(self):
        self.durations: List[float] = []

    def _listen(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.durations.append(duration)

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


def kernel_backends() -> Dict[str, str]:
    """What each kernel of the device path resolves to on this backend."""
    from repro.kernels.assemble.ops import resolve_backend as assemble_be
    from repro.models.gnn import GNNConfig

    agg = GNNConfig.__dataclass_fields__["agg_backend"].default
    return {"assemble": assemble_be("auto"),
            "cache_lookup.search": "inside assemble (fused only)",
            "gather_agg": f"off (forward aggregates with {agg!r})"}


def train(spec: CellSpec, sc: dict, label: str) -> dict:
    """Build and run one device runner over scenario ``sc``."""
    runner = build_device_runner(spec, sc)
    with CompileClock() as clock:
        t0 = time.perf_counter()
        reports = runner.run()
        jax.block_until_ready((runner.params, runner.opt_state))
        run_wall = time.perf_counter() - t0
    cell = device_cell_result(spec, sc["g"], sc["schedules"], runner,
                              reports)
    walls = [r.wall_time_s for r in reports]
    warm = walls[1:]
    executables = runner._fn._cache_size()
    print(f"[{label}] m_max={runner.m_max} edge_max={list(runner.edge_max)} "
          f"num_steps={runner.num_steps} k_max={runner.k_max} "
          f"n_hot={runner.n_hot}")
    print(f"[{label}] compile_s={sum(clock.durations)} "
          f"compiles={len(clock.durations)} "
          f"epoch_executables={executables} "
          f"trace_count={runner.trace_count}")
    print(f"[{label}] epoch_wall_s={walls} "
          f"warm_epoch_wall_s={sum(warm) / len(warm) if warm else None} "
          f"run_wall_s={run_wall} (ends in block_until_ready) "
          f"stage_s={runner.stage_time_s} "
          f"exposed_stage_s={runner.exposed_stage_s}")
    losses = np.concatenate([r.losses for r in reports])
    print(f"[{label}] loss first={losses[0]} last={losses[-1]} "
          f"steps={losses.size} miss_lanes={cell.miss_matrix}", flush=True)
    return {"runner": runner, "reports": reports, "cell": cell,
            "losses": losses, "executables": executables}


def assemble_case(sc: dict, m_max: int, n_hot: int) -> dict:
    """Inputs of worker 0's first step of epoch 0, as the epoch program
    assembles them: its query ids (device ids, -1 padded to ``m_max``),
    its shard, and its hot set. With one worker every row is local, so
    its table is split in two for this check: every other queried id of
    the upper half (at most ``n_hot``) forms the cache and the rest
    arrive pulled."""
    dv = sc["dv"]
    flat = sc["schedules"][0].epoch(0).flat
    n0 = int(flat.m_counts[0])
    query = np.full(m_max, -1, np.int64)
    query[:n0] = dv.g2d[flat.input_nodes[:n0]]
    d = dv.table.shape[-1]
    if dv.num_parts > 1:
        n_per = dv.n_per
        table = dv.table.reshape(-1, d)
        cache_ids = dv.remap_cache(sc["schedules"][0].epoch(0).cache_ids).ids
    else:
        n_per = -(-dv.n_per // 2)
        table = np.zeros((2 * n_per, d), np.float32)
        table[:dv.n_per] = dv.table[0]
        cache_ids = np.unique(query[query >= n_per])[::2][:n_hot]
    return {"table": table, "n_per": n_per, "query": query,
            "cache_ids": cache_ids.astype(np.int64)}


def check_assemble(case: dict, interpret: bool = False) -> dict:
    """Run the fused kernel and the jnp reference on one step's inputs on
    the default device. -> bit-equality of the two, of each against the
    rows the step asked for, and the count of rows from each source."""
    from repro.kernels.assemble.ops import assemble_features

    table, n_per, q = case["table"], case["n_per"], case["query"]
    cache_ids = case["cache_ids"]
    valid = q >= 0
    local = valid & (q < n_per)             # worker 0 owns [0, n_per)
    cached = valid & ~local & np.isin(q, cache_ids)
    pulled_rows = valid & ~local & ~cached
    want = np.zeros((q.shape[0], table.shape[1]), np.float32)
    want[valid] = table[q[valid]]
    pulled = np.where(pulled_rows[:, None], want, 0).astype(np.float32)
    args = (jnp.asarray(table[:n_per]), jnp.int32(0),
            jnp.asarray(cache_ids.astype(np.int32)),
            jnp.asarray(table[cache_ids]), jnp.asarray(q.astype(np.int32)),
            jnp.asarray(pulled))
    fused = np.asarray(assemble_features(*args, backend="fused",
                                         interpret=interpret))
    ref = np.asarray(assemble_features(*args, backend="ref"))
    bits = np.uint32
    return {"fused_eq_ref": bool(np.array_equal(fused.view(bits),
                                                ref.view(bits))),
            "fused_eq_rows": bool(np.array_equal(fused.view(bits),
                                                 want.view(bits))),
            "rows": int(q.shape[0]), "local": int(local.sum()),
            "cache": int(cached.sum()), "pulled": int(pulled_rows.sum()),
            "pad": int((~valid).sum())}


def peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def train_pair(size: str, workers: int, epochs: int, check: Checks,
               interpret: bool = False) -> dict:
    """Rapid then baseline on one scenario of ``workers`` devices."""
    spec = smoke_spec(size, workers, epochs)
    print(f"config: {spec.label()} fanouts={spec.fanouts} "
          f"hidden={spec.hidden} partition={spec.partition}")
    for k, v in kernel_backends().items():
        print(f"backend: {k}={v}")
    t0 = time.perf_counter()
    sc = _build_device_scenario(spec)
    g = sc["g"]
    print(f"setup_s={time.perf_counter() - t0} nodes={g.num_nodes} "
          f"d={g.feat_dim} classes={g.num_classes}", flush=True)

    out = {"sc": sc, "spec": spec}
    for system in ("rapidgnn", "dgl-metis"):
        label = "rapid" if system == "rapidgnn" else "baseline"
        run = train(smoke_spec(size, workers, epochs, system), sc, label)
        losses = run["losses"]
        check(bool(np.isfinite(losses).all()), f"{label} losses finite")
        check(losses[-1] < losses[0],
              f"{label} loss falls ({losses[0]} -> {losses[-1]})")
        check(run["runner"].trace_count == 1, f"{label} trace_count == 1")
        check(run["executables"] == 1,
              f"{label} epoch program compiled once")
        out[label] = run

    a, b = out["rapid"]["losses"], out["baseline"]["losses"]
    diff = np.abs(a - b)
    unequal = np.flatnonzero(a != b)
    first = int(unequal[0]) if unequal.size else None
    print(f"rapid_vs_baseline max_abs_diff={diff.max()} "
          f"max_rel_diff={(diff / np.maximum(np.abs(b), 1e-30)).max()} "
          f"unequal_steps={unequal.size}/{a.size} first_unequal_step={first}"
          + ("" if first is None else
             f" (rapid {a[first]!r} vs baseline {b[first]!r})"))
    if unequal.size:
        print(f"rapid_losses={a.tolist()}\nbaseline_losses={b.tolist()}")
    check(bool(np.allclose(a, b, rtol=LOSS_RTOL, atol=LOSS_ATOL)),
          f"rapid and baseline loss curves agree "
          f"(rtol={LOSS_RTOL}, atol={LOSS_ATOL})")

    runner = out["rapid"]["runner"]
    res = check_assemble(assemble_case(sc, runner.m_max, runner.n_hot),
                         interpret=interpret)
    print(f"assemble step: {res}")
    check(res["fused_eq_ref"], "fused assemble bit-equal to ref")
    check(res["fused_eq_rows"], "fused assemble bit-equal to the rows "
                                "the step asked for")
    return out


def one_chip_phase(size: str = "paper", epochs: int = EPOCHS,
                   interpret: bool = False) -> Checks:
    check = Checks()
    train_pair(size, 1, epochs, check, interpret=interpret)
    print(f"peak_bytes_in_use={peak_bytes()}")
    return check


def four_chip_phase(size: str = "paper", epochs: int = EPOCHS,
                    interpret: bool = False) -> Checks:
    from repro.dist import assert_host_parity

    check = Checks()
    out = train_pair(size, 4, epochs, check, interpret=interpret)
    sc, spec = out["sc"], out["spec"]
    try:
        host = assert_host_parity(sc["schedules"], sc["pg"],
                                  spec.batch_size, out["rapid"]["reports"])
        check(True, f"device pull lanes == host cache misses "
                    f"{host.tolist()}")
    except AssertionError as e:
        check(False, f"host parity: {e}")
    print(f"peak_bytes_in_use={peak_bytes()}")
    return check


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phase; 4: only the four-chip "
                         "phase")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    try:
        dev = require_tpu(args.chips)
    except NoTPUError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}")
    print(f"compile_cache_dir={cache_dir}", flush=True)
    phase = four_chip_phase if args.chips == 4 else one_chip_phase
    check = phase("paper")
    if check.failed:
        print(f"chip_smoke FAILED: {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
