"""Paper-metrics campaign runner + CLI.

``python -m repro.eval.campaign --fast``   -- CPU-sized paired grid:
host-sim AND device runners over the tiny graph, every host/device and
rapid/baseline pair differentially verified in-line, headline ratios
(throughput speedup, fetch reduction, modelled energy) derived per
pair, everything written to ``artifacts/BENCH_paper.json``.

``--full`` swaps in the paper-scale host grid (Tables 2/3 axes; slow).
``--host-only`` skips the device subprocess (e.g. minimal CI images).
``--loop-sampler`` swaps every cell's schedule path to the per-batch
oracle (``build_schedule(compiler="loop")``); the default is the
vectorized epoch-at-once compiler -- schedules are bit-identical either
way, so all differential checks must pass under both.
``--schedule-backend device`` swaps every cell to the accelerator
schedule compiler (DESIGN.md §2.2; device cells also go lazy/device-
resident) -- same bit-parity contract, same all-checks-pass bar.
``--inject-miscount`` perturbs one cell's counters AFTER measurement --
the differential layer must then fail and the CLI exit non-zero; this
is the self-test proving the checks have teeth.

Exit code: 0 iff every differential check passes.
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import Callable, List, Optional

from repro.compile_cache import enable_compile_cache
from repro.eval.spec import CampaignSpec, fast_grid, fault_grid, full_grid
from repro.eval.cells import (CellResult, run_host_cell,
                              run_device_cells, device_child_main)
from repro.eval.differential import verify_cells, verify_fault_pairs
from repro.eval.report import (build_fault_report, build_report,
                               validate_fault_report, write_report)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_OUT = os.path.join(ROOT, "artifacts", "BENCH_paper.json")
FAULT_OUT = os.path.join(ROOT, "artifacts", "BENCH_fault.json")


def _device_cells_first(spec: CampaignSpec, include_device: bool,
                        log: Callable[[str], None]) -> List[CellResult]:
    """Run the spec's device cells in child processes while this process
    has not yet touched JAX (one process per chip)."""
    dev = spec.device_cells() if include_device else []
    if not dev:
        return []
    log(f"[cell] {len(dev)} device cell(s) via subprocess ...")
    cells = run_device_cells(dev)
    for c in cells:
        log(f"[cell] {c.spec['backend']}/{c.spec['system']}/"
            f"{c.spec.get('fault_profile', 'none')} done: "
            f"step={c.step_time_ms:.2f}ms lanes={c.rpc_count} "
            f"fires={c.fault_events} degraded={c.degraded_epochs} "
            f"retries={c.stage_retries}")
    return cells


def run_campaign(spec: CampaignSpec, include_device: bool = True,
                 out_path: Optional[str] = None,
                 log: Callable[[str], None] = lambda s: None,
                 mutate_cells: Optional[Callable[[List[CellResult]],
                                                 None]] = None) -> dict:
    """Run every cell, verify, derive ratios, optionally write the
    artifact. ``mutate_cells`` is the injection hook: it edits the
    measured cells before verification (tests + ``--inject-miscount``
    use it to prove a perturbed counter is caught)."""
    # device cells FIRST: their child processes need the accelerator,
    # which the parent would hold once its host cells have run JAX
    cells = _device_cells_first(spec, include_device, log)
    for c in spec.host_cells():
        log(f"[cell] {c.label()} ...")
        cells.append(run_host_cell(c))
        log(f"[cell] {c.label()} done: "
            f"step={cells[-1].step_time_ms:.2f}ms "
            f"rpc={cells[-1].rpc_count}")
    if mutate_cells is not None:
        mutate_cells(cells)
    checks = verify_cells(cells)
    report = build_report(spec.name, cells, checks)
    if out_path:
        write_report(report, out_path)
        log(f"[out] {out_path}")
    return report


def run_fault_campaign(include_device: bool = True,
                       out_path: Optional[str] = None,
                       log: Callable[[str], None] = lambda s: None
                       ) -> dict:
    """The fault campaign (ISSUE: robustness): the fast-grid rapidgnn
    scenario re-run under named fault profiles, each injection verified
    to (a) fire and (b) recover bit-exactly against its clean twin.
    Artifact: ``artifacts/BENCH_fault.json``."""
    spec = fault_grid()
    cells = _device_cells_first(spec, include_device, log)
    for c in spec.host_cells():
        log(f"[cell] {c.label()} ...")
        cells.append(run_host_cell(c))
        log(f"[cell] {c.label()} done: fires={cells[-1].fault_events} "
            f"degraded={cells[-1].degraded_epochs}")
    checks = verify_cells(cells) + verify_fault_pairs(cells)
    report = build_fault_report(spec.name, cells, checks)
    if out_path:
        write_report(report, out_path)
        log(f"[out] {out_path}")
    return report


def _print_fault_report(report: dict) -> None:
    print(f"campaign={report['campaign']} cells={report['num_cells']}")
    for r in report["fault_summary"]:
        print(f"  {r['backend']:6s} f={r['fault_profile']:15s} "
              f"fires={r['fault_events']} degraded={r['degraded_epochs']} "
              f"retries={r['retry_total']} "
              f"recovery_wall={r['recovery_wall_s']}s")
    n_fail = sum(1 for c in report["differential"]
                 if c["status"] == "FAIL")
    n_pass = sum(1 for c in report["differential"]
                 if c["status"] == "PASS")
    print(f"differential: {n_pass} passed, {n_fail} failed")
    for c in report["differential"]:
        if c["status"] == "FAIL":
            print(f"  FAIL {c['check']} @ {c['cell']}: {c['detail']}")


def _print_report(report: dict) -> None:
    print(f"campaign={report['campaign']} cells={report['num_cells']} "
          f"pairs={len(report['pairs'])}")
    for p in report["pairs"]:
        sc = p["scenario"]
        print(f"  {p['backend']:6s} rapid vs {p['baseline_system']:10s} "
              f"{sc['dataset']}/b{sc['batch_size']}: "
              f"speedup={p['throughput_speedup']}x "
              f"fetch_reduction={p['fetch_reduction_x']}x "
              f"energy_total_ratio={p['energy']['total_ratio']}")
    n_fail = sum(1 for c in report["differential"]
                 if c["status"] == "FAIL")
    n_pass = sum(1 for c in report["differential"]
                 if c["status"] == "PASS")
    print(f"differential: {n_pass} passed, {n_fail} failed")
    for c in report["differential"]:
        if c["status"] == "FAIL":
            print(f"  FAIL {c['check']} @ {c['cell']}: {c['detail']}")


def _inject_miscount(cells: List[CellResult]) -> None:
    """Perturb one measured counter (the self-test of the checks)."""
    c = cells[0]
    c.rpc_count += 1
    if c.miss_matrix and c.miss_matrix[0]:
        c.miss_matrix[0][0] += 1
    print(f"[inject] perturbed counters of "
          f"{c.spec['backend']}/{c.spec['system']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="RapidGNN paper-metrics campaign")
    ap.add_argument("--fast", action="store_true",
                    help="CPU-sized paired grid (default)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale host grid + device pair (slow)")
    ap.add_argument("--host-only", action="store_true",
                    help="skip device-backend cells (no subprocess)")
    ap.add_argument("--fault", action="store_true",
                    help="run the fault-injection campaign instead "
                         "(artifacts/BENCH_fault.json)")
    ap.add_argument("--loop-sampler", action="store_true",
                    help="build schedules with the per-batch oracle "
                         "sampler instead of the batched compiler")
    ap.add_argument("--schedule-backend", choices=("numpy", "device"),
                    default="numpy",
                    help="where schedules compile: numpy (default) or "
                         "the accelerator port of the epoch compiler")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="artifact path (default artifacts/"
                         "BENCH_paper.json)")
    ap.add_argument("--inject-miscount", action="store_true",
                    help="perturb one cell's counters post-measurement; "
                         "differential checks must fail")
    # internal: the device-cell worker (spawned by run_device_cells
    # with XLA_FLAGS pinning the emulated device count)
    ap.add_argument("--device-child", nargs=2,
                    metavar=("SPECS", "OUT"), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.device_child:
        enable_compile_cache()
        device_child_main(*args.device_child)
        return 0

    if args.fault:
        out = (args.out if args.out != DEFAULT_OUT else FAULT_OUT)
        report = run_fault_campaign(include_device=not args.host_only,
                                    out_path=out, log=print)
        _print_fault_report(report)
        probs = validate_fault_report(report)
        for p in probs:
            print(f"  INVALID: {p}")
        if not report["all_checks_pass"]:
            print("recovery FAILED: fault campaign checks did not pass")
        return 0 if report["all_checks_pass"] and not probs else 1

    spec = full_grid() if args.full else fast_grid()
    if args.loop_sampler:
        import dataclasses
        spec = CampaignSpec(
            name=f"{spec.name}-loop",
            cells=tuple(dataclasses.replace(c, schedule_compiler="loop")
                        for c in spec.cells))
    if args.schedule_backend != "numpy":
        import dataclasses
        spec = CampaignSpec(
            name=f"{spec.name}-{args.schedule_backend}",
            cells=tuple(dataclasses.replace(
                c, schedule_backend=args.schedule_backend)
                for c in spec.cells))
    report = run_campaign(
        spec, include_device=not args.host_only, out_path=args.out,
        log=print,
        mutate_cells=_inject_miscount if args.inject_miscount else None)
    _print_report(report)
    return 0 if report["all_checks_pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
