"""Benchmark harness entrypoint: one section per paper table/figure.

``python -m benchmarks.run``        -- fast CPU-sized defaults
``python -m benchmarks.run --full`` -- paper-scale grids (slow)

Prints CSV blocks per benchmark plus a ``name,us_per_call,derived``
summary line per section (harness contract), exits non-zero when any
section failed, and writes EVERY section
as machine-readable JSON to ``artifacts/BENCH_<name>.json``:
``{"section", "status", "us", "summary", "rows"}`` -- the rows split
into header/records when the first row is a CSV header. Sections with
richer native records (assemble) additionally write their own files,
and the cross-backend paper grid lives in ``BENCH_paper.json``
(``python -m repro.eval.campaign``, DESIGN.md §7).

The paper campaign runs FIRST: its device cells run in child processes,
which need the accelerator that this process holds once a section has
run JAX in-process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from repro.compile_cache import enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART = os.path.join(ROOT, "artifacts")


def _write_section_json(name, status, us, summary, rows):
    rec = {"section": name, "status": status, "us": round(us, 1),
           "summary": summary, "rows": rows}
    if rows and isinstance(rows[0], str) and "," in rows[0]:
        header = rows[0].split(",")
        body = [r.split(",") for r in rows[1:]]
        if all(len(b) == len(header) for b in body):
            rec["columns"] = header
            rec["records"] = [dict(zip(header, b)) for b in body]
    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, f"BENCH_{name}.json"), "w") as f:
        json.dump(rec, f, indent=1)


def _section(name, fn, summary, failed):
    """Run one section; a failure is printed, recorded in its JSON and
    appended to ``failed`` so the process exits non-zero."""
    print(f"\n===== {name} =====")
    t0 = time.time()
    try:
        rows = fn()
        for r in rows:
            print(r)
        dt = (time.time() - t0) * 1e6
        s = summary(rows)
        print(f"#summary {name},{dt:.0f},{s}")
        _write_section_json(name, "ok", dt, str(s), list(rows))
        return rows
    except Exception as e:      # one section's failure must not stop the rest
        print(f"#summary {name},0,FAILED:{e}")
        traceback.print_exc()
        _write_section_json(name, "failed", 0.0, f"FAILED:{e}", [])
        failed.append(name)
        return []


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    failed: list = []

    from benchmarks import (speedup, access_dist, comm_volume, cache_sweep,
                            scaling, memory, energy, convergence,
                            embedding_cache, device_epoch, assemble,
                            schedule_build, topology)

    if args.full:
        ds = ("reddit_sim", "ogbn_products_sim", "ogbn_papers_sim")
        bs = (100, 200, 300)
        epochs = 4
    else:
        ds = ("ogbn_products_sim",)
        bs = (100, 200)
        epochs = 2

    campaign_box = {}

    def _campaign():
        """Paired host+device grid -> BENCH_paper.json (DESIGN.md §7);
        a differential-check failure fails the whole section. The
        device CellResults are stashed so the device_epoch section
        reuses them instead of re-running the SPMD subprocess."""
        from repro.eval.campaign import run_campaign
        from repro.eval.spec import fast_grid, full_grid

        rep = run_campaign(full_grid() if args.full else fast_grid(),
                           out_path=os.path.join(ART,
                                                 "BENCH_paper.json"))
        campaign_box["report"] = rep
        rows = ["backend,baseline,dataset,batch,throughput_speedup,"
                "fetch_reduction_x,energy_total_ratio"]
        for p in rep["pairs"]:
            sc = p["scenario"]
            rows.append(f"{p['backend']},{p['baseline_system']},"
                        f"{sc['dataset']},{sc['batch_size']},"
                        f"{p['throughput_speedup']},"
                        f"{p['fetch_reduction_x']},"
                        f"{p['energy']['total_ratio']}")
        n_fail = sum(1 for c in rep["differential"]
                     if c["status"] == "FAIL")
        n_pass = sum(1 for c in rep["differential"]
                     if c["status"] == "PASS")
        rows.append(f"differential,-,-,-,{n_pass}_pass,{n_fail}_fail,"
                    f"{'OK' if rep['all_checks_pass'] else 'BAD'}")
        if not rep["all_checks_pass"]:
            raise RuntimeError(f"{n_fail} differential check(s) failed")
        return rows

    _section("paper_campaign", _campaign,
             lambda rows: rows[-1] if rows else "-", failed)

    def _device_epoch():
        from repro.eval.cells import CellResult

        # the campaign's device cells, never a fresh child: this process
        # has run JAX by now and may hold the accelerator
        rep = campaign_box.get("report")
        dev = [CellResult.from_dict(d) for d in (rep or {}).get("cells", [])
               if d["spec"]["backend"] == "device"]
        if not dev:
            raise RuntimeError("paper_campaign produced no device cells")
        return device_epoch.run(results=dev)

    _section("device_epoch", _device_epoch,
             lambda rows: rows[-1] if rows else "-", failed)
    _section("table2_speedup",
             lambda: speedup.run(datasets=ds, batch_sizes=bs,
                                 epochs=epochs),
             lambda rows: rows[-1] if rows else "-", failed)
    _section("fig3_access_distribution", access_dist.run,
             lambda rows: next((r for r in rows if "once" in r), "-"),
             failed)
    _section("fig4_comm_volume",
             lambda: comm_volume.run(datasets=ds, batch_sizes=bs,
                                     epochs=epochs),
             lambda rows: rows[-1] if rows else "-", failed)
    _section("fig5_cache_sweep",
             lambda: cache_sweep.run(batch_sizes=bs[:1]),
             lambda rows: rows[-1] if rows else "-", failed)
    # raises (-> section FAILED) on a broken intra+inter byte-sum
    # identity or a DCN bias that raises cross-host traffic
    _section("topology",
             lambda: topology.run(datasets=ds, batch_sizes=bs[:1],
                                  epochs=epochs),
             lambda rows: rows[-1] if rows else "-", failed)
    _section("fig6_scaling", scaling.run,
             lambda rows: rows[-1] if rows else "-", failed)
    _section("fig7_memory", memory.run,
             lambda rows: rows[-1] if rows else "-", failed)
    _section("table3_energy", energy.run,
             lambda rows: next((r for r in rows if r.startswith("total")),
                               "-"), failed)
    _section("fig9_convergence", convergence.run,
             lambda rows: rows[-1] if rows else "-", failed)
    _section("beyond_embedding_cache", embedding_cache.run,
             lambda rows: rows[-1] if rows else "-", failed)
    _section("assemble_collation", assemble.run,
             lambda rows: rows[-1] if rows else "-", failed)
    # raises (-> section FAILED -> CI bench job fails) on any
    # batched-vs-loop schedule parity mismatch, campaign-style
    _section("schedule_build", schedule_build.run,
             lambda rows: rows[-1] if rows else "-", failed)

    def _fault_recovery():
        """Seeded chaos sweep (DESIGN.md §10): every injected fault
        plan must recover BIT-equal to the clean oracle or surface a
        typed error, and the checkpoint-atomicity drill must hold.
        Raises -> section FAILED + a ``recovery FAILED`` line in the
        log (CI greps for it)."""
        from repro.fault.chaos import run_chaos

        out = run_chaos(seed=0, fast=not args.full,
                        n_random=8 if args.full else 2)
        rows = ["plan,fires,outcome"]
        for r in out["runs"]:
            rows.append(f"{r['plan']},{r['fires']},{r['outcome']}")
        rows.append("checkpoint_drill,-,"
                    + ("ok" if out["checkpoint_drill"] else "failed"))
        if not out["ok"]:
            raise RuntimeError(
                "recovery FAILED: "
                + (",".join(out["failed_plans"]) or "checkpoint drill"))
        return rows

    _section("fault_recovery", _fault_recovery,
             lambda rows: rows[-1] if rows else "-", failed)

    def _serve():
        """Online-serving latency lanes (DESIGN.md §11): clean vs
        fault-injected Poisson streams; writes BENCH_serve.json and
        raises (-> ``recovery FAILED`` in the log, CI greps for it)
        when the fault-lane p99 exceeds 5x the clean lane's."""
        from benchmarks import serve_latency

        return serve_latency.run(requests=64 if args.full else 32)

    _section("serve_latency", _serve,
             lambda rows: rows[-1] if rows else "-", failed)
    if not args.skip_roofline:
        from benchmarks import roofline

        def _roof():
            rows = roofline.roofline_table()
            out = ["arch,shape,bottleneck,t_compute_s,t_memory_s,"
                   "t_collective_s,useful_ratio,attn_variant,source"]
            for r in rows:
                out.append(
                    f"{r['arch']},{r['shape']},{r['bottleneck']},"
                    f"{r['t_compute_s']:.4g},{r['t_memory_s']:.4g},"
                    f"{r['t_collective_s']:.4g},{r['useful_ratio']:.3f},"
                    f"{r['attn_variant']},{r['source']}")
            return out

        _section("roofline", _roof,
                 lambda rows: f"{len(rows) - 1}_combos", failed)

    if failed:
        print(f"FAILED sections: {','.join(failed)}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
