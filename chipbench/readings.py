"""The readings a cell's limits are set from (``check.py``).

  python -m chipbench.readings --workload <cell> \\
      --seeds 12 --control-seeds 3 --out readings.jsonl

On the cell's chips, for each seed: the program's check epochs against
the float32 reference (the lower readings); on the first
``--control-seeds`` seeds also the control, the reference computed in
bfloat16 and put in the program's place, and the step faults planted in
the reference (the upper readings):

``half``      half of each batch's seeds left out, the mean over the rest;
``exchange``  the rows a worker pulls over the all_to_all left out
              (neither local nor in its steady cache; four chips only);
``answer``    the step's answer altered where it is produced: the
              optimizer applies one leaf's update (``answer_leaf``:
              layer 0's largest) twice.

A state left unchanged reads 1 on ``grad`` and ``update`` by their
measure and needs no run. The benchmark's own runs never run this. One
JSON line per seed goes to ``--out``.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time

from chipbench import check, harness, reference
from chipbench.run import ROOT, enable_cache, log, require_chips


def plant(steps, fault: str, setup: "harness.Setup"):
    """A copy of the check's worker-steps with ``fault`` planted."""
    import numpy as np

    out = copy.deepcopy(steps)
    for per_worker in out:
        for w, x in enumerate(per_worker):
            if fault == "half":
                n = int(x["seed_mask"].sum())
                x["seed_mask"][n // 2:] = False
            elif fault == "exchange":
                rows = x["rows"]
                owner = setup.partition.owner[np.maximum(rows, 0)]
                cache = setup.schedules[w].epoch(0).cache_ids
                pulled = (rows >= 0) & (owner != w) & ~np.isin(rows, cache)
                rows[pulled] = -1
            else:
                raise ValueError(fault)
    return out


def answer_leaf(layer) -> str:
    """The leaf of a layer whose update ``answer`` doubles: the largest,
    the first by name among equals."""
    return min(layer, key=lambda k: (-layer[k].size, k))


def doubled_leaf_update(params, state, grads, zero_steps, *, hp):
    """AdamW that moves layer 0's ``answer_leaf`` twice as far."""
    new, state = reference.adamw_steps(params, state, grads, zero_steps,
                                       hp=hp)
    k = answer_leaf(params["layers"][0])
    old = params["layers"][0][k]
    new["layers"][0][k] = old + 2 * (new["layers"][0][k] - old)
    return new, state


def read_cell(cell: "harness.Cell", device: dict, seeds: int,
              control_seeds: int, first_seed: int, out_path: str) -> list:
    """Take the readings of ``seeds`` seeds; -> their records, also
    appended to ``out_path`` as JSON lines."""
    import jax
    import jax.numpy as jnp

    faults = ["half"] + (["exchange"] if cell.traffic["workers"] > 1
                         else [])
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    records, shared = [], {}
    with open(out_path, "a") as out:
        for i in range(seeds):
            seed = first_seed + 7919 * i
            t0 = time.perf_counter()
            s = harness.build(cell, seed, 1, log=log, **shared)
            shared = {"graph": s.graph, "arrays": s.arrays,
                      "partition": s.partition}
            prog = harness.train_check(s)
            steps, S = harness.check_steps(s), harness.steps_per_epoch(s)
            dev0 = list(s.runner.mesh.devices.flat)[0]
            s.runner = None
            gc.collect()
            table = jax.device_put(s.graph.features, dev0)
            p0 = jax.device_put(prog["params0"], dev0)
            lg = s.model.loss_and_grad
            ref = check.reference_readings(steps, table, p0, s.hp, S, lg)
            rec = {"workload": cell.name, "seed": seed, "device": device,
                   "program": check.compare(prog, ref),
                   "losses": {"program": prog["losses"],
                              "reference": ref["losses"]},
                   "grad_norms": check.leaf_norms(ref["grad1"])}
            if i < control_seeds:
                ctrl = check.reference_readings(steps, table, p0, s.hp, S,
                                                lg, dtype=jnp.bfloat16)
                rec["control"] = check.compare(ctrl, ref)
                rec["control_losses"] = ctrl["losses"]
                bad = check.reference_readings(
                    steps, table, p0, s.hp, S, lg,
                    update=doubled_leaf_update)
                rec["answer"] = check.compare(bad, ref)
                for f in faults:
                    bad = check.reference_readings(plant(steps, f, s),
                                                   table, p0, s.hp, S, lg)
                    rec[f] = check.compare(bad, ref)
            rec["seconds"] = time.perf_counter() - t0
            print(json.dumps(rec), file=out, flush=True)
            log(json.dumps(rec))
            records.append(rec)
            del s, table, p0
            gc.collect()
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=10_000_019)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.load_cell(bench, args.workload, ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    enable_cache(jax)
    device = require_chips(jax, cell.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    read_cell(cell, device, args.seeds, args.control_seeds,
              args.first_seed, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
