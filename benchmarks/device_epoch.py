"""Device rapid-vs-baseline epoch rows (paper Table 2, device path).

Formats the device-backend cells of a campaign run (``repro.eval.
campaign``; ``benchmarks.run`` passes its ``paper_campaign`` section's
cells, so the SPMD child runs once per invocation) into CSV rows. Step
time excludes the compile epoch; lane counts are the exact residual-miss
accounting the campaign's ``miss_parity`` differential check pins to the
host-sim runners.

Caveat: on EMULATED host devices the all_to_all is a shared-memory copy,
so the step-time ratio does not show the paper's network win -- the
miss-lane / payload columns carry that signal (9.7-15.4x fewer remote
fetches at paper scale; ~2-3x on the tiny graph), and step time becomes
meaningful on a real mesh where the pull has wire latency to hide.
"""
from __future__ import annotations

from typing import List

HEADER = ("system,workers,epochs,steps,step_time_ms,"
          "miss_lanes_per_epoch,payload_kb,wire_rows")


def run(results) -> List[str]:
    """-> CSV rows for the device ``CellResult``s of one campaign run."""
    rows = [HEADER]
    step_ms = {}
    for c in results:
        name = ("device_rapidgnn" if c.system == "rapidgnn"
                else "device_baseline")
        step_ms[name] = c.step_time_ms
        lanes = ";".join(str(sum(row)) for row in c.miss_matrix)
        rows.append(
            f"{name},{c.spec['workers']},{c.spec['epochs']},"
            f"{c.num_steps},{c.step_time_ms:.3f},{lanes},"
            f"{c.payload_bytes / 1024:.1f},{c.wire_rows}")
        assert c.trace_count == 1, \
            f"{name}: {c.trace_count} traces for " \
            f"{c.spec['epochs']} epochs"
    speedup = (step_ms["device_baseline"] /
               max(step_ms["device_rapidgnn"], 1e-9))
    rows.append(f"device_speedup,{results[0].spec['workers']},"
                f"{results[0].spec['epochs']},-,{speedup:.2f}x,-,-,-")
    return rows

