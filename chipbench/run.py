"""Run one benchmark cell on this machine's chips.

  python -m chipbench.run --workload sage-products.p1 --seed 7 \\
      --seconds 10 --trace 0

From the root of a checkout. Prints progress on standard error, then the
compared numbers beside their limits as its last lines there, and one
JSON result line as the last line of standard output. With ``--trace 0``
the result holds the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics from a profiler trace of the window. Exits non-zero
and prints no result when JAX finds no TPU or fewer chips than the cell
asks for, or when the program (``src/repro``) is not beside it.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:8.3f}s] {msg}", file=sys.stderr,
          flush=True)


def enable_cache(jax) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout,
    whatever the environment names, so only a checkout's first run of a
    cell compiles and two checkouts share nothing."""
    path = os.path.join(ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def require_chips(jax, chips: int) -> dict:
    """-> the device as JAX reports it; raises when it is not a TPU or
    there are fewer chips than the cell needs."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX's backend is {devs[0].platform!r}")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX sees "
                           f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    from chipbench import harness

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.load_cell(bench, args.workload, ROOT)
    # libtpu writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    enable_cache(jax)
    try:
        device = require_chips(jax, cell.chips)
    except RuntimeError as e:
        log(f"chipbench: {e}")
        return 3
    sys.path.insert(0, os.path.join(ROOT, "src"))
    log(f"device: {device} workload={cell.name} seed={args.seed}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), device, T0, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
