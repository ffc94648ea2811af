"""Multi-pod dry-run: lower + compile every (arch x input-shape) combo on
the production meshes and extract roofline inputs (assignment MULTI-POD
DRY-RUN + ROOFLINE ANALYSIS).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-2b \
      --shape train_4k [--multi-pod] [--out artifacts/dryrun]

Outputs one JSON per combo with: memory_analysis, cost_analysis (FLOPs /
bytes), per-collective byte volumes parsed from the post-SPMD HLO, and
compile wall-time. Default sweeps the full 10 x 4 matrix.
"""
import os
import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import ARCH_NAMES, INPUT_SHAPES
from repro.dist.mesh import host_device_flags
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import make_dryrun_spec

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def emulate_host_devices(n: int = 512) -> None:
    """Ask XLA's CPU backend for ``n`` devices, keeping flags already set.
    Call before the first JAX backend use: the device count locks then."""
    os.environ["XLA_FLAGS"] = host_device_flags(
        n, os.environ.get("XLA_FLAGS", ""))


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Per-device collective traffic by op kind, parsed from post-SPMD HLO.

    Accounting model (documented in EXPERIMENTS.md §Roofline): for each
    collective instruction we count the RESULT shard bytes, except
    all-reduce (2x: ring reduce-scatter + all-gather) and reduce-scatter
    (input shard bytes = result x group, approximated by the first
    operand's shape).
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.*)", s)
        if not m:
            continue
        body = m.group(1)
        op = None
        for k in _COLLECTIVES:
            if re.search(rf"\b{k}(-start|-done)?\(", body):
                op = k
                break
        if op is None:
            continue
        if re.search(rf"\b{op}-done\(", body):
            continue                       # avoid double count of async pairs
        shapes = _SHAPE_RE.findall(body)
        if not shapes:
            continue
        result = _shape_bytes(*shapes[0])
        if op == "all-reduce":
            vol = 2 * result
        elif op == "reduce-scatter":
            vol = _shape_bytes(*shapes[1]) if len(shapes) > 1 else result
        else:
            vol = result
        out[op] += vol
        counts[op] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts
    return out


def run_one(arch: str, shape: str, multi_pod: bool, cfg=None,
            S=None, B=None, opt: str = "") -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    if opt:
        import dataclasses
        from repro.configs import get_arch
        cfg = cfg or get_arch(arch)
        changes = {}
        if "seqshard" in opt:
            changes["seq_shard_attn"] = True
        if "resident" in opt:
            changes["moe_resident_experts"] = True
        cfg = dataclasses.replace(cfg, **changes)
    spec = make_dryrun_spec(arch, shape, mesh, cfg=cfg, S=S, B=B)
    rec = {"arch": arch, "shape": shape,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "kind": spec.meta["kind"],
           "S": spec.meta["seq"], "B": spec.meta["batch"],
           "attn_variant": spec.meta.get("attn_variant", "full")}
    t0 = time.time()
    with mesh:
        jitted = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                         out_shardings=spec.out_shardings)
        lowered = jitted.lower(*spec.args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes":
                getattr(mem, "generated_code_size_in_bytes", None),
        }
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        rec["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float)) and (
                           k in ("flops", "bytes accessed")
                           or k.startswith("bytes accessed"))}
        rec["collectives"] = collective_bytes(compiled.as_text())

    cfg = spec.meta["cfg"]
    pc = cfg.param_counts()
    rec["params_total"] = pc["total"]
    rec["params_active"] = pc["active"]
    rec["tokens"] = spec.meta["batch"] * (spec.meta["seq"]
                                          if spec.meta["kind"] != "decode"
                                          else 1)
    return rec


#: cost-variant grid (roofline): r repeats x small S (+ B split for decode)
CV_GRID = {
    "train": [("train_4k", r, S, 16) for r in (1, 2)
              for S in (512, 1024, 2048)],
    "prefill": [("prefill_32k", r, S, 16) for r in (1, 2)
                for S in (512, 1024, 2048)],
    "decode": ([("decode_32k", r, S, 16) for r in (1, 2)
                for S in (1024, 2048, 4096)]
               + [("decode_32k", r, 1024, 32) for r in (1, 2)]),
}


def run_cost_variants(archs, out_dir: str) -> None:
    from repro.configs import get_arch
    from repro.launch.specs import cost_variant_cfg
    for a in archs:
        for kind, grid in CV_GRID.items():
            for shape, r, S, B in grid:
                tag = f"{a}__cv_{kind}_r{r}_S{S}_B{B}"
                path = os.path.join(out_dir, tag + ".json")
                if os.path.exists(path):
                    continue
                cfg = cost_variant_cfg(get_arch(a), r, S)
                print(f"[cv] {tag} ...", flush=True)
                try:
                    rec = run_one(a, shape, False, cfg=cfg, S=S, B=B)
                    rec["cv"] = {"kind": kind, "r": r, "S": S, "B": B}
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    print(f"  ok {rec['compile_s']}s "
                          f"flops {rec['cost'].get('flops', 0):.3e}")
                except Exception as e:
                    print(f"  FAIL: {e}")
                    traceback.print_exc()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--cost-variants", action="store_true")
    ap.add_argument("--opt", default="",
                    help="comma list: seqshard,resident (EXPERIMENTS §Perf)")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()
    emulate_host_devices()

    archs = ARCH_NAMES if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    os.makedirs(args.out, exist_ok=True)
    if args.cost_variants:
        run_cost_variants(archs, args.out)
        return

    failures = []
    for a in archs:
        for s in shapes:
            tag = f"{a}__{s}__{'pod2' if args.multi_pod else 'pod1'}"
            if args.opt:
                tag += "__opt-" + args.opt.replace(",", "-")
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip] {tag} (exists)")
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                rec = run_one(a, s, args.multi_pod, opt=args.opt)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"  ok: compile {rec['compile_s']}s  "
                      f"flops {rec['cost'].get('flops', 0):.3e}  "
                      f"coll {rec['collectives']['total']:.3e}B")
            except Exception as e:
                failures.append((tag, str(e)))
                print(f"  FAIL: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e.splitlines()[0] if e else "")
        raise SystemExit(1)
    print("\nall dry-runs passed")


if __name__ == "__main__":
    main()
