"""A new configuration, traffic mix, cell and per-layer metric are new
files plus new ``BENCHMARK.json`` entries: no file that is there
changes, and the harness of that copy finds and runs them."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

from chipbench.tests import _tiny

SCRIPT = """
import json, os, sys
sys.path.insert(0, {src!r})
from chipbench import harness
bench = harness.load_json("BENCHMARK.json")
cell = harness.load_cell(bench, "tiny-sage.one", os.getcwd())
result = harness.run_cell(cell, 5, 0.2, False, {{"platform": "cpu"}}, 0.0,
                          log=lambda m: None)
data = harness.RunData(chips=1, peaks={{}}, window={{"epochs": 4}})
print(json.dumps({{
    "per_layer": [m["name"] for m in cell.per_layer],
    "read": harness.load_reader("window.epochs")(data),
    "correct": result["correct"],
    "metrics": sorted(result["metrics"])}}))
"""


def digest(root):
    out = {}
    for d, _, files in os.walk(root):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def test_new_files_and_entries_need_no_edit(tmp_path):
    shutil.copy(os.path.join(_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(_tiny.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(tmp_path / "chipbench")

    cb = tmp_path / "chipbench"
    write_json(cb / "configs" / "tiny-sage.json", _tiny.tiny_config())
    cell = _tiny.tiny_cell(1)
    write_json(cb / "traffic" / "one.json", cell.traffic)
    write_json(cb / "cells" / "tiny-sage.one.json", cell.bounds)
    (cb / "metrics" / "window.epochs.py").write_text(
        "def read(run):\n    return run.window['epochs']\n")
    bench = json.load(open(tmp_path / "BENCHMARK.json"))
    bench["configs"].append({
        "name": "tiny-sage", "source": "test",
        "file": "chipbench/configs/tiny-sage.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny-sage.one", "config": "tiny-sage", "traffic": "one",
        "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "window.epochs", "unit": "epochs", "better": "higher",
        "source": "program_counter", "layer": "epoch loop",
        "moves": "seed_nodes_per_s"})
    write_json(tmp_path / "BENCHMARK.json", bench)

    after = digest(tmp_path / "chipbench")
    assert {k: after[k] for k in before} == before

    out = subprocess.run(
        [sys.executable, "-c",
         SCRIPT.format(src=os.path.join(_tiny.ROOT, "src"))],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert "window.epochs" in got["per_layer"]
    assert got["read"] == 4
    assert got["correct"] is True
    assert got["metrics"] == ["peak_hbm_bytes", "seed_nodes_per_s",
                              "setup_s"]
