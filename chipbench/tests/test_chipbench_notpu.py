"""Without a TPU the harness exits non-zero and prints no result."""
import json
import os
import subprocess
import sys

import pytest

from chipbench.tests import _tiny

BENCH = json.load(open(os.path.join(_tiny.ROOT, "BENCHMARK.json")))


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_no_tpu_no_result(workload):
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", workload,
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=_tiny.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr
