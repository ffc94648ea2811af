"""``correct`` comes out false when the timed path is broken under the
harness: a step that returns its state unchanged, half of each batch
left out, one leaf's update applied twice, (four workers) the
all_to_all pull left out, and sampled sources that are no neighbours,
which reach the reference too and only the block check sees. Each case runs the whole harness but its look for a chip,
at CPU size, in a process of its own."""
import json
import os
import subprocess
import sys

import pytest

from chipbench.tests import _tiny


@pytest.mark.parametrize("fault,workers", [
    ("state", 1), ("half", 1), ("answer", 1), ("exchange", 4),
    ("sample", 1)])
def test_a_broken_program_is_not_correct(fault, workers):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if workers > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={workers}").strip()
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.tests._tiny", fault,
         str(workers)], cwd=_tiny.ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is False, result["checks"]
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
