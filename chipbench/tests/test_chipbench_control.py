"""The control at CPU size: the reference computed in bfloat16 and put
in the program's place fails the check, as do the faults planted in the
reference, while the program passes (``chipbench.readings``, which reads
the same at the cell's size on the chip)."""
import pytest

from chipbench import check, readings
from chipbench.tests import _tiny


@pytest.fixture(scope="module")
def records(tmp_path_factory):
    out = tmp_path_factory.mktemp("readings") / "tiny.jsonl"
    return readings.read_cell(_tiny.tiny_cell(1), {"platform": "cpu"},
                              3, 3, 10_000_019, str(out))


def test_the_program_passes_on_every_seed(records):
    assert len(records) == 3
    for r in records:
        assert check.judge(r["program"], _tiny.LIMITS), r["program"]


@pytest.mark.parametrize("reading", ["control", "half", "answer"])
def test_the_control_and_each_fault_fail(records, reading):
    for r in records:
        assert not check.judge(r[reading], _tiny.LIMITS), r[reading]


def test_the_exchange_fault_fails_on_four_workers(tmp_path):
    """The pull left out, planted in the reference, at P=4 on four
    virtual CPU devices (a process of its own: the device count locks
    when JAX starts)."""
    import json
    import os
    import subprocess
    import sys

    script = (
        "import json, sys\n"
        "from chipbench import check, readings\n"
        "from chipbench.tests import _tiny\n"
        "recs = readings.read_cell(_tiny.tiny_cell(4), {}, 2, 2, 7, "
        f"{str(tmp_path / 'r.jsonl')!r})\n"
        "print(json.dumps([[check.judge(r[k], _tiny.LIMITS) for k in "
        "('program', 'exchange')] for r in recs]))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], cwd=_tiny.ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == [
        [True, False], [True, False]]
