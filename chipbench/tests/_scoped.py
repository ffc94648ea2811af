"""The compiled epoch programs of the tiny cell, for the scope tests.

  python -m chipbench.tests._scoped <workers>

prints one JSON line: ``{"pipelined": <hlo text>, "ondemand": <hlo
text>}``, the optimized modules of ``make_pipelined_epoch`` (the rapid
runner's) and ``make_ondemand_epoch`` (the baseline runner's) as each
runner compiles them for one epoch. Run it with ``JAX_PLATFORMS=cpu``,
and for four workers with four XLA CPU devices, in a process of its own.
"""
from __future__ import annotations

import json
import sys
from typing import Dict

from chipbench import harness
from chipbench.tests import _tiny

SEED = 2 ** 35 + 9


def compiled_programs(workers: int) -> Dict[str, str]:
    from repro.dist import DeviceBaselineRunner

    s = harness.build(_tiny.tiny_cell(workers), SEED, 1, log=lambda m: None)
    rapid = s.runner
    base = DeviceBaselineRunner(
        s.views, rapid.dv, rapid.cfg, rapid.opt, rapid.mesh,
        rapid.batch_size, rapid.labels, seed=SEED, topology=rapid.topo)
    texts: Dict[str, str] = {}
    for name, runner in (("pipelined", rapid), ("ondemand", base)):
        fn = runner._fn

        def keep(*args, fn=fn, name=name):
            texts[name] = fn.lower(*args).compile().as_text()
            return fn(*args)
        runner._fn = keep
        runner.run(stop_epoch=1)
    return texts


def main(argv=None) -> int:
    workers = int((argv or sys.argv[1:])[0])
    print(json.dumps(compiled_programs(workers)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
