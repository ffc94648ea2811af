"""Chip benchmark of RapidGNN's device training path.

``python -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the chips of the
machine it starts on and prints one JSON result line. Everything that
belongs to one configuration, traffic mix, cell, per-layer metric or
model is a file of its own under ``configs/``, ``traffic/``, ``cells/``,
``metrics/`` and ``models/``, found by the name ``BENCHMARK.json`` or
the configuration's ``model`` gives it.
"""
