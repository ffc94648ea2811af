"""The plain reference against the program, on the ``tiny`` graph: the
copied generator, the reference's AdamW, and the check's three numbers
between the runner's training and the reference at P=1 and, on four
virtual CPU devices, at P=4."""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import check, harness, reference
from chipbench.graph import DatasetSpec, make_graph
from chipbench.tests import _tiny

ROOT = _tiny.ROOT


def test_graph_copy_matches_the_program_generator():
    from repro.graph.generate import DATASETS, make_powerlaw_graph

    spec = DATASETS["tiny"]
    mine = make_graph(DatasetSpec(**{
        f.name: getattr(spec, f.name)
        for f in dataclasses.fields(DatasetSpec)}), 0)
    g = make_powerlaw_graph(spec, seed=0)
    for k in ("indptr", "indices", "features", "labels", "train_mask"):
        np.testing.assert_array_equal(mine[k], getattr(g, k))


@pytest.mark.parametrize("config,dataset", [
    ("sage-products", "ogbn_products_sim"), ("sage-reddit", "reddit_sim")])
def test_configs_describe_the_program_stand_ins(config, dataset):
    from repro.graph.generate import DATASETS

    conf = harness.load_json(os.path.join(ROOT, "chipbench", "configs",
                                          config + ".json"))
    spec = harness.dataset_spec(conf)
    want = DATASETS[dataset]
    for f in dataclasses.fields(DatasetSpec):
        if f.name != "name":
            assert getattr(spec, f.name) == getattr(want, f.name), f.name


def test_reference_adamw_matches_the_program_optimizer():
    import jax
    from repro.train import AdamW

    params = harness.load_model("sage").init_params(
        {"feat_dim": 6, "hidden_dim": 5, "num_classes": 3,
         "num_layers": 2}, 2 ** 40 + 3)
    grads = jax.tree.map(lambda p: p * 0.5 + 0.01, params)
    opt = AdamW(lr=3e-3)
    p, st = params, opt.init(params)
    zero = jax.tree.map(np.zeros_like, grads)
    p, st = opt.update(grads, st, p)
    for _ in range(4):
        p, st = opt.update(zero, st, p)
    q, _ = reference.adamw_steps(params, reference.adamw_init(params),
                                 grads, 4,
                                 hp=(3e-3, 0.9, 0.999, 1e-8, 0.0))
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)


def test_reference_matches_the_runner_on_one_worker():
    import jax

    s = harness.build(_tiny.tiny_cell(1), 2 ** 35 + 1, 1, log=lambda m: None)
    prog = harness.train_check(s)
    ref = check.reference_readings(
        harness.check_steps(s), jax.numpy.asarray(s.graph.features),
        prog["params0"], s.hp, harness.steps_per_epoch(s),
        s.model.loss_and_grad)
    numbers = check.compare(prog, ref)
    assert all(v < 1e-5 for v in numbers.values()), numbers
    assert check.judge(numbers, _tiny.LIMITS)


def test_reference_matches_the_runner_on_four_workers():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.tests._tiny", "none", "4"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"], result["checks"]
