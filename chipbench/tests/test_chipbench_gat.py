"""The GAT configuration (``models/gat.py``, ``configs/gat-products.json``):
the shipped file builds the program's GAT at the leaderboard widths,
with exactly the leaderboard's parameter count; the reference's weights
carry the program's leaves; its FLOP count matches a hand count; and a
3-layer tiny GAT cell runs through the harness and the check, which the
program passes and the control and each fault fail."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import check, harness, readings
from chipbench.tests import _tiny

GAT = harness.load_model("gat")
CONFIG = harness.load_json(os.path.join(_tiny.ROOT, "chipbench", "configs",
                                        "gat-products.json"))


def gat_cell(workers: int = 1) -> "harness.Cell":
    """The tiny cell with a 3-layer, 2-head GAT."""
    cell = _tiny.tiny_cell(workers)
    cell.config = dict(cell.config, model="gat", heads=2, hidden_dim=8,
                       num_layers=3, fanouts=[4, 4, 4])
    cell.bounds = {"pad_floor": {"m_max": 200, "edge_max": [400, 160, 64],
                                 "k_max": 8},
                   "limits": dict(_tiny.LIMITS)}
    return cell


def test_the_shipped_config_builds_the_published_gat():
    from repro.models import GNNConfig

    assert GAT.gnn_config(CONFIG) == GNNConfig(
        kind="gat", in_dim=100, hidden_dim=128, num_classes=47,
        num_layers=3, fanouts=(10, 10, 10), heads=4, precision="highest")
    assert CONFIG["reduced"] == ["num_nodes"]


@pytest.mark.parametrize("matmul,want", [(None, None), ("default", None),
                                         ("highest", "highest")])
def test_the_stated_matmul_precision_reaches_the_program(matmul, want):
    conf = dict(CONFIG)
    if matmul is None:
        del conf["precision"]
    else:
        conf["precision"] = dict(conf["precision"], matmul=matmul)
    assert GAT.gnn_config(conf).precision == want


def test_a_model_name_with_no_module_is_refused_by_the_harness():
    cell = _tiny.tiny_cell(1)
    cell.config = dict(cell.config, model="no_such_model")
    with pytest.raises(ValueError,
                       match=r"model 'no_such_model': no .*no_such_model\.py"):
        harness.build(cell, 1, 1, log=lambda m: None)


def test_a_gat_configuration_without_heads_is_refused():
    cell = _tiny.tiny_cell(1)
    cell.config = dict(cell.config, model="gat")
    with pytest.raises(ValueError, match=r"no head count \('heads'\)"):
        harness.build(cell, 1, 1, log=lambda m: None)


def test_the_published_widths_hold_the_leaderboard_parameters():
    import jax

    params = GAT.init_params(CONFIG, 2 ** 33 + 5)
    sizes = [sum(layer[k].size for k in ("lin", "att_src", "att_dst",
                                         "bias"))
             for layer in params["layers"]]
    skips = [layer["skip_w"].size + layer["skip_b"].size
             for layer in params["layers"]]
    assert sizes == [52736, 263680, 96679]
    assert skips == [51712, 262656, 24111]
    assert sum(a.size for a in jax.tree.leaves(params)) == 751574


def test_the_weights_carry_the_program_leaves():
    import jax
    from repro.models.gnn import init_params

    conf = gat_cell().config
    mine = GAT.init_params(conf, 2 ** 40 + 3)
    prog = init_params(GAT.gnn_config(conf), jax.random.key(0))
    assert jax.tree.structure(mine) == jax.tree.structure(prog)
    assert ([a.shape for a in jax.tree.leaves(mine)]
            == [a.shape for a in jax.tree.leaves(prog)])


def test_epoch_flops_by_hand():
    """Two batches of a 3-layer schedule, each term counted by hand."""
    from repro.core import build_schedule
    from repro.graph import KHopSampler, partition_graph

    conf = dict(gat_cell().config, fanouts=[3, 2, 2])
    g, _ = harness.build_graph(conf)
    sampler = KHopSampler(g, fanouts=conf["fanouts"], batch_size=16)
    ws = build_schedule(sampler, partition_graph(g, 1, "metis"), worker=0,
                        s0=3, num_epochs=1, n_hot=8)
    flat = harness._subset(ws.epoch(0), 0, 2).flat
    assert flat.num_batches == 2
    d, H = [32, 16, 16], 2            # feat_dim, then heads x hidden 8
    W, out = [16, 16, 16], [16, 16, 8]  # H*C; the last averages 2 x 8
    want = 0.0
    for i in range(2):
        b = flat.batch(i)
        rows = b.input_nodes.shape[0]
        for l, blk in enumerate(b.blocks):
            nd, e = blk.num_dst, int(blk.edge_mask.sum())
            want += (rows * 2 * d[l] * W[l] + (rows + nd) * 2 * W[l]
                     + (e + nd) * (2 * H + 2 * W[l])
                     + nd * 2 * d[l] * out[l])
            rows = nd
    assert GAT.epoch_flops(conf, flat) == pytest.approx(3 * want,
                                                        rel=1e-12)


@pytest.fixture(scope="module")
def records(tmp_path_factory):
    """The tiny GAT cell through ``harness.build``, ``train_check`` and
    ``check.reference_readings``, with the control and the faults the
    readings plant in the reference, on two seeds."""
    out = tmp_path_factory.mktemp("readings") / "tiny-gat.jsonl"
    return readings.read_cell(gat_cell(), {"platform": "cpu"}, 2, 2,
                              10_000_019, str(out))


def test_the_program_passes_on_every_seed(records):
    assert len(records) == 2
    for r in records:
        assert set(r["program"]) == set(_tiny.LIMITS) - {"blocks"}
        assert check.judge(r["program"], _tiny.LIMITS), r["program"]


@pytest.mark.parametrize("reading", ["control", "half", "answer"])
def test_the_control_and_each_reference_fault_fail(records, reading):
    for r in records:
        assert not check.judge(r[reading], _tiny.LIMITS), r[reading]


SCRIPT = """
import json, sys
from chipbench import harness
from chipbench.tests import _tiny
from chipbench.tests.test_chipbench_gat import gat_cell
_tiny.plant({fault!r})
result = harness.run_cell(gat_cell(), 2 ** 33 + 11, 0.2, False,
                          {{"platform": "cpu", "kind": "cpu", "count": 1}},
                          0.0, log=lambda m: None)
print(json.dumps(result))
"""


@pytest.mark.parametrize("fault", ["none", "state", "half"])
def test_a_broken_program_is_not_correct(fault):
    """The whole harness over the tiny GAT cell, with a program fault of
    ``_tiny.plant`` (in a process of its own) or none."""
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(fault=fault)],
        cwd=_tiny.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    checks = result["checks"]
    assert set(checks) == set(_tiny.LIMITS)
    over = [k for k, c in checks.items() if c["value"] > c["limit"]]
    assert result["correct"] is (fault == "none"), checks
    assert bool(over) is (fault != "none"), checks
    assert np.isfinite([c["value"] for c in checks.values()]).all()
