"""A configuration's ``model`` is a module of its own under
``chipbench/models/``: the shipped configurations build the program's
GraphSAGE configuration through it, each model's weights carry the
program's leaf names, a second model (the program's ``gcn`` kind, from
a module beside these tests) runs the tiny cell through the whole
harness with no edit to it, and a model with no module is refused."""
import os

import pytest

from chipbench import harness
from chipbench.tests import _tiny

TEST_MODELS = os.path.join(os.path.dirname(__file__), "models")


def gcn_cell():
    cell = _tiny.tiny_cell(1)
    cell.config = dict(cell.config, model="gcn")
    return cell


@pytest.mark.parametrize("config", ["sage-products", "sage-reddit"])
def test_shipped_configs_build_the_same_gnn_config(config):
    from repro.models import GNNConfig

    conf = harness.load_json(os.path.join(_tiny.ROOT, "chipbench",
                                          "configs", config + ".json"))
    got = harness.load_model(conf["model"]).gnn_config(conf)
    assert got == GNNConfig(kind="sage", in_dim=conf["feat_dim"],
                            hidden_dim=256,
                            num_classes=conf["num_classes"], num_layers=2)


@pytest.mark.parametrize("where,model", [(harness.MODELS, "sage"),
                                         (TEST_MODELS, "gcn")])
def test_weights_carry_the_program_leaves(monkeypatch, where, model):
    import jax
    from repro.models.gnn import init_params

    monkeypatch.setattr(harness, "MODELS", where)
    conf = dict(_tiny.tiny_config(), model=model)
    mod = harness.load_model(model)
    mine = mod.init_params(conf, 2 ** 40 + 3)
    prog = init_params(mod.gnn_config(conf), jax.random.key(0))
    assert (jax.tree.structure(mine) == jax.tree.structure(prog))
    assert ([a.shape for a in jax.tree.leaves(mine)]
            == [a.shape for a in jax.tree.leaves(prog)])


def test_a_second_model_plugs_in_by_files_alone(monkeypatch):
    monkeypatch.setattr(harness, "MODELS", TEST_MODELS)
    result = harness.run_cell(
        gcn_cell(), 2 ** 35 + 3, 0.2, False,
        {"platform": "cpu", "kind": "cpu", "count": 1}, 0.0,
        log=lambda m: None)
    checks = result["checks"]
    assert set(checks) == set(_tiny.LIMITS)
    for name, c in checks.items():
        assert c["value"] <= _tiny.LIMITS[name], (name, checks)
    assert result["correct"] is True


def test_a_model_with_no_module_is_refused():
    cell = _tiny.tiny_cell(1)
    cell.config = dict(cell.config, model="gat")
    with pytest.raises(ValueError, match=r"model 'gat': no .*gat\.py"):
        harness.build(cell, 1, 1, log=lambda m: None)
