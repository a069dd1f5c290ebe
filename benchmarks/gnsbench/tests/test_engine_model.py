"""The drivers take the engine's model from the configuration: the fields
of ``engine_model`` reach the program's ``ModelConfig``, and a field it
does not have fails at set-up."""
import dataclasses

import pytest

from gnsbench import harness


def context(cell, cpu):
    return harness.Context(cell=cell, seed=2718281828, devices=cpu,
                           log=lambda msg: None)


class Built(Exception):
    """Raised in place of building the engine."""


@pytest.fixture
def engine_config(monkeypatch):
    """The ``EngineConfig`` each driver's ``setup`` hands the engine."""
    import repro.gns
    seen = []

    def build(cfg, **_):
        seen.append(cfg)
        raise Built
    monkeypatch.setattr(repro.gns, "GNSEngine", build)
    return seen


@pytest.mark.parametrize("name,fields", [
    ("products_train", {"input_impl": "where"}),
    ("products_serve", {})])
def test_config_without_engine_model_keeps_the_default_model(
        tiny_cell, cpu, engine_config, name, fields):
    from repro.gns.config import ModelConfig
    cell = tiny_cell(name)
    with pytest.raises(Built):
        cell.driver.setup(context(cell, cpu))
    assert engine_config[0].model == ModelConfig(
        hidden_dim=cell.config["hidden_dim"], **fields)


@pytest.mark.parametrize("name", ["products_train", "products_serve"])
def test_engine_model_reaches_the_engine(tiny_cell, cpu, engine_config,
                                         name):
    cell = tiny_cell(name)
    cell = dataclasses.replace(cell, config=dict(
        cell.config, engine_model={"sample_kernel": "reference"}))
    with pytest.raises(Built):
        cell.driver.setup(context(cell, cpu))
    model = engine_config[0].model
    assert model.sample_kernel == "reference"
    assert model.hidden_dim == cell.config["hidden_dim"]


@pytest.mark.parametrize("name", ["products_train", "products_serve"])
@pytest.mark.parametrize("engine_model", [{"no_such_field": 4},
                                          {"hidden_dim": 64}])
def test_bad_engine_model_field_raises_at_setup(tiny_cell, cpu, engine_config,
                                                name, engine_model):
    cell = tiny_cell(name)
    cell = dataclasses.replace(cell, config=dict(cell.config,
                                                 engine_model=engine_model))
    with pytest.raises(TypeError):
        cell.driver.setup(context(cell, cpu))
    assert engine_config == []
