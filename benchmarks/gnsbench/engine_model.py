"""The engine's model as a configuration file selects it."""
from __future__ import annotations


def model_config(cfg: dict, **fields):
    """``repro.gns.config.ModelConfig`` with the configuration's
    ``hidden_dim``, the driver's ``fields`` and the file's ``engine_model``
    fields.  A field given twice, or one ``ModelConfig`` does not have,
    raises ``TypeError``."""
    from repro.gns.config import ModelConfig
    return ModelConfig(hidden_dim=cfg["hidden_dim"], **fields,
                       **cfg.get("engine_model", {}))
