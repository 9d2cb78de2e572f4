"""The system under test, built from a configuration file.

``model_config`` turns the file's ``model`` section into the program's
``ModelConfig``; ``build_deployment`` wraps it in the program's
``CrossDCDeployment`` with the file's ``serving`` settings.  This is the only
module of the benchmark, with ``weights.py`` and ``driver.py``, that imports
the program.
"""
from __future__ import annotations


def model_config(config: dict):
    from repro.configs.base import (AttentionSpec, BlockSpec, FFNSpec,
                                    GroupSpec, LinearSpec, ModelConfig)

    m = config["model"]

    def block(spec: dict):
        mixer = dict(spec["mixer"])
        kind = mixer.pop("type")
        mixer = (AttentionSpec(**mixer) if kind == "attention"
                 else LinearSpec(**mixer))
        return BlockSpec(mixer=mixer, ffn=FFNSpec(**spec["ffn"]),
                         shared=bool(spec.get("shared", False)))

    blocks = {name: block(spec) for name, spec in m["blocks"].items()}
    groups = tuple(GroupSpec(blocks=tuple(blocks[b] for b in g["blocks"]),
                             repeats=int(g["repeats"]))
                   for g in m["groups"])
    return ModelConfig(name=config["name"], family="bench",
                       d_model=int(m["d_model"]),
                       vocab_size=int(m["vocab_size"]), groups=groups,
                       norm_eps=float(m["norm_eps"]), dtype=m["dtype"],
                       source=config["source"])


def build_deployment(config: dict, params):
    from repro.models import Model
    from repro.serving import CrossDCDeployment, DeploymentConfig

    return CrossDCDeployment(Model(model_config(config)), params,
                             DeploymentConfig(**config["serving"]))
