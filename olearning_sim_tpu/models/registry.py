"""Model registry.

The reference keeps models entirely inside user operator code (the
``ofl_commons`` model/optimizer/trainer wrappers named in its north star are
absent from the open-source snapshot; the surviving contract is the operator
param schema, ``ols_core/taskMgr/base/base_operator.py:15-52``). The rebuild
makes the model zoo a first-class, registry-addressable component so a task
JSON can name a model (``"model": {"name": "cnn4", ...}``) and the engine can
construct it without shipping code archives.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import flax.linen as nn
import numpy as np


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    builder: Callable[..., nn.Module]
    # Example input shape WITHOUT batch dim, used for init and compile checks.
    example_input_shape: Tuple[int, ...]
    num_classes: int
    defaults: Dict[str, Any]
    # Input element dtype (np.int32 for token models, np.float32 otherwise).
    input_dtype: Any = np.float32
    # False where the model cannot be ``jax.vmap``ped over per-client
    # weights (a grouped matmul batches over a leading axis only): the
    # round engine then takes clients one at a time (``block_clients`` 1).
    vmap_clients: bool = True

    def build(self, **overrides) -> nn.Module:
        kwargs = dict(self.defaults)
        kwargs.update(overrides)
        return self.builder(**kwargs)


_REGISTRY: Dict[str, ModelSpec] = {}


def register_model(spec: ModelSpec) -> ModelSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f"duplicate model name: {spec.name}")
    _REGISTRY[spec.name] = spec
    return spec


def get_model(name: str) -> ModelSpec:
    # Import model modules lazily so registration happens on first lookup.
    import importlib
    import importlib.util

    for mod in ("mlp", "cnn", "resnet", "transformer", "vit", "moe", "lfm2"):
        qual = f"olearning_sim_tpu.models.{mod}"
        # Only true absence is optional; a present-but-broken module raises.
        if importlib.util.find_spec(qual) is not None:
            importlib.import_module(qual)
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]
