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
from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import numpy as np


def sown(intermediates, name: str) -> list:
    """The leaves a model's layers sowed under ``name`` in a forward pass's
    ``intermediates`` collection, in layer order."""
    flat = jax.tree_util.tree_flatten_with_path(intermediates)[0]
    return [leaf for path, leaf in flat
            if name in jax.tree_util.keystr(path)]


@dataclasses.dataclass(frozen=True)
class WorkCounts:
    """How a model counts its own work: ``gather(intermediates)`` makes one
    int32 array of what its layers sowed in a forward pass (None where they
    sowed nothing), and ``describe(summed)`` names such an array summed over
    some stretch of work, ``{name: number}``. The form of the array is the
    model's; a trainer only sums it."""

    gather: Callable[[Any], Any]
    describe: Callable[[np.ndarray], Dict[str, Any]]


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
    # Set where the model's layers sow counts of their own work.
    work_counts: Optional[WorkCounts] = None

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

    for mod in ("mlp", "cnn", "resnet", "transformer", "vit", "moe", "lfm2",
                "kimi_linear", "nemotron_h", "phi4flash"):
        qual = f"olearning_sim_tpu.models.{mod}"
        # Only true absence is optional; a present-but-broken module raises.
        if importlib.util.find_spec(qual) is not None:
            importlib.import_module(qual)
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]
