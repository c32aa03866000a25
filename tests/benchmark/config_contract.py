"""What a configuration's contract test starts from: the configuration's
file found by name in BENCHMARK.json, the model block of its task, the
registry's spec for it, and the parameter shapes of the model as the
program builds it (``jax.eval_shape``: nothing is computed)."""

import json
import os

import jax

from benchmark import manifest


def load(name: str):
    """(the configuration's file, its task's ``model`` block, the
    registry's spec of that model) for configuration ``name``."""
    from olearning_sim_tpu.models import get_model

    with open(manifest.MANIFEST, encoding="utf-8") as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == name)
    with open(os.path.join(manifest.ROOT, entry["file"]),
              encoding="utf-8") as f:
        config = json.load(f)
    task_model = next(
        op["logical_simulation"]["operator_params"]["model"]
        for op in config["task"]["operatorflow"]["operators"]
        if isinstance(op["logical_simulation"]["operator_params"], dict))
    return config, task_model, get_model(task_model["name"])


def stated_input(stated: dict) -> list:
    """The input of one sample as the file's ``model`` block states it:
    ``input_shape``, or for a token model its ``sequence_length``."""
    if "input_shape" in stated:
        return list(stated["input_shape"])
    return [stated["sequence_length"]]


def init_shapes(spec, task_model: dict) -> dict:
    """``{"A/B/kernel": shape}`` of the model built from the task's
    overrides and initialised on one sample of the task's input."""
    module = spec.build(**task_model.get("overrides", {}))
    sample = jax.ShapeDtypeStruct((1, *task_model["input_shape"]),
                                  spec.input_dtype)
    tree = jax.eval_shape(
        lambda x: module.init(jax.random.key(0), x), sample)["params"]
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(p, "key", p)) for p in path): leaf.shape
            for path, leaf in leaves}
