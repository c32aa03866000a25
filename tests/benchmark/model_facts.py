"""What a cell's model emits, read off the model itself and not off a list
of names: the named scopes its forward pass opens and whether it marks a
lookup-only table (``models/lookup.py``). The tests that hold a per-layer
metric's ``workloads`` list to the cells that have something to read derive
the cells from this, so a cell a later PR appends is asked for on the lists
its model emits and on no other.

The model is the cell's own (the composed task's ``model.name``) at the
widths of its tiny preset (``data/tiny/<config>.json``; the configuration's
own where it has none, as the rehearsal's stand-in), traced once through
``init`` from shapes alone: nothing is computed."""

import dataclasses
import functools
import json
from typing import FrozenSet

import jax

import tiny_preset
from benchmark import manifest, trace_reduce


@dataclasses.dataclass(frozen=True)
class Facts:
    scopes: FrozenSet[str]          # ``moe.experts``, ``lfm2.attention``, ..
    marks_lookup_table: bool
    next_token: bool                # the task's loss opens ``lm_loss``
    evaluates: bool                 # the traffic mix has the operator


def _name_stacks(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.update(str(eqn.source_info.name_stack).split("/"))
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _name_stacks(inner, found)


@functools.lru_cache(maxsize=None)
def _traced(model_name: str, overrides: str, input_shape: tuple):
    from olearning_sim_tpu.models import get_model
    from olearning_sim_tpu.models.lookup import LOOKUP_ROWS

    spec = get_model(model_name)
    model = spec.build(**json.loads(overrides))
    x = jax.ShapeDtypeStruct(
        (1,) + (input_shape or spec.example_input_shape), spec.input_dtype)
    jaxpr, shapes = jax.make_jaxpr(model.init, return_shape=True)(
        jax.random.key(0), x)
    found = set()
    _name_stacks(jaxpr.jaxpr, found)
    marked = jax.tree_util.tree_flatten_with_path(
        shapes.get("perturbations", {}))[0]
    return (frozenset(c for c in found if trace_reduce.KERNEL_SCOPE.match(c)),
            any(LOOKUP_ROWS in jax.tree_util.keystr(p) for p, _ in marked))


def of(cell: manifest.Cell) -> Facts:
    task = manifest.compose_task(cell, 1)
    model = manifest.engine_params(task)["model"]
    try:
        tiny = tiny_preset.load(cell.config_name)
    except FileNotFoundError:
        tiny = {"overrides": model.get("overrides", {})}
    shape = tiny.get("input_shape", model.get("input_shape"))
    scopes, marks = _traced(
        model["name"], json.dumps(tiny["overrides"], sort_keys=True),
        tuple(shape) if shape else ())
    return Facts(
        scopes=scopes, marks_lookup_table=marks,
        next_token=any(d.get("task_type") == "next_token_prediction"
                       for d in task["target"]["data"]),
        evaluates="evaluate" in cell.traffic["operators"])


def cells_where(manifest_path: str, holds) -> list:
    """The names of the manifest's cells, in its order, whose facts
    ``holds`` accepts."""
    with open(manifest_path, encoding="utf-8") as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    return [n for n in names if holds(of(manifest.load_cell(n, manifest_path)))]
