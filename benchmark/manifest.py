"""BENCHMARK.json -> one cell's files -> the task JSON that is submitted.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name
``BENCHMARK.json`` gives it:

- ``benchmark/configs/<config>.json``   (the manifest's ``file``)
- ``benchmark/traffic/<traffic>.json``
- ``benchmark/layer_metrics/<metric>.py``
- ``benchmark/reference/<reference>.py`` (named by the configuration file)

No cell, model or metric name appears in this module or in the harness: a
later PR adds files and manifest entries and edits nothing here.
"""

from __future__ import annotations

import copy
import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

# Rounds asked of the task: the window is closed by stopTask, never by the
# task running out of rounds (60 s of the shortest rounds seen is ~10^4).
ROUNDS_NEVER_REACHED = 1_000_000


class ManifestError(ValueError):
    """BENCHMARK.json or one of the files it names is not usable."""


def _read_json(path: str) -> Any:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files it names, loaded."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]       # benchmark/configs/<config>.json
    traffic: Dict[str, Any]      # benchmark/traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]   # manifest entries this cell reports
    per_layer: List[Dict[str, Any]]
    files_root: str = HERE       # the manifest's first path: where files are found


def _reports(metric: Dict[str, Any], cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(workload: str, manifest_path: str = MANIFEST) -> Cell:
    """The cell ``workload`` of the manifest at ``manifest_path``; file
    paths in the manifest are relative to the manifest's directory, and
    traffic files sit in its first path's ``traffic/`` directory."""
    manifest = _read_json(manifest_path)
    root = os.path.dirname(os.path.abspath(manifest_path))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise ManifestError(
            f"no workload {workload!r} in {manifest_path} "
            f"(known: {sorted(cells)})")
    entry = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    if entry["config"] not in configs:
        raise ManifestError(
            f"workload {workload!r} names unknown config {entry['config']!r}")
    config = _read_json(os.path.join(root, configs[entry["config"]]["file"]))
    files_root = os.path.join(root, manifest["paths"][0])
    traffic = _read_json(os.path.join(
        files_root, "traffic", entry["traffic"] + ".json"))
    e2e = [m for m in manifest["end_to_end"] if _reports(m, workload)]
    names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if _reports(m, workload) and m["moves"] in names]
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=entry["config"], traffic_name=entry["traffic"],
        config=config, traffic=traffic, end_to_end=e2e, per_layer=layer,
        files_root=files_root,
    )


def load_module(directory: str, name: str):
    """``<directory>/<name>.py`` as a module (metric names hold dots, so
    these files are loaded by path, not imported by name)."""
    path = os.path.join(directory, name + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_file_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_module(kind: str, name: str, files_root: str = HERE):
    """The module ``<kind>/<name>.py`` (kind: ``end_to_end``,
    ``layer_metrics`` or ``reference``) under the manifest's first path,
    else under this directory."""
    for base in (files_root, HERE):
        if os.path.exists(os.path.join(base, kind, name + ".py")):
            return load_module(os.path.join(base, kind), name)
    raise ManifestError(f"no {kind}/{name}.py under {files_root} or {HERE}")


def task_id_for(workload: str, seed: int) -> str:
    return f"{workload}-s{seed}"


def compose_task(cell: Cell, seed: int,
                 fedcore_overrides: Optional[Dict[str, Any]] = None
                 ) -> Dict[str, Any]:
    """The task JSON of one run: the configuration's template with the
    traffic mix's population, engine tuning, operators and deviceflow
    strategy, and the seed. ``--seed`` sets ``data.synthetic.seed`` and the
    task id (the runner seeds the initial parameters from crc32(task_id)),
    so data, weights and participation traces are functions of the seed.

    ``fedcore_overrides`` is for the lower-precision control only
    (``benchmark/control.py``); a benchmark run never passes it.
    """
    config, traffic = cell.config, cell.traffic
    task = copy.deepcopy(config["task"])
    task["task_id"] = task_id_for(cell.name, seed)
    clients = int(traffic["clients"])
    for data in task["target"]["data"]:
        data["total_simulation"]["nums"] = [clients]
        data["total_simulation"]["dynamic_nums"] = [0]
        data["allocation"]["logical_simulation"] = [clients]
    task["operatorflow"]["flow_setting"]["round"] = int(
        traffic.get("rounds", ROUNDS_NEVER_REACHED))

    wanted = list(traffic["operators"])
    by_name = {op["name"]: op for op in task["operatorflow"]["operators"]}
    missing = [n for n in wanted if n not in by_name]
    if missing:
        raise ManifestError(
            f"traffic {cell.traffic_name!r} asks for operators {missing} "
            f"that configuration {cell.config_name!r} does not define")
    operators = [by_name[n] for n in wanted]
    for op in operators:
        op["input"] = [i for i in op["input"] if i in wanted]
        sim = op["logical_simulation"]
        params = sim.get("operator_params")
        if isinstance(params, dict):
            params = copy.deepcopy(params)
            params.setdefault("fedcore", {}).update(traffic.get("fedcore", {}))
            if fedcore_overrides:
                params["fedcore"].update(fedcore_overrides)
            synthetic = params.setdefault("data", {}).setdefault(
                "synthetic", {})
            synthetic["seed"] = int(seed)
            synthetic["n_local"] = int(traffic["n_local"])
            sim["operator_params"] = json.dumps(params)
            controller = op["operation_behavior_controller"]
            strategy = traffic.get("deviceflow")
            controller["use_gradient_house"] = strategy is not None
            controller["strategy_gradient_house"] = (
                json.dumps(strategy) if strategy is not None else "")
    task["operatorflow"]["operators"] = operators
    return task


def engine_params(task: Dict[str, Any]) -> Dict[str, Any]:
    """The engine-params object of a composed task (first operator that
    carries one) — what flops.py and the check read sizes from."""
    for op in task["operatorflow"]["operators"]:
        params = op["logical_simulation"].get("operator_params")
        if params:
            return json.loads(params)
    raise ManifestError("composed task has no engine params")


def train_operator_names(task: Dict[str, Any]) -> List[str]:
    return [op["name"] for op in task["operatorflow"]["operators"]
            if op["logical_simulation"]["operator_code_path"]
            == "builtin:train"]
