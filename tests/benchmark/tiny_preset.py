"""A tiny preset for the CPU tests: the real configuration with the model
shrunk through the task's own ``model.overrides``, a traffic mix of a few
clients, and a manifest that names them — written into a temporary
directory as NEW files and entries only. It never enters BENCHMARK.json."""

import copy
import json
import os

from benchmark import manifest

TINY_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "tiny")


def load(config_name: str) -> dict:
    """The CPU preset of configuration ``config_name``:
    ``data/tiny/<config>.json`` with ``overrides`` (the task's model
    overrides), ``model`` (the sizes the file's model block then states),
    ``input_shape``, ``traffic``, ``limits`` and, as ``why``, the CPU
    readings the limits were set from. A configuration brings its own."""
    path = os.path.join(TINY_DIR, config_name + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"configuration {config_name!r} has no tiny preset: add {path}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def write(tmp_path, base_config: str, base_traffic: str,
          extra_metric: bool = False) -> str:
    """Writes ``<tmp>/BENCHMARK.json`` + ``<tmp>/bench/...`` for a tiny cell
    ``tiny.cell`` built from ``base_config``; returns the manifest path."""
    real = json.load(open(manifest.MANIFEST))
    tiny = load(base_config)
    files = os.path.join(tmp_path, "bench")
    for sub in ("configs", "traffic", "layer_metrics"):
        os.makedirs(os.path.join(files, sub), exist_ok=True)

    config = json.load(open(os.path.join(
        manifest.HERE, "configs", base_config + ".json")))
    config["name"] = "tiny"
    config["check"] = {"limits": tiny["limits"]}
    config["model"].update(tiny["model"])
    for op in config["task"]["operatorflow"]["operators"]:
        params = op["logical_simulation"].get("operator_params")
        if isinstance(params, dict):
            params["model"]["overrides"] = tiny["overrides"]
            if "input_shape" in tiny:
                params["model"]["input_shape"] = tiny["input_shape"]
            params["data"]["eval_n"] = 32
            if "vocab_size" in tiny["overrides"]:
                params["data"]["synthetic"]["vocab_size"] = (
                    tiny["overrides"]["vocab_size"])
    json.dump(config, open(os.path.join(files, "configs", "tiny.json"), "w"))

    traffic = json.load(open(os.path.join(
        manifest.HERE, "traffic", base_traffic + ".json")))
    mix = copy.deepcopy(tiny["traffic"])
    if mix.pop("deviceflow", None) == "keep" and traffic.get("deviceflow"):
        strategy = copy.deepcopy(traffic["deviceflow"])
        strategy["flow_dispatch"]["total_dispatch_amount"] = mix["clients"]
        mix["deviceflow"] = strategy
    else:
        mix["deviceflow"] = None
    traffic.update(mix)
    traffic.update(warmup_rounds=1, trace_rounds=1, check_clients=3)
    json.dump(traffic, open(os.path.join(files, "traffic", "cell.json"), "w"))

    per_layer = [m for m in real["per_layer"] if "workloads" not in m]
    if extra_metric:
        with open(os.path.join(files, "layer_metrics",
                               "extra.rounds_seen.py"), "w") as f:
            f.write('LAYER = "Runner"\nUNIT = "count"\n'
                    'SOURCE = "program_span"\nMOVES = "round_s.p50"\n\n\n'
                    'def read(ctx):\n    return len(ctx.rounds)\n')
        per_layer.append({"name": "extra.rounds_seen", "unit": "count",
                          "better": "higher", "source": "program_span",
                          "layer": "Runner", "moves": "round_s.p50"})
    doc = {
        "command": real["command"], "paths": ["bench"], "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "tests", "reduced": [],
                     "file": "bench/configs/tiny.json", "why": "tests"}],
        "workloads": [{"name": "tiny.cell", "config": "tiny",
                       "traffic": "cell", "chips": 1, "why": "tests"}],
        "end_to_end": [m for m in real["end_to_end"] if "workloads" not in m],
        "per_layer": per_layer,
    }
    path = os.path.join(tmp_path, "BENCHMARK.json")
    json.dump(doc, open(path, "w"))
    return path
