"""A tiny preset for the CPU tests: the real configuration with the model
shrunk through the task's own ``model.overrides``, a traffic mix of a few
clients, and a manifest that names them — written into a temporary
directory as NEW files and entries only. It never enters BENCHMARK.json."""

import copy
import json
import os

from benchmark import manifest

TINY_MODELS = {
    "distilbert_sent140": {
        "overrides": {"num_classes": 2, "vocab_size": 64, "max_len": 8,
                      "width": 16, "depth": 1, "heads": 2, "mlp_dim": 32},
        "model": {"dim": 16, "n_layers": 1, "n_heads": 2, "hidden_dim": 32,
                  "vocab_size": 64, "sequence_length": 8,
                  "max_position_embeddings": 8},
        "input_shape": [8],
        "traffic": {"clients": 16, "n_local": 6,
                    "fedcore": {"block_clients": 4, "batch_size": 4,
                                "max_local_steps": 2},
                    "deviceflow": "keep"},
        # CPU readings at this size, 4 checks each (tests/benchmark only; the
        # chip's are in PERF.md), sound <= / carry_dtype=bf16 >= / planted
        # server faults >=: pseudo_grad_global 0.0082 / 0.165; param_delta
        # _global 0.0026 / 0.064 / 0.295; pseudo_grad worst leaf 0.0165 / 1.87.
        "limits": {"clients_trained_gap": 0, "client_loss_gap": 0.05,
                   "pseudo_grad_global_rel_l2": 0.05,
                   "param_delta_global_rel_l2": 0.03,
                   "pseudo_grad_rel_l2": 0.2},
    },
}


def write(tmp_path, base_config: str, base_traffic: str,
          extra_metric: bool = False) -> str:
    """Writes ``<tmp>/BENCHMARK.json`` + ``<tmp>/bench/...`` for a tiny cell
    ``tiny.cell`` built from ``base_config``; returns the manifest path."""
    real = json.load(open(manifest.MANIFEST))
    tiny = TINY_MODELS[base_config]
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
