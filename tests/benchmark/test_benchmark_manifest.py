"""BENCHMARK.json against the contract it has to meet, the files it names,
and the composition of a task from configuration + traffic + seed: once for
the real manifest, once for the rehearsal's (``rehearsal.py``: the real one
with a second configuration landed beside it as new files and entries), so
that what a later configuration has to meet is met here first."""

import json
import os
import re

import pytest

import rehearsal
from benchmark import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection")


@pytest.fixture(scope="module", params=["BENCHMARK.json", "rehearsal"])
def manifest_path(request, tmp_path_factory):
    if request.param == "rehearsal":
        return rehearsal.write(str(tmp_path_factory.mktemp("rehearsal")))
    return manifest.MANIFEST


@pytest.fixture(scope="module")
def root(manifest_path):
    return os.path.dirname(manifest_path)


@pytest.fixture(scope="module")
def doc(manifest_path):
    with open(manifest_path, encoding="utf-8") as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(doc, manifest_path):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(manifest_path) <= 64 * 1024
    assert 1 <= len(doc["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in doc["paths"])
    assert doc["paths"] == ["benchmark", "tests/benchmark"]
    assert len(doc["command"]) <= 32 and all(_line(w) for w in doc["command"])
    assert doc["command"][-1].startswith("benchmark/")
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    for key, most in (("configs", 24), ("workloads", 24), ("end_to_end", 16),
                      ("per_layer", 128)):
        assert 1 <= len(doc[key]) <= most


def test_configs(doc, root):
    names = [c["name"] for c in doc["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in doc["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in doc["workloads"]}
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(word in key for word in WIDTH_WORDS)
        body = json.load(open(os.path.join(root, c["file"])))
        # The file states its own cuts, and they are the manifest's.
        assert body["reduced"] == c["reduced"]
        assert set(body["reduced_why"]) == set(c["reduced"])
        for key in ("source", "precision", "guarantees", "assumed", "task",
                    "reference", "algorithm", "model", "check"):
            assert key in body, key
        assert body["check"]["limits"], "a cell needs the check's limits"


def test_workloads(doc, manifest_path):
    names = [w["name"] for w in doc["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in doc["configs"]}
    four = sum(1 for w in doc["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(names) // 4)
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        cell = manifest.load_cell(w["name"], manifest_path)   # files load
        assert cell.traffic["chips"] == w["chips"]
        for key in ("clients", "n_local", "operators", "warmup_rounds",
                    "trace_rounds", "check_clients"):
            assert key in cell.traffic, key
        manifest.find_module("reference", cell.config["reference"],
                             cell.files_root)
        manifest.find_module(
            "reference", "server_" + cell.config["algorithm"]["name"],
            cell.files_root)


def test_metrics(doc, manifest_path):
    cells = {w["name"] for w in doc["workloads"]}
    names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"]) and m["moves"] in e2e
        # Every cell that reports the metric reports what it moves.
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", moved)) <= moved
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        reported = [m for m in doc["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert len(reported) >= 2 and any(
            m["name"] == "setup_s" for m in reported)
        assert manifest.load_cell(cell, manifest_path).per_layer


def test_every_metric_has_its_reader_and_they_agree(doc, root):
    files_root = os.path.join(root, doc["paths"][0])
    for m in doc["end_to_end"]:
        reader = manifest.find_module("end_to_end", m["name"], files_root)
        assert (reader.UNIT, reader.SOURCE) == (m["unit"], m["source"])
        assert callable(reader.read)
    for m in doc["per_layer"]:
        reader = manifest.find_module("layer_metrics", m["name"], files_root)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
        assert callable(reader.read)


def test_files_under_paths_are_named_from_name_characters(doc, root):
    for base in doc["paths"]:
        for folder, dirs, files in os.walk(os.path.join(root, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), root)
                assert PATH.match(rel), rel


def test_the_harness_holds_no_cell_model_or_metric_name(doc):
    names = ([w["name"] for w in doc["workloads"]]
             + [c["name"] for c in doc["configs"]]
             + [w["traffic"] for w in doc["workloads"]]
             + [m["name"] for m in doc["end_to_end"] + doc["per_layer"]
                if m["name"] != "setup_s"])
    for module in ("run.py", "harness.py", "manifest.py", "window.py",
                   "check.py", "trace_reduce.py", "flops.py", "control.py"):
        text = open(os.path.join(manifest.HERE, module)).read()
        for name in names:
            assert name not in text, (module, name)


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_compose_task(doc, manifest_path, seed):
    for w in doc["workloads"]:
        cell = manifest.load_cell(w["name"], manifest_path)
        task = manifest.compose_task(cell, seed)
        assert task["task_id"] == f"{w['name']}-s{seed}"
        data = task["target"]["data"][0]
        assert data["allocation"]["logical_simulation"] == [
            cell.traffic["clients"]]
        assert task["logical_simulation"]["resource_request"][0][
            "num_request"] == [1]
        ops = task["operatorflow"]["operators"]
        assert [op["name"] for op in ops] == cell.traffic["operators"]
        params = manifest.engine_params(task)
        assert params["data"]["synthetic"]["seed"] == seed
        assert params["data"]["synthetic"]["n_local"] == cell.traffic["n_local"]
        for key, value in cell.traffic["fedcore"].items():
            assert params["fedcore"][key] == value
        assert "carry_dtype" not in params["fedcore"]
        controller = ops[0]["operation_behavior_controller"]
        assert controller["use_gradient_house"] == bool(
            cell.traffic.get("deviceflow"))
        # The program's own intake accepts it.
        from olearning_sim_tpu.taskmgr.codecs import json2taskconfig
        from olearning_sim_tpu.taskmgr.validation import (
            validate_task_parameters)

        ok, msg = validate_task_parameters(json2taskconfig(json.dumps(task)))
        assert ok, msg
        # The configuration's template is not written to.
        assert cell.config["task"]["task_id"] == "set-by-compose"
    control = manifest.compose_task(cell, seed, {"carry_dtype": "bf16"})
    assert manifest.engine_params(control)["fedcore"]["carry_dtype"] == "bf16"


def test_unknown_names_are_errors():
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell("no.such_cell")
    with pytest.raises(manifest.ManifestError):
        manifest.find_module("layer_metrics", "no.such_metric")
