"""The rehearsal of a second configuration: a manifest in a temporary
directory that holds a copy of the real one — every entry, and the
configuration and traffic files its cells name — and beside it the
stand-in configuration of ``data/rehearsal/``, landed the way a
``model_config`` PR has to land one: new files under the benchmark's first
path and new manifest entries, nothing edited. Readers and references the
copy does not bring are found in the real ``benchmark/`` (the manifest's
fallback)."""

import json
import os
import shutil

from benchmark import manifest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "data", "rehearsal")
CELL = "mlp2_standin.16_full"


def write(tmp_path: str) -> str:
    """Builds the rehearsal's tree under ``tmp_path``; returns the path of
    its BENCHMARK.json."""
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        doc = json.load(f)
    files = os.path.join(tmp_path, doc["paths"][0])
    for config in doc["configs"]:
        target = os.path.join(tmp_path, config["file"])
        os.makedirs(os.path.dirname(target), exist_ok=True)
        shutil.copy(os.path.join(manifest.ROOT, config["file"]), target)
    os.makedirs(os.path.join(files, "traffic"), exist_ok=True)
    for cell in doc["workloads"]:
        name = cell["traffic"] + ".json"
        shutil.copy(os.path.join(manifest.HERE, "traffic", name),
                    os.path.join(files, "traffic", name))
    # The stand-in: its files, then its entries, each list appended to.
    for entry in os.scandir(DATA):
        if entry.is_dir():
            shutil.copytree(entry.path, os.path.join(files, entry.name),
                            dirs_exist_ok=True,
                            ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(DATA, "entries.json"), encoding="utf-8") as f:
        for key, entries in json.load(f).items():
            doc[key] += entries
    path = os.path.join(tmp_path, "BENCHMARK.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1)
    return path
