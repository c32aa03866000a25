"""benchmark/flops.py against hand counts."""

import json
import os

import pytest

from benchmark import flops, manifest


def _model(config):
    body = json.load(open(os.path.join(manifest.HERE, "configs",
                                       config + ".json")))
    return manifest.find_module("reference", body["reference"]), body["model"]


def test_layer_passes_and_samples_per_step():
    first = flops.dense("first", 1, 10, 20, input_grad=False)
    later = flops.dense("later", 4, 20, 5)
    scores = flops.matmul("scores", 4, 8, 4)
    assert (first.macs, later.macs, scores.macs) == (200, 400, 128)
    assert flops.forward_flops([first, later, scores]) == 2 * 728
    # forward + weight gradient everywhere, input gradient but for the first
    assert flops.train_flops([first, later, scores]) == 2 * (3 * 728 - 200)
    # a batch of 32 draws from 20 local samples has at most 20 distinct ones
    assert flops.samples_per_step(32, 20) == 20
    assert flops.samples_per_step(16, 24) == 16


def test_round_flops_follow_residents_steps_and_eval():
    layers = [flops.dense("only", 1, 10, 10)]
    r = flops.round_flops(layers, clients=100, local_steps=10, batch_size=32,
                          n_local=20, eval_n=50)
    assert r["train_samples"] == 100 * 10 * 20
    assert r["train"] == 20000 * 600 and r["evaluate"] == 50 * 200
    assert r["total"] == r["train"] + r["evaluate"]
    assert flops.round_flops(layers, clients=100, local_steps=10,
                             batch_size=32, n_local=20)["evaluate"] == 0


def test_distilbert_per_sample_hand_count():
    ref, model = _model("distilbert_sent140")
    layers = ref.layers(model)
    L, W, M = 64, 768, 3072
    per_layer = L * (4 * W * W + 2 * W * M) + 2 * L * L * W
    assert sum(l.macs for l in layers) == 6 * per_layer + W * 2
    assert per_layer == 459_276_288
    # every layer feeds a trainable layer below it: 3 passes of 2 flops a mac
    assert flops.train_flops(layers) == 6 * (6 * per_layer + W * 2)
    assert flops.train_flops(layers) == pytest.approx(16.534e9, rel=1e-3)


def test_distilbert_cell_round_from_the_composed_task():
    cell = manifest.load_cell("distilbert_sent140.128_spike")
    task = manifest.compose_task(cell, 1)
    ref, model = _model("distilbert_sent140")
    r = flops.cell_round_flops(ref.layers(model), manifest.engine_params(task),
                               cell.traffic["clients"], evaluates=True)
    assert r["train_samples"] == 128 * 4 * 16
    assert r["total"] == pytest.approx(
        8192 * 16.534e9 + 2048 * 16.534e9 / 3, rel=1e-3)   # ~146.7 TFLOP
