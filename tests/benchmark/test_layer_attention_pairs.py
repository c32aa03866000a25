"""``attention.pairs_computed_over_needed``: its entry in the manifest, its
arithmetic on planted spans, what it reports where the spans count nothing
(the parent's program, a model without causal attention), that the four
decoder families name the two counters it reads, and its value on the tiny
``lfm2_moe_ep8`` cell run through the real session. CPU: counts only."""

import json
import types

import numpy as np
import pytest

import tiny_preset
from benchmark import harness, manifest
from olearning_sim_tpu.models import get_model
from olearning_sim_tpu.models import lfm2
from olearning_sim_tpu.telemetry import SpanTracer, set_default_tracer

NAME = "attention.pairs_computed_over_needed"
CELLS = ["lfm2_moe_ep8.8_silo_1k", "kimi_linear_ep32.8_silo_2k",
         "nemotron_twotower_ep16.8_silo_2k", "phi4flash_vp8.8_silo_2k"]
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}
TASK = "cell-s1"


def read(ctx):
    return manifest.find_module("layer_metrics", NAME).read(ctx)


def test_the_manifest_lists_it_in_the_four_decoder_cells(listed_manifest):
    with open(listed_manifest, encoding="utf-8") as f:
        doc = json.load(f)
    entry = next(m for m in doc["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "x", "better": "lower",
        "source": "program_counter", "layer": "Kernels",
        "moves": "round_s.p50", "workloads": CELLS}
    for cell in doc["workloads"]:
        loaded = manifest.load_cell(cell["name"], listed_manifest)
        listed = NAME in [m["name"] for m in loaded.per_layer]
        assert listed == (cell["name"] in CELLS)
        # The cells on the list are the ones whose model calls
        # ``lfm2._attend`` and so names the two counters.
        if cell["name"] in CELLS:
            spec = get_model(loaded.config["task"]["operatorflow"][
                "operators"][0]["logical_simulation"]["operator_params"][
                "model"]["name"])
            # One summed row of the mixers' counts, no expert layer's.
            named = spec.work_counts.describe(np.zeros((1, 16), np.int64))
            assert {"attend_pairs_needed", "attend_pairs_computed"} <= set(
                named)


@pytest.fixture
def planted():
    tracer = SpanTracer()
    old = set_default_tracer(tracer)
    ctx = types.SimpleNamespace(
        task={"task_id": TASK}, t_submitted=0.0,
        window=types.SimpleNamespace(rounds=[
            types.SimpleNamespace(idx=i) for i in (1, 2, 3)]))

    def put(round_idx, **attrs):
        tracer.record("bridge.build", 1.0, 1.0, task_id=TASK)
        tracer.record("round.train.host_transfer", 10.0 + round_idx, 0.001,
                      task_id=TASK, round_idx=round_idx, **attrs)

    yield ctx, put
    set_default_tracer(old)


def test_the_ratio_is_computed_over_needed_of_the_windows_rounds(planted):
    ctx, put = planted
    needed, computed = lfm2.attend_pairs(2048)
    put(0, attend_pairs_needed=7, attend_pairs_computed=700)    # before it
    for round_idx in (1, 2, 3):
        put(round_idx, attend_pairs_needed=32 * needed,
            attend_pairs_computed=32 * computed)
    put(4, attend_pairs_needed=7, attend_pairs_computed=700)    # after it
    assert read(ctx) == pytest.approx(computed / needed)
    # What it tells apart at the cells' lengths: L x L under a mask, and the
    # blocks this repo computes.
    assert 2048 ** 2 / needed == pytest.approx(1.999, abs=5e-4)
    assert 1024 ** 2 / lfm2.attend_pairs(1024)[0] == pytest.approx(
        1.998, abs=5e-4)
    B = lfm2.BLOCK
    for L in (1024, 2048):
        needed, computed = lfm2.attend_pairs(L)
        assert computed / needed == pytest.approx(1 + (B - 1) / (L + 1))


def test_no_counts_on_the_spans_reports_nothing_and_raises_nothing(planted):
    ctx, put = planted
    assert read(ctx) is None              # no span tree at all
    put(1)                                # the parent's span
    assert read(ctx) is None
    put(2, attend_pairs_needed=0, attend_pairs_computed=0)  # no such layer
    assert read(ctx) is None


def test_the_tiny_cells_counts_reach_the_reader(tmp_path):
    path = tiny_preset.write(str(tmp_path), "lfm2_moe_ep8", "8_silo_1k")
    run = harness.run_cell("tiny.cell", 2**31 + 45, 0.3, False,
                           manifest_path=path, device=CPU)
    assert run.result["correct"] is True and run.result["failed"] == 0
    # 16 tokens inside one block: L x L scores for the causal half.
    assert read(run.ctx) == pytest.approx(16 * 16 / (16 * 17 / 2))
