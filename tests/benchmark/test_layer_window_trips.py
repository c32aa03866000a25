"""``moe.window_trips_per_call``: its entry in the manifest, its arithmetic
on planted spans, what it reports where the spans count nothing (the
parent's program, a model without an expert layer), that the three sparse
decoder families name the counter it reads, and its value on a tiny
``lfm2_moe_ep8`` cell run through the real session. CPU: counts only."""

import json
import types

import numpy as np
import pytest

import tiny_preset
from benchmark import harness, manifest
from olearning_sim_tpu.models import get_model
from olearning_sim_tpu.telemetry import SpanTracer, set_default_tracer

NAME = "moe.window_trips_per_call"
COUNTER = "moe_window_trips"
CELLS = ["lfm2_moe_ep8.8_silo_1k", "kimi_linear_ep32.8_silo_2k",
         "nemotron_twotower_ep16.8_silo_2k"]
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}
TASK = "cell-s1"


def read(ctx):
    return manifest.find_module("layer_metrics", NAME).read(ctx)


def test_the_manifest_lists_it_in_the_three_sparse_cells(listed_manifest):
    with open(listed_manifest, encoding="utf-8") as f:
        doc = json.load(f)
    entry = next(m for m in doc["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "trips/call", "better": "lower",
        "source": "program_counter", "layer": "Expert layer",
        "moves": "round_s.p50", "workloads": CELLS}
    for cell in doc["workloads"]:
        loaded = manifest.load_cell(cell["name"], listed_manifest)
        listed = NAME in [m["name"] for m in loaded.per_layer]
        assert listed == (cell["name"] in CELLS)
        if cell["name"] not in CELLS:
            continue
        # The cells on the list are those whose model has expert layers
        # and names the counter beside their counts.
        params = loaded.config["task"]["operatorflow"]["operators"][0][
            "logical_simulation"]["operator_params"]
        assert params["model"]["overrides"]["held_experts"]
        spec = get_model(params["model"]["name"])
        # An expert layer's row of 3 + 8, the mixers' summed row, the trips'.
        named = spec.work_counts.describe(np.zeros((3, 11), np.int64))
        assert {COUNTER, "moe_assignments_local"} <= set(named)


@pytest.fixture
def planted():
    tracer = SpanTracer()
    old = set_default_tracer(tracer)
    ctx = types.SimpleNamespace(
        task={"task_id": TASK}, t_submitted=0.0,
        cell=manifest.load_cell(CELLS[0]),
        window=types.SimpleNamespace(rounds=[
            types.SimpleNamespace(idx=i) for i in (1, 2, 3)]))

    def put(round_idx, **attrs):
        tracer.record("bridge.build", 1.0, 1.0, task_id=TASK)
        tracer.record("round.train.host_transfer", 10.0 + round_idx, 0.001,
                      task_id=TASK, round_idx=round_idx, **attrs)

    yield ctx, put
    set_default_tracer(old)


def test_the_trips_are_taken_over_the_windows_expert_layer_steps(planted):
    ctx, put = planted
    # ``lfm2_moe_ep8``: 5 layers, one dense, so 4 expert layers; 8 silos of
    # 2 local steps are 64 calls a round.
    counts = dict(moe_assignments_local=1, moe_assignments_computed=1,
                  clients_resident=8, local_steps=2)
    put(0, **counts, **{COUNTER: 6400})                     # before it
    put(1, **counts, **{COUNTER: 64})
    put(2, **counts, **{COUNTER: 64})
    assert read(ctx) == 1.0
    put(3, **counts, **{COUNTER: 64 + 16})     # a quarter took two trips
    assert read(ctx) == pytest.approx((3 * 64 + 16) / (3 * 64))
    put(4, **counts, **{COUNTER: 6400})                     # after it


def test_no_trips_on_the_spans_reports_nothing_and_raises_nothing(planted):
    ctx, put = planted
    assert read(ctx) is None              # no span tree at all
    put(1)                                # a model without expert layers
    assert read(ctx) is None
    # The parent's program: the expert layers' counts and no trips.
    put(2, moe_assignments_local=5, moe_assignments_computed=5,
        clients_resident=8, local_steps=2)
    assert read(ctx) is None


def test_the_tiny_cells_trips_reach_the_reader(tmp_path):
    path = tiny_preset.write(str(tmp_path), "lfm2_moe_ep8", "8_silo_1k")
    run = harness.run_cell("tiny.cell", 2**31 + 47, 0.3, False,
                           manifest_path=path, device=CPU)
    assert run.result["correct"] is True and run.result["failed"] == 0
    # 64 tokens a step x 4 slots = 256 assignments, two of 16 experts
    # held: a window is all 256 rows, so no call takes two trips, and the four
    # clients that pad the population to the mesh (all their tokens alike)
    # draw none in a layer and take none.
    assert 0.5 < read(run.ctx) <= 1.0
    assert manifest.find_module(
        "layer_metrics", "moe.dropped_assignments").read(run.ctx) == 0
