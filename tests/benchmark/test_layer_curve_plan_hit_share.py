"""``runner.select.curve_plan_hit_share``: its arithmetic on planted spans,
what it reports where the spans count nothing (the parent's, a cell with no
``specific_interval`` strategy), and its value on the tiny ``128_spike``
cell run through the real session. CPU: counts only.

The reader has no entry in ``BENCHMARK.json`` yet (``PERF.md`` §7 says
which edit that waits for), so the harness does not call it; these tests
do."""

import types

import pytest

import tiny_preset
from benchmark import harness, manifest, program_spans
from olearning_sim_tpu.deviceflow import strategy
from olearning_sim_tpu.telemetry import SpanTracer, set_default_tracer

NAME = "runner.select.curve_plan_hit_share"
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}
TASK = "cell-s1"


def read(ctx):
    return manifest.find_module("layer_metrics", NAME).read(ctx)


def test_the_reader_states_the_entry_it_is_to_get():
    reader = manifest.find_module("layer_metrics", NAME)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        "Runner", "%", "program_counter", "round_s.p50")


@pytest.fixture
def planted():
    tracer = SpanTracer()
    old = set_default_tracer(tracer)
    ctx = types.SimpleNamespace(
        task={"task_id": TASK}, t_submitted=0.0,
        window=types.SimpleNamespace(rounds=[
            types.SimpleNamespace(idx=i) for i in (1, 2, 3, 4)]))

    def put(round_idx, **attrs):
        tracer.record("bridge.build", 1.0, 1.0, task_id=TASK)
        tracer.record("round.train.select.compile_trace", 10.0 + round_idx,
                      0.001, task_id=TASK, round_idx=round_idx, **attrs)

    yield ctx, put
    set_default_tracer(old)


def test_the_share_is_hits_over_hits_and_builds_of_the_windows_rounds(planted):
    ctx, put = planted
    put(0, curve_plan_hits=0, curve_plan_builds=1)      # round 0: before it
    for round_idx in (1, 2, 3):
        put(round_idx, curve_plan_hits=1, curve_plan_builds=0)
    assert read(ctx) == 100.0
    put(4, curve_plan_hits=0, curve_plan_builds=1)      # an absolute schedule
    assert read(ctx) == 75.0
    put(5, curve_plan_hits=0, curve_plan_builds=1)      # after it
    assert read(ctx) == 75.0


def test_no_counts_on_the_spans_reports_nothing_and_raises_nothing(planted):
    ctx, put = planted
    assert read(ctx) is None              # no span tree at all
    put(1)                                # the parent's span
    assert read(ctx) is None
    put(2, curve_plan_hits=0, curve_plan_builds=0)      # no strategy
    assert read(ctx) is None


def test_the_tiny_spike_cell_builds_in_round_0_and_finds_ever_after(tmp_path):
    strategy._curve_plans.clear()
    path = tiny_preset.write(str(tmp_path), "distilbert_sent140", "128_spike")
    run = harness.run_cell("tiny.cell", 2**31 + 31, 0.3, False,
                           manifest_path=path, device=CPU)
    assert run.result["correct"] is True and run.result["failed"] == 0
    spans = program_spans.task_spans(run.ctx)[
        "round.train.select.compile_trace"]
    found = [(s.attrs["curve_plan_hits"], s.attrs["curve_plan_builds"])
             for s in spans]
    assert found[0] == (0, 1)
    assert len(found) > 2 and set(found[1:]) == {(1, 0)}
    assert read(run.ctx) == 100.0
