"""``round_program.table_rows_written_share``: its manifest entry, its
arithmetic on planted spans, what it reports where the program counts
nothing (the parent's, or a model that marks no table), and its value on
the tiny DistilBERT cell run through the real session. CPU: counts only."""

import json
import types

import pytest

import model_facts
import tiny_preset
from benchmark import harness, manifest, program_spans
from olearning_sim_tpu.telemetry import SpanTracer, set_default_tracer

NAME = "round_program.table_rows_written_share"
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}
TASK = "cell-s1"


def read(ctx):
    return manifest.find_module("layer_metrics", NAME).read(ctx)


def test_the_manifest_lists_it_in_the_cells_whose_model_marks_a_table(
        listed_manifest):
    with open(listed_manifest, encoding="utf-8") as f:
        doc = json.load(f)
    entry = next(m for m in doc["per_layer"] if m["name"] == NAME)
    # Found from the models (``models/lookup.py``'s mark in a traced
    # ``init``), in the manifest's order: a cell a later PR appends is asked
    # for here if its model marks a table.
    cells = model_facts.cells_where(listed_manifest,
                                    lambda f: f.marks_lookup_table)
    assert cells[:2] == ["distilbert_sent140.128_spike",
                         "distilbert_sent140.128_full"]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "Round program",
        "moves": "device_rounds_per_s", "workloads": cells}
    for cell in doc["workloads"]:
        listed = NAME in [m["name"] for m in
                          manifest.load_cell(cell["name"],
                                             listed_manifest).per_layer]
        assert listed == (cell["name"] in cells)


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
        tracer.record("round.train.host_transfer", 10.0 + round_idx, 0.5,
                      task_id=TASK, round_idx=round_idx, **attrs)

    yield ctx, put
    set_default_tracer(old)


def test_the_share_is_written_over_total_median_over_the_windows_rounds(
        planted):
    ctx, put = planted
    put(0, table_rows_total=30522, table_rows_written_per_step=30522)  # warm-up
    put(1, table_rows_total=30522, table_rows_written_per_step=1024)
    put(2, table_rows_total=30522, table_rows_written_per_step=1024)
    put(3, table_rows_total=30522, table_rows_written_per_step=30522)
    assert read(ctx) == pytest.approx(100.0 * 1024 / 30522)     # 3.355%


def test_no_counts_on_the_spans_reports_nothing_and_raises_nothing(planted):
    ctx, put = planted
    assert read(ctx) is None              # no span tree at all
    put(1, clients_resident=8)            # the parent's counts, or lfm2's
    assert read(ctx) is None


def test_the_tiny_cell_reads_the_rows_its_batches_look_up(tmp_path):
    path = tiny_preset.write(str(tmp_path), "distilbert_sent140", "128_full")
    run = harness.run_cell("tiny.cell", 2**31 + 29, 0.3, False,
                           manifest_path=path, device=CPU)
    assert run.result["correct"] is True and run.result["failed"] == 0
    preset = tiny_preset.load("distilbert_sent140")
    rows, ids = preset["model"]["vocab_size"], preset["input_shape"][0]
    inside = {r.idx for r in run.ctx.window.rounds}
    attrs = [s.attrs for s in program_spans.task_spans(run.ctx)[
                 "round.train.host_transfer"]
             if s.attrs["round_idx"] in inside]
    assert len(attrs) == len(inside)
    for a in attrs:
        assert a["table_rows_total"] == rows
        assert a["table_rows_written_per_step"] == (
            a["samples_computed_per_step"] * ids)
    assert read(run.ctx) == pytest.approx(
        100.0 * attrs[0]["samples_computed_per_step"] * ids / rows)
