"""The per-layer metrics read from the program's span tree
(``benchmark/program_spans.py`` and the twelve readers built on it): each
reader's arithmetic on planted spans, what they report for a program that
has no tree, and their values — with the tree itself — on the tiny CPU
cell run through the real session.

CPU runs: every number here is a count, a containment or a sum of the
program's own spans, never a speed."""

import os
import types

import pytest

import tiny_preset
from benchmark import harness, manifest, program_spans, window as win
from olearning_sim_tpu.telemetry import (
    SpanTracer, default_tracer, set_default_tracer)

NEW = ["intake.queue_wait_s", "bridge.generate_s", "bridge.place_s",
       "bridge.build_core_s", "startup.first_round_s",
       "startup.trace_lower_s", "startup.compile_or_load_s",
       "runner.select.compile_trace_ms", "runner.select.place_ms",
       "runner.select_ms.max", "runner.eval_upload_ms",
       "round_program.useful_work_share"]
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}
TASK = "cell-s1"


def read(name, ctx):
    return manifest.find_module("layer_metrics", name).read(ctx)


def test_every_new_metric_is_in_the_manifest_and_has_a_reader():
    import json

    listed = {m["name"]: m for m in
              json.load(open(manifest.MANIFEST))["per_layer"]}
    assert set(NEW) <= set(listed)
    for name in NEW:
        assert "workloads" not in listed[name]
        assert listed[name]["source"] in ("program_span", "program_counter")


@pytest.fixture
def planted():
    """A tracer holding one submission's tree with chosen times, an earlier
    run of the same task id, and another task's spans; yields the context a
    reader is given."""
    tracer = SpanTracer()
    old = set_default_tracer(tracer)

    def put(name, start, duration, task_id=TASK, **attrs):
        tracer.record(name, start, duration, task_id=task_id, **attrs)

    # An earlier submission of the same id in this process, and a neighbour.
    put("bridge.build", 10.0, 5.0)
    put("bridge.generate", 10.0, 4.0)
    put("compile.backend", 16.0, 30.0)
    put("bridge.generate", 101.0, 9.0, task_id="other")
    # This submission: submitTask returned at 100.0.
    put("task.queue_wait", 99.95, 0.25)
    put("bridge.build", 100.3, 3.0)
    put("bridge.build_fedcore", 100.3, 0.5)
    put("bridge.generate", 100.8, 1.0)
    put("bridge.place", 101.8, 0.75)
    put("bridge.generate", 102.55, 0.25)
    put("bridge.init_state", 103.3, 0.5)
    put("compile.trace", 103.3, 0.125, fun_name="make")
    put("compile.trace", 104.0, 2.0, fun_name="round_step", round_idx=0)
    put("compile.lower", 106.0, 1.0, round_idx=0)
    put("compile.cache_load", 107.0, 4.0, round_idx=0, retrieval_s=3.5)
    put("compile.backend", 111.0, 0.5, round_idx=0)
    put("compile.backend", 121.0, 8.0, round_idx=2)   # inside the window
    selects = {0: 0.2, 1: 0.16, 2: 0.28, 3: 0.18, 4: 0.15}
    trained = {0: 10, 1: 12, 2: 14, 3: 16, 4: 9}
    for r in range(5):
        t0 = 104.0 if r == 0 else 110.0 + 4.0 * r     # round 0 is long
        put("round.train", t0, 3.0, round_idx=r)
        put("round.train.select", t0, selects[r], round_idx=r)
        put("round.train.select.compile_trace", t0, selects[r] - 0.03,
            round_idx=r)
        put("round.train.select.mask", t0 + selects[r] - 0.03, 0.01,
            round_idx=r)
        put("round.train.select.place", t0 + selects[r] - 0.02, 0.02,
            round_idx=r)
        put("round.train.host_transfer", t0 + 0.5, 2.5, round_idx=r,
            clients_resident=32, clients_released=trained[r],
            clients_trained=trained[r], local_steps=2,
            samples_computed_per_step=6, samples_needed_per_step=4)
        put("round.evaluate", t0 + 3.0, 0.5, round_idx=r)
        put("round.evaluate.eval", t0 + 3.0, 0.5, round_idx=r)
        for b in range(2):                             # two eval batches
            put("round.evaluate.eval.place", t0 + 3.0 + 0.2 * b,
                0.004 * (r + 1), round_idx=r)
            put("round.evaluate.eval.fetch", t0 + 3.1 + 0.2 * b, 0.1,
                round_idx=r)
    rounds = win.rounds_from_spans(
        [s for s in tracer.spans() if s.start_s >= 100.0], TASK)
    ctx = types.SimpleNamespace(
        task={"task_id": TASK}, t_submitted=100.0, t_running=100.32,
        rounds=rounds, window=win.select_window(rounds, 1, 11.0))
    try:
        yield ctx
    finally:
        set_default_tracer(old)


def test_each_readers_arithmetic_on_planted_spans(planted):
    ctx = planted
    assert [r.idx for r in ctx.window.rounds] == [1, 2, 3]
    assert read("intake.queue_wait_s", ctx) == 0.25
    assert read("bridge.generate_s", ctx) == 1.25        # both, not the old run's
    assert read("bridge.place_s", ctx) == 0.75
    assert read("bridge.build_core_s", ctx) == 1.0       # build_fedcore + init_state
    assert read("startup.first_round_s", ctx) == pytest.approx(10.0)
    # Up to window open (114.0): the compile in round 2 is not set-up.
    assert read("startup.trace_lower_s", ctx) == 3.125
    assert read("startup.compile_or_load_s", ctx) == 4.5
    # Medians and the maximum over the window's rounds 1..3, in ms.
    assert read("runner.select.compile_trace_ms", ctx) == pytest.approx(150.0)
    assert read("runner.select.place_ms", ctx) == pytest.approx(20.0)
    assert read("runner.select_ms.max", ctx) == pytest.approx(280.0)
    # Both batches of a round are summed before the median: 2 * 4 ms * 3.
    assert read("runner.eval_upload_ms", ctx) == pytest.approx(24.0)
    assert read("round_program.useful_work_share", ctx) == pytest.approx(
        100.0 * (12 + 14 + 16) * 2 * 4 / (3 * 32 * 2 * 6))


def test_a_task_that_evaluates_nothing_uploads_zero(planted):
    for r in planted.window.rounds:
        r.spans = [s for s in r.spans if ".eval" not in s[0]]
    assert read("runner.eval_upload_ms", planted) == 0.0


def test_a_program_without_the_tree_reports_nothing_and_does_not_raise(
        planted):
    """The parent of the PR that added the tree: ``round.<op>[.<phase>]``
    spans only. Every reader built on the tree leaves its metric out."""
    tracer = SpanTracer()
    for s in default_tracer().spans():
        if s.name.startswith("round.") and s.name.count(".") <= 2:
            tracer.record(s.name, s.start_s, s.duration_s,
                          task_id=s.attrs["task_id"],
                          round_idx=s.attrs["round_idx"])
    set_default_tracer(tracer)           # the fixture restores the real one
    assert program_spans.task_spans(planted) is None
    for name in NEW:
        if name != "startup.first_round_s":      # reads the rounds alone
            assert read(name, planted) is None, name


# ------------------------------------------------------- the tiny CPU cell
@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    path = tiny_preset.write(str(tmp_path_factory.mktemp("tiny_spans")),
                             "distilbert_sent140", "128_spike")
    return harness.run_cell("tiny.cell", 2**31 + 25, 0.5, False,
                            manifest_path=path, device=CPU)


def test_one_task_leaves_one_span_tree_from_submit_to_every_stage(tiny_run):
    """task.queue_wait -> bridge.* -> compile.* -> round.<op>.<phase>
    .<stage>, every span carrying the task id, parents as documented, and
    the work counts on host_transfer equal to the history record's."""
    ctx = tiny_run.ctx
    task_id = ctx.task["task_id"]
    spans = [s for s in default_tracer().spans()
             if s.attrs.get("task_id") == task_id]
    by_id = {s.span_id: s for s in default_tracer().spans()}
    names = {s.name for s in spans}
    assert {"task.queue_wait", "bridge.build", "bridge.generate",
            "bridge.place", "bridge.build_fedcore", "bridge.init_state",
            "compile.trace", "compile.lower", "compile.backend",
            "round.train", "round.train.select",
            "round.train.select.compile_trace", "round.train.select.mask",
            "round.train.select.place", "round.train.train",
            "round.train.host_transfer", "round.evaluate.eval",
            "round.evaluate.eval.place", "round.evaluate.eval.compute",
            "round.evaluate.eval.fetch"} <= names
    # Neither deadline nor async planning is configured: no plan stage.
    assert "round.train.select.plan" not in names

    def parent(s):
        return by_id[s.parent_id].name if s.parent_id in by_id else None

    for s in spans:
        parts = s.name.split(".")
        if s.name in ("bridge.generate", "bridge.place",
                      "bridge.build_fedcore"):
            assert parent(s) == "bridge.build"
        elif parts[0] == "round" and len(parts) == 4:
            assert parent(s) == ".".join(parts[:3])
            assert s.attrs["round_idx"] == by_id[s.parent_id].attrs["round_idx"]
        elif parts[0] == "compile":
            # Under whatever the compiling thread had open, whose task and
            # round it names.
            assert parent(s) is not None and "fun_name" in s.attrs
    order = [s.name for s in sorted(spans, key=lambda s: s.start_s)]
    assert order.index("task.queue_wait") < order.index("bridge.build") < (
        order.index("bridge.init_state")) < order.index("round.train")
    # Round 0 compiles the round program under its train phase and the
    # evaluate program under eval's compute stage; later rounds lower and
    # compile nothing (no-retrace, seen from the span tree). jax also
    # reports a trace duration, of 0.1 ms, when a call that missed its C++
    # dispatch cache is answered from the Python-side trace cache, so a
    # later round may hold a compile.trace and nothing else.
    compiles = [s for s in spans if s.name.startswith("compile.")]
    assert {parent(s) for s in compiles if s.attrs.get("round_idx") == 0} == {
        "round.train.train", "round.evaluate.eval.compute"}
    assert not [s for s in compiles if s.attrs.get("round_idx", 0) > 0
                and s.name != "compile.trace"]
    assert any(s.attrs["fun_name"] == "round_step" for s in compiles)
    # The counts, where the work is known.
    records = {r["round"]: r["train"]["data_0"] for r in ctx.history}
    transfers = [s for s in spans if s.name == "round.train.host_transfer"]
    assert len(transfers) == len(records) >= 2
    resident = tiny_run.ctx.params["fedcore"]["block_clients"] * 8
    for s in transfers:
        rec = records[s.attrs["round_idx"]]
        assert s.attrs["clients_trained"] == rec["clients_trained"]
        assert s.attrs["clients_released"] == rec["released"]
        assert s.attrs["clients_resident"] == resident >= 16
        assert (s.attrs["local_steps"], s.attrs["samples_computed_per_step"],
                s.attrs["samples_needed_per_step"]) == (2, 6, 4)
    # The budget: at most 12 spans a round more than the 9 there were.
    per_round = [len(r.spans) for r in ctx.window.rounds]
    assert max(per_round) <= 9 + 12 and min(per_round) >= 9 + 6


def test_new_metrics_on_the_tiny_cell(tiny_run):
    ctx = tiny_run.ctx
    values = {name: read(name, ctx) for name in NEW}
    assert all(v is not None and v >= 0 for v in values.values()), values
    outside = read("intake.submit_to_running_s", ctx)
    assert 0 < values["intake.queue_wait_s"] <= (
        outside + program_spans.SUBMIT_SLACK_S)
    # The three bridge stages lie between the submit and the first round
    # (the harness's own bridge.build_s starts when its 20 ms poll SAW the
    # task running, a little after the bridge started).
    stages = (values["bridge.generate_s"] + values["bridge.place_s"]
              + values["bridge.build_core_s"])
    assert 0 < stages <= read("bridge.build_s", ctx) + outside
    # Round 0 holds the compiles (the test process has no persistent cache).
    assert values["startup.first_round_s"] > max(
        r.seconds for r in ctx.window.rounds)
    assert 0 < values["startup.trace_lower_s"] + (
        values["startup.compile_or_load_s"]) < (
        ctx.window.open - ctx.t_submitted)
    assert values["runner.select_ms.max"] >= (
        values["runner.select.compile_trace_ms"]
        + values["runner.select.place_ms"]) > 0
    # released / resident x needed / computed samples: 16 clients padded to
    # 32 rows on the 8-device test mesh, 4 of 6 local samples a step.
    inside = {r.idx for r in ctx.window.rounds}
    released = [rec["train"]["data_0"]["released"] for rec in ctx.history
                if rec["round"] in inside]
    assert values["round_program.useful_work_share"] == pytest.approx(
        100.0 * sum(released) / (32 * len(released)) * 4 / 6)
    # A traced result line carries every one of them.
    cell = manifest.load_cell("tiny.cell", os.path.join(
        os.path.dirname(ctx.cell.files_root), "BENCHMARK.json"))
    traced = harness._read_metrics(cell.per_layer, "layer_metrics", ctx)
    assert set(NEW) <= set(traced)
