"""The seven per-layer metrics that read set-up and device memory from
inside the program (``benchmark/setup_memory_spans.py`` and the readers
built on it, ``round_program.recomputed_share`` on the reducer's label):
each reader's arithmetic on planted spans, that the three memory parts and
what was there before sum to the planted peak, what each reports for a
program without the new span or attribute, and their values on the tiny
CPU cell run through the real session.

CPU runs: every number here is a count, a containment or a sum of the
program's own spans, never a speed."""

import contextlib
import dataclasses
import json
import types

import pytest

import tiny_preset
from benchmark import harness, manifest, trace_reduce as tr, window as win
from olearning_sim_tpu.telemetry import SpanTracer, set_default_tracer

SETUP = ["startup.process_to_serving_s", "startup.round_program_ready_s",
         "startup.programs_compiled"]
MEMORY = ["device.hbm_data_gb", "device.hbm_state_gb",
          "device.hbm_program_gb"]
RECOMPUTED = "round_program.recomputed_share"
NEW = SETUP + MEMORY + [RECOMPUTED]
CHIP_CELLS = ["distilbert_sent140.128_spike", "distilbert_sent140.128_full",
              "lfm2_moe_ep8.8_silo_1k", "kimi_linear_ep32.8_silo_2k",
              "nemotron_twotower_ep16.8_silo_2k"]
CPU = {"platform": "cpu", "kind": "cpu", "count": 8}
TASK = "cell-s1"
GB = 10 ** 9
BEFORE, PLACED, STATE, PEAK = 2 * GB // 10, 3 * GB, 5 * GB, 13 * GB


def read(name, ctx):
    return manifest.find_module("layer_metrics", name).read(ctx)


@pytest.mark.parametrize("name", NEW)
def test_the_metric_is_appended_to_the_manifest_with_its_reader(
        name, listed_manifest):
    doc = json.load(open(listed_manifest))
    listed = doc["per_layer"]
    entry = next(m for m in listed if m["name"] == name)
    if name in MEMORY:
        # Listed for the cells whose backend keeps allocator statistics:
        # the five on the chip at least (a later cell appends itself). The
        # tiny CPU cell takes the entries without a list, and has none.
        assert set(CHIP_CELLS) <= set(entry["workloads"]) <= {
            w["name"] for w in doc["workloads"]}
    else:
        assert "workloads" not in entry       # every cell reports it
    # After PR 40's entries (the list held 44 then), not last: every later
    # PR appends its own.
    assert listed.index(entry) >= 44
    reader = manifest.find_module("layer_metrics", name)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert entry["better"] == "lower"


def _plant(tracer, new=True):
    """One submission's tree (submitTask returned at 100.0) after an
    earlier run of the same task id and an earlier session; ``new`` False
    leaves out what this PR's program adds (the parent's tree)."""
    def put(name, start, duration, task_id=TASK, **attrs):
        if not new:
            attrs = {k: v for k, v in attrs.items()
                     if k != "program" and not k.startswith("device_")}
        tracer.record(name, start, duration, task_id=task_id, **attrs)

    if new:
        tracer.record("session.start", 2.0, 0.5, process_age_s=9.0,
                      services=["taskmgr"])
        tracer.record("session.start", 90.0, 0.25, process_age_s=15.5,
                      services=["taskmgr"])
        tracer.record("session.start", 300.0, 0.125, process_age_s=200.0,
                      services=["taskmgr"])          # after the submit
    # The earlier run of the same id: its stamps must not be read.
    put("bridge.build", 10.0, 5.0, device_bytes_in_use_before=1,
        device_peak_bytes_before=1)
    put("bridge.place", 11.0, 1.0, device_bytes_in_use=2,
        device_peak_bytes=2)
    put("compile.backend", 16.0, 30.0, program="round_step")
    # This submission.
    put("task.queue_wait", 99.95, 0.25)
    put("bridge.build", 100.3, 3.0, device_bytes_in_use_before=BEFORE,
        device_peak_bytes_before=BEFORE + 7)
    put("bridge.place", 101.8, 0.75, device_bytes_in_use=PLACED,
        device_peak_bytes=PLACED)
    put("bridge.init_state", 103.3, 0.5, device_bytes_in_use=STATE,
        device_peak_bytes=STATE + GB)
    put("compile.trace", 103.3, 0.125, fun_name="make", program="make")
    put("compile.backend", 103.5, 0.25, fun_name="jit(make)",
        program="make")
    put("compile.trace", 104.0, 2.0, fun_name="round_step", round_idx=0,
        program="round_step")
    put("compile.lower", 106.0, 1.0, fun_name="jit(round_step)",
        round_idx=0, program="round_step")
    put("compile.cache_load", 107.0, 4.0, fun_name="jit(round_step)",
        round_idx=0, retrieval_s=3.5, program="round_step")
    put("compile.trace", 111.0, 0.5, fun_name="evaluate", round_idx=0,
        program="evaluate")
    put("compile.backend", 111.5, 0.75, fun_name="jit(evaluate)",
        round_idx=0, program="evaluate")
    put("compile.backend", 112.5, 0.0625, fun_name="jit(multiply)",
        round_idx=0, program="multiply")
    put("compile.backend", 121.0, 8.0, fun_name="jit(round_step)",
        round_idx=2, program="round_step")       # inside the window
    for r in range(5):
        t0 = 104.0 if r == 0 else 110.0 + 4.0 * r
        put("round.train", t0, 3.0, round_idx=r)
        put("round.train.host_transfer", t0 + 0.5, 2.5, round_idx=r,
            clients_trained=8, device_bytes_in_use=STATE + r,
            device_peak_bytes=PEAK - GB + (GB if r >= 1 else 0))
        put("round.evaluate", t0 + 3.0, 0.5, round_idx=r)
        put("round.evaluate.eval", t0 + 3.0, 0.5, round_idx=r,
            device_bytes_in_use=STATE + r,
            device_peak_bytes=PEAK + (r if r <= 3 else 10 * GB))
        put("round.evaluate.eval.fetch", t0 + 3.1, 0.1, round_idx=r,
            device_peak_bytes=99 * GB)           # a stage, not a phase
    rounds = win.rounds_from_spans(
        [s for s in tracer.spans() if s.start_s >= 100.0], TASK)
    return types.SimpleNamespace(
        task={"task_id": TASK}, t_submitted=100.0, t_running=100.32,
        rounds=rounds, window=win.select_window(rounds, 1, 11.0),
        memory_peak_bytes=PEAK + 3, trace=None)


@contextlib.contextmanager
def planted_tree(new=True):
    tracer = SpanTracer()
    old = set_default_tracer(tracer)
    try:
        yield _plant(tracer, new), tracer
    finally:
        set_default_tracer(old)


EXPECTED = {
    # The session the task was submitted to: age 15.5 s + 0.25 s to serve.
    "startup.process_to_serving_s": 15.75,
    # round_step's trace + lower + cache load before the window (114.0).
    "startup.round_program_ready_s": 7.0,
    # make, evaluate, multiply; round 2's is not set-up, the load is a hit.
    "startup.programs_compiled": 3,
    "device.hbm_data_gb": (PLACED - BEFORE) / GB,
    "device.hbm_state_gb": (STATE - PLACED) / GB,
    # The eval phase of the window's last round (3), not round 4's.
    "device.hbm_program_gb": (PEAK + 3 - STATE) / GB,
}


@pytest.mark.parametrize("name", SETUP + MEMORY)
def test_each_readers_arithmetic_on_planted_spans(name):
    with planted_tree() as (ctx, _):
        assert [r.idx for r in ctx.window.rounds] == [1, 2, 3]
        assert read(name, ctx) == pytest.approx(EXPECTED[name], abs=1e-12)


@pytest.mark.parametrize("name", SETUP + MEMORY)
def test_a_program_without_the_span_or_attribute_reports_nothing(name):
    """The parent's tree: no session.start, no program, no stamps."""
    with planted_tree(new=False) as (ctx, _):
        assert read(name, ctx) is None


def test_the_memory_parts_and_what_was_there_sum_to_the_peak():
    with planted_tree() as (ctx, _):
        parts = sum(read(name, ctx) for name in MEMORY)
        assert parts + BEFORE / GB == pytest.approx(
            read("device.hbm_peak_gb", ctx), rel=1e-9)


@pytest.mark.parametrize("span, attr", [
    ("bridge.build", "device_bytes_in_use_before"),
    ("bridge.place", "device_bytes_in_use"),
    ("bridge.init_state", "device_bytes_in_use"),
    ("round.train.host_transfer", "device_peak_bytes"),
])
def test_a_missing_stamp_leaves_the_parts_built_on_it_out(span, attr):
    """A streamed population is never placed whole, a backend may keep no
    statistics: nothing is guessed."""
    with planted_tree() as (ctx, tracer):
        for s in tracer.spans():
            if s.name in (span, "round.evaluate.eval"):
                s.attrs.pop(attr, None)
        assert [read(name, ctx) for name in MEMORY] == [None] * 3


def test_recomputed_share_is_the_reducers_label_over_all_operation_time():
    """Two devices' operations by ``trace_reduce.scope_path``'s own label:
    the share is recomputed time over all operation time, scoped or not."""
    body = "jit(round_step)/jit(main)/while/body/client_train/"
    seconds = {
        body + "jvp(Block)/moe.experts/dot_general": 0.004,
        body + "checkpoint/rematted_computation/Block/moe.experts/"
               "dot_general": 0.001,
        body + "checkpoint/rematted_computation/Block/mul": 0.0005,
        body + "transpose(jvp(Block))/moe.experts/dot_general": 0.003,
        "jit(round_step)/jit(main)/copy": 0.0015,
    }
    scopes = {tr.scope_path(name): s for name, s in seconds.items()}
    assert sorted(which for _, which in scopes) == [
        tr.BACKWARD, tr.FORWARD, tr.FORWARD, tr.RECOMPUTED, tr.RECOMPUTED]
    devices = [tr.DeviceTrace(index=i, busy_s=0.01, start_s=0.0, end_s=0.01,
                              modules={}, ops={}, collective_s=0.0, gaps=[],
                              scopes=scopes) for i in range(2)]
    ctx = types.SimpleNamespace(
        trace=tr.TraceSummary(devices, 0.0, 0.01, None))
    assert read(RECOMPUTED, ctx) == pytest.approx(100.0 * 0.0015 / 0.01)
    assert read(RECOMPUTED, types.SimpleNamespace(trace=None)) is None
    idle = tr.TraceSummary([dataclasses.replace(devices[0], scopes={})],
                           0.0, 0.01, None)
    assert read(RECOMPUTED, types.SimpleNamespace(trace=idle)) is None


# ------------------------------------------------------- the tiny CPU cell
@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    path = tiny_preset.write(str(tmp_path_factory.mktemp("tiny_setup")),
                             "distilbert_sent140", "128_spike")
    return harness.run_cell("tiny.cell", 2**31 + 41, 0.5, False,
                            manifest_path=path, device=CPU)


def test_the_tiny_cell_reads_set_up_from_inside_the_program(tiny_run):
    ctx = tiny_run.ctx
    serving = read("startup.process_to_serving_s", ctx)
    # The harness's own stretch starts at its first line and ends once
    # submitTask has returned; the program's starts with the process
    # (pytest's own start, here) and ends with the session serving.
    from olearning_sim_tpu.telemetry import default_tracer, process_age_s

    age_at_submit = process_age_s() - (default_tracer().now()
                                       - ctx.t_submitted)
    assert 0 <= age_at_submit - serving <= 0.5
    ready = read("startup.round_program_ready_s", ctx)
    assert 0 < ready <= (read("startup.trace_lower_s", ctx)
                         + read("startup.compile_or_load_s", ctx))
    # No persistent cache in the tests: round_step, evaluate and the
    # initialiser at least were compiled, all before the window.
    assert read("startup.programs_compiled", ctx) >= 3
    # The CPU's allocator keeps no statistics: left out, not guessed.
    assert [read(name, ctx) for name in MEMORY] == [None] * 3
    assert read(RECOMPUTED, ctx) is None              # no trace: --trace 0
    traced = harness._read_metrics(ctx.cell.per_layer, "layer_metrics", ctx)
    assert set(SETUP) <= set(traced) and not set(MEMORY) & set(traced)
