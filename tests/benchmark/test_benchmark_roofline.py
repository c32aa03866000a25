"""``benchmark/roofline.py`` against a hand count at ``lfm2_moe_ep8``'s
widths and, for the expert without a gate, at ``nemotron_twotower_ep16``'s,
and the arithmetic of ``moe.experts_roofline`` on planted traces and
counters: the same grouped-product seconds under ``client_train``, bare
inside a ``round_step`` execution and bare inside an ``evaluate`` one. CPU:
counts and arithmetic only, no device number."""

import types

import pytest

from benchmark import (harness, manifest, roofline, trace_reduce as tr,
                       xplane_reader)
from olearning_sim_tpu.telemetry import SpanTracer, set_default_tracer

NAME = "moe.experts_roofline"
CELL = "lfm2_moe_ep8.8_silo_1k"
TASK = "cell-s1"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# One expert layer in one local step of one client: 8,192 tokens x top-4 of
# 64 experts, 8 held -> 4,096 rows; widths 2048 x 1536.
ROWS, EXPERTS, HIDDEN, INTERMEDIATE = 4096, 8, 2048, 1536


def test_the_grouped_products_flops_and_bytes_by_hand():
    work = roofline.grouped_swiglu(ROWS, 1, EXPERTS, HIDDEN, INTERMEDIATE)
    macs = 4096 * 2048 * 1536                       # one product, forward
    assert macs == 12_884_901_888
    assert work.flops == 2 * macs * 3 * 3 == 231_928_233_984
    weights = 8 * 3 * 2048 * 1536                   # 75,497,472 numbers
    assert weights == 75_497_472
    rows = 3 * 4096 * (2048 + 1536) * 2             # a pass: in and out rows
    assert rows == 88_080_384
    assert work.bytes == (3 * weights * 2           # bfloat16, read a pass
                          + weights * 4             # float32 gradients
                          + 3 * rows) == 1_019_215_872
    # 64 calls a round (4 expert layers x 2 steps x 8 clients) are 64 times
    # that, and rows and calls scale apart.
    round_ = roofline.grouped_swiglu(64 * ROWS, 64, EXPERTS, HIDDEN,
                                     INTERMEDIATE)
    assert round_.flops == 64 * work.flops
    assert round_.bytes == 64 * work.bytes
    half = roofline.grouped_swiglu(ROWS // 2, 1, EXPERTS, HIDDEN, INTERMEDIATE)
    assert half.flops == work.flops / 2
    assert half.bytes == 3 * weights * 2 + weights * 4 + 3 * rows // 2
    both = work + half
    assert (both.flops, both.bytes) == (work.flops + half.flops,
                                        work.bytes + half.bytes)


def test_the_two_matrix_forms_flops_and_bytes_by_hand():
    """``W2(relu(W1 x)^2)`` at nemotron's widths, 2688 x the PUBLISHED 1856
    (the program pads it to 2,048: time, not work); one expert layer in one
    local step of one client: 4,096 tokens x top-6 of 128 experts, 8 held
    -> 1,536 rows."""
    work = roofline.grouped_relu2(1536, 1, 8, 2688, 1856)
    macs = 1536 * 2688 * 1856                       # one product, forward
    assert macs == 7_662_993_408
    assert work.flops == 2 * macs * 2 * 3 == 91_955_920_896
    weights = 8 * 2 * 2688 * 1856                   # two matrices an expert
    assert weights == 79_822_848
    rows = 2 * 1536 * (2688 + 1856) * 2             # a pass: in and out rows
    assert rows == 27_918_336
    assert work.bytes == (3 * weights * 2 + weights * 4
                          + 3 * rows) == 881_983_488
    seconds, bound = roofline.least_seconds(work, V5E)
    assert bound == "bytes"                         # 192 rows an expert
    assert seconds == pytest.approx(1.0769e-3, rel=1e-4)
    assert work.flops / 197e12 == pytest.approx(0.46678e-3, rel=1e-4)
    # Two thirds of the gated form's work at the same shapes, to the unit.
    gated = roofline.grouped_swiglu(1536, 1, 8, 2688, 1856)
    assert (3 * work.flops, 3 * work.bytes) == (2 * gated.flops,
                                                2 * gated.bytes)
    round_ = roofline.grouped_relu2(48 * 1536, 48, 8, 2688, 1856)
    assert (round_.flops, round_.bytes) == (48 * work.flops, 48 * work.bytes)


def test_the_least_time_is_the_larger_bound_and_names_it():
    work = roofline.grouped_swiglu(ROWS, 1, EXPERTS, HIDDEN, INTERMEDIATE)
    seconds, bound = roofline.least_seconds(work, V5E)
    assert bound == "bytes"                         # 512 rows an expert
    assert seconds == pytest.approx(1_019_215_872 / 819e9)      # 1.244 ms
    assert work.flops / 197e12 == pytest.approx(1.1773e-3, rel=1e-4)
    full = roofline.grouped_swiglu(8 * ROWS, 1, EXPERTS, HIDDEN, INTERMEDIATE)
    seconds, bound = roofline.least_seconds(full, V5E)
    assert bound == "flops"                         # the deployment's 4,096
    assert seconds == pytest.approx(8 * 231_928_233_984 / 197e12)
    assert roofline.least_seconds(full, V5E, chips=4)[0] == pytest.approx(
        seconds / 4)
    assert roofline.share_percent(work, 2 * 1_019_215_872 / 819e9, V5E
                                  ) == pytest.approx(50.0)
    assert roofline.share_percent(work, 0.0, V5E) is None
    assert roofline.share_percent(roofline.Work(0.0, 0.0), 1.0, V5E) is None
    assert harness.load_peaks()["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert harness.load_peaks()["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


TRAIN_PATH = "jit(round_step)/while/body/closed_call/client_train/closed_call/"
# One execution of each program on the device, and what ran inside them:
# 0.20 + 0.10 s of training under ``moe.experts``, 0.07 s of the
# evaluation's, 0.03 s of the evaluation's grouped kernels (always bare),
# 1.0 s of a dense product, and 0.50 s of TRAINING's grouped kernels whose
# ``op_name`` and place are the case's.
TRACE = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Modules"
    events { metadata_id: 20 offset_ps: 0 duration_ps: 2000000000000 }
    events { metadata_id: 21 offset_ps: 2100000000000 duration_ps: 700000000000 }
  }
  lines { name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 200000000000 }
    events { metadata_id: 2 offset_ps: 200000000000 duration_ps: 100000000000 }
    events { metadata_id: 3 offset_ps: 300000000000 duration_ps: 1000000000000 }
    events { metadata_id: 4 offset_ps: %(grouped_at)d duration_ps: 500000000000 }
    events { metadata_id: 5 offset_ps: 2100000000000 duration_ps: 70000000000 }
    events { metadata_id: 6 offset_ps: 2170000000000 duration_ps: 30000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%%fusion.1 = bf16[8,4]{1,0} fusion(bf16[8,4] %%p), kind=kLoop"
    stats { metadata_id: 9 str_value: "%(train)sjvp(LFM2)/layers_1/moe/moe.experts/mul:" } } }
  event_metadata { key: 2 value { id: 2 name: "%%fusion.2 = bf16[8,4]{1,0} fusion(bf16[8,4] %%p), kind=kLoop"
    stats { metadata_id: 9 str_value: "%(train)stranspose(jvp(LFM2))/layers_1/moe/moe.experts/checkpoint/rematted_computation/mul:" } } }
  event_metadata { key: 3 value { id: 3 name: "%%fusion.3 = bf16[8,4]{1,0} fusion(bf16[8,4] %%p), kind=kOutput"
    stats { metadata_id: 9 str_value: "%(train)sjvp(LFM2)/layers_0/mlp/dot_general:" } } }
  event_metadata { key: 4 value { id: 4 name: "%%custom-call.4 = bf16[8,4]{1,0} custom-call(bf16[8,4] %%p)"
    stats { metadata_id: 9 str_value: "%(grouped)sragged-dot-none:" } } }
  event_metadata { key: 5 value { id: 5 name: "%%fusion.5 = bf16[8,4]{1,0} fusion(bf16[8,4] %%p), kind=kLoop"
    stats { metadata_id: 9 str_value: "jit(evaluate)/evaluate/LFM2/layers_1/moe/moe.experts/checkpoint/mul:" } } }
  event_metadata { key: 6 value { id: 6 name: "%%custom-call.6 = bf16[8,4]{1,0} custom-call(bf16[8,4] %%p)"
    stats { metadata_id: 9 str_value: "ragged-dot-none:" } } }
  event_metadata { key: 20 value { id: 20 name: "jit_round_step(7073764014247856539)" } }
  event_metadata { key: 21 value { id: 21 name: "jit_evaluate(12168728352446727221)" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
"""
# (training's grouped kernels' op_name prefix, their start in ps)
UNDER_CLIENT_TRAIN = (TRAIN_PATH, 1300000000000)    # the steps unrolled
BARE_IN_ROUND_STEP = ("", 1300000000000)            # the steps as a loop
BARE_IN_EVALUATE = ("", 2200000000000)              # not training's at all


def _trace(case):
    from jax.profiler import ProfileData

    grouped, grouped_at = case
    raw = ProfileData.text_proto_to_serialized_xspace(TRACE % dict(
        train=TRAIN_PATH, grouped=grouped, grouped_at=grouped_at))
    return tr.reduce_profile(ProfileData.from_serialized_xspace(raw),
                             metadata=xplane_reader.read_planes(raw))


@pytest.fixture
def planted():
    """``ctx(cell, case)``: a context with two traced rounds (3 and 4) of a
    window that holds more and the planted trace of ``case``; ``put``: a
    way to put counts on ``round.train.host_transfer``."""
    tracer = SpanTracer()
    old = set_default_tracer(tracer)

    def ctx(cell=CELL, case=UNDER_CLIENT_TRAIN):
        return types.SimpleNamespace(
            task={"task_id": TASK}, t_submitted=0.0,
            cell=manifest.load_cell(cell), peaks=V5E,
            device={"count": 1}, trace_rounds=2, trace_interval=(30.0, 50.0),
            rounds=[types.SimpleNamespace(idx=i, start=10.0 * i)
                    for i in range(1, 7)],
            trace=_trace(case))

    def put(round_idx, **attrs):
        tracer.record("bridge.build", 1.0, 1.0, task_id=TASK)
        tracer.record("round.train.host_transfer", 10.0 * round_idx + 1, 0.5,
                      task_id=TASK, round_idx=round_idx, **attrs)

    yield ctx, put
    set_default_tracer(old)


def _reader(name=NAME):
    return manifest.find_module("layer_metrics", name)


def test_the_planted_trace_puts_every_operation_in_its_program():
    device = _trace(BARE_IN_ROUND_STEP).devices[0]
    assert set(device.programs) == {"jit_round_step", "jit_evaluate"}
    for which in device.programs.values():
        assert set(which) <= set(device.scopes)
    assert sum(sum(p.values()) for p in device.programs.values()
               ) == pytest.approx(sum(device.scopes.values())) == pytest.approx(
        1.9)
    assert device.programs["jit_evaluate"] == {
        (("evaluate", "LFM2", "layers_1", "moe", "moe.experts", "mul"),
         tr.FORWARD): pytest.approx(0.07),
        (("ragged-dot-none",), tr.FORWARD): pytest.approx(0.03)}
    # Both programs' bare kernels land on ONE scope path; only the program
    # parts them.
    assert device.scopes[("ragged-dot-none",), tr.FORWARD] == pytest.approx(
        0.53)
    assert device.programs["jit_round_step"][
        ("ragged-dot-none",), tr.FORWARD] == pytest.approx(0.50)
    # An operation that starts outside every recorded execution has no
    # program, and a trace with no ``XLA Modules`` line none at all.
    late = _trace(("", 2900000000000)).devices[0]
    assert late.programs[""] == {
        (("ragged-dot-none",), tr.FORWARD): pytest.approx(0.50)}


@pytest.mark.parametrize("case,train_s", [
    (UNDER_CLIENT_TRAIN, 0.80), (BARE_IN_ROUND_STEP, 0.80),
    (BARE_IN_EVALUATE, 0.30)])
def test_the_share_is_the_traced_rounds_least_time_over_their_seconds_in_training(
        planted, case, train_s):
    ctx, put = planted
    ctx = ctx(case=case)
    counts = dict(clients_resident=8, local_steps=2)
    put(2, moe_assignments_local=999_999, **counts)         # before the stretch
    put(3, moe_assignments_local=260_000, moe_assignments_computed=1, **counts)
    put(4, moe_assignments_local=262_000, **counts)
    put(5, moe_assignments_local=999_999, **counts)         # starts at its end
    work = _reader().needed(ctx)
    by_hand = roofline.grouped_swiglu(522_000, 2 * 4 * 2 * 8, EXPERTS, HIDDEN,
                                      INTERMEDIATE)
    assert (work.flops, work.bytes) == (by_hand.flops, by_hand.bytes)
    # Training's grouped products: the scope and the renamed kernels inside
    # the round_step execution, with or without ``client_train`` on their
    # path; the evaluation's and the dense MLP's seconds stay out, and so
    # does a bare kernel that ran inside the evaluate execution.
    experts_ms = _reader("moe.experts.device_ms")
    assert experts_ms.train_seconds(ctx) == pytest.approx(train_s)
    least, bound = roofline.least_seconds(by_hand, V5E)
    assert bound == "bytes"
    assert _reader().read(ctx) == pytest.approx(100.0 * least / train_s)
    assert 0 < _reader().read(ctx) <= 100
    # The whole kernel's time a round, evaluation included, wherever the
    # kernels are.
    assert experts_ms.read(ctx) == pytest.approx(1e3 * 0.90 / 2)
    # What the rule was until PR 46 loses the loop's kernels.
    by_path = (ctx.trace.scope_seconds("client_train", "moe.experts")
               + ctx.trace.scope_seconds("client_train", "ragged-dot-none"))
    assert by_path == pytest.approx(
        0.80 if case is UNDER_CLIENT_TRAIN else 0.30)


@pytest.mark.parametrize("cell,form,layers,hidden,intermediate", [
    ("lfm2_moe_ep8.8_silo_1k", roofline.grouped_swiglu, 4, 2048, 1536),
    ("kimi_linear_ep32.8_silo_2k", roofline.grouped_swiglu, 4, 2304, 1024),
    ("nemotron_twotower_ep16.8_silo_2k", roofline.grouped_relu2, 3, 2688,
     1856),
])
def test_the_form_the_layers_and_the_widths_come_from_the_cells_file(
        planted, cell, form, layers, hidden, intermediate):
    ctx, put = planted
    ctx = ctx(cell=cell)
    put(3, moe_assignments_local=10_000, clients_resident=8, local_steps=2)
    put(4, moe_assignments_local=12_000, clients_resident=8, local_steps=2)
    assert _reader().expert_layers(ctx.cell.config["model"]) == layers
    work = _reader().needed(ctx)
    by_hand = form(22_000, 2 * layers * 2 * 8, 8, hidden, intermediate)
    assert (work.flops, work.bytes) == (by_hand.flops, by_hand.bytes)
    assert _reader().read(ctx) == pytest.approx(
        100.0 * roofline.least_seconds(by_hand, V5E)[0] / 0.80)
    entry = next(m for m in ctx.cell.per_layer if m["name"] == NAME)
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        _reader().UNIT, _reader().LAYER, _reader().MOVES)


def test_nothing_counted_leaves_the_metric_out(planted):
    ctx, put = planted
    dense = ctx(cell="phi4flash_vp8.8_silo_2k")
    ctx = ctx()
    assert _reader().read(ctx) is None              # no span tree at all
    put(3, clients_resident=8, local_steps=2)       # a model with no experts
    assert _reader().read(ctx) is None
    put(4, moe_assignments_local=0, clients_resident=8, local_steps=2)
    assert _reader().read(ctx) is None              # counted, and nothing
    put(4, moe_assignments_local=262_000, clients_resident=8, local_steps=2)
    assert _reader().read(ctx) is not None
    # A configuration without expert layers, whatever is on the spans.
    assert "held_experts" not in dense.cell.config["model"]
    assert _reader().read(dense) is None
    assert NAME not in [m["name"] for m in dense.cell.per_layer]
    ctx.trace = None                                # a run with no trace
    assert _reader().read(ctx) is None
