"""``benchmark/roofline_delta_rule.py`` against a hand count at
``kimi_linear_ep32``'s widths (one local step of one KDA layer: 4,096
tokens, 32 heads, a 128 x 128 state), and the arithmetic of
``kda.chunk_scan_roofline`` on planted scope seconds and counters. CPU:
counts and arithmetic only, no device number."""

import types

import pytest

import model_facts
from benchmark import manifest, roofline, roofline_delta_rule
from benchmark import trace_reduce as tr
from olearning_sim_tpu.telemetry import SpanTracer, set_default_tracer

NAME = "kda.chunk_scan_roofline"
CELL = "kimi_linear_ep32.8_silo_2k"
TASK = "cell-s1"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TOKENS, CHUNKS, HEADS, KEYS, VALUES = 4096, 64, 32, 128, 128


def test_the_recurrences_flops_and_bytes_by_hand():
    work = roofline_delta_rule.gated_delta_rule(
        TOKENS, CHUNKS, HEADS, KEYS, VALUES)
    macs = 4096 * 32 * 3 * 128 * 128            # three products with S
    assert macs == 6_442_450_944
    assert work.flops == 2 * macs * 3 == 38_654_705_664
    # A token of a head, a pass: q, k, v, o in bfloat16, g in float32, beta.
    a_token = 32 * (4 * 128 * 2 + 128 * 4 + 4)
    assert a_token == 49_280
    states = 64 * 32 * 128 * 128 * 4            # what enters the chunks
    assert states == 134_217_728
    assert work.bytes == 3 * 4096 * a_token + 2 * states == 873_988_096
    # Tokens and chunks scale apart, and a round is a sum of its steps.
    half = roofline_delta_rule.gated_delta_rule(
        TOKENS // 2, CHUNKS, HEADS, KEYS, VALUES)
    assert half.flops == work.flops / 2
    assert half.bytes == 3 * 2048 * a_token + 2 * states
    round_ = roofline_delta_rule.gated_delta_rule(
        64 * TOKENS, 64 * CHUNKS, HEADS, KEYS, VALUES)
    assert (round_.flops, round_.bytes) == (64 * work.flops, 64 * work.bytes)


def test_the_bytes_bound_holds_at_these_widths():
    work = roofline_delta_rule.gated_delta_rule(
        TOKENS, CHUNKS, HEADS, KEYS, VALUES)
    seconds, bound = roofline.least_seconds(work, V5E)
    assert bound == "bytes"
    assert seconds == pytest.approx(873_988_096 / 819e9)        # 1.067 ms
    assert work.flops / 197e12 == pytest.approx(0.1962e-3, rel=1e-3)


@pytest.fixture
def planted():
    """Two traced rounds (3 and 4) of a window that holds more, a trace
    with planted scope seconds, and a way to put counts on
    ``round.train.host_transfer``."""
    tracer = SpanTracer()
    old = set_default_tracer(tracer)
    kda = ("client_train", "KimiLinear", "layers_1", "kda")
    scopes = {
        (kda + ("kda.chunk_scan", "dot_general"), tr.FORWARD): 0.30,
        (kda + ("kda.chunk_scan", "exp"), tr.RECOMPUTED): 0.50,
        (kda + ("kda.chunk_scan", "dot_general"), tr.BACKWARD): 0.40,
        (kda + ("kda.projections", "dot_general"), tr.FORWARD): 0.25,
        (("evaluate", "KimiLinear", "layers_1", "kda", "kda.chunk_scan",
          "dot_general"), tr.FORWARD): 0.06,
    }
    device = tr.DeviceTrace(index=0, busy_s=1.9, start_s=0.0, end_s=2.0,
                            modules={}, ops={}, collective_s=0.0, gaps=[],
                            scopes=scopes)
    ctx = types.SimpleNamespace(
        task={"task_id": TASK}, t_submitted=0.0,
        cell=manifest.load_cell(CELL), peaks=V5E,
        device={"count": 1}, trace_rounds=2, trace_interval=(30.0, 50.0),
        rounds=[types.SimpleNamespace(idx=i, start=10.0 * i)
                for i in range(1, 7)],
        trace=tr.TraceSummary(devices=[device], start_s=0.0, end_s=2.0,
                              sync_s=None))

    def put(round_idx, **attrs):
        tracer.record("bridge.build", 1.0, 1.0, task_id=TASK)
        tracer.record("round.train.host_transfer", 10.0 * round_idx + 1, 0.5,
                      task_id=TASK, round_idx=round_idx, **attrs)

    yield ctx, put
    set_default_tracer(old)


def _reader(name=NAME):
    return manifest.find_module("layer_metrics", name)


def test_the_share_is_the_traced_rounds_least_time_over_trainings_scope_time(
        planted):
    ctx, put = planted
    put(2, kda_scan_tokens=999_999, kda_scan_chunks=999)    # before
    put(3, kda_scan_tokens=262_144, kda_scan_chunks=4_096)
    put(4, kda_scan_tokens=262_144, kda_scan_chunks=4_096)
    put(5, kda_scan_tokens=999_999, kda_scan_chunks=999)    # starts at its end
    work = _reader().needed(ctx)
    by_hand = roofline_delta_rule.gated_delta_rule(
        2 * 262_144, 2 * 4_096, HEADS, KEYS, VALUES)
    assert (work.flops, work.bytes) == (by_hand.flops, by_hand.bytes)
    least, bound = roofline.least_seconds(by_hand, V5E)
    assert bound == "bytes"
    # Training's scan: forward, recomputed and backward; not evaluation's,
    # not the projections'.
    assert _reader().read(ctx) == pytest.approx(100.0 * least / 1.20)
    assert 0 < _reader().read(ctx) <= 100
    # The by-scope times a round take the evaluation's too.
    assert _reader("kda.chunk_scan.device_ms").read(ctx) == pytest.approx(
        1e3 * 1.26 / 2)
    assert _reader("kda.projections.device_ms").read(ctx) == pytest.approx(
        1e3 * 0.25 / 2)
    assert _reader("mla.attention.device_ms").read(ctx) == 0.0
    assert _reader("moe.shared_expert.device_ms").read(ctx) == 0.0


def test_nothing_counted_leaves_the_metric_out(planted):
    ctx, put = planted
    assert _reader().read(ctx) is None              # no span tree at all
    put(3, clients_resident=8, local_steps=2)       # a model with no scan
    assert _reader().read(ctx) is None
    put(4, kda_scan_tokens=0, kda_scan_chunks=0)    # counted, and nothing
    assert _reader().read(ctx) is None
    put(4, kda_scan_tokens=262_144, kda_scan_chunks=4_096)
    assert _reader().read(ctx) is not None
    ctx.trace = None                                # a run with no trace
    assert all(_reader(name).read(ctx) is None for name in (
        NAME, "kda.chunk_scan.device_ms", "kda.projections.device_ms",
        "mla.attention.device_ms", "moe.shared_expert.device_ms"))


@pytest.mark.parametrize("name,layer,unit", [
    ("kda.chunk_scan.device_ms", "Kernels", "ms"),
    ("kda.projections.device_ms", "Kernels", "ms"),
    ("mla.attention.device_ms", "Kernels", "ms"),
    ("moe.shared_expert.device_ms", "Expert layer", "ms"),
    (NAME, "Kernels", "%"),
])
def test_a_reader_agrees_with_its_manifest_entry(name, layer, unit):
    cell = manifest.load_cell(CELL)
    entry = next(m for m in cell.per_layer if m["name"] == name)
    reader = _reader(name)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"]) == (
        layer, unit, "device_trace", "device_rounds_per_s")
    # The model's own kernels are this cell's alone; the shared expert's
    # scope is opened by every model that has one, found from the models.
    assert entry["workloads"] == (
        [CELL] if name != "moe.shared_expert.device_ms"
        else model_facts.cells_where(
            manifest.MANIFEST, lambda f: "moe.shared_expert" in f.scopes))
