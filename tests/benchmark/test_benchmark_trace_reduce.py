"""benchmark/trace_reduce.py against a small recorded trace.

``data/tiny_round.xplane.pb.gz`` is the first 120 ms of device events (the
``XLA Ops``, ``XLA Modules`` and ``Steps`` lines of ``/device:TPU:0``, names
only) of a traced stretch of the tests' tiny cnn4 preset on one TPU v5e
chip (PR 24), plus the host's clock-sync annotation. The numbers below were
read from it once; every later PR has to compute the same ones."""

import gzip
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    with gzip.open(os.path.join(HERE, "data", "tiny_round.xplane.pb.gz")) as f:
        return tr.reduce_profile(ProfileData.from_serialized_xspace(f.read()))


def test_busy_idle_and_stretch(summary):
    assert len(summary.devices) == 1 and summary.devices[0].index == 0
    assert summary.start_s == pytest.approx(0.048427023, abs=1e-9)
    assert summary.window_s == pytest.approx(0.11958634, rel=1e-6)
    assert summary.busy_s == pytest.approx(0.004658115, rel=1e-6)
    assert summary.busy_share == pytest.approx(0.0389519, rel=1e-5)
    assert summary.worst_idle_share == pytest.approx(1 - 0.0389519, rel=1e-5)
    assert summary.sync_s == pytest.approx(0.044045094, abs=1e-9)
    # busy is a union: the while ops enclose their bodies, the plain sum of
    # the leaf ops is a little less than the union of everything.
    assert sum(summary.devices[0].ops.values()) == pytest.approx(
        0.004512157, rel=1e-6)


def test_programs_and_collectives(summary):
    assert summary.devices[0].modules["jit_round_step"][0] == 9
    assert summary.devices[0].modules["jit_evaluate"][0] == 10
    executions, seconds = summary.module_seconds(r"round_step")
    assert executions == 9 and seconds == pytest.approx(0.004625648, rel=1e-6)
    assert summary.collective_share == 0.0      # one chip: none emitted


def test_top_ops_are_named_short_and_leave_out_containers(summary):
    top = summary.top_ops(10)
    assert len(top) == 10
    assert top[0][0] == "bitcast_add_fusion.9 f32[2,3,3,3,4]"
    assert top[0][1] == pytest.approx(0.001118574, rel=1e-6)
    assert all(len(name) <= 80 and not name.startswith("while")
               for name, _ in top)
    assert top == sorted(top, key=lambda kv: -kv[1])


def test_short_name_and_base():
    text = ("%all-reduce.4 = f32[128,10]{1,0:T(8,128)} all-reduce(f32[128,10]"
            "{1,0:T(8,128)} %x), replica_groups={}")
    assert tr.short_name(text) == "all-reduce.4 f32[128,10]"
    assert tr._base(text) == "all-reduce"
    assert tr._base("%while.150 = (s32[]{:T(128)}, f32[4]) while(...)") == "while"
    assert tr.short_name("something unparsed") == "something unparsed"


def test_idle_gaps_are_labelled_by_the_covering_runner_span(summary):
    device = summary.devices[0]
    assert len(device.gaps) == 504
    total = sum(e - s for s, e in device.gaps)
    assert total == pytest.approx(summary.window_s - summary.busy_s, rel=1e-9)
    # Host clock = trace clock + 100 s; one operator span with a phase in it.
    sync_host = summary.sync_s + 100.0
    a, b = summary.start_s + 100.0, summary.end_s + 100.0
    mid = (a + b) / 2
    spans = [("round.train", a, mid - a),
             ("round.train.host_transfer", a + 0.01, 0.02)]
    labelled = dict(tr.label_gaps(summary, spans, sync_host))
    assert set(labelled) == {"round.train", "round.train.host_transfer",
                             "between rounds"}
    assert sum(labelled.values()) == pytest.approx(total, rel=1e-9)
    assert labelled["round.train.host_transfer"] <= 0.02
    # Without the sync event the gaps cannot be placed: say so, keep the sum.
    (label, seconds), = tr.label_gaps(summary, spans, None)
    assert "unlabelled" in label and seconds == pytest.approx(total)


def test_a_stretch_of_whole_rounds_is_cut_out_by_the_host_clock(summary):
    """The harness names the stretch on the host clock; the sync annotation
    moves it onto the profiler's, events are clipped to it, and the waits at
    its edges count as idle."""
    from jax.profiler import ProfileData

    with gzip.open(os.path.join(HERE, "data", "tiny_round.xplane.pb.gz")) as f:
        raw = f.read()
    # Host clock = trace clock + 100 s.
    a, b = summary.start_s + 0.02, summary.start_s + 0.08
    cut = tr.reduce_profile(ProfileData.from_serialized_xspace(raw),
                            (a + 100.0, b + 100.0), summary.sync_s + 100.0)
    assert cut.start_s == pytest.approx(a) and cut.end_s == pytest.approx(b)
    assert cut.window_s == pytest.approx(0.06)
    assert cut.busy_s == pytest.approx(0.002074054, rel=1e-6)
    assert 0 < cut.busy_s < summary.busy_s
    device = cut.devices[0]
    assert device.gaps[0][0] == pytest.approx(a)
    assert device.gaps[-1][1] == pytest.approx(b)
    assert sum(e - s for s, e in device.gaps) + cut.busy_s == pytest.approx(
        cut.window_s, rel=1e-9)
    assert cut.top_ops(1)[0][0] == "bitcast_add_fusion.9 f32[2,3,3,3,4]"
    assert cut.top_ops(1)[0][1] == pytest.approx(0.00049715, rel=1e-4)
    # A stretch needs the sync event's host time, and events inside it.
    with pytest.raises(ValueError, match="sync"):
        tr.reduce_profile(ProfileData.from_serialized_xspace(raw),
                          (a + 100.0, b + 100.0), None)
    with pytest.raises(ValueError, match="inside the stretch"):
        tr.reduce_profile(ProfileData.from_serialized_xspace(raw),
                          (a + 500.0, b + 500.0), summary.sync_s + 100.0)


def test_a_trace_without_device_ops_is_refused():
    class Empty:
        planes = []

    with pytest.raises(ValueError):
        tr.reduce_profile(Empty())
