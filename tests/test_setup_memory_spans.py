"""Set-up and device memory seen from inside the program: the
``session.start`` span with the process's age, the ``program`` attribute on
jax's four compile events, the device-memory stamps at the edges where
ownership changes, and what the runner feeds the chip pool's cost oracle
from them. CPU runs: counts, containments and planted numbers, never a
speed."""

import os
import time
import types

import pytest

from olearning_sim_tpu.telemetry import (
    SpanTracer,
    process_age_s,
    set_default_tracer,
    stamp_device_memory,
)


@pytest.fixture
def tracer():
    """An isolated process-default tracer (the session, the compile
    listener and the task bridge resolve the default at call time)."""
    fresh = SpanTracer()
    old = set_default_tracer(fresh)
    try:
        yield fresh
    finally:
        set_default_tracer(old)


def _age_by_wall_clock():
    """The process's age from ``time.time()`` and the kernel's boot time
    (``/proc/stat`` ``btime``): another route than the one under test,
    which reads ``/proc/uptime``. ``btime`` is whole seconds, rounded down,
    so half a second comes off and the error lies within +-0.5 s."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/stat") as f:
        btime = next(int(line.split()[1]) for line in f
                     if line.startswith("btime"))
    return time.time() - (btime + 0.5 + ticks / os.sysconf("SC_CLK_TCK"))


# ---------------------------------------------------------- session.start
@pytest.mark.skipif(not os.path.exists("/proc/self/stat"), reason="no /proc")
def test_process_age_follows_the_kernels_record():
    first = process_age_s()
    assert first == pytest.approx(_age_by_wall_clock(), abs=1.0)
    time.sleep(0.05)
    assert 0.03 <= process_age_s() - first <= 5.0


def test_process_age_is_absent_without_proc(monkeypatch):
    def no_proc(*_a, **_kw):
        raise FileNotFoundError("/proc/self/stat")

    monkeypatch.setattr("builtins.open", no_proc)
    assert process_age_s() is None


@pytest.mark.parametrize("starts", [1, 2])
def test_session_start_is_recorded_once_a_start(tracer, starts):
    from olearning_sim_tpu.services.session import SimulatorSession

    services = ["resourcemgr", "performancemgr"]
    for _ in range(starts):
        session = SimulatorSession(services=services)
        expected = _age_by_wall_clock()
        session.start()
        try:
            assert session.port
        finally:
            session.stop()
    spans = tracer.spans("session.start")
    assert len(spans) == starts
    last = spans[-1]
    assert last.attrs["services"] == services
    assert last.attrs["process_age_s"] == pytest.approx(expected, abs=1.0)
    assert "task_id" not in last.attrs and last.parent_id is None
    assert 0 < last.duration_s < 30
    if starts == 2:
        # /proc/uptime counts hundredths: two quick starts may read alike.
        assert spans[1].attrs["process_age_s"] >= spans[0].attrs[
            "process_age_s"]


# ------------------------------------------------- compile.* -> one program
@pytest.mark.parametrize("fun_name, program", [
    ("round_step", "round_step"),
    ("jit(round_step)", "round_step"),
    ("jit_round_step", "round_step"),
    ("FedCore._build_manual.<locals>.round_step", "round_step"),
    ("jit(FedCore._build_evaluate.<locals>.evaluate)", "evaluate"),
    ("pmap(step)", "step"),
    ("jit(<lambda>)", "<lambda>"),
    ("multiply", "multiply"),
])
def test_program_name_brings_jaxs_names_to_one_form(fun_name, program):
    from olearning_sim_tpu.engine.compile_cache import program_name

    assert program_name(fun_name) == program


def test_the_four_compile_events_of_one_function_carry_one_program(tracer):
    """A real jit under an open span: trace, lower and backend (no
    persistent cache in the tests) all name ``setup_probe_step``; a replayed
    cache hit of the same module does too."""
    import jax
    import jax.numpy as jnp
    from jax import monitoring

    from olearning_sim_tpu.engine import compile_cache as cc

    cc.install_listener()

    class Builder:
        def make(self):
            def setup_probe_step(x):
                return jnp.tanh(x) * 3.0 + 1.0

            return jax.jit(setup_probe_step)

    with tracer.span("round.train.train", task_id="T", round_idx=0):
        Builder().make()(jnp.arange(7.0)).block_until_ready()
        monitoring.record_scalar(cc.BACKEND_EVENT, 0.0,
                                 fun_name="jit(setup_probe_step)")
        monitoring.record_event_duration_secs(cc.CACHE_LOAD_EVENT, 0.25)
        monitoring.record_event_duration_secs(
            cc.BACKEND_EVENT, 0.5, fun_name="jit(setup_probe_step)")
    mine = [s for s in tracer.spans() if s.name.startswith("compile.")
            and s.attrs.get("program") == "setup_probe_step"]
    assert sorted(s.name for s in mine) == [
        "compile.backend", "compile.cache_load", "compile.lower",
        "compile.trace"]
    assert {s.attrs["fun_name"] for s in mine} == {
        "setup_probe_step", "jit(setup_probe_step)"}
    # Every compile span names a program: the eager operations their own.
    compiles = [s for s in tracer.spans() if s.name.startswith("compile.")]
    assert all(s.attrs["program"] == cc.program_name(s.attrs["fun_name"])
               for s in compiles)


# ------------------------------------------------------ stamp_device_memory
class _Device:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_stamp_sets_nothing_where_the_backend_keeps_no_statistics(tracer):
    import jax

    assert jax.local_devices()[0].memory_stats() is None     # the CPU
    with tracer.span("bridge.place", task_id="T") as span:
        assert stamp_device_memory(span) is None
        assert stamp_device_memory(span, devices=[_Device(None),
                                                  _Device({})]) is None
    assert span.attrs == {"task_id": "T"}
    assert stamp_device_memory(None) is None        # a disabled tracer's


def test_stamp_reads_the_fullest_device_and_sums_as_the_harness_does(tracer):
    devices = [
        _Device({"bytes_in_use": 900, "peak_bytes_in_use": 1000,
                 "peak_bytes_reserved": 50}),
        _Device({"bytes_in_use": 700, "peak_bytes_in_use": 1100,
                 "peak_bytes_reserved": 400}),
        _Device(None),
    ]
    with tracer.span("round.train.host_transfer", task_id="T") as span:
        assert stamp_device_memory(span, devices=devices) == 1500
    assert span.attrs["device_bytes_in_use"] == 700
    assert span.attrs["device_peak_bytes"] == 1500
    with tracer.span("bridge.build", task_id="T") as build:
        stamp_device_memory(build, "_before", devices[:1])
    assert build.attrs["device_bytes_in_use_before"] == 900
    assert build.attrs["device_peak_bytes_before"] == 1050
    assert "device_bytes_in_use" not in build.attrs


# ---------------------------------------------------- the runner's cost feed
class _Oracle:
    def __init__(self):
        self.fed = []

    def record_measurement(self, family, **kw):
        self.fed.append({k: v for k, v in kw.items() if v is not None})


def _feeder(peak=None):
    """What ``SimulationRunner._feed_cost`` reads of a runner."""
    from olearning_sim_tpu.engine.runner import SimulationRunner

    runner = types.SimpleNamespace(
        task_id="T", _cost_oracle=_Oracle(), _cost_family="f",
        _cost_round0=None, _cost_compile_fed=False, _device_peak_bytes=peak)
    runner._first_round_compile_s = types.MethodType(
        SimulationRunner._first_round_compile_s, runner)
    return runner, types.MethodType(SimulationRunner._feed_cost, runner)


@pytest.mark.parametrize("spans, fed_compile_s", [
    # XLA compiled: the sum of round 0's compile spans, not its wall.
    ([("compile.trace", 2.0), ("compile.lower", 1.0),
      ("compile.backend", 9.0), ("compile.cache_load", 0.5)], 12.5),
    # Every program loaded: a warm round 0 feeds nothing, however long.
    ([("compile.trace", 2.0), ("compile.lower", 1.0),
      ("compile.cache_load", 4.0)], None),
])
def test_compile_s_is_fed_from_round_0s_spans_where_xla_compiled(
        tracer, spans, fed_compile_s):
    for name, seconds in spans:
        tracer.record(name, 1.0, seconds, task_id="T", round_idx=0)
    tracer.record("compile.backend", 1.0, 30.0, task_id="other", round_idx=0)
    tracer.record("compile.backend", 50.0, 7.0, task_id="T", round_idx=3)
    runner, feed = _feeder(peak=13_000_000_000)
    feed(60.0, 0)
    assert runner._cost_oracle.fed == []          # round 0: held back
    feed(1.0, 1)
    feed(1.25, 2)
    expected = [{"round_time_s": 1.0, "peak_hbm_bytes": 13_000_000_000}]
    if fed_compile_s is not None:
        expected.append({"compile_s": fed_compile_s})
    expected.append({"round_time_s": 1.25, "peak_hbm_bytes": 13_000_000_000})
    assert runner._cost_oracle.fed == expected


def test_without_compile_spans_the_wall_clock_decides_and_no_peak_is_fed(
        tracer):
    runner, feed = _feeder()
    feed(60.0, 4)                                   # a resumed run's first
    feed(1.0, 5)
    assert runner._cost_oracle.fed == [{"round_time_s": 1.0},
                                       {"compile_s": 60.0}]
    runner, feed = _feeder()
    feed(1.1)
    feed(1.0)
    assert runner._cost_oracle.fed == [{"round_time_s": 1.0}]
