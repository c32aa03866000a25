"""Performance manager: timings, summaries, runner integration, tracing."""

import os
import time

import numpy as np
import pytest

from olearning_sim_tpu.engine import build_fedcore, fedavg, make_synthetic_dataset
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.engine.runner import DataPopulation, OperatorSpec, SimulationRunner
from olearning_sim_tpu.parallel.mesh import make_mesh_plan
from olearning_sim_tpu.performancemgr import PerformanceManager, RoundTiming
from olearning_sim_tpu.utils.repo import MemoryTableRepo
from olearning_sim_tpu.performancemgr.performance_manager import PERF_COLUMNS


def test_round_timing_derived_metrics():
    t = RoundTiming(task_id="t", round_idx=0, operator="train",
                    duration_s=2.0, num_clients=100, local_steps=5)
    assert t.device_rounds_per_sec == pytest.approx(50.0)
    assert t.per_client_step_latency_s == pytest.approx(2.0 / 500)


def test_record_and_summarize():
    perf = PerformanceManager()
    for r in range(10):
        perf.record_round(RoundTiming("t1", r, "train", 0.1 + 0.01 * r,
                                      num_clients=64, local_steps=2))
    s = perf.get_performance("t1")
    assert s["rounds_recorded"] == 10
    assert s["operator_executions"] == 10
    assert s["rounds_per_sec"] == pytest.approx(10 / s["total_time_s"])
    assert s["round_time_s"]["p50"] >= s["round_time_s"]["mean"] * 0.5
    assert s["round_time_s"]["max"] == pytest.approx(0.19)
    assert perf.list_tasks() == ["t1"]
    assert perf.get_performance("missing")["rounds_recorded"] == 0


def test_timer_context():
    perf = PerformanceManager()
    with perf.time_round("t2", 0, "train", num_clients=8, local_steps=1):
        time.sleep(0.01)
    s = perf.get_performance("t2")
    assert s["operator_executions"] == 1
    assert s["total_time_s"] >= 0.01


def test_rows_persisted():
    repo = MemoryTableRepo(PERF_COLUMNS)
    perf = PerformanceManager(repo=repo)
    perf.record_round(RoundTiming("t3", 1, "train", 0.5, num_clients=4))
    rows = repo.query_all()
    assert len(rows) == 1 and rows[0]["task_id"] == "t3"


def test_runner_records_perf():
    plan = make_mesh_plan()
    cfg = FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=2)
    core = build_fedcore(
        "mlp2", fedavg(0.1), plan, cfg,
        model_overrides={"hidden": (16,), "num_classes": 4},
        input_shape=(12,),
    )
    ds = make_synthetic_dataset(
        seed=1, num_clients=16, n_local=4, input_shape=(12,), num_classes=4
    ).pad_for(plan, 2).place(plan)
    perf = PerformanceManager()
    runner = SimulationRunner(
        task_id="perf-task", core=core,
        populations=[DataPopulation(
            name="pop", dataset=ds, device_classes=["hpc"],
            class_of_client=np.zeros(ds.num_clients, int),
            nums=[16], dynamic_nums=[0],
        )],
        operators=[OperatorSpec(name="train", kind="train")],
        rounds=3, perf=perf,
    )
    runner.run()
    s = perf.get_performance("perf-task")
    assert s["rounds_recorded"] == 3
    assert s["device_rounds_per_sec"] > 0


def test_profiler_trace(tmp_path):
    """start_trace/stop_trace capture one profile that holds the program's
    spans as host events (``/host:CPU`` plane, read with
    ``jax.profiler.ProfileData``) beside XLA's own — no side file."""
    import glob

    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from olearning_sim_tpu.telemetry import SpanTracer

    tracer = SpanTracer()
    perf = PerformanceManager()
    with tracer.span("before.window"):
        pass  # predates the trace: must NOT appear in the profile
    logdir = str(tmp_path / "trace")
    assert perf.start_trace(logdir)
    assert not perf.start_trace(logdir)  # one at a time
    with tracer.span("round.train", task_id="perf-task", round_idx=0) as span:
        jnp.square(jnp.arange(8.0)).block_until_ready()
    assert perf.stop_trace() == logdir
    assert perf.stop_trace() is None
    # Trace artifacts were written, and the runner-span side file is gone.
    found = [f for _, _, fs in os.walk(logdir) for f in fs]
    assert found, "no trace files written"
    assert not [f for f in found if f.endswith(".trace.json")]
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    host = [ev for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]
    (event,) = [ev for ev in host if ev.name == "round.train"]
    assert dict(event.stats) == {"task_id": "perf-task", "round_idx": 0}
    assert event.duration_ns * 1e-9 == pytest.approx(span.duration_s,
                                                     abs=2e-3)
    assert not [ev for ev in host if ev.name == "before.window"]


def test_percentile_linear_interpolation():
    from olearning_sim_tpu.performancemgr.performance_manager import _percentile

    vals = [1.0, 2.0, 3.0, 4.0]
    # numpy's linear interpolation is the reference behavior.
    for q in (0.0, 0.25, 0.5, 0.75, 0.9, 0.95, 1.0):
        assert _percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q * 100))
        ), q
    # The old nearest-rank rounding answered 4.0 (p100) for p95 of 4 samples.
    assert _percentile(vals, 0.95) == pytest.approx(3.85)
    assert _percentile([], 0.5) == 0.0
    assert _percentile([7.0], 0.95) == 7.0


def test_repo_roundtrip_rehydrates():
    """A manager rebuilt over a persisted repo answers get_performance for
    tasks only the repo remembers — including total_client_steps from the
    extra JSON (heterogeneous step profiles)."""
    repo = MemoryTableRepo(PERF_COLUMNS)
    first = PerformanceManager(repo=repo)
    for r in range(4):
        first.record_round(RoundTiming(
            "t-rt", r, "train", 0.5, num_clients=10, local_steps=4,
            total_client_steps=25, extra={"note": 1.0},
        ))
    expect = first.get_performance("t-rt")

    reborn = PerformanceManager(repo=repo)
    got = reborn.get_performance("t-rt")
    assert got["rounds_recorded"] == 4
    assert got == expect
    # total_client_steps survived the extra-JSON round trip: 0.5s / 25 steps.
    assert got["per_client_step_latency_s"] == pytest.approx(0.5 / 25)
    # Unknown tasks still answer empty.
    assert reborn.get_performance("nope")["rounds_recorded"] == 0


def test_start_trace_failure_resets_state(tmp_path, monkeypatch):
    """A start_trace that raises must not leave the manager wedged 'in a
    trace' — the next attempt runs."""
    import jax

    perf = PerformanceManager()
    calls = {"stopped": 0}

    def boom(logdir):
        raise RuntimeError("logdir unwritable")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    monkeypatch.setattr(
        jax.profiler, "stop_trace",
        lambda: calls.__setitem__("stopped", calls["stopped"] + 1),
    )
    with pytest.raises(RuntimeError):
        perf.start_trace(str(tmp_path / "t1"))
    assert perf._trace_dir is None
    assert calls["stopped"] == 1  # half-open profiler session closed
    # Recovered: a subsequent trace starts (stubbed start succeeds).
    monkeypatch.setattr(jax.profiler, "start_trace", lambda logdir: None)
    assert perf.start_trace(str(tmp_path / "t2"))
    assert perf.stop_trace() == str(tmp_path / "t2")
