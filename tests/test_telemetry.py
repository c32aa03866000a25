"""Telemetry subsystem: registry semantics, exposition formats, spans, and
end-to-end emission from an instrumented simulation run."""

import json
import threading
import urllib.request

import numpy as np
import pytest

from olearning_sim_tpu.telemetry import (
    CATALOG,
    MetricsHTTPServer,
    MetricsRegistry,
    SpanTracer,
    instrument,
    render_prometheus,
    set_default_registry,
    set_default_tracer,
    snapshot,
)


# ---------------------------------------------------------------- registry
def test_counter_and_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("ols_test_events_total", "events", labels=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels("b").inc()
    assert c.labels(kind="a").value == 3
    assert c.labels(kind="b").value == 1
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)  # counters only go up

    g = reg.gauge("ols_test_queue_depth", "depth")
    g.set(5)
    g.inc()
    g.dec(3)
    assert g._default_child().value == 3


def test_histogram_bucketing():
    reg = MetricsRegistry()
    h = reg.histogram("ols_test_latency_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 1.0, 5.0, 100.0):
        h.observe(v)
    child = h._default_child()
    assert child.count == 6
    assert child.sum == pytest.approx(106.65)
    # le semantics: a value equal to a bound lands in that bucket.
    assert child.cumulative() == [2, 4, 5]  # le=0.1, le=1, le=10; +Inf == 6


def test_histogram_rejects_empty_buckets():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("ols_test_empty_seconds", buckets=())


def test_label_schema_enforced():
    reg = MetricsRegistry()
    c = reg.counter("ols_test_labeled_total", labels=("task_id", "phase"))
    with pytest.raises(ValueError):
        c.labels(task_id="t")  # missing phase
    with pytest.raises(ValueError):
        c.labels(task_id="t", phase="p", extra="x")  # unknown label
    with pytest.raises(ValueError):
        c.labels("a", "b", "c")  # arity
    with pytest.raises(ValueError):
        c.inc()  # labeled metric needs .labels()
    # Distinct values are distinct children; same values share one.
    c.labels("t", "select").inc()
    c.labels("t", "train").inc(2)
    assert c.labels(task_id="t", phase="select").value == 1
    assert c.labels(task_id="t", phase="train").value == 2
    assert len(c.children()) == 2


def test_registration_idempotent_and_collision_checked():
    reg = MetricsRegistry()
    a = reg.counter("ols_test_things_total", labels=("k",))
    b = reg.counter("ols_test_things_total", labels=("k",))
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("ols_test_things_total")  # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("ols_test_things_total", labels=("other",))  # labels


def test_disabled_registry_short_circuits():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("ols_test_off_total", labels=("k",))
    c.labels(k="x").inc(100)
    h = reg.histogram("ols_test_off_seconds")
    h.observe(1.0)
    reg.enabled = True
    assert c.labels(k="x").value == 0
    assert h._default_child().count == 0


# -------------------------------------------------------------- exposition
def test_prometheus_render_golden():
    reg = MetricsRegistry()
    c = reg.counter("ols_test_rounds_total", "Rounds run", labels=("status",))
    c.labels(status="ok").inc(3)
    g = reg.gauge("ols_test_depth", "Queue depth")
    g.set(2)
    h = reg.histogram("ols_test_wait_seconds", "Wait", buckets=(0.5, 2.0))
    h.observe(0.25)
    h.observe(1.0)
    h.observe(9.0)
    assert render_prometheus(reg) == (
        "# HELP ols_test_depth Queue depth\n"
        "# TYPE ols_test_depth gauge\n"
        "ols_test_depth 2\n"
        "# HELP ols_test_rounds_total Rounds run\n"
        "# TYPE ols_test_rounds_total counter\n"
        'ols_test_rounds_total{status="ok"} 3\n'
        "# HELP ols_test_wait_seconds Wait\n"
        "# TYPE ols_test_wait_seconds histogram\n"
        'ols_test_wait_seconds_bucket{le="0.5"} 1\n'
        'ols_test_wait_seconds_bucket{le="2"} 2\n'
        'ols_test_wait_seconds_bucket{le="+Inf"} 3\n'
        "ols_test_wait_seconds_sum 10.25\n"
        "ols_test_wait_seconds_count 3\n"
    )


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    c = reg.counter("ols_test_esc_total", labels=("msg",))
    c.labels(msg='say "hi"\nback\\slash').inc()
    out = render_prometheus(reg)
    assert '{msg="say \\"hi\\"\\nback\\\\slash"}' in out


def test_json_snapshot_roundtrips():
    reg = MetricsRegistry()
    reg.counter("ols_test_a_total").inc(2)
    h = reg.histogram("ols_test_b_seconds", buckets=(1.0,))
    h.observe(0.5)
    snap = json.loads(json.dumps(snapshot(reg)))
    assert snap["ols_test_a_total"]["series"][0]["value"] == 2
    assert snap["ols_test_b_seconds"]["series"][0]["count"] == 1
    assert snap["ols_test_b_seconds"]["series"][0]["buckets"] == {"1": 1}


def test_http_endpoint_serves_both_formats():
    reg = MetricsRegistry()
    reg.counter("ols_test_http_total").inc()
    with MetricsHTTPServer(registry=reg) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "ols_test_http_total 1" in text
        body = json.loads(
            urllib.request.urlopen(f"{base}/metrics.json").read()
        )
        assert body["ols_test_http_total"]["series"][0]["value"] == 1
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope")


def test_thread_safety_counters():
    reg = MetricsRegistry()
    c = reg.counter("ols_test_race_total", labels=("t",))

    def worker(i):
        child = c.labels(t=str(i % 4))
        for _ in range(1000):
            child.inc()

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(ch.value for _, ch in c.children()) == 8000


# ------------------------------------------------------------------- spans
def test_span_nesting_and_parent_ids():
    tracer = SpanTracer()
    with tracer.span("round", round_idx=1) as outer:
        with tracer.span("round.train") as mid:
            with tracer.span("round.train.host_transfer") as inner:
                pass
    spans = {s.name: s for s in tracer.spans()}
    assert spans["round"].parent_id is None
    assert spans["round.train"].parent_id == spans["round"].span_id
    assert (spans["round.train.host_transfer"].parent_id
            == spans["round.train"].span_id)
    # Finished innermost-first; durations nest.
    assert [s.name for s in tracer.spans()] == [
        "round.train.host_transfer", "round.train", "round"
    ]
    assert outer.duration_s >= mid.duration_s >= inner.duration_s
    assert outer.attrs["round_idx"] == 1


def test_span_sibling_parents_and_error_capture():
    tracer = SpanTracer()
    with tracer.span("parent"):
        with tracer.span("a"):
            pass
        with pytest.raises(RuntimeError):
            with tracer.span("b"):
                raise RuntimeError("boom")
    spans = {s.name: s for s in tracer.spans()}
    assert spans["a"].parent_id == spans["parent"].span_id
    assert spans["b"].parent_id == spans["parent"].span_id
    assert spans["b"].attrs["error"].startswith("RuntimeError")


def test_perfetto_export(tmp_path):
    tracer = SpanTracer()
    with tracer.span("round", round_idx=0):
        pass
    path = tracer.export(str(tmp_path / "sub" / "runner.trace.json"))
    with open(path) as f:
        doc = json.load(f)
    (ev,) = doc["traceEvents"]
    assert ev["ph"] == "X" and ev["name"] == "round"
    assert ev["dur"] >= 0 and "span_id" in ev["args"]


def test_disabled_tracer_records_nothing():
    tracer = SpanTracer(enabled=False)
    with tracer.span("x"):
        pass
    assert tracer.record("y", 0.0, 1.0) is None
    assert tracer.spans() == []


def test_record_parents_to_the_open_span_of_the_calling_thread():
    """``record`` stamps a finished span after the fact: it keeps the start
    and duration it is given, parents to the innermost span the CALLING
    thread has open (not another thread's), and to nothing outside one."""
    tracer = SpanTracer()
    assert tracer.current() is None
    top = tracer.record("compile.backend", 1.5, 0.25, fun_name="f")
    assert (top.parent_id, top.start_s, top.duration_s) == (None, 1.5, 0.25)
    with tracer.span("round.train", round_idx=0) as outer:
        with tracer.span("round.train.train") as inner:
            assert tracer.current() is inner
            child = tracer.record("compile.trace", tracer.now() - 0.5, 0.5,
                                  fun_name="round_step")
            other = []
            t = threading.Thread(target=lambda: other.append(
                tracer.record("elsewhere", 0.0, 1.0)))
            t.start()
            t.join(10.0)
        sibling = tracer.record("compile.lower", tracer.now(), 0.0)
    assert child.parent_id == inner.span_id
    assert sibling.parent_id == outer.span_id
    assert other[0].parent_id is None
    assert other[0].thread_id != child.thread_id
    assert child.attrs == {"fun_name": "round_step"}
    # Recorded spans are ordinary spans: listed, and exported.
    names = [s.name for s in tracer.spans()]
    assert names.count("compile.trace") == 1 and "elsewhere" in names
    assert any(ev["name"] == "compile.trace" and ev["dur"] == 0.5e6
               for ev in tracer.to_trace_events())
    ids = [s.span_id for s in tracer.spans()]
    assert len(set(ids)) == len(ids)


def test_compile_listener_records_outermost_intervals_under_the_open_span(
        fresh_telemetry):
    """jax's own compile events, replayed: nested intervals (the jits a
    trace or a lowering traces inside itself) are dropped, a backend
    interval whose cache lookup hit is a ``compile.cache_load``, and every
    span names the task and round of the span it was recorded under."""
    from jax import monitoring

    from olearning_sim_tpu.engine import compile_cache as cc

    _, tracer = fresh_telemetry
    cc.install_listener()
    cc.install_listener()                      # idempotent: one listener

    def interval(event, seconds, inner=(), **kw):
        monitoring.record_scalar(event, 0.0, **kw)
        for args in inner:
            interval(*args)
        monitoring.record_event_duration_secs(event, seconds, **kw)

    with tracer.span("round.train.train", task_id="T", round_idx=0) as phase:
        interval(cc.TRACE_EVENT, 0.5, fun_name="round_step", inner=[
            (cc.TRACE_EVENT, 0.1), (cc.TRACE_EVENT, 0.2)])
        interval(cc.LOWER_EVENT, 0.25, fun_name="jit(round_step)", inner=[
            (cc.TRACE_EVENT, 0.05)])
        # A miss compiles; a hit reports its retrieval inside the interval.
        interval(cc.BACKEND_EVENT, 2.0, fun_name="jit(round_step)")
        monitoring.record_scalar(cc.BACKEND_EVENT, 0.0, fun_name="jit(ev)")
        monitoring.record_event_duration_secs(cc.CACHE_LOAD_EVENT, 0.75)
        monitoring.record_event_duration_secs(cc.BACKEND_EVENT, 1.0,
                                              fun_name="jit(ev)")
        monitoring.record_event_duration_secs("/jax/some/other", 9.0)
    interval(cc.TRACE_EVENT, 0.125, fun_name="eager")   # no span open
    got = [(s.name, s.duration_s, s.attrs.get("fun_name"), s.parent_id)
           for s in tracer.spans() if s.name.startswith("compile.")]
    assert got == [
        ("compile.trace", 0.5, "round_step", phase.span_id),
        ("compile.lower", 0.25, "jit(round_step)", phase.span_id),
        ("compile.backend", 2.0, "jit(round_step)", phase.span_id),
        ("compile.cache_load", 1.0, "jit(ev)", phase.span_id),
        ("compile.trace", 0.125, "eager", None),
    ]
    spans = [s for s in tracer.spans() if s.name.startswith("compile.")]
    assert all(s.attrs["task_id"] == "T" and s.attrs["round_idx"] == 0
               for s in spans[:4])
    assert spans[3].attrs["retrieval_s"] == 0.75
    assert "task_id" not in spans[4].attrs
    # Stamped to end when jax reported them.
    assert all(s.start_s + s.duration_s <= tracer.now() for s in spans)


def test_span_window_keeps_the_newest():
    tracer = SpanTracer(keep_last=3)
    for i in range(5):
        with tracer.span("s", i=i):
            pass
    assert [s.attrs["i"] for s in tracer.spans()] == [2, 3, 4]


def _host_events(logdir, prefix):
    """(name, start_ns, duration_ns, stats) of the ``/host:CPU`` plane's
    events whose name starts with ``prefix``, from the capture's
    ``.xplane.pb`` read with ``jax.profiler.ProfileData``."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    events.append((ev.name, ev.start_ns, ev.duration_ns,
                                   dict(ev.stats)))
    return events


def test_spans_are_host_events_of_a_profiler_capture(tmp_path):
    """Every span enters a ``jax.profiler.TraceAnnotation``: while any
    profiler session runs, the program's spans are host events of the
    profile itself — on its clock, attributes as stats — and spans outside
    the session are not in it."""
    import time

    import jax
    import jax.numpy as jnp

    tracer = SpanTracer()
    with tracer.span("tel.before"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("tel.round.train", task_id="T", round_idx=3) as outer:
            with tracer.span("tel.round.train.select", task_id="T",
                             round_idx=3) as inner:
                jnp.square(jnp.arange(8.0)).block_until_ready()
                time.sleep(0.01)
            time.sleep(0.005)
        tracer.record("tel.recorded", tracer.now() - 0.001, 0.001)
    finally:
        jax.profiler.stop_trace()
    events = {e[0]: e for e in _host_events(str(tmp_path), "tel.")}
    assert set(events) == {"tel.round.train", "tel.round.train.select"}
    for span in (outer, inner):
        _, start_ns, duration_ns, stats = events[span.name]
        assert stats == {"task_id": "T", "round_idx": 3}
        # The annotation sits just inside the span's own clock pair.
        assert duration_ns * 1e-9 == pytest.approx(span.duration_s, abs=2e-3)
    # One clock: the two events are as far apart in the profile as the two
    # spans are on the tracer's clock, and nest the same way.
    gap_profile = (events[inner.name][1] - events[outer.name][1]) * 1e-9
    assert gap_profile == pytest.approx(inner.start_s - outer.start_s,
                                        abs=2e-3)
    assert events[inner.name][2] <= events[outer.name][2]


# ------------------------------------------------------- e2e instrumentation
@pytest.fixture
def fresh_telemetry():
    """Swap in an isolated default registry + tracer for the test, restoring
    the process defaults afterwards (instrumented modules resolve the
    default at call time, so the swap captures everything)."""
    reg, tracer = MetricsRegistry(), SpanTracer()
    old_reg = set_default_registry(reg)
    old_tracer = set_default_tracer(tracer)
    try:
        yield reg, tracer
    finally:
        set_default_registry(old_reg)
        set_default_tracer(old_tracer)


def _label_value(metric, **want):
    """Sum of child values whose labels include ``want``."""
    names = metric.label_names
    total = 0.0
    for key, child in metric.children():
        labels = dict(zip(names, key))
        if all(labels.get(k) == v for k, v in want.items()):
            total += getattr(child, "value", getattr(child, "count", 0))
    return total


def test_two_round_run_emits_round_phase_metrics(fresh_telemetry, tmp_path):
    """Tier-1 e2e: a 2-round CPU run emits the expected round-phase metric
    names with nonzero values, plus compile/round/fedcore instruments."""
    reg, tracer = fresh_telemetry
    from olearning_sim_tpu.checkpoint import RoundCheckpointer
    from olearning_sim_tpu.engine import (
        build_fedcore,
        fedavg,
        make_synthetic_dataset,
    )
    from olearning_sim_tpu.engine.fedcore import FedCoreConfig
    from olearning_sim_tpu.engine.runner import (
        DataPopulation,
        OperatorSpec,
        SimulationRunner,
    )
    from olearning_sim_tpu.parallel.mesh import make_mesh_plan
    from olearning_sim_tpu.performancemgr import PerformanceManager

    plan = make_mesh_plan()
    cfg = FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=2)
    core = build_fedcore(
        "mlp2", fedavg(0.1), plan, cfg,
        model_overrides={"hidden": (8,), "num_classes": 3}, input_shape=(8,),
    )
    ds = make_synthetic_dataset(
        seed=3, num_clients=8, n_local=4, input_shape=(8,), num_classes=3
    ).pad_for(plan, 2).place(plan)
    runner = SimulationRunner(
        task_id="tel-task", core=core,
        populations=[DataPopulation(
            name="pop", dataset=ds, device_classes=["c"],
            class_of_client=np.zeros(ds.num_clients, int),
            nums=[8], dynamic_nums=[0],
        )],
        operators=[OperatorSpec(name="train", kind="train"),
                   OperatorSpec(name="eval", kind="eval")],
        rounds=2, perf=PerformanceManager(),
        checkpointer=RoundCheckpointer(str(tmp_path / "ck")),
    )
    runner.run()

    phases = reg.get("ols_engine_round_phase_duration_seconds")
    assert phases is not None
    for phase in ("select", "train", "host_transfer", "eval",
                  "accounting", "checkpoint"):
        count = _label_value(phases, task_id="tel-task", phase=phase)
        assert count >= 2, f"phase {phase}: {count} observations"
        seen = [dict(zip(phases.label_names, k)) for k, _ in phases.children()]
        assert any(lbl["phase"] == phase for lbl in seen)

    assert _label_value(reg.get("ols_engine_rounds_total"),
                        task_id="tel-task", status="ok") == 2
    assert _label_value(reg.get("ols_engine_device_rounds_total"),
                        task_id="tel-task") == 16  # 8 clients x 2 rounds
    # The compile gauge is what jax spent tracing, lowering and compiling
    # under the operator's first train phase (the compile.* spans there),
    # not that round's wall time; the phase histogram is fed the phase
    # spans' own durations.
    compile_g = reg.get("ols_engine_compile_duration_seconds")
    first_train = min((s for s in tracer.spans()
                       if s.name == "round.train.train"),
                      key=lambda s: s.start_s)
    under = [s for s in tracer.spans() if s.name.startswith("compile.")
             and s.parent_id == first_train.span_id]
    assert {"compile.trace", "compile.lower", "compile.backend"} <= {
        s.name for s in under}
    assert all(s.attrs["task_id"] == "tel-task" and s.attrs["round_idx"] == 0
               for s in under)
    assert _label_value(compile_g, task_id="tel-task", operator="train") == (
        pytest.approx(sum(s.duration_s for s in under)))
    assert 0 < sum(s.duration_s for s in under) < first_train.duration_s
    sums = {dict(zip(phases.label_names, k))["phase"]: child.sum
            for k, child in phases.children()
            if dict(zip(phases.label_names, k))["operator"] == "train"}
    assert sums["train"] == pytest.approx(sum(
        s.duration_s for s in tracer.spans() if s.name == "round.train.train"))
    assert _label_value(reg.get("ols_fedcore_round_steps_total"),
                        algorithm="fedavg") == 2
    assert _label_value(reg.get("ols_checkpoint_save_bytes_total"),
                        task_id="") > 0  # checkpointer built w/o task_id
    # PerformanceManager façade fed the round-duration histogram too.
    rd = reg.get("ols_engine_round_duration_seconds")
    assert _label_value(rd, task_id="tel-task", operator="train") >= 2
    # Runner spans nested under the operator span.
    names = {s.name for s in tracer.spans()}
    assert {"round.train", "round.train.select", "round.train.train",
            "round.train.host_transfer"} <= names
    by_id = {s.span_id: s for s in tracer.spans()}
    child = next(s for s in tracer.spans() if s.name == "round.train.select")
    assert by_id[child.parent_id].name == "round.train"
    # The rendered exposition carries all of it.
    body = render_prometheus(reg)
    assert 'phase="host_transfer"' in body
    assert "ols_engine_round_phase_duration_seconds_bucket" in body


def test_chaos_run_prometheus_render_matches_resilience_log(
    fresh_telemetry, tmp_path
):
    """Acceptance: a seeded 2-round chaos run exposes, via the Prometheus
    render, per-phase latency histograms, the deviceflow queue-depth gauge,
    and resilience counters that match ResilienceLog.counters() exactly."""
    reg, _tracer = fresh_telemetry
    from olearning_sim_tpu.checkpoint import RoundCheckpointer
    from olearning_sim_tpu.deviceflow.service import DeviceFlowService
    from olearning_sim_tpu.engine import (
        build_fedcore,
        fedavg,
        make_synthetic_dataset,
    )
    from olearning_sim_tpu.engine.fedcore import FedCoreConfig
    from olearning_sim_tpu.engine.runner import (
        DataPopulation,
        OperatorSpec,
        SimulationRunner,
    )
    from olearning_sim_tpu.parallel.mesh import make_mesh_plan
    from olearning_sim_tpu.resilience import (
        FailurePolicy,
        FaultPlan,
        FaultSpec,
        ResilienceConfig,
        ResilienceLog,
        fast_test_policy,
        faults,
    )

    task_id = "chaos-tel"
    log = ResilienceLog(registry=reg)
    plan = make_mesh_plan()
    cfg = FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=2)
    core = build_fedcore(
        "mlp2", fedavg(0.1), plan, cfg,
        model_overrides={"hidden": (8,), "num_classes": 3}, input_shape=(8,),
    )
    ds = make_synthetic_dataset(
        seed=7, num_clients=8, n_local=4, input_shape=(8,), num_classes=3
    ).pad_for(plan, 2).place(plan)
    svc = DeviceFlowService(poll_interval=0.01)
    svc.register_task(task_id, ["logical_simulation"])
    svc.start()
    strategy = json.dumps({"real_time_dispatch": {
        "use_strategy": True, "dispatch_batch_sizes": [4],
    }})
    ckpt = RoundCheckpointer(str(tmp_path / "ck"), max_to_keep=2,
                             retry_policy=fast_test_policy(3), log=log,
                             task_id=task_id)
    runner = SimulationRunner(
        task_id=task_id, core=core,
        populations=[DataPopulation(
            name="pop", dataset=ds, device_classes=["c"],
            class_of_client=np.zeros(ds.num_clients, int),
            nums=[8], dynamic_nums=[0],
        )],
        operators=[OperatorSpec(name="train", kind="train",
                                use_deviceflow=True,
                                deviceflow_strategy=strategy)],
        rounds=2, deviceflow=svc, checkpointer=ckpt,
        resilience=ResilienceConfig(
            failure_policy=FailurePolicy.RETRY, max_round_retries=2,
            snapshot_rounds=True, log=log,
        ),
    )
    fault_plan = FaultPlan(seed=13, specs=[
        FaultSpec(point="checkpoint.save", times=1, error="io"),
    ])
    try:
        with faults.chaos(fault_plan, log=log):
            # A few inbound messages so the queue gauges see real traffic.
            for i in range(3):
                svc.publish(f"{task_id}_train_0", "logical_simulation",
                            {"client": i})
            history = runner.run()
    finally:
        svc.stop()
    assert [h["round"] for h in history] == [0, 1]
    assert log.count("fault_injected") == 1
    assert log.count("retry") >= 1

    body = render_prometheus(reg)
    # Per-phase latency histograms.
    for phase in ("select", "train", "host_transfer", "checkpoint"):
        assert f'phase="{phase}"' in body
    assert "ols_engine_round_phase_duration_seconds_bucket" in body
    # Deviceflow queue-depth gauge (both rooms).
    assert 'ols_deviceflow_queue_depth{room="inbound"}' in body
    assert 'ols_deviceflow_queue_depth{room="shelf"}' in body
    assert "ols_deviceflow_inbound_messages_total 3" in body
    # Resilience counters in the render match the log exactly.
    events = reg.get("ols_resilience_events_total")
    rendered = {}
    for key, child in events.children():
        labels = dict(zip(events.label_names, key))
        if labels["task_id"] == task_id:
            rendered[labels["kind"]] = rendered.get(labels["kind"], 0) + \
                int(child.value)
    assert rendered == dict(log.counters(task_id))


def test_retire_label_value_drops_per_task_series():
    """Long-lived processes retire a finished task's label children so the
    registry (and scrape body) doesn't grow forever."""
    reg = MetricsRegistry()
    c = reg.counter("ols_test_per_task_total", labels=("task_id", "phase"))
    c.labels("t1", "train").inc()
    c.labels("t1", "eval").inc()
    c.labels("t2", "train").inc(5)
    h = reg.histogram("ols_test_per_task_seconds", labels=("task_id",),
                      buckets=(1.0,))
    h.labels("t1").observe(0.5)
    unlabeled = reg.gauge("ols_test_depth")
    unlabeled.set(1)

    assert reg.retire_label_value("task_id", "t1") == 3
    assert len(c.children()) == 1  # t2 survives
    assert c.labels("t2", "train").value == 5
    assert len(h.children()) == 0
    assert unlabeled._default_child().value == 1  # untouched
    # Unknown label on a labeled metric raises at the metric level.
    with pytest.raises(ValueError):
        c.remove_children(nope="x")
    # A retired series re-materializes at zero on next use (counter reset).
    assert c.labels("t1", "train").value == 0


# ---------------------------------------------------------------- catalog
def test_catalog_metrics_instantiable():
    """Every cataloged metric materializes cleanly in a fresh registry (no
    schema collisions, buckets valid)."""
    reg = MetricsRegistry()
    for name in CATALOG:
        instrument(name, reg)
    assert reg.names() == sorted(CATALOG)
