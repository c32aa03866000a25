"""One cell, one run: compose the task, submit it through the platform's
normal path, measure a window of steady rounds, stop the task, check one
round against the reference, and reduce spans, history and (``--trace 1``)
the profiler's trace to the cell's metrics.

The path is the user's: in-process ``build_session`` (taskmgr, resourcemgr,
performancemgr on an ephemeral port) -> gRPC ``submitTask`` -> scheduler ->
``LocalJobLauncher`` -> ``build_runner_from_taskconfig`` ->
``SimulationRunner`` -> ``FedCore.round_step`` / ``evaluate``; the window is
closed with the ``stopTask`` RPC. The submit/poll pattern is a copy of
``chip_smoke.py``'s.

From the program the benchmark takes the system under test, its spans
(``round.<operator>[.<phase>]``), its history records and its compile
cache; every number is worked out here, by ``window.py``, ``trace_reduce.py``,
``flops.py`` and one small reader per metric.

All clocks are the runner's span clock (``SpanTracer.now()``, which is
``time.perf_counter`` minus a constant), so harness stamps, spans and the
window share one axis.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence

from benchmark import manifest, trace_reduce, window as win

HERE = os.path.dirname(os.path.abspath(__file__))
TASK_TIMEOUT_S = 1100.0        # a first run compiles; the driver allows 1200 s
POLL_S = 0.02
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchmarkError(Exception):
    """The run cannot produce a result (no chip, task failed, no window)."""


def say(text: str) -> None:
    print(text, flush=True)


def load_peaks() -> Dict[str, Any]:
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        return json.load(f)["devices"]


def require_device(chips: int) -> Dict[str, Any]:
    """The device as JAX reports it; refuses anything but ``chips`` TPU chips
    of a kind the peak table knows. No fallback and no flag that allows one."""
    import jax

    devices = jax.devices()
    facts = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if facts["platform"] != "tpu":
        raise BenchmarkError(
            f"no TPU: JAX reports platform {facts['platform']!r}")
    if facts["kind"] not in load_peaks():
        raise BenchmarkError(
            f"device kind {facts['kind']!r} is not in benchmark/peaks.json")
    if facts["count"] != chips:
        raise BenchmarkError(
            f"the cell asks for {chips} chip(s), JAX sees {facts['count']}")
    return facts


@dataclasses.dataclass
class RunContext:
    """Everything a metric reader may read. Times are on the span clock."""

    cell: manifest.Cell
    task: Dict[str, Any]
    params: Dict[str, Any]              # engine params of the composed task
    seed: int
    seconds: float
    device: Dict[str, Any]
    peaks: Dict[str, Any]               # the peak-table row of this device
    t_process_start: float
    t_submitted: float = 0.0            # submitTask returned
    t_running: float = 0.0              # status RUNNING first seen
    rounds: List[win.RoundTiming] = dataclasses.field(default_factory=list)
    window: Optional[win.Window] = None
    history: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    device_rounds: int = 0
    setup_s: float = 0.0
    compiles: List[float] = dataclasses.field(default_factory=list)
    memory_peak_bytes: int = 0
    trace: Optional[trace_reduce.TraceSummary] = None
    sync_host_s: Optional[float] = None
    # The traced stretch: whole rounds, (first start, last end) on the span
    # clock, and how many.
    trace_interval: Optional[tuple] = None
    trace_rounds: int = 0
    # (generation, start on the span clock, seconds) of every collection of
    # Python's garbage collector from submit on: a full collection of a big
    # heap stalls the runner's host code for tenths of a second.
    gc_pauses: List[tuple] = dataclasses.field(default_factory=list)

    @property
    def window_compiles(self) -> int:
        return sum(1 for t in self.compiles
                   if self.window.open <= t <= self.window.close)


@dataclasses.dataclass
class Run:
    """What one run of a cell leaves: the result object (the last stdout
    line), the checks made, and what the metrics were read from."""

    result: Dict[str, Any]
    checks: List[Any]
    ctx: RunContext
    verdict: Dict[str, Any]


def _memory_peak_bytes() -> int:
    """Peak device memory on the fullest chip: the allocator's peak of live
    buffers plus its peak reservation, which is where a running program's
    scratch (XLA temp) memory is held — ``peak_bytes_in_use`` alone leaves
    that out (PR 24: a 2 GiB-temp program moved only the reservation)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def _wait(predicate, timeout_s: float, what: str, poll_s: float = POLL_S):
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(poll_s)
    raise BenchmarkError(f"timed out after {timeout_s:.0f} s waiting for {what}")


def _trace_stretch(ctx: RunContext, tracer, start_at: float, n_rounds: int,
                   task_rounds) -> str:
    """Profile ``n_rounds`` whole rounds of the run, the first being the
    first that starts after span-clock ``start_at``; returns the directory
    the trace was written to. The profiler runs from a little before the
    first round boundary to a little after the last; the reduction keeps
    what lies between the two boundaries, which it finds on the profiler's
    clock through the sync annotation."""
    import jax

    directory = tempfile.mkdtemp(prefix="benchmark_trace_")
    # No Python tracer (it slows the runner's host code and bloats the
    # file); host TraceMe events stay on for the clock-sync annotation.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    time.sleep(max(0.0, start_at - tracer.now()))
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        ctx.sync_host_s = tracer.now()
        with jax.profiler.TraceAnnotation(trace_reduce.SYNC_EVENT):
            time.sleep(0.001)

        def traced():
            starts = [r.start for r in task_rounds()
                      if r.start > ctx.sync_host_s]
            return starts if len(starts) > n_rounds else None

        starts = _wait(traced, TASK_TIMEOUT_S,
                       f"{n_rounds} whole rounds under the profiler", 0.05)
        ctx.trace_interval = (starts[0], starts[n_rounds])
        ctx.trace_rounds = n_rounds
        time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    return directory


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process_start_pc: Optional[float] = None,
             manifest_path: str = manifest.MANIFEST,
             device: Optional[Dict[str, Any]] = None,
             fedcore_overrides: Optional[Dict[str, Any]] = None,
             keep_trace: Optional[str] = None,
             more_check_seeds: Sequence[int] = (),
             plant: bool = False) -> Run:
    """Run one cell once. The checks made are the run's own first, then one
    per seed of ``more_check_seeds`` on the same runner, each starting where
    the one before ended; ``plant`` is ``check.run_check``'s (both for
    ``control.py``'s readings only). ``device`` skips the look for a chip and is for the
    tests only (``run.py`` never passes it); ``fedcore_overrides`` is for
    the lower-precision control (``control.py``) only; ``keep_trace`` names
    a directory that gets a copy of the traced stretch's ``.xplane.pb``."""
    if t_process_start_pc is None:
        t_process_start_pc = time.perf_counter()
    cell = manifest.load_cell(workload, manifest_path)
    task = manifest.compose_task(cell, seed, fedcore_overrides)
    params = manifest.engine_params(task)
    task_id = task["task_id"]
    if device is None:
        device = require_device(cell.chips)
        peaks = load_peaks()[device["kind"]]
    else:
        peaks = load_peaks().get(device["kind"], {})
    say(f"cell={cell.name} config={cell.config_name} "
        f"traffic={cell.traffic_name} seed={seed} seconds={seconds} "
        f"trace={int(trace)} task_id={task_id}")
    say("device " + json.dumps(device))

    import grpc
    from jax import monitoring

    from olearning_sim_tpu.config import build_session
    from olearning_sim_tpu.engine.compile_cache import (
        cache_stats, enable_compile_cache)
    from olearning_sim_tpu.taskmgr.codecs import json2taskconfig
    from olearning_sim_tpu.taskmgr.grpc_service import TaskMgrClient
    from olearning_sim_tpu.taskmgr.status import TaskStatus
    from olearning_sim_tpu.telemetry import default_tracer

    tracer = default_tracer()
    pc_to_span = tracer.now() - time.perf_counter()
    ctx = RunContext(
        cell=cell, task=task, params=params, seed=seed, seconds=seconds,
        device=device, peaks=peaks,
        t_process_start=t_process_start_pc + pc_to_span)
    say(f"compile_cache_dir={enable_compile_cache()}")

    def on_compile(event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            ctx.compiles.append(tracer.now())

    monitoring.register_event_duration_secs_listener(on_compile)
    gc_started: List[float] = []

    def on_gc(phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            gc_started.append(tracer.now())
        elif gc_started:
            t = gc_started.pop()
            ctx.gc_pauses.append((info["generation"], t, tracer.now() - t))

    gc.callbacks.append(on_gc)

    warmup = int(cell.traffic["warmup_rounds"])
    session = build_session({
        "session": {"services": ["taskmgr", "resourcemgr", "performancemgr"],
                    "address": "127.0.0.1:0"},
        "taskmgr": {"schedule_interval": 0.05, "release_interval": 0.2,
                    "interrupt_interval": 3600},
    })
    trace_dir = None
    try:
        with session, grpc.insecure_channel(
                f"127.0.0.1:{session.port}") as channel:
            client = TaskMgrClient(channel)

            def status() -> TaskStatus:
                return TaskStatus(client.getTaskStatus(task_id).taskStatus)

            def job():
                return session.supervisor.launcher.get_job(f"job-{task_id}")

            def task_rounds() -> List[win.RoundTiming]:
                # Spans of this submission only: one process may run the
                # same task id twice (control.py's sound and control runs).
                return win.rounds_from_spans(
                    (s for s in tracer.spans()
                     if s.start_s >= ctx.t_submitted), task_id)

            def alive_or_raise():
                s = status()
                if s in (TaskStatus.FAILED, TaskStatus.STOPPED,
                         TaskStatus.SUCCEEDED, TaskStatus.MISSING):
                    j = job()
                    raise BenchmarkError(
                        f"task ended {s.name} before the window closed: "
                        f"{j.error if j is not None else 'no job'}")

            accepted = client.submitTask(json2taskconfig(json.dumps(task)))
            ctx.t_submitted = tracer.now()
            if not accepted.is_success:
                raise BenchmarkError("submitTask was refused")
            _wait(lambda: status() == TaskStatus.RUNNING or alive_or_raise(),
                  TASK_TIMEOUT_S, "status RUNNING")
            ctx.t_running = tracer.now()
            say(f"status=RUNNING after {ctx.t_running - ctx.t_submitted:.3f} s")

            def opened():
                alive_or_raise()
                return any(r.idx == warmup for r in task_rounds())

            _wait(opened, TASK_TIMEOUT_S, f"round {warmup} to start", 0.01)
            t_open = next(r.start for r in task_rounds() if r.idx == warmup)
            say(f"window open at round {warmup}, setup_s="
                f"{t_open - ctx.t_process_start:.3f}")

            if trace:
                trace_dir = _trace_stretch(
                    ctx, tracer, t_open + 0.3 * seconds,
                    int(cell.traffic["trace_rounds"]), task_rounds)
            time.sleep(max(0.0, t_open + seconds - tracer.now()))

            def closed():
                alive_or_raise()
                return win.window_close_round(task_rounds(), warmup, seconds)

            # Polled slowly: every poll is an RPC and a pass over the spans
            # in the runner's own process, and the window's edges are read
            # from the spans afterwards, not from when this loop saw them.
            _wait(closed, TASK_TIMEOUT_S, "the round that closes the window",
                  0.05)
            client.stopTask(task_id)
            _wait(lambda: status() == TaskStatus.STOPPED
                  and job().status == TaskStatus.STOPPED,
                  TASK_TIMEOUT_S, "status STOPPED")
            job().join(60.0)
            runner = job().runner

        # ---- outside the window: reduce, then check --------------------
        ctx.memory_peak_bytes = _memory_peak_bytes()
        ctx.rounds = task_rounds()
        ctx.history = list(runner.history)
        ctx.window = win.select_window(ctx.rounds, warmup, seconds)
        if ctx.window is None:
            raise BenchmarkError("the spans do not cover a whole window")
        ctx.setup_s = ctx.window.open - ctx.t_process_start
        say(f"setup split: start->submitted "
            f"{ctx.t_submitted - ctx.t_process_start:.3f} s, ->running "
            f"{ctx.t_running - ctx.t_submitted:.3f} s, ->first round "
            f"{ctx.rounds[0].start - ctx.t_running:.3f} s, warm-up rounds "
            f"{ctx.window.open - ctx.rounds[0].start:.3f} s")
        operators = task["operatorflow"]["operators"]
        train_ops = manifest.train_operator_names(task)
        eval_ops = [op["name"] for op in operators
                    if op["name"] not in train_ops]
        verdict = win.judge_rounds(
            ctx.window, ctx.history, train_ops, eval_ops,
            all_clients=(None if cell.traffic.get("deviceflow")
                         else int(cell.traffic["clients"])))
        ctx.device_rounds = verdict["device_rounds"]
        say(f"window {ctx.window.seconds:.3f} s, rounds "
            f"{ctx.window.rounds[0].idx}..{ctx.window.rounds[-1].idx} "
            f"({verdict['attempted']}), device_rounds={ctx.device_rounds}, "
            f"compiles_in_window={ctx.window_compiles}, "
            f"cache={json.dumps(cache_stats())}")
        say("round seconds " + json.dumps(
            [round(r.seconds, 4) for r in ctx.window.rounds]))
        slow = max(ctx.window.rounds, key=lambda r: r.seconds)
        say(f"slowest round {slow.idx}: {slow.seconds:.4f} s, phases "
            + json.dumps({".".join(k): round(v, 4)
                          for k, v in sorted(slow.phases.items())}))
        pauses = [(g, d) for g, t, d in ctx.gc_pauses
                  if ctx.window.open <= t <= ctx.window.close]
        say(f"gc in window: {len(pauses)} collections, "
            f"{sum(d for _, d in pauses):.4f} s, longest "
            f"{max((d for _, d in pauses), default=0.0):.4f} s, full "
            f"{sum(1 for g, _ in pauses if g == 2)}")
        for line in verdict["failed"]:
            say("failed " + line)

        if trace_dir is not None:
            files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                raise BenchmarkError("the profiler wrote no .xplane.pb")
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                shutil.copy(files[0], os.path.join(
                    keep_trace, f"{task_id}.xplane.pb"))
            ctx.trace = trace_reduce.reduce_file(
                files[0], ctx.trace_interval, ctx.sync_host_s)

        from benchmark import check

        checks = []
        for check_seed in (seed, *more_check_seeds):
            checked = check.run_check(runner, cell, task, check_seed, plant)
            for line in checked.lines():
                say(line)
            say(f"check seed={check_seed} sample={checked.sample} took "
                f"{checked.seconds:.1f} s")
            checks.append(checked)
    finally:
        monitoring.unregister_event_duration_listener(on_compile)
        gc.callbacks.remove(on_gc)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    return Run(assemble(ctx, verdict, checks[0], trace), checks, ctx, verdict)


def _read_metrics(entries: List[Dict[str, Any]], kind: str,
                  ctx: RunContext) -> Dict[str, Dict[str, Any]]:
    out: Dict[str, Dict[str, Any]] = {}
    for entry in entries:
        reader = manifest.find_module(kind, entry["name"],
                                      ctx.cell.files_root)
        value = reader.read(ctx)
        if value is None:
            continue        # nothing to read in this run: left out
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def assemble(ctx: RunContext, verdict: Dict[str, Any], checked,
             trace: bool) -> Dict[str, Any]:
    """The result object: ``--trace 0`` carries the cell's end-to-end
    metrics, ``--trace 1`` its per-layer metrics and the breakdown."""
    device = dict(ctx.device, memory_peak_bytes=ctx.memory_peak_bytes)
    if trace:
        metrics = _read_metrics(ctx.cell.per_layer, "layer_metrics", ctx)
    else:
        metrics = _read_metrics(ctx.cell.end_to_end, "end_to_end", ctx)
    result = {
        "correct": bool(checked.correct and not verdict["failed"]),
        "attempted": verdict["attempted"],
        "failed": len(verdict["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace and ctx.trace is not None:
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        spans = [s for r in ctx.rounds for s in r.spans]
        result["breakdown"] = {
            "device_ops": ctx.trace.top_ops(10),
            "idle_gaps": trace_reduce.label_gaps(
                ctx.trace, spans, ctx.sync_host_s, 10),
        }
    return result


def compared(run: Run) -> Dict[str, Dict[str, float]]:
    """What decided ``correct``: each number the run's check held to a
    limit, and the window's failed rounds, each beside its limit. A reading
    that is not finite is written as the largest float, so the line stays
    plain JSON."""
    checked = run.checks[0]
    out = {name: {"value": (float(checked.numbers[name])
                            if math.isfinite(checked.numbers[name])
                            else sys.float_info.max),
                  "limit": limit}
           for name, limit in checked.limits.items()}
    out["failed_rounds"] = {"value": run.result["failed"], "limit": 0}
    return out


def main(argv: Optional[List[str]] = None,
         t_process_start_pc: Optional[float] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep-trace", default=None, metavar="DIR",
                        help="copy the traced stretch's .xplane.pb here")
    args = parser.parse_args(argv)
    try:
        run = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace),
                       t_process_start_pc=t_process_start_pc,
                       keep_trace=args.keep_trace)
    except (BenchmarkError, manifest.ManifestError) as e:
        print(f"benchmark FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    result = dict(run.result, compared=compared(run))    # the key comes last
    for name, c in result["compared"].items():
        print(f"compared {name}={c['value']:.6g} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
