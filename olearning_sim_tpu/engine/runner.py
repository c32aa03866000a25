"""SimulationRunner — the round-loop driver (the reference RayRunner rebuilt
for the TPU engine).

Reference semantics (``ols_core/taskMgr/run_task.py:212-322``): for each
round x operator: operator-flow start barrier -> optional deviceflow
NotifyStart -> execute the operator over all virtual devices -> deviceflow
NotifyComplete -> per-(data, device-class) success/failed accounting persisted
to the task table -> operator-flow stop barrier (tolerant on the final
round).

Execution differences (the point of the rebuild):

- "execute the operator" is ONE compiled ``FedCore.round_step`` advancing the
  whole population, not ``pool.map_unordered`` over actors spawning a
  subprocess per phone (``utils_run_task.py:481-514``);
- deviceflow behavior comes from the trace compiler as masks (participation /
  drops) applied inside the same program; when a DeviceFlowService is
  attached, the runner also walks the flow lifecycle so hybrid tasks and
  external aggregators observe identical Register/NotifyStart/NotifyComplete
  semantics;
- success/failed counts per device class are derived from per-client finite-
  loss masks instead of subprocess exit codes (``utils_run_task.py:490-494``);
- faults the reference absorbs through process supervision (dead actors,
  flaky object stores, preempted hosts) are absorbed here by the resilience
  layer: pass a :class:`~olearning_sim_tpu.resilience.ResilienceConfig` and
  the round loop gains rollback-and-retry / skip-round failure policies,
  client quarantine, and deterministic fault-injection points
  (``runner.round_begin``, ``runner.pre_checkpoint``,
  ``runner.poison_clients`` — see docs/resilience.md).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import math
import os
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from olearning_sim_tpu.deviceflow.service import DeviceFlowService
from olearning_sim_tpu.deviceflow.trace_compiler import (
    ClientTrace,
    combine_traces,
    compile_trace,
)
from olearning_sim_tpu.engine.client_data import ClientDataset, HostClientStore
from olearning_sim_tpu.engine.convergence import (
    ConvergenceConfig,
    ConvergenceTracker,
)
from olearning_sim_tpu.engine.scenario import ScenarioConfig, ScenarioModel
from olearning_sim_tpu.engine.defense import DefenseConfig
from olearning_sim_tpu.engine.fedcore import FedCore
from olearning_sim_tpu.engine import pacing
from olearning_sim_tpu.engine.pacing import (
    DeadlineConfig,
    DeadlineController,
    DeadlineMissError,
    RoundPacing,
)
from olearning_sim_tpu.parallel.mesh import global_put
from olearning_sim_tpu.resilience import (
    CLIENT_FLAGGED,
    DEADLINE_MISS,
    ROLLBACK,
    SKIP_ROUND,
    FailurePolicy,
    HostPreemption,
    QuarantineManager,
    ResilienceConfig,
    faults,
)
from olearning_sim_tpu.resilience.events import global_log
from olearning_sim_tpu.taskmgr.operator_flow import OperatorFlowController
from olearning_sim_tpu.taskmgr.task_repo import TaskTableRepo
from olearning_sim_tpu.utils.logging import Logger


@dataclasses.dataclass
class OperatorSpec:
    """One operator in the flow (reference ``Operator`` proto,
    ``taskService.proto:68-76``). ``kind``:

    - ``train``: one FedCore round step;
    - ``eval``: centralized evaluation of the global model;
    - ``custom``: host callback ``fn(runner, round_idx, operator,
      population) -> dict`` — the escape hatch for arbitrary user operator
      code (reference operator zips, ``base_operator.py``). Called once per
      population; a returned ``ok_mask`` feeds per-class success accounting.
      Callbacks that only take (runner, round_idx, operator) still work.
    """

    name: str
    kind: str = "train"
    use_deviceflow: bool = False
    deviceflow_strategy: str = ""
    # OperationBehaviorController.outboundService (taskservice.proto:86-88):
    # JSON config for where dispatched batches go, e.g.
    # {"type": "websocket", "url": "ws://..."} (deviceflow/outbound.py).
    outbound_service: str = ""
    inputs: List[str] = dataclasses.field(default_factory=list)
    custom_fn: Optional[Callable[..., Dict[str, Any]]] = None


@dataclasses.dataclass
class DataPopulation:
    """One target-data entry: a client population plus its device-class
    layout (reference ``TargetData`` + ``TotalSimulation``,
    ``taskService.proto:18-32``)."""

    name: str
    dataset: ClientDataset  # placed + padded
    device_classes: List[str]  # class names, e.g. ["high", "low"]
    class_of_client: np.ndarray  # [C] int index into device_classes (host)
    nums: List[int]  # target simulated devices per class
    dynamic_nums: List[int]  # failure allowance per class
    eval_data: Optional[tuple] = None  # (x, y) central eval set
    # Heterogeneous compute profiles: per-client local-step counts [C]
    # (padded). None = every client runs config.max_local_steps. This is how
    # device-tier speed differences (high/mid/low phones) enter the compiled
    # program — as masked step counts, not separate programs.
    num_steps: Optional[np.ndarray] = None
    # The population's label-class count (the scenario label-drift
    # modulus). None falls back to observed max(y)+1 — correct only when
    # the cohort's labels cover every class, so builders that know the
    # real count (task_bridge) set it.
    num_classes: Optional[int] = None
    # Block-streamed population (scenario.stream_block_rows): the cohort
    # lives host-resident in this store and train rounds run through
    # ``FedCore.stream_round`` (O(block) HBM). ``dataset`` then holds the
    # HOST arrays (never placed); populations without a store keep the
    # resident placed-dataset path bit-for-bit.
    store: Optional[HostClientStore] = None


class SimulationRunner:
    def __init__(
        self,
        task_id: str,
        core: FedCore,
        populations: List[DataPopulation],
        operators: List[OperatorSpec],
        rounds: int,
        task_repo: Optional[TaskTableRepo] = None,
        deviceflow: Optional[DeviceFlowService] = None,
        operator_flow: Optional[OperatorFlowController] = None,
        trace_seed: int = 0,
        logger: Optional[Logger] = None,
        stop_event: Optional[threading.Event] = None,
        checkpointer: Optional[Any] = None,
        checkpoint_every: int = 1,
        perf: Optional[Any] = None,
        model_io: Optional[Any] = None,
        warm_start_path: Optional[str] = None,
        resilience: Optional[ResilienceConfig] = None,
        registry: Optional[Any] = None,
        tracer: Optional[Any] = None,
        deadline: Optional[DeadlineConfig] = None,
        defense: Optional[DefenseConfig] = None,
        quarantine_preseed: Optional[Dict[str, List[int]]] = None,
        async_config: Optional[Any] = None,
        scenario: Optional[ScenarioConfig] = None,
        convergence: Optional[ConvergenceConfig] = None,
        cost_oracle: Optional[Any] = None,
        cost_family: Optional[str] = None,
    ):
        """``model_io`` — a :class:`ModelUpdateExporter` realizing the
        reference's model-update-style convention (round r's global model
        exported to storage as ``{task_id}_{r}_result_model.*`` and
        re-ingestable; ``utils_run_task.py:327-397``). ``warm_start_path`` —
        round-0 initial model fetched through ``model_io``'s repo
        (``Model.modelPath`` with ``useModel``). ``resilience`` — opt-in
        resilient round execution (None keeps the pre-resilience fail-fast
        behavior bit-for-bit). ``registry`` / ``tracer`` — telemetry sinks
        (:mod:`olearning_sim_tpu.telemetry`); None resolves the process
        defaults at use time. ``deadline`` — opt-in deadline-aware rounds
        (:class:`~olearning_sim_tpu.engine.pacing.DeadlineConfig`):
        completion-time model, over-selection, deadline-masked aggregation
        with distinct straggler accounting, quorum enforcement routed
        through the failure policy as ``deadline_miss`` events, and
        adaptive pacing whose controller state rides the per-round history
        records (and therefore checkpoint/rollback). None keeps rounds
        deadline-free, bitwise identical to the pre-deadline engine.
        ``defense`` — opt-in adversarial-client defense
        (:class:`~olearning_sim_tpu.engine.defense.DefenseConfig`): in-jit
        delta clipping / robust aggregation plus the anomaly→quarantine
        feedback loop; None keeps aggregation bitwise identical to the
        pre-defense engine. ``quarantine_preseed`` — map of population name
        → known-bad client ids blocklisted from round 0 (engine params
        ``{"quarantine": {"preseed": ...}}``). ``convergence`` — opt-in
        time-to-accuracy tracking
        (:class:`~olearning_sim_tpu.engine.convergence.ConvergenceConfig`):
        quality series at the configured eval cadence, time-to-target in
        simulated and wall time, state riding checkpoint meta.
        ``cost_oracle`` / ``cost_family`` — a
        :class:`~olearning_sim_tpu.taskmgr.pool.CostOracle` fed this
        task's measured per-round wall time at every round close (the
        telemetry→scheduler feedback loop)."""
        self.task_id = task_id
        self.core = core
        self.populations = populations
        self.operators = operators
        self.rounds = int(rounds)
        self.task_repo = task_repo if task_repo is not None else TaskTableRepo()
        self.deviceflow = deviceflow
        self.operator_flow = operator_flow or OperatorFlowController(task_id, rounds)
        self.trace_seed = trace_seed
        self.logger = logger if logger is not None else Logger()
        self.stop_event = stop_event  # threading.Event; honored between rounds
        self.checkpointer = checkpointer  # RoundCheckpointer (optional)
        self.checkpoint_every = max(1, int(checkpoint_every))
        self.perf = perf  # PerformanceManager (optional)
        self.registry = registry  # telemetry MetricsRegistry (optional)
        self.tracer = tracer  # telemetry SpanTracer (optional)
        # Operators whose round step has executed once for this task
        # (_note_first_compile).
        self._compiled_once: set = set()
        self.model_io = model_io
        self.warm_start_path = warm_start_path
        if warm_start_path and model_io is None:
            raise ValueError("warm_start_path needs model_io (a repo to fetch it from)")
        self._model_io_export_dead = False
        self.stopped = False
        self.states: Dict[str, Any] = {}
        self._custom_arity: Dict[int, bool] = {}
        self._round_outputs: Dict[str, Any] = {}
        # Ditto per-client personal state per population (personalized algos).
        self.personal_states: Dict[str, Any] = {}
        # SCAFFOLD control variates per population (control-variate algos).
        self.control_states: Dict[str, Any] = {}
        self.history: List[Dict[str, Any]] = []
        self.resilience = resilience
        self._rlog = (resilience.log if resilience is not None and
                      resilience.log is not None else global_log())
        # Adversarial-client defense (engine/defense.py): in-jit clipping /
        # robust aggregation each train round, plus the anomaly feedback
        # loop into the quarantine manager below.
        self.defense = (defense if defense is not None and defense.enabled
                        else None)
        self._quarantine: Optional[QuarantineManager] = None
        if resilience is not None and resilience.quarantine_after is not None:
            self._quarantine = QuarantineManager(
                quarantine_after=resilience.quarantine_after,
                readmit_after=resilience.readmit_after,
                log=self._rlog, task_id=task_id,
            )
        if self._quarantine is None and (
            quarantine_preseed
            or (self.defense is not None and self.defense.score_enabled)
        ):
            # The anomaly feedback loop / operator blocklist needs a
            # quarantine manager even when the resilience config did not
            # configure one. With anomaly scoring the defense knobs apply;
            # a preseed-only manager must keep pure blocklist semantics —
            # an effectively-infinite strike budget so it never
            # auto-quarantines clients nobody asked it to watch.
            if self.defense is not None and self.defense.score_enabled:
                qa, ra = (self.defense.quarantine_after,
                          self.defense.readmit_after)
            else:
                qa, ra = 1 << 30, 3
            self._quarantine = QuarantineManager(
                quarantine_after=qa, readmit_after=ra,
                log=self._rlog, task_id=task_id,
            )
        if quarantine_preseed:
            by_name = {p.name: p.dataset for p in populations}
            for pop, ids in quarantine_preseed.items():
                ds = by_name.get(pop)
                if ds is None:
                    raise ValueError(
                        f"quarantine.preseed names unknown population "
                        f"{pop!r} (known: {sorted(by_name)})"
                    )
                bad = [c for c in ids if c >= ds.num_real_clients]
                if bad:
                    raise ValueError(
                        f"quarantine.preseed[{pop!r}]: client ids {bad} out "
                        f"of range (population has {ds.num_real_clients} "
                        f"clients)"
                    )
                self._quarantine.preseed(pop, ids, ds.num_clients)
        # Per-round attack state from the ``runner.attack_clients``
        # injection point: population name -> {"scale": [C] or None,
        # "clients": [...], "mode": ...}; cleared and recomputed (seeded by
        # round) at every round begin, so rollback replays reproduce the
        # exact attack set.
        self._attacks: Dict[str, Dict[str, Any]] = {}
        self._clean_y: Dict[str, np.ndarray] = {}
        # Last-good-state snapshot for the round currently executing, plus
        # per-completed-round quarantine snapshots (rollback must restore the
        # quarantine decisions the replayed rounds originally saw).
        self._round_snapshot: Optional[Dict[str, Any]] = None
        self._qsnapshots: Dict[int, Any] = {}
        # Rounds <= this index are rollback replays: their checkpoint saves
        # force-overwrite in case a stale step survived the discard.
        self._force_checkpoint_until = -1
        # Routing key of the deviceflow flow currently open (None between
        # operators); closed best-effort when a round fails mid-operator.
        self._live_routing_key: Optional[str] = None
        # Deadline-aware rounds: one controller per task (shared across
        # populations/train operators — its EMA tracks the task's overall
        # completion-time distribution). None = deadline-free rounds.
        self.deadline = (deadline if deadline is not None and deadline.enabled
                         else None)
        self._pacer: Optional[DeadlineController] = (
            DeadlineController(self.deadline)
            if self.deadline is not None else None
        )
        # Buffered asynchronous rounds (engine/async_rounds.py): commits
        # every M arrivals with staleness-weighted aggregation instead of
        # one deadline-masked commit per round. Mutually exclusive with
        # deadline masking (max_staleness is the async lateness control)
        # and with per-client-state algorithms.
        self.async_config = async_config
        if self.async_config is not None:
            if self.deadline is not None:
                raise ValueError(
                    "async and deadline configs are mutually exclusive: "
                    "the buffered engine's lateness control is "
                    "async.max_staleness (docs/performance.md)"
                )
            if core.algorithm.personalized or core.algorithm.control_variates:
                raise ValueError(
                    f"async rounds do not support the personalized/"
                    f"control-variate algorithm {core.algorithm.name!r}"
                )
        # Cumulative committed buffer windows across the task (the async
        # staleness clock). Rides per-round history records -> checkpoint
        # meta, so rollback/resume replays the commit sequence exactly
        # (_reasync), like quarantine state and the deadline controller.
        self._async_commit_clock = 0
        # Scenario traces (engine/scenario.py): day-scale availability
        # masks (diurnal/charging/spike/churn) multiplied into each train
        # round's participation, arrival times combined into the pacing
        # model, and label drift applied as scoped placed-array swaps.
        # A trace is a pure function of (config, trace_seed, round), so
        # rollback/resume/supervisor relaunch replay the exact sets with
        # no persisted scenario state — the round index IS the cursor.
        self.scenario = scenario
        self._scenario_models: Dict[str, ScenarioModel] = {}
        if self.scenario is not None and self.scenario.streamed:
            if self.async_config is not None:
                raise ValueError(
                    "streamed scenario populations do not compose with "
                    "buffered async rounds (the commit-window scan needs "
                    "the whole cohort resident; docs/performance.md)"
                )
            if core.algorithm.personalized or core.algorithm.control_variates:
                raise ValueError(
                    f"streamed scenario populations do not support the "
                    f"personalized/control-variate algorithm "
                    f"{core.algorithm.name!r}"
                )
            if self.defense is not None and self.defense.gathers_deltas:
                raise ValueError(
                    "streamed scenario populations support clip-only "
                    "defense: robust aggregators / anomaly scoring need "
                    "every client's delta resident (docs/performance.md)"
                )
        # Convergence observability (engine/convergence.py): the per-round
        # quality series, evaluated at the configured cadence, with
        # time-to-target and accuracy-at-budget in simulated and wall
        # time. Tracker state rides per-round history records ->
        # checkpoint meta like the deadline/quarantine/async clocks
        # (_reconverge), so a supervisor-resumed run replays the record.
        self._convergence: Optional[ConvergenceTracker] = (
            ConvergenceTracker(convergence)
            if convergence is not None and convergence.enabled else None
        )
        self._convergence_warned = False
        # Telemetry->scheduler feedback: a CostOracle (taskmgr/pool.py)
        # fed the measured per-round wall time at every round close, so
        # the chip-pool scheduler packs from live numbers instead of only
        # bench ingests (_feed_cost: steady rounds feed round_time_s and
        # the device's measured peak; round 0 feeds compile_s only when
        # the process truly compiled).
        self._cost_oracle = cost_oracle
        self._cost_family = cost_family
        self._cost_round0: Optional[Tuple[int, float]] = None  # (idx, wall)
        self._cost_compile_fed = False
        # device_peak_bytes of the last phase span that stamped it
        # (_phase); None where the backend keeps no statistics.
        self._device_peak_bytes: Optional[int] = None
        # run()-loop state for the cooperative stepping API (begin/step/
        # finish) the MultiTaskDispatcher drives; None outside a run.
        self._loop: Optional[Dict[str, Any]] = None

        if not self.task_repo.has_task(task_id):
            self.task_repo.add_task(task_id)
        self._write_targets()

    # ------------------------------------------------------------ accounting
    def _write_targets(self) -> None:
        """Persist logical_target in the reference shape
        (``run_task.py:155-183``)."""
        target = [
            {
                "name": p.name,
                "simulation_target": {
                    "devices": list(p.device_classes),
                    "nums": list(p.nums),
                },
            }
            for p in self.populations
        ]
        self.task_repo.set_item_value(
            self.task_id, "logical_target", json.dumps({"logical_target": target})
        )

    def _analyze_results(self, operator: OperatorSpec, round_idx: int,
                         ok_by_population: Dict[str, np.ndarray]) -> None:
        """Reference ``analyze_results`` (``run_task.py:149-210``): rebuild
        per-(data, class) success/failed counts fresh each (round, operator)
        and persist round/operator/result."""
        result = []
        for p in self.populations:
            ok = ok_by_population.get(p.name)
            success = [0] * len(p.device_classes)
            failed = [0] * len(p.device_classes)
            if ok is not None:
                real = p.dataset.num_real_clients
                cls = p.class_of_client[:real]
                for ci in range(len(p.device_classes)):
                    mask = cls == ci
                    success[ci] = int(np.logical_and(mask, ok[:real]).sum())
                    failed[ci] = int(np.logical_and(mask, ~ok[:real]).sum())
            result.append(
                {
                    "name": p.name,
                    "simulation_target": {
                        "devices": list(p.device_classes),
                        "success_num": success,
                        "failed_num": failed,
                    },
                }
            )
        repo = self.task_repo
        repo.set_item_value(self.task_id, "logical_round", round_idx + 1)
        repo.set_item_value(self.task_id, "logical_operator", operator.name)
        repo.set_item_value(
            self.task_id, "logical_result", json.dumps({"logical_result": result})
        )

    # ------------------------------------------------------------- deviceflow
    def _notify(self, point: str, fn, *args, **kwargs):
        """Deviceflow RPCs return (ok, msg); under ``resilience.rpc_retry``
        a not-ok answer (or a raised transient) is retried with backoff
        before the round-level failure policy ever sees it."""
        policy = self.resilience.rpc_retry if self.resilience is not None else None
        if policy is None:
            return fn(*args, **kwargs)
        return policy.call(
            fn, *args, retry_if=lambda r: not r[0], point=point,
            task_id=self.task_id, log=self._rlog, **kwargs,
        )

    def _flow_start(self, operator: OperatorSpec, round_idx: int,
                    attempt: int = 0) -> Optional[str]:
        if self.deviceflow is None or not operator.use_deviceflow:
            return None
        routing_key = f"{self.task_id}_{operator.name}_{round_idx}"
        if attempt:
            # A replayed round gets a fresh flow: the failed attempt's flow
            # (same key) may still be awaiting the release loop, and joining
            # it would race close_shelf against the replay's updates.
            routing_key = f"{routing_key}~r{attempt}"
        outbound = None
        if operator.outbound_service:
            try:
                outbound = json.loads(operator.outbound_service)
            except json.JSONDecodeError as e:
                raise ValueError(
                    f"operator {operator.name}: outbound_service is not "
                    f"valid JSON: {e}"
                ) from e
        ok, msg = self._notify(
            "deviceflow.notify_start", self.deviceflow.notify_start,
            self.task_id, routing_key, "logical_simulation",
            operator.deviceflow_strategy or "{}",
            outbound_service=outbound,
        )
        if not ok:
            raise RuntimeError(f"deviceflow NotifyStart failed for {routing_key}: {msg}")
        return routing_key

    def _abandon_live_flow(self) -> None:
        """Best-effort NotifyComplete for the flow open at a round failure.
        Left open, its dispatcher would block on release forever and
        ``check_dispatch_finished`` would wedge task teardown — even though
        a retry replays the round under a fresh routing key."""
        key, self._live_routing_key = self._live_routing_key, None
        if self.deviceflow is None or key is None:
            return
        with contextlib.suppress(Exception):
            self.deviceflow.notify_complete(
                self.task_id, key, "logical_simulation"
            )

    def _flow_complete(self, routing_key: Optional[str]) -> None:
        if self.deviceflow is None or routing_key is None:
            return
        ok, msg = self._notify(
            "deviceflow.notify_complete", self.deviceflow.notify_complete,
            self.task_id, routing_key, "logical_simulation"
        )
        if not ok:
            raise RuntimeError(f"deviceflow NotifyComplete failed for {routing_key}: {msg}")

    # -------------------------------------------------------------- telemetry
    def _tracer(self):
        from olearning_sim_tpu.telemetry import default_tracer

        return self.tracer if self.tracer is not None else default_tracer()

    @contextlib.contextmanager
    def _phase(self, operator_name: str, phase: str, round_idx: int):
        """Span + per-phase latency histogram around one round phase; the
        histogram is fed the span's own duration. Yields the span (None
        under a disabled tracer). The two phases that end with a read from
        the device, ``host_transfer`` and ``eval``, close with the
        device's memory stamped on the span
        (``telemetry.stamp_device_memory``)."""
        from olearning_sim_tpu.telemetry import (
            instrument, stamp_device_memory)

        with self._tracer().span(f"round.{operator_name}.{phase}",
                                 task_id=self.task_id,
                                 round_idx=round_idx) as span:
            # A disabled tracer times nothing; the histogram still does.
            t0 = time.perf_counter() if span is None else None
            yield span
            if phase in ("host_transfer", "eval"):
                # The device has answered: what its allocator holds, and
                # the most it has held, now count this round's programs.
                peak = stamp_device_memory(span)
                if peak is not None:
                    self._device_peak_bytes = peak
        instrument(
            "ols_engine_round_phase_duration_seconds", self.registry
        ).labels(
            task_id=self.task_id, operator=operator_name, phase=phase
        ).observe(span.duration_s if span is not None
                  else time.perf_counter() - t0)

    def _stage(self, operator_name: str, phase: str, stage: str,
               round_idx: int):
        """Span ``round.<operator>.<phase>.<stage>``: one sub-stage of a
        phase, a child of the phase span the caller has open."""
        return self._tracer().span(
            f"round.{operator_name}.{phase}.{stage}",
            task_id=self.task_id, round_idx=round_idx,
        )

    def _note_first_compile(self, operator_name: str, train_span) -> None:
        """``ols_engine_compile_duration_seconds``: what jax spent tracing,
        lowering and compiling (or loading from the persistent cache)
        inside the operator's first ``train`` phase — the ``compile.*``
        spans the compile listener recorded under it (engine/compile_cache)
        — not the first round's wall time. Keyed by operator only: a second
        population's (possibly cache-hit) first execution must not
        overwrite the real compile time."""
        if operator_name in self._compiled_once:
            return
        self._compiled_once.add(operator_name)
        if train_span is None:
            return
        from olearning_sim_tpu.telemetry import instrument

        end = train_span.start_s + train_span.duration_s
        instrument(
            "ols_engine_compile_duration_seconds", self.registry
        ).labels(task_id=self.task_id, operator=operator_name).set(sum(
            s.duration_s for s in self._tracer().spans()
            if s.name.startswith("compile.")
            and s.thread_id == train_span.thread_id
            and train_span.start_s <= s.start_s <= end
        ))

    def _count_work(self, span, p: DataPopulation, trace: ClientTrace,
                    clients_trained: int, metrics) -> None:
        """Work counts of one train launch, on its ``host_transfer`` span
        (set once the device has answered): what the round program computed
        against what the round needed. The program trains every resident
        row, padding and withheld clients included, and where the core
        chose multiplicity (``FedCore.use_multiplicity``) every local
        sample each step. A next-token task adds the tokens of a step, a
        model that marks lookup-only tables (``FedCore.lookup_tables``) the
        tables' rows beside the rows a client's step writes (the ids of the
        rows the model is run on where the core trains them by rows,
        ``FedCore.row_updates``; every row where it falls back to the dense
        update), and a model that counts its own work (``RoundMetrics.model_stats``: a
        routed expert layer's assignments and loads, summed on the device
        over the round) what ``FedCore.describe_stats`` names."""
        if span is None:
            return
        cfg = self.core.config
        x = p.dataset.x
        n_local = int(x.shape[1])
        computed = (
            n_local
            if self.core.use_multiplicity(n_local, x.shape[2:], x.dtype)
            else int(cfg.batch_size))
        span.attrs.update(
            clients_resident=int(p.dataset.num_clients),
            clients_released=int(trace.num_released),
            clients_trained=clients_trained,
            local_steps=int(cfg.max_local_steps),
            samples_computed_per_step=computed,
            samples_needed_per_step=min(int(cfg.batch_size), n_local),
        )
        if cfg.task == "next_token":
            span.attrs["tokens_per_step"] = computed * int(x.shape[2])
        tables = self.core.lookup_tables
        if tables is not None:
            span.attrs.update(
                table_rows_total=tables.rows_total,
                table_rows_written_per_step=(
                    computed * math.prod(x.shape[2:]) * len(tables.paths)
                    if self.core.row_updates else tables.rows_total))
        if (self.core.describe_stats is not None
                and np.ndim(metrics.model_stats)):
            span.attrs.update(self.core.describe_stats(
                np.asarray(jax.device_get(metrics.model_stats))))

    # -------------------------------------------------------------- operators
    def _completion_times(self, p: DataPopulation, round_idx: int,
                          operator: OperatorSpec, trace: ClientTrace,
                          cfg) -> np.ndarray:
        """[real] simulated completion times for one (population, round)
        under ``cfg``'s completion model (DeadlineConfig or the async
        config's equivalent), with the ``runner.straggler_spike``
        injection applied. Shared by the deadline planner and the async
        round planner — both replay exactly under rollback/resume."""
        real = p.dataset.num_real_clients
        stream = zlib.crc32(f"{operator.name}\x00{p.name}".encode())
        if p.num_steps is not None:
            steps = np.minimum(
                np.asarray(p.num_steps[:real], np.int32),
                self.core.config.max_local_steps,
            )
        else:
            steps = np.full(real, self.core.config.max_local_steps, np.int32)
        completion = pacing.completion_times(
            trace.arrival_time[:real], steps, p.class_of_client[:real],
            p.device_classes, cfg, self.trace_seed, round_idx,
            stream=stream,
        )
        # ``runner.straggler_spike`` injection point: a simulated fleet-wide
        # (or targeted) slowdown — congestion, thermal throttling — that
        # multiplies completion times for this round. Payload:
        # ``{"factor": 5.0, "clients": [...]?}``; scope to one population
        # with the spec's ``match`` filter (the context is the population
        # name) — a payload-side filter would consume the firing for the
        # wrong population.
        spec = faults.fire("runner.straggler_spike", context=p.name,
                           round_idx=round_idx, task_id=self.task_id)
        if spec is not None:
            payload = spec.payload or {}
            factor = np.float32(payload.get("factor", 10.0))
            clients = payload.get("clients")
            if clients is None:
                completion = completion * factor
            else:
                idx = [int(c) for c in clients if int(c) < real]
                completion[idx] = completion[idx] * factor
        return completion

    def _plan_pacing(self, p: DataPopulation, round_idx: int,
                     operator: OperatorSpec, trace: ClientTrace,
                     eligible: np.ndarray) -> RoundPacing:
        """Host-side deadline plan for one (population, round): over-select
        the cohort, derive each client's simulated completion time (network
        arrival + device-class compute), and close the round at the earlier
        of (controller deadline, K-th arrival). Deterministic for a given
        (config, trace_seed, operator, population, round) — rollback
        replays reproduce the exact straggler set, while distinct
        (operator, population) pairs draw decorrelated streams."""
        cfg = self.deadline
        stream = zlib.crc32(f"{operator.name}\x00{p.name}".encode())
        selected = pacing.select_cohort(
            eligible, cfg, self.trace_seed, round_idx, stream=stream
        )
        completion = self._completion_times(p, round_idx, operator, trace,
                                            cfg)
        completion = np.where(selected, completion, np.inf).astype(np.float32)
        eff = pacing.effective_deadline(
            completion, selected, cfg, self._pacer.current_deadline()
        )
        n_selected = int(selected.sum())
        n_on_time = int((selected & (completion <= eff)).sum())
        quorum_base = (cfg.target_cohort if cfg.target_cohort is not None
                       else n_selected)
        return RoundPacing(
            selected=selected, completion=completion, deadline_s=float(eff),
            n_selected=n_selected, n_on_time=n_on_time,
            quorum_required=int(math.ceil(cfg.quorum_fraction * quorum_base)),
        )

    def _run_train(self, p: DataPopulation, round_idx: int,
                   operator: OperatorSpec) -> Dict[str, Any]:
        from olearning_sim_tpu.telemetry import instrument

        with self._phase(operator.name, "select", round_idx):
            stage = functools.partial(self._stage, operator.name, "select",
                                      round_idx=round_idx)
            real = p.dataset.num_real_clients
            strace = None
            with stage("compile_trace") as span:
                # Compile over REAL clients only — released slots must
                # never be spent on zero-weight padding clients (which
                # would silently shrink effective participation).
                trace = compile_trace(
                    json.loads(operator.deviceflow_strategy) if (
                        operator.use_deviceflow
                        and operator.deviceflow_strategy
                    ) else None,
                    real,
                    round_idx,
                    task_id=self.task_id,
                    operator=operator.name,
                    seed=self.trace_seed,
                )
                if span is not None:
                    # Whether the strategy's rate curve was integrated in
                    # this round (a build) or read from an earlier one.
                    span.attrs.update(
                        curve_plan_hits=trace.curve_plan_hits,
                        curve_plan_builds=trace.curve_plan_builds)
                if self.scenario is not None:
                    # Scenario availability (diurnal/charging/spike/churn)
                    # intersects the dispatch-strategy trace: a client
                    # participates only if both release it, and arrives at
                    # the later of the two times (feeds pacing/async).
                    strace = self._scenario_model(p).round_trace(round_idx)
                    trace = combine_traces(trace, strace.as_client_trace())
            with stage("mask"):
                mask = np.zeros(p.dataset.num_clients,
                                trace.participate.dtype)
                mask[:real] = trace.participate
                if self._quarantine is not None:
                    # Quarantined clients are masked out exactly like
                    # churned-out devices: zero weight, zero contribution,
                    # compiled program unchanged.
                    mask[:real] = mask[:real] * self._quarantine.active_mask(
                        p.name, real
                    ).astype(mask.dtype)
            pace: Optional[RoundPacing] = None
            aplan = None
            async_completion = None
            if self.async_config is not None or self.deadline is not None:
                with stage("plan"):
                    if self.async_config is not None:
                        # Buffered async rounds: simulate the cohort's
                        # arrivals and assign commit windows in
                        # completion-time order. Deterministic for (config,
                        # trace_seed, operator, population, round) —
                        # rollback/resume replays the exact commit sequence.
                        from olearning_sim_tpu.engine import async_rounds

                        async_completion = self._completion_times(
                            p, round_idx, operator, trace,
                            self.async_config.pacing_config(),
                        )
                        aplan = async_rounds.plan_async_round(
                            self.async_config, async_completion,
                            mask[:real] > 0, p.dataset.num_clients,
                        )
                    if self.deadline is not None:
                        pace = self._plan_pacing(p, round_idx, operator,
                                                 trace, mask[:real] > 0)
                        if not pace.quorum_met:
                            # Quorum enforced BEFORE any device transfer or
                            # round step launch (state untouched): a starved
                            # cohort must degrade through the failure
                            # policy, not silently aggregate.
                            self._rlog.record(
                                DEADLINE_MISS, point="runner.deadline",
                                task_id=self.task_id, round_idx=round_idx,
                                population=p.name, on_time=pace.n_on_time,
                                required=pace.quorum_required,
                                selected=pace.n_selected,
                                deadline_s=pace.deadline_s,
                            )
                            raise DeadlineMissError(
                                f"round {round_idx} population {p.name}: "
                                f"{pace.n_on_time} on-time of "
                                f"{pace.n_selected} selected is below the "
                                f"quorum of {pace.quorum_required} "
                                f"(deadline {pace.deadline_s:.3f}s)"
                            )
                        # Over-selection: non-selected eligible clients sit
                        # this round out (indistinguishable from churn to
                        # the program).
                        mask[:real] = np.where(pace.selected, mask[:real], 0)
            completion_dev = participate = num_steps = None
            if p.store is None:
                with stage("place"):
                    sharding = self.core.plan.client_sharding()
                    if pace is not None:
                        comp_full = np.full(p.dataset.num_clients, np.inf,
                                            np.float32)
                        comp_full[:real] = pace.completion
                        completion_dev = global_put(comp_full, sharding)
                    participate = global_put(mask, sharding)
                    if p.num_steps is not None:
                        num_steps = global_put(
                            np.asarray(p.num_steps, np.int32), sharding
                        )
        if p.store is not None:
            # Streamed population: per-client arrays stay on the host —
            # FedCore.stream_round stages the cohort block by block with
            # the partial aggregates carried on device (O(block) HBM).
            return self._run_train_streamed(
                p, round_idx, operator, trace, strace, mask, pace
            )
        with self._phase(operator.name, "train", round_idx) as train_span:
            state = self.states[p.name]
            pace_kwargs = {}
            if pace is not None:
                pace_kwargs = dict(completion_time=completion_dev,
                                   deadline=pace.deadline_s)
            if aplan is not None:
                pace_kwargs["async_plan"] = aplan
            atk = self._attacks.get(p.name)
            if atk is not None and atk["scale"] is not None:
                # Byzantine update attack (sign_flip/scale): the per-client
                # delta multiplier is data into the compiled program.
                pace_kwargs["attack_scale"] = global_put(
                    atk["scale"], self.core.plan.client_sharding()
                )
            if self.defense is not None:
                pace_kwargs["defense"] = self.defense
            y_swap = (atk["y"] if atk is not None and atk["y"] is not None
                      else None)
            if (strace is not None and strace.label_shift is not None
                    and strace.label_shift.any()):
                # Scenario label drift, scoped to THIS train launch like
                # the label-flip attack (and composing with it: drift
                # rotates whatever labels the round would otherwise
                # train on). Labels are data — no retrace.
                base = (y_swap if y_swap is not None
                        else self._host_labels(p))
                y_swap = self._drift_labels(p, base, strace.label_shift,
                                            real)
            clean_y_dev = None
            if y_swap is not None:
                # Label swap scoped to this train launch: only the placed
                # label array is swapped (features and the rest of the
                # dataset stay as-is), and the finally re-installs the
                # original device buffer — zero re-transfer, and
                # same-round eval operators / later rounds see clean
                # labels.
                clean_y_dev = p.dataset.y
                p.dataset = dataclasses.replace(
                    p.dataset,
                    y=global_put(y_swap, clean_y_dev.sharding),
                )
            try:
                if self.core.algorithm.personalized:
                    personal = self.personal_states.get(p.name)
                    if personal is None:
                        personal = self.core.init_personal(
                            state, p.dataset.num_clients
                        )
                    state, metrics, personal = self.core.round_step(
                        state, p.dataset, participate=participate,
                        personal=personal, num_steps=num_steps, **pace_kwargs,
                    )
                    self.personal_states[p.name] = personal
                elif self.core.algorithm.control_variates:
                    control = self.control_states.get(p.name)
                    if control is None:
                        control = self.core.init_control(
                            state, p.dataset.num_clients
                        )
                    state, metrics, control = self.core.round_step(
                        state, p.dataset, participate=participate,
                        control=control, num_steps=num_steps, **pace_kwargs,
                    )
                    self.control_states[p.name] = control
                else:
                    out = self.core.round_step(
                        state, p.dataset, participate=participate,
                        num_steps=num_steps, **pace_kwargs,
                    )
                    astats = None
                    if aplan is not None:
                        state, metrics, astats = out
                    else:
                        state, metrics = out
            finally:
                if clean_y_dev is not None:
                    p.dataset = dataclasses.replace(
                        p.dataset, y=clean_y_dev
                    )
            self.states[p.name] = state
        with self._phase(operator.name, "host_transfer", round_idx) as span:
            # The device_get is the host sync point: "train" above measures
            # async dispatch; this interval covers real device execution.
            client_loss = np.asarray(jax.device_get(metrics.client_loss))
            clients_trained = int(metrics.clients_trained)
            self._count_work(span, p, trace, clients_trained, metrics)
        self._note_first_compile(operator.name, train_span)
        ok = np.isfinite(client_loss)
        flagged = None
        clipped = 0
        if self.defense is not None:
            clipped = int(metrics.clipped)
            if clipped:
                instrument("ols_engine_clipped_total", self.registry).labels(
                    task_id=self.task_id
                ).inc(clipped)
            if self.defense.score_enabled:
                # Anomaly feedback loop: per-client Krum-style scores flow
                # out of the jit; a participant whose score exceeds
                # threshold x median(score) is flagged and accrues a
                # quarantine strike below. The median normalization makes
                # the threshold model- and scale-free.
                scores = np.asarray(
                    jax.device_get(metrics.anomaly_score)
                )[:real]
                # scores > 0 aligns the host mask with the program's own
                # participant set: a selected-but-deadline-late client has
                # its weight zeroed in-program and scores exactly 0 — it
                # must not pollute the ratio histogram (nor be flagged for
                # an update that was never aggregated).
                part = (mask[:real] > 0) & ok[:real] & (scores > 0)
                vals = scores[part]
                med = float(np.median(vals)) if vals.size else 0.0
                if med > 0:
                    instrument(
                        "ols_engine_anomaly_ratio", self.registry
                    ).labels(task_id=self.task_id).observe_many(
                        scores[part] / med
                    )
                    flagged = np.zeros(real, bool)
                    flagged[part] = (
                        scores[part] > self.defense.anomaly_threshold * med
                    )
                    ids = np.nonzero(flagged)[0]
                    if len(ids):
                        self._rlog.record(
                            CLIENT_FLAGGED, point="runner.defense",
                            task_id=self.task_id, round_idx=round_idx,
                            population=p.name,
                            clients=[int(i) for i in ids[:64]],
                            num_clients=int(len(ids)),
                            threshold=float(self.defense.anomaly_threshold),
                            median_score=med,
                        )
        if self._quarantine is not None:
            # Strikes accrue only for clients that actually participated and
            # came back non-finite (or anomaly-flagged by the defense
            # layer); quarantine countdowns advance once per train
            # operator. Quarantined clients are then reported failed in
            # the per-class accounting — the same way the reference reports
            # dead phones.
            self._quarantine.observe(
                p.name, round_idx, mask[:real] > 0, ok[:real],
                flagged=flagged,
            )
            for ci in self._quarantine.quarantined(p.name):
                if ci < len(ok):
                    ok[ci] = False
            instrument(
                "ols_engine_quarantined_clients", self.registry
            ).labels(task_id=self.task_id).set(
                self._quarantine.num_quarantined()
            )
        rec = {
            "mean_loss": float(metrics.mean_loss),
            "clients_trained": clients_trained,
            "released": trace.num_released,
            "dropped": trace.num_dropped,
            "sim_duration_s": trace.round_duration(),
            "ok_mask": ok,
        }
        if self.defense is not None:
            rec["clipped"] = clipped
            rec["flagged"] = int(flagged.sum()) if flagged is not None else 0
        if atk is not None:
            rec["attacked"] = len(atk["clients"])
            rec["attack_mode"] = atk["mode"]
        if strace is not None:
            # Scenario digest rides the per-round history record (and
            # therefore checkpoint meta): availability/churn/drift counts
            # of the trace this round actually trained under.
            rec["scenario"] = strace.counts()
        if pace is not None:
            # Stragglers of record come from the compiled program's own
            # deadline mask (metrics.stragglers) — the aggregation's truth,
            # reported distinctly from drops.
            stragglers = int(metrics.stragglers)
            rec.update(
                selected=pace.n_selected,
                on_time=pace.n_on_time,
                stragglers=stragglers,
                deadline_s=(pace.deadline_s
                            if np.isfinite(pace.deadline_s) else None),
                round_close_s=pace.round_close_s(),
            )
            instrument("ols_engine_stragglers_total", self.registry).labels(
                task_id=self.task_id
            ).inc(stragglers)
            finite = pace.completion[np.isfinite(pace.completion)]
            instrument(
                "ols_engine_completion_time_seconds", self.registry
            ).labels(task_id=self.task_id).observe_many(finite)
            if np.isfinite(pace.deadline_s):
                instrument(
                    "ols_engine_round_deadline_seconds", self.registry
                ).labels(task_id=self.task_id).observe(pace.deadline_s)
            # Adaptive pacing feedback: the controller observes the selected
            # cohort's completion times (deadline-independent), so the next
            # round's deadline tracks the population's real latency. Updated
            # only on rounds that launched — a rolled-back round's
            # observation is discarded with the rest of its state.
            self._pacer.observe(finite)
            # Tail idle of the synchronous round: every on-time update
            # waits from its arrival until the single round-close commit.
            # The async engine's headline claim is driving this to ~0.
            on_time = pace.completion[
                np.isfinite(pace.completion)
                & (pace.completion <= pace.deadline_s)
            ]
            idle = float(np.clip(pace.round_close_s() - on_time,
                                 0.0, None).sum())
            rec["idle_s"] = round(idle, 6)
            instrument(
                "ols_engine_idle_seconds_total", self.registry
            ).labels(task_id=self.task_id, mode="sync").inc(idle)
        if aplan is not None:
            # Buffered-async accounting: commits, staleness, buffer depth
            # and the committed updates' buffer-wait (idle) — all host-
            # derivable from the plan plus the program's own stats.
            commits = int(astats.commits)
            dropped_stale = int(astats.dropped_stale)
            committed = int(metrics.clients_trained)
            self._async_commit_clock += commits
            idle = aplan.idle_seconds(async_completion)
            rec.update(
                commits=commits,
                committed=committed,
                stale_dropped=dropped_stale,
                buffer_size=self.async_config.buffer_size,
                windows=aplan.num_windows,
                idle_s=round(idle, 6),
                commit_clock=self._async_commit_clock,
            )
            instrument("ols_engine_buffer_depth", self.registry).labels(
                task_id=self.task_id
            ).set(committed / commits if commits else 0.0)
            # Staleness of a committed client == its commit-window index
            # (server commits between its dispatch and its commit).
            committed_mask = (
                (aplan.window[:real] >= 0)
                & ~aplan.stale_dropped_mask()[:real]
                & ok[:real] & (mask[:real] > 0)
            )
            if committed_mask.any():
                instrument(
                    "ols_engine_staleness_rounds", self.registry
                ).labels(task_id=self.task_id).observe_many(
                    aplan.window[:real][committed_mask].astype(np.float64)
                )
                # Simulated makespan of the async round (last committed
                # update's arrival = the final buffer commit's clock) —
                # the convergence tracker's simulated-time denominator,
                # comparable with the sync path's round_close_s.
                rec["round_close_s"] = float(
                    async_completion[committed_mask].max()
                )
            instrument(
                "ols_engine_idle_seconds_total", self.registry
            ).labels(task_id=self.task_id, mode="async").inc(idle)
        if self.core.algorithm.personalized:
            rec["personal_loss"] = float(metrics.personal_loss)
        return rec

    # ------------------------------------------------- scenario / streaming
    def _scenario_model(self, p: DataPopulation) -> ScenarioModel:
        """One ScenarioModel per population, built lazily (static per-
        client draws are seeded by trace_seed, so every process — and
        every supervisor relaunch — realizes the identical fleet)."""
        m = self._scenario_models.get(p.name)
        if m is None:
            m = ScenarioModel(
                self.scenario,
                p.dataset.num_real_clients,
                seed=self.trace_seed,
                class_of_client=p.class_of_client,
                device_classes=p.device_classes,
            )
            self._scenario_models[p.name] = m
        return m

    def _host_labels(self, p: DataPopulation) -> np.ndarray:
        """Clean host label array (cached; shared with label_flip)."""
        if p.name not in self._clean_y:
            self._clean_y[p.name] = np.asarray(
                jax.device_get(p.dataset.y)
            ).copy()
        return self._clean_y[p.name]

    @staticmethod
    def _label_classes(p: DataPopulation, base: np.ndarray) -> int:
        """The label-drift modulus: the population's configured class
        count when the builder supplied it, else observed max(y)+1 (a
        cohort whose labels miss the top class would otherwise rotate
        with the wrong modulus)."""
        return (int(p.num_classes) if p.num_classes
                else int(np.asarray(base).max()) + 1)

    def _drift_labels(self, p: DataPopulation, base: np.ndarray,
                      shift: np.ndarray, real: int) -> np.ndarray:
        """Rotate the first ``real`` clients' labels by their per-client
        drift shift (mod the population's class count)."""
        n_cls = self._label_classes(p, base)
        y = np.array(base)
        y[:real] = (base[:real] + shift[:real, None]) % n_cls
        return y.astype(base.dtype, copy=False)

    def _run_train_streamed(self, p: DataPopulation, round_idx: int,
                            operator: OperatorSpec, trace: ClientTrace,
                            strace, mask: np.ndarray,
                            pace: Optional[RoundPacing]) -> Dict[str, Any]:
        """Train-round body for a block-streamed population
        (``scenario.stream_block_rows``): same accounting contract as the
        resident path, with per-client inputs handed to
        ``FedCore.stream_round`` as host arrays. Label-flip attacks and
        NaN poisoning are resident-path-only (they swap placed buffers);
        sign-flip/scale attacks, clip defense, deadline masking, and
        label drift all compose."""
        from olearning_sim_tpu.telemetry import instrument

        real = p.dataset.num_real_clients
        kwargs: Dict[str, Any] = {}
        if pace is not None:
            kwargs.update(completion_time=pace.completion,
                          deadline=pace.deadline_s)
        atk = self._attacks.get(p.name)
        if atk is not None and atk["scale"] is not None:
            kwargs["attack_scale"] = atk["scale"][:real]
        if atk is not None and atk["y"] is not None:
            self.logger.warning(
                task_id=self.task_id, system_name="engine",
                module_name="runner",
                message=f"label_flip attack skipped for streamed "
                        f"population {p.name} (labels stream from the "
                        f"host store; use sign_flip/scale)",
            )
        if self.defense is not None:
            kwargs["defense"] = self.defense
        if (strace is not None and strace.label_shift is not None
                and strace.label_shift.any()):
            kwargs["label_shift"] = strace.label_shift
            kwargs["label_classes"] = self._label_classes(p, p.dataset.y)
        with self._phase(operator.name, "train", round_idx) as train_span:
            state = self.states[p.name]
            state, metrics, sstats = self.core.stream_round(
                state, p.store,
                stream_rows=self.scenario.stream_block_rows,
                participate=mask[:real], num_steps=p.num_steps,
                tracer=self.tracer,
                **kwargs,
            )
            self.states[p.name] = state
        with self._phase(operator.name, "host_transfer", round_idx) as span:
            client_loss = np.asarray(jax.device_get(metrics.client_loss))
            clients_trained = int(metrics.clients_trained)
            self._count_work(span, p, trace, clients_trained, metrics)
        self._note_first_compile(operator.name, train_span)
        ok = np.isfinite(client_loss)
        clipped = 0
        if self.defense is not None:
            clipped = int(metrics.clipped)
            if clipped:
                instrument("ols_engine_clipped_total", self.registry).labels(
                    task_id=self.task_id
                ).inc(clipped)
        if self._quarantine is not None:
            self._quarantine.observe(
                p.name, round_idx, mask[:real] > 0, ok[:real]
            )
            for ci in self._quarantine.quarantined(p.name):
                if ci < len(ok):
                    ok[ci] = False
            instrument(
                "ols_engine_quarantined_clients", self.registry
            ).labels(task_id=self.task_id).set(
                self._quarantine.num_quarantined()
            )
        rec = {
            "mean_loss": float(metrics.mean_loss),
            "clients_trained": clients_trained,
            "released": trace.num_released,
            "dropped": trace.num_dropped,
            "sim_duration_s": trace.round_duration(),
            "ok_mask": ok,
            # The stream cursor of the COMMITTED round rides checkpoint
            # meta: rounds are atomic (one server commit at round close),
            # so a crash mid-stream replays from the previous round and
            # a completed round records its full block walk.
            "stream": {
                "blocks": sstats.blocks,
                "cursor": sstats.blocks,
                "block_rows": sstats.block_rows,
                "rows": sstats.rows,
                "host_transfer_s": sstats.host_transfer_s,
                "transfer_bytes": sstats.transfer_bytes,
                "overlap_fraction": sstats.overlap_fraction,
                "peak_hbm_bytes_est": sstats.peak_hbm_bytes_est,
            },
        }
        if self.defense is not None:
            rec["clipped"] = clipped
            rec["flagged"] = 0
        if atk is not None and atk["scale"] is not None:
            rec["attacked"] = len(atk["clients"])
            rec["attack_mode"] = atk["mode"]
        if strace is not None:
            rec["scenario"] = strace.counts()
        if pace is not None:
            stragglers = int(metrics.stragglers)
            rec.update(
                selected=pace.n_selected,
                on_time=pace.n_on_time,
                stragglers=stragglers,
                deadline_s=(pace.deadline_s
                            if np.isfinite(pace.deadline_s) else None),
                round_close_s=pace.round_close_s(),
            )
            instrument("ols_engine_stragglers_total", self.registry).labels(
                task_id=self.task_id
            ).inc(stragglers)
            finite = pace.completion[np.isfinite(pace.completion)]
            instrument(
                "ols_engine_completion_time_seconds", self.registry
            ).labels(task_id=self.task_id).observe_many(finite)
            self._pacer.observe(finite)
        return rec

    # ------------------------------------------------------------ convergence
    def _observe_convergence(self, round_idx: int,
                             round_record: Dict[str, Any],
                             wall_s: float) -> None:
        """Advance the convergence clocks for this completed round and, at
        the configured cadence, record an eval point. The quality value
        comes from an eval operator's existing ``eval_loss``/``eval_acc``
        record when this round produced one; otherwise the tracker
        evaluates the global model directly on the first population with
        held-out eval data. The cadence and target are host-side data —
        no compiled program depends on them (asserted in
        tests/test_convergence.py)."""
        from olearning_sim_tpu.telemetry import instrument

        tracker = self._convergence
        # Simulated round duration: the longest population's round close
        # (deadline rounds) or dispatch-trace duration this round.
        sim_s = 0.0
        for op in self.operators:
            if op.kind != "train":
                continue
            for rec in (round_record.get(op.name) or {}).values():
                dur = rec.get("round_close_s")
                if dur is None:
                    dur = rec.get("sim_duration_s")
                if dur:
                    sim_s = max(sim_s, float(dur))
        tracker.observe_round(round_idx, sim_s, wall_s)
        if not tracker.should_eval(round_idx, self.rounds):
            return
        eval_loss = eval_acc = None
        for op in self.operators:
            for rec in (round_record.get(op.name) or {}).values():
                if isinstance(rec, dict) and rec.get("eval_acc") is not None:
                    eval_loss, eval_acc = rec.get("eval_loss"), rec["eval_acc"]
                    break
            if eval_acc is not None:
                break
        t_eval0 = time.perf_counter()
        if eval_acc is None:
            for p in self.populations:
                if p.eval_data is not None:
                    x, y = p.eval_data
                    with self._phase("convergence", "eval", round_idx):
                        eval_loss, eval_acc = self.core.evaluate(
                            self.states[p.name].params, x, y,
                            stage=functools.partial(
                                self._stage, "convergence", "eval",
                                round_idx=round_idx),
                        )
                    break
        if eval_acc is None:
            if not self._convergence_warned:
                self._convergence_warned = True
                self.logger.warning(
                    task_id=self.task_id, system_name="engine",
                    module_name="runner",
                    message="convergence tracking enabled but no "
                            "population has eval_data and no eval "
                            "operator ran; the quality series stays "
                            "empty",
                )
            return
        tracker.observe_eval(round_idx, eval_loss, eval_acc)
        instrument("ols_engine_eval_accuracy", self.registry).labels(
            task_id=self.task_id
        ).set(float(eval_acc))
        # Published on every reached eval, not only the reach transition:
        # a supervisor-resumed process rehydrates reached=True from
        # checkpoint meta and must re-expose the to-target gauges in ITS
        # registry too (idempotent sets of the same committed values).
        if tracker.reached:
            if tracker.sim_seconds_to_target is not None:
                # None = the config has no simulated clock (no deadline/
                # async/scenario pacing) — publishing 0.0 would read as
                # "reached instantaneously".
                instrument(
                    "ols_engine_time_to_target_seconds", self.registry
                ).labels(task_id=self.task_id, clock="sim").set(
                    tracker.sim_seconds_to_target
                )
            instrument(
                "ols_engine_time_to_target_seconds", self.registry
            ).labels(task_id=self.task_id, clock="wall").set(
                tracker.wall_seconds_to_target
            )
            instrument(
                "ols_engine_rounds_to_target", self.registry
            ).labels(task_id=self.task_id).set(tracker.rounds_to_target)
        if self.perf is not None:
            # A distinct convergence_eval timing row per eval point: the
            # quality series then rides the PerformanceManager's persisted
            # rows, so get_performance()["convergence"] answers — and
            # survives manager restarts — like every throughput number.
            from olearning_sim_tpu.performancemgr.performance_manager import (
                RoundTiming,
            )

            extra = {
                "eval_acc": float(eval_acc),
                "sim_s": tracker.sim_seconds_total,
                "wall_s": tracker.wall_seconds_total,
                "reached": 1.0 if tracker.reached else 0.0,
            }
            if eval_loss is not None:
                extra["eval_loss"] = float(eval_loss)
            if tracker.config.target_accuracy is not None:
                extra["target"] = float(tracker.config.target_accuracy)
            if tracker.rounds_to_target is not None:
                extra["rounds_to_target"] = float(tracker.rounds_to_target)
                if tracker.sim_seconds_to_target is not None:
                    extra["sim_s_to_target"] = float(
                        tracker.sim_seconds_to_target
                    )
                extra["wall_s_to_target"] = float(
                    tracker.wall_seconds_to_target
                )
            self.perf.record_round(RoundTiming(
                task_id=self.task_id, round_idx=round_idx,
                operator="convergence_eval",
                duration_s=time.perf_counter() - t_eval0,
                extra=extra,
            ))

    def _feed_cost(self, round_wall_s: float, round_idx: int = 0) -> None:
        """Telemetry->scheduler loop: feed this round's measured wall time
        and the device's measured peak (``device_peak_bytes`` of the
        round's last ``host_transfer`` or ``eval`` span, where the backend
        reports it: the process's peak, so a process that runs several
        tasks reads their sum and admission errs on the refusing side)
        into the pool's CostOracle the moment the round completes, so the
        NEXT admission/packing decision for this family runs on live
        numbers. The first round is never fed as a round time; it refines
        compile_s once the second has run, and only where the process
        truly compiled: with the persistent XLA compile cache warm it is
        an ordinary round, and feeding it as compile_s would clobber the
        family's real compile estimate with a near-zero one."""
        if self._cost_round0 is None:
            self._cost_round0 = (round_idx, round_wall_s)
            return
        self._cost_oracle.record_measurement(
            self._cost_family, round_time_s=round_wall_s,
            peak_hbm_bytes=self._device_peak_bytes,
        )
        if not self._cost_compile_fed:
            self._cost_compile_fed = True
            compile_s = self._first_round_compile_s(round_wall_s)
            if compile_s:
                self._cost_oracle.record_measurement(
                    self._cost_family, compile_s=compile_s
                )

    def _first_round_compile_s(self, steady_wall_s: float) -> float:
        """What the first round spent making its programs ready: the sum of
        its ``compile.*`` spans where one of them is a ``compile.backend``
        (XLA compiled), 0.0 where every program came from the persistent
        cache. A first round always traces its programs, so a tree that
        holds no ``compile.*`` span of it saw nothing (the compile listener
        writes to the process's default tracer, and names the task only
        under a span of that tracer); the wall clock then decides, as it
        did before the spans: the first round's wall where it was more
        than 1.5 x a steady round's."""
        from olearning_sim_tpu.telemetry import default_tracer

        first_idx, first_wall_s = self._cost_round0
        spans = [s for s in default_tracer().spans()
                 if s.name.startswith("compile.")
                 and s.attrs.get("task_id") == self.task_id
                 and s.attrs.get("round_idx") == first_idx]
        if not spans:
            return first_wall_s if first_wall_s > 1.5 * steady_wall_s else 0.0
        if not any(s.name == "compile.backend" for s in spans):
            return 0.0
        return sum(s.duration_s for s in spans)

    def convergence_record(self) -> Optional[Dict[str, Any]]:
        """The task's convergence record (engine/convergence.py), or None
        when tracking is off."""
        if self._convergence is None:
            return None
        return self._convergence.record()

    def _run_eval(self, p: DataPopulation, stage=None) -> Dict[str, Any]:
        rec: Dict[str, Any] = {"eval_loss": None, "eval_acc": None}
        if p.eval_data is not None:
            x, y = p.eval_data
            loss, acc = self.core.evaluate(self.states[p.name].params, x, y,
                                           stage=stage)
            rec.update(eval_loss=loss, eval_acc=acc)
        personal = self.personal_states.get(p.name)
        if personal is not None:
            # Ditto metric of record: personalized models on own local data.
            ploss, pacc = self.core.evaluate_personal(personal, p.dataset)
            rec.update(personal_eval_loss=ploss, personal_eval_acc=pacc)
        return rec

    # ------------------------------------------------------------- checkpoint
    # --------------------------------------------------- model file interop
    def _host_params(self, params):
        """Fetch a param tree to host numpy, multi-host/TP-safe: leaves that
        span non-addressable devices (mp-sharded tensors on a pod) are
        replicated first — device_get on them would raise."""
        if all(
            getattr(leaf, "is_fully_addressable", True)
            for leaf in jax.tree.leaves(params)
        ):
            return jax.device_get(params)
        rep = self.core.plan.replicated()
        replicated = jax.jit(
            lambda p: p, out_shardings=jax.tree.map(lambda _: rep, params)
        )(params)
        return jax.device_get(replicated)

    def _place_params(self, host_params):
        """Host param tree -> placed per the core's param shardings (mp-
        sharded leaves land sharded; everything else replicated)."""
        sh = self.core._param_shardings()
        if sh is None:
            rep = self.core.plan.replicated()
            sh = jax.tree.map(lambda _: rep, host_params)
        return jax.tree.map(
            lambda leaf, s: global_put(np.asarray(leaf), s), host_params, sh
        )

    def _set_params(self, host_params, next_round: Optional[int] = None) -> None:
        """Install ingested params into every population's state. When the
        ingested model represents completed training through round
        ``next_round - 1``, the device round counter moves too — it feeds
        every client's RNG stream (fold_in(key, round)), so leaving it at 0
        would make a resumed run replay round-0 minibatches."""
        placed = self._place_params(host_params)
        for name, state in list(self.states.items()):
            state = state.replace(params=placed)
            if next_round is not None:
                state = state.replace(
                    round_idx=global_put(
                        np.int32(next_round), self.core.plan.replicated()
                    )
                )
            self.states[name] = state

    def _warm_start(self) -> None:
        """Round-0 model ingestion: ``Model.modelPath`` via the model repo
        (reference ``download_model_files`` round-0 branch,
        ``utils_run_task.py:327-397``)."""
        template = self._host_params(
            self.states[self.populations[0].name].params
        )
        self._set_params(self.model_io.load_path(self.warm_start_path, template))
        self.logger.info(
            task_id=self.task_id, system_name="engine", module_name="runner",
            message=f"warm-started from {self.warm_start_path}",
        )

    def _resume_from_exports(self) -> int:
        """Resume from the newest exported round model (the reference's
        ``{task_id}_{round}_result_model`` update style) when no Orbax
        checkpoint claimed the task first.

        Note the fidelity difference from checkpoint resume: the model file
        carries params only, so a stateful *server* optimizer (FedAdam
        moments) restarts cold — exactly what the reference's per-round
        model files give an external aggregator. Probes upward from round 0
        (first fresh-start probe misses and costs one round-trip; a run that
        completed r rounds costs r+1 probes against files known to exist).
        """
        last = None
        try:
            for r in range(self.rounds):
                if not self.model_io.repo.exists(self.model_io._name(r)):
                    break
                last = r
        except NotImplementedError:
            # Download-only repos (HTTP) cannot probe; warm start still
            # works, export-resume does not.
            return 0
        if last is None:
            return 0
        template = self._host_params(
            self.states[self.populations[0].name].params
        )
        self._set_params(self.model_io.load(last, template), next_round=last + 1)
        self.logger.info(
            task_id=self.task_id, system_name="engine", module_name="runner",
            message=f"resumed from exported round model {last}",
        )
        return last + 1

    def _client_state_slot(self):
        """The active per-client state dict and its initializer — Ditto
        personal params or SCAFFOLD control variates (mutually exclusive).
        Both ride the checkpoint's per-population tree slot so a resumed run
        keeps its drift/personalization state instead of re-initializing."""
        if self.core.algorithm.personalized:
            return self.personal_states, self.core.init_personal
        if self.core.algorithm.control_variates:
            return self.control_states, self.core.init_control
        return None, None

    def _materialized_client_states(self):
        slot, init = self._client_state_slot()
        if slot is None:
            return {}
        for p in self.populations:
            if p.name not in slot:
                slot[p.name] = init(self.states[p.name], p.dataset.num_clients)
        return slot

    def _try_resume(self) -> int:
        """Restore the latest round checkpoint if one exists; returns the
        round index to resume from (0 when starting fresh)."""
        if self.checkpointer is None:
            return 0
        template_client = dict(self._materialized_client_states())
        restored = self.checkpointer.restore(self.states, template_client)
        if restored is None:
            return 0
        last_round, states, client_states, history = restored
        # The restore may have fallen back past an unreadable newer step; it
        # must not stay newest or orbax would refuse the replayed rounds'
        # saves (StepAlreadyExistsError — this orbax cannot overwrite a step
        # even with force=True) and every restart would fall back, and
        # re-lose the replay, again. Deletion does mean a TRANSIENT read
        # error costs a valid step (recovered by the replay that follows);
        # wire a retry_policy on remote stores so transients are absorbed
        # before the fallback treats a step as corrupt.
        with contextlib.suppress(Exception):
            self.checkpointer.discard_steps_after(last_round)
        self.states = states
        if self.core.algorithm.personalized:
            self.personal_states = client_states
        elif self.core.algorithm.control_variates:
            self.control_states = client_states
        self.history = history
        self._repace()
        self._requarantine()
        self._reasync()
        self._reconverge()
        self.logger.info(
            task_id=self.task_id, system_name="engine", module_name="runner",
            message=f"resumed from checkpoint: round {last_round} complete",
        )
        return last_round + 1

    def _checkpoint(self, round_idx: int) -> None:
        if self.checkpointer is None:
            return
        if (round_idx + 1) % self.checkpoint_every and round_idx != self.rounds - 1:
            return
        # Materialize per-client state for every population before saving so
        # the checkpoint's tree structure is deterministic (matches the
        # restore template even when no train operator has run yet).
        kwargs = {}
        if round_idx <= self._force_checkpoint_until:
            kwargs["force"] = True
        self.checkpointer.save(
            round_idx, self.states, self._materialized_client_states(),
            self.history, **kwargs
        )

    def _checkpoint_on_stop(self, last_round: int) -> None:
        """Planned-preemption fence: a cooperative stop force-commits the
        last completed round through the manifest commit path, so a
        migrated task resumes from the fence round instead of replaying
        back to the last cadence checkpoint. No-op without a checkpointer
        or when the round is already durable; a save failure must not
        block the stop (the resume path replays the gap bitwise anyway)."""
        if self.checkpointer is None or last_round < 0:
            return
        try:
            # Settle in-flight cadence saves first so the latest-step read
            # is authoritative (saving an already-committed step raises).
            self.checkpointer.wait()
            latest = self.checkpointer.latest_round()
            if latest is not None and latest >= last_round:
                return
            self.checkpointer.save(
                last_round, self.states,
                self._materialized_client_states(), self.history,
            )
            self.checkpointer.wait()
        except Exception as e:  # noqa: BLE001 — fence best-effort
            self.logger.warning(
                task_id=self.task_id, system_name="engine",
                module_name="runner",
                message=f"fence checkpoint at round {last_round} failed "
                        f"({e}); resume will replay from the last "
                        f"committed step",
            )

    def operator_inputs(self, operator: OperatorSpec) -> Dict[str, Any]:
        """Named upstream outputs for ``operator`` this round.

        Realizes the operator DAG the validator enforces (``input`` must
        reference earlier operators — reference ``utils.py:647-651``):
        each entry maps an upstream operator's name to its per-population
        record from the CURRENT round (e.g. the train operator's round
        metrics), so train -> eval -> custom-aggregate chains compose
        instead of the list merely executing in order.
        """
        return {
            name: self._round_outputs[name]
            for name in operator.inputs
            if name in self._round_outputs
        }

    def _call_custom(self, operator: OperatorSpec, round_idx: int,
                     p: DataPopulation) -> Dict[str, Any]:
        """Invoke a custom operator callback, passing the population when the
        callback accepts a 4th positional argument (inspected once per
        callback and cached — catching TypeError at call time would mask
        errors raised inside the callback)."""
        fn = operator.custom_fn
        takes_population = self._custom_arity.get(id(fn))
        if takes_population is None:
            import inspect

            try:
                params = inspect.signature(fn).parameters.values()
                # Count only REQUIRED positional params: a legacy 3-arg
                # callback with an optional 4th keyword (verbose=False) must
                # not have a DataPopulation shoved into it.
                required = [
                    prm for prm in params
                    if prm.kind in (prm.POSITIONAL_ONLY, prm.POSITIONAL_OR_KEYWORD)
                    and prm.default is prm.empty
                ]
                takes_population = (
                    len(required) >= 4
                    or any(prm.kind == prm.VAR_POSITIONAL for prm in params)
                )
            except (TypeError, ValueError):
                takes_population = True
            self._custom_arity[id(fn)] = takes_population
        if takes_population:
            return fn(self, round_idx, operator, p)
        return fn(self, round_idx, operator)

    # ------------------------------------------------------------ resilience
    @staticmethod
    def _copy_tree(tree):
        """Deep-copy a pytree of arrays. Plain references are not enough:
        ``round_step`` donates the state buffers, so a kept reference would
        be invalidated the moment the retried round executes."""
        return jax.tree.map(
            lambda a: a.copy() if hasattr(a, "copy") else a, tree
        )

    def _capture_snapshot(self, round_idx: int) -> Dict[str, Any]:
        return {
            "round_idx": round_idx,
            "states": {k: self._copy_tree(v) for k, v in self.states.items()},
            "personal": {k: self._copy_tree(v)
                         for k, v in self.personal_states.items()},
            "control": {k: self._copy_tree(v)
                        for k, v in self.control_states.items()},
            "history": list(self.history),
            "quarantine": (self._quarantine.snapshot()
                           if self._quarantine is not None else None),
        }

    def _restore_snapshot(self) -> None:
        snap = self._round_snapshot
        if snap is None:
            return
        # Copy out of the snapshot (not move): a second failure of the same
        # round must be able to restore again.
        self.states = {k: self._copy_tree(v) for k, v in snap["states"].items()}
        self.personal_states = {
            k: self._copy_tree(v) for k, v in snap["personal"].items()
        }
        self.control_states = {
            k: self._copy_tree(v) for k, v in snap["control"].items()
        }
        self.history = list(snap["history"])
        self._repace()
        self._reasync()
        self._reconverge()
        if self._quarantine is not None and snap["quarantine"] is not None:
            self._quarantine.restore(snap["quarantine"])

    def _repace(self) -> None:
        """Rehydrate the adaptive deadline controller from the history just
        restored (rollback or checkpoint resume): the newest record carrying
        pacing state holds the controller as of that round's completion, so
        replayed rounds see exactly the deadlines they originally saw."""
        if self._pacer is not None:
            self._pacer.load_from_history(self.history)

    def _reasync(self) -> None:
        """Rehydrate the async commit clock from the history just restored
        (rollback or checkpoint resume): the newest record carrying an
        ``async_clock`` holds the cumulative commit count as of that
        round's completion, so replays continue the sequence instead of
        double-counting commits."""
        if self.async_config is None:
            return
        for rec in reversed(self.history):
            clock = rec.get("async_clock")
            if clock is not None:
                self._async_commit_clock = int(clock)
                return
        self._async_commit_clock = 0

    def _reconverge(self) -> None:
        """Rehydrate the convergence tracker from the history just restored
        (rollback or checkpoint resume): the ordered ``convergence_state``
        records carry the eval series as increments and the newest one
        the cumulative clocks/to-target facts, so a resumed run continues
        — and reports — the identical record instead of re-measuring
        committed rounds. No carrying records (rollback to round 0,
        pre-convergence checkpoints) resets the tracker."""
        if self._convergence is None:
            return
        self._convergence.load_history([
            rec["convergence_state"] for rec in self.history
            if rec.get("convergence_state") is not None
        ])

    def _requarantine(self) -> None:
        """Rehydrate quarantine (defense) state from the history just
        restored from checkpoint: the newest record carrying a
        ``quarantine_state`` holds the manager as of that round's
        completion, so a supervisor-relaunched process replays the masks —
        and therefore the aggregation — bitwise. Without a carrying record
        (fresh start, pre-defense checkpoints) the current state — e.g. an
        operator preseed — is kept."""
        if self._quarantine is None:
            return
        for rec in reversed(self.history):
            st = rec.get("quarantine_state")
            if st is not None:
                self._quarantine.load_json(st)
                return

    def _maybe_poison(self, round_idx: int) -> None:
        """``runner.poison_clients`` injection point: permanently corrupt the
        listed clients' features to NaN (a diverged/byzantine device), so
        their local training produces non-finite updates that exercise the
        real aggregation gate + quarantine path end-to-end.

        Spec payload: ``{"clients": [...], "population": "name"?}`` —
        population omitted poisons every population's listed rows."""
        spec = faults.fire("runner.poison_clients", round_idx=round_idx,
                           task_id=self.task_id)
        if spec is None:
            return
        payload = spec.payload or {}
        clients = [int(c) for c in payload.get("clients", [])]
        pop_name = payload.get("population")
        for p in self.populations:
            if pop_name and p.name != pop_name:
                continue
            if p.store is not None:
                self.logger.warning(
                    task_id=self.task_id, system_name="engine",
                    module_name="runner",
                    message=f"poison_clients: population {p.name} is "
                            f"streamed (host store); NaN poisoning "
                            f"skipped",
                )
                continue
            ds = p.dataset
            x = np.array(jax.device_get(ds.x))
            # jnp.issubdtype, not np: placed features are usually bfloat16
            # (an ml_dtypes type numpy's floating hierarchy doesn't know).
            import jax.numpy as jnp

            if not jnp.issubdtype(x.dtype, jnp.floating):
                self.logger.warning(
                    task_id=self.task_id, system_name="engine",
                    module_name="runner",
                    message=f"poison_clients: population {p.name} has "
                            f"integer features; NaN poisoning skipped",
                )
                continue
            idx = [c for c in clients if c < ds.num_real_clients]
            if not idx:
                continue
            x[idx] = np.nan
            self._replace_dataset(p, x=x)

    def _replace_dataset(self, p: DataPopulation, x=None, y=None) -> None:
        """Swap feature/label arrays into a population's placed dataset
        (already padded + already in its final feature dtype)."""
        ds = p.dataset
        host = ClientDataset(
            x=np.array(jax.device_get(ds.x)) if x is None else x,
            y=np.asarray(jax.device_get(ds.y)) if y is None else y,
            num_samples=np.asarray(jax.device_get(ds.num_samples)),
            client_uid=np.asarray(jax.device_get(ds.client_uid)),
            weight=np.asarray(jax.device_get(ds.weight)),
            num_real_clients=ds.num_real_clients,
            population_size=ds.population_size,
        )
        p.dataset = host.place(self.core.plan, feature_dtype=None)

    def _maybe_attack(self, round_idx: int) -> None:
        """``runner.attack_clients`` injection point: seeded byzantine
        client attacks, generalizing the NaN-only ``poison_clients`` to
        *finite* adversarial behavior the aggregation gate cannot catch —
        the workload the defense layer exists for.

        Spec payload: ``{"mode": "sign_flip"|"scale"|"label_flip",
        "clients": [...]?, "fraction": 0.1?, "factor": ...?}``; scope to one
        population with the spec's ``match`` filter (the context is the
        population name). Without an explicit ``clients`` list, a
        ``fraction`` of the population is drawn seeded by
        ``(plan seed, round, population)``. The client *draw* is therefore
        replay-exact; whether a spec fires at all follows the injector's
        usual hit counting, so chaos plans that must replay bitwise across
        rollbacks/resumes should scope attacks with ``rounds=[...]`` /
        ``times=-1`` rather than hit-count-limited specs (consumed firings
        do not rewind). ``sign_flip`` / ``scale`` transform
        the client's *update* inside the compiled program (delta × -1 /
        × factor); ``label_flip`` trains that round's train steps on
        flipped labels — the swap is scoped to the train launch itself
        (``_run_train``), so same-round eval operators and every later
        round see clean labels.
        """
        self._attacks = {}
        inj = faults.active_injector()
        for p in self.populations:
            spec = faults.fire("runner.attack_clients", context=p.name,
                               round_idx=round_idx, task_id=self.task_id)
            if spec is None:
                continue
            payload = spec.payload or {}
            mode = payload.get("mode", "sign_flip")
            if mode not in ("sign_flip", "scale", "label_flip"):
                raise ValueError(
                    f"runner.attack_clients: unknown mode {mode!r} "
                    f"(known: sign_flip, scale, label_flip)"
                )
            real = p.dataset.num_real_clients
            clients = payload.get("clients")
            if clients is None:
                frac = float(payload.get("fraction", 0.1))
                k = min(real, max(1, int(math.ceil(frac * real))))
                rng = np.random.default_rng([
                    int(inj.plan.seed) if inj is not None else 0,
                    int(round_idx), zlib.crc32(p.name.encode()),
                ])
                clients = rng.choice(real, size=k, replace=False)
            clients = sorted(int(c) for c in clients if 0 <= int(c) < real)
            if not clients:
                continue
            atk: Dict[str, Any] = {"mode": mode, "clients": clients,
                                   "scale": None, "y": None}
            if mode in ("sign_flip", "scale"):
                factor = float(payload.get(
                    "factor", -1.0 if mode == "sign_flip" else 10.0
                ))
                scale = np.ones(p.dataset.num_clients, np.float32)
                scale[clients] = np.float32(factor)
                atk["scale"] = scale
            else:  # label_flip: class c -> (num_classes - 1 - c)
                if p.name not in self._clean_y:
                    self._clean_y[p.name] = np.asarray(
                        jax.device_get(p.dataset.y)
                    ).copy()
                y = self._clean_y[p.name].copy()
                n_cls = int(y.max()) + 1
                y[clients] = n_cls - 1 - y[clients]
                atk["y"] = y
            self._attacks[p.name] = atk

    def _rollback(self, round_idx: int,
                  error: BaseException) -> Optional[int]:
        """Restore the last good state; returns the round to (re-)execute,
        or None when nothing restorable exists.

        A generic failure rolls back to the in-memory snapshot of this
        round's entry state (falling back to the checkpointer when
        ``snapshot_rounds`` is off). A :class:`HostPreemption` models process
        death: recovery prefers the checkpointer (falling back across corrupt
        steps), replaying any rounds after the last readable checkpoint — or
        resuming *past* the failed round when its checkpoint already
        committed before death. When NO checkpoint has committed yet the
        in-memory snapshot is used as a lenient approximation (a really
        preempted host would replay from round 0); chaos plans probing strict
        durability should preempt only after the first checkpoint."""
        preempt = isinstance(error, HostPreemption)
        # Quarantine state as of the failure: the right state to keep when
        # the checkpoint shows the failed round durably completed (its
        # observe() already ran before the save).
        qcur = (self._quarantine.snapshot()
                if self._quarantine is not None else None)
        had_snapshot = self._round_snapshot is not None
        self._restore_snapshot()
        resume_round = round_idx
        if self.checkpointer is not None:
            with contextlib.suppress(Exception):
                # A save may be in flight (or have failed) at "death".
                self.checkpointer.wait()
            if preempt or not had_snapshot:
                resumed = self._try_resume()
                if resumed == 0 and not had_snapshot:
                    # No checkpoint yet and no snapshot: nothing was
                    # restored, so a retry would replay on partially
                    # mutated state.
                    return None
                if resumed > 0:
                    resume_round = resumed
                    if self._quarantine is not None:
                        qsnap = (qcur if resume_round > round_idx
                                 else self._qsnapshots.get(resume_round - 1))
                        if qsnap is not None:
                            self._quarantine.restore(qsnap)
                    if resume_round != round_idx:
                        self._round_snapshot = None  # belongs to another round
            # Replayed rounds re-save their steps; a partially-saved step
            # from the failed attempt (or stale/corrupt future steps after a
            # checkpoint fallback) must not shadow them or trip save
            # collisions.
            with contextlib.suppress(Exception):
                self.checkpointer.discard_steps_after(resume_round - 1)
            self._force_checkpoint_until = max(
                self._force_checkpoint_until, round_idx
            )
        self._rlog.record(
            ROLLBACK, point="runner.rollback", task_id=self.task_id,
            round_idx=round_idx, to_round=resume_round, preempt=preempt,
            error=f"{type(error).__name__}: {str(error)[:200]}",
        )
        return resume_round

    def _handle_round_failure(self, round_idx: int, attempts: int,
                              error: BaseException):
        """Dispatch a failed round per the operator-level failure policy.
        Returns (action, next_round, next_attempts); action "raise" tells the
        caller to re-raise ``error``."""
        cfg = self.resilience
        policy = cfg.failure_policy if cfg is not None else FailurePolicy.FAIL_TASK
        self.logger.error(
            task_id=self.task_id, system_name="engine", module_name="runner",
            message=f"round {round_idx} failed "
                    f"({type(error).__name__}: {error}); policy={policy}",
        )
        if cfg is None or policy == FailurePolicy.FAIL_TASK:
            return "raise", round_idx, attempts
        if policy == FailurePolicy.SKIP_ROUND:
            if self._round_snapshot is None:
                # No rollback source: skipping would keep the round's
                # partial mutations. Degrade to fail_task.
                self.logger.error(
                    task_id=self.task_id, system_name="engine",
                    module_name="runner",
                    message="skip_round needs snapshot_rounds; failing task",
                )
                return "raise", round_idx, attempts
            self._restore_snapshot()
            if self.checkpointer is not None:
                # The round may have checkpointed before failing (e.g. the
                # stop barrier or model export failed after the save); that
                # step holds the state this skip just discarded and must not
                # resurrect it on a restart.
                with contextlib.suppress(Exception):
                    self.checkpointer.wait()
                with contextlib.suppress(Exception):
                    self.checkpointer.discard_steps_after(round_idx - 1)
            self._rlog.record(
                SKIP_ROUND, point="runner.round", task_id=self.task_id,
                round_idx=round_idx,
                error=f"{type(error).__name__}: {str(error)[:200]}",
            )
            from olearning_sim_tpu.telemetry import instrument

            instrument("ols_engine_rounds_total", self.registry).labels(
                task_id=self.task_id, status="skipped"
            ).inc()
            self.history.append({
                "round": round_idx, "skipped": True,
                "error": f"{type(error).__name__}: {str(error)[:200]}",
            })
            return "continue", round_idx + 1, 0
        # FailurePolicy.RETRY
        if attempts >= cfg.max_round_retries:
            # Retries exhausted: degrade to fail_task.
            return "raise", round_idx, attempts
        if self._round_snapshot is None and self.checkpointer is None:
            # Nothing to roll back to: re-running on partially mutated
            # state would double-apply trained populations.
            self.logger.error(
                task_id=self.task_id, system_name="engine",
                module_name="runner",
                message="retry needs snapshot_rounds or a checkpointer; "
                        "failing task",
            )
            return "raise", round_idx, attempts
        next_round = self._rollback(round_idx, error)
        if next_round is None:
            # No snapshot and no readable checkpoint: state is partially
            # mutated with nothing to restore from. Degrade to fail_task.
            self.logger.error(
                task_id=self.task_id, system_name="engine",
                module_name="runner",
                message="retry found no recoverable state; failing task",
            )
            return "raise", round_idx, attempts
        if cfg.round_backoff_s > 0:
            time.sleep(cfg.round_backoff_s * (attempts + 1))
        return "continue", next_round, attempts + 1

    def _persist_resilience(self) -> None:
        """Per-task resilience digest into the task table (the task status
        API's ``resilience`` column; TaskManager.get_resilience)."""
        summary = self._rlog.summary(self.task_id)
        if not summary["counters"]:
            return
        with contextlib.suppress(Exception):
            self.task_repo.set_item_value(
                self.task_id, "resilience", json.dumps(summary)
            )

    # -------------------------------------------------------------------- run
    def _execute_round(self, round_idx: int, attempt: int = 0) -> str:
        """One full round: barriers, operators, accounting, checkpoint,
        model export. Returns "ok", "stop" (cooperative stop observed), or
        "final" (final-round stop barrier tolerated)."""
        from olearning_sim_tpu.telemetry import instrument

        tracer = self._tracer()
        t_round0 = time.perf_counter()
        if not self.operator_flow.start():
            if self.stop_event is not None and self.stop_event.is_set():
                return "stop"  # barrier abandoned due to stop request
            raise RuntimeError(f"round {round_idx}: operator-flow start failed")

        round_record: Dict[str, Any] = {"round": round_idx}
        self._round_outputs = {}
        for operator in self.operators:
            routing_key = self._flow_start(operator, round_idx, attempt)
            # Tracked so a failure mid-operator can close the flow: an open
            # flow's dispatcher blocks on NotifyComplete forever, which
            # wedges check_dispatch_finished and with it task teardown —
            # even when a retry replays the round under a fresh key.
            self._live_routing_key = routing_key
            ok_by_population: Dict[str, np.ndarray] = {}
            op_record: Dict[str, Any] = {}
            # Only train operators advance clients: eval/custom must not
            # inflate the device-rounds/sec metric of record. Total client
            # steps honors heterogeneous per-class profiles so per-step
            # latency is not biased by config.max_local_steps.
            nc = total_steps = 0
            if operator.kind == "train":
                for p in self.populations:
                    real = p.dataset.num_real_clients
                    nc += real
                    total_steps += (
                        int(np.sum(p.num_steps[:real]))
                        if p.num_steps is not None
                        else real * self.core.config.max_local_steps
                    )
            timer = self.perf.time_round(
                self.task_id, round_idx, operator.name, num_clients=nc,
                local_steps=self.core.config.max_local_steps,
                total_client_steps=total_steps,
            ) if self.perf is not None else contextlib.nullcontext()
            with timer, tracer.span(
                f"round.{operator.name}", task_id=self.task_id,
                round_idx=round_idx, kind=operator.kind,
            ):
                for p in self.populations:
                    if operator.kind == "train":
                        r = self._run_train(p, round_idx, operator)
                        ok_by_population[p.name] = r.pop("ok_mask")
                    elif operator.kind == "eval":
                        with self._phase(operator.name, "eval", round_idx):
                            r = self._run_eval(p, functools.partial(
                                self._stage, operator.name, "eval",
                                round_idx=round_idx))
                        ok_by_population[p.name] = np.ones(
                            p.dataset.num_clients, bool
                        )
                    elif operator.kind == "custom":
                        with self._phase(operator.name, "custom", round_idx):
                            r = self._call_custom(operator, round_idx, p) or {}
                        ok_by_population[p.name] = r.pop(
                            "ok_mask", np.ones(p.dataset.num_clients, bool)
                        )
                    else:
                        raise ValueError(f"unknown operator kind {operator.kind!r}")
                    op_record[p.name] = r
                if operator.kind == "train" and hasattr(timer, "note"):
                    # Straggler/drop counts ride the RoundTiming extra so
                    # get_performance() reports them distinctly (satellite:
                    # stragglers are not drops). Defense counters ride the
                    # same channel into get_performance()["defense"].
                    timer.note(
                        stragglers=sum(rec.get("stragglers", 0)
                                       for rec in op_record.values()),
                        dropped=sum(rec.get("dropped", 0)
                                    for rec in op_record.values()),
                        clipped=sum(rec.get("clipped", 0)
                                    for rec in op_record.values()),
                        flagged=sum(rec.get("flagged", 0)
                                    for rec in op_record.values()),
                        attacked=sum(rec.get("attacked", 0)
                                     for rec in op_record.values()),
                    )
            if operator.kind == "train" and nc:
                instrument(
                    "ols_engine_device_rounds_total", self.registry
                ).labels(task_id=self.task_id).inc(nc)
            self._flow_complete(routing_key)
            self._live_routing_key = None
            with self._phase(operator.name, "accounting", round_idx):
                self._analyze_results(operator, round_idx, ok_by_population)
            round_record[operator.name] = op_record
            self._round_outputs[operator.name] = op_record

        round_wall_s = time.perf_counter() - t_round0
        if self._convergence is not None:
            self._observe_convergence(round_idx, round_record, round_wall_s)
        if self._cost_oracle is not None and self._cost_family:
            self._feed_cost(round_wall_s, round_idx)
        if self._pacer is not None and self.deadline.adaptive:
            # Controller state after this round's observations. History
            # records ride both the in-memory snapshot and the checkpoint
            # meta, so rollback/resume repaces deterministically (_repace).
            round_record["pacing"] = self._pacer.state_dict()
        if self._quarantine is not None:
            # Quarantine (defense) state after this round's observations
            # rides the history record — and therefore checkpoint meta — so
            # a supervisor-relaunched task replays quarantine decisions
            # bitwise (_requarantine), not just in-process rollbacks.
            round_record["quarantine_state"] = self._quarantine.state_json()
        if self.async_config is not None:
            # The async commit clock (cumulative committed buffer windows)
            # rides checkpoint meta the same way, so a resumed run reports
            # a continuous commit sequence (_reasync).
            round_record["async_clock"] = self._async_commit_clock
        if self._convergence is not None:
            # Convergence tracker state (clocks, eval series, to-target
            # facts) rides checkpoint meta so a supervisor-resumed run
            # reports the identical time-to-target record (_reconverge).
            round_record["convergence_state"] = self._convergence.state_json()
        self.history.append(round_record)
        # A preemption here ("runner.pre_checkpoint") dies with the round's
        # work done but not yet durable — the classic lost-round scenario the
        # checkpoint-rollback path must absorb.
        faults.inject("runner.pre_checkpoint", context=str(round_idx),
                      round_idx=round_idx, task_id=self.task_id)
        with self._phase("round", "checkpoint", round_idx):
            self._checkpoint(round_idx)
        if self.model_io is not None and not self._model_io_export_dead:
            # One global model per task (reference convention); multi-
            # population tasks export the first population's.
            try:
                with self._phase("round", "model_export", round_idx):
                    self.model_io.export(
                        round_idx,
                        self._host_params(
                            self.states[self.populations[0].name].params
                        ),
                    )
            except NotImplementedError as e:
                # Download-only repo (HTTP warm start): ingestion works,
                # export cannot — disable it once, loudly.
                self._model_io_export_dead = True
                self.logger.warning(
                    task_id=self.task_id, system_name="engine",
                    module_name="runner",
                    message=f"model export disabled: {e}",
                )

        if not self.operator_flow.stop():
            if self.stop_event is not None and self.stop_event.is_set():
                return "stop"
            if round_idx < self.rounds - 1:
                raise RuntimeError(f"round {round_idx}: operator-flow stop failed")
            # Final round: the work is done; don't block on the barrier
            # (reference ``run_task.py:319-322``).
            return "final"
        return "ok"

    def begin(self) -> None:
        """Arm the cooperative round loop: materialize per-population
        state, resume (checkpoint / exported model / warm start), and set
        the loop cursor. ``run()`` is exactly ``begin(); while step():
        pass; finish()`` — the stepping API is what lets a
        :class:`MultiTaskDispatcher` interleave several tasks' compiled
        round programs on one process."""
        # The compile.* spans of the task's tree (and the compile gauge
        # read from them) need jax's compile events listened to, also for a
        # runner that no task bridge built.
        from olearning_sim_tpu.engine.compile_cache import install_listener
        from olearning_sim_tpu.telemetry import stamp_device_memory

        install_listener()
        with self._tracer().span("bridge.init_state",
                                 task_id=self.task_id) as init_span:
            for p in self.populations:
                if p.name not in self.states:
                    # crc32, not hash(): str hashes are PYTHONHASHSEED-
                    # randomized per process, which would silently diverge
                    # the "replicated" ServerState across multi-controller
                    # processes (and break restart reproducibility). Same
                    # pattern as phone_farm.py.
                    self.states[p.name] = self.core.init_state(
                        jax.random.key(
                            zlib.crc32(self.task_id.encode()) & 0x7FFFFFFF
                        )
                    )
            stamp_device_memory(init_span)
        start_round = self._try_resume()
        if start_round == 0 and self.model_io is not None:
            start_round = self._resume_from_exports()
        if start_round == 0 and self.warm_start_path:
            # Only a genuinely fresh start ingests the round-0 model; any
            # resume supersedes it (no wasted fetch on restarts).
            self._warm_start()

        cfg = self.resilience
        snapshotting = cfg is not None and cfg.snapshot_rounds and (
            cfg.failure_policy != FailurePolicy.FAIL_TASK
        )
        if self._quarantine is not None:
            self._qsnapshots[start_round - 1] = self._quarantine.snapshot()
        # Retry budget is PER ROUND (not a running counter): a rollback that
        # resumes earlier than the failed round replays intervening rounds
        # successfully, and those successes must not refill the budget of a
        # deterministically failing round (infinite replay loop otherwise).
        # flow_epoch: monotonic per-rollback epoch for deviceflow
        # routing-key suffixes — any round executed as a replay needs a key
        # its earlier execution never used, or it joins a flow still
        # awaiting the release loop.
        self._loop = {
            "round_idx": start_round,
            "retries": {},
            "flow_epoch": 0,
            "snapshotting": snapshotting,
            "done": False,
        }

    def step(self) -> bool:
        """Execute at most one round (including its failure-policy
        dispatch); returns True while more rounds remain. An exception
        escaping means the task failed under its failure policy."""
        lp = self._loop
        if lp is None:
            raise RuntimeError("SimulationRunner.step() before begin()")
        if lp["done"] or lp["round_idx"] >= self.rounds:
            lp["done"] = True
            return False
        round_idx = lp["round_idx"]
        if self.stop_event is not None and self.stop_event.is_set():
            # Cooperative stop between rounds (reference analogue:
            # stopTask -> Ray job stop, ``task_manager.py:358-455``).
            self.stopped = True
            lp["done"] = True
            self._checkpoint_on_stop(round_idx - 1)
            return False
        if lp["snapshotting"] and (
            self._round_snapshot is None
            or self._round_snapshot["round_idx"] != round_idx
        ):
            self._round_snapshot = self._capture_snapshot(round_idx)
        replaying = (round_idx <= self._force_checkpoint_until
                     or lp["retries"].get(round_idx, 0) > 0)
        try:
            faults.inject("runner.round_begin", context=str(round_idx),
                          round_idx=round_idx, task_id=self.task_id)
            self._maybe_poison(round_idx)
            self._maybe_attack(round_idx)
            status = self._execute_round(
                round_idx, lp["flow_epoch"] if replaying else 0
            )
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:  # noqa: BLE001 — policy dispatch
            from olearning_sim_tpu.telemetry import instrument

            instrument("ols_engine_rounds_total", self.registry).labels(
                task_id=self.task_id, status="failed"
            ).inc()
            self._abandon_live_flow()
            action, next_round, new_attempts = self._handle_round_failure(
                round_idx, lp["retries"].get(round_idx, 0), e
            )
            if action == "raise":
                self._persist_resilience()
                raise
            lp["retries"][round_idx] = new_attempts
            lp["round_idx"] = next_round
            lp["flow_epoch"] += 1
            return True
        lp["retries"].pop(round_idx, None)
        # "ok" means the round's work completed: always true for
        # "ok"/"final"; true for "stop" only when the stop barrier was
        # abandoned AFTER the operators ran (history got the record) —
        # a stop at the START barrier executed nothing and counts as
        # no round at all.
        if status != "stop" or (
            self.history and self.history[-1].get("round") == round_idx
        ):
            from olearning_sim_tpu.telemetry import instrument

            instrument("ols_engine_rounds_total", self.registry).labels(
                task_id=self.task_id, status="ok"
            ).inc()
        if self._quarantine is not None:
            self._qsnapshots[round_idx] = self._quarantine.snapshot()
            # Retention must cover the deepest possible rollback: a
            # preemption can fall back across every retained checkpoint
            # step — max_to_keep steps spaced checkpoint_every rounds
            # apart — and _rollback then needs the quarantine state as
            # of the resume round's entry.
            keep = max(
                8,
                getattr(self.checkpointer, "max_to_keep", 0)
                * max(1, self.checkpoint_every) + 2,
            ) if self.checkpointer is not None else 8
            for k in [k for k in self._qsnapshots
                      if k < round_idx - keep]:
                del self._qsnapshots[k]
        if status == "stop":
            self.stopped = True
            lp["done"] = True
            done_round = round_idx if (
                self.history and self.history[-1].get("round") == round_idx
            ) else round_idx - 1
            self._checkpoint_on_stop(done_round)
            return False
        if status == "final":
            lp["done"] = True
            return False
        lp["round_idx"] = round_idx + 1
        if lp["round_idx"] >= self.rounds:
            lp["done"] = True
            return False
        return True

    def finish(self) -> List[Dict[str, Any]]:
        """Close out a run: block on the async checkpoint commit, persist
        the resilience digest, and return the history."""
        if self.checkpointer is not None:
            # Orbax saves are async; block until the last step is durably
            # committed so a process exit right after run() can't lose it.
            self.checkpointer.wait()
        self._persist_resilience()
        self._loop = None
        return self.history

    def pending_device_rounds(self) -> int:
        """Device-rounds this task still has to commit (remaining rounds x
        total real population) — the MultiTaskDispatcher's fair-share
        currency."""
        nxt = self._loop["round_idx"] if self._loop is not None else 0
        remaining = max(0, self.rounds - nxt)
        return remaining * sum(
            p.dataset.num_real_clients for p in self.populations
        )

    def run(self) -> List[Dict[str, Any]]:
        self.begin()
        while self.step():
            pass
        return self.finish()


class MultiTaskDispatcher:
    """Multiplex several tasks' compiled round programs on one process.

    One engine process historically ran one task and idled between its
    rounds' host-side phases (trace compile, accounting, checkpoint IO).
    The dispatcher drives several :class:`SimulationRunner`\\ s at once
    ("Optimal Task Assignment to Heterogeneous FL Devices",
    arxiv 2010.00239 motivates multi-task sharing of one accelerator):

    - ``interleave="step"`` (default): deterministic cooperative
      round-robin through the runners' ``begin()/step()/finish`` API —
      each turn advances ONE round of one task. With ``fair_share=True``
      the task with the most *pending device-rounds* goes next
      (deficit-style fairness: big tasks cannot be starved by small
      ones); otherwise strict rotation. Per-task results are bitwise
      those of solo runs — task states are independent and the
      interleaving order never enters any task's math
      (tests/test_async.py asserts this).
    - ``interleave="thread"``: each task runs its full round loop on its
      own thread, so one task's host-side phases overlap another's
      device compute and the device queue stays fed between programs.

    Leases (PR 4 supervision, reused): given a ``task_repo`` with lease
    columns, the dispatcher claims each task's lease at start, renews it
    as a heartbeat (every turn in step mode; a daemon in thread mode),
    releases on finish, and FENCES a task whose renewal fails — another
    process (e.g. a TaskSupervisor that saw the lease expire) owns it
    now, so the local run stops and cedes the row, exactly like
    TaskManager's heartbeat fencing. A fenced task's checkpointed rounds
    stay durable; the reclaimer resumes from them.
    """

    def __init__(self, runners: List[SimulationRunner], *,
                 task_repo: Optional[TaskTableRepo] = None,
                 owner_id: Optional[str] = None,
                 lease_ttl_s: float = 30.0,
                 fair_share: bool = True,
                 interleave: str = "step",
                 logger: Optional[Logger] = None):
        if interleave not in ("step", "thread"):
            raise ValueError(
                f"interleave must be 'step' or 'thread', got {interleave!r}"
            )
        ids = [r.task_id for r in runners]
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate task ids in dispatcher: {ids}")
        self.runners = list(runners)
        self.task_repo = task_repo
        self.owner_id = owner_id or f"dispatcher-{os.getpid()}"
        self.lease_ttl_s = float(lease_ttl_s)
        self.fair_share = bool(fair_share)
        self.interleave = interleave
        self.logger = logger if logger is not None else Logger()
        # Task ids dropped mid-run because another process took their
        # lease (inspect after run(); their histories are NOT returned —
        # the new owner's are the ones of record).
        self.fenced: List[str] = []

    # ------------------------------------------------------------- leases
    def _claim(self, runner: SimulationRunner) -> bool:
        if self.task_repo is None:
            return True
        if not self.task_repo.has_task(runner.task_id):
            self.task_repo.add_task(runner.task_id)
        return self.task_repo.claim_lease(
            runner.task_id, self.owner_id, self.lease_ttl_s
        )

    def _renew(self, runner: SimulationRunner) -> bool:
        if self.task_repo is None:
            return True
        return self.task_repo.renew_lease(
            runner.task_id, self.owner_id, self.lease_ttl_s
        )

    def _release(self, runner: SimulationRunner) -> None:
        if self.task_repo is not None:
            self.task_repo.release_lease(runner.task_id, self.owner_id)

    @staticmethod
    def _retire(runner: SimulationRunner) -> None:
        """Retire a FINISHED task's per-task metric series from its
        registry — a dispatcher multiplexing a stream of tasks on one
        long-lived process otherwise leaks one labeled series
        (ols_engine_idle_seconds_total{task_id,...}, round histograms)
        per completed task. Fenced/errored tasks keep their series: they
        are not terminal here (the reclaimer/supervisor owns them)."""
        from olearning_sim_tpu.telemetry import default_registry

        # getattr: dispatcher tests drive duck-typed stub runners that
        # carry no telemetry sink.
        reg = getattr(runner, "registry", None)
        reg = reg if reg is not None else default_registry()
        reg.retire_label_value("task_id", runner.task_id)

    def _fence(self, runner: SimulationRunner) -> None:
        """Another process owns the task now: stop locally, cede the row
        (no release — the lease belongs to the new owner)."""
        self.fenced.append(runner.task_id)
        self.logger.warning(
            task_id=runner.task_id, system_name="engine",
            module_name="dispatcher",
            message="lease renewal failed; fencing task (another process "
                    "reclaimed it)",
        )

    # ---------------------------------------------------------------- run
    def run(self) -> Dict[str, List[Dict[str, Any]]]:
        """Drive every task to completion; returns task_id -> history for
        the tasks this process finished (fenced tasks excluded)."""
        if self.interleave == "thread":
            return self._run_threaded()
        return self._run_cooperative()

    def _pick(self, active: List[SimulationRunner],
              rotation: int) -> SimulationRunner:
        if not self.fair_share:
            return active[rotation % len(active)]
        # Deficit fairness: the task with the most pending device-rounds
        # goes next; ties break by list order (deterministic).
        return max(active, key=lambda r: r.pending_device_rounds())

    def _run_cooperative(self) -> Dict[str, List[Dict[str, Any]]]:
        active: List[SimulationRunner] = []
        results: Dict[str, List[Dict[str, Any]]] = {}
        errors: Dict[str, BaseException] = {}
        for r in self.runners:
            if not self._claim(r):
                self._fence(r)
                continue
            try:
                r.begin()
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:  # noqa: BLE001 — reported below
                # Same isolation as step/finish errors: in threaded mode
                # begin() runs inside the worker's try, so a task that
                # can't even start must not abandon its co-tasks here
                # either. Lease left to TTL-expire for the supervisor.
                errors[r.task_id] = e
                continue
            active.append(r)
        rotation = 0
        while active:
            # Renew EVERY active task's lease each turn, not just the
            # picked one: one compile-dominated step on task A must not
            # let healthy task B's lease TTL-expire and hand it to the
            # supervisor mid-run (this is the cooperative analogue of
            # the threaded mode's heartbeat thread).
            for other in list(active):
                if not self._renew(other):
                    active.remove(other)
                    self._fence(other)
            if not active:
                break
            r = self._pick(active, rotation)
            rotation += 1
            try:
                more = r.step()
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:  # noqa: BLE001 — reported below
                # Per-task error isolation, matching _run_threaded: one
                # task failing under its failure policy must not abandon
                # the other tasks mid-run (their finish()/checkpoint
                # commit and lease release still happen). The failed
                # task's lease is left to TTL-expire so the supervisor
                # owns its disposition, same as a failed thread.
                active.remove(r)
                errors[r.task_id] = e
                continue
            if not more:
                active.remove(r)
                try:
                    results[r.task_id] = r.finish()
                except (KeyboardInterrupt, SystemExit):
                    raise
                except BaseException as e:  # noqa: BLE001 — reported below
                    # finish() (checkpoint-commit wait, resilience
                    # persistence) failing for one task must not abandon
                    # the others mid-run — threaded mode runs finish()
                    # inside the worker's try. No release: the lease
                    # TTL-expires so the supervisor owns disposition.
                    errors[r.task_id] = e
                    continue
                self._release(r)
                self._retire(r)
        if errors:
            for tid, e in errors.items():
                self.logger.error(
                    task_id=tid, system_name="engine",
                    module_name="dispatcher",
                    message=f"task failed under dispatch: "
                            f"{type(e).__name__}: {e}",
                )
            raise next(iter(errors.values()))
        return results

    def _run_threaded(self) -> Dict[str, List[Dict[str, Any]]]:
        results: Dict[str, List[Dict[str, Any]]] = {}
        errors: Dict[str, BaseException] = {}
        started: List[SimulationRunner] = []
        for r in self.runners:
            if not self._claim(r):
                self._fence(r)
                continue
            if r.stop_event is None:
                # Fencing needs a handle to stop a running loop.
                r.stop_event = threading.Event()
            started.append(r)

        fenced_ids: set = set()

        def worker(r: SimulationRunner) -> None:
            try:
                results[r.task_id] = r.run()
            except BaseException as e:  # noqa: BLE001 — reported below
                errors[r.task_id] = e

        threads = [
            threading.Thread(target=worker, args=(r,),
                             name=f"dispatch-{r.task_id}", daemon=True)
            for r in started
        ]
        stop_heart = threading.Event()

        def heartbeat() -> None:
            # Renew every ttl/3 (the TaskManager cadence); a failed
            # renewal stops that task's loop at the next round boundary.
            while not stop_heart.wait(max(0.05, self.lease_ttl_s / 3.0)):
                for r in started:
                    if r.task_id in fenced_ids or r.task_id in results:
                        continue
                    if not self._renew(r):
                        fenced_ids.add(r.task_id)
                        self._fence(r)
                        r.stop_event.set()

        heart = None
        if self.task_repo is not None:
            heart = threading.Thread(target=heartbeat,
                                     name="dispatch-heartbeat", daemon=True)
            heart.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop_heart.set()
        if heart is not None:
            heart.join()
        for r in started:
            if r.task_id in fenced_ids:
                # A fenced task's history is not ours to report — the
                # reclaimer's run is the one of record.
                results.pop(r.task_id, None)
            elif r.task_id in results:
                self._release(r)
                self._retire(r)
        if errors:
            first = next(iter(errors.values()))
            for tid, e in errors.items():
                self.logger.error(
                    task_id=tid, system_name="engine",
                    module_name="dispatcher",
                    message=f"task failed under dispatch: "
                            f"{type(e).__name__}: {e}",
                )
            raise first
        return results
