"""TaskConfig -> engine bridge: ``engine.run(task_json)``.

Realizes SURVEY.md section 7 step 1's goal: the same task-JSON schema the
reference accepts drives the TPU engine directly. Where the reference ships
operator *code archives* fetched per task (``utils_runner.py:684-782``) and
runs them as subprocesses, the rebuild's fast path addresses *builtin*
operators by name::

    "logical_simulation": {
        "operator_code_path": "builtin:train",   # or builtin:eval
        "operator_params": "{ ...engine params json... }"
    }

Arbitrary user code still works through the ``custom`` operator kind
(``engine/runner.py``). Engine params schema (all optional, defaults below):

    {
      "model":     {"name": "cnn4", "overrides": {...}, "input_shape": [32,32,3]},
      "algorithm": {"name": "fedavg", "local_lr": 0.05, ...},
      "fedcore":   {"batch_size": 32, "max_local_steps": 10, "block_clients": 64,
                    "carry_dtype": "bf16",          # bf16 local-SGD carry (validated)
                    "shard_server_update": false},  # O(params/dp) server update
      "data":      {"synthetic": {"seed": 0, "n_local": 20, "num_classes": 10,
                    "dirichlet_alpha": null, "class_sep": 2.0}, "eval_n": 1024},
      "resilience": { ...ResilienceConfig.from_dict... },    # docs/resilience.md
      "deadline":   { ...DeadlineConfig.from_dict... },      # deadline-aware rounds
      "defense":    { ...DefenseConfig.from_dict... },       # adversarial defense
      "quarantine": {"preseed": {"data_0": [3, 7]}},         # device blocklists
      "checkpoint": {"directory": "/ckpts/{task_id}",        # crash-safe resume
                     "every": 1, "max_to_keep": 3}
    }

What a sample's loss is follows the task's ``task_type`` (target data):
``"next_token"`` / ``"next_token_prediction"`` train and evaluate a language
model on the tokens themselves shifted by one (``FedCoreConfig.task``; the
synthetic generator's class labels are then topics the loss ignores); any
other value is classification, one label a sample.

The ``checkpoint`` block is what makes a task supervisable: it gives the
runner a ``RoundCheckpointer`` rooted at a durable per-task directory
(``{task_id}`` is substituted; relative/omitted directories land under the
system temp dir), so a relaunch of the same task — crash recovery through
``supervisor.TaskSupervisor``, or a plain restart — resumes from the last
committed round instead of replaying from zero.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import threading
from typing import Any, Dict, Optional

import numpy as np

from olearning_sim_tpu.engine.algorithms import from_config as algorithm_from_config
from olearning_sim_tpu.engine.client_data import (
    make_central_eval_set,
    make_central_text_eval_set,
    make_synthetic_dataset,
    make_synthetic_text_dataset,
)
from olearning_sim_tpu.engine.fedcore import FedCoreConfig, build_fedcore
from olearning_sim_tpu.engine.runner import (
    DataPopulation,
    OperatorSpec,
    SimulationRunner,
)
from olearning_sim_tpu.parallel.mesh import MeshPlan, make_mesh_plan
from olearning_sim_tpu.proto import taskservice_pb2 as pb
from olearning_sim_tpu.taskmgr.codecs import json2taskconfig
from olearning_sim_tpu.taskmgr.operator_flow import OperatorFlowController

BUILTIN_PREFIX = "builtin:"
# ``task_type`` values (task JSON, target data) that ask for the next-token
# loss (``FedCoreConfig.task``); every other value is classification.
NEXT_TOKEN_TASK_TYPES = ("next_token", "next_token_prediction")


def _engine_params(tc: pb.TaskConfig) -> Dict[str, Any]:
    """Engine params: first builtin operator's operatorParams JSON."""
    for op in tc.operatorFlow.operator:
        info = op.logicalSimulationOperatorInfo
        if info.operatorCodePath.startswith(BUILTIN_PREFIX) and info.operatorParams:
            return json.loads(info.operatorParams)
    return {}


def _operator_specs(tc: pb.TaskConfig, storage: Optional[Dict[str, Any]] = None) -> list:
    specs = []
    for op in tc.operatorFlow.operator:
        info = op.logicalSimulationOperatorInfo
        if info.operatorCodePath == "":
            # Device-only operator: belongs to the phone half, nothing for
            # the TPU engine to run (validation allows this shape).
            continue
        if not info.operatorCodePath.startswith(BUILTIN_PREFIX):
            # External user code: stage it (zip or dir) and run it through the
            # subprocess escape hatch (reference get_operator_code,
            # utils_runner.py:684-782 + the per-phone subprocess loop).
            import tempfile

            from olearning_sim_tpu.operators import external_operator_spec
            from olearning_sim_tpu.storage import (
                FileTransferType,
                fetch_operator_code,
                make_file_repo,
            )

            path = info.operatorCodePath
            if os.path.isdir(path):
                code_dir = path
            else:
                repo = make_file_repo(
                    FileTransferType(info.operatorTransferType),
                    **(storage or {}),
                )
                code_dir = fetch_operator_code(
                    repo, path, tempfile.mkdtemp(prefix=f"op_{op.name}_")
                )
            specs.append(external_operator_spec(
                name=op.name,
                code_dir=code_dir,
                entry_file=info.operatorEntryFile,
                operator_params=info.operatorParams,
                use_deviceflow=op.operationBehaviorController.useController,
                deviceflow_strategy=(
                    op.operationBehaviorController.strategyBehaviorController
                ),
                inputs=list(op.input),
            ))
            continue
        kind = info.operatorCodePath[len(BUILTIN_PREFIX):]
        if kind not in ("train", "eval"):
            raise ValueError(f"operator {op.name}: unknown builtin operator {kind!r}")
        specs.append(
            OperatorSpec(
                name=op.name,
                kind=kind,
                use_deviceflow=op.operationBehaviorController.useController,
                deviceflow_strategy=op.operationBehaviorController.strategyBehaviorController,
                outbound_service=op.operationBehaviorController.outboundService,
                inputs=list(op.input),
            )
        )
    return specs


def build_runner_from_taskconfig(
    tc: pb.TaskConfig | str | Dict[str, Any],
    plan: Optional[MeshPlan] = None,
    task_repo=None,
    deviceflow=None,
    stop_event: Optional["threading.Event"] = None,
    perf=None,
    checkpointer=None,
    cost_oracle=None,
    registry=None,
) -> SimulationRunner:
    """Build a ready-to-run SimulationRunner from a TaskConfig proto or the
    equivalent task JSON. ``cost_oracle`` — a
    :class:`~olearning_sim_tpu.taskmgr.pool.CostOracle` the runner feeds
    measured per-round wall times into (the chip-pool scheduler's live
    telemetry loop); the family key follows ``CostOracle.family_of``.
    ``registry`` — the telemetry MetricsRegistry the runner instruments
    into (None = process default); pass the same instance the embedding
    TaskManager retires finished tasks' series from."""
    if not isinstance(tc, pb.TaskConfig):
        tc = json2taskconfig(tc)
    from olearning_sim_tpu.telemetry import (
        default_tracer, stamp_device_memory)

    # The task's span tree (docs/observability.md): bridge.build with its
    # children bridge.build_fedcore, bridge.generate and bridge.place, all
    # carrying the task id; the runner adds bridge.init_state and round.*.
    tracer = default_tracer()
    span = functools.partial(tracer.span, task_id=tc.taskID.taskID)
    with span("bridge.build") as build:
        # What the device held before this task put anything there (a
        # long-lived server has other tasks' buffers).
        stamp_device_memory(build, "_before")
        return _build_runner(tc, span, plan, task_repo, deviceflow,
                             stop_event, perf, checkpointer, cost_oracle,
                             registry)


def _build_runner(tc: pb.TaskConfig, span, plan, task_repo, deviceflow,
                  stop_event, perf, checkpointer, cost_oracle,
                  registry) -> SimulationRunner:
    """:func:`build_runner_from_taskconfig`'s body; ``span(name)`` opens a
    span of the task's tree."""
    # Persistent XLA compilation cache: every task-bridge build (fresh
    # submits, bench children, supervisor relaunches after a crash) shares
    # the durable cache under artifacts/, so a relaunched or repeated
    # variant deserializes its round programs instead of recompiling.
    # Disable with OLS_COMPILE_CACHE=0 (docs/performance.md).
    from olearning_sim_tpu.engine.compile_cache import enable_compile_cache
    from olearning_sim_tpu.telemetry import stamp_device_memory

    enable_compile_cache()
    params = _engine_params(tc)

    # Model parallelism rides the engine params blob (docs/performance.md):
    #   {"parallel": {"mp": 2}}                      # tensor parallel
    #   {"parallel": {"pp": 2, "microbatches": 4}}   # stage pipelined
    # The block selects the mesh shape, so it is resolved BEFORE the plan:
    # with no injected plan the mesh is built to the block's mp/pp; an
    # injected plan must realize the block (a task validated for mp=2 must
    # never silently run replicated on a dp-only mesh).
    from olearning_sim_tpu.parallel.mesh import ParallelConfig

    parallel = (ParallelConfig.from_dict(params["parallel"])
                if params.get("parallel") else ParallelConfig())
    if plan is None:
        plan = parallel.make_plan() if parallel.enabled else make_mesh_plan()
    elif parallel.enabled and not parallel.matches(plan):
        raise ValueError(
            f"task {tc.taskID.taskID}: engine params ask for "
            f"parallel mp={parallel.mp} pp={parallel.pp} but the supplied "
            f"mesh plan has mp={plan.mp} pp={plan.pp}"
        )

    model_cfg = params.get("model", {})
    algo_cfg = dict(params.get("algorithm", {}))
    fed_cfg = params.get("fedcore", {})
    data_cfg = params.get("data", {})

    # One validated parser for every fedcore knob (carry_dtype included) —
    # the submit validator (taskmgr/validation.py) runs the same from_dict,
    # so a typo'd or wrong-typed knob fails at submit time, not mid-round.
    cfg = FedCoreConfig.from_dict(fed_cfg)
    # The task's ``task_type`` (target data) names what a sample's loss is.
    task_types = {td.taskType for td in tc.target.targetData}
    if task_types & set(NEXT_TOKEN_TASK_TYPES):
        if len(task_types) > 1:
            raise ValueError(
                f"task {tc.taskID.taskID}: target data mix task types "
                f"{sorted(task_types)}; one round program has one loss")
        cfg = dataclasses.replace(cfg, task="next_token")
    algorithm = algorithm_from_config(algo_cfg.pop("name", "fedavg"), **algo_cfg)
    input_shape = tuple(model_cfg.get("input_shape", [])) or None
    with span("bridge.build_fedcore"):
        core = build_fedcore(
            model_cfg.get("name", "mlp2"),
            algorithm,
            plan,
            cfg,
            model_overrides=model_cfg.get("overrides"),
            input_shape=input_shape,
            microbatches=parallel.microbatches,
        )

    # Scenario traces + streamed cohorts ride the same blob
    # (docs/performance.md):
    #   {"scenario": {"online_base": 0.4, "online_amp": 0.3,
    #                 "spikes": [{"round": 3, "rounds": 2, "boost": 3.0}],
    #                 "leave_rate": 0.001, "drift_period_rounds": 20,
    #                 "stream_block_rows": 2048}}
    # With stream_block_rows the population stays HOST-resident
    # (HostClientStore) and train rounds run block-streamed
    # (FedCore.stream_round — O(block) HBM); without it scenario masks
    # apply to the ordinary resident program.
    scenario = None
    if params.get("scenario"):
        from olearning_sim_tpu.engine.scenario import ScenarioConfig

        scenario = ScenarioConfig.from_dict(params["scenario"])

    from olearning_sim_tpu.models import get_model

    spec = get_model(model_cfg.get("name", "mlp2"))
    syn = data_cfg.get("synthetic", {})
    # The model's configured head size is the source of truth for how many
    # classes it can emit (mirrors the vocab-size handling below); the
    # synthetic generator may use fewer.
    model_classes = int(
        (model_cfg.get("overrides") or {}).get(
            "num_classes", spec.defaults.get("num_classes", spec.num_classes)
        )
    )
    num_classes = int(syn.get("num_classes", model_classes))
    if cfg.task == "next_token":
        # The generator's classes are topics (token bands) the next-token
        # loss never reads; the head is the vocabulary.
        num_classes = int(syn.get("num_classes", 2))
    elif num_classes > model_classes:
        raise ValueError(
            f"data.synthetic.num_classes={num_classes} exceeds the model's "
            f"head size {model_classes}; labels would fall outside the logits"
        )
    if input_shape is None:
        input_shape = spec.example_input_shape
    # Token models (int input dtype) get the text population; everything else
    # the Gaussian-blob image/feature population.
    is_text = np.issubdtype(np.dtype(spec.input_dtype), np.integer)
    # The model's embedding table is the source of truth for vocab size; a
    # mismatched data vocab would silently clamp out-of-range token gathers.
    model_vocab = int(
        (model_cfg.get("overrides") or {}).get(
            "vocab_size", spec.defaults.get("vocab_size", 30522)
        )
    )
    vocab_size = int(syn.get("vocab_size", model_vocab))
    if is_text and vocab_size > model_vocab:
        raise ValueError(
            f"data.synthetic.vocab_size={vocab_size} exceeds the model's "
            f"vocab_size={model_vocab}; token ids would fall outside the "
            f"embedding table"
        )

    populations = []
    for td in tc.target.targetData:
        devices = list(td.totalSimulation.deviceTotalSimulation)
        # The logical half simulates only its allocated share of device-
        # rounds; the remainder belongs to real phones (hybrid split,
        # reference JobSubmitter projection utils_runner.py:498-561).
        alloc = [int(a) for a in td.allocation.allocationLogicalSimulation]
        if alloc and any(a > 0 for a in alloc):
            nums = alloc
        else:
            nums = [int(n) for n in td.totalSimulation.numTotalSimulation]
        dynamic = [int(n) for n in td.totalSimulation.dynamicNumTotalSimulation]
        if not dynamic:
            dynamic = [0] * len(nums)
        num_clients = sum(nums)
        eval_data = None
        pop_classes = num_classes
        with span("bridge.generate"):
            if td.dataPath:
                # Real dataset: honor dataPath + dataTransferType (reference
                # download_data_files, utils_run_task.py:174-325). The archive's
                # test split (or a held-out tail) is the central eval set.
                from olearning_sim_tpu.data import load_population

                real_cfg = data_cfg.get("real", {})
                text_kwargs = (
                    {"vocab_size": vocab_size, "seq_len": int(input_shape[0])}
                    if is_text else {}
                )
                ds, eval_data, data_classes = load_population(
                    td.dataPath,
                    num_clients=num_clients,
                    n_local=int(real_cfg.get("n_local", syn.get("n_local", 20))),
                    scheme=real_cfg.get("scheme", "dirichlet"),
                    alpha=float(real_cfg.get("alpha", syn.get("dirichlet_alpha") or 0.5)),
                    seed=int(syn.get("seed", 0)),
                    transfer_type=td.dataTransferType,
                    storage_settings=params.get("storage"),
                    eval_n=data_cfg.get("eval_n"),
                    **text_kwargs,
                )
                if data_classes > model_classes:
                    raise ValueError(
                        f"dataset at {td.dataPath!r} has {data_classes} classes "
                        f"but the model's head emits only {model_classes}"
                    )
                pop_classes = data_classes
            elif is_text:
                ds = make_synthetic_text_dataset(
                    seed=int(syn.get("seed", 0)),
                    num_clients=num_clients,
                    n_local=int(syn.get("n_local", 20)),
                    seq_len=int(input_shape[0]),
                    num_classes=num_classes,
                    vocab_size=vocab_size,
                    dirichlet_alpha=syn.get("dirichlet_alpha"),
                )
            else:
                ds = make_synthetic_dataset(
                    seed=int(syn.get("seed", 0)),
                    num_clients=num_clients,
                    n_local=int(syn.get("n_local", 20)),
                    input_shape=input_shape,
                    num_classes=num_classes,
                    dirichlet_alpha=syn.get("dirichlet_alpha"),
                    class_sep=float(syn.get("class_sep", 2.0)),
                )
        store = None
        if scenario is not None and scenario.streamed:
            # Streamed population: never placed whole — the round engine
            # streams device-sized blocks from this host store.
            from olearning_sim_tpu.engine.client_data import HostClientStore

            store = HostClientStore.from_dataset(ds)
        else:
            with span("bridge.place") as placed:
                ds = ds.pad_for(plan, cfg.block_clients).place(plan)
                stamp_device_memory(placed)
        cls = np.zeros(ds.num_clients, int)
        start = 0
        for ci, n in enumerate(nums):
            cls[start : start + n] = ci
            start += n
        if eval_data is None and not td.dataPath and data_cfg.get("eval_n"):
            with span("bridge.generate"):
                if is_text:
                    eval_data = make_central_text_eval_set(
                        int(syn.get("seed", 0)), int(data_cfg["eval_n"]),
                        int(input_shape[0]), num_classes,
                        vocab_size=vocab_size,
                    )
                else:
                    eval_data = make_central_eval_set(
                        int(syn.get("seed", 0)), int(data_cfg["eval_n"]),
                        input_shape, num_classes,
                        class_sep=float(syn.get("class_sep", 2.0)),
                    )
        # Heterogeneous compute profiles: {"<device_class>": local_steps}
        # (Ditto/BASELINE config 5); unlisted classes run max_local_steps.
        profiles = data_cfg.get("compute_profiles") or {}
        num_steps = None
        if profiles:
            steps = np.full(ds.num_clients, cfg.max_local_steps, np.int32)
            for ci, dev in enumerate(devices):
                if dev in profiles:
                    steps[cls == ci] = int(profiles[dev])
            num_steps = steps
        populations.append(
            DataPopulation(
                name=td.dataName,
                dataset=ds,
                device_classes=devices,
                class_of_client=cls,
                nums=nums,
                dynamic_nums=dynamic,
                eval_data=eval_data,
                num_steps=num_steps,
                store=store,
                num_classes=pop_classes,
            )
        )

    fs = tc.operatorFlow.flowSetting
    start_strat = fs.startCondition.logicalSimulationStrategy
    stop_strat = fs.stopCondition.logicalSimulationStrategy
    flow = OperatorFlowController(
        tc.taskID.taskID,
        fs.round,
        start_params={
            "strategy": start_strat.strategyCondition,
            "wait_interval": start_strat.waitInterval,
            "total_timeout": start_strat.totalTimeout,
        },
        stop_params={
            "strategy": stop_strat.strategyCondition,
            "wait_interval": stop_strat.waitInterval,
            "total_timeout": stop_strat.totalTimeout,
        },
        strategy_kwargs=params.get("operator_flow", {}),
        stop_event=stop_event,
    )

    # Model proto (taskservice.proto Model): warm start + per-round export
    # named by modelUpdateStyle (reference download_model_files,
    # utils_run_task.py:327-397).
    model_io = None
    warm_start_path = None
    for op in tc.operatorFlow.operator:
        m = op.model
        if not (m.useModel or m.modelUpdateStyle):
            continue
        from olearning_sim_tpu.checkpoint import ModelUpdateExporter
        from olearning_sim_tpu.storage import FileTransferType, make_file_repo

        repo = make_file_repo(
            FileTransferType(m.modelTransferType), **(params.get("storage") or {})
        )
        model_io = ModelUpdateExporter(
            repo,
            tc.taskID.taskID,
            **({"update_style": m.modelUpdateStyle} if m.modelUpdateStyle else {}),
        )
        if m.useModel and m.modelPath:
            warm_start_path = m.modelPath
        break

    # Resilience knobs ride the engine params blob (docs/resilience.md):
    #   {"resilience": {"failure_policy": "retry", "max_round_retries": 2,
    #                   "quarantine_after": 1, "readmit_after": 3,
    #                   "rpc_retry": {"max_attempts": 3, "base_delay": 0.05}}}
    resilience = None
    if params.get("resilience"):
        from olearning_sim_tpu.resilience import ResilienceConfig

        resilience = ResilienceConfig.from_dict(params["resilience"])

    # Crash-safe resume: the checkpoint block builds the runner's
    # RoundCheckpointer unless the caller already injected one. Directory
    # is per-task ({task_id} substituted) so two tasks never share steps.
    # ``every`` applies either way — an injected checkpointer must not
    # silently force per-round cadence.
    ckpt_cfg = params.get("checkpoint")
    checkpoint_every = int(ckpt_cfg.get("every", 1)) if ckpt_cfg else 1
    if checkpointer is None and ckpt_cfg:
        import tempfile

        from olearning_sim_tpu.checkpoint import RoundCheckpointer

        task_id = tc.taskID.taskID
        # str.replace, not .format: a path with any other brace (literal or
        # foreign placeholder) must pass through, not raise.
        directory = str(ckpt_cfg.get("directory") or "").replace(
            "{task_id}", task_id
        )
        if not directory:
            directory = os.path.join(
                tempfile.gettempdir(), "ols_checkpoints", task_id
            )
        elif not os.path.isabs(directory):
            # Anchor relative paths: a supervisor relaunch from a different
            # CWD must open the SAME directory or it would silently resume
            # from round 0.
            directory = os.path.join(tempfile.gettempdir(), directory)
        checkpointer = RoundCheckpointer(
            directory,
            max_to_keep=int(ckpt_cfg.get("max_to_keep", 3)),
            task_id=task_id,
        )

    # Deadline-aware rounds ride the same blob (docs/resilience.md):
    #   {"deadline": {"deadline_s": 30.0, "over_selection": 0.3,
    #                 "target_cohort": 80, "quorum_fraction": 0.5,
    #                 "speed_profiles": {"high": 0.05, "low": 0.4},
    #                 "adaptive": true}}
    deadline = None
    if params.get("deadline"):
        from olearning_sim_tpu.engine.pacing import DeadlineConfig

        deadline = DeadlineConfig.from_dict(params["deadline"])

    # Adversarial-client defense rides the same blob (docs/resilience.md):
    #   {"defense": {"clip_norm": 5.0, "aggregator": "trimmed_mean",
    #                "trim_fraction": 0.1, "anomaly_threshold": 4.0}}
    defense = None
    if params.get("defense"):
        from olearning_sim_tpu.engine.defense import DefenseConfig

        defense = DefenseConfig.from_dict(params["defense"])

    # Buffered asynchronous rounds ride the same blob
    # (docs/performance.md):
    #   {"async": {"buffer_size": 64, "max_staleness": 8,
    #              "schedule": "polynomial", "staleness_alpha": 0.5,
    #              "speed_profiles": {"high": 0.05, "low": 0.4}}}
    async_config = None
    if params.get("async"):
        from olearning_sim_tpu.engine.async_rounds import AsyncConfig

        async_config = AsyncConfig.from_dict(params["async"])

    # Convergence tracking rides the same blob (docs/performance.md
    # "Time-to-accuracy benching"):
    #   {"convergence": {"target_accuracy": 0.9, "eval_every": 5,
    #                    "round_budget": 40, "sim_seconds_budget": 1800}}
    convergence = None
    if params.get("convergence"):
        from olearning_sim_tpu.engine.convergence import ConvergenceConfig

        convergence = ConvergenceConfig.from_dict(params["convergence"])

    # Operator blocklists: {"quarantine": {"preseed": {"data_0": [3, 7]}}}
    # — known-bad device ids quarantined from round 0 (validated again by
    # the runner against the actual population sizes).
    quarantine_preseed = None
    if params.get("quarantine"):
        from olearning_sim_tpu.resilience.quarantine import (
            parse_quarantine_params,
        )

        quarantine_preseed = parse_quarantine_params(
            params["quarantine"]
        )["preseed"]

    return SimulationRunner(
        task_id=tc.taskID.taskID,
        core=core,
        populations=populations,
        operators=_operator_specs(tc, storage=params.get("storage")),
        rounds=fs.round,
        task_repo=task_repo,
        deviceflow=deviceflow,
        operator_flow=flow,
        stop_event=stop_event,
        perf=perf,
        checkpointer=checkpointer,
        checkpoint_every=checkpoint_every,
        model_io=model_io,
        warm_start_path=warm_start_path,
        resilience=resilience,
        deadline=deadline,
        defense=defense,
        quarantine_preseed=quarantine_preseed,
        async_config=async_config,
        scenario=scenario,
        convergence=convergence,
        cost_oracle=cost_oracle,
        cost_family=(_cost_family(tc) if cost_oracle is not None else None),
        registry=registry,
    )


def _cost_family(tc: pb.TaskConfig) -> str:
    """The CostOracle family key for this task (lazy import: the bridge
    must not pull taskmgr in for pool-less builds)."""
    from olearning_sim_tpu.taskmgr.pool import CostOracle

    return CostOracle.family_of(tc)
