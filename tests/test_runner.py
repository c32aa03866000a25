"""SimulationRunner round loop: operators, barriers, deviceflow lifecycle,
result accounting and end-to-end status fusion."""

import json
import os

import jax
import numpy as np
import pytest

from olearning_sim_tpu.deviceflow import DeviceFlowService
from olearning_sim_tpu.engine import build_fedcore, fedavg, fedcore, make_synthetic_dataset
from olearning_sim_tpu.engine.client_data import make_central_eval_set
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.engine.runner import DataPopulation, OperatorSpec, SimulationRunner
from olearning_sim_tpu.parallel.mesh import make_mesh_plan
from olearning_sim_tpu.taskmgr.operator_flow import FlagFileBarrier, OperatorFlowController
from olearning_sim_tpu.taskmgr.status import (
    SimHalfState,
    TaskStatus,
    calculate_conditions,
    combine_task_status,
)
from olearning_sim_tpu.taskmgr.task_repo import TaskTableRepo
from olearning_sim_tpu.telemetry import SpanTracer

INPUT_SHAPE = (12,)
NUM_CLASSES = 3


def build_runner(num_clients=32, rounds=3, operators=None, deviceflow=None, repo=None,
                 tracer=None, batch_size=4):
    plan = make_mesh_plan(dp=8)
    cfg = FedCoreConfig(batch_size=batch_size, max_local_steps=3, block_clients=2)
    core = build_fedcore(
        "mlp2", fedavg(0.1), plan, cfg,
        model_overrides={"hidden": (16,), "num_classes": NUM_CLASSES},
        input_shape=INPUT_SHAPE,
    )
    ds = make_synthetic_dataset(3, num_clients, 10, INPUT_SHAPE, NUM_CLASSES,
                                class_sep=4.0).pad_for(plan, 2).place(plan)
    # device classes: first half "high", second half "low"
    cls = (np.arange(ds.num_clients) >= num_clients // 2).astype(int)
    pop = DataPopulation(
        name="data_0",
        dataset=ds,
        device_classes=["high", "low"],
        class_of_client=cls,
        nums=[num_clients // 2, num_clients - num_clients // 2],
        dynamic_nums=[0, 0],
        eval_data=make_central_eval_set(3, 256, INPUT_SHAPE, NUM_CLASSES, class_sep=4.0),
    )
    runner = SimulationRunner(
        task_id="task_e2e",
        core=core,
        populations=[pop],
        operators=operators or [OperatorSpec(name="train")],
        rounds=rounds,
        task_repo=repo,
        deviceflow=deviceflow,
        tracer=tracer,
    )
    return runner


def test_round_loop_trains_and_accounts():
    repo = TaskTableRepo()
    runner = build_runner(rounds=3, repo=repo)
    history = runner.run()
    assert len(history) == 3
    losses = [h["train"]["data_0"]["mean_loss"] for h in history]
    assert losses[-1] < losses[0]
    # accounting persisted in the reference shape
    assert repo.get_item_value("task_e2e", "logical_round") == 3
    assert repo.get_item_value("task_e2e", "logical_operator") == "train"
    result = json.loads(repo.get_item_value("task_e2e", "logical_result"))
    sim = result["logical_result"][0]["simulation_target"]
    assert sim["devices"] == ["high", "low"]
    assert sum(sim["success_num"]) == 32
    assert sum(sim["failed_num"]) == 0


@pytest.mark.parametrize("flop_per_byte,computed", [
    pytest.param(0.0, 6, id="gather"),
    pytest.param(float("inf"), 10, id="multiplicity"),
])
def test_work_counts_follow_the_cores_minibatch_choice(
        monkeypatch, flop_per_byte, computed):
    """``samples_computed_per_step`` on the ``host_transfer`` span is what
    ``FedCore.use_multiplicity`` decided for the program that ran, on either
    side of the ``auto`` rule's constant (10 local rows for a batch of 6:
    the band where the model's cost decides)."""
    monkeypatch.setattr(fedcore, "GATHER_FLOP_PER_BYTE", flop_per_byte)
    tracer = SpanTracer()
    runner = build_runner(rounds=2, tracer=tracer, batch_size=6)
    assert runner.core.config.sample_mode == "auto"
    runner.run()
    x = runner.populations[0].dataset.x
    decided = runner.core.use_multiplicity(x.shape[1], x.shape[2:], x.dtype)
    assert decided == (computed == 10)
    transfers = [s for s in tracer.spans()
                 if s.name == "round.train.host_transfer"]
    assert len(transfers) == 2
    for s in transfers:
        assert (s.attrs["samples_computed_per_step"],
                s.attrs["samples_needed_per_step"]) == (computed, 6)


def test_status_fusion_from_runner_output():
    """Full pipeline: runner accounting -> calculate_conditions ->
    combine_task_status == SUCCEEDED."""
    repo = TaskTableRepo()
    runner = build_runner(rounds=2, repo=repo)
    runner.run()

    logical = SimHalfState(
        present=True,
        target=json.loads(repo.get_item_value("task_e2e", "logical_target"))["logical_target"],
        result=json.loads(repo.get_item_value("task_e2e", "logical_result"))["logical_result"],
        current_round=repo.get_item_value("task_e2e", "logical_round"),
        operator_name=repo.get_item_value("task_e2e", "logical_operator"),
    )
    tp = {
        "max_round": 2,
        "operator_name_list": ["train"],
        "data_name_list": ["data_0"],
        "total_simulation": [
            {"simulation_target": {"devices": ["high", "low"],
                                   "nums": [16, 16], "dynamic_nums": [0, 0]}}
        ],
    }
    c = calculate_conditions(tp, logical, SimHalfState(present=False))
    assert c.logical_success
    status = combine_task_status(c, TaskStatus.SUCCEEDED, True)
    assert status == TaskStatus.SUCCEEDED


def test_multi_operator_chain_with_eval():
    ops = [OperatorSpec(name="train"), OperatorSpec(name="evaluate", kind="eval")]
    runner = build_runner(rounds=2, operators=ops)
    history = runner.run()
    assert history[-1]["evaluate"]["data_0"]["eval_acc"] > 0.5
    # last persisted operator is the last of the chain
    assert runner.task_repo.get_item_value("task_e2e", "logical_operator") == "evaluate"


def test_custom_operator_escape_hatch():
    calls = []

    def my_op(runner, round_idx, op):
        calls.append(round_idx)
        return {"note": "external"}

    ops = [OperatorSpec(name="train"), OperatorSpec(name="ext", kind="custom", custom_fn=my_op)]
    runner = build_runner(rounds=2, operators=ops)
    history = runner.run()
    assert calls == [0, 1]
    assert history[0]["ext"]["data_0"]["note"] == "external"


def test_runner_with_deviceflow_lifecycle():
    """use_deviceflow operators must walk Register/NotifyStart/NotifyComplete
    and the trace strategy must modulate participation."""
    svc = DeviceFlowService(poll_interval=0.01)
    svc.start()
    try:
        svc.register_task("task_e2e", ["logical_simulation"])
        strategy = json.dumps({
            "flow_dispatch": {
                "use_strategy": True,
                "total_dispatch_amount": 20,
                "specific_timing": {
                    "use": True, "time_type": "relative",
                    "timings": [0], "amounts": [20],
                },
            }
        })
        ops = [OperatorSpec(name="train", use_deviceflow=True,
                            deviceflow_strategy=strategy)]
        runner = build_runner(rounds=2, operators=ops, deviceflow=svc)
        history = runner.run()
        # only 20 of 32 clients released per round by the trace
        assert history[0]["train"]["data_0"]["released"] == 20
        assert history[0]["train"]["data_0"]["clients_trained"] == 20
        # all flows completed -> dispatch finished gate opens
        import time
        deadline = time.monotonic() + 5
        while not svc.check_dispatch_finished("task_e2e") and time.monotonic() < deadline:
            time.sleep(0.02)
        assert svc.check_dispatch_finished("task_e2e")
    finally:
        svc.stop()


def test_operator_flow_flag_file_barrier(tmp_path):
    flag = tmp_path / "aggregation_finished.txt"

    # aggregator writes the flag "during" the round: pre-create it
    flag.write_text("done")
    flow = OperatorFlowController(
        "t", 1,
        start_params={"strategy": "sample_and_aggregation"},
        stop_params={"strategy": "sample_and_aggregation",
                     "wait_interval": 0.01, "total_timeout": 1},
        strategy_kwargs={"flag_path": str(flag)},
    )
    assert flow.start()
    assert flow.stop()
    assert not flag.exists()  # consumed
    # next stop times out (no flag)
    flow.stop_params["total_timeout"] = 0.05
    assert not flow.stop()


def test_operator_flow_polling_round_barrier():
    rounds = iter([5, 5, 6])
    provider = lambda: next(rounds)
    flow = OperatorFlowController(
        "t", 1,
        start_params={"strategy": "waiting_for_global_aggregation",
                      "wait_interval": 0.01, "total_timeout": 1},
        stop_params={"strategy": "waiting_for_global_aggregation",
                     "wait_interval": 0.01, "total_timeout": 1},
        strategy_kwargs={"round_provider": provider},
    )
    assert flow.start()
    assert flow.current_round == 5
    assert flow.stop()  # advances when provider returns 6
    assert flow.current_round == 6


def test_final_round_stop_tolerance():
    """Stop-barrier failure on the final round is tolerated
    (reference ``run_task.py:319-322``)."""
    flow = OperatorFlowController(
        "t", 2,
        stop_params={"strategy": "sample_and_aggregation",
                     "wait_interval": 0.01, "total_timeout": 0.05},
        strategy_kwargs={"flag_path": "/nonexistent/flag.txt"},
    )
    runner = build_runner(rounds=2)
    runner.operator_flow = flow
    with pytest.raises(RuntimeError):
        runner.run()  # first-round stop failure raises

    flow2 = OperatorFlowController(
        "t", 1,
        stop_params={"strategy": "sample_and_aggregation",
                     "wait_interval": 0.01, "total_timeout": 0.05},
        strategy_kwargs={"flag_path": "/nonexistent/flag.txt"},
    )
    runner2 = build_runner(rounds=1)
    runner2.operator_flow = flow2
    history = runner2.run()  # single round: tolerated
    assert len(history) == 1


def test_operator_dag_inputs_compose():
    """train -> eval -> custom chain: the custom operator consumes the train
    operator's round metrics through its declared `input` (the DAG the
    validator enforces, reference utils.py:647-651)."""
    seen = []

    def aggregate(runner, round_idx, operator, population):
        ins = runner.operator_inputs(operator)
        assert set(ins) == {"train"}
        train_rec = ins["train"][population.name]
        seen.append((round_idx, float(train_rec["mean_loss"])))
        return {"consumed_loss": float(train_rec["mean_loss"])}

    ops = [
        OperatorSpec(name="train", kind="train"),
        OperatorSpec(name="evaluate", kind="eval", inputs=["train"]),
        OperatorSpec(name="agg", kind="custom", inputs=["train"],
                     custom_fn=aggregate),
    ]
    runner = build_runner(rounds=2, operators=ops)
    history = runner.run()
    assert len(seen) == 2
    for h, (r, loss) in zip(history, seen):
        assert h["agg"]["data_0"]["consumed_loss"] == loss
        assert loss == h["train"]["data_0"]["mean_loss"]
