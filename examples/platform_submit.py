"""Full-platform flow: boot a SimulatorSession from a YAML config, submit a
reference-schema task JSON over gRPC, and poll it to completion (the
reference's submitTask → schedule → run → getTaskStatus loop)."""

import _bootstrap  # noqa: F401 — repo path

import json
import time

import grpc

from olearning_sim_tpu.config import build_session
from olearning_sim_tpu.taskmgr.codecs import json2taskconfig
from olearning_sim_tpu.taskmgr.grpc_service import TaskMgrClient
from olearning_sim_tpu.taskmgr.status import TaskStatus
from olearning_sim_tpu.utils.clocks import Deadline


def make_task(task_id: str) -> dict:
    engine_params = {
        "model": {"name": "mlp2", "overrides": {"hidden": [32], "num_classes": 4},
                  "input_shape": [16]},
        "algorithm": {"name": "fedavg", "local_lr": 0.1},
        "fedcore": {"batch_size": 8, "max_local_steps": 3, "block_clients": 4},
        "data": {"synthetic": {"seed": 1, "n_local": 12, "num_classes": 4,
                               "class_sep": 3.0}, "eval_n": 128},
    }
    return {
        "user_id": "example_user",
        "task_id": task_id,
        "target": {
            "priority": 1,
            "data": [{
                "name": "data_0", "data_path": "", "data_split_type": False,
                "data_transfer_type": "FILE", "task_type": "classification",
                "total_simulation": {"devices": ["high"], "nums": [32],
                                      "dynamic_nums": [0]},
                "allocation": {"optimization": False,
                                "logical_simulation": [32],
                                "device_simulation": [0],
                                "running_response": {"devices": [], "nums": []}},
            }],
        },
        "operatorflow": {
            "flow_setting": {"round": 3,
                "start": {"logical_simulation": {"strategy": "", "wait_interval": 0,
                                                  "total_timeout": 0},
                           "device_simulation": {"strategy": "", "wait_interval": 0,
                                                  "total_timeout": 0}},
                "stop": {"logical_simulation": {"strategy": "", "wait_interval": 0,
                                                 "total_timeout": 0},
                          "device_simulation": {"strategy": "", "wait_interval": 0,
                                                 "total_timeout": 0}}},
            "operators": [{
                "name": "train", "input": [],
                "logical_simulation": {
                    "simulation_num": 32,
                    "operator_code_path": "builtin:train",
                    "operator_entry_file": "",
                    "operator_transfer_type": "FILE",
                    "operator_params": json.dumps(engine_params)},
                "device_simulation": {},
                "operation_behavior_controller": {
                    "use_gradient_house": False,
                    "strategy_gradient_house": ""},
            }],
        },
        "logical_simulation": {
            "computation_unit": {"devices": ["high"],
                                  "setting": [{"num_cpus": 1}]},
            "resource_request": [{"name": "data_0", "devices": ["high"],
                                   "num_request": [1]}]},
        "device_simulation": {"resource_request": [{"name": "data_0",
                                                     "devices": [],
                                                     "num_request": []}]},
    }


def main():
    session = build_session({
        "session": {"services": ["taskmgr", "resourcemgr", "phonemgr",
                                  "performancemgr"],
                    "address": "127.0.0.1:0"},
        "taskmgr": {"schedule_interval": 0.2, "release_interval": 0.2,
                     "interrupt_interval": 3600},
        "phonemgr": {"inventory": {"example_user": {"high": 4}},
                      "speedup": 1000.0},
    })
    with session:
        print(f"platform up on 127.0.0.1:{session.port}")
        with grpc.insecure_channel(f"127.0.0.1:{session.port}") as ch:
            client = TaskMgrClient(ch)
            tc = json2taskconfig(json.dumps(make_task("example-task")))
            status = client.submitTask(tc)
            print("submitTask:", status.is_success)
            # Monotonic countdown: immune to NTP/wall-clock steps
            # (utils.clocks is the platform's one timeout clock).
            deadline = Deadline(120.0)
            while not deadline.expired():
                st = TaskStatus(client.getTaskStatus("example-task").taskStatus)
                print("status:", st.name)
                if st in (TaskStatus.SUCCEEDED, TaskStatus.FAILED):
                    break
                time.sleep(1.0)
            assert st == TaskStatus.SUCCEEDED, st
            print("task completed successfully")


if __name__ == "__main__":
    main()
