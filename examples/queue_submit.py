"""Non-gRPC intake: push task JSON onto the durable sqlite FIFO (the
reference's Redis-list submit path) and let the scheduler daemon drain it
through the normal validated submit."""

import _bootstrap  # noqa: F401 — repo path

import json
import os
import tempfile
import time

from olearning_sim_tpu.config import build_session
from olearning_sim_tpu.taskmgr.queue_repo import SqliteQueueRepo
from olearning_sim_tpu.taskmgr.status import TaskStatus
from olearning_sim_tpu.utils.clocks import Deadline

from platform_submit import make_task


def main():
    with tempfile.TemporaryDirectory() as d:
        intake_path = os.path.join(d, "intake.db")

        # Producer side: any local process, no gRPC needed.
        producer = SqliteQueueRepo(intake_path)
        producer.push(json.dumps(make_task("queued-task")))
        producer.close()
        print("task JSON pushed to", intake_path)

        # Platform side: the scheduler daemon drains the FIFO each tick.
        session = build_session({
            "session": {"services": ["taskmgr", "resourcemgr", "phonemgr"],
                        "address": "127.0.0.1:0"},
            "taskmgr": {"schedule_interval": 0.2, "release_interval": 0.2,
                         "interrupt_interval": 3600},
            "repos": {"intake_queue_path": intake_path},
            "phonemgr": {"inventory": {"example_user": {"high": 4}},
                          "speedup": 1000.0},
        })
        with session:
            # Monotonic countdown: immune to NTP/wall-clock steps
            # (utils.clocks is the platform's one timeout clock).
            deadline = Deadline(120.0)
            while not deadline.expired():
                st = session.task_manager.get_task_status("queued-task")
                print("status:", st.name)
                if st in (TaskStatus.SUCCEEDED, TaskStatus.FAILED):
                    break
                time.sleep(1.0)
            assert st == TaskStatus.SUCCEEDED, st
            print("queued task completed successfully")


if __name__ == "__main__":
    main()
