"""Intake & scheduling: the program's ``task.queue_wait`` span — submit
accepted to engine job launched, the interval
``ols_taskmgr_task_wait_seconds`` observes — timed inside the task manager,
where ``intake.submit_to_running_s`` is a 20 ms poll from outside."""

from benchmark import program_spans

LAYER = "Intake & scheduling"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    return program_spans.seconds(ctx, "task.queue_wait")
