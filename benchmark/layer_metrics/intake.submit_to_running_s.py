"""Intake & scheduling: from ``submitTask`` returning to the task's status
first read as RUNNING (harness clock, polled every 20 ms): validation,
queue, resource freeze, scheduler tick, job launch."""

LAYER = "Intake & scheduling"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(ctx):
    return ctx.t_running - ctx.t_submitted
