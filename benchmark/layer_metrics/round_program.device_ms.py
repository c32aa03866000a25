"""Round program: device-busy time per round, from the profiler trace alone.

Rule: the traced stretch is a whole number of rounds (round start to round
start, ``harness._trace_stretch``); the union of the ``XLA Ops`` intervals
inside it, mean over the chips, divided by that number of rounds. In a
steady round everything the device runs is the round program — the jitted
round step, the evaluate program and a [C] mask multiply — so busy time per
round is its device time. Whole ``XLA Modules`` executions are not used: a
round step lasts seconds and its event is clipped or lost at the profiler's
edges."""

LAYER = "Round program"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "round_s.p50"


def per_round_seconds(ctx):
    if ctx.trace is None or not ctx.trace_rounds:
        return None
    return ctx.trace.busy_s / ctx.trace_rounds


def read(ctx):
    seconds = per_round_seconds(ctx)
    return None if seconds is None else 1e3 * seconds
