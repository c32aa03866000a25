"""Runner: whole rounds inside the measured window. A count, there to show
that a per-layer metric with a ``workloads`` list arrives as one file and
one manifest entry."""

LAYER = "Runner"
UNIT = "count"
SOURCE = "program_span"
MOVES = "device_rounds_per_s"


def read(ctx):
    return len(ctx.window.rounds)
