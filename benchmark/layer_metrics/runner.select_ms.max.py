"""Runner: the longest ``select`` phase among the window's rounds. One
round in 25 takes 0.27-0.28 s where the others take 0.15-0.19 s (PERF.md
section 2): those rounds are the run-to-run spread of the rate."""

from benchmark import program_spans

LAYER = "Runner"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "device_rounds_per_s"


def read(ctx):
    per_round = program_spans.window_round_ms(ctx, "select")
    return None if per_round is None else max(per_round)
