"""Runner: median over the window's rounds of the ``eval`` phase's
``place`` stages — the evaluation set sent to the chips again every round,
batch by batch, while they wait. 0 where the task evaluates nothing."""

from benchmark import program_spans

LAYER = "Runner"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "round_s.p50"


def read(ctx):
    return program_spans.median(
        program_spans.window_round_ms(ctx, "eval", "place"))
