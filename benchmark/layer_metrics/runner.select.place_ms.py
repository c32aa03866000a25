"""Runner: median over the window's rounds of the ``select`` phase's
``place`` stage — the round's participation mask (and completion times,
per-client step counts where configured) put on the chips."""

from benchmark import program_spans

LAYER = "Runner"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "round_s.p50"


def read(ctx):
    return program_spans.median(
        program_spans.window_round_ms(ctx, "select", "place"))
