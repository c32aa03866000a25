"""Runner: median over the window's rounds of the ``select`` phase's
``compile_trace`` stage — the deviceflow strategy compiled into this
round's participation trace on the host (and intersected with the
scenario's), while the chip waits."""

from benchmark import program_spans

LAYER = "Runner"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "round_s.p50"


def read(ctx):
    return program_spans.median(
        program_spans.window_round_ms(ctx, "select", "compile_trace"))
