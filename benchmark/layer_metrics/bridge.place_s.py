"""Task bridge: the ``bridge.place`` spans — the population padded to the
mesh and put on the chips (``pad_for(...).place(plan)``)."""

from benchmark import program_spans

LAYER = "Task bridge"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    return program_spans.seconds(ctx, "bridge.place")
