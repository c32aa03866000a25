"""Task bridge: the ``bridge.generate`` spans — the population's data made
(or loaded) on the host, and the central evaluation set."""

from benchmark import program_spans

LAYER = "Task bridge"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    return program_spans.seconds(ctx, "bridge.generate")
