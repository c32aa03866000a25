"""Task bridge: ``bridge.build_fedcore`` (model, algorithm, round-program
builders; nothing compiles yet) plus ``bridge.init_state`` (the server
state made on the chips: parameters, optimizer state)."""

from benchmark import program_spans

LAYER = "Task bridge"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    return program_spans.seconds(ctx, "bridge.build_fedcore",
                                 "bridge.init_state")
