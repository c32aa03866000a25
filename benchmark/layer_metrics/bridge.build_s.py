"""Task bridge: from RUNNING first seen to the start of the task's first
``round.*`` span: dataset generation on the host, ``place()`` onto the
chips, ``build_fedcore``, state initialisation."""

LAYER = "Task bridge"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    if not ctx.rounds:
        return None
    return ctx.rounds[0].start - ctx.t_running
