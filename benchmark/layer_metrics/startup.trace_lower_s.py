"""Start-up: what jax spent tracing the task's programs to jaxprs
(``compile.trace``, outermost traces only) and lowering them to MLIR
(``compile.lower``), from submit to window open. Paid by a warm process as
by a cold one: the persistent cache is keyed by the lowered module."""

from benchmark import program_spans

LAYER = "Start-up"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    return program_spans.seconds(ctx, "compile.trace", "compile.lower",
                                 until=ctx.window.open)
