"""Start-up: XLA compilation (``compile.backend``) plus loads from the
persistent compilation cache (``compile.cache_load``: key hashing, read,
deserialize) of the task's programs, from submit to window open. A cold
process pays the first, a warm one the second."""

from benchmark import program_spans

LAYER = "Start-up"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    return program_spans.seconds(ctx, "compile.backend",
                                 "compile.cache_load",
                                 until=ctx.window.open)
