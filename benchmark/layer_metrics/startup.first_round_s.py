"""Start-up: round 0, from its first operator span's start to round 1's —
the round that traces, lowers and compiles (or loads from the persistent
cache) every program of the task and runs each once.
``startup.trace_lower_s`` and ``startup.compile_or_load_s`` say how much of
it is not execution."""

LAYER = "Start-up"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    first = next((r for r in ctx.rounds if r.idx == 0), None)
    return None if first is None else first.seconds
