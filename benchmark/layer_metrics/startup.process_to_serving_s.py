"""Start-up: the process's boot, from the kernel's record of its start to
the session serving: ``process_age_s`` of the ``session.start`` span this
run's task was submitted to (the interpreter's start, the imports and the
backend's initialisation, none of which the program can put a span around)
plus the span's own duration (the gRPC server bound, the services' threads
up). The part of ``setup_s`` before the submit."""

from benchmark import setup_memory_spans

LAYER = "Start-up"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    span = setup_memory_spans.serving_span(ctx)
    return None if span is None else (
        span.attrs["process_age_s"] + span.duration_s)
