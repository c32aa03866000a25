"""Start-up: what making the round program ready took, from submit to
window open: the task's ``compile.trace``, ``.lower``, ``.backend`` and
``.cache_load`` spans whose ``program`` is ``round_step``, summed. The
rest of ``startup.trace_lower_s`` + ``startup.compile_or_load_s`` is the
initialiser's, the evaluate program's and the eager operations'."""

from benchmark import setup_memory_spans

LAYER = "Start-up"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    spans = setup_memory_spans.setup_compiles(ctx)
    return None if spans is None else sum(
        s.duration_s for s in spans
        if s.attrs["program"] == setup_memory_spans.ROUND_PROGRAM)
