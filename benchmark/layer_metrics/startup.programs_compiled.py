"""Start-up: programs XLA compiled for the task from submit to window open
(``compile.backend`` spans; a ``compile.cache_load`` is a hit of the
persistent cache and does not count). 0 in a warm process; what a run paid
for a cache that did not hold its programs otherwise."""

from benchmark import setup_memory_spans

LAYER = "Start-up"
UNIT = "count"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    spans = setup_memory_spans.setup_compiles(ctx)
    return None if spans is None else sum(
        1 for s in spans if s.name == "compile.backend")
