"""Runner: of the window's train rounds whose dispatch strategy has a rate
curve to integrate, the share that found the strategy's curve plan kept
from an earlier round (``deviceflow/strategy.py``) instead of building it
(12,120 evaluations of the expression in ``128_spike``) while the chip
waits; from ``curve_plan_hits`` / ``curve_plan_builds`` on the
``round.<operator>.select.compile_trace`` spans. Below 100%: an absolute
schedule with another interval list every round, or an expression that
names ``random``. Nothing to read (no ``specific_interval`` strategy, or
the program does not count, as the parent's): the metric is left out."""

from benchmark import program_spans

LAYER = "Runner"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "round_s.p50"


def read(ctx):
    by_name = program_spans.task_spans(ctx)
    if by_name is None:
        return None
    inside = {r.idx for r in ctx.window.rounds}
    hits = builds = 0
    for name, spans in by_name.items():
        if not name.endswith(".select.compile_trace"):
            continue
        for s in spans:
            if s.attrs.get("round_idx") in inside:
                hits += s.attrs.get("curve_plan_hits", 0)
                builds += s.attrs.get("curve_plan_builds", 0)
    return 100.0 * hits / (hits + builds) if hits + builds else None
