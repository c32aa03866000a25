"""Expert layer: (token, slot) assignments the routers sent to experts this
chip holds, less the assignments its grouped expert products computed, over
the window's train rounds; from the counts the round program sums on the
device and the runner puts on ``round.<operator>.host_transfer``
(``moe_assignments_local``, ``moe_assignments_computed``). A dropless layer
reads 0, as ``startup.window_compiles`` does; a capacity that cut a group
short would show here. Nothing to read (a program whose model has no such
layer, or that does not count): the metric is left out."""

from benchmark import program_spans

LAYER = "Expert layer"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "device_rounds_per_s"


def window_counts(ctx):
    """The work-count attrs of the window's train rounds that carry the
    expert layer's counts, or None where the program records none."""
    by_name = program_spans.task_spans(ctx)
    if by_name is None:
        return None
    inside = {r.idx for r in ctx.window.rounds}
    found = [s.attrs for name, spans in by_name.items()
             if name.endswith(".host_transfer") for s in spans
             if s.attrs.get("round_idx") in inside
             and "moe_assignments_local" in s.attrs]
    return found or None


def read(ctx):
    counts = window_counts(ctx)
    if counts is None:
        return None
    return sum(a["moe_assignments_local"] - a["moe_assignments_computed"]
               for a in counts)
