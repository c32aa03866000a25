"""Round program: the share of the client-sample-steps the program computed
that the round needed, over the window's train rounds, from the counts on
the ``round.<operator>.host_transfer`` spans:

    sum(clients_trained * local_steps * samples_needed_per_step)
    / sum(clients_resident * local_steps * samples_computed_per_step)

The resident program trains every resident row (padding and withheld
clients too, their weight zeroed) and, under ``use_multiplicity``, every
local sample each step where a batch is needed. ``round_program.mfu``
counts needed FLOPs only; this is the ratio where the work happens."""

from benchmark import program_spans

LAYER = "Round program"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "device_rounds_per_s"


def read(ctx):
    by_name = program_spans.task_spans(ctx)
    if by_name is None:
        return None
    inside = {r.idx for r in ctx.window.rounds}
    needed = computed = 0
    for name, spans in by_name.items():
        if not name.endswith(".host_transfer"):
            continue
        for s in spans:
            a = s.attrs
            if a.get("round_idx") in inside and "clients_resident" in a:
                needed += (a["clients_trained"] * a["local_steps"]
                           * a["samples_needed_per_step"])
                computed += (a["clients_resident"] * a["local_steps"]
                             * a["samples_computed_per_step"])
    return 100.0 * needed / computed if computed else None
