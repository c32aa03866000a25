"""Round program: the share of a lookup-only table's rows that a client's
local step writes, median over the window's train rounds; from
``table_rows_written_per_step`` / ``table_rows_total`` on the
``round.<operator>.host_transfer`` spans. A model that marks such a table
(``models/lookup.py``) has it trained by the rows a step looks up (batch
rows x ids a row: 1,024 of 30,522 in the DistilBERT cells); 100% says the
round program fell back to the dense gradient, zero fill and whole-table
update. Nothing to read (the model marks no table, or the program does not
count, as the parent's): the metric is left out."""

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
    shares = [100.0 * s.attrs["table_rows_written_per_step"]
              / s.attrs["table_rows_total"]
              for name, spans in by_name.items()
              if name.endswith(".host_transfer") for s in spans
              if s.attrs.get("round_idx") in inside
              and s.attrs.get("table_rows_total")]
    return program_spans.median(shares) if shares else None
