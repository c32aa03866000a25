"""Kernels: the scores the S layer formed over the (query, key) pairs its
window holds, over the window's train rounds; from the counts the round
program sums on the device and the runner puts on
``round.<operator>.host_transfer`` (``window_attn_pairs_computed``,
``window_attn_pairs_needed``). 2.0 where every 512-token query block is
scored against itself and the block before (half of each is masked); 4.57
would say that L x L scores under a mask ran at 2,048 tokens. Nothing to
read (a program whose model has no such layer): the metric is left out."""

from benchmark import program_spans

LAYER = "Kernels"
UNIT = "ratio"
SOURCE = "program_counter"
MOVES = "device_rounds_per_s"


def read(ctx):
    by_name = program_spans.task_spans(ctx)
    if by_name is None:
        return None
    inside = {r.idx for r in ctx.window.rounds}
    counts = [s.attrs for name, spans in by_name.items()
              if name.endswith(".host_transfer") for s in spans
              if s.attrs.get("round_idx") in inside
              and s.attrs.get("window_attn_pairs_needed")]
    if not counts:
        return None
    return (sum(a["window_attn_pairs_computed"] for a in counts)
            / sum(a["window_attn_pairs_needed"] for a in counts))
