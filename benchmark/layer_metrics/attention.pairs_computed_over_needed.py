"""Kernels: the scores the causal softmax attention of the decoders
(``lfm2._attend``: ``lfm2``'s GQA, ``kimi_linear``'s MLA, ``nemotron_h``'s
attention, ``phi4flash``'s F and C layers) formed over the (query, key)
pairs its mask lets through, over the window's train rounds; from the
counts the round program sums on the device and the runner puts on
``round.<operator>.host_transfer`` (``attend_pairs_computed``,
``attend_pairs_needed``). 2.0 says L x L scores under a mask ran (1.999 at
2,048 tokens, 1.998 at 1,024); by blocks of B queries, each against the keys
up to its own end, 1 + (B - 1) / (L + 1): 1.249 at 2,048 tokens and 1.499
at 1,024 with the program's 512-query blocks (1.124 and 1.249 with 256).
Nothing to read (a program whose attention counts no pairs, as the parent's;
a model without such a layer): the metric is left out."""

from benchmark import program_spans

LAYER = "Kernels"
UNIT = "x"
SOURCE = "program_counter"
MOVES = "round_s.p50"


def read(ctx):
    by_name = program_spans.task_spans(ctx)
    if by_name is None:
        return None
    inside = {r.idx for r in ctx.window.rounds}
    counts = [s.attrs for name, spans in by_name.items()
              if name.endswith(".host_transfer") for s in spans
              if s.attrs.get("round_idx") in inside
              and s.attrs.get("attend_pairs_needed")]
    if not counts:
        return None
    return (sum(a["attend_pairs_computed"] for a in counts)
            / sum(a["attend_pairs_needed"] for a in counts))
