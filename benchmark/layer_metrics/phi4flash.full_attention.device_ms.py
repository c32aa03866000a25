"""Kernels: device time a round under ``phi4flash.full_attention`` — the F
and C layers' mixers: the projections with their biases (C: the query's
alone), the two members' L x L float32 scores and softmaxes over the causal
prefix (recomputed in the backward pass), lambda, the sub-layer norm and
the output projection."""

from benchmark import scope_metrics

LAYER = "Kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    return scope_metrics.round_ms(ctx, "phi4flash.full_attention")
