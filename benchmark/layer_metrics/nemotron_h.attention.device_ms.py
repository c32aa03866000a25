"""Kernels: device time a round under ``nemotron_h.attention`` — causal
grouped-query attention without positional embedding: the four projections
and the L x L float32 scores, recomputed in the backward pass."""

from benchmark import scope_metrics

LAYER = "Kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    return scope_metrics.round_ms(ctx, "nemotron_h.attention")
