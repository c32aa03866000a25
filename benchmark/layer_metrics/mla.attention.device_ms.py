"""Kernels: device time a round under ``mla.attention`` — the whole latent
attention mixer: the query projection, the shared down-projection, the
latent's norm and its expansion to per-head keys and values, the L x L
scores (one sequence at a time, recomputed in the backward pass) and the
output projection."""

from benchmark import scope_metrics

LAYER = "Kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    return scope_metrics.round_ms(ctx, "mla.attention")
