"""Kernels: device time a round under ``kda.projections`` — everything of a
KDA layer around its recurrence: the q, k, v projections with their causal
4-tap convolutions and SiLU, the L2 norms, the rank-128 decay gate, the
write strength, and after the scan the per-head RMS norm, the rank-128
output gate and the output projection."""

from benchmark import scope_metrics

LAYER = "Kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    return scope_metrics.round_ms(ctx, "kda.projections")
