"""Kernels: device time a round under ``phi4flash.mamba_projections`` —
everything of the M* layer's mixer around its scan: the input projection,
the depthwise convolution with its bias and SiLU, the projections to the
step sizes, B and C, softplus, the skip, the gate and the output
projection."""

from benchmark import scope_metrics

LAYER = "Kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    return scope_metrics.round_ms(ctx, "phi4flash.mamba_projections")
