"""Kernels: device time a round under ``ssd.projections`` — what a Mamba-2
layer does around its scan: the fused input projection, the depthwise
convolution over x, B and C with its bias and SiLU, the step sizes, the
skip, the gated grouped RMSNorm and the output projection."""

from benchmark import scope_metrics

LAYER = "Kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    return scope_metrics.round_ms(ctx, "ssd.projections")
