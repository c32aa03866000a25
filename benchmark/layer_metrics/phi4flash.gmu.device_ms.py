"""Kernels: device time a round under ``phi4flash.gmu`` — the G layer's
mixer: the gate's projection, SiLU, the product with the M* layer's memory
and the output projection."""

from benchmark import scope_metrics

LAYER = "Kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    return scope_metrics.round_ms(ctx, "phi4flash.gmu")
