"""Kernels: device time a round in the S layer's mixer — under
``phi4flash.window_attention`` the fused q/k/v projection with its bias,
lambda, the sub-layer norm and the output projection, and under
``phi4flash.window_products`` (never nested in it) the two members' banded
float32 scores, softmaxes and context products over the 512-token window,
recomputed in the backward pass."""

from benchmark import scope_metrics

LAYER = "Kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    return scope_metrics.round_ms(ctx, "phi4flash.window_attention",
                                  "phi4flash.window_products")
