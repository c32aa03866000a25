"""Expert layer: device time a round under ``moe.shared_expert`` — the
SwiGLU every token passes beside the routed experts (dense matmuls over all
of a step's tokens, which every chip of the deployment computes alike)."""

from benchmark import scope_metrics

LAYER = "Expert layer"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    return scope_metrics.round_ms(ctx, "moe.shared_expert")
