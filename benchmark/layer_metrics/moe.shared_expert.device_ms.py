"""Expert layer: device time a round under ``moe.shared_expert`` — the
expert every token passes beside the routed ones, of the routed experts'
form (a SwiGLU in ``kimi_linear``, ``W2(relu(W1 h)^2)`` in ``nemotron_h``):
dense matmuls over all of a step's tokens, which every chip of the
deployment computes alike. Listed for the cells whose model opens the
scope."""

from benchmark import scope_metrics

LAYER = "Expert layer"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    return scope_metrics.round_ms(ctx, "moe.shared_expert")
