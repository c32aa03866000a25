"""Kernels: device time a round under ``kda.chunk_scan`` — the gated
delta-rule recurrence of the KDA layers in its chunked form, in training
(forward, the chunk bodies and the intra-chunk decays computed again, and
backward) and evaluation: the running sums of the log decay, the C x C x K
pairwise decays and their two reductions, the triangular solve, and the
scan over a sequence's chunks with its four products against the state."""

from benchmark import scope_metrics

LAYER = "Kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    return scope_metrics.round_ms(ctx, "kda.chunk_scan")
