"""Kernels: device time a round under ``ssd.chunk_scan`` — the Mamba-2
recurrence of the M layers in its chunked form, in training (forward, the
intra-chunk products computed again, and backward) and evaluation: the
running sums of the log decay, the Q x Q pairwise decays a head and ``C
B^T`` a group, the chunks' contributions to the state, the scan that
carries the state over a sequence's chunks, and the carried part of the
output."""

from benchmark import scope_metrics

LAYER = "Kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    return scope_metrics.round_ms(ctx, "ssd.chunk_scan")
