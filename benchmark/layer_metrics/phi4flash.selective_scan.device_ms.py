"""Kernels: device time a round under ``phi4flash.selective_scan`` — the
Mamba-1 recurrence of the M* layer, elementwise over 5,120 channels x 16
states, in training (forward, the chunks walked again, and backward) and
evaluation: the scan over a sequence's chunks that carries the state, the
token loop inside a chunk, the exponentials and the read by ``C``."""

from benchmark import scope_metrics

LAYER = "Kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"


def read(ctx):
    return scope_metrics.round_ms(ctx, "phi4flash.selective_scan")
