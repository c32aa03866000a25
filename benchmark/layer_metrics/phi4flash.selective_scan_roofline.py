"""Kernels: the selective scan's share of its roofline in training — the
least time the chip could take for the recurrence the traced rounds' local
steps needed (``benchmark/roofline_selective_scan.py``: the tokens and
chunks the program counted, ``sscan_tokens`` and ``sscan_chunks`` on
``round.<operator>.host_transfer``; 2 multiply-accumulates a (token,
channel, state), three passes; x and y in bfloat16, the step sizes in
float32, B and C once a pass, the states that enter the chunks written and
read once) over the training rounds' time under
``phi4flash.selective_scan``. The exponentials, the token loop's state
traffic, the chunks walked again in the backward pass and every layout copy
are in the time and not in the work.

Which bound holds: the bytes (8.1 GB of tokens and 0.7 GB of states a round
of 65,536 tokens in 1,024 chunks, 10.7 ms at 819 GB/s, against 64 GFLOP,
0.33 ms at 197 TFLOP/s): an elementwise recurrence has no matrix product.
Nothing counted (no such layer, or no trace): the metric is left out."""

from benchmark import roofline, roofline_selective_scan, scope_metrics

LAYER = "Kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"
SCOPE = "phi4flash.selective_scan"


def needed(ctx):
    """The traced train rounds' work, or None where nothing was counted."""
    counts = [a for a in scope_metrics.traced_round_counts(ctx) or ()
              if "sscan_tokens" in a]
    if not counts:
        return None
    model = ctx.cell.config["model"]
    return roofline_selective_scan.selective_scan(
        tokens=sum(a["sscan_tokens"] for a in counts),
        chunks=sum(a["sscan_chunks"] for a in counts),
        channels=int(model["d_inner"]), states=int(model["d_state"]))


def read(ctx):
    if ctx.trace is None:
        return None
    work = needed(ctx)
    if work is None:
        return None
    return roofline.share_percent(
        work, ctx.trace.scope_seconds("client_train", SCOPE), ctx.peaks,
        ctx.device["count"])
