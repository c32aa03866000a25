"""Kernels: the window attention's share of its roofline in training — the
least time the chip could take for the score and context products the
traced rounds' local steps needed (``benchmark/roofline_selective_scan.py``
``window_attention``: the (query, key) pairs the program counted as inside
the window, ``window_attn_pairs_needed`` on
``round.<operator>.host_transfer``, a head; 64 multiply-accumulates of score
and 128 of context a pair a query head, three passes; q, k, v and the
output once a pass in bfloat16) over the training rounds' time under
``phi4flash.window_products``, the S mixer's score and context products.
The masked half of the blocks the band computes, the float32 softmaxes, the
scores computed again in the backward pass and the layout copies are in the
time and not in the work.

Which bound holds: the FLOPs (1.35 TFLOP a round of 29.4 M pairs a head,
6.9 ms at 197 TFLOP/s, against 3.0 GB, 3.7 ms at 819 GB/s).
Nothing counted (no such layer, or no trace): the metric is left out."""

from benchmark import roofline, roofline_selective_scan, scope_metrics

LAYER = "Kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"
SCOPE = "phi4flash.window_products"


def needed(ctx):
    """The traced train rounds' work, or None where nothing was counted."""
    counts = [a for a in scope_metrics.traced_round_counts(ctx) or ()
              if "window_attn_pairs_needed" in a]
    if not counts:
        return None
    model = ctx.cell.config["model"]
    return roofline_selective_scan.window_attention(
        pairs=sum(a["window_attn_pairs_needed"] for a in counts),
        tokens=sum(a["tokens_per_step"] * a["local_steps"]
                   * a["clients_resident"] for a in counts),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        head_dim=int(model["head_dim"]))


def read(ctx):
    if ctx.trace is None:
        return None
    work = needed(ctx)
    if work is None:
        return None
    return roofline.share_percent(
        work, ctx.trace.scope_seconds("client_train", SCOPE), ctx.peaks,
        ctx.device["count"])
