"""Kernels: the chunked delta-rule scan's share of its roofline in training
— the least time the chip could take for the recurrence the traced rounds'
local steps needed (``benchmark/roofline_delta_rule.py``: the tokens and
chunks the program counted, ``kda_scan_tokens`` and ``kda_scan_chunks`` on
``round.<operator>.host_transfer``; three products with the 128 x 128 state
a token a head, three passes; q, k, v and o in bfloat16, the log decay in
float32 and the write strength once a pass, the states that enter the
chunks written and read once) over the training rounds' time under
``kda.chunk_scan``. How the lowering gets there (since PR 36 by 16-token
sub-blocks: 16 x 16 x 128 pairwise decays and substitution inside one,
matrix products between them, the blocked inverse of the unit
lower-triangular system merged 16 -> 32 -> 64 and applied as one product a
chunk), the chunk bodies computed again in the backward pass, the float32
products at ``Precision.HIGHEST`` and every layout copy are in the time and
not in the work.

Which bound holds: the bytes (38.8 GB of tokens and 17.2 GB of states a
round of 262,144 token-layers in 4,096 chunks, 68.3 ms at 819 GB/s, against
2.47 TFLOP, 12.6 ms at 197 TFLOP/s): a token moves 1,540 bytes a head a
pass for 98,304 MACs.
Nothing counted (no such layer, or no trace): the metric is left out."""

from benchmark import roofline, roofline_delta_rule, scope_metrics

LAYER = "Kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"
SCOPE = "kda.chunk_scan"


def needed(ctx):
    """The traced train rounds' work, or None where nothing was counted."""
    counts = [a for a in scope_metrics.traced_round_counts(ctx) or ()
              if "kda_scan_tokens" in a]
    if not counts:
        return None
    kda = ctx.cell.config["model"]["linear_attn_config"]
    return roofline_delta_rule.gated_delta_rule(
        tokens=sum(a["kda_scan_tokens"] for a in counts),
        chunks=sum(a["kda_scan_chunks"] for a in counts),
        heads=int(kda["num_heads"]), key_dim=int(kda["head_dim"]),
        value_dim=int(kda["head_dim"]))


def read(ctx):
    if ctx.trace is None:
        return None
    work = needed(ctx)
    if work is None:
        return None
    return roofline.share_percent(
        work, ctx.trace.scope_seconds("client_train", SCOPE), ctx.peaks,
        ctx.device["count"])
