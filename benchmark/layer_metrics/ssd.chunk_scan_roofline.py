"""Kernels: the chunked Mamba-2 scan's share of its roofline in training —
the least time the chip could take for the recurrence the traced rounds'
local steps needed (``benchmark/roofline_ssd.py``: the tokens and chunks
the program counted, ``ssd_scan_tokens`` and ``ssd_scan_chunks`` on
``round.<operator>.host_transfer``; two products with the ``mamba_head_dim``
x ``ssm_state_size`` state a token a head, three passes; x, y, B and C in
bfloat16, the step size and the log decay in float32 once a pass, the
states that enter the chunks written and read once) over the training
rounds' time under ``ssd.chunk_scan``. The intra-chunk Q x Q decays and
products, whatever is computed again in the backward pass, the float32
products at ``Precision.HIGHEST`` and every layout copy are in the time and
not in the work.

Nothing counted (no such layer, or no trace): the metric is left out."""

from benchmark import roofline, roofline_ssd, scope_metrics

LAYER = "Kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "device_rounds_per_s"
SCOPE = "ssd.chunk_scan"


def needed(ctx):
    """The traced train rounds' work, or None where nothing was counted."""
    counts = [a for a in scope_metrics.traced_round_counts(ctx) or ()
              if "ssd_scan_tokens" in a]
    if not counts:
        return None
    model = ctx.cell.config["model"]
    return roofline_ssd.ssd(
        tokens=sum(a["ssd_scan_tokens"] for a in counts),
        chunks=sum(a["ssd_scan_chunks"] for a in counts),
        heads=int(model["mamba_num_heads"]),
        head_dim=int(model["mamba_head_dim"]),
        state_dim=int(model["ssm_state_size"]),
        groups=int(model["n_groups"]))


def read(ctx):
    if ctx.trace is None:
        return None
    work = needed(ctx)
    if work is None:
        return None
    return roofline.share_percent(
        work, ctx.trace.scope_seconds("client_train", SCOPE), ctx.peaks,
        ctx.device["count"])
