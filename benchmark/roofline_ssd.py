"""What a Mamba-2 (SSD) recurrence needs, counted from shapes: the
numerator of the chunked scan's share of its roofline.

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t

The work is the recurrence's, whatever implements it (``benchmark/
roofline.py`` has the rules): a token of a head needs two products with its
``head_dim x state_dim`` state (the rank-one write, ``S C``), 2 FLOPs a
multiply-accumulate, forward + two gradient passes. A chunked form's
intra-chunk Q x Q products and pairwise decays, the recomputed chunk bodies
and every cast are in the scope's time and not in the work, so the share
cannot pass 100% by over-counting.

Shape arithmetic only; no cell, model or metric name in this module.
"""

from __future__ import annotations

from benchmark.roofline import PASSES, Work


def ssd(tokens: float, chunks: float, heads: int, head_dim: int,
        state_dim: int, groups: int, *, io_bytes: int = 2,
        decay_bytes: int = 4, state_bytes: int = 4) -> Work:
    """Training work of the recurrence over ``tokens`` tokens (tokens x
    layers x local steps x clients) of ``heads`` heads whose B and C come
    in ``groups`` groups, whose sequences are handed on in ``chunks``
    chunks (sequences x chunks a sequence x layers x local steps x
    clients).

    FLOPs: tokens x heads x 2 products x head_dim x state_dim MACs x 2 x 3
    passes. Bytes, a pass: a token's x and y (``head_dim`` a head) and its
    B and C (``state_dim`` a group) read or written once in ``io_bytes``,
    its step size and its log decay (one number a head each) in
    ``decay_bytes``; once for all passes: the state that enters a chunk
    written once and read once in ``state_bytes`` (what the backward pass
    keeps)."""
    a_token = ((2 * heads * head_dim + 2 * groups * state_dim) * io_bytes
               + 2 * heads * decay_bytes)
    return Work(
        flops=2.0 * PASSES * tokens * heads * 2 * head_dim * state_dim,
        bytes=(PASSES * tokens * a_token
               + 2.0 * chunks * heads * head_dim * state_dim * state_bytes))
