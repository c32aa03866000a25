"""What a gated delta-rule recurrence needs, counted from shapes: the
numerator of the chunked scan's share of its roofline.

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

The work is the recurrence's, whatever implements it (``benchmark/
roofline.py`` has the rules): a token of a head needs three products with
its ``key_dim x value_dim`` state (``k^T S``, the rank-one write, ``S^T
q``), 2 FLOPs a multiply-accumulate, forward + two gradient passes. A
chunked form's triangular solve, its intra-chunk C x C products, the
recomputed chunk bodies and every cast are in the scope's time and not in
the work, so the share cannot pass 100% by over-counting.

Shape arithmetic only; no cell, model or metric name in this module.
"""

from __future__ import annotations

from benchmark.roofline import PASSES, Work


def gated_delta_rule(tokens: float, chunks: float, heads: int, key_dim: int,
                     value_dim: int, *, io_bytes: int = 2,
                     decay_bytes: int = 4, state_bytes: int = 4) -> Work:
    """Training work of the recurrence over ``tokens`` tokens (tokens x
    layers x local steps x clients) of ``heads`` heads, whose sequences are
    handed on in ``chunks`` chunks (sequences x chunks a sequence x layers
    x local steps x clients).

    FLOPs: tokens x heads x 3 products x key_dim x value_dim MACs x 2 x 3
    passes. Bytes, a pass: a token's q and k (``key_dim`` each) and v and o
    (``value_dim`` each) of every head read or written once in
    ``io_bytes``, its per-channel log decay (``key_dim``) in
    ``decay_bytes`` and its write strength (one float32); once for all
    passes: the state that enters a chunk written once and read once in
    ``state_bytes`` (what the backward pass keeps)."""
    a_token = heads * ((2 * key_dim + 2 * value_dim) * io_bytes
                       + key_dim * decay_bytes + 4)
    return Work(
        flops=2.0 * PASSES * tokens * heads * 3 * key_dim * value_dim,
        bytes=(PASSES * tokens * a_token
               + 2.0 * chunks * heads * key_dim * value_dim * state_bytes))
