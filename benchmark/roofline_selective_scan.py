"""What a Mamba-1 selective scan and a windowed differential attention need,
counted from shapes: the numerators of their shares of their rooflines.

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] S_t[c, n]

The work is the mathematics', whatever implements it (``benchmark/
roofline.py`` has the rules). The scan is elementwise over ``channels x
states``: a token needs 2 multiply-accumulates a (channel, state) (the
decayed state plus the write, and the read by ``C``), 2 FLOPs each, forward
+ two gradient passes; the exponentials, the token loop's state traffic,
the chunk bodies computed again and every layout copy are in the scope's
time and not in the work. The window's attention needs a score and a context
product for every (query, key) pair the window holds and no other: the
masked half of a block the band computes, the softmax and the second
member's subtraction are in the time and not in the work. Neither share can
pass 100% by over-counting.

Shape arithmetic only; no cell, model or metric name in this module.
"""

from __future__ import annotations

from benchmark.roofline import PASSES, Work


def selective_scan(tokens: float, chunks: float, channels: int, states: int,
                   *, io_bytes: int = 2, step_bytes: int = 4,
                   state_bytes: int = 4) -> Work:
    """Training work of the recurrence over ``tokens`` tokens (tokens x
    layers x local steps x clients) of ``channels`` channels with
    ``states`` states each, whose sequences are handed on in ``chunks``
    chunks (sequences x chunks a sequence x layers x local steps x clients).

    FLOPs: tokens x channels x states x 2 MACs x 2 x 3 passes. Bytes, a
    pass: a token's x and y (``channels`` each) read or written once in
    ``io_bytes``, its step sizes (``channels``) in ``step_bytes``, its B
    and C (``states`` each) in ``io_bytes``; once for all passes: the state
    that enters a chunk written once and read once in ``state_bytes`` (what
    the backward pass keeps)."""
    a_token = (2 * channels + 2 * states) * io_bytes + channels * step_bytes
    return Work(
        flops=2.0 * PASSES * tokens * channels * states * 2,
        bytes=(PASSES * tokens * a_token
               + 2.0 * chunks * channels * states * state_bytes))


def window_attention(pairs: float, tokens: float, heads: int, kv_heads: int,
                     head_dim: int, *, io_bytes: int = 2) -> Work:
    """Training work of differential attention over a window: ``pairs``
    (query, key) pairs a head (sequences x pairs a sequence x layers x
    local steps x clients) of ``heads`` query heads ``head_dim`` wide over
    ``kv_heads`` key heads, each query head's context ``2 x head_dim`` wide
    (a pair's two value heads side by side), over ``tokens`` tokens.

    FLOPs: pairs x heads x (head_dim + 2 head_dim) MACs x 2 x 3 passes.
    Bytes, a pass: a token's q and its context-wide output (``heads x
    head_dim`` each: the two members' contexts are differenced before they
    leave), k and v (``kv_heads x head_dim`` each), once in ``io_bytes``."""
    a_token = (2 * heads + 2 * kv_heads) * head_dim * io_bytes
    return Work(flops=2.0 * PASSES * pairs * heads * 3 * head_dim,
                bytes=float(PASSES * tokens * a_token))
