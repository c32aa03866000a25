"""What a kernel needs, counted from shapes, and the least time the chip
could take for it: the numerator of a kernel's share of its roofline.

A kernel's share = ``least_seconds(work, peaks)`` over the seconds the
trace gives its named scope (``trace_reduce.TraceSummary.scope_seconds``).
As in ``flops.py`` the work is what the mathematics needs — 2 FLOPs a
multiply-accumulate, forward + weight-gradient + input-gradient passes —
and never what a lowering executes: recomputed products, casts, padding
rows and layout copies are in the scope's time and not in the work, so the
share cannot pass 100% by over-counting. The bytes are the least traffic
with nothing kept on the chip between passes: every operand read once and
every result written once a pass.

Shape arithmetic only: a new kernel brings its function here and its metric
a reader under ``layer_metrics/``; no cell, model or metric name in this
module.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

PASSES = 3      # forward, weight gradient, input gradient


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)


def _grouped_products(products: int, rows: float, calls: int, experts: int,
                      hidden: int, intermediate: int, matmul_bytes: int,
                      grad_bytes: int) -> Work:
    """Training work of an expert of ``products`` matrices, ``hidden`` x
    ``intermediate`` each, over ``rows`` rows grouped by expert."""
    weights = products * experts * hidden * intermediate
    return Work(
        flops=2.0 * PASSES * products * rows * hidden * intermediate,
        bytes=(calls * weights * (PASSES * matmul_bytes + grad_bytes)
               + PASSES * products * rows * (hidden + intermediate)
               * matmul_bytes))


def grouped_swiglu(rows: float, calls: int, experts: int, hidden: int,
                   intermediate: int, *, matmul_bytes: int = 2,
                   grad_bytes: int = 4) -> Work:
    """Training work of ``W2(silu(W1 x) * W3 x)`` over ``rows`` rows grouped
    by expert, summed over ``calls`` executions of the layer (layers x local
    steps x clients) that each hold ``experts`` experts' three ``hidden`` x
    ``intermediate`` weights.

    FLOPs: rows x 3 products x hidden x intermediate MACs x 2 x 3 passes.
    Bytes, a call: the three weights of every held expert read once a pass
    in the matmul dtype, their gradients written once in ``grad_bytes``;
    all calls together: each product's input rows read and output rows
    written once a pass (two products take ``hidden`` in and give
    ``intermediate`` out, the third the reverse)."""
    return _grouped_products(3, rows, calls, experts, hidden, intermediate,
                             matmul_bytes, grad_bytes)


def grouped_relu2(rows: float, calls: int, experts: int, hidden: int,
                  intermediate: int, *, matmul_bytes: int = 2,
                  grad_bytes: int = 4) -> Work:
    """Training work of ``W2(relu(W1 x)^2)``, the expert without a gate:
    as :func:`grouped_swiglu` with two products a row and two weights an
    expert (one takes ``hidden`` in and gives ``intermediate`` out, the
    other the reverse). ``intermediate`` is the width the mathematics has:
    columns of zeros a lowering pads it with are in the time, not here."""
    return _grouped_products(2, rows, calls, experts, hidden, intermediate,
                             matmul_bytes, grad_bytes)


def least_seconds(work: Work, peaks: Dict[str, Any], chips: int = 1
                  ) -> Tuple[float, str]:
    """(the least seconds ``chips`` chips could take for ``work``, which
    bound held: ``"flops"`` or ``"bytes"``); ``peaks`` is the device's row
    of ``peaks.json``."""
    compute = work.flops / (chips * peaks["bf16_flops_per_s"])
    memory = work.bytes / (chips * peaks["hbm_bytes_per_s"])
    return (compute, "flops") if compute >= memory else (memory, "bytes")


def share_percent(work: Work, scope_seconds: float, peaks: Dict[str, Any],
                  chips: int = 1):
    """The share of its roofline, in percent, or None where there is
    nothing to divide (no work counted, or the scope ran nothing)."""
    if work.flops <= 0 or scope_seconds <= 0:
        return None
    return 100.0 * least_seconds(work, peaks, chips)[0] / scope_seconds
