"""Pallas TPU kernels for the hot ops.

The reference has no native kernels (it is 100% Python; SURVEY.md
section 2 language note) — its "hot loop" is a subprocess per device step.
In the rebuild the hot ops are on-device; :mod:`flash_attention` fuses
attention without materializing the [Lq, Lk] score matrix in HBM (an
optional ring-attention per-step primitive via
:func:`flash_attention_stats`, and a fusion point for variants XLA's
fused path can't reach). A ``weighted_sum`` FedAvg-reduction kernel existed
through round 1 but measured at parity with XLA's ``tensordot`` and was
retired — the engine's aggregation is plain XLA (``fedcore.py``).
:mod:`kda_scan` is the forward pass of the ``kimi_linear`` family's gated
delta-rule scan (a chunk's system, its inverse and the state in VMEM, float32
throughout) behind a custom VJP whose backward pass is the model's plain-JAX
scan: the first kernel that lowers inside ``FedCore``'s round program, through
:func:`lowering.manual_over_auto_axes`, which any other kernel of this
package can use. On the TPU backend the kernels lower to Mosaic; on the CPU
backend (the test path) the Pallas interpreter runs the same kernel bodies so
numerics are CI-testable.
"""

from olearning_sim_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_stats,
)
from olearning_sim_tpu.ops.kda_scan import chunk_scan as kda_chunk_scan
from olearning_sim_tpu.ops.lowering import manual_over_auto_axes

__all__ = ["flash_attention", "flash_attention_stats", "kda_chunk_scan",
           "manual_over_auto_axes"]
