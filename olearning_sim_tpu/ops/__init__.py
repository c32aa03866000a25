"""Pallas TPU kernels for the hot ops.

The reference has no native kernels (it is 100% Python; SURVEY.md
section 2 language note) — its "hot loop" is a subprocess per device step.
In the rebuild the hot ops are on-device; :mod:`flash_attention` fuses
attention without materializing the [Lq, Lk] score matrix in HBM (an
optional ring-attention per-step primitive via
:func:`flash_attention_stats`, and a fusion point for variants XLA's
fused path can't reach). A ``weighted_sum`` FedAvg-reduction kernel existed
through round 1 but measured at parity with XLA's ``tensordot`` and was
retired — the engine's aggregation is plain XLA (``fedcore.py``). On the
TPU backend the kernels lower to Mosaic; on the CPU backend (the test path)
the Pallas interpreter runs the same kernel bodies so numerics are
CI-testable.
"""

from olearning_sim_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_stats,
)

__all__ = ["flash_attention", "flash_attention_stats"]
