"""Pallas TPU kernels for the hot ops.

The reference has no native kernels (it is 100% Python; SURVEY.md
section 2 language note) — its "hot loop" is a subprocess per device step.
In the rebuild the hot ops are on-device, and XLA compiles nearly all of
them: a kernel lives here only where it won a measurement on the chip.
:mod:`kda_scan` is the forward pass of the ``kimi_linear`` family's gated
delta-rule scan (a chunk's system, its inverse and the state in VMEM, float32
throughout) behind a custom VJP whose backward pass is the model's plain-JAX
scan. It lowers inside ``FedCore``'s round program through
:func:`lowering.manual_over_auto_axes`, which any other kernel of this
package can use. On the TPU backend the kernel lowers to Mosaic; on the CPU
backend (the test path) the Pallas interpreter runs the same kernel body so
numerics are CI-testable.
"""

from olearning_sim_tpu.ops.kda_scan import chunk_scan as kda_chunk_scan
from olearning_sim_tpu.ops.lowering import manual_over_auto_axes

__all__ = ["kda_chunk_scan", "manual_over_auto_axes"]
