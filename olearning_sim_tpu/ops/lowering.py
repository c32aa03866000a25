"""Where a Pallas kernel of this package may lower.

Mosaic refuses a kernel under a mesh axis that is left to the auto
partitioner ("Mosaic kernels cannot be automatically partitioned. Please wrap
the call in a shard_map"). ``FedCore``'s round program is a ``shard_map``
manual over ``dp`` that leaves ``mp`` (size 1 on that branch) auto, so a
kernel called from a client model carries an inner ``shard_map`` of its own
over whatever axes its context leaves auto: :func:`manual_over_auto_axes`.
Under ``check_vma`` the kernel's ``out_shape`` also has to say how its output
varies over the axes that are manual already: ``jax.ShapeDtypeStruct(...,
vma=jax.typeof(x).vma)``.
"""

from __future__ import annotations

from typing import Callable

import jax
from jax.sharding import PartitionSpec as P


def manual_over_auto_axes(fn: Callable) -> Callable:
    """``fn`` (arrays -> arrays, every one replicated over them) inside a
    ``jax.shard_map`` over the axes of the context's mesh that are left to
    the auto partitioner, which is where a Mosaic kernel may lower; ``fn``
    itself where there are none (no mesh, or every axis manual already)."""

    def wrapped(*args):
        mesh = jax.sharding.get_abstract_mesh()
        auto = frozenset() if mesh.empty else frozenset(mesh.auto_axes)
        if not auto:
            return fn(*args)
        return jax.shard_map(fn, in_specs=P(), out_specs=P(),
                             axis_names=auto)(*args)

    return wrapped
