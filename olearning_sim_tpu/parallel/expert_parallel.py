"""Expert parallelism (``ep``): shard MoE expert weights over a mesh axis
and let GSPMD insert the token all-to-alls.

Counterpart to :mod:`olearning_sim_tpu.parallel.tp` (tensor parallelism,
``mp``) and :mod:`olearning_sim_tpu.parallel.long_context` (sequence
parallelism, ``sp``). The reference has none of these axes (SURVEY.md
section 2.5); MoE/expert parallelism is the rebuild's third model-scaling
axis, for the :class:`~olearning_sim_tpu.models.moe.MoETextTransformer`
family.

Design (pure GSPMD auto mode — no shard_map): every per-expert leaf (leading
expert dim, names ``expert_*`` from :class:`SwitchFFN` and
:class:`~olearning_sim_tpu.models.moe.DroplessMoE`) is annotated
``PartitionSpec("ep", ...)``; the batch is sharded over ``dp``. XLA then
places each device's expert shard locally and inserts all-to-alls moving
token slots to their experts' devices and back — exactly the hand-written
MoE dispatch of GShard/Switch, derived from shardings instead of coded.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from olearning_sim_tpu.parallel.mesh import MeshPlan, global_put
from olearning_sim_tpu.parallel.tp import _path_str, sharded_fraction


_EXPERT_PREFIX = "expert_"

# Same "fraction of elements on sharded leaves" metric as tensor
# parallelism; for ep specs only expert leaves carry a non-None axis.
sharded_expert_fraction = sharded_fraction


def ep_param_specs(params: Any, ep: int) -> Any:
    """PartitionSpec tree: per-expert weights (``expert_*`` leaves of two or
    more dims whose leading expert dim is divisible by ``ep``: ``SwitchFFN``'s
    ``expert_w1/b1/w2/b2`` and ``DroplessMoE``'s ``expert_w1/w3/w2``) shard
    that dim over ``ep``; everything else is replicated — the routers too,
    ``DroplessMoE``'s ``gate`` and its one-dim ``expert_bias`` over the
    router's whole width, which every chip holds whole."""

    def rule(path, leaf):
        names = _path_str(path)
        if names and names[-1].startswith(_EXPERT_PREFIX):
            shape = getattr(leaf, "shape", ())
            if len(shape) >= 2 and shape[0] % ep == 0:
                return P("ep", *([None] * (len(shape) - 1)))
        return P()

    return jax.tree_util.tree_map_with_path(rule, params)


def ep_place_params(params: Any, plan: MeshPlan) -> Any:
    """Place a params tree per :func:`ep_param_specs` on the plan's mesh."""
    if plan.ep <= 1:
        raise ValueError(
            "ep_place_params needs a mesh with an ep axis (make_mesh_plan(ep=...))"
        )
    specs = ep_param_specs(params, plan.ep)
    from olearning_sim_tpu.parallel.tp import warn_if_unsharded

    warn_if_unsharded(params, specs, plan.ep, axis="ep")
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(plan.mesh, s)),
        params, specs, is_leaf=lambda x: isinstance(x, P),
    ), specs


_TRAIN_CACHE: dict = {}


def ep_train_step(model, params, opt_state, tokens, labels, optimizer,
                  plan: MeshPlan, aux_weight: float = 0.01):
    """One optimizer step on a MoE text model with experts sharded over
    ``ep`` and the batch over ``dp`` (GSPMD auto mode — XLA derives the
    token all-to-alls from the weight shardings).

    The Switch load-balancing auxiliary loss (sown by :class:`SwitchFFN`)
    is added with weight ``aux_weight``. Returns
    ``(new_params, new_opt_state, loss)``; params keep their ep shardings.

    Contract: ``params``/``opt_state`` are DONATED (the input arrays are
    consumed — keep using the returned ones), and the caller must reuse ONE
    optimizer instance across steps: the compiled step is cached per
    (model, mesh, aux_weight) keyed on the optimizer's identity, so a fresh
    ``optax.sgd(...)`` per call recompiles every step."""
    if plan.ep <= 1:
        raise ValueError(
            "ep_train_step needs a mesh with an ep axis (make_mesh_plan(ep=...))"
        )
    B = tokens.shape[0]
    if B % plan.dp:
        raise ValueError(f"dp={plan.dp} must divide the batch {B}")
    tokens = global_put(np.asarray(tokens), NamedSharding(plan.mesh, P("dp")))
    labels = global_put(np.asarray(labels), NamedSharding(plan.mesh, P("dp")))
    return _compiled_step(model, plan, optimizer, aux_weight)(
        params, opt_state, tokens, labels
    )


def _compiled_step(model, plan: MeshPlan, optimizer, aux_weight: float):
    key = (model, plan.mesh, aux_weight)
    cached = _TRAIN_CACHE.get(key)
    # Strong reference + identity check (id() could match a recycled
    # address after GC of the original optimizer).
    if cached is not None and cached[0] is optimizer:
        return cached[1]

    def step(params, opt_state, tokens, labels):
        def loss_fn(p):
            logits, inter = model.apply(
                {"params": p}, tokens, mutable=["intermediates"]
            )
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()
            # Mean of the per-block Switch aux losses (each sown as a
            # 1-tuple under intermediates).
            aux_vals = jax.tree.leaves(inter["intermediates"])
            aux_loss = (
                sum(jax.numpy.asarray(a).sum() for a in aux_vals)
                / max(len(aux_vals), 1)
            )
            return ce + aux_weight * aux_loss, ce

        (loss, ce), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, new_opt = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt, ce

    fn = jax.jit(step, donate_argnums=(0, 1))
    _TRAIN_CACHE[key] = (optimizer, fn)
    return fn
