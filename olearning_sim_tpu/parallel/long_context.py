"""Long-context sequence parallelism: run and train the text family over
sequences too long for one device's HBM.

The per-client FL path keeps dense attention (device-class models see short
sequences — SURVEY.md section 5: client count, not sequence length, is the
platform's scaling axis). This module is the reachable surface for the
long-context machinery (:mod:`ring_attention`): forward/eval
(:func:`sp_forward` / :func:`sp_evaluate`) and centralized training
(:func:`sp_train_step`) of a global model over arbitrarily long inputs,
with the sequence axis sharded over the mesh ``sp`` axis and K/V chunks
rotating around the ring with ``ppermute`` — per-device attention memory is
O(L/sp) in forward AND backward, and the transfers ride ICI neighbor links.

Because :class:`RingSelfAttention` is parameter-compatible with the dense
path, the SAME params trained with ``attention_impl="dense"`` evaluate here
unchanged (and vice versa: one sp training step lands on the same params as
a dense step on the same global batch).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from olearning_sim_tpu.parallel.mesh import MeshPlan, global_put


def _validate_sp_inputs(model, tokens, plan: MeshPlan, caller: str) -> None:
    if plan.sp <= 1:
        raise ValueError(
            f"{caller} needs a mesh with an sp axis (make_mesh_plan(sp=...))"
        )
    B, L = tokens.shape
    if L % plan.sp:
        raise ValueError(
            f"sp={plan.sp} must divide the sequence length {L}; pad the "
            f"sequences (pad_id tokens are masked out)"
        )
    if B % plan.dp:
        raise ValueError(f"dp={plan.dp} must divide the batch {B}")
    max_len = getattr(model, "max_len", None)
    if max_len is not None and L > max_len:
        # The ring path's positional dynamic_slice would clamp out-of-range
        # offsets and silently reuse early positions.
        raise ValueError(
            f"global sequence length {L} exceeds the model's max_len "
            f"{max_len}; build the model with max_len >= {L}"
        )


def sp_forward(model, params, tokens, plan: MeshPlan):
    """Forward the text ``model`` (built with ``attention_impl="ring"``)
    over ``tokens`` [B, L] with L sharded over the plan's ``sp`` axis and
    the batch over ``dp``. Returns logits [B, num_classes].

    ``sp`` must divide ``L`` and ``dp`` must divide ``B`` (pad with the
    model's pad_id / duplicate rows if not — padding tokens are masked out
    of attention and pooling by construction).
    """
    _validate_sp_inputs(model, tokens, plan, "sp_forward")
    tokens = global_put(
        np.asarray(tokens), NamedSharding(plan.mesh, P("dp", "sp"))
    )
    return _compiled_forward(model, plan.mesh)(params, tokens)


# flax Modules and Meshes hash by value, so identical (model, mesh) pairs
# reuse the compiled program across calls (sp_evaluate loops batches —
# rebuilding the jit closure per call would retrace and recompile every
# time).
_FWD_CACHE: dict = {}


def _compiled_forward(model, mesh):
    key = (model, mesh)
    if key not in _FWD_CACHE:
        def body(params, tokens_chunk):
            # logits are replicated over sp after the model's pooling psum.
            return model.apply({"params": params}, tokens_chunk)

        # Manual over EVERY mesh axis (the plan's size-1 mp included): the
        # ring's Pallas step primitive lowers to a Mosaic kernel, which
        # refuses to sit under any axis left to the auto partitioner.
        _FWD_CACHE[key] = jax.jit(
            jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(P(), P("dp", "sp")),
                out_specs=P("dp"),
            )
        )
    return _FWD_CACHE[key]


def sp_train_step(model, params, opt_state, tokens, labels, optimizer,
                  plan: MeshPlan):
    """One optimizer step on a text model with the sequence sharded over
    ``sp`` (ring attention) and the batch over ``dp``.

    Differentiation goes straight through the ring: ``ppermute`` and the
    online-softmax merge are plain XLA ops, so ``jax.grad`` of the chunked
    loss is the exact gradient of the dense loss — per-device activation
    memory stays O(L/sp) in the backward pass too (the [L, L] score matrix
    never materializes). Gradients are psum'd over BOTH mesh axes (dp batch
    shards + sp sequence chunks) before the replicated optimizer update.

    Returns ``(new_params, new_opt_state, loss)`` with params/opt_state
    replicated — shapes and semantics match a single-device
    ``optimizer.update`` step on the same global batch.
    """
    _validate_sp_inputs(model, tokens, plan, "sp_train_step")
    tokens = global_put(
        np.asarray(tokens), NamedSharding(plan.mesh, P("dp", "sp"))
    )
    labels = global_put(
        np.asarray(labels), NamedSharding(plan.mesh, P("dp"))
    )
    return _compiled_train(model, plan.mesh, optimizer)(
        params, opt_state, tokens, labels
    )


_TRAIN_CACHE: dict = {}


def _compiled_train(model, mesh, optimizer):
    # optax transforms are closures without value hashing — track the
    # optimizer by identity, but key the cache on (model, mesh) only and
    # REPLACE on optimizer change: a caller constructing optax.sgd(...)
    # inline every step then pays a recompile per step (visible, fixable)
    # instead of silently growing an executable per call.
    key = (model, mesh)
    cached = _TRAIN_CACHE.get(key)
    # Strong reference + identity check (id() could match a recycled
    # address after GC of the original optimizer).
    if cached is not None and cached[0] is optimizer:
        return cached[1]

    import optax

    from olearning_sim_tpu.parallel.scale_check import verify_grad_scale

    # The grads pmean below encodes an empirical JAX transpose behavior;
    # measure it on a one-scalar program first and refuse to train if it
    # moved (e.g. after a JAX upgrade) — see parallel/scale_check.py.
    verify_grad_scale(mesh, ("dp", "sp"))

    def body(params, opt_state, tokens_chunk, labels_chunk):
        def loss_fn(p):
            logits = model.apply({"params": p}, tokens_chunk)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels_chunk
            ).mean()
            return jax.lax.pmean(loss, "dp")

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # With check_vma=False (below), psum/pmean transpose to psum — AD
        # inserts the cross-device reductions itself, so every device
        # already holds the FULL gradient and a further psum would multiply
        # it by the device count (verified empirically: per-leaf ratio vs
        # the dense single-device gradient is uniformly n_devices before
        # this pmean, 1.0 after).
        grads = jax.lax.pmean(grads, ("dp", "sp"))
        updates, new_opt = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt, loss

    # check_vma=False: the default VMA bookkeeping inserts copy-computation
    # all-reduces into the ring backward, and XLA-CPU's AllReducePromotion
    # pass crashes cloning them ("Invalid binary instruction opcode copy").
    # Replication of the outputs is established explicitly by the grads
    # pmean + replicated update.
    fn = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(), P("dp", "sp"), P("dp")),
            out_specs=(P(), P(), P()),
            check_vma=False,  # manual over every mesh axis, as in forward
        ),
        donate_argnums=(0, 1),
    )
    _TRAIN_CACHE[key] = (optimizer, fn)
    return fn


def sp_evaluate(model, params, tokens, labels, plan: MeshPlan,
                batch: Optional[int] = None) -> Tuple[float, float]:
    """Central eval (loss, accuracy) of a text model over long sequences,
    batched host-side."""
    import optax

    n = tokens.shape[0]
    if n == 0 or (batch is not None and batch <= 0):
        raise ValueError(
            f"sp_evaluate needs a non-empty eval set and positive batch "
            f"(n={n}, batch={batch})"
        )
    batch = batch or n
    batch += (-batch) % plan.dp
    # Pad the tail slice to the FULL batch (not just dp-divisibility): a
    # distinct tail shape would retrace and recompile the whole sharded
    # forward for one slice; padded rows are dropped via [:real] below.
    losses = accs = seen = 0.0
    for i in range(0, n, batch):
        tb, yb = tokens[i : i + batch], labels[i : i + batch]
        real = len(yb)
        pad = batch - real
        if pad:
            tb = np.concatenate([tb, np.repeat(tb[-1:], pad, 0)])
            yb = np.concatenate([yb, np.repeat(yb[-1:], pad, 0)])
        logits = jax.device_get(sp_forward(model, params, tb, plan))[:real]
        losses += float(
            optax.softmax_cross_entropy_with_integer_labels(
                jnp.asarray(logits), jnp.asarray(yb[:real])
            ).sum()
        )
        accs += float((logits.argmax(-1) == yb[:real]).sum())
        seen += real
    return losses / seen, accs / seen
