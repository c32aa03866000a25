"""Pipeline parallelism (``pp``): GPipe-style stage pipelining of the text
family over a mesh axis.

Completes the rebuild's parallelism set — ``dp`` (clients), ``mp``
(tensor), ``sp`` (sequence), ``ep`` (experts), ``pp`` (layers). The
reference has none of these axes (SURVEY.md section 2.5).

Design (manual ``shard_map`` over ``pp``, dp composes as a batch axis):

- the transformer's blocks are stacked into one ``[depth, ...]`` pytree
  (every block shares a treedef) and the stage axis is sharded over ``pp``:
  each device owns ``depth / pp`` consecutive blocks;
- the batch is split into M microbatches; a ``lax.scan`` over
  ``M + pp - 1`` ticks streams them through the stages, rotating
  activations stage-to-stage with ``ppermute`` (neighbor hops on the ICI
  torus). Stage 0 feeds embeddings in; the last stage collects block
  outputs; head/pooling run on the collected stream and the logits are
  summed across stages (only the last stage contributes non-zero);
- parameters are the DENSE model's — :func:`stack_block_params` /
  :func:`unstack_block_params` convert between the per-name layout
  (``TransformerBlock_i``) and the stacked stage layout, so params trained
  densely pipeline unchanged (and vice versa).

``pp_forward(model, params, tokens, plan)`` matches
``model.apply(params, tokens)`` (dense, single device) exactly up to bf16
reduction order — asserted in ``tests/test_pipeline.py``.
"""

from __future__ import annotations

import re
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from olearning_sim_tpu.parallel.mesh import MeshPlan, global_put


_BLOCK_RE = re.compile(r"^TransformerBlock_(\d+)$")


def stack_block_params(params: Any) -> Tuple[Any, Any]:
    """Split a dense TextTransformer param tree into (rest, stacked_blocks)
    where ``stacked_blocks`` has every leaf led by a ``depth`` axis."""
    blocks = {}
    rest = {}
    for name, sub in params.items():
        m = _BLOCK_RE.match(name)
        if m:
            blocks[int(m.group(1))] = sub
        else:
            rest[name] = sub
    if not blocks:
        raise ValueError("no TransformerBlock_i entries in params")
    ordered = [blocks[i] for i in range(len(blocks))]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *ordered)
    return rest, stacked


def unstack_block_params(rest: Any, stacked: Any) -> Any:
    """Inverse of :func:`stack_block_params`."""
    depth = jax.tree.leaves(stacked)[0].shape[0]
    out = dict(rest)
    for i in range(depth):
        out[f"TransformerBlock_{i}"] = jax.tree.map(lambda x: x[i], stacked)
    return out


def _validate_pp_inputs(model, plan: MeshPlan, caller: str, tokens,
                        num_microbatches) -> int:
    """Validate and return the resolved microbatch count M."""
    if plan.pp <= 1:
        raise ValueError(
            f"{caller} needs a mesh with a pp axis (make_mesh_plan(pp=...))"
        )
    if model.depth % plan.pp:
        raise ValueError(
            f"pp={plan.pp} must divide the model depth {model.depth}"
        )
    impl = getattr(model, "attention_impl", "dense")
    if impl != "dense":
        # The stage blocks apply dense attention. Ring params are
        # layout-compatible, but the ring forward needs an sp axis inside
        # shard_map (sharded sequence + psum pooling) which the pipeline
        # graph doesn't provide; flash additionally has a different param
        # layout. Fail at the boundary, not inside scan.
        raise ValueError(
            f"pipeline parallelism requires attention_impl='dense', the "
            f"model was built with {impl!r}"
        )
    M = num_microbatches if num_microbatches is not None else plan.pp
    if M <= 0:
        raise ValueError(f"num_microbatches must be positive, got {M}")
    B = np.asarray(tokens).shape[0]
    if B % (plan.dp * M):
        raise ValueError(
            f"dp*num_microbatches = {plan.dp}*{M} must divide the batch {B} "
            f"(microbatching applies to each dp shard's local batch)"
        )
    return M


def _microbatch(tokens, num_microbatches: int):
    B = tokens.shape[0]
    if B % num_microbatches:
        raise ValueError(
            f"num_microbatches={num_microbatches} must divide the batch {B}"
        )
    mb = B // num_microbatches
    return tokens.reshape((num_microbatches, mb) + tokens.shape[1:])


def pp_forward(model, params, tokens, plan: MeshPlan,
               num_microbatches: int = None):
    """Forward the dense-attention text ``model`` with its blocks pipelined
    over the plan's ``pp`` axis. Returns logits [B, num_classes], matching
    the dense ``model.apply`` on one device."""
    M = _validate_pp_inputs(model, plan, "pp_forward", tokens,
                            num_microbatches)
    if isinstance(params, tuple):
        # Pre-placed (rest, stacked) from pp_place_params — no host
        # round-trip of the block weights.
        rest, stacked = params
    else:
        rest, stacked = pp_place_params(params, plan)
    return _compiled_forward(model, plan.mesh, M)(
        rest, stacked, global_put(np.asarray(tokens),
                                  NamedSharding(plan.mesh, P("dp"))),
    )


_FWD_CACHE: dict = {}


def _compiled_forward(model, mesh, M: int):
    key = (model, mesh, M)
    if key not in _FWD_CACHE:
        _FWD_CACHE[key] = _build(model, mesh, M)
    return _FWD_CACHE[key]


def _build(model, mesh, M: int):
    pipeline = _PipelineGraph(model, mesh, M)

    def body(rest, local_blocks, tokens):
        return pipeline.logits(rest, local_blocks, tokens)

    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P("pp"), P("dp")),
            out_specs=P("dp"),
            axis_names=frozenset({"dp", "pp"}),
            check_vma=False,
        )
    )


# ------------------------------------------------------------------ training
def pp_place_params(params: Any, plan: MeshPlan) -> Tuple[Any, Any]:
    """Split and place dense params for pipelined training: returns
    ``(rest, stacked)`` with the block stack's leading depth axis sharded
    over ``pp`` and everything else replicated."""
    if plan.pp <= 1:
        raise ValueError(
            "pp_place_params needs a mesh with a pp axis (make_mesh_plan(pp=...))"
        )
    rest, stacked = stack_block_params(params)
    rest = jax.tree.map(
        lambda x: global_put(np.asarray(x), NamedSharding(plan.mesh, P())),
        rest,
    )
    stacked = jax.tree.map(
        lambda x: global_put(np.asarray(x), NamedSharding(plan.mesh, P("pp"))),
        stacked,
    )
    return rest, stacked


_GRAD_CACHE: dict = {}
_APPLY_CACHE: dict = {}


def pp_train_step(model, rest, stacked, opt_state, tokens, labels, optimizer,
                  plan: MeshPlan, num_microbatches: int = None):
    """One optimizer step with the block stack pipelined over ``pp``.

    Block gradients are computed stage-local (each stage only differentiates
    through its own layers — they stay sharded over ``pp``); embed/head
    gradients are partial per stage and are psum'd. The optimizer update
    runs in a follow-up GSPMD-auto jit so optimizer-state shardings follow
    the params they track.

    Contract: ``rest``/``stacked``/``opt_state`` are DONATED; reuse one
    optimizer instance across steps (compiled steps cached per
    (model, mesh, microbatches)). Returns
    ``(rest, stacked, opt_state, loss)``.
    """
    M = _validate_pp_inputs(model, plan, "pp_train_step", tokens,
                            num_microbatches)
    tokens = global_put(np.asarray(tokens), NamedSharding(plan.mesh, P("dp")))
    labels = global_put(np.asarray(labels), NamedSharding(plan.mesh, P("dp")))

    key = (model, plan.mesh, M)
    if key not in _GRAD_CACHE:
        _GRAD_CACHE[key] = _build_grads(model, plan.mesh, M)
    loss, g_rest, g_blocks = _GRAD_CACHE[key](rest, stacked, tokens, labels)

    # Cache holds a strong reference to the optimizer and compares object
    # identity — an id() comparison could silently match a recycled address
    # after the original optimizer is garbage-collected.
    cached = _APPLY_CACHE.get(key)
    if cached is None or cached[0] is not optimizer:
        def apply(params, opt_state, grads):
            updates, new_opt = optimizer.update(grads, opt_state, params)
            import optax as _optax

            return _optax.apply_updates(params, updates), new_opt

        _APPLY_CACHE[key] = (optimizer, jax.jit(apply, donate_argnums=(0, 1)))
        cached = _APPLY_CACHE[key]
    (rest, stacked), opt_state = cached[1](
        (rest, stacked), opt_state, (g_rest, g_blocks)
    )
    return rest, stacked, opt_state, loss


def _build_grads(model, mesh, M: int):
    import optax

    from olearning_sim_tpu.parallel.scale_check import verify_grad_scale

    # The /scale division below encodes an empirical JAX transpose behavior;
    # measure it on a one-scalar program first and refuse to train if it
    # moved (e.g. after a JAX upgrade) — see parallel/scale_check.py.
    verify_grad_scale(mesh, ("dp", "pp"))
    pipeline = _PipelineGraph(model, mesh, M)

    def body(rest, local_blocks, tokens, labels):
        def loss_fn(r, lb):
            logits = pipeline.logits(r, lb, tokens)
            local = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()
            return jax.lax.pmean(local, "dp")

        loss, (g_rest, g_blocks) = jax.value_and_grad(
            loss_fn, argnums=(0, 1)
        )(rest, local_blocks)
        # With check_vma=False every psum/pmean transposes to psum, so the
        # replicated loss cotangent enters the backward once per stage —
        # each device's gradient is uniformly pp x its true partial
        # (verified empirically leaf by leaf, see tests). Blocks are
        # stage-local shards whose dp-partials must sum; embed/head
        # partials sum across both axes.
        scale = jax.lax.psum(1, "pp") * jax.lax.psum(1, "dp")
        g_rest = jax.lax.psum(g_rest, ("dp", "pp"))
        g_rest = jax.tree.map(lambda g: g / scale, g_rest)
        g_blocks = jax.lax.psum(g_blocks, "dp")
        g_blocks = jax.tree.map(lambda g: g / scale, g_blocks)
        return loss, g_rest, g_blocks

    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P("pp"), P("dp"), P("dp")),
            out_specs=(P(), P(), P("pp")),
            axis_names=frozenset({"dp", "pp"}),
            check_vma=False,
        )
    )


class _PipelineGraph:
    """The pipelined logits computation, shared by forward and training
    (identical graph; ``_build``'s body wraps it for inference).

    COUPLING NOTE: ``embed``/``head`` mirror TextTransformer.__call__'s
    prologue/epilogue by flax auto-generated param name (Embed_0 /
    pos_embedding / LayerNorm_0 / Dense_0) — restructuring the dense model
    into setup()-style methods would rename every param and break existing
    checkpoints, so the mirror is kept and
    ``test_pp_forward_matches_dense`` enforces it stays in sync. The dense
    model marks its lookup (``models/lookup.py``); the mark is a no-op
    outside ``FedCore``'s local step, so plain ``nn.Embed`` mirrors it."""

    def __init__(self, model, mesh, M: int):
        self.model = model
        self.pp = mesh.shape["pp"]
        self.M = M

        from olearning_sim_tpu.models.transformer import TransformerBlock

        self.blk = TransformerBlock(
            model.width, model.heads, model.mlp_dim, model.dtype, "dense"
        )

    def embed(self, rest, toks):
        model = self.model
        emb = nn.Embed(
            model.vocab_size, model.width, param_dtype=jnp.float32,
        ).apply({"params": rest["Embed_0"]}, toks)
        L = toks.shape[1]
        x = (emb + rest["pos_embedding"][:, :L]).astype(model.dtype)
        return nn.LayerNorm(dtype=model.dtype).apply(
            {"params": rest["LayerNorm_0"]}, x
        )

    def head(self, rest, x, pad_mask):
        m = pad_mask[..., None].astype(jnp.float32)
        s = (x.astype(jnp.float32) * m).sum(1)
        c = m.sum(1)
        pooled = s / jnp.maximum(c, 1.0)
        return nn.Dense(self.model.num_classes, dtype=jnp.float32).apply(
            {"params": rest["Dense_0"]}, pooled
        )

    def logits(self, rest, local_blocks, tokens):
        model, M, pp = self.model, self.M, self.pp
        stage = jax.lax.axis_index("pp")
        toks_mb = _microbatch(tokens, M)
        pad_mb = toks_mb != model.pad_id
        emb_mb = jax.vmap(lambda t: self.embed(rest, t))(toks_mb)

        mb, L, W = emb_mb.shape[1:]
        total = M + pp - 1
        perm = [(i, i + 1) for i in range(pp - 1)]

        def stage_apply(x, pad_mask):
            def one(c, bp):
                return self.blk.apply({"params": bp}, c, pad_mask), None

            x, _ = jax.lax.scan(one, x, local_blocks)
            return x

        def tick(carry, t):
            recv, outs = carry
            feed_idx = jnp.clip(t, 0, M - 1)
            x0 = jnp.where(t < M, emb_mb[feed_idx], jnp.zeros_like(emb_mb[0]))
            xin = jnp.where(stage == 0, x0, recv)
            mb_idx = jnp.clip(t - stage, 0, M - 1)
            y = stage_apply(xin, pad_mb[mb_idx])
            sent = jax.lax.ppermute(y, "pp", perm)
            out_idx = jnp.clip(t - (pp - 1), 0, M - 1)
            valid = (t >= pp - 1) & (stage == pp - 1)
            outs = jnp.where(
                valid,
                jax.lax.dynamic_update_index_in_dim(outs, y, out_idx, 0),
                outs,
            )
            return (sent, outs), None

        outs0 = jnp.zeros((M, mb, L, W), emb_mb.dtype)
        (_, outs), _ = jax.lax.scan(
            tick, (jnp.zeros((mb, L, W), emb_mb.dtype), outs0),
            jnp.arange(total),
        )
        logits = jax.vmap(lambda x, m: self.head(rest, x, m))(outs, pad_mb)
        logits = jnp.where(stage == pp - 1, logits, jnp.zeros_like(logits))
        logits = jax.lax.psum(logits, "pp")
        return logits.reshape((M * mb,) + logits.shape[2:])
