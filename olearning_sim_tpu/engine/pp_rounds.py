"""Stage-pipelined per-client training: the ``pp > 1`` round program.

Wires :mod:`olearning_sim_tpu.parallel.pipeline` into the compiled FL
round for block-structured text families (DistilBERT shapes): the model's
transformer blocks are stacked into one ``[depth, ...]`` pytree whose
stage axis is sharded over the mesh ``pp`` axis, and EVERY client's local
SGD runs with its forward/backward streamed through the stages as
microbatches (GPipe schedule, ``_PipelineGraph`` — the same graph
``pp_forward``/``pp_train_step`` compile, here vmapped over the client
block inside the round program's ``shard_map``).

Program shape (manual over BOTH ``dp`` and ``pp``; ``check_vma=False``
like every pipeline program — the ppermute ring breaks replication
typing)::

    round_step = jit( shard_map( stack blocks; slice this stage's ->
                                 scan over client blocks:
                                     vmap over clients:
                                         masked lax.scan over local SGD
                                         steps, each fwd/bwd pipelined
                                         over pp
                                 -> psum(weighted deltas over dp) )
                      -> unstack -> dense server update )

The block stack/slice runs INSIDE the manual region, not as a jit
prologue: on this runtime (jaxlib 0.4.x CPU SPMD partitioner) a manual
``shard_map`` whose operands are produced by surrounding GSPMD-auto
code silently reads corrupted values once the mesh has dp > 1 — the
auto->manual handoff mispartitions (reproduced with a bare in-jit
``jnp.stack`` feeding a ``P('pp')`` in_spec; ``with_sharding_constraint``
does not help). Every shard_map operand must therefore be a DIRECT jit
input; the stage's local ``[depth/pp, ...]`` block slice is carved out
per device with ``dynamic_slice`` on ``axis_index("pp")``, which is pure
local compute (params enter replicated, so no collective is added).
tests/test_pp_rounds.py pins dp-invariance of per-client losses, which
is exactly the symptom the prologue-stack layout broke.

Gradient scale: with ``check_vma=False`` every psum transposes to psum,
so the replicated per-client loss cotangent re-enters the backward once
per stage — raw grads are uniformly ``pp`` x their true value
(:mod:`olearning_sim_tpu.parallel.scale_check` guards this empirical
transpose behavior at build time, exactly like ``pp_train_step``). The
per-step ``grad_transform`` psums the shared (embed/head) grads across
stages and divides everything by ``pp``, so the local-SGD trajectory
matches the dense program's up to bf16/f32 reduction order — asserted
against the dp-only program in tests/test_pp_rounds.py.

The server update runs DENSE in GSPMD-auto land after the shard_map
(stack/unstack are cheap view ops): ``ServerState`` keeps the normal
param-tree layout, so eval, export, checkpointing, and warm starts are
oblivious to pp. Composition: pipeline parallelism supports the plain
FedOpt families only — deadline/attack/defense/async variants and
personalized/control-variate algorithms are rejected at validation and
at build (docs/performance.md has the composition matrix).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from olearning_sim_tpu.engine.round_stages import (
    RoundMetrics,
    ServerState,
    _tree_l2_sq,
    client_block,
    next_state,
    server_commit,
)


def validate_pp_build(model, plan, config, algorithm, microbatches):
    """Build-time checks for a pipelined fedcore — fail before any trace.

    Returns the resolved microbatch count M."""
    from olearning_sim_tpu.parallel import pipeline as pl  # noqa: F401

    if plan.pp <= 1:
        raise ValueError("validate_pp_build needs a mesh with pp > 1")
    depth = getattr(model, "depth", None)
    if depth is None:
        raise ValueError(
            f"pipeline parallelism needs a block-structured text model "
            f"(TextTransformer family); {type(model).__name__} has no depth"
        )
    if depth % plan.pp:
        raise ValueError(
            f"parallel.pp={plan.pp} must divide the model depth {depth}"
        )
    impl = getattr(model, "attention_impl", "dense")
    if impl != "dense":
        raise ValueError(
            f"pipeline parallelism requires attention_impl='dense', the "
            f"model was built with {impl!r}"
        )
    if algorithm.personalized or algorithm.control_variates:
        raise ValueError(
            f"pipeline parallelism (pp>1) does not support the "
            f"personalized/control-variate algorithm {algorithm.name!r}"
        )
    if config.shard_server_update:
        raise ValueError(
            "pp>1 does not compose with fedcore.shard_server_update (the "
            "flat dp coordinate shards would cut across the stage "
            "partition); docs/performance.md has the composition matrix"
        )
    M = int(microbatches) if microbatches is not None else plan.pp
    if M < 1:
        raise ValueError(f"parallel.microbatches must be >= 1, got {M}")
    if config.batch_size % M:
        raise ValueError(
            f"parallel.microbatches={M} must divide "
            f"fedcore.batch_size={config.batch_size} (each local-SGD "
            f"minibatch is streamed through the stages in M microbatches)"
        )
    return M


def build_pp_round_step(core, model, microbatches):
    """The (single) compiled round program for a ``pp > 1`` mesh plan.

    ``core`` — the owning :class:`~olearning_sim_tpu.engine.fedcore.
    FedCore`; ``model`` — the dense-attention TextTransformer instance the
    core's apply/init functions wrap; ``microbatches`` — GPipe microbatch
    count M (None = pp)."""
    from olearning_sim_tpu.parallel.pipeline import (
        _PipelineGraph,
        stack_block_params,
        unstack_block_params,
    )
    from olearning_sim_tpu.parallel.scale_check import verify_grad_scale

    plan = core.plan
    cfg = core.config
    alg = core.algorithm
    mesh = plan.mesh
    ppn = plan.pp
    M = validate_pp_build(model, plan, cfg, alg, microbatches)
    # The /pp division below encodes the empirical psum-transpose behavior
    # under check_vma=False; refuse to train if a JAX upgrade moved it.
    verify_grad_scale(mesh, ("dp", "pp"))
    graph = _PipelineGraph(model, mesh, M)
    trace_key = ("pp", ppn, M)

    def persample(p, xb, yb):
        if xb.shape[0] % M:
            raise ValueError(
                f"pipelined minibatch of {xb.shape[0]} samples is not "
                f"divisible by microbatches={M}; pick batch_size (and, in "
                f"multiplicity sample mode, n_local) divisible by M"
            )
        logits = graph.logits(p["rest"], p["blocks"], xb)
        return (
            optax.softmax_cross_entropy_with_integer_labels(logits, yb),
            jnp.float32(0.0),
        )

    stage_depth = model.depth // ppn

    def shard_body(params, round_idx, base_key,
                   x, y, num_samples, num_steps, uid, weight):
        # Trace-time probe (see fedcore: the no-retrace regression guard).
        core.trace_counts[trace_key] = \
            core.trace_counts.get(trace_key, 0) + 1
        c_local = x.shape[0]
        if c_local % cfg.block_clients != 0:
            raise ValueError(
                f"per-device client count {c_local} must be a multiple of "
                f"block_clients={cfg.block_clients}; pad the dataset with "
                f"ClientDataset.pad_for(plan, block=config.block_clients)"
            )
        nb = c_local // cfg.block_clients
        # Stack + slice in the manual region (module docstring: shard_map
        # operands must be direct jit inputs on this runtime). Params come
        # in replicated; each stage keeps only its own [stage_depth, ...]
        # block slice — a local view, no collective.
        stage = jax.lax.axis_index("pp")
        rest, stacked_full = stack_block_params(params)
        stacked = jax.tree.map(
            lambda v: jax.lax.dynamic_slice_in_dim(
                v, stage * stage_depth, stage_depth, 0
            ),
            stacked_full,
        )
        globals0 = {"rest": rest, "blocks": stacked}

        penalty = None
        if alg.prox_mu:
            # FedProx proximal pull toward the global model, as the TRUE
            # full-model ||p - w||^2 (the dense program's semantics): the
            # stage-local block slices psum to the whole blocks term, the
            # replicated rest term stays outside the psum. Routing the
            # block term through a pp psum also puts its backward on the
            # same psum-transpose path as the CE gradients, so grad_fix's
            # uniform /pp restores mu exactly — a stage-local penalty
            # would come out mu/pp on block leaves (its cotangent never
            # passes the logits psum) AND make the per-client loss
            # stage-divergent under the replicated out_specs.
            def penalty(p):
                blocks_sq = jax.lax.psum(
                    _tree_l2_sq(p["blocks"], globals0["blocks"]), "pp"
                )
                rest_sq = _tree_l2_sq(p["rest"], globals0["rest"])
                return 0.5 * alg.prox_mu * (rest_sq + blocks_sq)

        def grad_fix(grads, _params):
            # Undo the check_vma=False psum-transpose inflation (module
            # docstring): shared embed/head grads are per-stage partials
            # (non-zero only on the stage that used them) summed across
            # stages; block grads are stage-local. Everything is pp x its
            # true value, so one uniform division restores the dense
            # program's gradients.
            g_rest = jax.lax.psum(grads["rest"], "pp")
            return jax.tree.map(lambda g: g / ppn,
                                {"rest": g_rest, "blocks": grads["blocks"]})

        def local_train(xc, yc, ns, st, uc):
            key = jax.random.fold_in(
                jax.random.fold_in(base_key, uc), round_idx
            )
            steps_eff = jnp.minimum(st, cfg.max_local_steps)
            params_f, mean_loss = core._masked_sgd(
                globals0, alg.local_optimizer.init(globals0),
                xc, yc, ns, steps_eff, key, persample, penalty_fn=penalty,
                grad_transform=grad_fix, varying_init=False,
            )
            delta = jax.tree.map(jnp.subtract, params_f, globals0)
            return delta, mean_loss

        def blocked(a):
            return a.reshape((nb, cfg.block_clients) + a.shape[1:])

        xs = (blocked(x), blocked(y), blocked(num_samples),
              blocked(num_steps), blocked(uid), blocked(weight))
        zero_delta = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), globals0
        )
        init = (zero_delta, jnp.float32(0.0), jnp.float32(0.0),
                jnp.float32(0.0))

        def block_step(carry, inp):
            sum_delta, sum_w, sum_loss, count = carry
            bx, by, bns, bst, buid, bw = inp
            # Resilience gate: a diverged client contributes nothing
            # (same stage as the dense program). The mask must agree
            # across pp stages — a non-finite value confined to ONE
            # stage's block slice would otherwise flip ok there only,
            # making sum_w/count/rest-deltas stage-divergent under the
            # replicated out_specs — so stages AND their verdicts.
            blk = client_block(
                local_train, (0, 0, 0, 0, 0), (bx, by, bns, bst, buid), bw,
                agree=lambda ok: jax.lax.pmin(
                    ok.astype(jnp.int32), "pp").astype(jnp.bool_),
            )
            sum_delta = blk.weighted_sum(sum_delta)
            return ((sum_delta, *blk.tally(sum_w, sum_loss, count)),
                    blk.losses)

        (sum_delta, sum_w, sum_loss, count), block_losses = jax.lax.scan(
            block_step, init, xs, unroll=min(cfg.block_unroll, nb)
        )
        client_loss = block_losses.reshape((c_local,))
        # Clients are sharded over dp (every pp stage holds the same
        # clients and computes identical per-client values — the rest
        # deltas are stage-identical after grad_fix's psum, the block
        # deltas stage-local slices), so the cross-replica reduction is a
        # psum over dp only.
        with jax.named_scope("aggregate"):
            sum_w = jax.lax.psum(sum_w, "dp")
            sum_loss = jax.lax.psum(sum_loss, "dp")
            count = jax.lax.psum(count, "dp")
            sum_delta = jax.lax.psum(sum_delta, "dp")
        return (sum_delta["rest"], sum_delta["blocks"], sum_w, sum_loss,
                count, client_loss)

    rep = P()
    cl = P("dp")
    shard_fn = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(rep, rep, rep, cl, cl, cl, cl, cl, cl),
        out_specs=(rep, P("pp"), rep, rep, rep, cl),
        axis_names=frozenset({"dp", "pp"}),
        check_vma=False,
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def round_step(state: ServerState, x, y, num_samples, num_steps,
                   uid, weight):
        d_rest, d_blocks, sum_w, sum_loss, count, client_loss = shard_fn(
            state.params, state.round_idx, state.base_key,
            x, y, num_samples, num_steps, uid, weight,
        )
        denom = jnp.maximum(sum_w, 1e-8)
        with jax.named_scope("aggregate"):
            mean_delta = unstack_block_params(
                jax.tree.map(lambda s: s / denom, d_rest),
                jax.tree.map(lambda s: s / denom, d_blocks),
            )
        # Dense FedOpt server update — identical math and state layout to
        # the dp-only program's (the pipeline only changed WHERE the
        # per-client compute ran).
        with jax.named_scope("server_update"):
            new_params, new_opt_state = server_commit(
                alg.server_optimizer, state.params, state.opt_state,
                mean_delta)
        metrics = RoundMetrics(
            mean_loss=sum_loss / denom,
            weight_sum=sum_w,
            clients_trained=count,
            client_loss=client_loss,
            personal_loss=jnp.float32(0.0),
            stragglers=jnp.float32(0.0),
            anomaly_score=jnp.float32(0.0),
            clipped=jnp.float32(0.0),
        )
        return (next_state(state, new_params, new_opt_state,
                           state.round_idx + 1), metrics)

    return round_step
