"""What every round-program builder shares: the state and metrics types a
round program takes and returns, the client-block stage, the replicated
server commit and the jit wrapper of the resident programs.

The builders (``fedcore.FedCore._build_round_step`` and
``_build_stream_step``, ``async_rounds.build_async_round_step``,
``pp_rounds.build_pp_round_step``) import from here; nothing here knows a
builder. A round program is four decisions — how clients lie over the mesh
(the builder's boundary), what a block of clients does (:func:`client_block`,
here, called by all five ``block_step`` bodies), what becomes of a block's
gated deltas (each builder's accumulator) and how the mean delta becomes a
new model (:func:`server_commit`, here, or a builder's coordinate-sharded
form).

Trace order is part of the contract: the compiled text of a program follows
the order its operations were traced in, so a stage keeps the order the
builders had (train, attack scale, finiteness gate, ``bw_eff``, float32
cast + gate + clip), the accumulator runs between :func:`client_block` and
:meth:`ClientBlock.tally`, and a change of order here changes every
program at once.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct

from olearning_sim_tpu.parallel.mesh import pad_to_multiple


class ServerState(struct.PyTreeNode):
    """Global FL state carried across rounds (the checkpointable unit —
    reference analogue: ``{task_id}_{round}_result_model.mnn`` round-scoped
    model files, ``utils_run_task.py:327-397``)."""

    params: Any
    opt_state: Any
    round_idx: jnp.ndarray  # int32 scalar
    base_key: jax.Array     # PRNG key; per-client streams fold in (uid, round)


class RoundMetrics(struct.PyTreeNode):
    """Per-round aggregates (reference analogue: ``analyze_results`` success /
    failure accounting persisted to MySQL, ``run_task.py:149-210``)."""

    mean_loss: jnp.ndarray      # weight-averaged local training loss
    weight_sum: jnp.ndarray     # total aggregation weight (participants)
    clients_trained: jnp.ndarray  # number of clients with weight > 0
    # Per-client mean local loss [C] (sharded over dp). Finiteness doubles as
    # the success signal replacing subprocess exit codes
    # (``utils_run_task.py:490-494``).
    client_loss: jnp.ndarray
    # Weight-averaged Ditto personal-branch loss (0 when not personalized).
    personal_loss: jnp.ndarray = struct.field(default_factory=lambda: jnp.float32(0.0))
    # Participating clients whose simulated completion_time exceeded the
    # round deadline (deadline-masked aggregation; always 0 on the
    # deadline-off path). Distinct from drops: a straggler's update exists
    # but arrived too late to aggregate.
    stragglers: jnp.ndarray = struct.field(default_factory=lambda: jnp.float32(0.0))
    # Adversarial-client defense (engine/defense.py). ``anomaly_score``:
    # per-client [C] Krum-style distance-to-median scores (sharded over dp)
    # when scoring is enabled, scalar 0 otherwise — the runner's
    # quarantine feedback signal. ``clipped``: participants whose delta
    # L2 norm was clipped this round (0 on the defense-off path).
    anomaly_score: jnp.ndarray = struct.field(default_factory=lambda: jnp.float32(0.0))
    clipped: jnp.ndarray = struct.field(default_factory=lambda: jnp.float32(0.0))
    # Work counts the client model sows while it trains (``apply_stats_fn``:
    # a routed expert layer's assignments and loads, a chunked scan's tokens
    # and chunks), int32, summed over
    # the round's active local steps of every computed client; scalar 0 for
    # a model that sows none and on every program but the resident
    # dp-manual one. ``FedCore.describe_stats`` names what is in it.
    model_stats: jnp.ndarray = struct.field(default_factory=lambda: jnp.float32(0.0))


class PersonalState(struct.PyTreeNode):
    """Ditto per-client personalized parameters: every leaf has a leading
    client axis [C, ...] sharded over ``dp`` — the rebuild's answer to the
    'per-client optimizer state at 10k clients' memory plan (SURVEY.md
    section 7 hard parts): state lives sharded across devices and is updated
    in place (donated) each round."""

    params: Any


class ControlState(struct.PyTreeNode):
    """SCAFFOLD control variates (Karimireddy et al. 2020): per-client
    ``client_controls`` c_i [C, ...] sharded over ``dp`` (same memory plan
    as Ditto's personal params) and the replicated server control c."""

    client_controls: Any
    server_control: Any


def _accumulate_delta(sum_delta, deltas, bw_eff, gate):
    """``sum_delta + bw_eff . gate(f32(delta))`` leaf by leaf: the finiteness
    gate and float32 cast of each client delta (scope ``delta_transform``)
    and its weighted sum into the round's accumulator (scope
    ``aggregate``)."""
    def one(s, d):
        with jax.named_scope("delta_transform"):
            d = gate(d.astype(jnp.float32))
        with jax.named_scope("aggregate"):
            return s + jnp.tensordot(bw_eff, d, axes=(0, 0))

    return jax.tree.map(one, sum_delta, deltas)


def _one_client_block(fn, in_axes):
    """``jax.vmap(fn, in_axes)`` for a block of exactly one client, without
    the batching: the block axis is squeezed off the mapped arguments and
    put back on the results."""

    def one(*args):
        args = [a if axis is None else jax.tree.map(lambda t: t[0], a)
                for a, axis in zip(args, in_axes)]
        return jax.tree.map(lambda t: t[None], fn(*args))

    return one


def _to_varying(tree, axis: str):
    """Type a replicated value as device-varying over ``axis`` (shard_map VMA).

    Needed for scan carries that start replicated (e.g. global params) but
    accumulate shard-local data inside ``shard_map``.
    """
    return jax.lax.pcast(tree, (axis,), to="varying")


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _flat_pad_leaf(p, multiple: int):
    """Flatten a leaf and zero-pad to a multiple of ``multiple`` — the
    coordinate layout shared by the sharded server update and the sharded
    robust aggregation (defense.shard_client_deltas pads identically, so a
    robust aggregate shard can feed the sharded optimizer directly)."""
    flat = p.reshape(-1)
    target = pad_to_multiple(flat.shape[0], multiple)
    if target != flat.shape[0]:
        flat = jnp.pad(flat, (0, target - flat.shape[0]))
    return flat


def _tree_l2_sq(a, b):
    leaves = jax.tree.map(lambda x, y: jnp.sum(jnp.square(x - y)), a, b)
    return jax.tree.reduce(jnp.add, leaves, jnp.float32(0.0))


def _attack_deltas(deltas, batk):
    """Byzantine update attack: the client "trains honestly" but ships a
    transformed delta (sign_flip = -1, scale = factor). A benign scale of
    exactly 1.0 is a bitwise no-op, so an all-ones attack vector
    reproduces the attack-free program's outputs."""
    return jax.tree.map(
        lambda d: d * batk.astype(d.dtype).reshape(
            (-1,) + (1,) * (d.ndim - 1)
        ),
        deltas,
    )


def _finite_client_mask(losses, deltas):
    """[block] bool — clients whose local training stayed finite (finite
    loss AND every delta leaf finite). The resilience gate of every round
    program: a diverged client contributes NOTHING to the aggregate —
    without it, one NaN client poisons the global params even at weight 0
    (the weighted reduction turns 0 * NaN into NaN). For all-finite clients
    the downstream selects keep untouched values, so healthy rounds are
    bitwise unchanged."""
    ok = jnp.isfinite(losses)
    for d in jax.tree.leaves(deltas):
        ok = jnp.logical_and(
            ok, jnp.isfinite(d.reshape(d.shape[0], -1)).all(axis=1)
        )
    return ok


def _clip_client_deltas(d32, clip_norm):
    """Per-client L2 norm clip over a block of f32 deltas: a delta beyond
    the clip sphere is rescaled onto it. where-select (not a
    multiply-by-1) so an unclipped delta — and the whole program under
    the disabled-clip sentinel — stays bitwise untouched. Returns
    ``(clipped_d32, too_big)``."""
    norm2 = functools.reduce(
        jnp.add,
        [jnp.square(l.reshape(l.shape[0], -1)).sum(axis=1)
         for l in jax.tree.leaves(d32)],
    )
    too_big = norm2 > clip_norm * clip_norm
    scale = jnp.where(too_big, clip_norm / jnp.sqrt(norm2), 1.0)
    clipped = jax.tree.map(
        lambda d: jnp.where(
            too_big.reshape((-1,) + (1,) * (d.ndim - 1)),
            d * scale.reshape((-1,) + (1,) * (d.ndim - 1)),
            d,
        ),
        d32,
    )
    return clipped, too_big


# ------------------------------------------------------------ block stage
class ClientBlock(NamedTuple):
    """One block of clients, trained and gated (:func:`client_block`): what
    a builder's accumulator consumes."""

    deltas: Any           # [B, ...] per-client deltas, attack-scaled, ungated
    # float32, gated and (under a clip) clipped deltas; None where the
    # builder asked for neither and :func:`_accumulate_delta` gates leaf by
    # leaf instead.
    d32: Any
    losses: jnp.ndarray   # [B] mean local loss (NaN: no step run)
    ok: jnp.ndarray       # [B] the finiteness verdict
    bw: jnp.ndarray       # [B] aggregation weights as given
    bw_eff: jnp.ndarray   # [B] ... and zeroed where not ``ok``
    gate: Callable        # leaf -> leaf, zero where not ``ok``
    clipped: Any          # participants of this block clipped (None: no clip)
    extra: List[Any]      # ``train``'s further results (dc_i, work counts)

    def weighted_sum(self, sum_delta):
        """``sum_delta + bw_eff . deltas``, gated and in float32: the
        accumulator of the resident, streamed and pipelined programs."""
        if self.d32 is None:
            return _accumulate_delta(sum_delta, self.deltas, self.bw_eff,
                                     self.gate)
        with jax.named_scope("aggregate"):
            return jax.tree.map(
                lambda s, d: s + jnp.tensordot(self.bw_eff, d, axes=(0, 0)),
                sum_delta, self.d32,
            )

    def tally(self, sum_w, sum_loss, count, loss_first: bool = False):
        """The round's running (weight, weighted loss, participants) with
        this block added; called after the accumulator, which is where the
        builders' trace order has it. ``loss_first``: the buffered program
        adds the loss before the weight, and its compiled text says so."""
        def add_loss(total):
            return total + jnp.where(
                self.ok, self.bw * self.losses, 0.0).sum()

        if loss_first:
            sum_loss = add_loss(sum_loss)
        sum_w = sum_w + self.bw_eff.sum()
        if not loss_first:
            sum_loss = add_loss(sum_loss)
        count = count + (self.bw_eff > 0).sum().astype(jnp.float32)
        return sum_w, sum_loss, count


def client_block(train, in_axes, args, bw, *, vmap_clients: bool = True,
                 pin_clients: Optional[Callable] = None, attack_scale=None,
                 agree: Optional[Callable] = None, clip_norm=None,
                 f32: bool = False) -> ClientBlock:
    """The client-block stage of every round program: train the block's
    clients, scale the attackers' deltas, gate what did not stay finite and,
    where asked, cast to float32 and clip.

    ``train(*one client's args) -> (delta, mean_loss, *extra)`` is mapped
    over the block by ``in_axes`` — batched, or for a model that cannot be
    (``vmap_clients=False``) one client at a time (:func:`_one_client_block`).
    ``pin_clients``: the boundary's sharding constraint on the per-client
    deltas (GSPMD-auto programs). ``attack_scale`` [B]: see
    :func:`_attack_deltas`. ``agree(ok) -> ok``: makes the finiteness
    verdict one across a further mesh axis (the pipelined program's
    ``pmin`` over ``pp``). ``clip_norm``: per-client L2 clip of the gated
    float32 deltas (:func:`_clip_client_deltas`); ``f32`` asks for those
    deltas without a clip (the buffered program always does; the others
    only under a defense, and otherwise leave cast and gate to
    :meth:`ClientBlock.weighted_sum`)."""
    with jax.named_scope("client_train"):
        deltas, losses, *extra = (
            jax.vmap if vmap_clients else _one_client_block)(
            train, in_axes=in_axes)(*args)
        if pin_clients is not None:
            deltas = pin_clients(deltas)
    with jax.named_scope("delta_transform"):
        if attack_scale is not None:
            deltas = _attack_deltas(deltas, attack_scale)
        # Resilience gate (_finite_client_mask): a diverged client
        # contributes nothing, finite clients bitwise unchanged.
        ok = _finite_client_mask(losses, deltas)
        if agree is not None:
            ok = agree(ok)

    def gate(d):
        return jnp.where(ok.reshape((-1,) + (1,) * (d.ndim - 1)), d, 0.0)

    bw_eff = jnp.where(ok, bw, 0.0)
    d32 = clipped = None
    if f32 or clip_norm is not None:
        with jax.named_scope("delta_transform"):
            d32 = jax.tree.map(lambda d: gate(d.astype(jnp.float32)), deltas)
            if clip_norm is not None:
                d32, too_big = _clip_client_deltas(d32, clip_norm)
                clipped = jnp.logical_and(
                    bw_eff > 0, too_big).sum().astype(jnp.float32)
    return ClientBlock(deltas, d32, losses, ok, bw, bw_eff, gate, clipped,
                       extra)


# ----------------------------------------------------------- server commit
def server_commit(optimizer, params, opt_state, mean_delta, scale=None):
    """The replicated server commit: the server optimizer consumes the
    negative mean delta (times ``scale``, the buffered program's staleness
    discount) as a pseudo-gradient in the parameters' dtype (FedOpt
    formulation). Returns ``(new_params, new_opt_state)``."""
    pseudo_grad = jax.tree.map(
        lambda d, p: (-(d if scale is None else scale * d)).astype(p.dtype),
        mean_delta, params,
    )
    updates, new_opt_state = optimizer.update(pseudo_grad, opt_state, params)
    return optax.apply_updates(params, updates), new_opt_state


def next_state(state: ServerState, new_params, new_opt_state,
               new_round) -> ServerState:
    """``state`` after a commit (the PRNG base key never moves)."""
    return ServerState(params=new_params, opt_state=new_opt_state,
                       round_idx=new_round, base_key=state.base_key)


def jit_round_step(make_fn, personalized: bool, controlled: bool):
    """The jitted ``round_step`` of a resident program — plain, personalized
    (Ditto: ``PersonalState`` in and out) or controlled (SCAFFOLD:
    ``ControlState`` in and out, and the true population) — around
    ``make_fn(vp_tree, sc_tree)``, which gives the boundary-wrapped body

        fn(params, opt_state, round_idx, base_key, x, y, num_samples,
           num_steps, uid, weight, vparams, server_c, true_n, *extras)
        -> (new_params, new_opt_state, new_round, metrics, new_vparams,
            new_server_c)

    for per-client state shaped like ``vp_tree`` and a server control like
    ``sc_tree`` (``None`` where there is none). The compiled module is named
    after the jitted function and its parameters after the arguments, so
    all three are ``round_step`` with the signatures they always had."""
    if controlled:
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def round_step(state: ServerState, control: ControlState,
                       x, y, num_samples, num_steps, uid, weight, true_n,
                       *extras):
            *new, metrics, new_ci, new_sc = make_fn(
                control.client_controls, control.server_control
            )(
                state.params, state.opt_state, state.round_idx,
                state.base_key, x, y, num_samples, num_steps, uid,
                weight, control.client_controls, control.server_control,
                true_n, *extras,
            )
            return (next_state(state, *new), metrics,
                    ControlState(client_controls=new_ci,
                                 server_control=new_sc))
    elif personalized:
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def round_step(state: ServerState, personal: PersonalState,
                       x, y, num_samples, num_steps, uid, weight,
                       *extras):
            *new, metrics, new_vp, _ = make_fn(personal.params, None)(
                state.params, state.opt_state, state.round_idx,
                state.base_key, x, y, num_samples, num_steps, uid,
                weight, personal.params, None, jnp.float32(0.0),
                *extras,
            )
            return (next_state(state, *new), metrics,
                    PersonalState(params=new_vp))
    else:
        fn = make_fn(None, None)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def round_step(state: ServerState, x, y, num_samples, num_steps,
                       uid, weight, *extras):
            *new, metrics, _, _ = fn(
                state.params, state.opt_state, state.round_idx,
                state.base_key, x, y, num_samples, num_steps, uid, weight,
                None, None, jnp.float32(0.0), *extras,
            )
            return next_state(state, *new), metrics

    return round_step
