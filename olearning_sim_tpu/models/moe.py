"""Mixture-of-Experts layers and the toy text family built on one of them.

Two expert layers live here, and they share nothing:

- :class:`DroplessMoE` — the routed expert layer of the ``lfm2``,
  ``kimi_linear`` and ``nemotron_h`` families (``models/lfm2.py``;
  ``models/kimi_linear.py`` and ``models/nemotron_h.py`` add a shared expert
  beside it): a sigmoid router over the model's PUBLISHED number
  of experts, top-k selection steered by a bias that never enters the
  weights, experts of one of two forms (gated SwiGLU, three matrices, the
  default; or squared ReLU without a gate, two), and no capacity: every
  (token, slot) assignment
  to an expert this layer holds is computed, whatever the imbalance. The
  layer is TOLD which experts it holds (``held``): it routes over all of
  them and returns the partial sum its own experts give, which is what one
  chip of an expert-parallel deployment computes before the exchange.
  The assignments are sorted by held expert and worked **in windows of C
  sorted rows** (:func:`window_rows`: twice what an even router would send
  the held experts, in whole :data:`GROUPED_ROW_BLOCK`s), every window, the
  first too, inside one dynamic-trip ``lax.while_loop``
  (:func:`_expert_windows`) with a backward loop of its own: the arrays a
  trip builds have C rows where the layer's used to have one an assignment
  (an eighth to a thirty-second of those are to held experts in the
  benchmark's cells), and a window is no capacity: a layer whose held
  experts draw more than C rows takes another trip and stays exact.
- :class:`SwitchFFN` — a top-1, softmax, GELU toy with a STATIC capacity
  that drops overflow tokens through the residual, kept because
  ``tests/test_expert_parallel.py`` and the ``moe_text`` family
  (:class:`MoETextTransformer`, 256 wide) exercise the ``ep`` mesh axis
  with it (:mod:`olearning_sim_tpu.parallel.expert_parallel`). Nothing in
  the benchmark runs it. Its routing is one-hot einsums with static shapes;
  its Switch load-balancing loss (num_experts * sum_e f_e * P_e) is sown
  into ``intermediates`` as ``aux_loss`` and picked up by ``build_fedcore``
  and ``ep_train_step``.

Per-expert weights of both carry a leading expert axis and names that start
with ``expert_``; ``ep_param_specs`` shards that axis over ``ep``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from olearning_sim_tpu.models.decoder_parts import TRIPS
from olearning_sim_tpu.models.registry import ModelSpec, register_model

BIAS_INIT_SCALE = 0.01


# The TPU compiler's grouped-matmul kernel tiles a width that is a multiple
# of this by it, and any other width by 128.
GROUPED_TILE = 512
# Rows the expert layer's windows are counted in (:func:`window_rows`), and
# rows the squared-ReLU form's grouped products are given at a time
# (:func:`_whole_blocks`).
GROUPED_ROW_BLOCK = 4096


def _hidden_rows(gated, xs, ws, sizes):
    """The hidden rows of rows ``xs`` grouped by expert, ``silu(W1 x) * W3
    x`` (``gated``: ``ws`` is ``w1, w3``) or ``relu(W1 x)^2`` (``w1``):
    group g is the next ``sizes[g]`` rows and uses ``w*[g]``. Rows past the
    groups are not multiplied (what comes back in them is the kernel's to
    leave: the caller masks them)."""
    a = jax.lax.ragged_dot(xs, ws[0], sizes)
    if gated:
        return jax.nn.silu(a) * jax.lax.ragged_dot(xs, ws[1], sizes)
    a = jax.nn.relu(a)
    return a * a


def _padded_width(w1, w2):
    """The two-matrix form's weights with their hidden width padded with
    zero columns of ``w1`` and zero rows of ``w2`` up to a multiple of
    :data:`GROUPED_TILE`: ``relu(0)^2 = 0`` through zero rows adds exact
    zeros to the sums, and a 1,856-wide expert's products take a fraction
    of the time a row (128-wide tiles are 4 x the kernel's steps; PERF.md
    section 6, PR 38)."""
    pad = -w1.shape[-1] % GROUPED_TILE
    return (jnp.pad(w1, ((0, 0), (0, 0), (0, pad))),
            jnp.pad(w2, ((0, 0), (0, pad), (0, 0))))


def _whole_blocks(sizes, rows):
    """The groups' sizes with the last group lengthened to the next multiple
    of :data:`GROUPED_ROW_BLOCK`, never past ``rows`` (one window's): what
    the two-matrix form's grouped products run over.

    The rows that follow the groups are zeros (the caller's gather fills
    them), and what the last expert makes of them is zero and is never added
    to a token, so values and gradients are the same. What changes is the
    time: the kernel's follows the rows it is given, the held experts' load
    follows the seeded weights (0.15-0.27 of a step's assignments over three
    layers, seed to seed), and a round's time followed it by 1.2% where 1%
    is the bound; in blocks it does not, as long as a layer's load stays
    under one block, for 2% of the round."""
    used = sizes.sum()
    return sizes.at[-1].add(jnp.minimum(
        -used % GROUPED_ROW_BLOCK, rows - used))


def window_rows(assignments: int, held: int, experts: int) -> int:
    """C, the sorted rows an expert layer takes a trip: twice the rows an
    even router sends the held experts (``assignments * held / experts``),
    in whole :data:`GROUPED_ROW_BLOCK`s, and never more than there are
    assignments. Twice, because the seeded routers are not even (a 256-wide
    one sends a held expert up to 6.4 times its mean, a layer's held share
    moves 0.15-0.27 around 0.19 seed to seed: PERF.md section 6, PRs 34
    and 38), and a layer-step that draws more only takes another trip."""
    even = -(-assignments * held // experts)
    return min(-(-2 * even // GROUPED_ROW_BLOCK) * GROUPED_ROW_BLOCK,
               assignments)


def _window(gated, trip, rows, slots, perm, sizes):
    """Rows ``trip * rows`` onward of the sorted order, ``rows`` of them:
    the token each row is (out of range past the held groups' end, so that a
    gather fills zeros there and a scatter drops), the (token, slot)
    assignment it is (out of range likewise; ``slots`` a token), which rows
    are inside the held groups, the groups' sizes clipped to the window,
    and the sizes the grouped products run over (those, in whole blocks
    where the form is the two-matrix one)."""
    with jax.named_scope("moe.dispatch"):
        first = trip * rows
        ends = jnp.cumsum(sizes)
        row = first + jnp.arange(rows, dtype=jnp.int32)
        held = row < ends[-1]
        assigned = jnp.where(
            held, jnp.take(perm, row, mode="clip"), perm.shape[0])
        inside = jnp.diff(jnp.clip(ends, first, first + rows), prepend=first)
        groups = inside if gated else _whole_blocks(inside, rows)
    return assigned // slots, assigned, held, inside, groups


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _expert_windows(gated, hidden, dtype, rows, x, weights, perm, sizes, ws):
    """The held experts' weighted sum by token, float32 ``[S, W]``, from the
    tokens ``x [S, W]``, their routing weights ``[S, K]``, the sorted order
    of the (token, slot) assignments (``perm``: sorted row r is assignment
    ``perm[r]``, token ``perm[r] // K``; the held groups first, ``sizes``
    rows each) and the float32 expert matrices ``ws``; and beside it two
    counts, the trips taken and the rows they covered.

    One ``lax.while_loop`` over windows of ``rows`` sorted rows, while a
    window starts inside the held groups: a trip gathers its rows' tokens,
    runs the grouped products over the groups' parts inside the window,
    weights the results and adds them to their tokens. No array here has a
    row an assignment: what a trip touches is ``rows`` long, and a layer
    whose held experts drew nothing takes no trip. ``lax.while_loop`` has
    no reverse-mode rule, so the backward pass is a loop of its own over the
    same windows (:func:`_expert_windows_bwd`)."""
    return _expert_windows_fwd(gated, hidden, dtype, rows, x, weights, perm,
                               sizes, ws)[0]


def _expert_windows_fwd(gated, hidden, dtype, rows, x, weights, perm, sizes,
                        ws):
    local = sizes.sum()
    with jax.named_scope("moe.experts"):
        ws = tuple(w.astype(dtype) for w in ws)
        ws = ws if gated else _padded_width(*ws)

    def trip(carry):
        n, y, covered = carry
        token, assigned, held, inside, groups = _window(
            gated, n, rows, weights.shape[1], perm, sizes)
        with jax.named_scope("moe.dispatch"):
            xs = jnp.take(x, token, axis=0, mode="fill",
                          fill_value=0).astype(dtype)
        with jax.named_scope("moe.experts"):
            ye = jax.lax.ragged_dot(
                _hidden_rows(gated, xs, ws[:-1], groups), ws[-1], groups)
        with jax.named_scope("moe.combine"):
            weight = jnp.take(weights.reshape(-1), assigned, mode="fill",
                              fill_value=0)
            # Rows past the groups hold what the kernel left there.
            y = y.at[token].add(jnp.where(
                held[:, None], ye.astype(jnp.float32) * weight[:, None], 0),
                mode="drop")
        return n + 1, y, covered + inside.sum()

    n, y, covered = jax.lax.while_loop(
        lambda carry: carry[0] * rows < local, trip,
        (jnp.int32(0), jax.lax.full_like(x, 0, jnp.float32),
         jax.lax.full_like(local, 0)))
    return (y, jnp.stack([n, covered])), (x, weights, perm, sizes, ws)


def _expert_windows_bwd(gated, hidden, dtype, rows, res, cotangents):
    """A second dynamic-trip loop over the same windows: a trip computes its
    window's hidden rows again from the tokens and pulls the window's
    cotangent back through them, adding into float32 sums for the tokens,
    the routing weights and the expert matrices.

    What a trip differentiates is the forward's sum with a row's weight
    moved inside the last product, onto the hidden row (that product is
    linear in it, so the two are one function): the weight's cotangent then
    comes off the hidden width, sum of hidden x its cotangent, and the
    window's results, which only the weight's cotangent in the forward's
    own form would read, are not built again: eleven grouped products a
    layer-step in the gated form, as before the windows, where twelve cost
    2.2% of the round in nemotron's cell (PERF.md section 6, PR 47)."""
    x, weights, perm, sizes, ws = res
    # The sum's cotangent at the width the products take it (a caller that
    # casts the sum to ``dtype`` sends one that is exact there).
    g = cotangents[0].astype(dtype)
    local = sizes.sum()
    H, W = ws[0].shape[:2]
    leaves = [(H, W, hidden)] * (len(ws) - 1) + [(H, hidden, W)]

    def trip(carry):
        n, dx, dweights, dws = carry
        token, assigned, held, _, groups = _window(
            gated, n, rows, weights.shape[1], perm, sizes)
        with jax.named_scope("moe.dispatch"):
            xs = jnp.take(x, token, axis=0, mode="fill",
                          fill_value=0).astype(dtype)
        with jax.named_scope("moe.combine"):
            weight = jnp.take(weights.reshape(-1), assigned, mode="fill",
                              fill_value=0)
            gs = jnp.take(g, token, axis=0, mode="fill", fill_value=0)

        def weighted(xs, weight, ws):
            h = _hidden_rows(gated, xs, ws[:-1], groups)
            return jax.lax.ragged_dot(
                (weight[:, None] * h).astype(dtype), ws[-1], groups)

        with jax.named_scope("moe.experts"):
            dxs, dweight, dw = jax.vjp(weighted, xs, weight, ws)[1](gs)
            # Out of the padded width as they are added: the sums are as
            # wide as the leaves.
            dws = tuple(
                total + d[:, :total.shape[1], :total.shape[2]].astype(
                    jnp.float32) for total, d in zip(dws, dw))
        with jax.named_scope("moe.combine"):
            dweights = dweights.at[assigned].add(
                jnp.where(held, dweight, 0), mode="drop")
        with jax.named_scope("moe.dispatch"):
            dx = dx.at[token].add(jnp.where(
                held[:, None], dxs.astype(jnp.float32), 0), mode="drop")
        return n + 1, dx, dweights, dws

    _, dx, dweights, dws = jax.lax.while_loop(
        lambda carry: carry[0] * rows < local, trip,
        (jnp.int32(0), jax.lax.full_like(x, 0, jnp.float32),
         jax.lax.full_like(weights, 0).reshape(-1),
         tuple(jax.lax.full_like(w, 0, jnp.float32, shape=shape)
               for w, shape in zip(ws, leaves))))
    return (dx.astype(x.dtype), dweights.reshape(weights.shape), None, None,
            dws)


_expert_windows.defvjp(_expert_windows_fwd, _expert_windows_bwd)


class DroplessMoE(nn.Module):
    """Dropless top-k routed expert layer that is told which experts it
    holds.

    ``s = sigmoid(W_g h)`` over all ``num_experts`` (the published count:
    the router is never cut), in float32; the ``top_k`` experts of a token
    are those of ``top_k(s + expert_bias)``; their weights are the selected
    ``s`` (never the biased score), divided by their sum + 1e-6 when
    ``norm_topk_prob``, times ``routed_scaling_factor``; expert
    ``e(h) = W2(silu(W1 h) * W3 h)`` (``gated``, the default: leaves
    ``expert_w1``, ``expert_w3``, ``expert_w2``) or, without a gate,
    ``e(h) = W2(relu(W1 h)^2)`` (``expert_w1``, ``expert_w2``: no third
    matrix, two grouped products). The layer returns the weighted sum
    over the selected experts that are in ``held`` and nothing for the
    others: with ``held`` = every id that is the whole layer, with a share
    of them it is that share's part of the sum, and the parts of disjoint
    shares add up to the whole (``tests/test_lfm2.py``). Nothing stands in
    for the chips that hold the other experts.

    No capacity and no drop: the (token, slot) assignments are sorted by
    the held expert they go to — assignments to experts not held form a
    tail group — and the expert products run as grouped matmuls
    (``jax.lax.ragged_dot``) over the held groups only; the tail is never
    multiplied, read or summed. One expert may get every
    assignment, or none.

    **By row windows, and why a window is no capacity.** Only the int32 and
    float32 routing vectors have a row an assignment (A = tokens x
    ``top_k``). Everything W or ``mlp_dim`` wide is built a window at a
    time, C = :func:`window_rows` sorted rows (a static function of A, the
    held share and :data:`GROUPED_ROW_BLOCK`: 8,192 / 4,096 / 4,096 rows in
    the benchmark's three cells, where a layer-step's held experts draw
    about 4,100 / 1,600 / 1,100 of 32,768 / 24,576 / 32,768), inside one
    ``lax.while_loop`` that runs while a window still starts inside the
    held groups (:func:`_expert_windows`): a trip gathers its rows straight
    from the tokens, clips the groups to the window, runs the grouped
    products, and adds the weighted results to their tokens in a float32
    ``[S, W]`` sum. A capacity would stop after the first window and drop
    the rest; here the rows past C take a second trip (a third, ...), at
    the cost of that trip's time, and the result is the same sum. The
    backward pass is a second loop over the same windows, written here
    because ``lax.while_loop`` has no reverse-mode rule: its residuals are
    the tokens, the routing vectors and the expert matrices as the products
    take them, and a trip computes its window's products again. The trips
    a call took are sown as ``moe_window_trips`` (1 in every call of the
    benchmark's cells: the round's time does not follow the load).

    ``expert_bias`` steers the selection only. It is a float32 parameter
    that takes no gradient (so local training, aggregation and the server
    step leave it as it is); the published rule that updates it from the
    experts' load is not in the model's config and is not implemented. It
    is seeded normal(``BIAS_INIT_SCALE``). What that scale does is a
    configuration's measurement, not a fact of the layer (PERF.md section
    6). ``lfm2_moe_ep8`` (a 64-wide router, 4 slots, 8 held; PR 28): 0.01
    changes some selections, the most loaded held expert gets 1.4 times
    the mean load for 1.25 with no bias, and the share of assignments that
    land on the held experts stays within 2% from seed to seed; at 0.05
    that share swung 8% and the round time with it. ``kimi_linear_ep32``
    (a 256-wide router, 8 slots, 8 held; PR 34): at 0.01 the 8 held
    experts get 2.8-3.4% of a round's assignments (1/32 is 3.1%) and the
    most loaded of them 3.8-6.4 times the mean load (seeded weights: a few
    of 256 experts draw most tokens); the round time follows neither (the
    layer's arrays were sized by slots then and are by the window since PR
    47, not by load: 0.12% over six seeds).
    """

    num_experts: int
    top_k: int
    held: Tuple[int, ...]
    mlp_dim: int
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    dtype: jnp.dtype = jnp.bfloat16
    gated: bool = True

    @nn.compact
    def __call__(self, x):
        B, L, W = x.shape
        E, K, H, M = self.num_experts, self.top_k, len(self.held), self.mlp_dim
        if not 0 < K <= E or len(set(self.held)) != H or not all(
                0 <= e < E for e in self.held):
            raise ValueError(
                f"DroplessMoE: top_k={K}, held={tuple(self.held)} do not fit "
                f"a router over {E} experts")
        S, A = B * L, B * L * K
        xf = x.reshape(S, W)
        gate = self.param("gate", nn.initializers.lecun_normal(), (W, E),
                          jnp.float32)
        bias = self.param(
            "expert_bias", nn.initializers.normal(BIAS_INIT_SCALE),
            (E,), jnp.float32)
        per_expert = nn.initializers.lecun_normal(batch_axis=(0,))
        w1 = self.param("expert_w1", per_expert, (H, W, M), jnp.float32)
        if self.gated:
            w3 = self.param("expert_w3", per_expert, (H, W, M), jnp.float32)
        w2 = self.param("expert_w2", per_expert, (H, M, W), jnp.float32)

        with jax.named_scope("moe.route"):
            scores = jax.nn.sigmoid(jnp.dot(
                xf.astype(jnp.float32), gate,
                precision=jax.lax.Precision.HIGHEST))            # [S, E]
            _, chosen = jax.lax.top_k(
                scores + jax.lax.stop_gradient(bias), K)         # [S, K]
            weights = jnp.take_along_axis(scores, chosen, axis=-1)
            if self.norm_topk_prob:
                weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
            weights = weights * self.routed_scaling_factor

        with jax.named_scope("moe.dispatch"):
            # Expert id -> its group here; H is the tail (not held).
            group_of = np.full((E,), H, np.int32)
            group_of[list(self.held)] = np.arange(H, dtype=np.int32)
            group = jnp.asarray(group_of)[chosen].reshape(A)
            here = group < H
            sizes = (group[:, None] == jnp.arange(H, dtype=jnp.int32)
                     ).sum(0, dtype=jnp.int32)                   # [H]
            # Sorted position r holds assignment perm[r], token perm[r] //
            # K in its slot perm[r] % K: the held groups first, the tail
            # after them, which no window reaches.
            perm = jnp.argsort(group, stable=True).astype(jnp.int32)

        y, (trips, computed) = _expert_windows(
            self.gated, M, self.dtype, window_rows(A, H, E), xf, weights,
            perm, sizes, (w1, w3, w2) if self.gated else (w1, w2))

        # The choices themselves, for whoever asks for the intermediates
        # (scripts/lfm2_routing_agreement.py); training does not.
        self.sow("intermediates", "moe_chosen", chosen)
        # Counted twice on purpose: what the router sent to held experts,
        # and the rows the windows' grouped products covered.
        self.sow("intermediates", "moe_stats", jnp.concatenate([
            jnp.stack([jnp.int32(A), here.sum(dtype=jnp.int32), computed]),
            sizes]))
        self.sow("intermediates", TRIPS, trips)
        return y.reshape(B, L, W).astype(x.dtype)


class SwitchFFN(nn.Module):
    """The toy: top-1 (Switch) routing over ``num_experts`` GELU FFNs,
    weighted by the softmax gate probability, with a static capacity of
    ``capacity_factor * tokens / num_experts`` slots an expert. A token
    over capacity is DROPPED (it rides the residual unchanged); for a layer
    that drops nothing see :class:`DroplessMoE`."""

    num_experts: int
    width: int
    mlp_dim: int
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, valid=None):
        # x: [B, T, W]; valid: [B, T] bool, True = real token. Padding must
        # stay out of routing — pads share one embedding, so they would all
        # argmax to the same expert, eat its static capacity (evicting real
        # tokens through the residual) and skew the load-balance statistics.
        B, T, W = x.shape
        E = self.num_experts
        S = B * T
        cap = max(1, int(self.capacity_factor * S / E))
        xf = x.reshape(S, W)
        vf = (jnp.ones((S,), bool) if valid is None
              else valid.reshape(S))

        # Router in f32 (gate logits are precision-sensitive).
        logits = nn.Dense(E, dtype=jnp.float32, name="gate")(
            xf.astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)              # [S, E]
        expert = jnp.argmax(probs, axis=-1)                  # [S]
        gate_val = jnp.max(probs, axis=-1)                   # [S]

        # Pads contribute no queue entry: their one-hot row is zeroed before
        # the cumsum, so pos_in_expert is -1 for them.
        onehot = (
            jax.nn.one_hot(expert, E, dtype=jnp.int32)
            * vf[:, None].astype(jnp.int32)
        )                                                    # [S, E]
        pos = jnp.cumsum(onehot, axis=0) * onehot            # [S, E], 1-based
        pos_in_expert = pos.sum(axis=-1) - 1                 # [S], -1 for pads
        keep = (pos_in_expert >= 0) & (pos_in_expert < cap)

        # dispatch [S, E, C]: 1 where token s goes to (expert e, slot c);
        # pads and over-capacity tokens ride the residual unchanged.
        dispatch = (
            jax.nn.one_hot(expert, E, dtype=self.dtype)[:, :, None]
            * jax.nn.one_hot(pos_in_expert, cap, dtype=self.dtype)[:, None, :]
            * keep[:, None, None].astype(self.dtype)
        )
        combine = dispatch * gate_val[:, None, None].astype(self.dtype)

        # Gather tokens per expert: [E, C, W] — an einsum, not a scatter.
        xe = jnp.einsum("sec,sd->ecd", dispatch, xf.astype(self.dtype))

        # Per-expert FFN, leading expert axis sharded over ep.
        w1 = self.param(
            "expert_w1", nn.initializers.lecun_normal(), (E, W, self.mlp_dim),
            jnp.float32,
        )
        b1 = self.param(
            "expert_b1", nn.initializers.zeros, (E, 1, self.mlp_dim),
            jnp.float32,
        )
        w2 = self.param(
            "expert_w2", nn.initializers.lecun_normal(), (E, self.mlp_dim, W),
            jnp.float32,
        )
        b2 = self.param(
            "expert_b2", nn.initializers.zeros, (E, 1, W), jnp.float32
        )
        h = jax.nn.gelu(
            jnp.einsum("ecd,edm->ecm", xe, w1.astype(self.dtype))
            + b1.astype(self.dtype)
        )
        ye = (
            jnp.einsum("ecm,emd->ecd", h, w2.astype(self.dtype))
            + b2.astype(self.dtype)
        )
        # Un-dispatch, weighted by the gate.
        y = jnp.einsum("sec,ecd->sd", combine, ye)

        # Switch aux loss over REAL tokens only:
        # E * sum_e (fraction routed to e) * (mean prob e).
        n_valid = jnp.maximum(vf.sum().astype(jnp.float32), 1.0)
        f = onehot.astype(jnp.float32).sum(axis=0) / n_valid  # [E]
        p = (
            probs * vf[:, None].astype(jnp.float32)
        ).sum(axis=0) / n_valid                               # [E]
        self.sow("intermediates", "aux_loss", E * jnp.sum(f * p))

        return y.reshape(B, T, W).astype(x.dtype)


class MoEBlock(nn.Module):
    width: int
    heads: int
    mlp_dim: int
    num_experts: int
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, pad_mask):
        attn_mask = nn.make_attention_mask(pad_mask, pad_mask, dtype=self.dtype)
        y = nn.MultiHeadDotProductAttention(
            num_heads=self.heads, dtype=self.dtype, deterministic=True
        )(x, x, mask=attn_mask)
        x = nn.LayerNorm(dtype=self.dtype)(x + y)
        y = SwitchFFN(
            self.num_experts, self.width, self.mlp_dim,
            self.capacity_factor, self.dtype,
        )(x, valid=pad_mask)
        return nn.LayerNorm(dtype=self.dtype)(x + y)


class MoETextTransformer(nn.Module):
    """Text classifier with Switch-MoE FFNs in every block (same tokenizer
    conventions as the dense text family: int32 tokens, pad_id masked)."""

    vocab_size: int = 30522
    max_len: int = 128
    width: int = 256
    depth: int = 4
    heads: int = 8
    mlp_dim: int = 512
    num_experts: int = 8
    capacity_factor: float = 1.25
    num_classes: int = 2
    pad_id: int = 0
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens):
        pad_mask = tokens != self.pad_id
        emb = nn.Embed(
            self.vocab_size, self.width,
            embedding_init=nn.initializers.normal(stddev=0.02),
            param_dtype=jnp.float32,
        )(tokens)
        pos = self.param(
            "pos_embedding", nn.initializers.normal(stddev=0.02),
            (1, self.max_len, self.width), jnp.float32,
        )
        L = tokens.shape[1]
        x = (emb + pos[:, :L]).astype(self.dtype)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        for _ in range(self.depth):
            x = MoEBlock(
                self.width, self.heads, self.mlp_dim, self.num_experts,
                self.capacity_factor, self.dtype,
            )(x, pad_mask)
        m = pad_mask[..., None].astype(jnp.float32)
        s = (x.astype(jnp.float32) * m).sum(1)
        c = m.sum(1)
        pooled = s / jnp.maximum(c, 1.0)
        return nn.Dense(self.num_classes, dtype=jnp.float32)(pooled)


register_model(
    ModelSpec(
        name="moe_text",
        builder=MoETextTransformer,
        example_input_shape=(64,),
        num_classes=2,
        input_dtype=np.int32,
        defaults={
            "vocab_size": 30522, "max_len": 128, "width": 256, "depth": 4,
            "heads": 8, "mlp_dim": 512, "num_experts": 8, "num_classes": 2,
        },
    )
)
