"""Plain float32 reference for the Nemotron-H next-token model as one chip
of a deployment whose chips share each layer holds it: the forward pass,
the next-token loss of one client's minibatch and its gradient, in
straightforward ``jax.numpy`` at ``highest`` matmul precision. No flax, no
engine code, no chunked scan, no grouped matmul, no sort, no kernels; one
sequence at a time, gradients accumulated, and the parameters kept on the
host between calls, so that it fits beside the runner's state on the chip.

Architecture (``nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16``
config.json, ``model_type`` ``nemotron_h``: one Nemotron-H tower; the
family's public modelling code for what the config does not say): token
embedding, then pre-norm residual layers, each ONE of three things by its
letter in ``hybrid_override_pattern``,

    h = h + f(rms(h))

- ``M``, Mamba-2 (``d_inner`` = the output projection's rows, the heads
  are ``A_log``'s, ``P = d_inner / heads``; the ``G`` groups are the rows
  of the gated norm's scale and the state size ``N`` what the convolution
  is wider than ``d_inner`` by, over ``2 G``): ``[z, xBC, dt] = u W_in``;
  ``xBC = silu(conv(xBC) +
  bias)``, one causal depthwise convolution (taps ``[T, d_inner + 2 G N]``,
  tap ``T - 1`` is "now") over x, B and C together; head ``h`` reads B and
  C of group ``h // (heads / G)``; step size ``D_t = softplus(dt_t +
  dt_bias)`` (no clamp); decay ``a_t = exp(D_t * A)``, ``A = -exp(A_log)``,
  one number a head; **the recurrence itself, token by token**, from ``S_0
  = 0`` (``S``: ``P x N``)

      S_t = a_t S_{t-1} + D_t x_t B_t^T
      y_t = S_t C_t + D x_t

  and ``out = (rms_grouped(y * silu(z)) * w) W_out``: the gate before the
  norm, the norm over ``G`` groups of ``d_inner / G``;
- ``E``: the routed experts — ``s = sigmoid(W_g h)`` over ALL experts of
  the router; the ``TOP_K`` chosen are those of ``top_k(s + expert_bias)``
  (one group: the grouped top-k is the plain one); their weights are the
  chosen ``s`` over their sum + 1e-6, times ``ROUTED_SCALING_FACTOR``;
  ``sum_e weight_e * expert_e(h)``, ``expert(h) = W2(relu(W1 h)^2)``, over
  the chosen experts THAT ARE HELD (``held``: the ids of the stacked expert
  weights, the first ones where not given), computed the dense way: every
  held expert on every token, times a weight that is zero where the token
  did not choose it — plus the shared expert, the same form on every token
  with weight 1, added once;
- ``*``: causal grouped-query attention, ``HEAD_DIM``-wide heads (query
  heads and key/value heads from the projections' widths), no bias, no
  positional embedding, softmax of ``q k^T / sqrt(HEAD_DIM)``, scores kept;
- final RMSNorm, logits against the untied head, over the rows of the
  vocabulary that are held.

Not built, because the published config carries no key of theirs: the
second (denoising) tower, its conditioning on the first and a
block-diffusion objective. This is the tower the config defines, trained by
next-token loss.

Loss of a sequence: the mean over its L - 1 positions of the cross-entropy
of position t's logits against token t + 1. ``loss_and_grad`` returns
``sum_i sw_i * loss_i`` and its gradient; labels are ignored.

Departure from the program, on purpose: everything is float32 (the program
feeds its projections bfloat16 and computes the recurrence in chunks). A
near-tie among the router's scores can therefore be chosen differently here
and there (``chosen_experts`` is for measuring how often).
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops

NAME = "nemotron_h"
# Published constants that are not shapes of the parameter tree.
TOP_K = 6
NORM_TOPK_PROB = True
ROUTED_SCALING_FACTOR = 2.5
NORM_EPS = 1e-5
HEAD_DIM = 128
# Tokens between two kept states of the recurrence's backward pass.
SEGMENT = 64


def run_pattern(model: dict) -> str:
    """The letters of the layers this configuration runs: the published
    layer numbers ``layer_slice`` (counted from 1, both ends in) of
    ``hybrid_override_pattern``."""
    lo, hi = model["layer_slice"]
    return model["hybrid_override_pattern"][lo - 1:hi]


def layers(model: dict) -> List[flops.Layer]:
    """Forward matmul-like layers of one sample (a sequence of
    ``sequence_length`` tokens), for benchmark/flops.py. An expert layer
    counts the expected ``num_experts_per_tok * held / published`` routed
    experts a token (two products an expert) and the shared expert whole,
    attention its causal half of the L x L products, the recurrence its two
    ``P x N`` products a token a head (the rank-one write, ``S C``), the
    head the L - 1 positions the loss reads. The embedding lookup counts
    nothing."""
    L, W = model["sequence_length"], model["hidden_size"]
    Hm, P = model["mamba_num_heads"], model["mamba_head_dim"]
    N, G, T = model["ssm_state_size"], model["n_groups"], model["conv_kernel"]
    d_inner, conv_dim = Hm * P, Hm * P + 2 * G * N
    H, Hk, D = (model["num_attention_heads"], model["num_key_value_heads"],
                model["head_dim"])
    M, Ms = (model["moe_intermediate_size"],
             model["moe_shared_expert_intermediate_size"])
    share = (model["num_experts_per_tok"] * model["n_routed_experts"]
             / model["n_routed_experts_published"])
    out = []
    for i, kind in enumerate(run_pattern(model)):
        if kind == "M":
            out += [flops.dense(f"l{i}.in_proj", L, W, d_inner + conv_dim + Hm),
                    flops.Layer(f"l{i}.conv_taps", float(L * conv_dim * T)),
                    flops.Layer(f"l{i}.ssd", float(L * Hm * 2 * P * N)),
                    flops.dense(f"l{i}.ssd_out", L, d_inner, W)]
        elif kind == "*":
            out += [flops.dense(f"l{i}.qkv", L, W, (H + 2 * Hk) * D),
                    flops.Layer(f"l{i}.scores", L * (L + 1) / 2 * H * D),
                    flops.Layer(f"l{i}.context", L * (L + 1) / 2 * H * D),
                    flops.dense(f"l{i}.attn_out", L, H * D, W)]
        else:
            out += [flops.dense(f"l{i}.router", L, W,
                                model["n_routed_experts_published"]),
                    flops.Layer(f"l{i}.experts", L * share * 2 * W * M),
                    flops.Layer(f"l{i}.shared_expert",
                                float(L * model["n_shared_experts"]
                                      * 2 * W * Ms))]
    out.append(flops.dense("head", L - 1, W, model["vocab_size"]))
    return out


def _rms(x, scale):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + NORM_EPS) * scale


def _conv_silu(x, taps, bias):
    """x [L, D] through the causal depthwise ``taps`` [T, D], plus the
    bias, then SiLU."""
    T, L = taps.shape[0], x.shape[0]
    x = jnp.concatenate([jnp.zeros((T - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(taps[j] * x[j:j + L] for j in range(T)) + bias)


def ssd(x, dt, A, B, C):
    """The recurrence as written, one token after another: x [L, H, P], dt
    [L, H], A [H], B, C [L, H, N] (each head's own group's) -> y [L, H, P]
    (without the skip ``D x``). The arithmetic is a token's step and
    nothing else; the walk over the sequence is cut into ``SEGMENT``-token
    stretches for the backward pass's memory only (it keeps the state that
    enters a stretch and walks the stretch again: 2,048 kept states of 64
    heads are 4.3 GB a layer)."""

    def step(S, xs):                                    # S [H, P, N]
        x_t, dt_t, B_t, C_t = xs
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t)

    L = x.shape[0]
    stretches = tuple(
        jnp.pad(a, ((0, -L % SEGMENT),) + ((0, 0),) * (a.ndim - 1)
                ).reshape((-1, SEGMENT) + a.shape[1:])
        for a in (x, dt, B, C))
    S0 = jnp.zeros((x.shape[1], x.shape[2], B.shape[2]), x.dtype)
    y = jax.lax.scan(jax.checkpoint(lambda S, xs: jax.lax.scan(step, S, xs)),
                     S0, stretches)[1]
    return y.reshape((-1,) + y.shape[2:])[:L]


def mamba2(p: Dict[str, jax.Array], prefix: str, u):
    """u [L, W] -> [L, W]."""
    L = u.shape[0]
    H, d_inner = p[prefix + "A_log"].shape[0], p[prefix + "out_proj"].shape[0]
    groups = p[prefix + "norm"].shape[0]
    state = (p[prefix + "conv"].shape[1] - d_inner) // (2 * groups)
    z, xBC, dt = jnp.split(u @ p[prefix + "in_proj"],
                           [d_inner, 2 * d_inner + 2 * groups * state], -1)
    xBC = _conv_silu(xBC, p[prefix + "conv"], p[prefix + "conv_bias"])
    x, B, C = jnp.split(xBC, [d_inner, d_inner + groups * state], -1)
    x = x.reshape(L, H, -1)
    # Head h reads group h // (H / groups).
    B, C = (jnp.repeat(a.reshape(L, groups, state), H // groups, axis=1)
            for a in (B, C))
    dt = jax.nn.softplus(dt + p[prefix + "dt_bias"])
    y = ssd(x, dt, -jnp.exp(p[prefix + "A_log"]), B, C)
    y = (y + p[prefix + "D"][:, None] * x).reshape(L, d_inner)
    y = (y * jax.nn.silu(z)).reshape(L, groups, -1)
    y = _rms(y, p[prefix + "norm"])
    return y.reshape(L, d_inner) @ p[prefix + "out_proj"]


def attention(p: Dict[str, jax.Array], prefix: str, x):
    """x [L, W] -> [L, W]; the heads follow from the projections' widths."""
    L, D = x.shape[0], HEAD_DIM
    q = (x @ p[prefix + "q_proj"]).reshape(L, -1, D)
    k = (x @ p[prefix + "k_proj"]).reshape(L, -1, D)
    v = (x @ p[prefix + "v_proj"]).reshape(L, -1, D)
    rep = q.shape[1] // k.shape[1]          # query heads a key/value head
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(D)
    scores = jnp.where(np.tril(np.ones((L, L), bool)), scores,
                       jnp.finfo(jnp.float32).min)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return ctx.reshape(L, -1) @ p[prefix + "out_proj"]


def relu2(x, w1, w2):
    a = jax.nn.relu(x @ w1)
    return (a * a) @ w2


def route(p: Dict[str, jax.Array], prefix: str, x, top_k: int = TOP_K
          ) -> Tuple[jax.Array, jax.Array]:
    """([T, k] chosen expert ids, [T, k] their weights)."""
    scores = jax.nn.sigmoid(x @ p[prefix + "gate"])
    _, chosen = jax.lax.top_k(scores + p[prefix + "expert_bias"], top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if NORM_TOPK_PROB:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    return chosen, weights * ROUTED_SCALING_FACTOR


def experts(p: Dict[str, jax.Array], prefix: str, x,
            held: Optional[Sequence[int]] = None, top_k: int = TOP_K):
    """The routed expert layer's part that the held experts give."""
    w1, w2 = p[prefix + "expert_w1"], p[prefix + "expert_w2"]
    held = np.arange(w1.shape[0]) if held is None else np.asarray(held)
    chosen, weights = route(p, prefix, x, top_k)
    # [T, H]: the weight token t gives held expert j (0 where not chosen).
    mix = (weights[:, :, None]
           * (chosen[:, :, None] == held[None, None, :])).sum(1)
    hidden = jax.nn.relu(jnp.einsum("tw,hwm->htm", x, w1))
    return jnp.einsum("th,htw->tw", mix,
                      jnp.einsum("htm,hmw->htw", hidden * hidden, w2))


def _depth(params) -> int:
    return sum(1 for k in params
               if k.startswith("layers_") and k.endswith("/norm/scale"))


def forward(params: Dict[str, jax.Array], tokens,
            held: Optional[Sequence[int]] = None, top_k: int = TOP_K,
            chosen_out: Optional[list] = None):
    """tokens [L] int32 -> logits [L, V] over the held vocabulary rows.
    ``chosen_out`` gets each expert layer's [L, top_k] chosen ids."""
    h = params["embed/embedding"][tokens]
    for i in range(_depth(params)):
        pre = f"layers_{i}/"
        x = _rms(h, params[pre + "norm/scale"])
        if pre + "mamba/A_log" in params:
            h = h + mamba2(params, pre + "mamba/", x)
        elif pre + "attn/q_proj" in params:
            h = h + attention(params, pre + "attn/", x)
        else:
            if chosen_out is not None:
                chosen_out.append(route(params, pre + "moe/", x, top_k)[0])
            h = (h + experts(params, pre + "moe/", x, held, top_k)
                 + relu2(x, params[pre + "shared/w1"],
                         params[pre + "shared/w2"]))
    return _rms(h, params["final_norm/scale"]) @ params["head"]


def sequence_loss(params, tokens, held=None, top_k: int = TOP_K):
    logits = forward(params, tokens, held, top_k)
    logp = jax.nn.log_softmax(logits[:-1], -1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()


@jax.jit
def _sequence_value_and_grad(params, tokens, weight):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: weight * sequence_loss(p, tokens))(params)


@contextlib.contextmanager
def _leaving_nothing_in_the_compile_cache():
    """What compiles inside is not written to JAX's persistent compilation
    cache: where the cache is capped, this program's entry (every product
    at ``highest``, layer by layer) would push out the programs a run is
    timed on, its own cell's and the other cells', and a check that
    compiles it anew takes longer and moves no metric."""
    key = "jax_persistent_cache_min_compile_time_secs"
    kept = getattr(jax.config, key)
    jax.config.update(key, float("inf"))
    try:
        yield
    finally:
        jax.config.update(key, kept)


@jax.jit
def _chosen(params, tokens):
    with jax.default_matmul_precision("highest"):
        seen: list = []
        forward(params, tokens, chosen_out=seen)
        return jnp.stack(seen)


def chosen_experts(params: Dict[str, jax.Array], tokens) -> np.ndarray:
    """[expert layers, L, TOP_K] ids the reference's routers choose for one
    sequence: what the program's choices are held against when the share
    of (token, slot) choices that agree is measured."""
    return np.asarray(_chosen(params, jnp.asarray(tokens, jnp.int32)))


def loss_and_grad(params: Dict[str, np.ndarray], x, y, sw
                  ) -> Tuple[float, Dict[str, np.ndarray]]:
    """loss = sum_i sw_i * loss(sequence x_i) and its gradient; ``y`` (the
    generator's topics) is not read. The parameters go to the device for
    this call and the gradient comes back to the host; one sequence at a
    time, the gradient accumulated leaf by leaf so that two copies are
    never whole."""
    x, sw = np.asarray(x), np.asarray(sw, np.float32)
    on_chip = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    total, grads = 0.0, None
    with _leaving_nothing_in_the_compile_cache():
        for i in np.nonzero(sw)[0]:
            loss, g = _sequence_value_and_grad(
                on_chip, jnp.asarray(x[i], jnp.int32), jnp.float32(sw[i]))
            total += float(loss)
            if grads is None:
                grads = g
            else:
                for k in grads:
                    grads[k] = grads[k] + g.pop(k)
    return total, {k: np.asarray(v) for k, v in grads.items()}


def prepare(params: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Flat program-layout params -> float32 arrays ON THE HOST: the six
    copies of 528 M parameters a reference round makes (the start, the
    carry, a step's gradient, a sequence's gradient, a client's delta, the
    mean delta) do not fit on the chip beside the runner's state, so the
    round's own arithmetic (``fedround.py``) runs in numpy and
    :func:`loss_and_grad` alone uses the device."""
    return {k: np.asarray(v, np.float32) for k, v in params.items()}
