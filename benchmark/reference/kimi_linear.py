"""Plain float32 reference for the Kimi-Linear next-token model as one chip
of a deployment whose chips share each layer holds it: the forward pass,
the next-token loss of one client's minibatch and its gradient, in
straightforward ``jax.numpy`` at ``highest`` matmul precision. No flax, no
engine code, no chunked scan, no triangular solve, no grouped matmul, no
sort, no kernels; one sequence at a time, gradients accumulated, and the
parameters kept on the host between calls, so that it fits beside the
runner's state on the chip.

Architecture (``moonshotai/Kimi-Linear-48B-A3B-Instruct`` config.json,
``model_type`` ``kimi_linear``; the family's public modelling code for what
the config does not say): token embedding, then pre-norm residual layers

    h = h + mixer(rms(h));   h = h + ffn(rms(h))

- ``kda`` mixer, a head of ``d`` keys and values (``d`` = the length of the
  output norm's scale; the heads are ``A_log``'s): ``q~, k~, v~ = x W_q, x
  W_k, x W_v``, each through its own causal depthwise convolution (taps
  ``[T, heads * d]``, tap ``T - 1`` is "now") and SiLU; ``q = l2norm(q~) /
  sqrt(d)``, ``k = l2norm(k~)`` (``L2_EPS`` under the root); log decay of a
  channel ``g = -exp(A_log[head]) * softplus((x W_fa) W_fb + dt_bias)``;
  write strength of a head ``beta = sigmoid(x W_b)``; **the recurrence
  itself, token by token**, from ``S_0 = 0`` (``S``: keys x values)

      S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  and ``out = concat(rms_d(o; w_o) * sigmoid((x W_ga) W_gb)) W_o``;
- ``mla`` mixer (no rotary embedding, no low rank on q): ``q = x W_q`` ->
  heads x (nope + rope); ``[c, k_r] = x W_kva`` (the latent, as wide as
  the latent norm's scale, and a ``rope``-wide key part shared by all heads
  and used as it is); ``[k_n, v] = rms(c) W_kvb`` a head; ``k = [k_n,
  k_r]``; causal softmax of ``q k^T / sqrt(nope + rope)``, scores kept;
  ``out = concat(probs v) W_o``;
- ffn of a leading dense layer: ``W2(silu(W1 h) * W3 h)``;
- ffn of the others: the routed experts — ``s = sigmoid(W_g h)`` over ALL
  experts of the router; the ``TOP_K`` chosen are those of ``top_k(s +
  expert_bias)`` (one group: the grouped top-k is the plain one); their
  weights are the chosen ``s`` over their sum + 1e-6, times
  ``ROUTED_SCALING_FACTOR``; ``sum_e weight_e * expert_e(h)`` over the
  chosen experts THAT ARE HELD (``held``: the ids of the stacked expert
  weights, the first ones where not given), computed the dense way: every
  held expert on every token, times a weight that is zero where the token
  did not choose it — plus the shared expert, the same SwiGLU on every
  token with weight 1, added once;
- final RMSNorm, logits against the untied head, over the rows of the
  vocabulary that are held.

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

NAME = "kimi_linear"
# Published constants that are not shapes of the parameter tree.
TOP_K = 8
NORM_TOPK_PROB = True
ROUTED_SCALING_FACTOR = 2.446
NORM_EPS = 1e-5
L2_EPS = 1e-6
# Tokens between two kept states of the recurrence's backward pass.
SEGMENT = 64


def run_layer_types(model: dict) -> List[str]:
    """The mixers of the layers this configuration runs: the published
    layer numbers ``layer_slice`` (counted from 1, both ends in), ``kda``
    where ``linear_attn_config.kda_layers`` lists the layer and ``mla``
    where ``full_attn_layers`` does."""
    lo, hi = model["layer_slice"]
    lists = model["linear_attn_config"]
    kinds = {**{i: "kda" for i in lists["kda_layers"]},
             **{i: "mla" for i in lists["full_attn_layers"]}}
    return [kinds[i] for i in range(lo, hi + 1)]


def layers(model: dict) -> List[flops.Layer]:
    """Forward matmul-like layers of one sample (a sequence of
    ``sequence_length`` tokens), for benchmark/flops.py. An expert layer
    counts the expected ``num_experts_per_token * held / published`` routed
    experts a token and the shared expert whole, attention its causal half
    of the L x L products, the recurrence its three ``d x d`` products a
    token a head (``k^T S``, the rank-one write, ``S^T q``), the head the
    L - 1 positions the loss reads. The embedding lookup counts nothing."""
    L, W = model["sequence_length"], model["hidden_size"]
    kda = model["linear_attn_config"]
    Hk, d, T = kda["num_heads"], kda["head_dim"], kda["short_conv_kernel_size"]
    H, R = model["num_attention_heads"], model["kv_lora_rank"]
    Dn, Dr, Dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    share = (model["num_experts_per_token"] * model["num_experts"]
             / model["num_experts_published"])
    out = []
    for i, kind in enumerate(run_layer_types(model)):
        if kind == "kda":
            out += [flops.dense(f"l{i}.qkv", L, W, 3 * Hk * d),
                    flops.Layer(f"l{i}.conv_taps", float(L * 3 * Hk * d * T)),
                    flops.dense(f"l{i}.decay_gate", L, W + Hk * d, d),
                    flops.dense(f"l{i}.beta", L, W, Hk),
                    flops.Layer(f"l{i}.delta_rule", float(L * Hk * 3 * d * d)),
                    flops.dense(f"l{i}.out_gate", L, W + Hk * d, d),
                    flops.dense(f"l{i}.kda_out", L, Hk * d, W)]
        else:
            out += [flops.dense(f"l{i}.q", L, W, H * (Dn + Dr)),
                    flops.dense(f"l{i}.kv_a", L, W, R + Dr),
                    flops.dense(f"l{i}.kv_b", L, R, H * (Dn + Dv)),
                    flops.Layer(f"l{i}.scores",
                                L * (L + 1) / 2 * H * (Dn + Dr)),
                    flops.Layer(f"l{i}.context", L * (L + 1) / 2 * H * Dv),
                    flops.dense(f"l{i}.attn_out", L, H * Dv, W)]
        if i < model["first_k_dense_replace"]:
            M = model["intermediate_size"]
            out += [flops.dense(f"l{i}.mlp_in", L, W, 2 * M),
                    flops.dense(f"l{i}.mlp_out", L, M, W)]
        else:
            M = model["moe_intermediate_size"]
            out += [flops.dense(f"l{i}.router", L, W,
                                model["num_experts_published"]),
                    flops.Layer(f"l{i}.experts", L * share * 3 * W * M),
                    flops.Layer(f"l{i}.shared_expert",
                                float(L * model["num_shared_experts"]
                                      * 3 * W * M))]
    out.append(flops.dense("head", L - 1, W, model["vocab_size"]))
    return out


def _rms(x, scale):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + NORM_EPS) * scale


def _l2norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def _conv_silu(x, taps):
    """x [L, D] through the causal depthwise ``taps`` [T, D], then SiLU."""
    T, L = taps.shape[0], x.shape[0]
    x = jnp.concatenate([jnp.zeros((T - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(taps[j] * x[j:j + L] for j in range(T)))


def delta_rule(q, k, v, g, beta):
    """The recurrence as written, one token after another: q, k, g
    [L, H, d], v [L, H, dv], beta [L, H] -> o [L, H, dv]. The arithmetic
    is a token's step and nothing else; the walk over the sequence is cut
    into ``SEGMENT``-token stretches for the backward pass's memory only
    (it keeps the state that enters a stretch and walks the stretch again:
    2,048 kept states of 32 heads are 4.3 GB a layer), the tail padded with
    tokens that write and decay nothing."""

    def step(S, x):                                     # S [H, d, dv]
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    L = q.shape[0]
    stretches = tuple(
        jnp.pad(x, ((0, -L % SEGMENT),) + ((0, 0),) * (x.ndim - 1)
                ).reshape((-1, SEGMENT) + x.shape[1:])
        for x in (q, k, v, g, beta))
    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), q.dtype)
    o = jax.lax.scan(jax.checkpoint(lambda S, xs: jax.lax.scan(step, S, xs)),
                     S0, stretches)[1]
    return o.reshape((-1,) + o.shape[2:])[:L]


def kda(p: Dict[str, jax.Array], prefix: str, x):
    """x [L, W] -> [L, W]."""
    L = x.shape[0]
    H, d = p[prefix + "A_log"].shape[0], p[prefix + "o_norm"].shape[0]
    q, k, v = (_conv_silu(x @ p[f"{prefix}{r}_proj"],
                          p[f"{prefix}{r}_conv"]).reshape(L, H, d)
               for r in "qkv")
    q, k = _l2norm(q) / np.sqrt(d), _l2norm(k)
    g = -jnp.exp(p[prefix + "A_log"])[:, None] * jax.nn.softplus(
        (x @ p[prefix + "f_a"]) @ p[prefix + "f_b"] + p[prefix + "dt_bias"]
    ).reshape(L, H, d)
    beta = jax.nn.sigmoid(x @ p[prefix + "b_proj"])
    o = _rms(delta_rule(q, k, v, g, beta), p[prefix + "o_norm"])
    gate = jax.nn.sigmoid((x @ p[prefix + "g_a"]) @ p[prefix + "g_b"])
    return (o.reshape(L, H * d) * gate) @ p[prefix + "out_proj"]


def mla(p: Dict[str, jax.Array], prefix: str, x):
    """x [L, W] -> [L, W]. The sizes are the leaves': the latent's width is
    its norm's, the shared key part what ``kv_a`` gives beyond it, and the
    heads follow from the three projections' widths."""
    L = x.shape[0]
    R = p[prefix + "kv_norm/scale"].shape[0]
    Dr = p[prefix + "kv_a"].shape[1] - R
    q_cols, kvb_cols = p[prefix + "q_proj"].shape[1], p[prefix + "kv_b"].shape[1]
    v_cols = p[prefix + "out_proj"].shape[0]            # heads * Dv
    H = (q_cols - (kvb_cols - v_cols)) // Dr            # heads * Dr over Dr
    Dn = (kvb_cols - v_cols) // H
    q = (x @ p[prefix + "q_proj"]).reshape(L, H, Dn + Dr)
    c, k_r = jnp.split(x @ p[prefix + "kv_a"], [R], axis=-1)
    k_n, v = jnp.split(
        (_rms(c, p[prefix + "kv_norm/scale"]) @ p[prefix + "kv_b"]
         ).reshape(L, H, -1), [Dn], axis=-1)
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r[:, None, :], (L, H, Dr))], -1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(Dn + Dr)
    scores = jnp.where(np.tril(np.ones((L, L), bool)), scores,
                       jnp.finfo(jnp.float32).min)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return ctx.reshape(L, -1) @ p[prefix + "out_proj"]


def swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


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
    w1, w3, w2 = (p[prefix + n] for n in ("expert_w1", "expert_w3",
                                          "expert_w2"))
    held = np.arange(w1.shape[0]) if held is None else np.asarray(held)
    chosen, weights = route(p, prefix, x, top_k)
    # [T, H]: the weight token t gives held expert j (0 where not chosen).
    mix = (weights[:, :, None]
           * (chosen[:, :, None] == held[None, None, :])).sum(1)
    hidden = (jax.nn.silu(jnp.einsum("tw,hwm->htm", x, w1))
              * jnp.einsum("tw,hwm->htm", x, w3))
    return jnp.einsum("th,htw->tw", mix, jnp.einsum("htm,hmw->htw", hidden, w2))


def _depth(params) -> int:
    return sum(1 for k in params if k.endswith("/operator_norm/scale"))


def forward(params: Dict[str, jax.Array], tokens,
            held: Optional[Sequence[int]] = None, top_k: int = TOP_K,
            chosen_out: Optional[list] = None):
    """tokens [L] int32 -> logits [L, V] over the held vocabulary rows.
    ``chosen_out`` gets each expert layer's [L, top_k] chosen ids."""
    h = params["embed/embedding"][tokens]
    for i in range(_depth(params)):
        pre = f"layers_{i}/"
        x = _rms(h, params[pre + "operator_norm/scale"])
        if pre + "kda/A_log" in params:
            h = h + kda(params, pre + "kda/", x)
        else:
            h = h + mla(params, pre + "mla/", x)
        x = _rms(h, params[pre + "ffn_norm/scale"])
        if pre + "mlp/w1" in params:
            h = h + swiglu(x, *(params[pre + "mlp/" + n]
                                for n in ("w1", "w3", "w2")))
            continue
        if chosen_out is not None:
            chosen_out.append(route(params, pre + "moe/", x, top_k)[0])
        h = h + experts(params, pre + "moe/", x, held, top_k)
        if pre + "shared/w1" in params:
            h = h + swiglu(x, *(params[pre + "shared/" + n]
                                for n in ("w1", "w3", "w2")))
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
    cache. :func:`_sequence_value_and_grad` at the published widths is 0.24
    GB of code, 52 MB as a cache entry (every product at ``highest``, layer
    by layer): where the cache is capped, that entry pushes out the
    programs a run is timed on, its own cell's and the other cells', and a
    check that compiles it anew takes longer and moves no metric."""
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
    copies of 602 M parameters a reference round makes (the start, the
    carry, a step's gradient, a sequence's gradient, a client's delta, the
    mean delta) do not fit on the chip beside the runner's state, so the
    round's own arithmetic (``fedround.py``) runs in numpy and
    :func:`loss_and_grad` alone uses the device."""
    return {k: np.asarray(v, np.float32) for k, v in params.items()}
