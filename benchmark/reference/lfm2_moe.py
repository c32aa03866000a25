"""Plain float32 reference for the LFM2-MoE next-token model as one chip of
an expert-parallel deployment holds it: the forward pass, the next-token
loss of one client's minibatch and its gradient, in straightforward
``jax.numpy`` at ``highest`` matmul precision. No flax, no engine code, no
grouped matmul, no sort, no kernels; one sequence at a time, gradients
accumulated, so that it fits beside the runner's state on the chip.

Architecture (``LiquidAI/LFM2-24B-A2B`` config.json, ``model_type``
``lfm2_moe``; the family's public modelling code for what the config does
not say): token embedding, then pre-norm residual layers

    h = h + operator(rms(h));   h = h + ffn(rms(h))

- ``conv`` operator: ``B, C, x = split(h W_in, 3)``;
  ``y = (C * causal_depthwise_conv1d(B * x, K taps)) W_out``, no bias;
- ``full_attention`` operator: grouped-query attention (the head size is
  the length of the q/k norm scales), RMSNorm over the head size on q and
  k, then rotary embedding in the rotate-half form, causal softmax;
- ffn of a leading dense layer: ``W2(silu(W1 h) * W3 h)``;
- ffn of the others, the routed experts: ``s = sigmoid(W_g h)`` over ALL
  experts of the router; the ``TOP_K`` chosen are those of
  ``top_k(s + expert_bias)``; their weights are the chosen ``s`` over their
  sum + 1e-6, times ``ROUTED_SCALING_FACTOR``; the layer returns
  ``sum_e weight_e * W2_e(silu(W1_e h) * W3_e h)`` over the chosen experts
  THAT ARE HELD (``held``: the ids of the stacked expert weights, the first
  ones where not given) and nothing for the others. Computed here the
  dense way: every held expert on every token, times a weight that is zero
  where the token did not choose it;
- final RMSNorm, logits against the embedding (tied head), over the rows of
  the vocabulary that are held.

Loss of a sequence: the mean over its L - 1 positions of the cross-entropy
of position t's logits against token t + 1. ``loss_and_grad`` returns
``sum_i sw_i * loss_i`` and its gradient; labels are ignored.

Departure from the program, on purpose: everything is float32 (the program
feeds its matmuls bfloat16). A near-tie among the router's scores can
therefore be chosen differently here and there (``chosen_experts`` is for
measuring how often).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops

NAME = "lfm2"
# Published constants that are not shapes of the parameter tree.
TOP_K = 4
NORM_TOPK_PROB = True
ROUTED_SCALING_FACTOR = 1.0
NORM_EPS = 1e-5
ROPE_THETA = 1e6


def run_layer_types(model: dict) -> List[str]:
    """The layers this configuration runs: the published ``layer_types``
    cut to ``layer_slice`` (the pipeline stage on this chip)."""
    lo, hi = model["layer_slice"]
    return list(model["layer_types"][lo:hi])


def layers(model: dict) -> List[flops.Layer]:
    """Forward matmul-like layers of one sample (a sequence of
    ``sequence_length`` tokens), for benchmark/flops.py. An expert layer
    counts the expected ``num_experts_per_tok * held / published`` experts a
    token (what this chip's share of a uniformly routed layer computes),
    attention its causal half of the L x L products, the head the L - 1
    positions the loss reads. The embedding lookup counts nothing."""
    L, W = model["sequence_length"], model["hidden_size"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    D = W // heads
    share = (model["num_experts_per_tok"] * model["num_experts"]
             / model["num_experts_published"])
    out = []
    for i, kind in enumerate(run_layer_types(model)):
        if kind == "conv":
            out += [flops.dense(f"l{i}.conv_in", L, W, 3 * W),
                    flops.Layer(f"l{i}.conv_taps",
                                float(L * W * model["conv_L_cache"])),
                    flops.dense(f"l{i}.conv_out", L, W, W)]
        else:
            out += [flops.dense(f"l{i}.q", L, W, heads * D),
                    flops.dense(f"l{i}.kv", L, W, 2 * kv * D),
                    flops.Layer(f"l{i}.scores", L * (L + 1) / 2 * heads * D),
                    flops.Layer(f"l{i}.context", L * (L + 1) / 2 * heads * D),
                    flops.dense(f"l{i}.attn_out", L, heads * D, W)]
        if i < model["num_dense_layers"]:
            M = model["intermediate_size"]
            out += [flops.dense(f"l{i}.mlp_in", L, W, 2 * M),
                    flops.dense(f"l{i}.mlp_out", L, M, W)]
        else:
            M = model["moe_intermediate_size"]
            out += [flops.dense(f"l{i}.router", L, W,
                                model["num_experts_published"]),
                    flops.Layer(f"l{i}.experts", L * share * 3 * W * M)]
    out.append(flops.dense("head", L - 1, W, model["vocab_size"]))
    return out


def _rms(x, scale):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + NORM_EPS) * scale


def _rotary(x):
    """x [L, heads, D]: pairs (i, i + D/2) turn by position * theta^(-2i/D)."""
    L, D = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / ROPE_THETA ** (np.arange(0, D, 2, dtype=np.float64) / D)
    angles = np.arange(L, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(p: Dict[str, jax.Array], prefix: str, x):
    """x [L, W] -> [L, W]."""
    b, c, u = jnp.split(x @ p[prefix + "in_proj"], 3, axis=-1)
    taps = p[prefix + "conv"]                       # [K, W]; tap K-1 is "now"
    K, L = taps.shape[0], x.shape[0]
    bu = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), b * u])
    y = sum(taps[j] * bu[j:j + L] for j in range(K))
    return (c * y) @ p[prefix + "out_proj"]


def attention(p: Dict[str, jax.Array], prefix: str, x):
    L = x.shape[0]
    D = p[prefix + "q_norm/scale"].shape[0]
    q = (x @ p[prefix + "q_proj"]).reshape(L, -1, D)
    k = (x @ p[prefix + "k_proj"]).reshape(L, -1, D)
    v = (x @ p[prefix + "v_proj"]).reshape(L, -1, D)
    q = _rotary(_rms(q, p[prefix + "q_norm/scale"]))
    k = _rotary(_rms(k, p[prefix + "k_norm/scale"]))
    group = q.shape[1] // k.shape[1]                # query heads a kv head
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(D)
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
        if pre + "conv/conv" in params:
            h = h + short_conv(params, pre + "conv/", x)
        else:
            h = h + attention(params, pre + "attn/", x)
        x = _rms(h, params[pre + "ffn_norm/scale"])
        if pre + "mlp/w1" in params:
            h = h + swiglu(x, *(params[pre + "mlp/" + n]
                                for n in ("w1", "w3", "w2")))
        else:
            if chosen_out is not None:
                chosen_out.append(route(params, pre + "moe/", x, top_k)[0])
            h = h + experts(params, pre + "moe/", x, held, top_k)
    return _rms(h, params["final_norm/scale"]) @ params["embed/embedding"].T


def sequence_loss(params, tokens, held=None, top_k: int = TOP_K):
    logp = jax.nn.log_softmax(forward(params, tokens, held, top_k)[:-1], -1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()


@jax.jit
def _weighted_value_and_grad(params, tokens, weight):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: weight * sequence_loss(p, tokens))(params)


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


def loss_and_grad(params: Dict[str, jax.Array], x, y, sw
                  ) -> Tuple[float, Dict[str, jax.Array]]:
    """loss = sum_i sw_i * loss(sequence x_i) and its gradient; ``y`` (the
    generator's topics) is not read. One sequence at a time; the gradient
    is accumulated leaf by leaf so that two copies are never whole."""
    x, sw = np.asarray(x), np.asarray(sw, np.float32)
    total, grads = 0.0, None
    for i in np.nonzero(sw)[0]:
        loss, g = _weighted_value_and_grad(
            params, jnp.asarray(x[i], jnp.int32), jnp.float32(sw[i]))
        total += float(loss)
        if grads is None:
            grads = g
        else:
            for k in grads:
                grads[k] = grads[k] + g.pop(k)
    return total, grads


def prepare(params: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
    """Flat program-layout params -> float32 arrays on the default device."""
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
