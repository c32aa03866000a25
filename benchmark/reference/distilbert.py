"""Plain float32 reference for the DistilBERT-shaped text classifier: the
forward pass, the weighted cross-entropy loss of one client's minibatch and
its gradient, in straightforward ``jax.numpy`` at ``highest`` matmul
precision (on a TPU a float32 matmul otherwise runs in bfloat16 passes). No
flax, no engine code, no kernels, no vmap over clients.

Architecture (Sanh et al. 2019, arXiv:1910.01108; sizes from
distilbert-base-uncased's config.json): token + learned position
embeddings, LayerNorm, then ``n_layers`` post-LN encoder blocks

    a = MHA(x);  x = LN(x + a);  f = W2 gelu(W1 x);  x = LN(x + f)

with ``n_heads`` heads of ``dim / n_heads``, scores scaled by
1/sqrt(head size), padded keys masked out of the softmax.

Departures from the published model, which are the program's
(``models/transformer.py``) and therefore kept here:

- no dropout anywhere;
- GELU in its tanh approximation (flax's default), not the exact erf form;
- LayerNorm epsilon 1e-6 (flax's default), not 1e-12;
- the classifier is a mean over the non-pad positions followed by one
  Dense(num_classes); the published head is CLS -> Dense(dim) -> ReLU ->
  Dense(num_classes);
- the position table holds ``max_position_embeddings`` = the sequence
  length served, not 512.

Departure from the program, on purpose: everything here is float32 (the
program feeds its encoder matmuls bfloat16 and keeps bfloat16 activations).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops

NAME = "distilbert"
PAD_ID = 0
LN_EPS = 1e-6


def layers(model: dict) -> List[flops.Layer]:
    """Forward matmul-like layers of one sample (a sequence of
    ``sequence_length`` tokens), for benchmark/flops.py. The embedding
    lookup and its scatter gradient are not matmuls and count nothing."""
    L, W, M = model["sequence_length"], model["dim"], model["hidden_dim"]
    out = []
    for i in range(model["n_layers"]):
        out += [
            flops.dense(f"l{i}.qkv", L, W, 3 * W),
            flops.matmul(f"l{i}.scores", L, W, L),     # heads x L x L x W/heads
            flops.matmul(f"l{i}.context", L, L, W),
            flops.dense(f"l{i}.attn_out", L, W, W),
            flops.dense(f"l{i}.ffn_in", L, W, M),
            flops.dense(f"l{i}.ffn_out", L, M, W),
        ]
    out.append(flops.dense("head", 1, W, model["num_classes"]))
    return out


def _layer_norm(x, scale, bias):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _depth(params) -> int:
    return sum(1 for k in params
               if k.startswith("TransformerBlock_")
               and k.endswith("/Dense_0/kernel"))


def forward(params: Dict[str, jax.Array], tokens: jax.Array) -> jax.Array:
    """tokens [B, L] int32 -> logits [B, K]. ``params`` is the flat
    ``{path: array}`` form of the model's tree."""
    pad = tokens != PAD_ID                                   # [B, L]
    L = tokens.shape[1]
    x = params["Embed_0/embedding"][tokens] + params["pos_embedding"][:, :L]
    x = _layer_norm(x, params["LayerNorm_0/scale"], params["LayerNorm_0/bias"])
    key_mask = (pad[:, None, :, None] & pad[:, None, None, :])  # [B,1,Lq,Lk]
    for i in range(_depth(params)):
        b = f"TransformerBlock_{i}/"
        a = b + "MultiHeadDotProductAttention_0/"
        q = jnp.einsum("blw,whd->blhd", x, params[a + "query/kernel"]) \
            + params[a + "query/bias"]
        k = jnp.einsum("blw,whd->blhd", x, params[a + "key/kernel"]) \
            + params[a + "key/bias"]
        v = jnp.einsum("blw,whd->blhd", x, params[a + "value/kernel"]) \
            + params[a + "value/bias"]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        scores = jnp.where(key_mask, scores, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        y = jnp.einsum("blhd,hdw->blw", ctx, params[a + "out/kernel"]) \
            + params[a + "out/bias"]
        x = _layer_norm(x + y, params[b + "LayerNorm_0/scale"],
                        params[b + "LayerNorm_0/bias"])
        y = x @ params[b + "Dense_0/kernel"] + params[b + "Dense_0/bias"]
        y = _gelu_tanh(y) @ params[b + "Dense_1/kernel"] \
            + params[b + "Dense_1/bias"]
        x = _layer_norm(x + y, params[b + "LayerNorm_1/scale"],
                        params[b + "LayerNorm_1/bias"])
    m = pad[..., None].astype(jnp.float32)
    pooled = (x * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
    return pooled @ params["Dense_0/kernel"] + params["Dense_0/bias"]


def _loss(params, tokens, y, sw):
    logp = jax.nn.log_softmax(forward(params, tokens), axis=-1)
    ce = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return (sw * ce).sum()


@jax.jit
def _value_and_grad(params, tokens, y, sw):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(_loss)(params, tokens, y, sw)


def loss_and_grad(params: Dict[str, jax.Array], x, y, sw
                  ) -> Tuple[float, Dict[str, jax.Array]]:
    """loss = sum_i sw_i * CE(logits_i, y_i) and its gradient."""
    loss, grads = _value_and_grad(
        params, jnp.asarray(x, jnp.int32), jnp.asarray(y, jnp.int32),
        jnp.asarray(sw, jnp.float32))
    return float(loss), grads


def prepare(params: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
    """Flat program-layout params -> float32 arrays on the default device
    (the 66M-parameter model is stepped there, not on the host)."""
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
