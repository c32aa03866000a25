"""Plain float32 reference for the Phi-4-mini-flash (SambaY) next-token model
as one pipeline stage with a slice of the vocabulary holds it: the forward
pass, the next-token loss of one client's minibatch and its gradient, in
straightforward ``jax.numpy`` at ``highest`` matmul precision. No flax, no
engine code, no chunked scan, no banded attention, no checkpointed scores;
one sequence at a time, gradients accumulated, and the parameters kept on
the host between calls, so that it fits beside the runner's state on the
chip.

Architecture (``microsoft/Phi-4-mini-flash-reasoning`` config.json,
``model_type`` ``phi4flash``; the kind of every layer, the Mamba sizes and
differential attention from the family's modelling code, SambaY,
arXiv:2507.06607): token embedding, then layers

    h = h + mixer(LN1(h));   h = h + W2(silu(W1 u) * (W3 u)),  u = LN2(h)

``LN`` a LayerNorm with scale and bias (``NORM_EPS``), the mixer by the
layer's published index ``i = FIRST_LAYER + its place here`` (:func:`kind`):

- Mamba (``M``, and ``M*`` at index 16): ``[x, z] = u W_in``; ``x =
  silu(conv(x) + bias)``, a causal depthwise convolution (taps ``[T,
  d_inner]``, tap ``T - 1`` is "now"); ``[d, B_t, C_t] = x W_x`` (``dt_rank``
  = the rows of ``W_dt``, the state size the columns of ``A_log``); step
  size ``D_t = softplus(d W_dt + b_dt)``; ``A = -exp(A_log)``; **the
  recurrence itself, token by token**, from ``S_0 = 0`` (``S``: ``d_inner x
  N``, every element with a decay of its own)

      S_t[c, n] = exp(D_t[c] A[c, n]) S_{t-1}[c, n] + D_t[c] B_t[n] x_t[c]
      y_t[c]    = sum_n C_t[n] S_t[c, n] + D[c] x_t[c]

  and ``out = (silu(z) * y) W_out``. The ``M*`` layer's ``y`` is the memory
  ``m`` the ``G`` layers read;
- ``G``, a gated memory unit: ``(silu(u W_in) * m) W_out``;
- differential attention (``S``, ``F``, ``C``): ``[q, k, v] = u W_qkv + b``
  (``C``: ``q = u W_q + b`` alone, ``k`` and ``v`` the ``F`` layer's), heads
  ``D`` wide (the lambda vectors' length); head ``2 p + j`` is member ``j`` of pair ``p``; query
  pair ``p`` reads key pair ``p // (query pairs / key pairs)`` and that
  pair's two value heads side by side, ``2 D`` wide; ``a_j =
  softmax(q_j k_j^T / sqrt(D) + mask) v``, the L x L scores kept;
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda0``, ``lambda0 = 0.8 -
  0.6 exp(-0.3 i)``; ``a = rms(a_1 - lambda a_2) * scale * (1 - lambda0)``
  over a pair's ``2 D``; ``a W_o + b_o``. Mask: causal, and in an
  ``S`` layer also ``t - s <= WINDOW - 1``. No positional embedding;
- the last LayerNorm, logits against the table's held rows (tied head).

Loss of a sequence: the mean over its L - 1 positions of the cross-entropy
of position t's logits against token t + 1. ``loss_and_grad`` returns
``sum_i sw_i * loss_i`` and its gradient; labels are ignored.

Departure from the program, on purpose: everything is float32 (the program
feeds its projections and its attention products bfloat16, computes the
recurrence in chunks and the window in blocks).
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops

NAME = "phi4flash"
# Published constants that are not shapes of the parameter tree.
NORM_EPS = 1e-5
WINDOW = 512
# The published index (0-based) of the first layer held here: the
# configuration's ``layer_slice`` starts there.
FIRST_LAYER = 15
# Tokens between two kept states of the recurrence's backward pass.
SEGMENT = 64


def kind(i: int) -> str:
    """Published layer ``i``: even indices are Mamba positions, odd ones
    attention positions; below 16 ``M`` / ``S``, 16 ``M*``, 17 ``F``, from
    18 on ``G`` / ``C``."""
    if i == 16:
        return "M*"
    if i == 17:
        return "F"
    return ("MS" if i < 16 else "GC")[i % 2]


def window_pairs(L: int, window: int) -> int:
    """The (query, key) pairs of one head over ``L`` tokens with ``t - s``
    in ``0 .. window - 1``."""
    w = min(L, window)
    return L * w - w * (w - 1) // 2


def layers(model: dict) -> List[flops.Layer]:
    """Forward matmul-like layers of one sample (a sequence of
    ``sequence_length`` tokens), for benchmark/flops.py. The window layer
    counts its window's pairs, the full and cross layers their causal half,
    each pair ``D`` MACs of score and ``2 D`` of context a query head of
    size ``D``; the recurrence 2 MACs a (token, channel, state): the
    decayed state plus the write, and the read by ``C``; the head the L - 1
    positions the loss reads. The embedding lookup counts nothing."""
    L, W = model["sequence_length"], model["hidden_size"]
    H, Hk = model["num_attention_heads"], model["num_key_value_heads"]
    D, Mi = W // H, model["intermediate_size"]
    Di, N = model["d_inner"], model["d_state"]
    R, T = model["dt_rank"], model["d_conv"]
    lo, hi = model["layer_slice"]
    out = []
    for i in range(lo, hi + 1):
        k = kind(i)
        if k in ("M", "M*"):
            out += [flops.dense(f"l{i}.in_proj", L, W, 2 * Di),
                    flops.Layer(f"l{i}.conv_taps", float(L * Di * T)),
                    flops.dense(f"l{i}.x_proj", L, Di, R + 2 * N),
                    flops.dense(f"l{i}.dt_proj", L, R, Di),
                    flops.Layer(f"l{i}.selective_scan", float(L * Di * N * 2)),
                    flops.dense(f"l{i}.mamba_out", L, Di, W)]
        elif k == "G":
            out += [flops.dense(f"l{i}.gmu_in", L, W, Di),
                    flops.dense(f"l{i}.gmu_out", L, Di, W)]
        else:
            pairs = (window_pairs(L, model["sliding_window"]) if k == "S"
                     else L * (L + 1) // 2)
            out += [flops.dense(f"l{i}.qkv", L, W,
                                H * D if k == "C" else (H + 2 * Hk) * D),
                    flops.Layer(f"l{i}.scores", float(pairs * H * D)),
                    flops.Layer(f"l{i}.context", float(pairs * H * 2 * D)),
                    flops.dense(f"l{i}.attn_out", L, H * D, W)]
        out += [flops.dense(f"l{i}.mlp_in", L, W, 2 * Mi),
                flops.dense(f"l{i}.mlp_out", L, Mi, W)]
    out.append(flops.dense("head", L - 1, W, model["vocab_size"]))
    return out


def _ln(x, p, prefix):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + NORM_EPS)
            * p[prefix + "scale"] + p[prefix + "bias"])


def _conv_silu(x, taps, bias):
    """x [L, D] through the causal depthwise ``taps`` [T, D], plus the
    bias, then SiLU."""
    T, L = taps.shape[0], x.shape[0]
    x = jnp.concatenate([jnp.zeros((T - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(taps[j] * x[j:j + L] for j in range(T)) + bias)


def recurrence(x, dt, A, B, C):
    """The recurrence as written, one token after another: x, dt [L, D], A
    [D, N], B, C [L, N] -> y [L, D] (without the skip ``D x``). The
    arithmetic is a token's step and nothing else; the walk over the
    sequence is cut into ``SEGMENT``-token stretches for the backward pass's
    memory only (it keeps the state that enters a stretch and walks the
    stretch again)."""

    def step(S, xs):                                    # S [D, N]
        x_t, dt_t, B_t, C_t = xs
        S = (jnp.exp(dt_t[:, None] * A) * S
             + (dt_t * x_t)[:, None] * B_t[None, :])
        return S, S @ C_t

    L = x.shape[0]
    stretches = tuple(
        jnp.pad(a, ((0, -L % SEGMENT), (0, 0))
                ).reshape((-1, SEGMENT) + a.shape[1:])
        for a in (x, dt, B, C))
    y = jax.lax.scan(jax.checkpoint(lambda S, xs: jax.lax.scan(step, S, xs)),
                     jnp.zeros(A.shape, x.dtype), stretches)[1]
    return y.reshape(-1, y.shape[-1])[:L]


def mamba(p: Dict[str, jax.Array], prefix: str, u):
    """u [L, W] -> ([L, W], the scan's y with the skip: the memory)."""
    d_inner = p[prefix + "out_proj"].shape[0]
    rank, state = p[prefix + "dt_proj"].shape[0], p[prefix + "A_log"].shape[1]
    x, z = jnp.split(u @ p[prefix + "in_proj"], [d_inner], -1)
    x = _conv_silu(x, p[prefix + "conv"], p[prefix + "conv_bias"])
    d, B, C = jnp.split(x @ p[prefix + "x_proj"], [rank, rank + state], -1)
    dt = jax.nn.softplus(d @ p[prefix + "dt_proj"] + p[prefix + "dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p[prefix + "A_log"]), B, C)
    y = y + p[prefix + "D"] * x
    return (jax.nn.silu(z) * y) @ p[prefix + "out_proj"], y


def gmu(p: Dict[str, jax.Array], prefix: str, u, m):
    return (jax.nn.silu(u @ p[prefix + "in_proj"]) * m) @ p[prefix + "out_proj"]


def mask(L: int, window: int = 0):
    """[L, L] bool: query t (row) sees key s (column) where ``s <= t`` and,
    with a window, ``t - s <= window - 1``."""
    t, s = np.arange(L)[:, None], np.arange(L)[None, :]
    seen = s <= t
    return seen & (t - s <= window - 1) if window else seen


def differential(a1, a2, lam):
    """The two members' attention outputs into one: the second, times
    lambda, taken from the first."""
    return a1 - lam * a2


def diff_attention(p: Dict[str, jax.Array], prefix: str, u, index: int,
                   seen, kv=None):
    """u [L, W] -> ([L, W], (k [L, Hk, D], v [L, Hk, D])) for the layer at
    published ``index`` under the mask ``seen``; with ``kv`` given, the
    layer has a query projection alone."""
    L, D = u.shape[0], p[prefix + "lambda_q1"].shape[0]   # the head size
    if kv is None:
        qkv = u @ p[prefix + "qkv_proj"] + p[prefix + "qkv_bias"]
        width = p[prefix + "out_proj"].shape[0]
        q, k, v = jnp.split(qkv, [width, (width + qkv.shape[1]) // 2], -1)
        k, v = k.reshape(L, -1, D), v.reshape(L, -1, D)
    else:
        q = u @ p[prefix + "q_proj"] + p[prefix + "q_bias"]
        k, v = kv
    pairs, kv_pairs = q.shape[1] // (2 * D), k.shape[1] // 2
    share = pairs // kv_pairs               # query pairs a key pair
    lam0 = 0.8 - 0.6 * np.exp(-0.3 * index)
    lam = (jnp.exp(p[prefix + "lambda_q1"] @ p[prefix + "lambda_k1"])
           - jnp.exp(p[prefix + "lambda_q2"] @ p[prefix + "lambda_k2"])
           + lam0)
    # Member j of query pair p against member j of its key pair, over that
    # pair's two value heads side by side.
    q = q.reshape(L, pairs, 2, D)
    keys = jnp.repeat(k.reshape(L, kv_pairs, 2, D), share, axis=1)
    values = jnp.repeat(v.reshape(L, kv_pairs, 2 * D), share, axis=1)
    scores = jnp.einsum("qpjd,kpjd->jpqk", q, keys) / np.sqrt(D)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    a = jnp.einsum("jpqk,kpe->jqpe", probs, values)     # [2, L, pairs, 2D]
    d = differential(a[0], a[1], lam)
    d = d * jax.lax.rsqrt((d * d).mean(-1, keepdims=True) + NORM_EPS)
    out = (d * p[prefix + "subln"] * (1.0 - lam0)).reshape(L, -1)
    return out @ p[prefix + "out_proj"] + p[prefix + "out_bias"], (k, v)


def _depth(params) -> int:
    return sum(1 for k in params
               if k.startswith("layers_") and k.endswith("/mixer_norm/scale"))


def forward(params: Dict[str, jax.Array], tokens):
    """tokens [L] int32 -> logits [L, V] over the held vocabulary rows."""
    L = tokens.shape[0]
    h = params["embed/embedding"][tokens]
    memory = keys_values = None
    for j in range(_depth(params)):
        pre, i = f"layers_{j}/", FIRST_LAYER + j
        u = _ln(h, params, pre + "mixer_norm/")
        which = kind(i)
        if which in ("M", "M*"):
            y, m = mamba(params, pre + "mamba/", u)
            if which == "M*":
                memory = m
        elif which == "G":
            y = gmu(params, pre + "gmu/", u, memory)
        elif which == "S":
            y, _ = diff_attention(params, pre + "attn/", u, i,
                                  mask(L, WINDOW))
        elif which == "F":
            y, keys_values = diff_attention(params, pre + "attn/", u, i,
                                            mask(L))
        else:
            y, _ = diff_attention(params, pre + "attn/", u, i, mask(L),
                                  keys_values)
        h = h + y
        u = _ln(h, params, pre + "mlp_norm/")
        h = h + (jax.nn.silu(u @ params[pre + "mlp/w1"])
                 * (u @ params[pre + "mlp/w3"])) @ params[pre + "mlp/w2"]
    return (_ln(h, params, "final_norm/") @ params["embed/embedding"].T)


def sequence_loss(params, tokens):
    logits = forward(params, tokens)
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
    copies of 577 M parameters a reference round makes (the start, the
    carry, a step's gradient, a sequence's gradient, a client's delta, the
    mean delta) do not fit on the chip beside the runner's state, so the
    round's own arithmetic (``fedround.py``) runs in numpy and
    :func:`loss_and_grad` alone uses the device."""
    return {k: np.asarray(v, np.float32) for k, v in params.items()}
