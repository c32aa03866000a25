"""Phi-4-mini-flash decoder family (``model_type: phi4flash``; sizes from the
public ``microsoft/Phi-4-mini-flash-reasoning`` config.json, the kind of
every layer and what the config does not carry from the family's modelling
code, SambaY, arXiv:2507.06607): a next-token language model of pre-norm
residual layers,

    h = embed(tokens)
    for each layer:   h = h + mixer(ln1(h));   h = h + mlp(ln2(h))
    logits = ln(h) @ embed.T                  (head tied to the embedding)

``ln`` a LayerNorm with scale and bias, ``mlp`` the gated SiLU MLP without
bias (:class:`~olearning_sim_tpu.models.decoder_parts.SwiGLU`), and ``mixer`` one of
six things by the layer's PUBLISHED index ``i`` (:func:`layer_kind`):

- **M**, Mamba-1 (``d_inner`` = ``expand`` x width channels, ``N`` =
  ``d_state``): ``[x, z] = u W_in``; ``x = silu(conv(x) + b)``, a causal
  depthwise convolution of ``d_conv`` taps; ``[d, B_t, C_t] = x W_x``
  (``dt_rank + 2 N`` wide); step sizes ``D_t = softplus(d W_dt + b_dt)``, one
  a channel; ``A = -exp(A_log)``, one number a (channel, state); the
  recurrence, elementwise over ``d_inner x N`` from ``S_0 = 0``,

      S_t[c, n] = exp(D_t[c] A[c, n]) S_{t-1}[c, n] + D_t[c] B_t[n] x_t[c]
      y_t[c]    = sum_n C_t[n] S_t[c, n] + D[c] x_t[c]

  (:func:`selective_scan`: no product with a state matrix anywhere, every
  (channel, state) decays at its own rate) and ``out = (silu(z) * y) W_out``;
- **M\\***: an M that also hands on ``m = y``, its scan's output with the
  skip, before the gate;
- **G**, a gated memory unit: ``(silu(u W_in) * m) W_out``: no scan, no
  convolution, ``m`` the M\\* layer's;
- **S**, differential attention over a window: ``[q, k, v] = u W_qkv + b``;
  adjacent heads pair up, ``q`` as ``heads / 2`` pairs, ``k`` as ``kv_heads
  / 2`` pairs (two query pairs read one key pair), ``v`` as ``kv_heads / 2``
  groups ``2 D`` wide; with ``a_j = softmax(q_j k_j^T / sqrt(D) + mask) v``
  for the first and second member ``j`` of a pair,

      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda0(i)
      a = rms(a_1 - lambda a_2) * scale * (1 - lambda0(i))

  (``lambda0(i) = 0.8 - 0.6 exp(-0.3 i)``, ``rms`` over the ``2 D`` of a
  pair), then ``a W_o + b_o``; the mask lets query ``t`` see keys ``t -
  window + 1 .. t`` and the scores are computed only for the key blocks the
  window reaches (:func:`window_attend`);
- **F**: the same with the whole causal prefix (``decoder_parts.attend``: a block of
  queries against the keys up to its end), and it hands on its ``k`` and
  ``v``;
- **C**, cross-attention: ``q = u W_q + b`` only; keys and values are the F
  layer's; the same differential attention, causal, its own lambda vectors,
  sub-norm and ``W_o``.

No rotary or other positional embedding anywhere.

What one chip of a deployment holds is a matter of the sizes given, as in
``models/lfm2.py``: ``layer_slice`` (the published indices of this pipeline
stage's layers, both ends in; ``m``, ``k`` and ``v`` are made and read
inside it) and ``vocab_size`` (the rows of the tied table held here; ids,
logits and loss are over them). Nothing here stands in for the other chips.

Precision: float32 parameters; matmul operands in ``dtype`` (bfloat16): the
projections, the MLP, the score and context products, the head; float32 for
the residual stream, the norms, the convolution, softplus, softmax, lambda,
the logits, and everything inside the recurrence (what feeds it leaves its
projection in float32; every exponent is ``D_t A <= 0``).

Every layer with a scan or attention sows ``phi4flash_stats``
(:data:`STATS`).
"""

from __future__ import annotations

import functools
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from olearning_sim_tpu.models.decoder_parts import (
    SwiGLU, a_log_init, attend, attend_pairs, causal_taps, dense_init,
    dt_bias_init, mm, sown_attend_pairs, work_counts_beside)
from olearning_sim_tpu.models.registry import ModelSpec, register_model

# What a layer with a scan or attention sows as ``phi4flash_stats`` on every
# call, one int32 vector: the tokens and chunks its scan took, the
# (query, key) pairs of a sequence's window a head needs and the scores a
# head formed for them (masked ones among them), and the same two of an F or
# C layer's whole causal prefix (``decoder_parts.attend_pairs``).
STATS = ("sscan_tokens", "sscan_chunks", "window_attn_pairs_needed",
         "window_attn_pairs_computed", "attend_pairs_needed",
         "attend_pairs_computed")
# Tokens between two states the scan's backward pass keeps, and tokens a
# step of the loop inside a chunk (measured on the chip at the benchmark
# cell's shapes, PERF.md section 6, PR 44: 64 and 8 were the fastest pair).
CHUNK = 64
UNROLL = 8
_NEG = float(jnp.finfo(jnp.float32).min)


def layer_kind(i: int) -> str:
    """The kind of published layer ``i`` (0-based): Mamba positions are the
    even ones, attention positions the odd; the first half is M / S, layer
    16 the Mamba that hands on its memory, 17 the one full attention, and
    from 18 on gated memory units / cross-attention."""
    if i < 16:
        return "S" if i % 2 else "M"
    if i < 18:
        return "F" if i % 2 else "M*"
    return "C" if i % 2 else "G"


def lambda_init(i: int) -> float:
    """``lambda0`` of the attention layer at published index ``i``."""
    return 0.8 - 0.6 * float(np.exp(-0.3 * i))


def selective_scan(x, dt, A, B, C, chunk: int = CHUNK):
    """The Mamba-1 recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t``,
    ``y_t = S_t C_t`` from ``S_0 = 0``. ``x``, ``dt`` ``[n, L, D]`` (``dt``
    positive), ``A`` ``[D, N]`` (negative), ``B``, ``C`` ``[n, L, N]``,
    float32. Returns ``y`` ``[n, L, D]``.

    Exact: the recurrence token by token. A ``jax.lax.scan`` over chunks of
    ``chunk`` tokens carries the state ``[n, N, D]`` (channels last: the
    lanes); the backward pass keeps the states that enter the chunks and
    walks a chunk again (``jax.checkpoint`` around the chunk), so nothing
    ``L x D x N`` lives across the step. A tail shorter than a chunk is
    padded with tokens whose step size is zero: they write nothing and decay
    nothing."""
    n, L, D = x.shape
    pad = -L % chunk
    c = (L + pad) // chunk

    def chunks(a):      # [n, L, K] -> [c, chunk, n, K]
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(a.reshape(n, c, chunk, -1), 0, 2)

    At = A.T                                            # [N, D]

    def token(S, xs):                                   # S [n, N, D]
        x_t, dt_t, B_t, C_t = xs
        S = (jnp.exp(dt_t[:, None, :] * At) * S
             + (dt_t * x_t)[:, None, :] * B_t[:, :, None])
        return S, (S * C_t[:, :, None]).sum(1)

    def a_chunk(S, xs):
        return jax.lax.scan(token, S, xs, unroll=UNROLL)

    # A zero typed like the inputs (inside ``shard_map``, device-varying
    # where they are: the carry that comes back is).
    S0 = jnp.zeros((n, At.shape[0], D), x.dtype) + jax.lax.full_like(
        x[:, :1], 0)
    _, y = jax.lax.scan(jax.checkpoint(a_chunk), S0,
                        (chunks(x), chunks(dt), chunks(B), chunks(C)))
    return jnp.moveaxis(y.reshape(c * chunk, n, D), 0, 1)[:, :L]


def window_pairs(L: int, window: int) -> Tuple[int, int]:
    """(the (query, key) pairs a head's window needs over a sequence of
    ``L`` tokens, the scores :func:`window_attend` forms for them): every
    query block against itself and, but for the first, the one before;
    inside one window, what ``decoder_parts.attend`` forms."""
    reach = min(L, window)
    blocks = -(-L // window)
    return (L * reach - reach * (reach - 1) // 2,
            (2 * blocks - 1) * window * window if L > window
            else attend_pairs(L)[1])


@functools.partial(jax.checkpoint, static_argnums=(3,))
def window_attend(q, k, v, window: int):
    """Softmax attention of q [n, L, G, R, D] over k [n, L, G, D] and v [n,
    L, G, Dv] in which query ``t`` sees keys ``t - window + 1 .. t``, scores
    and softmax in float32. Computed in blocks of ``window`` tokens: a query
    block against its own keys (causal inside) and against the block before
    (the keys the window still reaches: in-block position ``j > i``), never
    L x L. The backward pass recomputes the scores rather than keep them. A
    tail shorter than a block is padded with keys no real query sees."""
    n, L, G, R, D = q.shape
    if L <= window:
        return attend(q, k, v)
    pad = -L % window
    b = (L + pad) // window

    def blocks(a):      # [n, L, ...] -> [n, b, window, ...]
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape((n, b, window) + a.shape[2:])

    q, k, v = blocks(q), blocks(k), blocks(v)
    scale = 1.0 / np.sqrt(D)
    own = jnp.einsum("nbqgrd,nbkgd->nbgrqk", q, k,
                     preferred_element_type=jnp.float32) * scale
    before = jnp.einsum("nbqgrd,nbkgd->nbgrqk", q[:, 1:], k[:, :-1],
                        preferred_element_type=jnp.float32) * scale
    lower = np.tril(np.ones((window, window), bool))
    own = jnp.where(lower, own, _NEG)
    before = jnp.where(~lower, before, _NEG)
    # The first block has no block before it: a row of masked scores that
    # no product formed.
    before = jnp.pad(before, ((0, 0), (1, 0)) + ((0, 0),) * 4,
                     constant_values=_NEG)
    probs = jax.nn.softmax(jnp.concatenate([before, own], -1), -1)
    probs = probs.astype(q.dtype)
    ctx = jnp.einsum("nbgrqk,nbkgd->nbqgrd", probs[..., window:], v)
    ctx = ctx.at[:, 1:].add(jnp.einsum(
        "nbgrqk,nbkgd->nbqgrd", probs[:, 1:, ..., :window], v[:, :-1]))
    return ctx.reshape((n, b * window) + ctx.shape[3:])[:, :L]


class Mamba(nn.Module):
    """The Mamba-1 mixer: what feeds the scan, the scan, what follows it.
    Returns (the mixer's output, ``y`` with the skip and before the gate:
    what an M* layer hands on)."""

    d_inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        n, L, W = u.shape
        Di, N, R = self.d_inner, self.d_state, self.dt_rank
        f32 = jnp.float32
        in_proj = self.param("in_proj", dense_init, (W, 2 * Di), f32)
        conv = self.param("conv", nn.initializers.lecun_normal(),
                          (self.d_conv, Di), f32)
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (Di,), f32)
        x_proj = self.param("x_proj", dense_init, (Di, R + 2 * N), f32)
        dt_proj = self.param("dt_proj", dense_init, (R, Di), f32)
        # A step log-uniform in [0.001, 0.1], a channel.
        dt_bias = self.param("dt_bias", dt_bias_init, (Di,), f32)
        A_log = self.param("A_log", a_log_init, (Di, N), f32)
        D = self.param("D", nn.initializers.ones, (Di,), f32)
        out_proj = self.param("out_proj", dense_init, (Di, W), f32)

        def fed(a, kernel):     # operands in ``dtype``, the result float32
            return jnp.dot(a.astype(self.dtype), kernel.astype(self.dtype),
                           preferred_element_type=f32)

        with jax.named_scope("phi4flash.mamba_projections"):
            x, z = jnp.split(mm(u, in_proj, self.dtype), 2, axis=-1)
            x = jax.nn.silu(causal_taps(x.astype(f32), conv) + conv_bias)
            d, B, C = jnp.split(fed(x, x_proj), [R, R + N], axis=-1)
            dt = jax.nn.softplus(fed(d, dt_proj) + dt_bias)
        with jax.named_scope("phi4flash.selective_scan"):
            y = selective_scan(x, dt, -jnp.exp(A_log), B, C)
        with jax.named_scope("phi4flash.mamba_projections"):
            y = y + D * x
            out = mm(jax.nn.silu(z.astype(f32)) * y, out_proj, self.dtype)
        self.sow("intermediates", "phi4flash_stats", jnp.asarray(
            [n * L, n * -(-L // CHUNK), 0, 0, 0, 0], jnp.int32))
        return out, y


class GMU(nn.Module):
    """The gated memory unit: the M* layer's memory ``m`` [n, L, d_inner]
    under a gate of this layer's own input."""

    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u, m):
        W, Di = u.shape[-1], m.shape[-1]
        in_proj = self.param("in_proj", dense_init, (W, Di), jnp.float32)
        out_proj = self.param("out_proj", dense_init, (Di, W), jnp.float32)
        with jax.named_scope("phi4flash.gmu"):
            gate = jax.nn.silu(mm(u, in_proj, self.dtype).astype(jnp.float32))
            return mm(gate * m, out_proj, self.dtype)


class DiffAttention(nn.Module):
    """Differential attention of the layer at published ``index``: over a
    ``window`` (S), over the causal prefix (F; ``window`` 0), or, given
    another layer's ``k`` and ``v``, with a query projection alone (C).
    Returns (the mixer's output, k, v)."""

    index: int
    heads: int
    kv_heads: int
    window: int = 0
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u, k=None, v=None):
        n, L, W = u.shape
        H, G = self.heads, self.kv_heads
        D = W // H
        f32 = jnp.float32

        def projected(name, width):     # u W + b, the bias added in float32
            kernel = self.param(name + "_proj", dense_init, (W, width), f32)
            bias = self.param(name + "_bias", nn.initializers.zeros,
                              (width,), f32)
            return (mm(u, kernel, self.dtype).astype(f32)
                    + bias).astype(self.dtype)

        lam = {name: self.param(name, nn.initializers.normal(0.1), (D,), f32)
               for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                            "lambda_k2")}
        subln = self.param("subln", nn.initializers.ones, (2 * D,), f32)
        wo = self.param("out_proj", dense_init, (H * D, W), f32)
        bo = self.param("out_bias", nn.initializers.zeros, (W,), f32)
        lam0 = lambda_init(self.index)
        # The S mixer's score and context products have a scope of their
        # own (the window's roofline divides by it); scopes never nest.
        scope = ("phi4flash.window_attention" if self.window
                 else "phi4flash.full_attention")
        with jax.named_scope(scope):
            if k is None:
                q, k, v = jnp.split(projected("qkv", (H + 2 * G) * D),
                                    [H * D, (H + G) * D], axis=-1)
                # Pairs of adjacent heads: k as G/2 pairs of two D-wide
                # members, v as G/2 groups 2 D wide.
                k = k.reshape(n, L, G // 2, 2, D)
                v = v.reshape(n, L, G // 2, 2 * D)
            else:
                q = projected("q", H * D)
            # [n, L, key pair, query pairs of it, member, D]
            q = q.reshape(n, L, G // 2, H // G, 2, D)
        with jax.named_scope(
                "phi4flash.window_products" if self.window else scope):
            a1, a2 = (
                window_attend(q[..., j, :], k[..., j, :], v, self.window)
                if self.window else attend(q[..., j, :], k[..., j, :], v)
                for j in (0, 1))
        with jax.named_scope(scope):
            lam_full = (jnp.exp(jnp.sum(lam["lambda_q1"] * lam["lambda_k1"]))
                        - jnp.exp(jnp.sum(lam["lambda_q2"] * lam["lambda_k2"]))
                        + lam0)
            a = a1.astype(f32) - lam_full * a2.astype(f32)
            a = a * jax.lax.rsqrt(
                jnp.mean(a * a, axis=-1, keepdims=True) + self.eps)
            a = a * subln * (1.0 - lam0)
            out = mm(a.reshape(n, L, H * D), wo, self.dtype).astype(f32) + bo
        if self.window:
            needed, computed = window_pairs(L, self.window)
            self.sow("intermediates", "phi4flash_stats", jnp.asarray(
                [0, 0, n * needed, n * computed, 0, 0], jnp.int32))
        else:
            self.sow("intermediates", "phi4flash_stats",
                     sown_attend_pairs(n, L, 4))
        return out, k, v


class Layer(nn.Module):
    """One layer: ``h + mixer(ln1(h))`` then ``h + mlp(ln2(h))``, the mixer
    by ``layer_kind(index)``. Called with what its kind reads beside ``h``
    (G: ``m``; C: ``k, v``) and returns ``h`` and what its kind hands on
    (M*: ``(m,)``; F: ``(k, v)``; the others nothing)."""

    index: int
    heads: int
    kv_heads: int
    mlp_dim: int
    window: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h, *read):
        kind = layer_kind(self.index)

        def ln(name):
            return nn.LayerNorm(epsilon=self.eps, dtype=jnp.float32,
                                param_dtype=jnp.float32, name=name)

        u = ln("mixer_norm")(h)
        handed = ()
        if kind in ("M", "M*"):
            y, m = Mamba(self.d_inner, self.d_state, self.d_conv,
                         self.dt_rank, self.dtype, name="mamba")(u)
            handed = (m,) if kind == "M*" else ()
        elif kind == "G":
            y = GMU(self.dtype, name="gmu")(u, *read)
        else:
            y, k, v = DiffAttention(
                self.index, self.heads, self.kv_heads,
                self.window if kind == "S" else 0, self.eps, self.dtype,
                name="attn")(u, *read)
            handed = (k, v) if kind == "F" else ()
        h = h + y.astype(jnp.float32)
        h = h + SwiGLU(self.mlp_dim, self.dtype, name="mlp")(
            ln("mlp_norm")(h)).astype(jnp.float32)
        return h, handed


class Phi4Flash(nn.Module):
    """The stack: the tied table, one :class:`Layer` a published index of
    ``layer_slice``, the last LayerNorm and the logits over the table's
    rows. ``h`` flows from layer to layer, and beside it the M* layer's
    memory ``m`` (to every G layer) and the F layer's ``k, v`` (to every C
    layer): their cotangents add into M*'s and F's backward pass.

    **No layer is wrapped in ``nn.remat``**: the benchmark cell's round
    program (layers 15-19, 4,096 tokens a step, 21 B a parameter of state
    around it) compiles for a v5e and runs at 12.8 of the chip's 16 GB with
    every layer's residuals kept (``scripts/compile_cell.py``; PERF.md
    section 4 has the compiler's row), and a wrapped layer is computed
    twice. What the backward pass computes again: the attention scores
    (``decoder_parts.attend``'s and :func:`window_attend`'s own checkpoints) and the
    scan's chunks."""

    vocab_size: int = 200064
    max_len: int = 262144           # positions served; no position table
    width: int = 2560
    num_hidden_layers: int = 32
    layer_slice: Tuple[int, int] = (0, 31)   # published indices, both in
    heads: int = 40
    kv_heads: int = 20
    mlp_dim: int = 10240
    window: int = 512
    d_inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens):
        if tokens.shape[-1] > self.max_len:
            raise ValueError(
                f"sequence of {tokens.shape[-1]} tokens, max_len is "
                f"{self.max_len}")
        first, last = self.layer_slice
        if not 0 <= first <= last < self.num_hidden_layers:
            raise ValueError(f"layer_slice {self.layer_slice} is not inside "
                             f"the {self.num_hidden_layers} published layers")
        embed = nn.Embed(
            self.vocab_size, self.width, name="embed",
            embedding_init=nn.initializers.normal(stddev=0.02),
            param_dtype=jnp.float32)
        h = embed(tokens)
        handed = {}     # by the kind that made it: M* -> (m,), F -> (k, v)
        for i in range(first, last + 1):
            kind = layer_kind(i)
            source = {"G": "M*", "C": "F"}.get(kind)
            if source and source not in handed:
                raise ValueError(
                    f"layer {i} ({kind}) reads what layer "
                    f"{16 if kind == 'G' else 17} hands on, and layer_slice "
                    f"{self.layer_slice} starts after it")
            h, made = Layer(
                index=i, heads=self.heads, kv_heads=self.kv_heads,
                mlp_dim=self.mlp_dim, window=self.window,
                d_inner=self.d_inner, d_state=self.d_state,
                d_conv=self.d_conv, dt_rank=self.dt_rank, eps=self.norm_eps,
                dtype=self.dtype, name=f"layers_{i - first}",
            )(h, *handed.get(source, ()))
            if made:
                handed[kind] = made
        h = nn.LayerNorm(epsilon=self.norm_eps, dtype=jnp.float32,
                         param_dtype=jnp.float32, name="final_norm")(h)
        return jnp.dot(h.astype(self.dtype),
                       embed.embedding.astype(self.dtype).T,
                       preferred_element_type=jnp.float32)


register_model(
    ModelSpec(
        name="phi4flash",
        builder=Phi4Flash,
        example_input_shape=(1024,),
        # A language model: its "classes" are its vocabulary.
        num_classes=200064,
        input_dtype=np.int32,
        # One client at a time, as the other long-context decoders: a
        # client's float32 carry and gradient fill most of a chip.
        vmap_clients=False,
        work_counts=work_counts_beside("phi4flash_stats", STATS),
        defaults={
            "vocab_size": 200064, "max_len": 262144, "width": 2560,
            "num_hidden_layers": 32, "layer_slice": [0, 31], "heads": 40,
            "kv_heads": 20, "mlp_dim": 10240, "window": 512,
            "d_inner": 5120, "d_state": 16, "d_conv": 4, "dt_rank": 160,
            "norm_eps": 1e-5,
        },
    )
)
