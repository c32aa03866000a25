"""Kimi-Linear decoder family (``model_type: kimi_linear``; sizes from the
public ``moonshotai/Kimi-Linear-48B-A3B-Instruct`` config.json): a
next-token language model of pre-norm residual blocks whose mixer is either
a KDA layer (a gated delta-rule linear attention with a per-channel decay)
or an MLA layer (latent attention, no rotary embedding), and whose
feed-forward is a dense SwiGLU MLP in the leading dense layers and, in the
others, a routed expert layer
(:class:`~olearning_sim_tpu.models.moe.DroplessMoE`) plus one shared expert
that every token passes.

    h = embed(tokens)
    for each layer:   h = h + mixer(rms(h));   h = h + ffn(rms(h))
    logits = rms(h) @ head                       (untied: a leaf of its own)

**KDA**, a head (``heads`` heads of ``kda_head_dim`` keys and values):
``q~, k~, v~`` = three projections, each through its own causal depthwise
convolution of ``conv_kernel`` taps and SiLU; ``q = l2norm(q~) / sqrt(d)``,
``k = l2norm(k~)``; a per-channel log decay ``g = -exp(A_log) *
softplus((x W_fa) W_fb + dt_bias)``; a per-head write strength ``beta =
sigmoid(x W_b)``; the recurrence over a ``d x d`` state (keys x values)

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

and ``out = (rms_d(o) * sigmoid((x W_ga) W_gb)) W_o``. The recurrence is
computed chunked (:func:`chunk_scan`): a chunk of :data:`CHUNK` tokens is
one unit lower-triangular system, built and inverted by sub-blocks of
:data:`SUB` tokens, pairwise decays and substitution inside a sub-block and
matrix products between them. What runs where: a program lowered for a TPU
runs the scan forward as the Pallas kernel of ``ops/kda_scan.py`` (the same
arithmetic with a chunk's system, its inverse and the state in VMEM), in
training's forward pass, the evaluation and the initialiser's trace alike;
every other platform runs :func:`chunk_scan` itself, and so does every
backward pass (the forward computed again, then its backward pass), which
makes this file's plain-JAX code the backward's code and the tests'
reference.

**MLA**: ``q = x W_q`` (``heads`` x (``qk_nope_dim`` + ``qk_rope_dim``));
``[c, k_r] = x W_kva`` (``kv_rank`` + ``qk_rope_dim``; ``k_r`` is shared by
all heads and, with no rotary embedding, used as it is); ``[k_n, v] =
rms(c) W_kvb``; ``k = [k_n, k_r]``; causal softmax of ``q k^T / sqrt(qk
width)``; ``out = concat(probs v) W_o``.

What one chip of a deployment holds is a matter of the sizes given, as in
``models/lfm2.py``: ``held_experts``, ``vocab_size`` (rows of the embedding
and of the head held here) and ``layer_types`` (this pipeline stage's
layers). Nothing here stands in for the other chips.

Precision: float32 parameters; matmul inputs and outputs in ``dtype``
(bfloat16); the residual stream, the norms, the router, the softmax and the
logits in float32, and float32 for everything inside the recurrence that
carries a decay: ``g``, its running sums, every exponential, the state, the
inverse of a chunk's triangular system and the products with any of them
(``Precision.HIGHEST``).

The embedding is only looked up (:class:`~olearning_sim_tpu.models.lookup.
LookupOnlyEmbed`), so a trainer may train it by the rows a step reads. Every
KDA layer sows ``kda_stats`` (:data:`STATS`): the tokens and the chunks its
scan took and how many of those chunks the kernel took (all of them on a TPU,
none elsewhere); every MLA layer, the pairs its mask lets through and the
scores it formed.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from olearning_sim_tpu.models.decoder_parts import (
    RMSNorm, SwiGLU, a_log_init, attend, causal_taps, dense_init,
    dt_bias_init, mm, sown_attend_pairs, work_counts_beside)
from olearning_sim_tpu.models.lookup import LookupOnlyEmbed
from olearning_sim_tpu.models import moe
from olearning_sim_tpu.models.registry import ModelSpec, register_model

# Tokens a chunk of the delta-rule scan holds: the intra-chunk system is
# CHUNK x CHUNK, the scan over a sequence has L / CHUNK steps.
CHUNK = 64
# Tokens a sub-block of a chunk holds (CHUNK is a multiple): pairwise decays
# and row-by-row substitution inside one, matrix products between them.
SUB = 16
L2_EPS = 1e-6
# What a KDA or MLA layer sows as ``kda_stats`` on every call, one int32
# vector: the tokens and chunks of a KDA layer's scan, the (query, key)
# pairs an MLA layer's mask lets through, a head, and the scores a head
# formed for them (``decoder_parts.attend_pairs``), and the chunks of a KDA layer's
# scan that ``ops/kda_scan.py``'s kernel took forward (= the chunks where
# the program was lowered for a TPU, 0 elsewhere).
STATS = ("kda_scan_tokens", "kda_scan_chunks", "attend_pairs_needed",
         "attend_pairs_computed", "kda_scan_kernel_chunks")
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm32(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


@jax.checkpoint
def _intra_chunk(q, k, G, beta):
    """The decayed interactions inside one chunk, ``[..., C, K]`` each and
    ``beta`` ``[..., C]``; ``G`` is the running sum of the log decay from
    the chunk's start. ``A[t, j] = beta_t sum_c k_t k_j exp(G_t - G_j)``
    for j < t and ``B[t, j] = sum_c q_t k_j exp(G_t - G_j)`` for j <= t,
    zero elsewhere, by sub-blocks of :data:`SUB` tokens. Returns two
    tuples, ``A``'s blocks and ``B``'s: first the ``C / SUB`` blocks on the
    diagonal, then, for each size ``SUB, 2 SUB, .. C / 2``, the block of
    every later half against its earlier half (``[..., C / 2 size, size,
    size]``): what :func:`_merged` puts together.

    Inside a sub-block the decay of a pair is one exponential of a
    difference that is never positive (``exp(G_t) * exp(-G_j)`` would
    underflow one factor and overflow the other), and the sum over the
    channels a masked reduction of a ``SUB x SUB x K`` array. Between a
    later block, whose first token is ``r``, and the block before it,
    ``exp(G_t - G_j) = exp(G_t - G_r) * exp(G_r - G_j)``: ``j < r <= t``
    and ``G`` never rises, so neither exponent is positive, nothing
    overflows, and a factor underflows only where the product lies below
    float32's smallest number. Those blocks are therefore matrix products,
    ``(beta k * exp(G - G_r)) (k * exp(G_r - G))^T`` and the same with
    ``q`` on the left. The largest array is the pairwise decays', ``C x SUB
    x K``, and lives here only (recomputed in the backward pass)."""
    C = q.shape[-2]

    def blocks(x, size):            # [.., C, K] -> [.., C / size, size, K]
        return x.reshape(x.shape[:-2] + (C // size, size, x.shape[-1]))

    bk = beta[..., None] * k
    kb, Gb = blocks(k, SUB), blocks(G, SUB)
    s = np.arange(SUB)
    lower = (s[:, None] >= s[None, :])[..., None]
    decay = jnp.exp(jnp.where(
        lower, Gb[..., :, None, :] - Gb[..., None, :, :], -jnp.inf))
    kd = kb[..., None, :, :] * decay                    # [.., t, j, K]
    A = [jnp.where(s[:, None] > s[None, :],
                   (blocks(bk, SUB)[..., :, None, :] * kd).sum(-1), 0.0)]
    B = [(blocks(q, SUB)[..., :, None, :] * kd).sum(-1)]
    size = SUB
    while size < C:

        def halves(x):              # each pair's earlier and later block
            x = blocks(x, size)
            return x[..., 0::2, :, :], x[..., 1::2, :, :]

        (_, q2), (k1, _), (G1, G2), (_, bk2) = map(halves, (q, k, G, bk))
        G_first = G2[..., :1, :]
        since = jnp.exp(G2 - G_first)
        until = jnp.swapaxes(k1 * jnp.exp(G_first - G1), -1, -2)
        A.append(_mm32(bk2 * since, until))
        B.append(_mm32(q2 * since, until))
        size *= 2
    return tuple(A), tuple(B)


def _merged(diagonal, lower):
    """``[..., 2 P, s, s]`` blocks on a diagonal and the ``P`` blocks under
    it, each pair's ``[..., P, s, s]`` -> the ``P`` blocks ``[[D1, 0], [L,
    D2]]`` of twice the size."""
    first, second = diagonal[..., 0::2, :, :], diagonal[..., 1::2, :, :]
    return jnp.concatenate([
        jnp.concatenate([first, jnp.zeros_like(first)], -1),
        jnp.concatenate([lower, second], -1)], -2)


def _substitute(a):
    """``(I + a)^-1`` of strictly lower-triangular ``a`` ``[..., SUB,
    SUB]`` by forward substitution, a row a step; the blocks lie in the
    minor dimension meanwhile, so that a step is elementwise over them."""
    lead = a.shape[:-2]
    a = jnp.moveaxis(a.reshape((-1, SUB, SUB)), 0, -1)       # [t, j, blocks]
    eye = jnp.eye(SUB, dtype=a.dtype)[..., None]

    def row(t, X):
        # Rows of X from t on are still the identity's, and a[t, j] = 0 there.
        x_t = eye[t] - (a[t][:, None] * X).sum(0)
        return jax.lax.dynamic_update_index_in_dim(X, x_t, t, 0)

    # A constant typed like ``a`` (see chunk_scan's zero state).
    X = jax.lax.fori_loop(1, SUB, row, eye + jax.lax.full_like(a, 0))
    return jnp.moveaxis(X, -1, 0).reshape(lead + (SUB, SUB))


def _unit_lower_inverse(A):
    """``(I + A)^-1`` from ``A``'s blocks as :func:`_intra_chunk` gives
    them: the :data:`SUB`-row blocks on the diagonal by substitution, every
    block of every chunk and head at once, then merged two and two, ``[[T1,
    0], [-T2 A21 T1, T2]]``, until one block is left: between sub-blocks
    there are matrix products only, forward and backward."""
    T = _substitute(A[0])
    for below in A[1:]:
        T = _merged(T, -_mm32(_mm32(T[..., 1::2, :, :], below),
                              T[..., 0::2, :, :]))
    return T[..., 0, :, :]


@jax.checkpoint
def _chunk_step(S, xs):
    """One chunk against the state ``S`` ``[n, H, K, V]`` that enters it:
    the writes ``U`` its tokens make, their outputs, and the state that
    leaves it. Kept for the backward pass: ``S`` alone."""
    w_v, w_k, q_in, B, k_out, decay_out = xs
    U = w_v - _mm32(w_k, S)                                  # [n, H, C, V]
    out = _mm32(q_in, S) + _mm32(B, U)
    S = decay_out[..., None] * S + _mm32(jnp.swapaxes(k_out, -1, -2), U)
    return S, out


def chunk_scan(q, k, v, g, beta):
    """The gated delta rule ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t))
    S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t`` from ``S_0 = 0``, in
    chunks of :data:`CHUNK` tokens. ``q, k, g`` ``[n, L, H, K]``, ``v``
    ``[n, L, H, V]``, ``beta`` ``[n, L, H]``, float32; ``g <= 0``. Returns
    ``o`` ``[n, L, H, V]``.

    With ``G`` the running sum of ``g`` inside a chunk, the writes ``u_t =
    beta_t (v_t - S~_t^T k_t)`` of a chunk's tokens solve the unit
    lower-triangular system ``(I + A) U = beta (V - (exp(G) K) S_in)``
    (the WY / UT form with a diagonal decay; ``A``, ``B`` as
    :func:`_intra_chunk` gives them), so ``U = W_v - W_k S_in`` with
    ``[W_v, W_k] = (I + A)^-1 [beta V, beta exp(G) K]``, one ``C x C x (V +
    K)`` product with the inverse :func:`_unit_lower_inverse` makes, once
    for all chunks, before the scan; then ``O = (exp(G) Q) S_in + B U`` and
    ``S_out = Diag(exp(G_last)) S_in + (exp(G_last - G) K)^T U``, a
    ``jax.lax.scan`` carrying ``S`` over the sequence's chunks. Every
    exponent is a difference that is never positive, those of a decay split
    at a sub-block's first token too (:func:`_intra_chunk`). A sequence is
    padded to whole chunks with tokens that write nothing and decay
    nothing.

    :class:`KDA` calls it through ``ops.kda_scan.chunk_scan``: on a TPU the
    forward pass is that module's kernel, which does the same arithmetic a
    chunk at a time in VMEM, and this function is what the backward pass
    differentiates (and computes forward once more to do so); elsewhere it
    is the forward pass too."""
    n, L, H, K = q.shape
    N = -(-L // CHUNK)
    pad = N * CHUNK - L

    def chunks(x):          # [n, L, H, ...] -> [N, n, H, CHUNK, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((n, N, CHUNK) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    q, k, v, g, beta = (chunks(x.astype(jnp.float32))
                        for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-2)
    A, B = jax.lax.map(lambda xs: _intra_chunk(*xs), (q, k, G, beta))
    B = functools.reduce(_merged, B)[..., 0, :, :]
    decay_in = jnp.exp(G)
    w = _mm32(_unit_lower_inverse(A),
              beta[..., None] * jnp.concatenate([v, decay_in * k], -1))
    w_v, w_k = w[..., :v.shape[-1]], w[..., v.shape[-1]:]
    G_last = G[..., -1:, :]
    # A zero typed like the inputs (inside ``shard_map``, device-varying
    # where they are: the carry that comes back is).
    _, out = jax.lax.scan(
        _chunk_step, jax.lax.full_like(v, 0, shape=(n, H, K, v.shape[-1])),
        (w_v, w_k, q * decay_in, B, k * jnp.exp(G_last - G),
         jnp.exp(G_last[..., 0, :])))
    out = jnp.moveaxis(jnp.moveaxis(out, 2, 3), 0, 1)        # [n, N, C, H, V]
    return out.reshape((n, N * CHUNK) + out.shape[3:])[:, :L]


def _l2norm(u):
    return u * jax.lax.rsqrt((u * u).sum(-1, keepdims=True) + L2_EPS)


def _scan_inputs(x, p, heads, dtype):
    """Steps 1-4 of the KDA mixer: ``x`` [n, L, W] and the layer's leaves
    -> ``q, k, v, g`` [n, L, H, D] and ``beta`` [n, L, H], float32."""
    n, L, _ = x.shape
    f32 = jnp.float32
    q, k, v = (
        jax.nn.silu(causal_taps(
            mm(x, p[f"{r}_proj"], dtype).astype(f32), p[f"{r}_conv"])
        ).reshape(n, L, heads, -1) for r in "qkv")
    q, k = _l2norm(q) / np.sqrt(q.shape[-1]), _l2norm(k)
    gate_in = mm(mm(x, p["f_a"], dtype), p["f_b"], dtype)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        gate_in.astype(f32) + p["dt_bias"]).reshape(n, L, heads, -1)
    beta = jax.nn.sigmoid(mm(x, p["b_proj"], dtype).astype(f32))
    return q, k, v, g, beta


def _gated_out(o, x, p, eps, dtype):
    """Step 6: the scan's ``o`` [n, L, H, D] through the per-head RMSNorm,
    the output gate and the output projection -> [n, L, W]."""
    n, L, _ = x.shape
    o = o * jax.lax.rsqrt(
        jnp.mean(o * o, axis=-1, keepdims=True) + eps) * p["o_norm"]
    gate = jax.nn.sigmoid(mm(
        mm(x, p["g_a"], dtype), p["g_b"], dtype).astype(jnp.float32))
    return mm(o.reshape(n, L, -1) * gate, p["out_proj"], dtype)


class KDA(nn.Module):
    """The gated delta-rule mixer. Its three parts (what feeds the scan,
    the scan, what follows it) each keep for the backward pass what enters
    them (``x``; ``q, k, v, g, beta``; ``o`` and ``x``) and compute their
    float32 internals again, once, when it gets there: the two around the
    scan as a ``jax.checkpoint``, the scan as ``ops.kda_scan.chunk_scan``, a
    custom VJP whose forward pass is the Pallas kernel where the program is
    lowered for a TPU (the plain :func:`chunk_scan` elsewhere) and whose
    backward pass is ``jax.vjp`` of the checkpointed :func:`chunk_scan` at
    the five kept inputs, which is what the ``jax.checkpoint`` that stood
    here until PR 49 did in the backward pass and what it kept. Sized by
    compiling the benchmark cell's round program (five blocks, 4,096 tokens
    a step, 21 B a parameter of state around it) for a v5e with
    ``scripts/compile_cell.py`` (PERF.md section 6, PR 42): keeping the
    scan's internals instead (``A``, ``B``, the inverse and ``w`` of every
    chunk, from the forward pass to the backward in all four KDA layers)
    would cost 1.6 GiB a step where the blocks keep their residuals, and the
    compiler refuses that program, 16.06 of 15.75 GiB; the two checkpoints
    around the scan buy 0.5 GiB together. Inside the plain scan the chunk
    bodies are checkpoints again (:func:`_intra_chunk`,
    :func:`_chunk_step`)."""

    heads: int
    head_dim: int = 128
    conv_kernel: int = 4
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        n, L, W = x.shape
        H, D, T = self.heads, self.head_dim, self.conv_kernel
        f32 = jnp.float32
        p = {f"{r}_proj": self.param(f"{r}_proj", dense_init, (W, H * D),
                                     f32) for r in "qkv"}
        p.update({f"{r}_conv": self.param(
            f"{r}_conv", nn.initializers.lecun_normal(), (T, H * D), f32)
            for r in "qkv"})
        for name, shape in (("f_a", (W, D)), ("f_b", (D, H * D)),
                            ("b_proj", (W, H)), ("g_a", (W, D)),
                            ("g_b", (D, H * D)), ("out_proj", (H * D, W))):
            p[name] = self.param(name, dense_init, shape, f32)
        p["A_log"] = self.param("A_log", a_log_init, (H,), f32)
        p["dt_bias"] = self.param("dt_bias", dt_bias_init, (H * D,), f32)
        p["o_norm"] = self.param("o_norm", nn.initializers.ones, (D,), f32)

        with jax.named_scope("kda.projections"):
            q, k, v, g, beta = jax.checkpoint(
                _scan_inputs, static_argnums=(2, 3))(x, p, H, self.dtype)
        with jax.named_scope("kda.chunk_scan"):
            # Imported where a KDA layer is traced: a process that builds no
            # such model (every other family's) does not load Pallas, a
            # second of its start.
            from olearning_sim_tpu.ops import kda_scan

            o, kernel_chunks = kda_scan.chunk_scan(chunk_scan, q, k, v, g, beta)
        with jax.named_scope("kda.projections"):
            y = jax.checkpoint(_gated_out, static_argnums=(3, 4))(
                o, x, p, self.eps, self.dtype)
        self.sow("intermediates", "kda_stats", jnp.stack(
            [jnp.int32(n * L), jnp.int32(n * -(-L // CHUNK)), jnp.int32(0),
             jnp.int32(0), kernel_chunks]))
        return y


class MLA(nn.Module):
    """Latent attention: keys and values expanded from one normed
    ``kv_rank``-wide latent, no rotary embedding."""

    heads: int
    kv_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        n, L, W = x.shape
        H, R = self.heads, self.kv_rank
        Dn, Dr, Dv = self.qk_nope_dim, self.qk_rope_dim, self.v_dim
        f32 = jnp.float32
        q_proj = self.param("q_proj", dense_init, (W, H * (Dn + Dr)), f32)
        kv_a = self.param("kv_a", dense_init, (W, R + Dr), f32)
        kv_b = self.param("kv_b", dense_init, (R, H * (Dn + Dv)), f32)
        out_proj = self.param("out_proj", dense_init, (H * Dv, W), f32)
        kv_norm = RMSNorm(self.eps, name="kv_norm")
        with jax.named_scope("mla.attention"):
            q = mm(x, q_proj, self.dtype).reshape(n, L, H, 1, Dn + Dr)
            c, k_r = jnp.split(mm(x, kv_a, self.dtype), [R], axis=-1)
            k_n, v = jnp.split(
                mm(kv_norm(c), kv_b, self.dtype).reshape(n, L, H, Dn + Dv),
                [Dn], axis=-1)
            k = jnp.concatenate(
                [k_n, jnp.broadcast_to(k_r[:, :, None], (n, L, H, Dr))], -1)
            # One query head a key/value head; by query blocks, the scores
            # recomputed in the backward pass.
            ctx = attend(q, k, v)
            out = mm(ctx.reshape(n, L, H * Dv), out_proj, self.dtype)
        self.sow("intermediates", "kda_stats", jnp.pad(
            sown_attend_pairs(n, L, 2), (0, 1)))
        return out


class Block(nn.Module):
    """One decoder layer: the mixer of ``kind`` (``"kda"`` or ``"mla"``),
    then the dense MLP (``num_experts`` 0) or the routed experts plus the
    shared ones, each on the RMS-normed residual stream."""

    kind: str
    heads: int
    kda_head_dim: int
    conv_kernel: int
    kv_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    mlp_dim: int
    num_experts: int
    experts_per_token: int
    held_experts: Tuple[int, ...]
    num_shared_experts: int
    norm_eps: float
    norm_topk_prob: bool
    routed_scaling_factor: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h):
        x = RMSNorm(self.norm_eps, name="operator_norm")(h)
        if self.kind == "kda":
            y = KDA(self.heads, self.kda_head_dim, self.conv_kernel,
                    self.norm_eps, self.dtype, name="kda")(x)
        elif self.kind == "mla":
            y = MLA(self.heads, self.kv_rank, self.qk_nope_dim,
                    self.qk_rope_dim, self.v_dim, self.norm_eps, self.dtype,
                    name="mla")(x)
        else:
            raise ValueError(f"unknown layer type {self.kind!r}")
        h = h + y.astype(jnp.float32)
        x = RMSNorm(self.norm_eps, name="ffn_norm")(h)
        if self.num_experts == 0:
            return h + SwiGLU(self.mlp_dim, self.dtype, name="mlp")(
                x).astype(jnp.float32)
        y = moe.DroplessMoE(
            self.num_experts, self.experts_per_token, self.held_experts,
            self.mlp_dim, self.norm_topk_prob, self.routed_scaling_factor,
            dtype=self.dtype, name="moe")(x).astype(jnp.float32)
        if self.num_shared_experts:
            # Every token, weight 1: what every chip computes alike.
            with jax.named_scope("moe.shared_expert"):
                y = y + SwiGLU(self.num_shared_experts * self.mlp_dim,
                               self.dtype, name="shared")(
                                   x).astype(jnp.float32)
        return h + y


class KimiLinear(nn.Module):
    """The stack: embedding, one :class:`Block` a layer, the final norm and
    the untied head.

    **No block is computed again in the backward pass**: a block keeps what
    its forward pass produced outside the parts that carry a checkpoint of
    their own (the residual stream, the norms' outputs, an MLA layer's q, k,
    v and context, the dense MLP's and the shared expert's hidden products;
    the routed layer keeps no array of its own, only its input and routing
    vectors), and the backward pass
    computes again only what those checkpoints cover: each of a KDA
    mixer's three parts once (:class:`KDA`), the chunk bodies inside the
    scan, the MLA scores (``decoder_parts.attend``), and the routed experts' rows
    and hidden products, which the backward loop of ``models/moe.py``
    gathers and multiplies again a window at a time. ``nn.remat`` around
    every block would run
    each block's whole forward a second time (a quarter of the benchmark
    cell's round, until PR 42) to save 2.98 GiB of the cell's 4,096-token
    step. Around one block alone it gives back, by the compiler's
    temporaries, 0.59 GiB at the dense KDA block, 0.60 at a KDA block with
    experts and 0.94 at the MLA block: a block keeps about 150 KB a token
    (MLA with experts: 245 KB), and every token of a local step beyond the
    cell's 4,096 pays that in each block. By the compiler's own check
    (reserved 0.258 + arguments + HLO temporaries <= 15.75 GiB) the cell's
    round program has about 0.2 GiB to spare, and the chip's peak with the
    evaluation beside it is 14.71 GB; a longer step needs ``nn.remat`` back
    around some blocks, the MLA one first (0.94 GiB for 4% of the round;
    PERF.md section 6, PR 42, has every policy compiled and the chip's
    readings)."""

    vocab_size: int = 163840
    max_len: int = 1048576          # positions served; no position table
    width: int = 2304
    layer_types: Sequence[str] = ("kda", "kda", "kda", "mla")
    num_dense_layers: int = 1       # leading layers with the dense MLP
    heads: int = 32
    kda_head_dim: int = 128
    conv_kernel: int = 4
    kv_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    mlp_dim: int = 9216
    moe_mlp_dim: int = 1024
    num_experts: int = 256          # the router's width
    experts_per_token: int = 8
    held_experts: Sequence[int] = tuple(range(256))
    num_shared_experts: int = 1
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens):
        if tokens.shape[-1] > self.max_len:
            raise ValueError(
                f"sequence of {tokens.shape[-1]} tokens, max_len is "
                f"{self.max_len}")
        h = LookupOnlyEmbed(
            self.vocab_size, self.width, name="embed",
            embedding_init=nn.initializers.normal(stddev=0.02),
            param_dtype=jnp.float32)(tokens)
        for i, kind in enumerate(self.layer_types):
            dense = i < self.num_dense_layers
            # A plain ``Block``: its residuals are kept (150-245 KB a token)
            # and its forward runs once; the class docstring has what that
            # costs and when ``nn.remat`` has to come back.
            h = Block(
                kind=kind, heads=self.heads, kda_head_dim=self.kda_head_dim,
                conv_kernel=self.conv_kernel, kv_rank=self.kv_rank,
                qk_nope_dim=self.qk_nope_dim, qk_rope_dim=self.qk_rope_dim,
                v_dim=self.v_dim,
                mlp_dim=self.mlp_dim if dense else self.moe_mlp_dim,
                # No experts: the dense MLP.
                num_experts=0 if dense else self.num_experts,
                experts_per_token=self.experts_per_token,
                held_experts=tuple(self.held_experts),
                num_shared_experts=self.num_shared_experts,
                norm_eps=self.norm_eps, norm_topk_prob=self.norm_topk_prob,
                routed_scaling_factor=self.routed_scaling_factor,
                dtype=self.dtype, name=f"layers_{i}")(h)
        h = RMSNorm(self.norm_eps, name="final_norm")(h)
        head = self.param("head", dense_init,
                          (self.width, self.vocab_size), jnp.float32)
        return jnp.dot(h.astype(self.dtype), head.astype(self.dtype),
                       preferred_element_type=jnp.float32)


register_model(
    ModelSpec(
        name="kimi_linear",
        builder=KimiLinear,
        example_input_shape=(64,),
        # A language model: its "classes" are its vocabulary.
        num_classes=163840,
        input_dtype=np.int32,
        # DroplessMoE's jax.lax.ragged_dot has no batching rule for
        # per-client expert weights.
        vmap_clients=False,
        work_counts=work_counts_beside("kda_stats", STATS),
        defaults={
            "vocab_size": 163840, "max_len": 1048576, "width": 2304,
            "layer_types": ["kda", "kda", "kda", "mla"],
            "num_dense_layers": 1, "heads": 32, "kda_head_dim": 128,
            "conv_kernel": 4, "kv_rank": 512, "qk_nope_dim": 128,
            "qk_rope_dim": 64, "v_dim": 128, "mlp_dim": 9216,
            "moe_mlp_dim": 1024, "num_experts": 256,
            "experts_per_token": 8, "held_experts": list(range(256)),
            "num_shared_experts": 1, "norm_eps": 1e-5,
            "routed_scaling_factor": 2.446,
        },
    )
)
