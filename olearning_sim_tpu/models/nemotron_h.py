"""Nemotron-H decoder family (``model_type: nemotron_h``; sizes from the
public ``nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16`` config.json,
which is, key for key, one Nemotron-H tower): a next-token language model
of pre-norm residual layers, each of which is ONE of three things behind one
RMSNorm, by its letter in ``pattern``:

    h = embed(tokens)
    for each letter:   h = h + f(rms(h))        f: M, E or *
    logits = rms(h) @ head                      (untied: a leaf of its own)

**M, Mamba-2** (``d_inner`` = ``mamba_heads`` x ``mamba_head_dim``; ``G``
groups of B and C, ``N`` = ``state_size``): ``[z, xBC, dt] = u W_in``;
``xBC = silu(conv(xBC) + b)``, one causal depthwise convolution of
``conv_kernel`` taps over x, B and C together; ``x`` a head ``P`` =
``mamba_head_dim`` wide, ``B``, ``C`` a group ``N`` wide (head ``h`` reads
group ``h // (heads / G)``); step sizes ``D_t = softplus(dt_t + dt_bias)``,
decay ``a_t = exp(D_t * A)``, ``A = -exp(A_log)``, one number a head; the
recurrence over a ``P x N`` state

    S_t = a_t S_{t-1} + D_t x_t B_t^T
    y_t = S_t C_t + D x_t

and ``out = rms_grouped(y * silu(z)) W_out`` (the gate before the norm, the
norm over ``G`` groups of ``d_inner / G``). The recurrence is computed
chunked (:func:`chunk_scan`): inside a chunk of ``chunk_size`` tokens a
masked matrix of pairwise decays times ``C B^T``, between chunks a
``jax.lax.scan`` that carries the state.

**E**: a routed expert layer (:class:`~olearning_sim_tpu.models.moe.
DroplessMoE` in its two-matrix form, ``W_down(relu(W_up h)^2)``) plus one
shared expert of the same form that every token passes.

**\\***: causal grouped-query attention, ``heads`` query heads over
``kv_heads`` key/value heads of ``head_dim``, no bias, no positional
embedding (the family's modelling code applies none in its attention
layers), scores in float32.

What one chip of a deployment holds is a matter of the sizes given, as in
``models/lfm2.py``: ``held_experts``, ``vocab_size`` (rows of the embedding
and of the head held here) and ``pattern`` (this pipeline stage's layers).
Nothing here stands in for the other chips.

Precision: float32 parameters; matmul inputs and outputs in ``dtype``
(bfloat16); the residual stream, the norms, the convolution, the router,
the softmax and the logits in float32, and float32 for everything inside
the recurrence that carries a decay: the step sizes, the log decay and its
running sums, every exponential, the state and the products with any of
them (``Precision.HIGHEST``).

The embedding is only looked up (:class:`~olearning_sim_tpu.models.lookup.
LookupOnlyEmbed`), so a trainer may train it by the rows a step reads. Every
Mamba-2 layer sows ``ssd_stats`` (:data:`STATS`): the tokens and the chunks
its scan took; every attention layer, the pairs its mask lets through and the
scores it formed.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from olearning_sim_tpu.models.decoder_parts import (
    RMSNorm, a_log_init, attend, causal_taps, dense_init, dt_bias_init, mm,
    sown_attend_pairs, work_counts_beside)
from olearning_sim_tpu.models.lookup import LookupOnlyEmbed
from olearning_sim_tpu.models import moe
from olearning_sim_tpu.models.registry import ModelSpec, register_model

# What a Mamba-2 or attention layer sows as ``ssd_stats`` on every call, one
# int32 vector: the tokens and chunks of a Mamba-2 layer's scan, the (query,
# key) pairs an attention layer's mask lets through, a head, and the scores
# a head formed for them (``decoder_parts.attend_pairs``).
STATS = ("ssd_scan_tokens", "ssd_scan_chunks", "attend_pairs_needed",
         "attend_pairs_computed")
_HIGHEST = jax.lax.Precision.HIGHEST


def _intra_chunk(x, B, C, G):
    """What a chunk's own tokens give its outputs, and what they leave in
    the state at its end. ``x`` ``[..., Q, g, r, P]`` (the step size already
    in it: ``D_s x_s``), ``B``, ``C`` ``[..., Q, g, N]``, ``G`` ``[..., Q,
    g, r]`` the running sum of the log decay from the chunk's start, heads
    as ``g`` groups of ``r``. Returns ``y`` like ``x`` and the chunk's
    contribution ``[..., g, r, P, N]``.

    ``y_t = sum_{s <= t} exp(G_t - G_s) (C_t . B_s) x_s``: one ``Q x Q``
    matrix of ``C B^T`` a group, times one of pairwise decays a head, every
    exponent a difference that is never positive (``exp(G_t) * exp(-G_s)``
    would underflow one factor and overflow the other)."""
    Q = x.shape[-4]
    cb = jnp.einsum("...tgn,...sgn->...gts", C, B, precision=_HIGHEST)
    Gh = jnp.moveaxis(G, -3, -1)                            # [.., g, r, Q]
    lower = np.tril(np.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(
        lower, Gh[..., :, None] - Gh[..., None, :], -jnp.inf))
    y = jnp.einsum("...grts,...sgrp->...tgrp",
                   decay * cb[..., None, :, :], x, precision=_HIGHEST)
    to_end = jnp.exp(G[..., -1:, :, :] - G)                 # [.., Q, g, r]
    left = jnp.einsum("...sgrp,...sgn->...grpn", x * to_end[..., None], B,
                      precision=_HIGHEST)
    return y, left


def chunk_scan(x, dt, A, B, C, chunk: int):
    """The Mamba-2 (SSD) recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T``, ``y_t = S_t C_t`` from ``S_0 = 0``, in chunks of ``chunk``
    tokens. ``x`` ``[n, L, H, P]``, ``dt`` ``[n, L, H]`` (positive), ``A``
    ``[H]`` (negative), ``B``, ``C`` ``[n, L, g, N]`` (head ``h`` reads
    group ``h // (H / g)``), float32. Returns ``y`` ``[n, L, H, P]``.

    With ``G`` the running sum of ``dt A`` inside a chunk, a token's output
    is its chunk's own part (:func:`_intra_chunk`) plus what the state that
    entered the chunk still gives it, ``exp(G_t) S_in C_t``; the state that
    leaves a chunk is ``exp(G_last) S_in`` plus the chunk's contribution,
    a ``jax.lax.scan`` carrying ``S`` over the sequence's chunks. Every
    exponent is a sum or a difference that is never positive.

    ``L`` must be whole chunks: the traffic that reaches this model gives
    sequences of whole chunks only (a tail would have to be padded with
    tokens whose step size is zero, which write nothing and decay
    nothing)."""
    n, L, H, P = x.shape
    g, N = B.shape[-2:]
    if L % chunk:
        raise ValueError(
            f"chunk_scan: {L} tokens are not whole chunks of {chunk}")
    c, r = L // chunk, H // g

    def chunks(a, *tail):       # [n, L, ...] -> [c, n, chunk, *tail]
        return jnp.moveaxis(a.reshape((n, c, chunk) + tail), 1, 0)

    G = jnp.cumsum(chunks(dt * A, g, r), axis=2)
    B, C = chunks(B, g, N), chunks(C, g, N)
    y, left = _intra_chunk(chunks(x * dt[..., None], g, r, P), B, C, G)

    def step(S, xs):            # S [n, g, r, P, N]: the state that enters
        left_c, keep = xs
        return keep[..., None, None] * S + left_c, S

    # A zero typed like the inputs (inside ``shard_map``, device-varying
    # where they are: the carry that comes back is).
    _, entered = jax.lax.scan(
        step, jax.lax.full_like(left[0], 0), (left, jnp.exp(G[:, :, -1])))
    y = y + jnp.exp(G)[..., None] * jnp.einsum(
        "cbtgn,cbgrpn->cbtgrp", C, entered, precision=_HIGHEST)
    return jnp.moveaxis(y, 0, 1).reshape(n, L, H, P)


def _scan_inputs(u, p, heads, groups, state, dtype):
    """What feeds the scan: ``u`` [n, L, W] and the layer's leaves -> the
    gate ``z`` [n, L, d_inner] (``dtype``), ``x`` [n, L, H, P], the step
    sizes [n, L, H], ``B`` and ``C`` [n, L, g, N], float32."""
    n, L, _ = u.shape
    f32 = jnp.float32
    d_inner = p["out_proj"].shape[0]
    z, xBC, dt = jnp.split(mm(u, p["in_proj"], dtype),
                           [d_inner, p["in_proj"].shape[1] - heads], axis=-1)
    xBC = jax.nn.silu(
        causal_taps(xBC.astype(f32), p["conv"]) + p["conv_bias"])
    x, B, C = jnp.split(xBC, [d_inner, d_inner + groups * state], axis=-1)
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"])
    return (z, x.reshape(n, L, heads, -1), dt,
            B.reshape(n, L, groups, state), C.reshape(n, L, groups, state))


def _gated_out(y, x, z, p, groups, eps, dtype):
    """What follows the scan: its ``y`` and its input ``x`` [n, L, H, P]
    and the gate ``z`` -> the skip ``D x``, the gate, the grouped RMSNorm
    and the output projection -> [n, L, W]."""
    n, L, H, _ = y.shape
    y = (y + p["D"][:, None] * x).reshape(n, L, -1)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(n, L, groups, -1)
    y = y * jax.lax.rsqrt(
        jnp.mean(y * y, axis=-1, keepdims=True) + eps) * p["norm"]
    return mm(y.reshape(n, L, -1), p["out_proj"], dtype)


class Mamba2(nn.Module):
    """The Mamba-2 mixer: what feeds the scan, the scan, what follows
    it."""

    heads: int = 64
    head_dim: int = 64
    state_size: int = 128
    groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        n, L, W = u.shape
        H, g, N, T = self.heads, self.groups, self.state_size, self.conv_kernel
        d_inner, conv_dim = H * self.head_dim, H * self.head_dim + 2 * g * N
        f32 = jnp.float32
        p = {
            "in_proj": self.param("in_proj", dense_init,
                                  (W, d_inner + conv_dim + H), f32),
            "conv": self.param("conv", nn.initializers.lecun_normal(),
                               (T, conv_dim), f32),
            "conv_bias": self.param("conv_bias", nn.initializers.zeros,
                                    (conv_dim,), f32),
            # A step log-uniform in [time_step_min 0.001, time_step_max 0.1]:
            # the published time_step_floor 1e-4 lies under it.
            "dt_bias": self.param("dt_bias", dt_bias_init, (H,), f32),
            "A_log": self.param("A_log", a_log_init, (H,), f32),
            "D": self.param("D", nn.initializers.ones, (H,), f32),
            # The gated norm's scale, a row a group.
            "norm": self.param("norm", nn.initializers.ones,
                               (g, d_inner // g), f32),
            "out_proj": self.param("out_proj", dense_init, (d_inner, W),
                                   f32),
        }
        with jax.named_scope("ssd.projections"):
            z, x, dt, B, C = _scan_inputs(u, p, H, g, N, self.dtype)
        with jax.named_scope("ssd.chunk_scan"):
            y = chunk_scan(
                x, dt, -jnp.exp(p["A_log"]), B, C, self.chunk_size)
        with jax.named_scope("ssd.projections"):
            out = _gated_out(y, x, z, p, g, self.eps, self.dtype)
        self.sow("intermediates", "ssd_stats",
                 jnp.asarray([n * L, n * (L // self.chunk_size), 0, 0],
                             jnp.int32))
        return out


class Attention(nn.Module):
    """Causal grouped-query attention with no positional embedding."""

    heads: int
    kv_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        n, L, W = x.shape
        H, G, D = self.heads, self.kv_heads, self.head_dim
        wq = self.param("q_proj", dense_init, (W, H * D), jnp.float32)
        wk = self.param("k_proj", dense_init, (W, G * D), jnp.float32)
        wv = self.param("v_proj", dense_init, (W, G * D), jnp.float32)
        wo = self.param("out_proj", dense_init, (H * D, W), jnp.float32)
        with jax.named_scope("nemotron_h.attention"):
            q = mm(x, wq, self.dtype).reshape(n, L, G, H // G, D)
            k = mm(x, wk, self.dtype).reshape(n, L, G, D)
            v = mm(x, wv, self.dtype).reshape(n, L, G, D)
            # By query blocks; the scores are recomputed in the backward
            # pass.
            ctx = attend(q, k, v)
            out = mm(ctx.reshape(n, L, H * D), wo, self.dtype)
        self.sow("intermediates", "ssd_stats", sown_attend_pairs(n, L, 2))
        return out


class ReLU2(nn.Module):
    """``W2(relu(W1 x)^2)``: the family's feed-forward, no gate, no bias."""

    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        W = x.shape[-1]
        w1 = self.param("w1", dense_init, (W, self.mlp_dim), jnp.float32)
        w2 = self.param("w2", dense_init, (self.mlp_dim, W), jnp.float32)
        a = jax.nn.relu(mm(x, w1, self.dtype))
        return mm(a * a, w2, self.dtype)


class Layer(nn.Module):
    """One layer: ``h + f(rms(h))``, ``f`` by ``kind``: ``"M"`` the Mamba-2
    mixer, ``"E"`` the routed experts plus the shared one, ``"*"``
    attention.

    What a layer leaves for the backward pass when nothing computes it again
    (:class:`NemotronH` decides by ``kind``; bfloat16 unless said): ``"*"``
    q, k, v and the context, 17 KB a token (the scores never:
    ``decoder_parts.attend`` computes them again, a block of queries against the
    keys up to its end); ``"E"`` the per-assignment arrays,
    ``experts_per_token`` rows a token (the gathered inputs, both grouped
    products' results, the float32 combine) and the shared expert's hidden
    product, about 140 KB a token; ``"M"`` the fused projection's
    10,304-wide result, the convolution's, the scan's float32 inputs,
    running sums and pairwise decays and the gate, about 215 KB a token."""

    kind: str
    mamba_heads: int
    mamba_head_dim: int
    state_size: int
    groups: int
    conv_kernel: int
    chunk_size: int
    heads: int
    kv_heads: int
    head_dim: int
    moe_mlp_dim: int
    shared_mlp_dim: int
    num_experts: int
    experts_per_token: int
    held_experts: Tuple[int, ...]
    norm_eps: float
    norm_topk_prob: bool
    routed_scaling_factor: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h):
        x = RMSNorm(self.norm_eps, name="norm")(h)
        if self.kind == "M":
            y = Mamba2(self.mamba_heads, self.mamba_head_dim, self.state_size,
                       self.groups, self.conv_kernel, self.chunk_size,
                       eps=self.norm_eps, dtype=self.dtype, name="mamba")(x)
        elif self.kind == "*":
            y = Attention(self.heads, self.kv_heads, self.head_dim,
                          self.dtype, name="attn")(x)
        elif self.kind == "E":
            y = moe.DroplessMoE(
                self.num_experts, self.experts_per_token, self.held_experts,
                self.moe_mlp_dim, self.norm_topk_prob,
                self.routed_scaling_factor, dtype=self.dtype, gated=False,
                name="moe")(x).astype(jnp.float32)
            # Every token, weight 1: what every chip computes alike.
            with jax.named_scope("moe.shared_expert"):
                y = y + ReLU2(self.shared_mlp_dim, self.dtype,
                              name="shared")(x).astype(jnp.float32)
        else:
            raise ValueError(f"unknown layer letter {self.kind!r}")
        return h + y.astype(jnp.float32)


class NemotronH(nn.Module):
    """The stack: embedding, one :class:`Layer` a letter of ``pattern``, the
    final norm and the untied head.

    **Which layers the backward pass computes again is chosen by the
    layer's letter**, not by a knob, and since PR 48 the choice is none: no
    layer is wrapped in ``nn.remat``; every layer keeps its residuals
    (:class:`Layer` has their sizes) and the backward pass computes again
    only what the parts' own checkpoints cover, ``decoder_parts.attend``'s scores
    and an ``"E"`` layer's windows (``models/moe.py``). Sized by compiling
    the benchmark cell's round program (``MEMEM*E``, 4,096 tokens a step, 8
    held experts, 21 B a parameter of state around it) for a v5e with
    ``scripts/compile_cell.py``: temporaries 13.982 GiB, ``peak_memory``
    12.179 GiB, and the compiler's own check (0.258 reserved + 1.968 of
    arguments + temporaries less the 1.968 of outputs that share the
    donated arguments <= 15.75 GiB) leaves 1.51 GiB; on the chip the round
    with its evaluation peaks at 13.47 GB of 16.9. ``nn.remat`` around the
    three ``"M"`` layers bought 2.34 GiB there (205 KB a token a layer by
    the compiler, temporaries 11.640) and cost their second forward, 0.25
    s of a 2.82 s round on the chip. What a caller gives up: every token of
    a local step beyond the cell's 4,096 needs about 1.1 MB (three ``"M"``
    layers' residuals are 0.6 of it; 0.5 MB with them computed again), so
    about 1,400 more tokens a step still fit beside that state, and a third
    sequence of 2,048 does not (temporaries 16.064 GiB: the compiler then
    fits the program some other way, with a third of the code, at a price
    nobody has measured). A fuller stage puts ``nn.remat`` back around
    ``Layer`` where ``kind == "M"`` first: the cheapest second pass, proven
    to change no value (``tests/test_nemotron_h.py``), and with it three
    sequences a step compile with 2.87 GiB to spare. An ``"E"`` layer's
    ``nn.remat`` bought 0.5 GiB for the dearest second pass when PR 40
    took it off (the sort, the gathers and the combine over
    ``experts_per_token`` rows a token) and buys less since PR 47 (its
    routed part keeps only its input and routing vectors); an attention
    layer's bought nothing (PERF.md section 6, PR 48, has the compiler's
    table of the policies and the chip's readings)."""

    vocab_size: int = 131072
    max_len: int = 262144           # positions served; no position table
    width: int = 2688
    pattern: str = "MEMEM*E"        # hybrid_override_pattern, this stage's
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    state_size: int = 128
    groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    moe_mlp_dim: int = 1856
    shared_mlp_dim: int = 3712
    num_experts: int = 128          # the router's width
    experts_per_token: int = 6
    held_experts: Sequence[int] = tuple(range(128))
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
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
        for i, kind in enumerate(self.pattern):
            # No layer is wrapped in nn.remat: an M layer's 215 KB a token
            # are kept like an attention layer's 17 (an E layer's routed
            # part keeps its input). Sized against the compiler's check,
            # 1.51 GiB to spare: the class docstring.
            h = Layer(
                kind=kind, mamba_heads=self.mamba_heads,
                mamba_head_dim=self.mamba_head_dim,
                state_size=self.state_size, groups=self.groups,
                conv_kernel=self.conv_kernel, chunk_size=self.chunk_size,
                heads=self.heads, kv_heads=self.kv_heads,
                head_dim=self.head_dim,
                moe_mlp_dim=self.moe_mlp_dim,
                shared_mlp_dim=self.shared_mlp_dim,
                num_experts=self.num_experts,
                experts_per_token=self.experts_per_token,
                held_experts=tuple(self.held_experts),
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
        name="nemotron_h",
        builder=NemotronH,
        example_input_shape=(128,),
        # A language model: its "classes" are its vocabulary.
        num_classes=131072,
        input_dtype=np.int32,
        # DroplessMoE's jax.lax.ragged_dot has no batching rule for
        # per-client expert weights.
        vmap_clients=False,
        work_counts=work_counts_beside("ssd_stats", STATS),
        defaults={
            "vocab_size": 131072, "max_len": 262144, "width": 2688,
            "pattern": "MEMEM*E", "mamba_heads": 64, "mamba_head_dim": 64,
            "state_size": 128, "groups": 8, "conv_kernel": 4,
            "chunk_size": 128, "heads": 32, "kv_heads": 2, "head_dim": 128,
            "moe_mlp_dim": 1856, "shared_mlp_dim": 3712, "num_experts": 128,
            "experts_per_token": 6, "held_experts": list(range(128)),
            "norm_eps": 1e-5, "routed_scaling_factor": 2.5,
        },
    )
)
