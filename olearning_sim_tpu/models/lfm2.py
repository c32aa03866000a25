"""LFM2-MoE decoder family (``model_type: lfm2_moe``; sizes from the public
``LiquidAI/LFM2-24B-A2B`` config.json): a next-token language model of
pre-norm residual blocks whose sequence operator is either a gated short
convolution or causal grouped-query attention, and whose feed-forward is a
dense SwiGLU MLP in the leading dense layers and a routed expert layer
(:class:`~olearning_sim_tpu.models.moe.DroplessMoE`) in the others.

    h = embed(tokens)
    for each layer:   h = h + operator(rms(h));   h = h + ffn(rms(h))
    logits = rms(h) @ embed.T                     (head tied to the embedding)

- gated short convolution: ``B, C, x = split(in_proj(h), 3)``;
  ``y = out_proj(C * conv1d_causal_depthwise(B * x, kernel conv_kernel))``,
  no bias anywhere;
- attention: ``heads`` query heads over ``kv_heads`` key/value heads of size
  ``width // heads``, RMSNorm over the head size on q and k, then rotary
  embedding (the rotate-half form, base ``rope_theta``), causal softmax;
- tokens in, ``[n, L, vocab_size]`` float32 logits out: the engine's
  ``task: "next_token"`` loss reads them (``engine/fedcore.py``).

What one chip of an expert-parallel deployment holds is a matter of the
sizes given: ``held_experts`` (ids of the experts of every expert layer
that live here; the router keeps ``num_experts`` outputs), ``vocab_size``
(the rows of the embedding held here; ids, logits and loss are over them)
and ``layer_types`` (the layers of this pipeline stage). Nothing here
stands in for the other chips.

Precision: float32 parameters; matmul inputs and their outputs in ``dtype``
(bfloat16); the residual stream, the norms, rotary embedding, the router
(logits at ``Precision.HIGHEST``, sigmoid, bias, top-k, weights), the
attention softmax and the logits in float32.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

# BLOCK and attend_pairs are not used below: the benchmark's own tests read
# them as this module's (tests/benchmark/test_layer_attention_pairs.py).
from olearning_sim_tpu.models.decoder_parts import (  # noqa: F401
    BLOCK, RMSNorm, SwiGLU, attend, attend_pairs, dense_init, mm,
    sown_attend_pairs, work_counts_beside)
from olearning_sim_tpu.models.moe import DroplessMoE
from olearning_sim_tpu.models.registry import ModelSpec, register_model


class ShortConv(nn.Module):
    """The gated short-convolution operator."""

    kernel_size: int = 3
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        W, K = x.shape[-1], self.kernel_size
        in_proj = self.param("in_proj", dense_init, (W, 3 * W), jnp.float32)
        # conv[j] multiplies the input K-1-j positions back.
        conv = self.param("conv", nn.initializers.lecun_normal(), (K, W),
                          jnp.float32)
        out_proj = self.param("out_proj", dense_init, (W, W), jnp.float32)
        with jax.named_scope("lfm2.short_conv"):
            b, c, u = jnp.split(mm(x, in_proj, self.dtype), 3, axis=-1)
            bu = jnp.pad((b * u).astype(jnp.float32),
                         ((0, 0), (K - 1, 0), (0, 0)))
            L = x.shape[1]
            y = sum(conv[j] * bu[:, j:j + L] for j in range(K))
            return mm(c.astype(jnp.float32) * y, out_proj, self.dtype)


def _rotary(x, theta: float):
    """Rotary embedding of ``x`` [n, L, heads, D] in float32, the
    rotate-half form: pairs are (i, i + D/2)."""
    L, D = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    angles = np.arange(L, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# What an attention layer sows as ``lfm2_stats`` on every call, one int32
# vector: the (query, key) pairs its causal mask lets through, a head, and
# the scores a head formed for them (``decoder_parts.attend_pairs``, by the
# sequences).
STATS = ("attend_pairs_needed", "attend_pairs_computed")


class CausalGQA(nn.Module):
    """Causal grouped-query attention with q/k RMSNorm before rotary."""

    heads: int
    kv_heads: int
    rope_theta: float = 1e6
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        n, L, W = x.shape
        H, G = self.heads, self.kv_heads
        D = W // H
        wq = self.param("q_proj", dense_init, (W, H * D), jnp.float32)
        wk = self.param("k_proj", dense_init, (W, G * D), jnp.float32)
        wv = self.param("v_proj", dense_init, (W, G * D), jnp.float32)
        wo = self.param("out_proj", dense_init, (H * D, W), jnp.float32)
        q_norm, k_norm = (RMSNorm(self.eps, name="q_norm"),
                          RMSNorm(self.eps, name="k_norm"))
        with jax.named_scope("lfm2.attention"):
            q = mm(x, wq, self.dtype).reshape(n, L, H, D)
            k = mm(x, wk, self.dtype).reshape(n, L, G, D)
            v = mm(x, wv, self.dtype).reshape(n, L, G, D)
            q = _rotary(q_norm(q), self.rope_theta)
            k = _rotary(k_norm(k), self.rope_theta)
            ctx = attend(q.reshape(n, L, G, H // G, D).astype(self.dtype),
                          k.astype(self.dtype), v)
            out = mm(ctx.reshape(n, L, H * D), wo, self.dtype)
        self.sow("intermediates", "lfm2_stats", sown_attend_pairs(n, L))
        return out


class LFM2(nn.Module):
    vocab_size: int = 65536
    max_len: int = 128000           # positions served; rotary has no table
    width: int = 2048
    layer_types: Sequence[str] = ("conv", "conv", "full_attention", "conv")
    num_dense_layers: int = 2       # leading layers with the dense MLP
    heads: int = 32
    kv_heads: int = 8
    mlp_dim: int = 11776
    moe_mlp_dim: int = 1536
    num_experts: int = 64           # the router's width
    experts_per_token: int = 4
    held_experts: Sequence[int] = tuple(range(64))
    conv_kernel: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens):
        if tokens.shape[-1] > self.max_len:
            raise ValueError(
                f"sequence of {tokens.shape[-1]} tokens, max_len is "
                f"{self.max_len}")
        embed = nn.Embed(
            self.vocab_size, self.width, name="embed",
            embedding_init=nn.initializers.normal(stddev=0.02),
            param_dtype=jnp.float32)
        h = embed(tokens)
        for i, kind in enumerate(self.layer_types):
            dense = i < self.num_dense_layers
            h = Block(
                kind=kind, heads=self.heads, kv_heads=self.kv_heads,
                mlp_dim=self.mlp_dim if dense else self.moe_mlp_dim,
                # No experts: the dense MLP.
                num_experts=0 if dense else self.num_experts,
                experts_per_token=self.experts_per_token,
                held_experts=tuple(self.held_experts),
                conv_kernel=self.conv_kernel, norm_eps=self.norm_eps,
                rope_theta=self.rope_theta,
                norm_topk_prob=self.norm_topk_prob,
                routed_scaling_factor=self.routed_scaling_factor,
                dtype=self.dtype, name=f"layers_{i}")(h)
        h = RMSNorm(self.norm_eps, name="final_norm")(h)
        return jnp.dot(h.astype(self.dtype),
                       embed.embedding.astype(self.dtype).T,
                       preferred_element_type=jnp.float32)


class Block(nn.Module):
    """One decoder layer: the operator of ``kind`` (``"conv"`` or
    ``"full_attention"``), then the dense MLP (``num_experts`` 0) or the
    routed experts, each on the RMS-normed residual stream."""

    kind: str
    heads: int
    kv_heads: int
    mlp_dim: int
    num_experts: int
    experts_per_token: int
    held_experts: Tuple[int, ...]
    conv_kernel: int
    norm_eps: float
    rope_theta: float
    norm_topk_prob: bool
    routed_scaling_factor: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h):
        x = RMSNorm(self.norm_eps, name="operator_norm")(h)
        if self.kind == "conv":
            y = ShortConv(self.conv_kernel, self.dtype, name="conv")(x)
        elif self.kind == "full_attention":
            y = CausalGQA(self.heads, self.kv_heads, self.rope_theta,
                          self.norm_eps, self.dtype, name="attn")(x)
        else:
            raise ValueError(f"unknown layer type {self.kind!r}")
        h = h + y.astype(jnp.float32)
        x = RMSNorm(self.norm_eps, name="ffn_norm")(h)
        if self.num_experts == 0:
            y = SwiGLU(self.mlp_dim, self.dtype, name="mlp")(x)
        else:
            y = DroplessMoE(
                self.num_experts, self.experts_per_token, self.held_experts,
                self.mlp_dim, self.norm_topk_prob,
                self.routed_scaling_factor, dtype=self.dtype, name="moe")(x)
        return h + y.astype(jnp.float32)


register_model(
    ModelSpec(
        name="lfm2",
        builder=LFM2,
        example_input_shape=(64,),
        # A language model: its "classes" are its vocabulary (the data
        # generator's topics are labels the next-token loss ignores).
        num_classes=65536,
        input_dtype=np.int32,
        # DroplessMoE's jax.lax.ragged_dot has no batching rule for
        # per-client expert weights.
        vmap_clients=False,
        work_counts=work_counts_beside("lfm2_stats", STATS),
        defaults={
            "vocab_size": 65536, "max_len": 128000, "width": 2048,
            "layer_types": ["conv", "conv", "full_attention", "conv"],
            "num_dense_layers": 2, "heads": 32, "kv_heads": 8,
            "mlp_dim": 11776, "moe_mlp_dim": 1536, "num_experts": 64,
            "experts_per_token": 4, "held_experts": list(range(64)),
            "conv_kernel": 3, "norm_eps": 1e-5, "rope_theta": 1e6,
        },
    )
)
