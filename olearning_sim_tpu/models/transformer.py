"""Text-transformer family (BASELINE config 4: FedAdam + DistilBERT on
Sent140, 10k clients with an access-spike trace).

DistilBERT-shaped encoder: 6 layers, width 768, 12 heads, GELU FFN 3072,
learned positional embeddings, post-LN residuals — re-specified from the
public DistilBERT geometry, not ported (the reference keeps models in user
operator code; SURVEY.md section 2.6). Token inputs are int32; padding id 0 is
masked out of attention and pooling. bfloat16 compute, fp32 head.

``attention_impl`` selects the attention kernel: ``"dense"`` (XLA fused
attention) or ``"ring"`` (sequence-parallel ring attention over the mesh's
``sp`` axis — see ``olearning_sim_tpu/parallel/ring_attention.py``) for
sequences too long for one device's HBM.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from olearning_sim_tpu.models.lookup import LookupOnlyEmbed
from olearning_sim_tpu.models.registry import ModelSpec, register_model


class TransformerBlock(nn.Module):
    width: int
    heads: int
    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "dense"

    @nn.compact
    def __call__(self, x, pad_mask):
        # pad_mask: [B, L] bool, True = real token.
        if self.attention_impl == "ring":
            try:
                from olearning_sim_tpu.parallel.ring_attention import RingSelfAttention
            except ImportError as e:
                raise NotImplementedError(
                    "attention_impl='ring' requires olearning_sim_tpu.parallel."
                    "ring_attention (sequence-parallel ring attention); use "
                    "'dense' on builds without it"
                ) from e

            # Named to match the dense branch's auto-name so dense-trained
            # params apply unchanged under ring attention (long-context
            # eval of a model trained with attention_impl="dense").
            y = RingSelfAttention(
                num_heads=self.heads, dtype=self.dtype,
                name="MultiHeadDotProductAttention_0",
            )(x, pad_mask)
        else:
            attn_mask = nn.make_attention_mask(pad_mask, pad_mask, dtype=self.dtype)
            y = nn.MultiHeadDotProductAttention(
                num_heads=self.heads, dtype=self.dtype, deterministic=True
            )(x, x, mask=attn_mask)
        x = nn.LayerNorm(dtype=self.dtype)(x + y)  # post-LN, BERT-style
        y = nn.Dense(self.mlp_dim, dtype=self.dtype)(x)
        y = nn.gelu(y)
        y = nn.Dense(self.width, dtype=self.dtype)(y)
        return nn.LayerNorm(dtype=self.dtype)(x + y)


class TextTransformer(nn.Module):
    vocab_size: int = 30522
    max_len: int = 128
    width: int = 768
    depth: int = 6
    heads: int = 12
    mlp_dim: int = 3072
    num_classes: int = 2
    pad_id: int = 0
    dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "dense"

    def __post_init__(self):
        # Refused when the model is built: any other string used to mean
        # dense, and a stored task may name an implementation that is gone.
        if self.attention_impl not in ("dense", "ring"):
            raise ValueError(
                f"attention_impl={self.attention_impl!r}: the text "
                "transformer has 'dense' and 'ring'")
        super().__post_init__()

    @nn.compact
    def __call__(self, tokens):
        # tokens: [B, L] int32. Under attention_impl="ring" this runs inside
        # shard_map with L sharded over the "sp" mesh axis: tokens is the
        # LOCAL chunk, positions are offset by the rank's chunk start, and
        # the mean-pool reduces over the global sequence via psum.
        # NOTE: parallel/pipeline.py mirrors this method's prologue/epilogue
        # by param name — change both together (the pipeline dense-parity
        # test fails if they drift). The lookup is MARKED as the table's
        # only reader (models/lookup.py; still nn.Embed's parameter,
        # Embed_0/embedding), so FedCore's local step trains the rows the
        # step looked up instead of a dense table gradient. The mark is a
        # no-op outside that step: the pipeline's mirror needs no change.
        ring = self.attention_impl == "ring"
        pad_mask = tokens != self.pad_id
        emb = LookupOnlyEmbed(
            self.vocab_size, self.width,
            embedding_init=nn.initializers.normal(stddev=0.02),
            param_dtype=jnp.float32, name="Embed_0",
        )(tokens)
        pos = self.param(
            "pos_embedding",
            nn.initializers.normal(stddev=0.02),
            (1, self.max_len, self.width),
            jnp.float32,
        )
        L = tokens.shape[1]
        if ring:
            offset = jax.lax.axis_index("sp") * L
            pos_slice = jax.lax.dynamic_slice_in_dim(pos, offset, L, axis=1)
        else:
            pos_slice = pos[:, :L]
        x = (emb + pos_slice).astype(self.dtype)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        for _ in range(self.depth):
            x = TransformerBlock(
                self.width, self.heads, self.mlp_dim, self.dtype,
                self.attention_impl,
            )(x, pad_mask)
        # Mean-pool over real tokens (robust when no CLS convention exists in
        # the synthetic/Sent140 tokenization).
        m = pad_mask[..., None].astype(jnp.float32)
        s = (x.astype(jnp.float32) * m).sum(1)
        c = m.sum(1)
        if ring:
            s = jax.lax.psum(s, "sp")
            c = jax.lax.psum(c, "sp")
        pooled = s / jnp.maximum(c, 1.0)
        return nn.Dense(self.num_classes, dtype=jnp.float32)(pooled)


register_model(
    ModelSpec(
        name="distilbert",
        builder=TextTransformer,
        example_input_shape=(64,),
        num_classes=2,
        defaults={
            "vocab_size": 30522,
            "max_len": 64,
            "width": 768,
            "depth": 6,
            "heads": 12,
            "mlp_dim": 3072,
            "num_classes": 2,
        },
        input_dtype=np.int32,
    )
)
