"""Ring attention: sequence-parallel self-attention over a mesh axis.

Long-context path for the transformer family. The sequence axis is sharded
over a mesh axis (``sp``): each device holds a [B, H, L/P, D] chunk of
q/k/v. P ring steps rotate the K/V chunks (+their padding masks) around the
axis with ``jax.lax.ppermute`` while every device accumulates attention for
its local queries using the online-softmax merge (m, l, acc) — so the full
[L, L] score matrix never exists anywhere, per-device memory is O(L/P), and
the K/V transfers ride ICI neighbor links (a ring is exactly what ppermute
with a +1 rotation lays onto the torus).

Per-step local attention is plain XLA ops.

Usage requires being inside ``shard_map`` with the sequence axis sharded
over ``axis_name`` — see ``ring_self_attention`` for the module-level entry.
"""

from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


NEG_INF = -1e30


def _local_scores(q, k, scale):
    # [B, H, Lq, D] x [B, H, Lk, D] -> [B, H, Lq, Lk], f32 accumulation.
    return jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    ) * scale


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_mask: Optional[jax.Array],
    axis_name: str,
    scale: Optional[float] = None,
) -> jax.Array:
    """Attention over a sequence sharded on ``axis_name``.

    Args (all per-device chunks, inside shard_map):
      q, k, v: [B, H, Lc, D] local chunks (global L = Lc * axis size).
      kv_mask: [B, Lc] bool, True = real key; None = no padding.
    Returns [B, H, Lc, D] — the local queries' attention over the GLOBAL
    sequence, in q's dtype.
    """
    B, H, Lc, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    p = jax.lax.psum(1, axis_name)
    if kv_mask is None:
        kv_mask = jnp.ones((B, Lc), bool)
    perm = [(i, (i + 1) % p) for i in range(p)]

    qf = q.astype(jnp.float32)
    # Accumulators derive from q (full_like/zeros_like) so their varying-
    # manual-axes type matches the scan body's outputs under ANY enclosing
    # shard_map (sp alone, dp x sp, ...) — a pvary over just the ring axis
    # would mismatch when other manual axes are present.
    m0 = jnp.full_like(qf[..., :1], NEG_INF)
    l0 = jnp.zeros_like(qf[..., :1])
    acc0 = jnp.zeros_like(qf)

    def step(carry, _):
        k_cur, v_cur, mask_cur, m, l, acc = carry
        s = _local_scores(qf, k_cur, scale)                    # [B,H,Lc,Lck]
        s = s + jnp.where(mask_cur, 0.0, NEG_INF)[:, None, None, :]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        # Fully-masked-so-far rows keep m at NEG_INF; pin the shift to 0 so
        # exp() underflows instead of producing exp(0)=1 garbage.
        shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        alpha = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - shift)
        pij = jnp.exp(s - shift)
        l_new = alpha * l + jnp.sum(pij, axis=-1, keepdims=True)
        acc_new = alpha * acc + jax.lax.dot_general(
            pij, v_cur.astype(jnp.float32),
            (((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32,
        )
        # Rotate K/V (and their padding mask) one hop around the ring.
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = jax.lax.ppermute(mask_cur, axis_name, perm)
        return (k_nxt, v_nxt, mask_nxt, m_new, l_new, acc_new), None

    carry, _ = jax.lax.scan(step, (k, v, kv_mask, m0, l0, acc0), None, length=p)
    _, _, _, m, l, acc = carry
    out = acc / jnp.maximum(l, 1e-20)
    return out.astype(q.dtype)


class RingSelfAttention(nn.Module):
    """Drop-in MHA replacement whose sequence axis is sharded over
    ``axis_name`` (the model's ``attention_impl='ring'`` path,
    ``models/transformer.py``). Must be applied inside shard_map with the
    L axis of its input sharded on that mesh axis; projections are local
    (per-token), so only attention itself communicates.

    Parameter-compatible with ``nn.MultiHeadDotProductAttention``
    (submodules ``query``/``key``/``value`` with kernels [W, H, D] and
    ``out`` with kernel [H, D, W]) — a model trained with dense attention
    applies unchanged with ``attention_impl='ring'`` for long-context
    inference/eval.
    """

    num_heads: int
    axis_name: str = "sp"
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, pad_mask: jax.Array) -> jax.Array:
        # x: [B, Lc, W] local chunk; pad_mask: [B, Lc].
        B, Lc, W = x.shape
        head_dim = W // self.num_heads
        proj = lambda name: nn.DenseGeneral(
            features=(self.num_heads, head_dim), axis=-1, dtype=self.dtype,
            name=name,
        )
        q, k, v = (
            jnp.moveaxis(proj(n)(x), 2, 1)         # [B, H, Lc, D]
            for n in ("query", "key", "value")
        )
        o = ring_attention(q, k, v, pad_mask, self.axis_name)
        o = jnp.moveaxis(o, 1, 2)                  # [B, Lc, H, D]
        return nn.DenseGeneral(
            features=W, axis=(-2, -1), dtype=self.dtype, name="out"
        )(o)
