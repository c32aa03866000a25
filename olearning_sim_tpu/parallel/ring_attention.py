"""Ring attention: sequence-parallel self-attention over a mesh axis.

Long-context path for the transformer family. The sequence axis is sharded
over a mesh axis (``sp``): each device holds a [B, H, L/P, D] chunk of
q/k/v. P ring steps rotate the K/V chunks (+their padding masks) around the
axis with ``jax.lax.ppermute`` while every device accumulates attention for
its local queries using the online-softmax merge (m, l, acc) — so the full
[L, L] score matrix never exists anywhere, per-device memory is O(L/P), and
the K/V transfers ride ICI neighbor links (a ring is exactly what ppermute
with a +1 rotation lays onto the torus).

Per-step local attention is either plain XLA ops (the default — measured
faster single-chip, see ``ops/flash_attention.py``) or the fused Pallas
kernel (``use_flash=True``; per-chunk scores stay in VMEM; trainable —
the kernel carries a custom VJP that rematerializes the backward through
XLA). ``scripts/bench_ring_step.py`` measures the two at ring-chunk
shapes.

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
    use_flash: bool = False,
) -> jax.Array:
    """Attention over a sequence sharded on ``axis_name``.

    Args (all per-device chunks, inside shard_map):
      q, k, v: [B, H, Lc, D] local chunks (global L = Lc * axis size).
      kv_mask: [B, Lc] bool, True = real key; None = no padding.
      use_flash: compute each ring step's local attention with the fused
        Pallas kernel (``ops.flash_attention_stats``) instead of plain XLA
        ops. Trainable (the kernel carries a custom VJP whose backward
        rematerializes through XLA) but default OFF: XLA's fused dense
        attention measured faster at every single-chip length tried (see
        ``ops/flash_attention.py``); flip the default only if
        ``scripts/bench_ring_step.py`` shows the kernel winning at your
        chunk shapes.
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

    def combine_dense(k_cur, v_cur, mask_cur, m, l, acc):
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
        return m_new, l_new, acc_new

    def combine_flash(k_cur, v_cur, mask_cur, m, l, acc):
        # The kernel returns this block's normalized output + its softmax
        # stats; fold it into the running (m, l, acc) exactly. Fully-masked
        # rows come back as (o=0, m=0, l=0): beta * l_blk = 0, and the m
        # overestimate rescales l and acc identically, so acc/l is intact.
        from olearning_sim_tpu.ops.flash_attention import flash_attention_stats

        o_blk, m_blk, l_blk = flash_attention_stats(
            q, k_cur, v_cur, kv_mask=mask_cur, scale=scale
        )
        m_blk = m_blk[..., None]                     # [B,H,Lc,1] f32
        l_blk = l_blk[..., None]
        m_new = jnp.maximum(m, m_blk)
        shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        alpha = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - shift)
        beta = jnp.exp(jnp.where(l_blk > 0, m_blk, NEG_INF) - shift)
        l_new = alpha * l + beta * l_blk
        acc_new = alpha * acc + beta * (o_blk.astype(jnp.float32) * l_blk)
        return m_new, l_new, acc_new

    combine = combine_flash if use_flash else combine_dense

    def step(carry, _):
        k_cur, v_cur, mask_cur, m, l, acc = carry
        m_new, l_new, acc_new = combine(k_cur, v_cur, mask_cur, m, l, acc)
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
    use_flash: bool = False  # see ring_attention(use_flash=); trainable

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
        o = ring_attention(q, k, v, pad_mask, self.axis_name,
                           use_flash=self.use_flash)
        o = jnp.moveaxis(o, 1, 2)                  # [B, Lc, H, D]
        return nn.DenseGeneral(
            features=W, axis=(-2, -1), dtype=self.dtype, name="out"
        )(o)
