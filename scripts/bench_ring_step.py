"""A/B the ring-attention per-step primitive: XLA dense local attention vs
the fused Pallas kernel, at ring-chunk shapes, on ONE chip.

VERDICT r3 weak #7: ring attention's step primitive should be chosen by
measurement. The ring scan body minus the ppermute IS a single-device
computation — local queries attending over one K/V chunk with an
online-softmax merge — so the primitive choice is measurable without a
multi-chip sp mesh. Sweeps the per-device chunk length Lc from the sp-leg
dryrun scale up to VMEM-stressing sizes at DistilBERT head geometry
(H=12, D=64, bf16).

Timing discipline (per-dispatch timing is latency-dominated at the small
shapes and once produced 25x-wrong conclusions): each variant runs ITERS
steps inside ONE jitted lax.scan with a single host sync.

Writes RING_STEP.json {shape -> {dense_ms, flash_ms, winner}} and prints a
table for docs/DESIGN.md. Runs on the chip only: on the CPU backend the
kernel would run in the Pallas interpreter and the numbers would say
nothing about speed, so without a TPU the script exits non-zero.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from olearning_sim_tpu.ops.flash_attention import flash_attention_stats
from olearning_sim_tpu.parallel.ring_attention import NEG_INF, _local_scores

ITERS = 50
B, H, D = 8, 12, 64


def dense_step(q, k, v, mask, m, l, acc, scale):
    """The ring scan body's dense combine (ring_attention.combine_dense)."""
    s = _local_scores(q, k, scale)
    s = s + jnp.where(mask, 0.0, NEG_INF)[:, None, None, :]
    m_blk = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    alpha = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - shift)
    pij = jnp.exp(s - shift)
    l_new = alpha * l + jnp.sum(pij, axis=-1, keepdims=True)
    acc_new = alpha * acc + jax.lax.dot_general(
        pij, v.astype(jnp.float32), (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def flash_step(q, k, v, mask, m, l, acc, scale):
    """The ring scan body's flash combine (ring_attention.combine_flash)."""
    o_blk, m_blk, l_blk = flash_attention_stats(q, k, v, kv_mask=mask,
                                                scale=scale)
    m_blk, l_blk = m_blk[..., None], l_blk[..., None]
    m_new = jnp.maximum(m, m_blk)
    shift = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    alpha = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - shift)
    beta = jnp.exp(jnp.where(l_blk > 0, m_blk, NEG_INF) - shift)
    l_new = alpha * l + beta * l_blk
    acc_new = alpha * acc + beta * (o_blk.astype(jnp.float32) * l_blk)
    return m_new, l_new, acc_new


def time_variant(step_fn, lc, seed=0):
    key = jax.random.key(seed)
    kq, kk, kv = jax.random.split(key, 3)
    scale = 1.0 / np.sqrt(D)
    q = jax.random.normal(kq, (B, H, lc, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, H, lc, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, H, lc, D), jnp.bfloat16)
    mask = jnp.ones((B, lc), bool)

    @jax.jit
    def loop(q, k, v, mask):
        qf = q.astype(jnp.float32)
        m0 = jnp.full_like(qf[..., :1], NEG_INF)
        l0 = jnp.zeros_like(qf[..., :1])
        acc0 = jnp.zeros_like(qf)

        def body(carry, _):
            # K/V live IN the carry and rotate every step, mirroring the
            # real ring's ppermute — and, critically, keeping the heavy
            # attention work loop-variant. With static operands XLA hoists
            # the dense variant's q.k^T out of the scan (the Pallas call is
            # opaque to LICM), which would make the A/B meaningless.
            k_c, v_c, m, l, acc = carry
            m, l, acc = step_fn(q, k_c, v_c, mask, m, l, acc, scale)
            k_n = jnp.roll(k_c, 1, axis=2)
            v_n = jnp.roll(v_c, 1, axis=2)
            return (k_n, v_n, m, l, acc), None

        (_, _, m, l, acc), _ = jax.lax.scan(body, (k, v, m0, l0, acc0),
                                            None, length=ITERS)
        return (acc / jnp.maximum(l, 1e-20)).sum()

    out = loop(q, k, v, mask)
    float(out)  # compile + warm, ended by a host read
    t0 = time.perf_counter()
    float(loop(q, k, v, mask))
    return (time.perf_counter() - t0) / ITERS * 1e3  # ms per step


def main():
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"bench_ring_step: JAX backend is {backend!r}, not tpu; "
                 f"nothing measured")
    results = []
    # 16: the sp dryrun chunk; 512-8192: long-context chunks (8192 stresses
    # VMEM: K+V = 2*8*12*8192*64*2B = 192 MB streamed per step).
    for lc in (16, 512, 1024, 2048, 4096, 8192):
        dense_ms = time_variant(dense_step, lc)
        flash_ms = time_variant(flash_step, lc)
        rec = {
            "B": B, "H": H, "D": D, "chunk_len": lc,
            "dense_ms_per_step": round(dense_ms, 3),
            "flash_ms_per_step": round(flash_ms, 3),
            "winner": "flash" if flash_ms < dense_ms else "dense",
        }
        results.append(rec)
        print(json.dumps(rec), flush=True)
    out = {
        "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        "iters_per_timing": ITERS,
        "results": results,
        "note": ("per-step primitive for ring attention "
                 "(ring_attention.use_flash); dense stays the default "
                 "unless flash wins here on real hardware"),
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "RING_STEP.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
