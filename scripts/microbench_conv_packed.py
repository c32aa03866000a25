"""Packed-client first-conv microbench: can block-diagonal client packing
lift the headline's conv-bound MXU ceiling?

The measured position (round 2, docs/DESIGN.md): the 10k-client cnn4 round
is conv-bound at ~23 TF/s effective (~12%% of v5e bf16 peak) at block 16.
The first conv dominates the waste: per client it is a GEMM
[M=batch*16*16, K=27] x [K=27, N=32] — the MXU's weight-stationary tile is
128x128, so each streamed row uses 27*32/16384 = 5.3%% of the array, and
the vmap-over-clients lowering (batch-grouped conv) streams every client's
M rows separately.

The lever: pack p=4 clients into ONE tile-filling GEMM. Concatenate the 4
clients' patch rows along K (a dense concat — row j carries client 1..4's
row j side by side) and their kernels into a block-diagonal [4K=108,
4N=128] weight tile. Each streamed row now performs all 4 clients' dot
products at once: same row count as ONE client, 4x the work per cycle,
~16x the tile utilization, zero wasted FLOPs (the off-diagonal zero blocks
are weight-memory only, never streamed). Two structural gifts make this
cheap for cnn4's L1 specifically:

  * the layer-1 im2col patches depend only on the CLIENT DATA, not the
    step's weights — they are computed once per round and reused across
    all 10 local-SGD steps (the scan carries weights, not inputs);
  * layer 1 needs no dL/dx (it is the input layer), so the backward is
    just patches^T @ dY — the same packed layout serves it.

This microbench measures, at the exact headline L1 shapes:
  a. vmap-conv        — what the engine does today (batch-grouped conv)
  b. packed-GEMM      — the lever (patches precomputed, p=4 block-diag)
  c. batched-GEMM     — im2col WITHOUT packing (round-2's dead end, as the
                        control separating "packing" from "im2col")
and asserts (b) and (c) match (a) numerically (fwd AND dW) before timing.

Timing discipline: ITERS steps inside one jit (lax.scan), single host
sync (per-dispatch timing is latency-dominated at these sizes).
The loop re-uses static patches and varies weights per step, mirroring the
local-SGD structure. Writes CONV_PACKED.json; perf numbers are only
meaningful on the chip, a CPU run checks numerics.

Run: python scripts/microbench_conv_packed.py [--iters N]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

G, B, H, W, C, F, P = 16, 32, 32, 32, 3, 32, 4  # block, batch, img, feats, pack
KH = KW = 3
STRIDE = 2
OH, OW = H // STRIDE, W // STRIDE
K = KH * KW * C            # 27
M = B * OH * OW            # streamed rows per client


def extract_patches(x):
    """im2col for the 3x3/s2 SAME conv: [N, H, W, C] -> [N, OH*OW, K].

    Feature order matches conv_general_dilated_patches: C-major (channel
    slowest) — the kernel reshape below uses the same order."""
    from jax.lax import conv_general_dilated_patches

    pat = conv_general_dilated_patches(
        x, (KH, KW), (STRIDE, STRIDE), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )  # [N, OH, OW, C*KH*KW]
    return pat.reshape(x.shape[0], OH * OW, K)


def kernel_matrix(w):
    """[KH, KW, C, F] -> [K, F] in the patch feature order (C-major)."""
    return w.transpose(2, 0, 1, 3).reshape(K, F)


# ------------------------------------------------------------ the variants
def fwd_vmap_conv(ws, x):
    """(a) today's lowering: vmap over clients of a plain conv."""
    def one(w, xi):
        return jax.lax.conv_general_dilated(
            xi, w, (STRIDE, STRIDE), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
    return jax.vmap(one)(ws, x)  # [G, B, OH, OW, F]


def fwd_batched_gemm(ws, patches):
    """(c) im2col + per-client batched GEMM (no packing)."""
    km = jax.vmap(kernel_matrix)(ws)                     # [G, K, F]
    out = jnp.einsum("gmk,gkf->gmf", patches, km)
    return out.reshape(G, B, OH, OW, F)


def pack_weights(ws):
    """[G, KH, KW, C, F] -> block-diagonal [G/P, P*K, P*F]."""
    km = jax.vmap(kernel_matrix)(ws).reshape(G // P, P, K, F)
    blk = jnp.zeros((G // P, P * K, P * F), km.dtype)
    for i in range(P):
        blk = blk.at[:, i * K:(i + 1) * K, i * F:(i + 1) * F].set(
            km[:, i]
        )
    return blk


def pack_patches(patches):
    """[G, B*OH*OW, K] -> [G/P, B*OH*OW, P*K] (dense concat along K)."""
    return (patches.reshape(G // P, P, M, K)
            .transpose(0, 2, 1, 3)
            .reshape(G // P, M, P * K))


def fwd_packed_gemm(blk_w, packed_patches):
    """(b) the lever: one tile-filling GEMM per P clients."""
    out = jnp.einsum("gmk,gkn->gmn", packed_patches, blk_w)  # [G/P, M, P*F]
    return (out.reshape(G // P, M, P, F)
            .transpose(0, 2, 1, 3)
            .reshape(G, B, OH, OW, F))


K_PAD = 32  # pad the L1 contraction K=27 up to the lane width


def fwd_padk_gemm(ws, patches_pad):
    """(d) K-padding lever (VERDICT r4 #4): the per-client GEMM with its
    contraction dim zero-padded 27->32 so the streamed rows align with the
    MXU lane width. Algorithmically identical (zero rows contribute 0)."""
    km = jax.vmap(kernel_matrix)(ws)                     # [G, K, F]
    km_pad = jnp.pad(km, ((0, 0), (0, K_PAD - K), (0, 0)))
    out = jnp.einsum("gmk,gkf->gmf", patches_pad, km_pad)
    return out.reshape(G, B, OH, OW, F)


def fwd_padc_conv(ws, x_pad):
    """(e) channel-padding lever: the same vmap-conv with input channels
    zero-padded 3->4 (K becomes 36, a multiple of 4) — tests whether XLA's
    conv lowering picks a better tiling for an aligned input channel
    count without leaving the conv op."""
    ws_pad = jnp.pad(ws, ((0, 0), (0, 0), (0, 0), (0, 1), (0, 0)))
    return fwd_vmap_conv(ws_pad, x_pad)


# --------------------------------------------------------------- numerics
def check_numerics():
    kx, kw, kr = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(kx, (G, B, H, W, C), jnp.float32)
    ws = jax.random.normal(kw, (G, KH, KW, C, F), jnp.float32) * 0.1
    r = jax.random.normal(kr, (G, B, OH, OW, F), jnp.float32)

    patches = jax.vmap(extract_patches)(x).reshape(G, M, K)

    def loss_a(ws):
        return (fwd_vmap_conv(ws, x) * r).sum()

    def loss_b(ws):
        return (fwd_packed_gemm(pack_weights(ws), pack_patches(patches)) * r).sum()

    def loss_c(ws):
        return (fwd_batched_gemm(ws, patches) * r).sum()

    patches_pad = jnp.pad(patches, ((0, 0), (0, 0), (0, K_PAD - K)))
    x_pad = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, 0), (0, 1)))

    def loss_d(ws):
        return (fwd_padk_gemm(ws, patches_pad) * r).sum()

    def loss_e(ws):
        return (fwd_padc_conv(ws, x_pad) * r).sum()

    va, ga = jax.value_and_grad(loss_a)(ws)
    for loss in (loss_b, loss_c, loss_d, loss_e):
        v, g = jax.value_and_grad(loss)(ws)
        np.testing.assert_allclose(va, v, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(g), rtol=2e-3,
                                   atol=2e-3)
    print("numerics: packed/batched/padK/padC variants match vmap-conv "
          "(fwd + dW)", flush=True)


# ----------------------------------------------------------------- timing
def time_loop(make_step, iters, dtype=jnp.bfloat16):
    """Scan `iters` fwd+dW steps inside one jit; returns ms/step."""
    kx, kw, kr = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(kx, (G, B, H, W, C), dtype)
    ws0 = (jax.random.normal(kw, (G, KH, KW, C, F), dtype) * 0.1)
    r = jax.random.normal(kr, (G, B, OH, OW, F), dtype)
    step = make_step(x, r)

    @jax.jit
    def loop(ws0):
        def body(ws, _):
            return step(ws), None
        ws, _ = jax.lax.scan(body, ws0, None, length=iters)
        return jax.tree.map(lambda t: t.sum(), ws)

    out = loop(ws0)
    jax.tree.map(float, out)  # compile + warm, host sync
    t0 = time.perf_counter()
    float(loop(ws0))
    return (time.perf_counter() - t0) / iters * 1e3


def step_vmap(x, r):
    def step(ws):
        def loss(ws):
            return ((fwd_vmap_conv(ws, x).astype(jnp.float32)
                     * r.astype(jnp.float32)).sum())
        g = jax.grad(loss)(ws)
        return ws - 0.01 * g
    return step


def step_packed(x, r):
    patches = jax.vmap(extract_patches)(x).reshape(G, M, K)
    packed = pack_patches(patches)  # static across steps, like the real L1

    def step(ws):
        def loss(ws):
            return ((fwd_packed_gemm(pack_weights(ws), packed)
                     .astype(jnp.float32) * r.astype(jnp.float32)).sum())
        g = jax.grad(loss)(ws)
        return ws - 0.01 * g
    return step


def step_batched(x, r):
    patches = jax.vmap(extract_patches)(x).reshape(G, M, K)

    def step(ws):
        def loss(ws):
            return ((fwd_batched_gemm(ws, patches).astype(jnp.float32)
                     * r.astype(jnp.float32)).sum())
        g = jax.grad(loss)(ws)
        return ws - 0.01 * g
    return step


def step_padk(x, r):
    patches = jax.vmap(extract_patches)(x).reshape(G, M, K)
    patches_pad = jnp.pad(patches, ((0, 0), (0, 0), (0, K_PAD - K)))

    def step(ws):
        def loss(ws):
            return ((fwd_padk_gemm(ws, patches_pad).astype(jnp.float32)
                     * r.astype(jnp.float32)).sum())
        g = jax.grad(loss)(ws)
        return ws - 0.01 * g
    return step


def step_padc(x, r):
    x_pad = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, 0), (0, 1)))

    def step(ws):
        def loss(ws):
            return ((fwd_padc_conv(ws, x_pad).astype(jnp.float32)
                     * r.astype(jnp.float32)).sum())
        g = jax.grad(loss)(ws)
        return ws - 0.01 * g
    return step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--skip-numerics", action="store_true")
    args = ap.parse_args()

    backend = jax.default_backend()
    print(f"backend: {backend}", flush=True)
    if not args.skip_numerics:
        check_numerics()

    flops_per_step = 2 * G * M * K * F * 3  # fwd + dW (~2x fwd)
    results = {}
    for name, mk in (("vmap_conv", step_vmap), ("packed_gemm", step_packed),
                     ("batched_gemm", step_batched), ("padK_gemm", step_padk),
                     ("padC_conv", step_padc)):
        ms = time_loop(mk, args.iters)
        results[name] = {
            "ms_per_step": round(ms, 4),
            "effective_tflops": round(flops_per_step / (ms / 1e3) / 1e12, 2),
        }
        print(json.dumps({name: results[name]}), flush=True)

    rec = {
        "shape": {"block_clients": G, "batch": B, "img": [H, W, C],
                  "features": F, "pack": P, "gemm_per_client": [M, K, F],
                  "gemm_packed": [M, P * K, P * F]},
        "backend": backend,
        "perf_meaningful": backend == "tpu",
        "iters": args.iters,
        "results": results,
        "speedup_packed_vs_vmap": round(
            results["vmap_conv"]["ms_per_step"]
            / results["packed_gemm"]["ms_per_step"], 3),
    }
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "CONV_PACKED.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"wrote {out}", flush=True)


if __name__ == "__main__":
    main()
