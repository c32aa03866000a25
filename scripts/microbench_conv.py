"""Microbench: per-client-weight conv formulations on TPU.

The round program trains C independent client models at once, so every conv
has batched (per-client) kernels. Measures vmap(lax.conv) against explicit
im2col + batched-GEMM, with the loop INSIDE one jit (lax.scan) so dispatch
latency doesn't pollute the numbers.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

C = 128   # clients per block
S = 20    # samples per client
ITERS = 50

LAYERS = [
    (32, 32, 3, 32, 2),
    (16, 16, 32, 64, 2),
    (8, 8, 64, 128, 2),
]


def vmapped_conv(x, w):
    def one(xc, wc):
        return jax.lax.conv_general_dilated(
            xc, wc, window_strides=(2, 2), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
    return jax.vmap(one)(x, w)


def im2col_conv(x, w):
    C_, S_, H, W, cin = x.shape
    cout = w.shape[-1]
    patches = jax.vmap(
        lambda xc: jax.lax.conv_general_dilated_patches(
            xc, filter_shape=(3, 3), window_strides=(2, 2), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
    )(x)  # [C, S, H', W', cin*9] — feature dim ordered (cin, kh, kw)
    Hp, Wp = patches.shape[2], patches.shape[3]
    k = patches.shape[-1]
    pm = patches.reshape(C_, S_ * Hp * Wp, k)
    # kernel [C,3,3,cin,cout] -> [C, cin,3,3, cout] -> [C, cin*9, cout]
    wm = jnp.transpose(w, (0, 3, 1, 2, 4)).reshape(C_, k, cout)
    out = jnp.einsum("cpk,ckn->cpn", pm, wm).astype(x.dtype)
    return out.reshape(C_, S_, Hp, Wp, cout)


def scan_time(fn, x, w, iters=ITERS):
    """Mean per-iteration time of fn(x, w) scanned inside one jit; the
    output feeds back through a cheap reduction so iterations can't fuse
    away or run as one."""

    @jax.jit
    def run(x, w):
        def body(carry, _):
            out = fn(x + carry, w)
            return out.astype(jnp.float32).mean().astype(x.dtype), None

        carry, _ = jax.lax.scan(body, jnp.bfloat16(0.0), None, length=iters)
        return carry

    float(run(x, w))  # compile
    t0 = time.perf_counter()
    float(run(x, w))
    return (time.perf_counter() - t0) / iters


def main():
    print("backend:", jax.default_backend())
    key = jax.random.key(0)
    for (H, W, cin, cout, stride) in LAYERS:
        x = jax.random.normal(key, (C, S, H, W, cin), jnp.bfloat16)
        w = jax.random.normal(key, (C, 3, 3, cin, cout), jnp.bfloat16) * 0.05

        a = np.asarray(jax.jit(vmapped_conv)(x, w), np.float32)
        b = np.asarray(jax.jit(im2col_conv)(x, w), np.float32)
        err = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-6)
        t1 = scan_time(vmapped_conv, x, w)
        t2 = scan_time(im2col_conv, x, w)
        flops = 2 * C * S * (H // stride) * (W // stride) * 9 * cin * cout
        print(
            f"L {H}x{W}x{cin}->{cout}: vmap_conv {t1*1e3:.3f}ms "
            f"({flops/t1/1e12:.1f} TF/s)  im2col {t2*1e3:.3f}ms "
            f"({flops/t2/1e12:.1f} TF/s)  rel_err {err:.2e}"
        )

    def make_stack(conv):
        def loss(ws, x):
            h = x
            for w in ws:
                h = jax.nn.relu(conv(h, w))
            return (h.astype(jnp.float32) ** 2).mean()
        return jax.grad(loss)

    ws = [jax.random.normal(key, (C, 3, 3, cin, cout), jnp.bfloat16) * 0.05
          for (_, _, cin, cout, _) in LAYERS]
    x = jax.random.normal(key, (C, S, 32, 32, 3), jnp.bfloat16)

    for name, conv in (("vmap_conv", vmapped_conv), ("im2col", im2col_conv)):
        g = make_stack(conv)

        @jax.jit
        def run(ws, x):
            def body(carry, _):
                gs = g([w + carry for w in ws], x)
                return gs[0].astype(jnp.float32).mean().astype(jnp.bfloat16), None

            carry, _ = jax.lax.scan(body, jnp.bfloat16(0.0), None, length=ITERS)
            return carry

        float(run(ws, x))
        t0 = time.perf_counter()
        float(run(ws, x))
        dt = (time.perf_counter() - t0) / ITERS
        print(f"3-layer fwd+bwd ({name}): {dt*1e3:.3f}ms/iter")


if __name__ == "__main__":
    main()
