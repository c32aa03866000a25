"""Chip check for the delta-rule scan's forward kernel (ops/kda_scan.py).

On the TPU backend, at the benchmark cell's shape (2 sequences x 2,048 tokens
x 32 heads x 128 channels, seeded inputs in the ranges the model's
``_scan_inputs`` gives: unit keys, queries a ``sqrt(K)``-th of one, sigmoid
write strengths, log decays ``-exp(A_log) softplus(..)`` with ``A_log`` up
to ``log 16``), the kernel must lower to Mosaic (``tpu_custom_call`` in the
compiled HLO), agree with the plain-JAX ``chunk_scan`` of
``models/kimi_linear.py``, and the wrapper must take the kernel
(``kernel_chunks`` = every chunk) and give the plain code's gradients. Prints
the largest difference and both forwards' seconds a call over ``--calls``
calls. Exits non-zero when JAX finds no TPU, when the kernel is refused, or
when a result is off.

    python scripts/check_kda_scan_tpu.py     # one process; holds the chip
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from olearning_sim_tpu.models import kimi_linear as km
from olearning_sim_tpu.ops import kda_scan

MOSAIC = "tpu_custom_call"
SHAPE = (2, 2048, 32, 128)          # n, L, H, K = V: the cell's layer-step
# Float32 rounding over a chunk's products, relative to the largest output.
TOL = 2e-5


def scan_inputs(seed: int, shape=SHAPE):
    """``q, k, v, g, beta`` as ``[n, L, H K]`` (``beta`` ``[n, L, H]``), the
    way ``_scan_inputs``' projections leave them before its reshape."""
    n, L, H, K = shape
    keys = jax.random.split(jax.random.key(seed), 6)
    q, k, v = (jax.random.normal(key, shape, jnp.float32) for key in keys[:3])
    q, k = km._l2norm(q) / np.sqrt(K), km._l2norm(k)
    a = jnp.exp(jax.random.uniform(keys[3], (H, 1), jnp.float32, 0.0,
                                   np.log(16.0)))
    g = -a * jax.nn.softplus(jax.random.normal(keys[4], shape, jnp.float32))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (n, L, H), jnp.float32))
    flat = lambda x: x.reshape(n, L, H * K)
    return tuple(map(flat, (q, k, v, g))) + (beta,)


def unflat(fn, H):
    """``fn`` of ``[n, L, H, K]`` arrays, taking them flat."""

    def wrapped(q, k, v, g, beta):
        return fn(*(x.reshape(x.shape[:2] + (H, -1)) for x in (q, k, v, g)),
                  beta)

    return wrapped


def seconds_a_call(fn, args, calls: int) -> float:
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / calls


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=2**31 + 49)
    parser.add_argument("--calls", type=int, default=20)
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"check_kda_scan_tpu: backend is {jax.default_backend()!r}, "
              f"not tpu", file=sys.stderr)
        return 1
    H = SHAPE[2]
    xs = scan_inputs(args.seed)
    kernel = jax.jit(unflat(kda_scan.scan_forward, H))
    plain = jax.jit(unflat(km.chunk_scan, H))
    if MOSAIC not in kernel.lower(*xs).compile().as_text():
        raise AssertionError(f"the kernel's compiled HLO has no {MOSAIC}")
    got, want = kernel(*xs), plain(*xs)
    largest = float(jnp.abs(want).max())
    diff = float(jnp.abs(got - want).max())
    line = {"shape": list(SHAPE), "largest_output": largest,
            "max_abs_diff": diff,
            "kernel_s_a_call": seconds_a_call(kernel, xs, args.calls),
            "plain_s_a_call": seconds_a_call(plain, xs, args.calls),
            "calls": args.calls}
    print(json.dumps(line), flush=True)
    if not diff <= TOL * largest:
        raise AssertionError(f"kernel and plain chunk_scan differ by {diff} "
                             f"at a largest output of {largest}")

    # The wrapper: the kernel forward, the plain code's backward pass.
    probe = jax.random.normal(jax.random.key(args.seed + 1), want.shape)

    def loss(scan):
        def fn(*xs):
            out = scan(*xs)
            o, chunks = out if isinstance(out, tuple) else (out, 0)
            return (o * probe).sum(), chunks
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))

    wrapped = loss(unflat(
        lambda *a: kda_scan.chunk_scan(km.chunk_scan, *a), H))
    plainly = loss(unflat(km.chunk_scan, H))
    (_, chunks), grads = wrapped(*xs)
    (_, _), plain_grads = plainly(*xs)
    worst = max(float(jnp.abs(a - b).max() / jnp.abs(b).max())
                for a, b in zip(grads, plain_grads))
    line = {"kernel_chunks": int(chunks),
            "chunks": SHAPE[0] * -(-SHAPE[1] // kda_scan.CHUNK),
            "max_rel_grad_diff": worst,
            "wrapped_value_and_grad_s_a_call": seconds_a_call(
                wrapped, xs, args.calls),
            "plain_value_and_grad_s_a_call": seconds_a_call(
                plainly, xs, args.calls)}
    print(json.dumps(line), flush=True)
    if line["kernel_chunks"] != line["chunks"] or not worst <= TOL:
        raise AssertionError(f"the wrapper is off: {line}")
    print(json.dumps({"ok": True, "device_kind": jax.devices()[0].device_kind,
                      "count": len(jax.devices())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
