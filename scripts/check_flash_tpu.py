"""Chip check for the Pallas attention kernels (ops/flash_attention.py).

On the TPU backend both entry points must lower to the Mosaic kernel — the
compiled HLO carries the ``tpu_custom_call`` — and agree with
``_reference_stats``, at the DistilBERT shape (L=64, H=12, D=64) and at one
long-context chunk (L=2048); ``ring_attention(use_flash=True)`` must do the
same inside ``shard_map`` over every visible chip. Exits non-zero when JAX
finds no TPU, when a kernel is refused, or when a result is off.

    python scripts/check_flash_tpu.py        # one process; holds the chip
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from olearning_sim_tpu.ops.flash_attention import (
    _reference_stats,
    flash_attention,
    flash_attention_stats,
)
from olearning_sim_tpu.parallel.ring_attention import ring_attention

MOSAIC = "tpu_custom_call"
# One bf16 MXU pass: the kernels feed the MXU at default precision for f32
# operands too, while the reference below is computed at "highest".
TOL = 2e-2
# (batch, heads, length, head dim): DistilBERT block; one long-context chunk.
SHAPES = ((8, 12, 64, 64), (1, 12, 2048, 64))


def _inputs(shape, dtype, seed):
    B, _, L, _ = shape
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q, k, v = (jax.random.normal(key, shape, dtype) for key in (kq, kk, kv))
    # Last eighth of batch row 0's keys is padding.
    mask = jnp.ones((B, L), jnp.float32).at[0, L - L // 8:].set(0.0)
    return q, k, v, mask


def _reference(q, k, v, mask):
    with jax.default_matmul_precision("highest"):
        return _reference_stats(q, k, v, mask, 1.0 / np.sqrt(q.shape[-1]))


def _f32(a):
    return np.asarray(a, np.float32)


def _compiled_with_mosaic(fn, *args, **kwargs):
    compiled = fn.lower(*args, **kwargs).compile()
    if MOSAIC not in compiled.as_text():
        raise AssertionError(f"{fn}: compiled HLO has no {MOSAIC}")
    return compiled


def check_entry_points(shape, dtype) -> dict:
    q, k, v, mask = _inputs(shape, dtype, seed=shape[2])
    ro, rm, rl = _reference(q, k, v, mask)
    _compiled_with_mosaic(flash_attention, q, k, v, kv_mask=mask)
    _compiled_with_mosaic(flash_attention_stats, q, k, v, kv_mask=mask)
    o = flash_attention(q, k, v, kv_mask=mask)
    so, sm, sl = flash_attention_stats(q, k, v, kv_mask=mask)
    np.testing.assert_allclose(_f32(o), _f32(ro), atol=TOL, rtol=TOL)
    np.testing.assert_allclose(_f32(so), _f32(ro), atol=TOL, rtol=TOL)
    np.testing.assert_allclose(_f32(sm), _f32(rm), atol=TOL, rtol=TOL)
    np.testing.assert_allclose(_f32(sl), _f32(rl), rtol=TOL)
    return {"shape": list(shape), "dtype": jnp.dtype(dtype).name,
            "flash_attention": "ok", "flash_attention_stats": "ok",
            "max_abs_err_o": float(np.abs(_f32(so) - _f32(ro)).max())}


def check_ring_in_shard_map() -> dict:
    devices = jax.devices()
    sp = len(devices)
    mesh = Mesh(np.array(devices), ("sp",))
    shape = (2, 12, 256 * sp, 64)
    q, k, v, mask = _inputs(shape, jnp.bfloat16, seed=7)
    spec = P(None, None, "sp", None)
    fn = jax.jit(jax.shard_map(
        lambda q, k, v, m: ring_attention(q, k, v, m > 0, "sp",
                                          use_flash=True),
        mesh=mesh, in_specs=(spec, spec, spec, P(None, "sp")),
        out_specs=spec,
    ))
    _compiled_with_mosaic(fn, q, k, v, mask)
    out = _f32(fn(q, k, v, mask))
    ref = _f32(_reference(q, k, v, mask)[0])
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=TOL)
    return {"ring_use_flash_in_shard_map": "ok", "sp": sp,
            "shape": list(shape),
            "max_abs_err_o": float(np.abs(out - ref).max())}


def main() -> int:
    if jax.default_backend() != "tpu":
        print(f"check_flash_tpu: backend is {jax.default_backend()!r}, "
              f"not tpu", file=sys.stderr)
        return 1
    for shape in SHAPES:
        for dtype in (jnp.bfloat16, jnp.float32):
            print(json.dumps(check_entry_points(shape, dtype)), flush=True)
    print(json.dumps(check_ring_in_shard_map()), flush=True)
    print(json.dumps({"ok": True, "device_kind": jax.devices()[0].device_kind,
                      "count": len(jax.devices())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
