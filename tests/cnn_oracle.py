"""Independent NumPy oracle for the cnn4 family (BASELINE ±0.3% parity).

Implements the same network as ``olearning_sim_tpu/models/cnn.py::CNN``
— three stride-2 SAME 3x3 convs + ReLU, global average pool, Dense head —
entirely in NumPy float32, forward and backward, with FedAvg local SGD
using the engine's exact RNG streams (fold_in(fold_in(base_key, uid),
round) then fold_in(key, step) -> randint) and multiplicity-weighted
minibatches (what the engine's auto sample mode picks for cnn4 at the
sizes tests/test_parity_cnn.py runs: see ``FedCore.use_multiplicity``). No
code is shared with the engine beyond jax.random for RNG stream
reproduction — RNG is an input, not the system under test.

Local SGD gives every client its own weights after the first step, so all
convs are batched GEMMs over im2col patches: [C, rows, K] @ [C, K, F]
with a leading cohort axis C (np.matmul -> BLAS per client).

SAME padding for kernel 3 / stride 2 / even input: out = in/2, total pad
1 -> (0 before, 1 after) on both spatial axes (the TF/XLA convention flax
follows).
"""

from __future__ import annotations

import jax
import numpy as np


# ---------------------------------------------------------------- im2col
def im2col_s2(x: np.ndarray) -> np.ndarray:
    """[C, B, H, W, Cin] -> [C, B, (H/2)*(W/2), 9*Cin] patches for a 3x3
    stride-2 SAME conv (even H, W). Patch order (kh, kw, cin) matches the
    flax kernel layout [3, 3, Cin, F] flattened to [9*Cin, F]."""
    C, B, H, W, Ci = x.shape
    xp = np.zeros((C, B, H + 1, W + 1, Ci), x.dtype)
    xp[:, :, :H, :W, :] = x
    OH, OW = H // 2, W // 2
    s = xp.strides
    pat = np.lib.stride_tricks.as_strided(
        xp,
        shape=(C, B, OH, OW, 3, 3, Ci),
        strides=(s[0], s[1], 2 * s[2], 2 * s[3], s[2], s[3], s[4]),
    )
    return np.ascontiguousarray(pat).reshape(C, B, OH * OW, 9 * Ci)


def col2im_s2(dpat: np.ndarray, H: int, W: int, Ci: int) -> np.ndarray:
    """Adjoint of :func:`im2col_s2`: scatter-add patch cotangents back to
    the [C, B, H, W, Cin] input."""
    C, B, P, K = dpat.shape
    OH, OW = H // 2, W // 2
    d = dpat.reshape(C, B, OH, OW, 3, 3, Ci)
    out = np.zeros((C, B, H + 1, W + 1, Ci), dpat.dtype)
    for kh in range(3):
        for kw in range(3):
            out[:, :, kh : kh + 2 * OH : 2, kw : kw + 2 * OW : 2, :] += (
                d[:, :, :, :, kh, kw, :]
            )
    return out[:, :, :H, :W, :]


# ---------------------------------------------------------------- params
def init_from_flax(params) -> dict:
    """Flax cnn4 param tree -> oracle layout (conv kernels flattened to
    [9*Cin, F])."""
    out = {}
    for i in range(3):
        k = np.asarray(params[f"Conv_{i}"]["kernel"], np.float32)
        out[f"w{i}"] = k.reshape(-1, k.shape[-1])
        out[f"b{i}"] = np.asarray(params[f"Conv_{i}"]["bias"], np.float32)
    out["wd"] = np.asarray(params["Dense_0"]["kernel"], np.float32)
    out["bd"] = np.asarray(params["Dense_0"]["bias"], np.float32)
    return out


def tile(p: dict, C: int) -> dict:
    """Global params -> per-client copies with a leading cohort axis."""
    return {k: np.repeat(v[None], C, axis=0).copy() for k, v in p.items()}


# --------------------------------------------------------------- network
def forward(p: dict, x: np.ndarray):
    """Per-client forward. x: [C, B, H, W, 3]; p: per-client (leading C).
    Returns (cache, logits [C, B, ncls])."""
    C, B = x.shape[:2]
    cache = {"shapes": []}
    h = x.astype(np.float32)
    for i in range(3):
        H, W, Ci = h.shape[2:]
        cache["shapes"].append((H, W, Ci))
        pat = im2col_s2(h)                               # [C, B, P, K]
        P, K = pat.shape[2:]
        F = p[f"w{i}"].shape[-1]
        z = np.matmul(
            pat.reshape(C, B * P, K), p[f"w{i}"]
        ).reshape(C, B, P, F) + p[f"b{i}"][:, None, None, :]
        cache[f"pat{i}"] = pat
        cache[f"z{i}"] = z
        h = np.maximum(z, 0.0).reshape(C, B, H // 2, W // 2, F)
    cache["h3_shape"] = h.shape
    OH, OW = h.shape[2:4]
    pooled = h.mean(axis=(2, 3))                         # [C, B, F3]
    cache["pooled"] = pooled
    logits = np.matmul(pooled, p["wd"]) + p["bd"][:, None, :]
    return cache, logits


def backward(p: dict, cache: dict, dlogits: np.ndarray) -> dict:
    """Per-client grads for loss whose logit cotangent is ``dlogits``
    [C, B, ncls] (already weighted per sample)."""
    C, B = dlogits.shape[:2]
    pooled = cache["pooled"]
    grads = {
        "wd": np.matmul(np.swapaxes(pooled, 1, 2), dlogits),
        "bd": dlogits.sum(axis=1),
    }
    dpooled = np.matmul(dlogits, np.swapaxes(p["wd"], 1, 2))   # [C, B, F3]
    _, _, OH, OW, F3 = cache["h3_shape"]
    dh = np.broadcast_to(
        dpooled[:, :, None, None, :] / (OH * OW), cache["h3_shape"]
    )
    for i in (2, 1, 0):
        z = cache[f"z{i}"]                               # [C, B, P, F]
        P, F = z.shape[2:]
        dz = dh.reshape(C, B, P, F) * (z > 0)
        pat = cache[f"pat{i}"]
        K = pat.shape[-1]
        pm = pat.reshape(C, B * P, K)
        dm = dz.reshape(C, B * P, F)
        grads[f"w{i}"] = np.matmul(np.swapaxes(pm, 1, 2), dm)
        grads[f"b{i}"] = dz.sum(axis=(1, 2))
        if i > 0:
            dpat = np.matmul(dm, np.swapaxes(p[f"w{i}"], 1, 2))
            H, W, Ci = cache["shapes"][i]
            dh = col2im_s2(dpat.reshape(C, B, P, K), H, W, Ci)
    return grads


def np_softmax(z):
    z = z - z.max(axis=-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(axis=-1, keepdims=True)


# -------------------------------------------------------------- training
def local_sgd_cohort(p_global: dict, x, y, num_samples, uids, base_key,
                     round_idx: int, *, steps: int, batch: int, lr: float,
                     num_classes: int) -> dict:
    """All cohort clients' local SGD at once. Returns per-client deltas
    (leading C axis). Mirrors FedCore._masked_sgd in multiplicity mode:
    loss = sum_i sw_i * CE_i with sw = minibatch multiplicities / batch."""
    C, B = x.shape[:2]
    p = tile(p_global, C)
    eye = np.eye(num_classes, dtype=np.float32)
    for i in range(steps):
        sw = np.zeros((C, B), np.float32)
        for c in range(C):
            key = jax.random.fold_in(
                jax.random.fold_in(base_key, int(uids[c])), round_idx
            )
            idx = np.asarray(jax.random.randint(
                jax.random.fold_in(key, i), (batch,), 0, int(num_samples[c])
            ))
            np.add.at(sw[c], idx, 1.0)
        sw /= batch
        cache, logits = forward(p, x)
        dlogits = (np_softmax(logits) - eye[y]) * sw[..., None]
        grads = backward(p, cache, dlogits)
        for k in p:
            p[k] -= lr * grads[k]
    return {k: p[k] - p_global[k][None] for k in p_global}


def fedavg_round(p_global: dict, x, y, num_samples, uids, weights, base_key,
                 round_idx: int, *, steps: int, batch: int, lr: float,
                 num_classes: int) -> dict:
    """One FedAvg round over the cohort: weighted-mean delta applied to the
    global params (the engine's fedavg server optimizer is sgd(1.0) on the
    negative mean delta)."""
    delta = local_sgd_cohort(
        p_global, x, y, num_samples, uids, base_key, round_idx,
        steps=steps, batch=batch, lr=lr, num_classes=num_classes,
    )
    w = np.asarray(weights, np.float32)
    den = w.sum()
    return {
        k: p_global[k] + np.tensordot(w, delta[k], axes=(0, 0)) / den
        for k in p_global
    }


def evaluate(p_global: dict, x, y) -> float:
    """Accuracy of the global model on [N, H, W, 3] eval data."""
    _, logits = forward(tile(p_global, 1), x[None])
    return float((logits[0].argmax(-1) == y).mean())
