"""What more than one decoder family is built from, owned by none of them.

``models/lfm2.py``, ``models/kimi_linear.py``, ``models/nemotron_h.py`` and
``models/phi4flash.py`` (four of the benchmark's six cells) import these and
never one another, so an edit here is an edit to every cell that uses the
part, and an edit to a family's own file is an edit to its cell alone:

- :class:`RMSNorm`, :class:`SwiGLU`, :func:`mm`, :data:`dense_init`;
- blocked causal attention, :func:`attend` (:data:`BLOCK`,
  :func:`attend_pairs`, :func:`sown_attend_pairs`);
- the scan mixers' initialisers and causal convolution
  (:func:`a_log_init`, :func:`dt_bias_init`, :func:`causal_taps`);
- how a family counts its own work (:func:`work_counts_beside`, with the
  names of what a :class:`~olearning_sim_tpu.models.moe.DroplessMoE` layer
  sows, which that layer imports from here).

The functions that are checkpointed keep their names in a lowered program
(``_attend_prefix``, ``blocks``): rename one and every cell's program text
changes.
"""

from __future__ import annotations

from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from olearning_sim_tpu.models.registry import WorkCounts, sown


dense_init = nn.initializers.lecun_normal()


def mm(x, kernel, dtype):
    return jnp.dot(x.astype(dtype), kernel.astype(dtype))


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + self.eps) * scale


class SwiGLU(nn.Module):
    mlp_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        W = x.shape[-1]
        w1 = self.param("w1", dense_init, (W, self.mlp_dim), jnp.float32)
        w3 = self.param("w3", dense_init, (W, self.mlp_dim), jnp.float32)
        w2 = self.param("w2", dense_init, (self.mlp_dim, W), jnp.float32)
        gated = jax.nn.silu(mm(x, w1, self.dtype)) * mm(x, w3, self.dtype)
        return mm(gated, w2, self.dtype)


# Queries a block of :func:`_attend`: phi4flash's window block, and a whole
# number of MXU tiles. 256 takes 0.42 to 0.82 of the time on the chip and
# doubles the blocks' code, which set-up pays (PERF.md section 6, PR 45).
BLOCK = 512


def _query_blocks(L: int, block: int):
    """(first query, end) of each block of ``block`` queries of ``L``; the
    last may be shorter."""
    return [(start, min(L, start + block)) for start in range(0, L, block)]


def attend_pairs(L: int, block: int = BLOCK) -> Tuple[int, int]:
    """(the (query, key) pairs causal attention over ``L`` tokens needs a
    head, the scores :func:`_attend` forms for them): every query block
    against the keys up to its own end."""
    return (L * (L + 1) // 2,
            sum((end - start) * end for start, end in _query_blocks(L, block)))


def sown_attend_pairs(n: int, L: int, before: int = 0):
    """What a layer that called :func:`_attend` on ``n`` sequences of ``L``
    tokens sows: :func:`attend_pairs` times ``n``, after ``before`` zeros
    (the counts of the model's other layers)."""
    return jnp.asarray(
        [0] * before + [n * pairs for pairs in attend_pairs(L)], jnp.int32)


def _attend_prefix(q, k, v):
    """Softmax attention of the LAST ``Q`` queries of a prefix, q [n, Q, G,
    R, D], over all of its keys k [n, P, G, D] and v [n, P, G, Dv]: query
    ``i`` sees keys ``0 .. P - Q + i``."""
    Q, P, D = q.shape[1], k.shape[1], q.shape[-1]
    scores = jnp.einsum("nqgrd,nkgd->ngrqk", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(D)
    causal = jnp.arange(P) <= jnp.arange(P - Q, P)[:, None]
    probs = jax.nn.softmax(
        jnp.where(causal, scores, jnp.finfo(jnp.float32).min), -1)
    return jnp.einsum("ngrqk,nkgd->nqgrd", probs.astype(q.dtype), v)


def _attend(q, k, v, block: int = BLOCK):
    """Causal softmax attention of q [n, L, G, R, D] over k [n, L, G, D] and
    v [n, L, G, Dv] (R query heads a key/value head), scores and softmax in
    float32. By blocks of ``block`` queries: a block scores the keys up to
    its own end and no others, so a row's softmax is over exactly the keys
    it sees, the largest score array is ``block x L`` a head, and of the
    ``L x L`` pairs :func:`attend_pairs` are formed (5/8 at four blocks).
    A last block shorter than ``block`` is a shorter block. One
    ``jax.checkpoint`` around all of it: the backward pass recomputes the
    scores rather than keep them."""
    L = q.shape[1]
    if L <= block:
        return jax.checkpoint(_attend_prefix)(q, k, v)

    def blocks(q, k, v):
        return jnp.concatenate([
            _attend_prefix(q[:, start:end], k[:, :end], v[:, :end])
            for start, end in _query_blocks(L, block)], 1)

    return jax.checkpoint(blocks)(q, k, v)


attend = _attend


def a_log_init(key, shape, dtype=jnp.float32):
    """log of uniform(1, 16): the family's modelling code."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of a step drawn log-uniformly from [0.001, 0.1]."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, np.log(0.001), np.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def causal_taps(x, taps):
    """Causal depthwise convolution of ``x`` [n, L, D] (float32) with
    ``taps`` [T, D]; tap j multiplies the input T-1-j positions back."""
    T, L = taps.shape[0], x.shape[1]
    x = jnp.pad(x, ((0, 0), (T - 1, 0), (0, 0)))
    return sum(taps[j] * x[:, j:j + L] for j in range(T))


# What a DroplessMoE layer sows as ``moe_stats`` on every call, one int32
# vector: these three counts, then the assignments each held expert got.
STATS_HEAD = ("assignments_total", "assignments_local", "assignments_computed")
# ... and beside it, one int32: the trips its loop over row windows took.
TRIPS = "moe_window_trips"


def describe_stats(stats: np.ndarray) -> dict:
    """Work counts from the ``moe_stats`` of a model's expert layers summed
    over some stretch of work (``[layers, 3 + held]``): the assignments
    made, routed to held experts and computed, and the largest and the mean
    load of a held expert (one of one layer) over that stretch."""
    stats = np.asarray(stats, np.int64).reshape(-1, stats.shape[-1])
    head = dict(zip(STATS_HEAD, stats[:, :len(STATS_HEAD)].sum(0).tolist()))
    loads = stats[:, len(STATS_HEAD):]
    return {
        "moe_assignments_total": head["assignments_total"],
        "moe_assignments_local": head["assignments_local"],
        "moe_assignments_computed": head["assignments_computed"],
        "moe_expert_load_max": int(loads.max()),
        "moe_expert_load_mean": float(loads.mean()),
    }


def gather_stats(intermediates):
    """The ``moe_stats`` a forward pass sowed, one row an expert layer."""
    found = sown(intermediates, "moe_stats")
    return jnp.stack(found) if found else None


def work_counts_beside(sown_name: str, names: Tuple[str, ...]) -> WorkCounts:
    """For the ``ModelSpec`` of a model whose mixers sow counts of their own
    (``sown_name``: one int32 vector a layer, ``len(names)`` long) beside
    the expert layers': one array of a forward pass's counts, the mixers' vectors
    summed as its one row where there are no expert layers, else the expert
    layers' ``moe_stats`` a row each, then a row that starts with the
    mixers' sum, then a row that starts with the expert layers' trips summed
    (:data:`TRIPS`), both zero after that (the names need no more than a
    layer with one held expert is wide); summed over some stretch of work it
    is named ``{names[i]: count}`` and, where there are expert layers, what
    :func:`describe_stats` names and ``{TRIPS: count}``."""

    def gather(intermediates):
        own = sown(intermediates, sown_name)
        if not own:
            return None
        own = sum(own)
        experts = gather_stats(intermediates)
        if experts is None:
            return own[None]
        trips = sum(sown(intermediates, TRIPS))[None]
        return jnp.concatenate([experts] + [
            jnp.pad(row, (0, experts.shape[1] - len(row)))[None]
            for row in (own, trips)])

    def describe(counts: np.ndarray) -> dict:
        layers = max(len(counts) - 2, 0)
        named = dict(zip(names, np.asarray(
            counts[layers, :len(names)], np.int64).tolist()))
        if layers:
            named.update(describe_stats(counts[:layers]))
            named[TRIPS] = int(counts[-1, 0])
        return named

    return WorkCounts(gather, describe)
