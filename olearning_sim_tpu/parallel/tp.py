"""Tensor parallelism for the transformer families over the mesh ``mp`` axis.

Design (idiomatic XLA, per the scaling-book recipe): the engine's round
program is a ``shard_map`` that is *manual* over ``dp`` (clients) and
*auto* over ``mp`` — large model tensors are annotated with
``PartitionSpec``s over ``mp`` and GSPMD inserts the collectives
(all-gather/reduce-scatter through attention and the Megatron-style
column->row FFN split). No hand-written psums, no model rewrites: the same
Flax modules run at any ``mp``.

Replaces nothing in the reference — it has no model parallelism at all
(SURVEY.md section 2.5: the inventory of DP/TP/PP/SP is "absent"); this is
the rebuild's first-class scaling axis for the DistilBERT/ViT families
(BASELINE configs 4-5).

Sharding rules (Megatron layout):

- attention ``query/key/value``: kernel ``[W, H, hd]`` -> ``P(None, mp, None)``
  (heads split), bias ``[H, hd]`` -> ``P(mp, None)``
- attention ``out``: kernel ``[H, hd, W]`` -> ``P(mp, None, None)`` (row
  parallel; GSPMD reduce-scatters), bias replicated
- FFN up (``Dense_0`` inside a block): kernel ``[W, M]`` -> ``P(None, mp)``,
  bias ``[M]`` -> ``P(mp)``
- FFN down (``Dense_1`` inside a block): kernel ``[M, W]`` -> ``P(mp, None)``,
  bias replicated
- embeddings / LayerNorm / heads / everything else: replicated.

A leaf whose to-be-sharded dimension does not divide ``mp`` (e.g. ViT-Tiny's
3 heads at mp=2) falls back to replication for that leaf — correct, just
not distributed.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
from jax.sharding import PartitionSpec as P


_BLOCK_MARKERS = ("TransformerBlock", "EncoderBlock", "Block")
_ATTN_MARKER = "MultiHeadDotProductAttention"


def _path_str(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        else:
            names.append(str(k))
    return tuple(names)


def _rule(names: Tuple[str, ...], shape: Tuple[int, ...], axis: str):
    """Spec for one param leaf, or P() if it stays replicated."""
    in_block = any(any(m in n for m in _BLOCK_MARKERS) for n in names)
    if not in_block:
        return P()
    leaf = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    in_attn = any(_ATTN_MARKER in n for n in names)
    if in_attn:
        if parent in ("query", "key", "value"):
            if leaf == "kernel" and len(shape) == 3:
                return P(None, axis, None)
            if leaf == "bias" and len(shape) == 2:
                return P(axis, None)
        if parent == "out":
            if leaf == "kernel" and len(shape) == 3:
                return P(axis, None, None)
            return P()
        return P()
    if parent == "Dense_0":  # FFN up projection
        if leaf == "kernel" and len(shape) == 2:
            return P(None, axis)
        if leaf == "bias" and len(shape) == 1:
            return P(axis)
    if parent == "Dense_1" and leaf == "kernel" and len(shape) == 2:
        return P(axis, None)  # FFN down projection (row parallel)
    return P()


def tp_param_specs(params: Any, mp: int, axis: str = "mp") -> Any:
    """PartitionSpec pytree for ``params`` sharding the transformer-block
    tensors over ``axis``. Leaves whose target dim doesn't divide ``mp``
    (or anything outside a block) come back replicated, so the result is
    always valid for the given mesh."""

    def spec_for(path, leaf):
        if mp <= 1:
            return P()
        spec = _rule(_path_str(path), tuple(leaf.shape), axis)
        for dim, name in zip(leaf.shape, spec):
            if name == axis and dim % mp != 0:
                return P()  # indivisible -> replicate this leaf
        return spec

    return jax.tree_util.tree_map_with_path(spec_for, params)


def sharded_fraction(params: Any, specs: Any) -> float:
    """Fraction of parameter elements that live on mp-sharded leaves —
    the dryrun's 'non-redundant work' evidence. Works on concrete arrays
    and on ``jax.eval_shape`` outputs alike."""
    import math

    total = sharded = 0
    for leaf, spec in zip(jax.tree.leaves(params),
                          jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        n = math.prod(leaf.shape)
        total += n
        if any(s is not None for s in spec):
            sharded += n
    return sharded / max(total, 1)


def warn_if_unsharded(params: Any, specs: Any, n_way: int,
                      axis: str = "mp") -> float:
    """Log the sharding coverage of a parallel plan; warn when a requested
    model axis degrades to (almost) full replication.

    The per-leaf indivisibility fallback in :func:`tp_param_specs` is
    silent by design (the program stays correct), but a user requesting
    ``mp=4`` on a model whose dims don't divide 4 would otherwise get 0%
    sharding with no signal. Returns the fraction."""
    import logging
    import warnings

    frac = sharded_fraction(params, specs)
    logging.getLogger(__name__).info(
        "%s=%d sharding coverage: %.1f%% of parameter elements", axis, n_way,
        frac * 100.0,
    )
    if frac < 0.01:
        warnings.warn(
            f"{axis}={n_way} was requested but only {frac:.1%} of parameter "
            f"elements are sharded (dimensions indivisible by {n_way} fall "
            f"back to replication) — the model axis is doing no useful "
            f"work; pick a divisor of the model's head/FFN/expert counts",
            stacklevel=3,
        )
    return frac
