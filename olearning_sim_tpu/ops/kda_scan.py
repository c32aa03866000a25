"""The forward pass of the gated delta-rule scan (``models/kimi_linear.py``
``chunk_scan``) as one Pallas TPU kernel, and the wrapper that puts it where
that scan runs forward.

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                    from S_0 = 0

**The kernel** (:func:`scan_forward`). A grid over (sequence x group of
heads, chunk): the chunk axis is sequential, and a head's ``K x V`` float32
state lives in a VMEM scratch across its sequence's chunks and never visits
HBM. ``q, k, v, g`` arrive as ``[n, L, H, K]``, which is ``[n, L, H K]`` for
nothing, and a block ``(1, CHUNK, K)`` at ``(sequence, chunk, head)`` is one
head's chunk with no transpose; ``o`` leaves the same way, ``beta`` ``[n, L,
H]`` as a chunk's whole ``(CHUNK, H)`` block. A grid step does in VMEM, term
for term, what the plain code's ``_intra_chunk``, ``_unit_lower_inverse``,
the ``w`` product and ``_chunk_step`` do in a dozen fusions (that
docstring has the algebra): the running sum ``G`` (by doubling, six shifted
adds); the pairwise decays of the diagonal sub-blocks, each exponent a
difference that is never positive and masked before the exponential; the
blocks left of the diagonal as products split at a sub-block's first token;
the unit lower-triangular inverse by substitution inside a sub-block and
``[[T1, 0], [-T2 A21 T1, T2]]`` between them; the writes ``U`` (the inverse
applied once, to ``beta (V - (exp(G) K) S)``, where the plain code, which
inverts before it knows ``S``, forms ``[W_v, W_k]`` first), ``O`` and the
state that leaves the chunk. Everything is float32 and every product
``Precision.HIGHEST``; no operand is cast below float32.

Layout inside a chunk: a token's channels lie on the lanes. A pairwise block
is built a later token ``t`` at a time, the earlier tokens ``j`` on the
sublanes, so the sum over the channels of ``x_t k_j exp(G_t - G_j)`` is one
lane reduction of a ``[SUB, K]`` tile that leaves column ``t`` of the
*transposed* block with ``j`` still on the sublanes: ``A^T`` and ``B^T`` are
what the kernel holds, the inverse it builds is the transposed one, and the
products that need ``A``, ``B`` or the inverse contract their first
dimension. Such a column is also what the substitution wants: in the
upper-triangular ``(I + a^T) Y = I``, taken from the last row up, a finished
row ``t`` leaves every earlier row ``j`` with the weight ``a[t, j]``, one
broadcast multiply-subtract a step and no reduction. What has no product in
it (running sum, pairwise blocks, substitution) runs over a step's heads and
their sub-blocks at once, the sub-blocks on a leading axis: the kernel's body
is about 1,700 operations to trace and lower (0.2 s and 0.35 s on this
repository's host) where one written as loops over heads and sub-blocks was
13,000 (1.0 s and 1.9 s, every time a program that holds the kernel is
traced or lowered: CHANGES.md, PR 49).

**The wrapper** (:func:`chunk_scan`): a ``jax.custom_vjp`` whose forward is
the kernel where the program is lowered for a TPU and the plain-JAX scan
elsewhere (``jax.lax.platform_dependent``: no flag, no config key, no model
name decides), and whose backward pass is ``jax.vjp`` of the plain-JAX scan at
the five kept inputs: what ``jax.checkpoint(chunk_scan)`` did before there was
a kernel, the forward computed again in plain JAX and then its backward pass,
and what it kept. The backward kernel is the next step.

**Where it lowers** (``ops/lowering.py`` ``manual_over_auto_axes``): Mosaic
refuses a kernel under a mesh axis that is left to the auto partitioner, and
``FedCore``'s round program is a ``shard_map`` manual over ``dp`` that leaves
``mp`` (size 1 on that branch) auto. The kernel's call therefore carries an
inner ``shard_map`` of its own over whatever axes the context leaves auto,
everything replicated, and its ``out_shape`` says how the output varies over
the manual ones (``vma``). Outside any mesh the helper does nothing.
``scripts/check_kda_scan_tpu.py`` is the chip's check and timing.
"""

from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from olearning_sim_tpu.ops.lowering import manual_over_auto_axes

# Tokens of a chunk and of a sub-block: the plain code's (the kernel's
# ``kda_scan_kernel_chunks`` counts the same chunks as its
# ``kda_scan_chunks``).
CHUNK = 64
SUB = 16
# Heads a grid step takes (where the model has that many): their chains of
# small dependent products and substitution steps are independent, so the
# scheduler has one to issue from while another waits. By the compiler's own
# schedule for a v5e, bundles a head's chunk: 2,246 at 1, 2,056 at 2, 1,928
# at 4, 1,873 at 8 (CHANGES.md, PR 49).
HEADS_PER_STEP = 4
_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _running_sum(g):
    """``g`` ``[CHUNK, lanes]`` summed over the tokens up to each one: by
    doubling, the rows ``s`` back added for ``s`` = 1, 2, 4 .."""
    row = _iota(g.shape, 0)
    G, s = g, 1
    while s < CHUNK:
        G = G + jnp.where(row >= s, pltpu.roll(G, s, 0), 0.0)
        s *= 2
    return G


def _pairwise(q, bk, k, G):
    """The diagonal sub-blocks' transposed pairwise sums, every sub-block at
    once (``q, bk, k, G`` ``[blocks, SUB, K]``, a sub-block's tokens on the
    sublanes): ``A^T``'s blocks ``[blocks, SUB, SUB]``, ``[b, j, t]`` =
    ``sum_c bk[t, c] k[j, c] exp(G[t, c] - G[j, c])`` at the earlier tokens
    ``j < t`` and 0 elsewhere; ``B^T``'s, the same with ``q`` and ``j <=
    t``; and ``A^T``'s columns, ``[blocks, SUB, 1]`` for each ``t``, as
    :func:`_substitute` takes them."""
    j_sub = _iota((1, SUB, 1), 1)
    lane = _iota((1, SUB, SUB), 2)
    a_blocks = b_blocks = jnp.zeros(k.shape[:2] + (SUB,), _F32)
    a_columns = []
    for t in range(SUB):
        later = slice(t, t + 1)
        # Earlier tokens on the sublanes; a later one's are masked before the
        # exponential, whose argument is then never positive.
        kd = k * jnp.exp(jnp.where(j_sub <= t, G[:, later] - G, -jnp.inf))
        a_column = jnp.where(j_sub < t, jnp.sum(
            bk[:, later] * kd, axis=-1, keepdims=True), 0.0)
        b_column = jnp.sum(q[:, later] * kd, axis=-1, keepdims=True)
        a_columns.append(a_column)
        # A column is the same on every lane: a lane select puts it at t.
        a_blocks = jnp.where(lane == t, a_column, a_blocks)
        b_blocks = jnp.where(lane == t, b_column, b_blocks)
    return a_blocks, b_blocks, a_columns


def _substitute(a_columns):
    """The transposed ``(I + a)^-1`` of the strictly lower-triangular ``SUB x
    SUB`` blocks ``a``, every block at once, by substitution in the
    upper-triangular ``(I + a^T) Y = I`` from its last row up: once row ``t``
    of ``Y`` is final, ``a[t, j]`` times it leaves every row ``j < t``.
    ``a_columns[t]`` ``[blocks, SUB, 1]`` holds those coefficients with ``j``
    on the sublanes, where ``Y``'s rows lie. Returns ``[blocks, SUB, CHUNK]``:
    block ``b``'s ``Y`` at the lanes of its place in its chunk, ``(b mod
    CHUNK / SUB) SUB`` on, and 0 beside it, so that a chunk's blocks, row
    under row, are the block-diagonal ``[CHUNK, CHUNK]`` that
    :func:`_merged_inverse` starts from."""
    shape = (a_columns[0].shape[0], SUB, CHUNK)
    Y = (_iota(shape, 2) == _iota(shape, 0) % (CHUNK // SUB) * SUB
         + _iota(shape, 1)).astype(_F32)
    for t in range(SUB - 1, 0, -1):
        Y = Y - a_columns[t] * Y[:, t:t + 1]        # a[t, j] = 0 at j >= t
    return Y


def _chunk_systems(q, bk, k, G, a_blocks, b_blocks):
    """A chunk's ``A^T`` (``A[t, j] = beta_t sum_c k_t k_j exp(G_t - G_j)``,
    ``j < t``) and ``B^T`` (the same with ``q_t`` and ``j <= t``), ``[CHUNK,
    CHUNK]`` each, from their diagonal sub-blocks (``[CHUNK / SUB, SUB,
    SUB]``, :func:`_pairwise`) and, left of the diagonal, products."""
    a_cols, b_cols = [], []
    for i in range(CHUNK // SUB):
        r = i * SUB
        rows = slice(r, r + SUB)
        a_col, b_col = [a_blocks[i]], [b_blocks[i]]
        if i:
            # Left of the diagonal: exp(G_t - G_j) split at the sub-block's
            # first token r, j < r <= t, neither exponent positive.
            since = jnp.exp(G[rows] - G[r:r + 1])
            until = k[:r] * jnp.exp(G[r:r + 1] - G[:r])
            left = _dot(until, jnp.concatenate(
                [bk[rows] * since, q[rows] * since], 0), _NT)   # [r, 2 SUB]
            a_col.insert(0, left[:, :SUB])
            b_col.insert(0, left[:, SUB:])
        if r + SUB < CHUNK:
            below = jnp.zeros((CHUNK - r - SUB, SUB), _F32)
            a_col.append(below)
            b_col.append(below)
        a_cols.append(jnp.concatenate(a_col, 0))
        b_cols.append(jnp.concatenate(b_col, 0))
    return jnp.concatenate(a_cols, 1), jnp.concatenate(b_cols, 1)


def _merged_inverse(a_t, Tt):
    """``(I + A)^-1`` transposed, ``[CHUNK, CHUNK]``, from ``A^T`` and the
    block-diagonal ``Tt`` of the diagonal sub-blocks' transposed inverses:
    two and two, ``[[T1, 0], [-T2 A21 T1, T2]]`` = ``T - T L T`` with ``T``
    the block-diagonal of the inverses so far and ``L`` the blocks of ``A``
    between each pair (transposed: ``Tt - Tt L^T Tt``), until one block is
    left."""
    row, col = _iota((CHUNK, CHUNK), 0), _iota((CHUNK, CHUNK), 1)
    size = SUB
    while size < CHUNK:
        # In A^T: an earlier half's rows against its later half's columns.
        pair = (row // (2 * size) == col // (2 * size)) & (
            row // size < col // size)
        Tt = Tt - _dot(_dot(Tt, jnp.where(pair, a_t, 0.0)), Tt)
        size *= 2
    return Tt


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state_ref, *,
            heads, width):
    """One chunk of ``heads`` heads (``width`` channels each, side by side on
    the lanes of the blocks) against the states in ``state_ref``. What has no
    product in it (the running sum, the pairwise sub-blocks, the
    substitution) runs over the step's heads at once, their sub-blocks on a
    leading axis: a few hundred operations to trace and lower where a loop
    over heads and sub-blocks has thousands, and independent chains for the
    scheduler to issue from."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    group = pl.program_id(0) % (beta_ref.shape[-1] // heads)
    beta_all = beta_ref[0]                                   # [CHUNK, H]
    head_lane = _iota(beta_all.shape, 1)
    G_all = _running_sum(g_ref[0])                 # [CHUNK, heads width]
    per_head = []
    for h in range(heads):
        lanes = slice(h * width, (h + 1) * width)
        beta = jnp.sum(jnp.where(head_lane == group * heads + h, beta_all,
                                 0.0), axis=-1, keepdims=True)
        k = k_ref[0, :, lanes]
        per_head.append((q_ref[0, :, lanes], beta * k, k, G_all[:, lanes],
                         beta))

    def sub_blocks(of_heads):   # [CHUNK, width] a head -> [blocks, SUB, width]
        return jnp.concatenate(of_heads, 0).reshape(-1, SUB, width)

    a_blocks, b_blocks, a_columns = _pairwise(
        *map(sub_blocks, list(zip(*per_head))[:4]))
    t_blocks = _substitute(a_columns)
    per_chunk = CHUNK // SUB
    for h, (q, bk, k, G, beta) in enumerate(per_head):
        lanes = slice(h * width, (h + 1) * width)
        mine = slice(h * per_chunk, (h + 1) * per_chunk)
        a_t, b_t = _chunk_systems(q, bk, k, G, a_blocks[mine], b_blocks[mine])
        Tt = _merged_inverse(a_t, t_blocks[mine].reshape(CHUNK, CHUNK))
        decay_in = jnp.exp(G)
        S = state_ref[h]
        # (I + A) U = beta (V - (exp(G) K) S): the plain code's W_v - W_k S
        # with the inverse applied once, to the difference.
        U = _dot(Tt, beta * (v_ref[0, :, lanes] - _dot(decay_in * k, S)), _TN)
        o_ref[0, :, lanes] = _dot(q * decay_in, S) + _dot(b_t, U, _TN)
        G_last = G[CHUNK - 1:]
        state_ref[h] = jnp.exp(G_last).T * S + _dot(
            k * jnp.exp(G_last - G), U, _TN)


def kernel_takes(q, v) -> bool:
    """Whether the kernel has these widths: a head's keys and values fill
    whole lanes and its state is square."""
    return q.shape[-1] == v.shape[-1] and q.shape[-1] % 128 == 0


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _scan_call(q, k, v, g, beta, *, heads, interpret):
    """The kernel over whole chunks: ``q, k, v, g`` ``[n, N CHUNK, H K]``,
    ``beta`` ``[n, N CHUNK, H]``, ``heads`` of the ``H`` a grid step. A
    ``jax.jit`` of its own so that the kernel's body is traced once a shape
    and lowered once a program (one custom call, called by every KDA layer)
    however often a model is traced (``build_fedcore`` traces one some sixty
    times, and each of a model's KDA layers calls this): without it the
    round program of the benchmark's cell took 54 s to lower where it takes
    10 (CHANGES.md, PR 49)."""
    n, length, H = beta.shape
    K = q.shape[-1] // H
    groups = H // heads
    tile = pl.BlockSpec((1, CHUNK, heads * K),
                        lambda i, c: (i // groups, c, i % groups))
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, width=K),
        grid=(n * groups, length // CHUNK),
        in_specs=[tile] * 4 + [
            pl.BlockSpec((1, CHUNK, H), lambda i, c: (i // groups, c, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(q.shape, _F32, vma=jax.typeof(q).vma),
        scratch_shapes=[pltpu.VMEM((heads, K, K), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, g, beta)


def scan_forward(q, k, v, g, beta, *, interpret: bool = False):
    """``o`` ``[n, L, H, V]`` of the recurrence above by the kernel; ``q, k,
    g`` ``[n, L, H, K]``, ``v`` ``[n, L, H, V]``, ``beta`` ``[n, L, H]``,
    float32, ``g <= 0``, ``K`` = ``V`` a multiple of 128. A ragged ``L`` is
    padded to whole chunks with tokens that write nothing and decay
    nothing."""
    n, L, H, K = q.shape
    if not kernel_takes(q, v):
        raise ValueError(
            f"the kernel takes K = V, a multiple of 128; got {K}, "
            f"{v.shape[-1]}")

    def flat(x):            # [n, L, H, K] -> [n, N CHUNK, H K]
        x = x.astype(_F32).reshape(n, L, -1)
        return jnp.pad(x, ((0, 0), (0, -L % CHUNK), (0, 0)))

    call = functools.partial(_scan_call, heads=math.gcd(H, HEADS_PER_STEP),
                             interpret=interpret)
    o = manual_over_auto_axes(call)(*map(flat, (q, k, v, g, beta)))
    return o[:, :L].reshape(n, L, H, K)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def chunk_scan(plain: Callable, q, k, v, g, beta):
    """``(o, kernel_chunks)``: ``plain(q, k, v, g, beta)``'s ``o`` by the
    kernel where the program is lowered for a TPU and the widths are the
    kernel's (``kernel_chunks`` = the chunks it took, ``n ceil(L / CHUNK)``,
    int32) and by ``plain`` itself elsewhere (0). Differentiated, the
    backward pass is ``jax.vjp(plain)`` at the five inputs, which are all it
    keeps."""

    def kernel(*xs):
        n, L = xs[0].shape[:2]
        return scan_forward(*xs), jnp.int32(n * -(-L // CHUNK))

    def plainly(*xs):
        return plain(*xs), jnp.int32(0)

    if not kernel_takes(q, v):
        return plainly(q, k, v, g, beta)
    return jax.lax.platform_dependent(
        q, k, v, g, beta, tpu=kernel, default=plainly)


def _chunk_scan_fwd(plain, *xs):
    return chunk_scan(plain, *xs), xs


def _chunk_scan_bwd(plain, xs, cotangents):
    # ``jax.checkpoint`` so that the forward computed here a second time
    # carries the label every recomputation has (``rematted_computation`` in
    # an operation's path: ``benchmark/trace_reduce.py`` RECOMPUTED); its own
    # first forward has no use and the compiler drops it.
    return jax.vjp(jax.checkpoint(plain), *xs)[1](cotangents[0])


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)
