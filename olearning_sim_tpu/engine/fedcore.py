"""FedCore — the compiled FL round engine (the TPU replacement for the
reference's execution layer).

Reference semantics being replaced (SURVEY.md sections 2.2, 3.3):

- ``Actor.loop_run`` runs one Python subprocess per virtual phone per step
  (``ols_core/taskMgr/utils/utils_run_task.py:481-514``) — here each round is
  ONE jitted XLA program that advances every client.
- ``construct_run_params`` splits N virtual devices over M Ray actors
  (``ols_core/taskMgr/run_task.py:62-106``) — here clients are sharded over
  the mesh ``dp`` axis and vmapped in blocks inside ``shard_map``.
- Gradient shipping via Pulsar + external aggregation
  (``ols_core/deviceflow/non_grpc/sorter.py:37-92``, ``dispatcher.py:84-242``)
  — here the weighted-delta reduction is a ``psum`` over ICI.

Program shape::

    round_step = jit( shard_map( scan over client blocks:
                                     vmap over clients:
                                         lax.scan over local SGD steps
                                 -> psum(weighted deltas) )
                      -> server optimizer update )

Heterogeneity (per-client local-step counts / data sizes) is handled with
masking: step ``i`` is active iff ``i < num_steps[c]``; minibatch indices are
drawn in ``[0, num_samples[c])``; aggregation weights are 0 for padded or
non-participating clients. Behavior traces (churn/drop/spike) enter purely as
the ``weight``/``num_steps`` arrays, produced by the deviceflow trace compiler.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.traverse_util import flatten_dict, unflatten_dict
from jax.sharding import NamedSharding, PartitionSpec as P

from olearning_sim_tpu.engine import async_rounds, defense as defense_mod, pp_rounds
from olearning_sim_tpu.engine.algorithms import Algorithm
from olearning_sim_tpu.engine.client_data import ClientDataset
from olearning_sim_tpu.engine.round_stages import (  # noqa: F401
    ControlState,
    PersonalState,
    RoundMetrics,
    ServerState,
    _flat_pad_leaf,
    _to_varying,
    _tree_l2_sq,
    _tree_where,
    client_block,
    jit_round_step,
    next_state,
    server_commit,
)
from olearning_sim_tpu.models.lookup import LOOKUP_ROWS, TABLE
from olearning_sim_tpu.parallel.mesh import MeshPlan, global_put, pad_to_multiple


@dataclasses.dataclass
class StreamStats:
    """Host-side accounting of one block-streamed round
    (:meth:`FedCore.stream_round`)."""

    blocks: int                  # stream blocks executed
    block_rows: int              # global clients per stream block
    rows: int                    # padded population walked
    transfer_bytes: int          # host->device bytes staged
    host_transfer_s: float       # wall seconds inside staging calls
    # Estimated fraction of the steady-state transfer hidden behind
    # in-flight compute: 1 - (observed staging wall after the first
    # block / the same bytes at the first (unoverlapped) block's
    # measured rate). ~0 on synchronous backends (CPU), ->1 when the
    # runtime overlaps DMA with compute. None for single-block rounds.
    overlap_fraction: Optional[float]
    # Peak resident device bytes: params + optimizer state + the partial
    # aggregate carry + two staged blocks (current + prefetched). The
    # streamed round's O(block) HBM claim, stated as a number.
    peak_hbm_bytes_est: int
    state_bytes: int             # host-resident per-client state bytes


# ``sample_mode: "auto"``: wasted training FLOPs per gathered byte above
# which the minibatch is gathered. Read on one TPU v5 lite chip (jax 0.9.0,
# libtpu 0.0.34) on 2026-09-28, PR 26's tree on d96ac51, both realizations
# timed at eight ratios (PERF.md section 6, PR 26). With no more than twice
# the batch in local rows: multiplicity 3-4% faster at 6.1e2 (mlp2, 64 rows
# for 32) and 1.4e3 (cnn4, 50 for 32); gather 1.7x and 1.6x faster at 2.0e5
# (ViT-Tiny 50/32) and 1.7e6 (ResNet-18 40/20), and a 1.26x shorter round
# at 3.2e7 (DistilBERT 24/16, the benchmark's cell). The constant sits at
# the low end of that bracket because cnn4's two readings interpolate to a
# crossover at 2e3, and no lower so that the toy programs of the audit grid
# (3.5e3) and of tests/benchmark (1.7e3) stay what analysis/budgets.json
# and those tests hold them to. With more than twice the batch gather won
# at every ratio read: 2.1x at 4.3e3 (mlp2 256/32), 1.8x at 1.3e4 (cnn4
# 200/32), 4.8x at 1.9e4 (mlp2 1,024/32), and mlp2's readings interpolate
# to a crossover at 2.15x the batch (7e2) — hence the rule's second clause.
GATHER_FLOP_PER_BYTE = 4e3

TASKS = ("classification", "next_token")


def auto_uses_multiplicity(n_local: int, batch_size: int, row_bytes: int,
                           row_train_flops: Callable[[], float]) -> bool:
    """The ``sample_mode: "auto"`` rule, a function of shapes and — only
    where they do not decide — of what the model costs to train on one row
    (``row_train_flops()``). With no more local rows than the batch,
    multiplicity computes fewer rows than a gathered batch and gathers
    nothing. With more than twice the batch it trains over twice the rows
    the step needs, and gather won every such reading on the chip. Between
    the two it trains ``n_local - batch_size`` rows a step whose weight is
    zero where gathering moves ``batch_size`` rows: gather when the wasted
    FLOPs per moved byte pass ``GATHER_FLOP_PER_BYTE``."""
    if n_local <= batch_size:
        return True
    if n_local > 2 * batch_size:
        return False
    wasted = (n_local - batch_size) * row_train_flops()
    moved = batch_size * row_bytes
    return wasted <= GATHER_FLOP_PER_BYTE * moved


def _matmul_flops(jaxpr) -> float:
    """2 FLOPs per multiply-add of every ``dot_general`` and convolution in
    ``jaxpr``, sub-jaxprs included (a ``scan`` body times its length, every
    other one once: a ``while`` counts one trip, a ``cond`` all branches)."""
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            (lhs_contract, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2.0 * math.prod(eqn.outvars[0].aval.shape) * math.prod(
                lhs[d] for d in lhs_contract)
        elif name == "conv_general_dilated":
            rhs = eqn.invars[1].aval.shape
            _, in_dim, *spatial = eqn.params["dimension_numbers"].rhs_spec
            total += 2.0 * math.prod(eqn.outvars[0].aval.shape) * (
                rhs[in_dim] * math.prod(rhs[d] for d in spatial))
        else:
            trips = eqn.params["length"] if name == "scan" else 1
            total += trips * sum(
                _matmul_flops(sub)
                for sub in jax.core.jaxprs_in_params(eqn.params))
    return total


@dataclasses.dataclass(frozen=True)
class FedCoreConfig:
    batch_size: int = 32
    max_local_steps: int = 10
    # Clients vmapped at once per device; the scan over blocks bounds peak HBM
    # (activations scale with block_clients * batch_size, not population size).
    block_clients: int = 64
    eval_batch_size: int = 1024
    # Storage dtype for Ditto per-client personal params; None = same as the
    # global params. jnp.bfloat16 halves resident HBM at 10k-client scale.
    personal_dtype: Any = None
    # Minibatch realization. "gather": draw indices and gather rows (the
    # textbook form). "multiplicity": draw the same indices but realize the
    # batch as per-sample multiplicity weights over the client's full local
    # set — sum_b grad(x[i_b]) == sum_i m_i grad(x_i), so the gradient and
    # loss are EXACTLY those of the gathered minibatch (same RNG draw), but
    # the dynamic gather disappears from the hot loop and the fwd/bwd runs
    # over n_local samples instead of batch_size. The two modes are
    # mathematically identical for the same index draw (not bitwise: the
    # reductions accumulate in different orders). "auto" lets the core
    # choose per (local-set size, row shape and dtype, model): see
    # ``auto_uses_multiplicity`` for the rule and ``FedCore.use_multiplicity``
    # for the one place it is asked. For a model that sows an auxiliary
    # loss the two are different estimators of that term (multiplicity:
    # the whole local set; gather: the minibatch); "auto" may choose
    # either and the aux estimator follows the choice.
    sample_mode: str = "auto"
    # lax.scan unroll factor for the local-SGD step loop. Unrolling lets XLA
    # fuse/pipeline across sequential steps, and multiplies the program's
    # generated code by the factor: the benchmark's cells set 1 or 2
    # (benchmark/configs/), and PERF.md section 6 has what each measured.
    step_unroll: int = 1
    # Unroll factor for the outer scan over client blocks. Successive blocks
    # are independent work (the carry is only an accumulator), so a small
    # unroll lets XLA software-pipeline one block's epilogue against the
    # next's prologue.
    block_unroll: int = 1
    # Weight on a model-sown auxiliary loss (Switch-MoE load balancing);
    # only consumed when the model sows one (build_fedcore detects it).
    aux_loss_weight: float = 0.01
    # Dtype for the local-SGD scan carry (per-client params while stepping).
    # None = keep the global param dtype (f32). jnp.bfloat16 halves the
    # carry bytes the step loop reads/writes each iteration AND removes the
    # f32->bf16 cast in front of every conv/matmul (models compute bf16
    # anyway); the per-round delta is then quantized to bf16 steps. Changes
    # numerics — gate on the accuracy-parity oracle
    # (tests/test_parity_cnn.py::test_bf16_carry_parity) before shipping a
    # measured config with it.
    carry_dtype: Any = None
    # Cross-replica sharded server update (arXiv 2004.13336): the weighted
    # delta is reduce-scattered over ``dp``, the optax update runs on each
    # chip's 1/dp slice of the flattened params with the optimizer state
    # laid out the same way (O(params/dp) resident per chip instead of a
    # full replica), and fresh params are stitched back from the disjoint
    # shards. Results match the replicated update to float-reduction order
    # (bitwise for the shard-local elementwise transform itself; the
    # reduce-scatter may re-associate the cross-replica sum). Requires an
    # elementwise server optimizer (every optax built-in the algorithms
    # use qualifies) and is mutually exclusive with tensor parallelism
    # (mp > 1).
    shard_server_update: bool = False
    # What a sample's loss is. "classification": one class label a sample,
    # cross-entropy of ``[n, classes]`` logits. "next_token": the model
    # returns ``[n, L, vocab]`` logits for ``[n, L]`` tokens, the targets
    # are the tokens themselves shifted by one, a sample's loss is the mean
    # over its L - 1 positions, and evaluation reports that loss and the
    # next-token accuracy; the dataset's labels are ignored. Not an
    # engine-params knob: the task bridge sets it from the task's
    # ``task_type``.
    task: str = "classification"

    def __post_init__(self):
        # scan(unroll=0) and zero-length loops fail at trace time with
        # opaque errors — reject misconfiguration with a clear one.
        for fld in ("batch_size", "max_local_steps", "block_clients",
                    "step_unroll", "block_unroll", "eval_batch_size"):
            v = getattr(self, fld)
            if not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"FedCoreConfig.{fld} must be an int >= 1, got {v!r}"
                )
        if self.sample_mode not in ("auto", "gather", "multiplicity"):
            # Checked here so a bad value fails at submit validation, not
            # at first trace.
            raise ValueError(f"unknown sample_mode {self.sample_mode!r}")
        if self.task not in TASKS:
            raise ValueError(
                f"unknown fedcore task {self.task!r} (known: {TASKS})")

    @classmethod
    def from_dict(cls, obj: dict) -> "FedCoreConfig":
        """Engine-params JSON shape (``{"fedcore": {...}}``)::

            {"batch_size": 32, "max_local_steps": 10, "block_clients": 64,
             "step_unroll": 1, "block_unroll": 1, "sample_mode": "auto",
             "carry_dtype": "bf16", "personal_dtype": "bf16",
             "shard_server_update": false}

        Typos and wrong-typed knobs fail at submit time
        (``taskmgr/validation.py``) rather than mid-round. Dtype knobs
        accept ``"bf16"``/``"bfloat16"``/``"f32"``/``"float32"`` (or any
        floating numpy dtype string); ``null`` keeps the default f32 path.
        """
        if not isinstance(obj, dict):
            raise TypeError(
                f"fedcore config must be a JSON object, got "
                f"{type(obj).__name__}"
            )
        # ``task`` is not a knob here: the task's ``task_type`` sets it.
        known = {f.name for f in dataclasses.fields(cls)} - {"task"}
        unknown = sorted(set(obj) - known)
        if unknown:
            # A typo (cary_dtype) must fail at submit time, not silently
            # run the full-precision path.
            raise ValueError(
                f"unknown fedcore config keys: {unknown} "
                f"(known: {sorted(known)})"
            )
        kw: dict = {}
        for k in ("batch_size", "max_local_steps", "block_clients",
                  "eval_batch_size", "step_unroll", "block_unroll"):
            if k in obj and obj[k] is not None:
                kw[k] = int(obj[k])
        if obj.get("sample_mode") is not None:
            kw["sample_mode"] = str(obj["sample_mode"])
        if obj.get("aux_loss_weight") is not None:
            kw["aux_loss_weight"] = float(obj["aux_loss_weight"])
        if obj.get("shard_server_update") is not None:
            kw["shard_server_update"] = bool(obj["shard_server_update"])
        for k in ("carry_dtype", "personal_dtype"):
            if obj.get(k) is not None:
                kw[k] = parse_float_dtype(k, obj[k])
        return cls(**kw)


def parse_float_dtype(knob: str, value):
    """A validated engine-params dtype knob (``carry_dtype`` /
    ``personal_dtype``): dtype-like values pass through; strings accept the
    common bf16/f32 shorthands. Non-floating dtypes are rejected — these
    knobs select a *precision*, and an int dtype would silently corrupt the
    SGD carry."""
    aliases = {"bf16": jnp.bfloat16, "f32": jnp.float32,
               "fp32": jnp.float32, "f16": jnp.float16}
    if isinstance(value, str) and value in aliases:
        value = aliases[value]
    try:
        dt = jnp.dtype(value)
    except TypeError as e:
        raise ValueError(f"fedcore.{knob}: not a dtype: {value!r}") from e
    if not jnp.issubdtype(dt, jnp.floating):
        raise ValueError(
            f"fedcore.{knob} must be a floating dtype, got {dt.name!r}"
        )
    return dt


@dataclasses.dataclass(frozen=True)
class LookupTables:
    """The tables a model only looks up by its integer input
    (``models/lookup.py``), as ``build_fedcore`` found them by abstract
    evaluation. With them a local SGD step differentiates with respect to
    the rows the lookup returned and scatter-adds their updates into the
    carried table (``FedCore._masked_sgd``): a row the step did not read
    gets ``p - lr * 0 = p`` either way, so the dense table gradient, its
    zero fill and the whole-table add leave the step.

    ``paths``: each table's parameter path; the perturbation that marks
    its rows sits beside it under ``LOOKUP_ROWS``.
    The model's apply functions take that collection as ``rows=``.
    ``rows_total``: rows of all the tables (the runner's work counts)."""

    paths: Tuple[Tuple[str, ...], ...]
    rows_total: int

    @staticmethod
    def rows_path(table_path):
        return table_path[:-1] + (LOOKUP_ROWS,)

    def split(self, params):
        """``(every other leaf, the tables)``, both keyed by path."""
        rest = flatten_dict(params)
        return rest, {path: rest.pop(path) for path in self.paths}

    def zero_rows(self, tables, ids):
        """The zero perturbation of the rows ``ids`` looks up, a block of
        ``ids.shape + (width,)`` a table, typed like the table (its dtype;
        inside ``shard_map``, device-varying where it is: a replicated
        zero's gradient would be summed over the mesh)."""
        return unflatten_dict({
            self.rows_path(path): jax.lax.full_like(
                t, 0, shape=ids.shape + t.shape[1:])
            for path, t in tables.items()})

    def add_rows(self, tables, ids, row_updates):
        """The tables with ``row_updates`` (a tree like :meth:`zero_rows`')
        scatter-added at ``ids``; a duplicate id takes every one of its
        updates, as the dense gradient's scatter-add gives it."""
        row_updates = flatten_dict(row_updates)
        return {
            path: t.at[ids].add(
                row_updates[self.rows_path(path)].astype(t.dtype))
            for path, t in tables.items()}


def _reshard(tree, shardings):
    """Re-lay a placed pytree under new shardings via a jitted identity —
    unlike ``jax.device_put`` this also works on multi-host meshes where the
    target sharding spans non-addressable devices. Values are bitwise
    unchanged (it lowers to slices/collectives, never recomputes)."""
    return jax.jit(lambda t: t, out_shardings=shardings)(tree)


def _dp_shardable(leaf, dp: int) -> bool:
    """Whether an optimizer-state leaf carries per-coordinate state (flat,
    dp-divisible — shard it) as opposed to a replicated scalar like Adam's
    step count (keep it whole on every chip)."""
    shape = getattr(leaf, "shape", ())
    return len(shape) >= 1 and shape[0] > 0 and shape[0] % dp == 0


def _call_in_roomy_frame(fn, *args):
    """``fn(*args)`` from a Python frame of 256 KB. CPython 3.12 keeps a
    thread's frames in 16 KB chunks and maps a fresh chunk, and unmaps it
    again, every time a call crosses the end of the current one. Tracing a
    round program calls across such an end tens of thousands of times, and
    how often depends on the bytes of frames above the trace: on the text
    of every caller, the builders' and the runner's included, and on
    nothing the trace does (``PERF.md`` section 7 item 8: the same 13.6 M
    lines traced took 41,464 page faults at PR 30's parent, 56,743 with one
    more frame in the builder, 3,240 under this frame). A frame this large
    gets a chunk of its own, with room below it for the whole trace."""
    return fn(*args)


_call_in_roomy_frame.__code__ = _call_in_roomy_frame.__code__.replace(
    co_stacksize=1 << 15)


class _MeshBoundary:
    """How the clients of a resident round program lie over the mesh: the
    hooks the one body of :meth:`FedCore._build_round_step` calls where its
    two programs differ.

    ``manual`` (``mp`` = 1): a ``shard_map`` over ``dp`` whose body sees one
    device's clients and psums what leaves it. Otherwise (``mp`` > 1)
    GSPMD-auto — one ``jax.jit`` and no ``shard_map``: clients are an
    ordinary dp-sharded array axis, model tensors carry the tensor-parallel
    layout of ``param_specs`` through sharding constraints (params, grads,
    per-client deltas and the delta accumulators all pin to the SAME mp
    shards, so nothing is re-laid between train and aggregate), and GSPMD
    inserts every collective: the Megatron all-gathers/reduce-scatters
    inside the per-client forward/backward AND the cross-replica delta
    reductions. Models without specs (all-``P()`` trees) are replicated
    over ``mp`` — correct but redundant; the transformer families shard
    (parallel/tp.py)."""

    def __init__(self, core: "FedCore", manual: bool):
        self.core = core
        self.manual = manual
        # One "block" is block_clients PER dp shard on either boundary —
        # the same per-device peak-memory bound: the auto body sees every
        # client, so its block is dp times as wide.
        self.block_width = core.config.block_clients * (
            1 if manual else core.plan.dp)

    def wrap(self, body, in_specs, out_specs):
        """``body`` over the mesh: specs are the manual program's."""
        if not self.manual:
            return body
        # Manual over dp only; mp is an AUTO axis — specs here describe
        # the dp placement, while the mp sharding of model tensors rides
        # in from param_specs and GSPMD inserts the TP collectives.
        return jax.shard_map(
            body, mesh=self.core.plan.mesh, in_specs=in_specs,
            out_specs=out_specs, axis_names=frozenset({"dp"}),
        )

    def psum(self, x):
        """A per-device partial summed over ``dp``; on the auto boundary the
        sum already ranges over every client."""
        return jax.lax.psum(x, "dp") if self.manual else x

    def varying(self, tree):
        return _to_varying(tree, "dp") if self.manual else tree

    def _pin(self, tree, shardings):
        if self.manual or shardings is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            shardings)

    def pin_params(self, tree):
        """Params-shaped tree on the tensor-parallel layout."""
        return self._pin(tree, self.core._param_shardings())

    def pin_clients(self, tree):
        """Per-client params-shaped tree [B, ...]: client axis over dp,
        tensor-parallel leaves additionally over mp."""
        return self._pin(tree, None if self.manual
                         else self.core._client_sharded_like(tree))

    def pin_client_axis(self, v):
        """A per-client vector [C] over dp."""
        return self._pin(v, self.core.plan.client_sharding())

    def scatter_flat(self, leaf):
        """A summed leaf as the flat padded coordinate shards the sharded
        server update runs on: reduce-scattered over dp, or laid over
        (dp, mp) for GSPMD to reduce."""
        flat = _flat_pad_leaf(leaf, self.core._shard_pad)
        if self.manual:
            return jax.lax.psum_scatter(flat, "dp", scatter_dimension=0,
                                        tiled=True)
        return jax.lax.with_sharding_constraint(
            flat, NamedSharding(self.core.plan.mesh, P(("dp", "mp"))))

    def sharded_commit(self, params, opt_state, delta_shards):
        core = self.core
        return (core._apply_manual_sharded_update if self.manual
                else core._apply_auto_sharded_update)(
            params, opt_state, delta_shards)


class FedCore:
    """Builds and owns the jitted round/eval programs for one (model,
    algorithm, mesh) triple."""

    def __init__(
        self,
        apply_fn: Callable[[Any, jax.Array], jax.Array],
        init_params_fn: Callable[[jax.Array], Any],
        algorithm: Algorithm,
        plan: MeshPlan,
        config: FedCoreConfig = FedCoreConfig(),
        param_specs: Any = None,
        apply_aux_fn: Optional[Callable[[Any, jax.Array], Tuple[jax.Array, jax.Array]]] = None,
        pp_train: Optional[Tuple[Any, Optional[int]]] = None,
        apply_stats_fn: Optional[Callable[[Any, jax.Array], Tuple[jax.Array, jax.Array]]] = None,
        describe_stats: Optional[Callable[[np.ndarray], dict]] = None,
        vmap_clients: bool = True,
        lookup_tables: Optional[LookupTables] = None,
    ):
        """``param_specs`` — optional PartitionSpec pytree (same treedef as
        the params) sharding model tensors over the mesh ``mp`` axis
        (:func:`olearning_sim_tpu.parallel.tp.tp_param_specs`). The round
        program is manual over ``dp`` and *auto* over ``mp``, so GSPMD
        inserts the tensor-parallel collectives from these annotations.

        ``apply_aux_fn(params, x) -> (logits, aux_scalar)`` — optional
        forward that also returns a model-sown auxiliary loss (Switch-MoE
        load balancing). When given, local training minimizes
        ``ce + config.aux_loss_weight * aux`` so the router stays balanced
        in the federated path too (not just under ``ep_train_step``).

        ``apply_stats_fn(params, x) -> (logits, stats)`` — optional forward
        that also returns the int32 work counts the model sows (a routed
        expert layer's assignments, a chunked scan's tokens and chunks);
        the resident dp-manual round program sums them over the round into
        ``RoundMetrics.model_stats``, and ``describe_stats(summed) ->
        {name: number}`` names them for the runner's work counts. Mutually
        exclusive with ``apply_aux_fn``.

        ``vmap_clients=False`` — the model cannot be ``vmap``ped over
        per-client weights (``ModelSpec.vmap_clients``: a grouped matmul
        batches over a leading axis only): clients are taken one at a
        time, which the block stage (``round_stages.client_block``: the
        resident, streamed and buffered programs) does at
        ``block_clients`` 1 by squeezing the block axis.

        ``lookup_tables`` — the model's lookup-only tables
        (:class:`LookupTables`), where it marks any: ``_masked_sgd`` then
        trains them by the rows a step reads wherever that is the dense
        step's result (see there). Dropped on an ``mp`` > 1 plan, whose
        program leaves every leaf to the auto partitioner.

        ``pp_train`` — ``(model, microbatches)`` for a pipeline-parallel
        mesh plan (``plan.pp > 1``): the per-client train body is then the
        stage-pipelined program of :mod:`olearning_sim_tpu.engine.
        pp_rounds` (GPipe microbatching of the dense TextTransformer
        ``model``). Required iff ``plan.pp > 1``."""
        self.apply_fn = apply_fn
        self.apply_aux_fn = apply_aux_fn
        self.apply_stats_fn = apply_stats_fn
        self.describe_stats = describe_stats
        self.vmap_clients = vmap_clients
        self.init_params_fn = init_params_fn
        self.algorithm = algorithm
        self.plan = plan
        self.config = config
        self.param_specs = param_specs
        self._pp_train = pp_train
        # use_multiplicity's answers, by (n_local, row shape).
        self._multiplicity: dict = {}
        self.lookup_tables = lookup_tables if plan.mp == 1 else None
        # Whether every _masked_sgd traced so far trains the lookup tables
        # by rows (None: none traced yet): the runner's work counts.
        self.row_updates: Optional[bool] = None
        if plan.pp > 1 and pp_train is None:
            raise ValueError(
                "plan has pp > 1 but no pp_train=(model, microbatches) was "
                "given — the pipelined per-client body needs the dense "
                "TextTransformer instance (build_fedcore wires this)"
            )
        if not vmap_clients and (
                config.block_clients != 1 or plan.mp > 1 or plan.pp > 1
                or algorithm.personalized or algorithm.control_variates):
            raise ValueError(
                "this model takes clients one at a time: it needs "
                "fedcore.block_clients 1, no mp or pp mesh axis and a "
                "plain (not personalized, no control variates) algorithm")
        if apply_stats_fn is not None and apply_aux_fn is not None:
            raise ValueError(
                "apply_stats_fn and apply_aux_fn are mutually exclusive")
        if config.task == "next_token" and algorithm.personalized:
            raise ValueError(
                "task 'next_token' does not compose with a personalized "
                "algorithm: evaluate_personal scores one label a sample")
        if algorithm.personalized and algorithm.control_variates:
            raise ValueError(
                "personalized and control_variates are mutually exclusive "
                "(both claim the per-client state slot)"
            )
        if algorithm.control_variates and algorithm.local_lr <= 0.0:
            raise ValueError(
                "control_variates needs algorithm.local_lr > 0 (the "
                "option-II refresh divides by K * local_lr)"
            )
        # Classification flag, not a code gate: tensor parallelism is
        # ACTIVE only when the mesh has an mp axis AND at least one leaf
        # actually shards. The boundary itself keys on
        # plan.mp > 1 (mp=1 programs never see the auto boundary, so
        # inert/all-replicated specs leave them byte-identical — the
        # lowering-equality tests in tests/test_modelparallel.py and
        # tests/test_sharded_engine.py consume this flag as that
        # invariant's witness).
        self._tp_active = (
            param_specs is not None
            and plan.mp > 1
            and any(any(s is not None for s in spec) for spec in
                    jax.tree.leaves(param_specs,
                                    is_leaf=lambda x: isinstance(x, P)))
        )
        # Cross-replica sharded server update (arXiv 2004.13336): the
        # optimizer state lives as flat per-coordinate shards — over dp at
        # mp=1 (O(params/dp) per chip, updated inside the manual shard_map
        # via psum_scatter), and over BOTH (dp, mp) when the mesh has a
        # model axis (O(params/(dp*mp)) per chip; the whole mp>1 round
        # program runs in GSPMD-auto land — see _MeshBoundary —
        # so the flat (dp, mp) layout is an ordinary sharding constraint).
        # The PartitionSpec tree is derived once from the optimizer-state
        # structure so init_state, the program specs, and checkpoint
        # templates can never disagree on layout.
        self._opt_spec = None
        self._auto_shard_update = config.shard_server_update and plan.mp > 1
        self._shard_pad = plan.dp * plan.mp
        if config.shard_server_update:
            p_shapes = jax.eval_shape(init_params_fn, jax.random.key(0))
            flat_spec = P(("dp", "mp")) if self._auto_shard_update else P("dp")
            flat_t = jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(
                    (pad_to_multiple(
                        int(np.prod(p.shape, dtype=np.int64)),
                        self._shard_pad,
                    ),),
                    p.dtype,
                ),
                p_shapes,
            )
            opt_t = jax.eval_shape(algorithm.server_optimizer.init, flat_t)
            # Shardability is decided HERE, on the global template — inside
            # shard_map the same leaves appear shard-local ([D_pad/dp]),
            # where a shape test would misclassify them.
            self._opt_sharded = jax.tree.map(
                lambda l: _dp_shardable(l, self._shard_pad), opt_t
            )
            self._opt_spec = jax.tree.map(
                lambda sharded: flat_spec if sharded else P(),
                self._opt_sharded,
            )
        self._round_step = self._build_round_step()
        # Program variants keyed by (with_deadline, with_attack,
        # defense_structure): built on first use so tasks that never set a
        # deadline / attack / defense pay no extra trace/compile. The
        # all-off path above stays byte-identical to a build without those
        # subsystems. Scalar knobs (per-round deadline, attack scales,
        # clip norm, trim fraction) are DATA within a variant — changing
        # them across rounds never recompiles; ``trace_counts`` (bumped at
        # trace time, never at execution) is the regression probe tests
        # assert that on.
        self._round_step_variants: dict = {(False, False, None): self._round_step}
        # Block-streamed round programs (stream_round): keyed by
        # (rows-per-device, with_deadline, with_attack, defense structure)
        # -> (partial_fn, finalize_fn, zero_acc_fn). Built on first use;
        # resident-path programs above are untouched by streaming.
        self._stream_variants: dict = {}
        self.trace_counts: dict = {}
        self._evaluate = self._build_evaluate()
        self._evaluate_personal = None  # built on first use

    def _param_shardings(self):
        if self.param_specs is None:
            return None
        mesh = self.plan.mesh
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), self.param_specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    # ------------------------------------------------------------------ init
    def init_state(self, rng: jax.Array) -> ServerState:
        # jit with out_shardings (not device_put) so placement also works on
        # multi-host meshes, where the sharding spans non-addressable devices.
        rep = self.plan.replicated()
        shardings = self._param_shardings()
        if self.config.shard_server_update:
            # Params stay in the normal tree layout (eval/export/checkpoint
            # see it; tensor-parallel leaves are placed per param_specs);
            # the optimizer state is initialized over the FLAT padded
            # coordinate view and placed sharded over dp (and mp on a
            # model-parallel mesh) — zeros either way, so the values are
            # bitwise those of the replicated init.
            mesh = self.plan.mesh
            opt_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, s), self._opt_spec,
                is_leaf=lambda x: isinstance(x, P),
            )
            pk, bk = jax.jit(jax.random.split, out_shardings=rep)(rng)
            params = jax.jit(self.init_params_fn, out_shardings=rep)(pk)
            if shardings is not None:
                params = _reshard(params, shardings)

            def make_opt(params):
                flat = jax.tree.map(
                    lambda p: _flat_pad_leaf(p, self._shard_pad), params
                )
                return self.algorithm.server_optimizer.init(flat)

            opt_state = jax.jit(make_opt, out_shardings=opt_sh)(params)
            return ServerState(
                params=params,
                opt_state=opt_state,
                round_idx=jax.jit(lambda: jnp.int32(0), out_shardings=rep)(),
                base_key=bk,
            )
        if shardings is None:

            def make(rng):
                pk, bk = jax.random.split(rng)
                params = self.init_params_fn(pk)
                opt_state = self.algorithm.server_optimizer.init(params)
                return ServerState(
                    params=params,
                    opt_state=opt_state,
                    round_idx=jnp.int32(0),
                    base_key=bk,
                )

            return jax.jit(make, out_shardings=rep)(rng)
        # Tensor-parallel: params initialized REPLICATED and then resharded
        # per spec in a separate program (init directly under mp-sharded
        # out_shardings partitions threefry and draws DIFFERENT values for
        # row-sharded leaves on 0.4.x — the mp=2 model would not equal the
        # mp=1 model at round 0). The optimizer state is initialized in a
        # follow-up jit with no out constraint, so GSPMD shards
        # moments/momenta exactly like the params they track.
        pk, bk = jax.jit(jax.random.split, out_shardings=rep)(rng)
        params = _reshard(
            jax.jit(self.init_params_fn, out_shardings=rep)(pk), shardings
        )
        opt_state = jax.jit(self.algorithm.server_optimizer.init)(params)
        return ServerState(
            params=params,
            opt_state=opt_state,
            round_idx=jax.jit(lambda: jnp.int32(0), out_shardings=rep)(),
            base_key=bk,
        )

    # ------------------------------------------------------- local training
    def use_multiplicity(self, n_local: int, row_shape: Tuple[int, ...],
                         row_dtype: Any) -> bool:
        """Whether local training realizes its minibatch as multiplicity
        weights over a client's ``n_local`` rows (else gathers
        ``batch_size`` of them): ``config.sample_mode`` and, under
        ``"auto"``, :func:`auto_uses_multiplicity` on what this model
        costs to train on one row of that shape and dtype. Decided at the
        first ask for ``(n_local, row_shape)`` and remembered, so
        ``_masked_sgd``, which asks while it traces, and the runner's work
        counts get one answer even where the host holds the rows in another
        float width than the program is given (the streamed path)."""
        mode = self.config.sample_mode
        if mode != "auto":
            return mode == "multiplicity"
        key = (int(n_local), tuple(row_shape))
        if key not in self._multiplicity:
            self._multiplicity[key] = auto_uses_multiplicity(
                n_local, self.config.batch_size,
                math.prod(row_shape) * np.dtype(row_dtype).itemsize,
                lambda: self._row_train_flops(row_shape, row_dtype),
            )
        return self._multiplicity[key]

    def _row_train_flops(self, row_shape: Tuple[int, ...],
                         row_dtype: Any) -> float:
        """FLOPs to train this model on one row: the matmul and convolution
        work of its one-row forward pass, times 3 for the forward,
        input-gradient and weight-gradient passes. Counted from the jaxpr,
        so the chip and a CPU test price a model alike
        (``Lowered.cost_analysis`` has no TPU implementation)."""
        p_shapes = jax.eval_shape(self.init_params_fn, jax.random.key(0))
        row = jax.ShapeDtypeStruct((1,) + tuple(row_shape), row_dtype)
        return 3.0 * _matmul_flops(
            jax.make_jaxpr(self.apply_fn)(p_shapes, row).jaxpr)

    def _masked_sgd(self, params0, opt_state0, x, y, num_samples, steps_eff,
                    key, persample_loss_fn, penalty_fn=None,
                    grad_transform=None, varying_init=False,
                    with_stats=False):
        """Masked local-SGD loop shared by the global and Ditto branches:
        step ``i`` samples a minibatch from the valid prefix, applies the
        local optimizer, and is a no-op when ``i >= steps_eff``. Returns
        (final_params, mean_loss) with NaN loss for zero-step clients ("no
        work performed" must not read as success downstream — finiteness is
        the success signal replacing subprocess exit codes).

        ``persample_loss_fn(params, x, y) -> ([n] losses, aux_scalar)``
        unreduced losses plus an already-weighted auxiliary loss (0.0 for
        models without one);
        ``penalty_fn(params) -> scalar`` optional regularizer (FedProx).
        The minibatch is realized either by gathering rows or as
        multiplicity weights over the full local set, as
        :meth:`use_multiplicity` decides for this ``x``; both produce
        mathematically identical gradients for the same index draw (up to
        float reduction order). An auxiliary loss is the exception: it sees
        the rows the model is run on, the whole local set or the minibatch.

        ``with_stats``: ``persample_loss_fn`` returns a third value, the
        model's int32 work counts of that forward pass, and the return is
        ``(final_params, mean_loss, counts summed over the active steps)``.
        """
        cfg = self.config
        alg = self.algorithm
        n = jnp.maximum(num_samples, 1)
        n_local = x.shape[0]
        use_mult = self.use_multiplicity(n_local, x.shape[1:], x.dtype)
        # SGD without momentum has an empty optimizer state; then masking is
        # cheaper as update-scaling (one fused multiply) than as a
        # double-buffered tree_where over params AND state.
        stateless_opt = not jax.tree.leaves(opt_state0)

        # A model's lookup-only tables are trained by the rows a step reads
        # wherever that is what the dense step computes: a stateless
        # elementwise optimizer (a row's update is a function of its
        # gradient alone), nothing dense added to the loss or the gradients
        # (FedProx's pull, SCAFFOLD's and Ditto's corrections move rows the
        # step did not read), and FedCore's own loss, which hands the rows'
        # perturbation to the model.
        lookup = self.lookup_tables
        by_rows = (
            lookup is not None and stateless_opt and penalty_fn is None
            and grad_transform is None
            and persample_loss_fn in (self._persample,
                                      self._persample_counted)
        )
        self.row_updates = by_rows and self.row_updates is not False

        def step(carry, i):
            params, opt_state = carry
            k = jax.random.fold_in(key, i)
            idx = jax.random.randint(k, (cfg.batch_size,), 0, n)
            if use_mult:
                sw = (
                    jnp.zeros((n_local,), jnp.float32).at[idx].add(1.0)
                    / cfg.batch_size
                )

            def loss_fn(p, **rows):
                if use_mult:
                    losses, aux, *stats = persample_loss_fn(p, x, y, **rows)
                    loss = (sw * losses).sum() + aux
                else:
                    xb = jnp.take(x, idx, axis=0)
                    yb = jnp.take(y, idx, axis=0)
                    losses, aux, *stats = persample_loss_fn(p, xb, yb, **rows)
                    loss = losses.mean() + aux
                loss = loss + (penalty_fn(p) if penalty_fn else 0.0)
                return (loss, stats[0]) if with_stats else loss

            if by_rows:
                # The ids are whichever rows the model is run on.
                ids = x if use_mult else jnp.take(x, idx, axis=0)
                rest, tables = lookup.split(params)
                loss, grads = jax.value_and_grad(
                    lambda r, rows: loss_fn(
                        unflatten_dict({**r, **tables}), rows=rows),
                    argnums=(0, 1), has_aux=with_stats,
                )(rest, lookup.zero_rows(tables, ids))
            else:
                loss, grads = jax.value_and_grad(
                    loss_fn, has_aux=with_stats)(params)
            if with_stats:
                loss, stats = loss
            if grad_transform is not None:
                grads = grad_transform(grads, params)
                # Transforms mixing in f32 state (SCAFFOLD controls, Ditto
                # pull) promote grads to f32; a bf16 carry must get bf16
                # updates back or the scan carry changes dtype mid-loop.
                grads = jax.tree.map(
                    lambda g, p: g.astype(p.dtype), grads, params
                )
            updates, new_opt = alg.local_optimizer.update(
                grads, opt_state, None if by_rows else params)
            active = i < steps_eff
            if stateless_opt:
                # where, not multiply-by-gate: 0 * non-finite = NaN would let
                # an inactive step corrupt params that must stay frozen
                # (e.g. a churned-out Ditto client whose data still produces
                # overflowing grads under the shared vmap).
                updates = jax.tree.map(
                    lambda u: jnp.where(active, u, jnp.zeros_like(u)), updates
                )
            if by_rows:
                new_params = unflatten_dict({
                    **optax.apply_updates(rest, updates[0]),
                    **lookup.add_rows(tables, ids, updates[1]),
                })
            else:
                new_params = optax.apply_updates(params, updates)
            if stateless_opt:
                carry = (new_params, opt_state)
            else:
                carry = _tree_where(
                    active, (new_params, new_opt), (params, opt_state)
                )
            if with_stats:
                return carry, (jnp.where(active, loss, 0.0),
                               jnp.where(active, stats, 0))
            return carry, jnp.where(active, loss, 0.0)

        orig_dtypes = jax.tree.map(lambda p: p.dtype, params0)
        if cfg.carry_dtype is not None:
            cast = lambda t: jax.tree.map(
                lambda p: p.astype(cfg.carry_dtype)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, t
            )
            params0, opt_state0 = cast(params0), cast(opt_state0)
        init = (params0, opt_state0)
        if varying_init:
            # Replicated initial carry accumulating shard-local data inside
            # shard_map must be typed device-varying over dp.
            init = _to_varying(init, "dp")
        (params, _), losses = jax.lax.scan(
            step, init, jnp.arange(cfg.max_local_steps),
            unroll=min(cfg.step_unroll, cfg.max_local_steps),
        )
        if with_stats:
            losses, stats = losses
        if cfg.carry_dtype is not None:
            params = jax.tree.map(
                lambda p, d: p.astype(d), params, orig_dtypes
            )
        mean_loss = jnp.where(
            steps_eff > 0,
            losses.sum() / jnp.maximum(steps_eff, 1).astype(jnp.float32),
            jnp.float32(jnp.nan),
        )
        if with_stats:
            return params, mean_loss, stats.sum(0)
        return params, mean_loss

    def _sample_scores(self, logits, xb, yb):
        """([n] losses, [n] accuracies) of a batch under ``config.task``:
        cross-entropy and a hit against the sample's label, or — next-token
        — the mean over a sequence's L - 1 positions of the cross-entropy
        and of the hits against the following token (labels ignored)."""
        if self.config.task == "next_token":
            with jax.named_scope("lm_loss"):
                logits, targets = logits[:, :-1], xb[:, 1:]
                return (
                    optax.softmax_cross_entropy_with_integer_labels(
                        logits, targets).mean(-1),
                    (logits.argmax(-1) == targets).mean(-1),
                )
        return (optax.softmax_cross_entropy_with_integer_labels(logits, yb),
                logits.argmax(-1) == yb)

    def _persample(self, p, xb, yb, **rows):
        """Shared per-sample loss + (weighted) model aux loss. In
        multiplicity mode the aux term sees the client's full local set
        rather than the sampled minibatch — both are unbiased regularizer
        estimates, and which one a ``sample_mode: "auto"`` build trains
        with follows :meth:`use_multiplicity`. ``rows=`` (the by-rows local
        step alone): the perturbation of the model's looked-up rows."""
        if self.apply_aux_fn is None:
            logits = self.apply_fn(p, xb, **rows)
            aux = jnp.float32(0.0)
        else:
            logits, aux = self.apply_aux_fn(p, xb, **rows)
            aux = self.config.aux_loss_weight * aux.astype(jnp.float32)
        return self._sample_scores(logits, xb, yb)[0], aux

    def _persample_counted(self, p, xb, yb, **rows):
        """:meth:`_persample` through ``apply_stats_fn``: also the model's
        work counts of this forward pass."""
        logits, stats = self.apply_stats_fn(p, xb, **rows)
        return (self._sample_scores(logits, xb, yb)[0], jnp.float32(0.0),
                stats)

    def _local_train(self, global_params, x, y, num_samples, num_steps, uid,
                     base_key, round_idx, server_c=None, ci=None,
                     varying=True, with_stats=False):
        """One client's local training: masked lax.scan over SGD steps.

        Per-client RNG stream: fold_in(fold_in(base_key, uid), round) — stable
        under any resharding of clients to devices, which is what makes the
        accuracy-parity claim reproducible (SURVEY.md section 7 hard parts).

        With SCAFFOLD control variates (``server_c``/``ci`` given): every
        step's gradient is corrected by ``+ c - c_i``, and afterwards c_i
        refreshes by option II of the paper: c_i' = c_i - c +
        (x0 - x_K)/(K * lr) = c_i - c - delta/(K * lr). Returns an extra
        ``dci = c_i' - c_i`` (zero when the client ran no steps).

        ``with_stats`` (needs ``apply_stats_fn``; not with control
        variates): returns ``(delta, mean_loss, work counts)``.
        """
        alg = self.algorithm
        key = jax.random.fold_in(jax.random.fold_in(base_key, uid), round_idx)
        # The scan length is static; clamp so a larger requested step count is
        # an explicit cap, and metrics divide by the steps actually run.
        steps_eff = jnp.minimum(num_steps, self.config.max_local_steps)
        persample = self._persample_counted if with_stats else self._persample

        penalty = None
        if alg.prox_mu:
            penalty = lambda p: 0.5 * alg.prox_mu * _tree_l2_sq(p, global_params)

        grad_transform = None
        if ci is not None:
            def grad_transform(grads, _params):
                return jax.tree.map(
                    lambda g, c, cc: g + c - cc, grads, server_c, ci
                )

        params, mean_loss, *stats = self._masked_sgd(
            global_params, alg.local_optimizer.init(global_params),
            x, y, num_samples, steps_eff, key, persample, penalty_fn=penalty,
            grad_transform=grad_transform, varying_init=varying,
            with_stats=with_stats,
        )
        delta = jax.tree.map(jnp.subtract, params, global_params)
        if ci is None:
            return (delta, mean_loss, *stats)
        k_lr = jnp.maximum(steps_eff, 1).astype(jnp.float32) * alg.local_lr
        ran = steps_eff > 0
        dci = jax.tree.map(
            lambda c, d: jnp.where(ran, -c - d / k_lr, jnp.zeros_like(c)),
            server_c, delta,
        )
        return delta, mean_loss, dci

    def _personal_train(self, vparams, global_params, x, y, num_samples,
                        num_steps, uid, active, base_key, round_idx):
        """One client's Ditto personal branch (Ditto: Li et al. 2021):
        v_k <- v_k - eta * (grad F_k(v_k) + lambda * (v_k - w)).

        Runs in the same compiled program as the global branch; ``active``
        (participation) gates every update so churned-out clients keep their
        personal params frozen. The minibatch RNG stream is salted away from
        the global branch's so the two branches see decorrelated batches.
        """
        alg = self.algorithm
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.fold_in(base_key, uid), round_idx), 0x0D1770
        )
        v0 = jax.tree.map(lambda v, p: v.astype(p.dtype), vparams, global_params)
        steps_eff = jnp.where(
            active, jnp.minimum(num_steps, self.config.max_local_steps), 0
        )
        persample = self._persample

        def ditto_pull(grads, v):
            return jax.tree.map(
                lambda g, vv, ww: g + alg.ditto_lambda * (vv - ww),
                grads, v, global_params,
            )

        # The carry derives from the sharded per-client params, so it is
        # already device-varying — no pcast (varying_init=False).
        v, mean_loss = self._masked_sgd(
            v0, alg.local_optimizer.init(v0), x, y, num_samples, steps_eff,
            key, persample, grad_transform=ditto_pull,
        )
        return jax.tree.map(lambda t, orig: t.astype(orig.dtype), v, vparams), mean_loss

    # ----------------------------------------------------------- round step
    def _build_round_step(self, with_deadline: bool = False,
                          with_attack: bool = False, defense=None):
        """``with_deadline=True`` builds the deadline-masked variant: two
        extra inputs — ``completion_time`` [C] (simulated seconds, sharded
        like the clients) and a replicated ``deadline`` scalar — turn
        ``completion_time > deadline`` into zero aggregation weight with
        pure ``lax`` masking (no host round-trip), and the late
        participants are counted as ``metrics.stragglers``.

        ``with_attack=True`` adds a per-client ``attack_scale`` [C] input
        multiplied into each client's delta after local training — the
        in-program half of the ``runner.attack_clients`` injection point
        (sign_flip = -1, scale = factor, benign = 1; data, never a
        recompile).

        ``defense`` (a :class:`~olearning_sim_tpu.engine.defense.
        DefenseConfig`) adds two replicated data inputs — ``clip_norm`` and
        ``trim_fraction`` — and composes per-client L2 delta clipping,
        optional coordinate-wise trimmed-mean/median aggregation, and
        Krum-style per-client anomaly scores (``metrics.anomaly_score``)
        into the same compiled program (pure ``lax``; the robust
        aggregators/scores run coordinate-SHARDED over dp via one
        all_to_all — O(clients x params / dp) peak per device, see
        engine/defense.py).

        One body serves both boundaries (:class:`_MeshBoundary`); on the
        GSPMD-auto one the supported variants are plain, deadline, attack
        and clip-only defense — gathering defenses (robust aggregators /
        anomaly scoring) are rejected at :meth:`_prepare_round_args`, their
        coordinate-sharded layout is built on manual dp collectives
        (docs/performance.md has the composition matrix) — and under
        ``shard_server_update`` the optimizer runs on flat coordinates
        sharded over BOTH axes (:meth:`_apply_auto_sharded_update`).

        The default variant is byte-identical to the pre-deadline,
        pre-defense program."""
        if self.plan.pp > 1:
            # Pipeline-parallel mesh: the per-client body streams
            # microbatches through the pp stages (engine/pp_rounds.py).
            # Only the plain program exists — every other variant is
            # rejected at _prepare_round_args / submit validation.
            if with_deadline or with_attack or defense is not None:
                raise ValueError(
                    "pipeline-parallel (pp>1) rounds support the plain "
                    "program only (no deadline/attack/defense variants); "
                    "docs/performance.md has the composition matrix"
                )
            return pp_rounds.build_pp_round_step(self, *self._pp_train)
        # mp > 1 takes the GSPMD-auto boundary. The manual one with mp left
        # to the auto partitioner was written off for a check-failure of an
        # older XLA's SPMD partitioner on every lax.scan. What is known now
        # (PR 30's probe: jax 0.9.0, the CPU backend's 8 virtual devices,
        # this line skipped so that mp = 2 went through the manual
        # boundary): 14 of the 16 tests of tests/test_tp.py and
        # tests/test_modelparallel.py pass, test_mp2_matches_mp1 and the
        # numpy-oracle aggregation among them; the two that fail are the
        # shard_server_update x mp tests, whose optimizer state is laid out
        # P(("dp", "mp")) and cannot enter a shard_map that is manual over
        # dp alone. The chip has not been asked (ROADMAP.md Design 2).
        bd = _MeshBoundary(self, manual=self.plan.mp == 1)
        cfg = self.config
        alg = self.algorithm
        dpn = self.plan.dp
        shard_update = cfg.shard_server_update
        personalized = alg.personalized
        controlled = alg.control_variates
        defense_gather = defense is not None and defense.gathers_deltas
        defense_score = defense is not None and defense.score_enabled
        aggregator = defense.aggregator if defense is not None else "mean"
        robust_agg = aggregator in ("trimmed_mean", "median")
        if defense_gather and not bd.manual:
            raise ValueError(
                "robust aggregators / anomaly scoring are not supported on "
                "a model-parallel mesh (mp > 1); use clip_norm only"
            )
        trace_key = (with_deadline, with_attack,
                     defense.structure_key if defense is not None else None)
        # The model's work counts ride the block scan as one more
        # accumulator, last in the carry (the manual boundary's program
        # alone: ROADMAP.md Design 1).
        counted = (self.apply_stats_fn is not None and not controlled
                   and bd.manual)
        # varying typing is a manual-shard_map concern; the auto program
        # must not ask for it (pvary outside a bound axis is an error).
        train_fn = functools.partial(self._local_train, varying=bd.manual,
                                     with_stats=counted)

        def body(params, opt_state, round_idx, base_key,
                 x, y, num_samples, num_steps, uid, weight, vparams,
                 server_c, true_n, *extras):
            # Host-side effect that runs at TRACE time only: the
            # no-recompile regression probe (tests assert this count stays
            # flat while per-round data knobs change).
            self.trace_counts[trace_key] = \
                self.trace_counts.get(trace_key, 0) + 1
            extras = list(extras)
            stragglers = jnp.float32(0.0)
            attack_scale = clip_norm = trim_fraction = None
            if with_deadline:
                completion_time, deadline = extras[0], extras[1]
                del extras[:2]
                # A participating client whose simulated completion misses
                # the round deadline contributes nothing. where(late, 0, w)
                # selects the untouched weight bitwise for on-time clients,
                # so a non-binding deadline (inf) leaves aggregation
                # bit-for-bit unchanged.
                late = completion_time > deadline
                stragglers = bd.psum(
                    jnp.logical_and(weight > 0, late)
                    .sum().astype(jnp.float32)
                )
                weight = jnp.where(late, jnp.zeros_like(weight), weight)
            if with_attack:
                attack_scale = extras.pop(0)
            if defense is not None:
                clip_norm, trim_fraction = extras[0], extras[1]
                del extras[:2]
            params = bd.pin_params(params)
            # The clients this body sees: one device's (manual) or all.
            c_local = x.shape[0]
            if c_local % bd.block_width != 0:
                raise ValueError(
                    f"client count {c_local} must be a multiple of "
                    f"{bd.block_width} (block_clients="
                    f"{cfg.block_clients} a dp shard); pad the dataset with "
                    f"ClientDataset.pad_for(plan, block=config.block_clients)"
                )
            nb = c_local // bd.block_width

            def blocked(a):
                return a.reshape((nb, bd.block_width) + a.shape[1:])

            xs = (blocked(x), blocked(y), blocked(num_samples),
                  blocked(num_steps), blocked(uid), blocked(weight),
                  jax.tree.map(blocked, vparams)
                  if (personalized or controlled) else None,
                  blocked(attack_scale) if with_attack else None)

            # Delta accumulators live on the same mp shards as the params,
            # so the weighted-sum scan never re-lays model tensors.
            zero_delta = bd.pin_params(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            ))
            init = (zero_delta, jnp.float32(0.0), jnp.float32(0.0),
                    jnp.float32(0.0), jnp.float32(0.0),
                    zero_delta if controlled else jnp.float32(0.0))
            if defense is not None:
                # Extra accumulator: participants whose delta was clipped.
                init = init + (jnp.float32(0.0),)
            if counted:
                stats_shape = jax.eval_shape(
                    self.apply_stats_fn, params, x[0, :1])[1]
                init = init + (jnp.zeros(stats_shape.shape, jnp.int32),)
            # The carry accumulates device-varying values (per-shard client
            # sums), so its initial value must be typed as varying over dp.
            init = bd.varying(init)

            def block_step(carry, inp):
                if counted:
                    *carry, sum_stats = carry
                if defense is not None:
                    (sum_delta, sum_w, sum_loss, count, sum_ploss, sum_dc,
                     n_clip) = carry
                else:
                    sum_delta, sum_w, sum_loss, count, sum_ploss, sum_dc = carry
                    n_clip = None
                bx, by, bns, bst, buid, bw, bvp, batk = inp
                blk = client_block(
                    train_fn,
                    (None, 0, 0, 0, 0, 0, None, None)
                    + ((None, 0) if controlled else ()),
                    (params, bx, by, bns, bst, buid, base_key, round_idx)
                    + ((server_c, bvp) if controlled else ()),
                    bw, vmap_clients=self.vmap_clients,
                    pin_clients=bd.pin_clients, attack_scale=batk,
                    clip_norm=clip_norm,
                )
                losses, bw_eff, gate = blk.losses, blk.bw_eff, blk.gate
                if defense is not None:
                    n_clip = n_clip + blk.clipped
                sum_delta = bd.pin_params(blk.weighted_sum(sum_delta))
                # The gathering aggregators/scores need every client's
                # (gated, clipped) delta — emitted from the scan and
                # all-gathered after it.
                defense_ys = (blk.d32, bw_eff) if defense_gather else None
                sum_w, sum_loss, count = blk.tally(sum_w, sum_loss, count)
                if controlled:
                    # c_i advances only for participating clients whose
                    # update survived the finiteness gate; the server
                    # control absorbs the weighted mean correction below.
                    active = bw_eff > 0
                    dcis, = blk.extra

                    def gate_active(d):
                        return jnp.where(
                            active.reshape((-1,) + (1,) * (d.ndim - 1)), d, 0.0
                        )

                    new_bvp = jax.tree.map(
                        lambda v, d: v + gate_active(d), bvp, dcis
                    )
                    sum_dc = jax.tree.map(
                        lambda s, d: s + jnp.tensordot(bw_eff, gate(d), axes=(0, 0)),
                        sum_dc, dcis,
                    )
                    ys = (losses, new_bvp)
                elif personalized:
                    with jax.named_scope("client_train"):
                        new_vp, plosses = jax.vmap(
                            self._personal_train,
                            in_axes=(0, None, 0, 0, 0, 0, 0, 0, None, None),
                        )(bvp, params, bx, by, bns, bst, buid, bw > 0,
                          base_key, round_idx)
                    # Keep a client's previous personal params when its
                    # personal branch diverged — a non-finite v_k would
                    # otherwise stay poisoned forever. For participating
                    # finite clients (and frozen non-participants) the new
                    # value is selected, so healthy rounds are bitwise
                    # unchanged.
                    okp = jnp.isfinite(plosses)
                    for d in jax.tree.leaves(new_vp):
                        okp = jnp.logical_and(
                            okp,
                            jnp.isfinite(d.reshape(d.shape[0], -1)).all(axis=1),
                        )
                    keep = jnp.logical_or(okp, jnp.logical_not(bw > 0))
                    new_vp = jax.tree.map(
                        lambda nv, ov: jnp.where(
                            keep.reshape((-1,) + (1,) * (nv.ndim - 1)), nv, ov
                        ),
                        new_vp, bvp,
                    )
                    sum_ploss = sum_ploss + jnp.where(
                        jnp.logical_and(bw > 0, okp), bw * plosses, 0.0
                    ).sum()
                    ys = (losses, new_vp)
                else:
                    ys = (losses, None)
                new_carry = (sum_delta, sum_w, sum_loss, count, sum_ploss,
                             sum_dc)
                if defense is not None:
                    new_carry = new_carry + (n_clip,)
                if counted:
                    new_carry = new_carry + (sum_stats + blk.extra[0].sum(0),)
                return new_carry, ys + (defense_ys,)

            carry, (block_losses, new_vparams, defense_out) = jax.lax.scan(
                block_step, init, xs, unroll=min(cfg.block_unroll, nb)
            )
            model_stats = jnp.float32(0.0)
            if counted:
                *carry, sum_stats = carry
                model_stats = bd.psum(sum_stats)
            if defense is not None:
                (sum_delta, sum_w, sum_loss, count, sum_ploss, sum_dc,
                 n_clip) = carry
            else:
                sum_delta, sum_w, sum_loss, count, sum_ploss, sum_dc = carry
                n_clip = jnp.float32(0.0)
            client_loss = bd.pin_client_axis(block_losses.reshape((c_local,)))
            if personalized or controlled:
                new_vparams = bd.pin_clients(jax.tree.map(
                    lambda a: a.reshape((c_local,) + a.shape[2:]), new_vparams
                ))

            with jax.named_scope("aggregate"):
                # Cross-device FedAvg: the Pulsar gradient transport of the
                # reference becomes one collective over the dp axis of the ICI
                # mesh — a full psum of the weighted delta on the replicated
                # path, or a reduce-scatter (each chip keeps the cross-replica
                # sum for its 1/dp of the coordinates) under the sharded
                # server update. On the auto boundary the sums already range
                # over every client and the reduction is a GSPMD-inserted
                # collective (``bd.psum`` is the identity).
                sum_w = bd.psum(sum_w)
                sum_loss = bd.psum(sum_loss)
                count = bd.psum(count)
                sum_ploss = bd.psum(sum_ploss)
                if defense is not None:
                    n_clip = bd.psum(n_clip)

                denom = jnp.maximum(sum_w, 1e-8)
                mean_delta = delta_shards = None
                if not (defense_gather and robust_agg):
                    # Weighted-mean aggregation (a robust aggregator replaces
                    # it entirely below, so its collective is skipped then).
                    if shard_update:
                        delta_shards = jax.tree.map(
                            lambda s: bd.scatter_flat(s) / denom, sum_delta
                        )
                    else:
                        sum_delta = bd.psum(sum_delta)
                        mean_delta = jax.tree.map(
                            lambda s: s / denom, sum_delta
                        )
                anomaly_score = jnp.float32(0.0)
                if defense_gather:
                    # Sharded robust aggregation: one all_to_all re-lays the
                    # clipped per-client deltas so THIS device holds every
                    # client for 1/dp of the coordinates — peak
                    # O(clients x params / dp) instead of the full
                    # O(clients x params) matrix an all_gather would
                    # replicate. Each coordinate's client column is intact, so
                    # the per-coordinate sort/window statistics are bit-for-bit
                    # those of the gathered formulation.
                    d_pc, w_pc = defense_out
                    # The participant mask is the only thing replicated in
                    # full — O(clients) bytes.
                    w_all = jax.lax.all_gather(
                        w_pc.reshape((c_local,)), "dp", tiled=True
                    )
                    participants = w_all > 0
                    shards = jax.tree.map(
                        lambda a: defense_mod.shard_client_deltas(
                            a.reshape((c_local,) + a.shape[2:]), "dp", dpn
                        ),
                        d_pc,
                    )
                    center_shards = None
                    if robust_agg:
                        agg_shards = jax.tree.map(
                            lambda s: defense_mod.robust_leaf_aggregate(
                                s, participants, aggregator, trim_fraction
                            ),
                            shards,
                        )
                        if aggregator == "median":
                            center_shards = agg_shards
                        if shard_update:
                            # Same coordinate partition as the sharded server
                            # update (_flat_pad_leaf pads identically), so the
                            # robust aggregate feeds the sharded optimizer
                            # directly — no reconstruction collective at all.
                            delta_shards = agg_shards
                        else:
                            mean_delta = jax.tree.map(
                                lambda s, p: defense_mod.place_coordinate_shard(
                                    s, "dp", dpn, p.shape
                                ),
                                agg_shards, params,
                            )
                    if defense_score:
                        if center_shards is None:
                            center_shards = jax.tree.map(
                                lambda s: defense_mod.robust_leaf_aggregate(
                                    s, participants, "median", trim_fraction
                                ),
                                shards,
                            )
                        # Krum-style distances from per-shard partial squared
                        # distances combined with one psum; sqrt after the sum
                        # recovers the gathered formulation's scores.
                        partial = functools.reduce(
                            jnp.add,
                            [defense_mod.partial_distance_sq(s, c)
                             for s, c in zip(jax.tree.leaves(shards),
                                             jax.tree.leaves(center_shards))],
                        )
                        scores = jnp.where(
                            participants,
                            jnp.sqrt(jax.lax.psum(partial, "dp")),
                            0.0,
                        )
                        # Each shard exits with its own clients' scores (same
                        # layout as client_loss).
                        anomaly_score = jax.lax.dynamic_slice(
                            scores,
                            (jax.lax.axis_index("dp") * c_local,),
                            (c_local,),
                        )
            with jax.named_scope("server_update"):
                if shard_update:
                    new_params, new_opt_state = bd.sharded_commit(
                        params, opt_state, delta_shards)
                else:
                    new_params, new_opt_state = server_commit(
                        alg.server_optimizer, params, opt_state, mean_delta)
                    new_params = bd.pin_params(new_params)
            new_server_c = None
            if controlled:
                # c <- c + (|S|/N) * weighted-mean dc_i (SCAFFOLD eq. 5 with
                # aggregation weights). N is the TRUE unpadded population
                # (ds.population, threaded in as a scalar): it survives both
                # dp/block_clients padding AND cohort take() subsetting, so
                # partial participation keeps frac = |S|/N instead of
                # collapsing to ~1 (ADVICE r3).
                sum_dc = bd.psum(sum_dc)
                frac = count / jnp.maximum(true_n, 1.0)
                new_server_c = jax.tree.map(
                    lambda c, s: c + frac * (s / denom), server_c, sum_dc
                )
            metrics = RoundMetrics(
                mean_loss=sum_loss / denom,
                weight_sum=sum_w,
                clients_trained=count,
                client_loss=client_loss,
                personal_loss=sum_ploss / denom,
                stragglers=stragglers,
                anomaly_score=anomaly_score,
                clipped=n_clip,
                model_stats=model_stats,
            )
            return (new_params, new_opt_state, round_idx + 1, metrics,
                    new_vparams, new_server_c)

        rep = P()
        cl = P("dp")
        metrics_specs = RoundMetrics(
            mean_loss=rep, weight_sum=rep, clients_trained=rep, client_loss=cl,
            personal_loss=rep, stragglers=rep,
            anomaly_score=cl if defense_score else rep, clipped=rep,
            model_stats=rep,
        )
        # completion_time is sharded like the clients; deadline replicated.
        pace_specs = (cl, rep) if with_deadline else ()
        # attack_scale sharded like the clients; defense scalars replicated.
        attack_specs = (cl,) if with_attack else ()
        defense_specs = (rep, rep) if defense is not None else ()
        extra_specs = pace_specs + attack_specs + defense_specs

        # Optimizer state is replicated on the classic path; under the
        # sharded server update its per-coordinate leaves ride in/out as
        # flat dp shards (scalar leaves stay replicated) per the spec tree
        # derived at construction.
        opt_spec = self._opt_spec if shard_update else rep

        def make_fn(vp_tree, sc_tree):
            vp_spec = jax.tree.map(lambda _: cl, vp_tree)
            sc_spec = jax.tree.map(lambda _: rep, sc_tree)
            return bd.wrap(
                body,
                in_specs=(rep, opt_spec, rep, rep, cl, cl, cl, cl, cl,
                          cl, vp_spec, sc_spec, rep) + extra_specs,
                out_specs=(rep, opt_spec, rep, metrics_specs, vp_spec,
                           sc_spec),
            )

        return jit_round_step(make_fn, personalized, controlled)

    def _apply_manual_sharded_update(self, params, opt_state, delta_shards):
        """Cross-replica sharded weight update (arXiv 2004.13336) inside the
        manual-dp ``shard_map``: update THIS chip's 1/dp coordinate slice
        with the optimizer state that lives sharded the same way, then
        stitch the fresh params from the disjoint shards (exact — each
        coordinate has exactly one contributor). ``delta_shards``: this
        chip's slice of the mean delta, flat."""
        dpn = self.plan.dp

        def my_shard(p):
            flat = _flat_pad_leaf(p, dpn)
            s = flat.shape[0] // dpn
            return jax.lax.dynamic_slice(
                flat, (jax.lax.axis_index("dp") * s,), (s,)
            )

        shard_params = jax.tree.map(my_shard, params)
        pseudo_grad = jax.tree.map(
            lambda d, p: (-d).astype(p.dtype),
            delta_shards, shard_params,
        )
        # Replicated state (Adam's count) stays whole on every
        # chip; type it varying on entry and re-type on exit (pmax
        # over identical values — a bitwise no-op) so it can cross
        # the sharded update on VMA runtimes. The sharded/
        # replicated split comes from the build-time template
        # (self._opt_sharded) — a shape test here would see
        # shard-LOCAL leaves and misclassify them.
        opt_in = jax.tree.map(
            lambda l, sharded: l if sharded
            else _to_varying(l, "dp"),
            opt_state, self._opt_sharded,
        )
        updates, new_opt_state = self.algorithm.server_optimizer.update(
            pseudo_grad, opt_in, shard_params
        )
        new_shards = optax.apply_updates(shard_params, updates)
        new_opt_state = jax.tree.map(
            lambda l, sharded: l if sharded
            else jax.lax.pmax(l, "dp"),
            new_opt_state, self._opt_sharded,
        )
        new_params = jax.tree.map(
            lambda s, p: defense_mod.place_coordinate_shard(
                s, "dp", dpn, p.shape
            ),
            new_shards, params,
        )
        return new_params, new_opt_state

    def _apply_auto_sharded_update(self, params, opt_state, delta_flat):
        """Cross-replica sharded server update on a model-parallel mesh
        (the mp>1 composition of arXiv 2004.13336): every param leaf is
        viewed as flat padded coordinates sharded over BOTH mesh axes
        (``P(("dp", "mp"))`` — O(params/(dp*mp)) resident optimizer state
        per chip), the elementwise optax update runs on those shards in
        GSPMD-auto land, and fresh params are restored to their
        tensor-parallel layout (param_specs) by one gather per leaf.
        Runs inside the jitted GSPMD-auto round program
        (:class:`_MeshBoundary` — there is no shard_map at mp>1):
        ``delta_flat`` arrives as the flat mean delta pinned to
        ``P(("dp", "mp"))`` by a with_sharding_constraint, and GSPMD
        places the scatter/gather collectives."""
        mesh = self.plan.mesh
        wsc = jax.lax.with_sharding_constraint
        flat_sh = NamedSharding(mesh, P(("dp", "mp")))

        flat_p = jax.tree.map(
            lambda p: wsc(_flat_pad_leaf(p, self._shard_pad), flat_sh),
            params,
        )
        delta = jax.tree.map(lambda d: wsc(d, flat_sh), delta_flat)
        pseudo_grad = jax.tree.map(
            lambda d, p: (-d).astype(p.dtype), delta, flat_p
        )
        updates, new_opt_state = self.algorithm.server_optimizer.update(
            pseudo_grad, opt_state, flat_p
        )
        new_flat = optax.apply_updates(flat_p, updates)
        new_opt_state = jax.tree.map(
            lambda l, sharded: wsc(l, flat_sh) if sharded else l,
            new_opt_state, self._opt_sharded,
        )
        shardings = self._param_shardings()
        if shardings is None:
            shardings = jax.tree.map(
                lambda _: NamedSharding(mesh, P()), params
            )

        def unflat(f, p, sh):
            n = int(np.prod(p.shape, dtype=np.int64))
            return wsc(f[:n].reshape(p.shape), sh)

        new_params = jax.tree.map(unflat, new_flat, params, shardings)
        return new_params, new_opt_state

    def _client_sharded_like(self, params):
        """Shardings for a per-client tree [C, ...]: client axis over ``dp``,
        tensor-parallel leaves additionally over ``mp`` per param_specs.
        Shared by Ditto's personal params and SCAFFOLD's control variates."""
        mesh = self.plan.mesh
        if self.param_specs is None:
            return jax.tree.map(
                lambda _: NamedSharding(mesh, P("dp")), params
            )
        return jax.tree.map(
            lambda _, s: NamedSharding(mesh, P("dp", *s)),
            params, self.param_specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def init_personal(self, state: ServerState, num_clients: int) -> PersonalState:
        """Materialize Ditto personal params for ``num_clients`` (padded)
        clients: every client starts at the current global model, stored
        sharded over ``dp`` (and, for tensor-parallel leaves, additionally
        over ``mp``) in ``config.personal_dtype``."""
        dt = self.config.personal_dtype

        def tile(p):
            target = p.astype(dt) if dt is not None else p
            return jnp.broadcast_to(target[None], (num_clients,) + p.shape)

        tiled = jax.jit(
            lambda params: jax.tree.map(tile, params),
            out_shardings=self._client_sharded_like(state.params),
        )(state.params)
        return PersonalState(params=tiled)

    def init_control(self, state: ServerState, num_clients: int) -> ControlState:
        """Zero-initialized SCAFFOLD control variates: per-client c_i
        [C, ...] sharded over ``dp`` (and ``mp`` for tensor-parallel
        leaves), server c replicated."""
        ci = jax.jit(
            lambda params: jax.tree.map(
                lambda p: jnp.zeros((num_clients,) + p.shape, jnp.float32),
                params,
            ),
            out_shardings=self._client_sharded_like(state.params),
        )(state.params)
        sc = jax.jit(
            lambda params: jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            ),
            out_shardings=self.plan.replicated(),
        )(state.params)
        return ControlState(client_controls=ci, server_control=sc)

    def round_step(self, *args, **kwargs):
        """Advance one FL round over the (placed, padded) population —
        resolve the program variant + arguments (:meth:`_prepare_round_args`
        holds the full parameter documentation) and launch it."""
        fn, call_args = self._prepare_round_args(*args, **kwargs)
        return self._launch(fn, *call_args)

    def _prepare_round_args(
        self,
        state: ServerState,
        ds: ClientDataset,
        participate: Optional[jax.Array] = None,
        num_steps: Optional[jax.Array] = None,
        personal: Optional[PersonalState] = None,
        control: Optional[ControlState] = None,
        completion_time: Optional[jax.Array] = None,
        deadline: Optional[float] = None,
        attack_scale: Optional[jax.Array] = None,
        defense: Optional[Any] = None,
        async_plan: Optional[Any] = None,
    ):
        """Resolve one FL round's compiled program variant and its launch
        arguments; ``round_step`` executes them, ``lower_round_step``
        AOT-lowers them.

        ``participate`` — optional [C] 0/1 mask from the deviceflow trace
        compiler; multiplies the base weights. ``num_steps`` — optional
        per-client local-step counts (hetero compute profiles); defaults to
        ``max_local_steps`` everywhere. ``personal`` — Ditto per-client state
        (required iff the algorithm is personalized); when given the return is
        ``(state, metrics, personal)``. ``control`` — SCAFFOLD control
        variates (required iff the algorithm uses them); the return is then
        ``(state, metrics, control)``.

        ``deadline`` + ``completion_time`` — deadline-masked aggregation:
        clients whose simulated ``completion_time`` [C] exceeds the
        ``deadline`` scalar get zero aggregation weight inside the compiled
        program and are counted in ``metrics.stragglers``. Both are data
        (not compile-time constants), so per-round deadlines never
        recompile. With ``deadline=None`` the original program runs with
        the original inputs — bitwise identical to the deadline-free build.

        ``attack_scale`` — optional [C] per-client multiplier applied to
        each client's delta after local training (byzantine update attack:
        sign_flip = -1, scale = factor, benign = 1; data, so per-round
        attack sets never recompile).

        ``defense`` — optional
        :class:`~olearning_sim_tpu.engine.defense.DefenseConfig`: in-jit
        L2 delta clipping, trimmed-mean / median robust aggregation, and
        Krum-style per-client anomaly scores (``metrics.anomaly_score``).
        Scalar knobs (clip_norm, trim_fraction) are data; the aggregator
        choice and scoring toggle select a lazily-compiled program variant.

        ``async_plan`` — optional
        :class:`~olearning_sim_tpu.engine.async_rounds.AsyncRoundPlan`:
        runs the buffered asynchronous round program instead of the
        synchronous one (FedBuff-style staleness-weighted commits every
        ``buffer_size`` arrivals; the call then returns
        ``(state, metrics, async_stats)``). Window assignments, scores,
        ``staleness_alpha`` and ``max_staleness`` are data; the buffer
        capacity (from M) and schedule key the program variant. Mutually
        exclusive with ``deadline`` (``max_staleness`` is the async
        lateness control) and with personalized / control-variate
        algorithms.
        """
        weight = ds.weight if participate is None else ds.weight * participate
        if num_steps is None:
            num_steps = global_put(
                np.full((ds.num_clients,), self.config.max_local_steps, np.int32),
                self.plan.client_sharding(),
            )
        if defense is not None and not defense.enabled:
            defense = None
        if self.plan.pp > 1 and (
            deadline is not None or completion_time is not None
            or attack_scale is not None or defense is not None
            or async_plan is not None
        ):
            raise ValueError(
                "pipeline-parallel (pp>1) rounds support the plain "
                "program only: deadline/attack/defense/async do not "
                "compose with the stage-pipelined per-client body "
                "(docs/performance.md has the composition matrix)"
            )
        if async_plan is not None:
            return self._prepare_async_args(
                state, ds, async_plan, weight, num_steps,
                completion_time=completion_time, deadline=deadline,
                attack_scale=attack_scale, defense=defense,
                personal=personal, control=control,
            )
        if defense is not None and defense.gathers_deltas \
                and self.algorithm.control_variates:
            raise ValueError(
                "robust aggregators / anomaly scoring are not supported "
                "with control-variate algorithms (the SCAFFOLD server "
                "control consumes the weighted mean); use clip_norm only"
            )
        if defense is not None and defense.gathers_deltas \
                and self.plan.mp > 1:
            raise ValueError(
                "robust aggregators / anomaly scoring do not compose with "
                "a model-parallel mesh (mp > 1): their coordinate-sharded "
                "layout is built on manual dp collectives the mp>1 "
                "GSPMD-auto round program cannot host — run mp=1 or use "
                "clip_norm only (docs/performance.md has the composition "
                "matrix)"
            )
        extras = ()
        if deadline is not None:
            if completion_time is None:
                raise ValueError(
                    "deadline given without completion_time; compute one "
                    "with olearning_sim_tpu.engine.pacing.completion_times"
                )
            extras += (completion_time, jnp.float32(deadline))
        elif completion_time is not None:
            raise ValueError("completion_time given without a deadline")
        if attack_scale is not None:
            extras += (attack_scale,)
        if defense is not None:
            clip = defense.clip_norm
            if clip is None or not np.isfinite(clip):
                # clip disabled: a literal inf input re-keys the jit
                # executable cache (observed: one extra compile per
                # finite<->inf transition), so pass a finite sentinel
                # instead — its square overflows to f32 inf, making
                # ``norm2 > clip*clip`` unconditionally false, which
                # disables clipping bitwise-identically.
                clip = 3.0e38
            extras += (jnp.float32(clip), jnp.float32(defense.trim_fraction))
        key = (deadline is not None, attack_scale is not None,
               defense.structure_key if defense is not None else None)
        fn = self._round_step_variants.get(key)
        if fn is None:
            fn = self._build_round_step(
                with_deadline=key[0], with_attack=key[1], defense=defense,
            )
            self._round_step_variants[key] = fn
        if self.algorithm.control_variates:
            if control is None:
                raise ValueError(
                    f"algorithm {self.algorithm.name!r} uses control "
                    f"variates; pass control=core.init_control(state, "
                    f"ds.num_clients)"
                )
            return fn, (
                state, control, ds.x, ds.y, ds.num_samples, num_steps,
                ds.client_uid, weight, jnp.float32(ds.population), *extras,
            )
        if control is not None:
            raise ValueError(
                f"algorithm {self.algorithm.name!r} does not use control "
                f"variates but control state was supplied"
            )
        if self.algorithm.personalized:
            if personal is None:
                raise ValueError(
                    f"algorithm {self.algorithm.name!r} is personalized; pass "
                    f"personal=core.init_personal(state, ds.num_clients)"
                )
            return fn, (
                state, personal, ds.x, ds.y, ds.num_samples, num_steps,
                ds.client_uid, weight, *extras,
            )
        if personal is not None:
            raise ValueError(
                f"algorithm {self.algorithm.name!r} is not personalized but "
                f"personal state was supplied"
            )
        return fn, (
            state, ds.x, ds.y, ds.num_samples, num_steps, ds.client_uid,
            weight, *extras,
        )

    def _prepare_async_args(self, state, ds, async_plan, weight, num_steps,
                            completion_time=None, deadline=None,
                            attack_scale=None, defense=None,
                            personal=None, control=None):
        """Resolve the buffered-async program variant + launch arguments
        for one :class:`~olearning_sim_tpu.engine.async_rounds.
        AsyncRoundPlan` (see :meth:`_prepare_round_args`)."""
        if self.plan.mp > 1:
            raise ValueError(
                "buffered asynchronous rounds do not compose with a "
                "model-parallel mesh (mp > 1): the async commit scan is a "
                "manual-dp shard_map program, which XLA 0.4.x cannot "
                "partition with a >1 auto mp axis — run the async family "
                "at mp=1 (docs/performance.md has the composition matrix)"
            )
        if deadline is not None or completion_time is not None:
            raise ValueError(
                "async rounds and deadline masking are mutually exclusive "
                "(async.max_staleness is the buffered engine's lateness "
                "control; the completion-time model drives arrival order)"
            )
        if personal is not None or control is not None:
            raise ValueError(
                "async rounds do not take personal/control state "
                "(personalized and control-variate algorithms are not "
                "supported by the buffered engine)"
            )
        acfg = async_plan.config
        W = int(async_plan.num_windows)
        if W != acfg.num_windows(ds.num_clients):
            raise ValueError(
                f"async plan was built for a different population: "
                f"plan windows {W} != "
                f"{acfg.num_windows(ds.num_clients)} for "
                f"{ds.num_clients} padded clients at "
                f"M={acfg.buffer_size}"
            )
        sh = self.plan.client_sharding()
        window_dev = global_put(
            np.asarray(async_plan.window, np.int32), sh
        )
        if acfg.schedule == "score":
            score_dev = global_put(
                np.asarray(async_plan.score, np.float32), sh
            )
        else:
            # Replicated zero placeholder (spec rep): keeps the program
            # signature uniform without shipping a per-client array.
            score_dev = jnp.float32(0.0)
        max_stale = (float(acfg.max_staleness)
                     if acfg.max_staleness is not None
                     else async_rounds._NO_MAX_STALENESS)
        extras = ()
        if attack_scale is not None:
            extras += (attack_scale,)
        if defense is not None:
            clip = defense.clip_norm
            if clip is None or not np.isfinite(clip):
                clip = 3.0e38  # finite sentinel — see the sync path note
            extras += (jnp.float32(clip), jnp.float32(defense.trim_fraction))
        key = async_rounds.async_variant_key(
            W, acfg.schedule, attack_scale is not None, defense
        )
        fn = self._round_step_variants.get(key)
        if fn is None:
            fn = async_rounds.build_async_round_step(
                self, W, acfg.schedule,
                with_attack=attack_scale is not None, defense=defense,
            )
            self._round_step_variants[key] = fn
        return fn, (
            state, ds.x, ds.y, ds.num_samples, num_steps, ds.client_uid,
            weight, window_dev, score_dev,
            jnp.float32(acfg.staleness_alpha), jnp.float32(max_stale),
            *extras,
        )

    def lower_round_step(self, *args, **kwargs):
        """AOT-lower the round-program variant :meth:`round_step` would
        launch for these arguments, WITHOUT executing it. Same signature
        as :meth:`round_step`; returns the ``jax.stages.Lowered`` (whose
        ``.compile().as_text()`` is what ``engine/hlo_stats`` and
        ``scripts/check_hlo_collectives.py`` analyze)."""
        fn, call_args = self._prepare_round_args(*args, **kwargs)
        return fn.lower(*call_args)

    def _launch(self, fn, *args):
        """Launch a compiled round step, counting launches and host-side
        dispatch latency (async — device completion is the runner's
        ``host_transfer`` phase). The first launch pays synchronous
        trace+compile (seconds to minutes) and is excluded from the
        dispatch histogram — one compile sample would dominate its sum
        forever; the runner records compile time distinctly."""
        import time

        from olearning_sim_tpu.telemetry import instrument

        t0 = time.perf_counter()
        out = _call_in_roomy_frame(fn, *args)
        name = self.algorithm.name
        instrument("ols_fedcore_round_steps_total").labels(
            algorithm=name
        ).inc()
        if getattr(self, "_dispatch_warm", False):
            instrument("ols_fedcore_round_step_dispatch_seconds").labels(
                algorithm=name
            ).observe(time.perf_counter() - t0)
        else:
            self._dispatch_warm = True
        return out

    # ------------------------------------------------------- streamed rounds
    # Block-streamed round execution: the cohort is processed in
    # device-sized blocks with the partial aggregates carried ON DEVICE
    # across blocks and the server update applied once at round close, so
    # peak HBM is O(block) regardless of population size. The per-block
    # computation is the resident program's block stage and accumulator
    # (round_stages.client_block -> ClientBlock.weighted_sum),
    # and the client->device layout interleaves stream blocks so each
    # device folds ITS monolithic row range in the monolithic order —
    # which is what makes a >=2-block streamed round bitwise identical to
    # the resident single-program round (tests/test_streaming.py pins
    # params, metrics, and per-client losses).
    def _stream_reject(self, defense):
        if self.plan.pp > 1 or self.plan.mp > 1:
            raise ValueError(
                "streamed rounds run on dp-only meshes: the partial-"
                "aggregate carry is a manual-dp program (mp>1 runs "
                "GSPMD-auto end-to-end, pp>1 pipelines the train body; "
                "docs/performance.md has the composition matrix)"
            )
        if self.algorithm.personalized or self.algorithm.control_variates:
            raise ValueError(
                f"streamed rounds do not support the personalized/"
                f"control-variate algorithm {self.algorithm.name!r} "
                f"(per-client state does not yet stream; keep the "
                f"population resident)"
            )
        if self.config.shard_server_update:
            raise ValueError(
                "streamed rounds use the replicated server update; "
                "fedcore.shard_server_update=true does not compose with "
                "scenario.stream_block_rows (the round-close stitch "
                "would need the manual psum_scatter tail per stream "
                "variant — docs/performance.md composition matrix)"
            )
        if defense is not None and defense.gathers_deltas:
            raise ValueError(
                "robust aggregators / anomaly scoring do not compose "
                "with streamed rounds: they need every client's delta "
                "simultaneously (O(cohort x params)), which is exactly "
                "the residency streaming removes — use clip_norm only"
            )

    def _build_stream_step(self, rows_per_device: int,
                           with_deadline: bool = False,
                           with_attack: bool = False, defense=None):
        """Build (partial_fn, finalize_fn, zero_acc_fn) for one streamed
        program shape. ``partial_fn(params, base_key, round_idx, acc,
        <block data>, *extras) -> (acc, client_loss)`` advances the
        partial aggregates over one staged block (the carry is donated —
        HBM holds one live accumulator); ``finalize_fn(state, acc) ->
        (state, metrics)`` applies the cross-replica reduction and the
        server optimizer update once at round close. All per-round knobs
        (deadline, attack scales, clip norm) are data, exactly like the
        resident program's."""
        plan = self.plan
        cfg = self.config
        alg = self.algorithm
        mesh = plan.mesh
        if rows_per_device % cfg.block_clients != 0:
            raise ValueError(
                f"stream rows per device {rows_per_device} must be a "
                f"multiple of block_clients={cfg.block_clients}"
            )
        dkey = defense.structure_key if defense is not None else None
        trace_key = ("stream", rows_per_device, with_deadline, with_attack,
                     dkey)
        fin_key = ("stream_finalize", with_deadline, with_attack, dkey)

        def partial_body(params, base_key, round_idx, acc,
                         x, y, num_samples, num_steps, uid, weight,
                         *extras):
            # Trace-time probe: scenario/stream knob changes across
            # rounds must never re-trace (same regression contract as
            # the resident program's trace_counts).
            self.trace_counts[trace_key] = \
                self.trace_counts.get(trace_key, 0) + 1
            extras = list(extras)
            if defense is not None:
                (sum_delta, sum_w, sum_loss, count, stragglers,
                 n_clip) = acc
            else:
                sum_delta, sum_w, sum_loss, count, stragglers = acc
                n_clip = None
            # Per-device accumulator slices arrive [1, ...]; peel the
            # leading stream axis.
            peel = lambda t: jax.tree.map(lambda a: a[0], t)
            sum_delta = peel(sum_delta)
            sum_w, sum_loss, count, stragglers = (
                sum_w[0], sum_loss[0], count[0], stragglers[0]
            )
            if n_clip is not None:
                n_clip = n_clip[0]
            clip_norm = None
            if with_deadline:
                completion_time, deadline = extras[0], extras[1]
                del extras[:2]
                late = completion_time > deadline
                stragglers = stragglers + jnp.logical_and(
                    weight > 0, late
                ).sum().astype(jnp.float32)
                weight = jnp.where(late, jnp.zeros_like(weight), weight)
            if with_attack:
                attack_scale = extras.pop(0)
            if defense is not None:
                clip_norm = extras[0]
                del extras[:2]
            c_local = x.shape[0]
            nb = c_local // cfg.block_clients

            def blocked(a):
                return a.reshape((nb, cfg.block_clients) + a.shape[1:])

            xs = (blocked(x), blocked(y), blocked(num_samples),
                  blocked(num_steps), blocked(uid), blocked(weight),
                  blocked(attack_scale) if with_attack else None)
            init = (sum_delta, sum_w, sum_loss, count)
            if defense is not None:
                init = init + (n_clip,)

            def block_step(carry, inp):
                if defense is not None:
                    sum_delta, sum_w, sum_loss, count, n_clip = carry
                else:
                    sum_delta, sum_w, sum_loss, count = carry
                    n_clip = None
                bx, by, bns, bst, buid, bw, batk = inp
                blk = client_block(
                    self._local_train, (None, 0, 0, 0, 0, 0, None, None),
                    (params, bx, by, bns, bst, buid, base_key, round_idx),
                    bw, vmap_clients=self.vmap_clients, attack_scale=batk,
                    clip_norm=clip_norm,
                )
                if defense is not None:
                    n_clip = n_clip + blk.clipped
                sum_delta = blk.weighted_sum(sum_delta)
                new_carry = (sum_delta,
                             *blk.tally(sum_w, sum_loss, count))
                if defense is not None:
                    new_carry = new_carry + (n_clip,)
                return new_carry, blk.losses

            carry, block_losses = jax.lax.scan(
                block_step, init, xs, unroll=min(cfg.block_unroll, nb)
            )
            if defense is not None:
                sum_delta, sum_w, sum_loss, count, n_clip = carry
            else:
                sum_delta, sum_w, sum_loss, count = carry
            client_loss = block_losses.reshape((c_local,))
            pack = lambda t: jax.tree.map(lambda a: a[None], t)
            new_acc = (pack(sum_delta), sum_w[None], sum_loss[None],
                       count[None], stragglers[None])
            if defense is not None:
                new_acc = new_acc + (n_clip[None],)
            return new_acc, client_loss

        def finalize_body(params, opt_state, round_idx, acc):
            self.trace_counts[fin_key] = \
                self.trace_counts.get(fin_key, 0) + 1
            if defense is not None:
                (sum_delta, sum_w, sum_loss, count, stragglers,
                 n_clip) = acc
            else:
                sum_delta, sum_w, sum_loss, count, stragglers = acc
                n_clip = None
            sum_delta = jax.tree.map(lambda a: a[0], sum_delta)
            sum_w, sum_loss, count, stragglers = (
                sum_w[0], sum_loss[0], count[0], stragglers[0]
            )
            # Cross-replica reduction + server update: the exact tail of
            # the resident program (each device's partial is its
            # monolithic scan total, so the psum reduces the identical
            # operands).
            with jax.named_scope("aggregate"):
                sum_w = jax.lax.psum(sum_w, "dp")
                sum_loss = jax.lax.psum(sum_loss, "dp")
                count = jax.lax.psum(count, "dp")
                stragglers = jax.lax.psum(stragglers, "dp")
                if n_clip is not None:
                    n_clip = jax.lax.psum(n_clip[0], "dp")
                else:
                    n_clip = jnp.float32(0.0)
                sum_delta = jax.lax.psum(sum_delta, "dp")
                denom = jnp.maximum(sum_w, 1e-8)
                mean_delta = jax.tree.map(lambda s: s / denom, sum_delta)
            with jax.named_scope("server_update"):
                new_params, new_opt_state = server_commit(
                    alg.server_optimizer, params, opt_state, mean_delta)
            metrics = RoundMetrics(
                mean_loss=sum_loss / denom,
                weight_sum=sum_w,
                clients_trained=count,
                # Assembled host-side from the streamed per-block losses
                # (the driver replaces this placeholder).
                client_loss=jnp.float32(0.0),
                personal_loss=jnp.float32(0.0),
                stragglers=stragglers,
                anomaly_score=jnp.float32(0.0),
                clipped=n_clip,
            )
            return new_params, new_opt_state, round_idx + 1, metrics

        rep = P()
        cl = P("dp")
        acc_leaf = P("dp")
        p_shapes = jax.eval_shape(self.init_params_fn, jax.random.key(0))
        acc_delta_spec = jax.tree.map(lambda _: acc_leaf, p_shapes)
        acc_specs = (acc_delta_spec, acc_leaf, acc_leaf, acc_leaf, acc_leaf)
        if defense is not None:
            acc_specs = acc_specs + (acc_leaf,)
        pace_specs = (cl, rep) if with_deadline else ()
        attack_specs = (cl,) if with_attack else ()
        defense_specs = (rep, rep) if defense is not None else ()
        extra_specs = pace_specs + attack_specs + defense_specs

        partial_fn = jax.jit(
            jax.shard_map(
                partial_body,
                mesh=mesh,
                in_specs=(rep, rep, rep, acc_specs, cl, cl, cl, cl, cl,
                          cl) + extra_specs,
                out_specs=(acc_specs, cl),
                axis_names=frozenset({"dp"}),
            ),
            donate_argnums=(3,),
        )

        fin_shard = jax.shard_map(
            finalize_body,
            mesh=mesh,
            in_specs=(rep, rep, rep, acc_specs),
            out_specs=(rep, rep, rep, jax.tree.map(
                lambda _: rep,
                RoundMetrics(
                    mean_loss=0, weight_sum=0, clients_trained=0,
                    client_loss=0, personal_loss=0, stragglers=0,
                    anomaly_score=0, clipped=0,
                ),
            )),
            axis_names=frozenset({"dp"}),
        )

        # Only the state is donated here: the accumulator's [dp, ...]
        # leaves cannot alias the (smaller) outputs, and donating them
        # would just emit an unusable-donation warning per compile; they
        # die with their last reference the moment this call returns.
        @functools.partial(jax.jit, donate_argnums=(0,))
        def finalize_fn(state: ServerState, acc):
            *new, metrics = fin_shard(
                state.params, state.opt_state, state.round_idx, acc
            )
            return next_state(state, *new), metrics

        dpn = plan.dp
        acc_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s), acc_specs,
            is_leaf=lambda x: isinstance(x, P),
        )

        def make_zeros():
            zeros_delta = jax.tree.map(
                lambda p: jnp.zeros((dpn,) + p.shape, jnp.float32), p_shapes
            )
            scalars = [jnp.zeros((dpn,), jnp.float32)
                       for _ in range(5 if defense is not None else 4)]
            return (zeros_delta, *scalars)

        zero_acc_fn = jax.jit(make_zeros, out_shardings=acc_sh)
        return partial_fn, finalize_fn, zero_acc_fn

    def _stream_variant(self, rows_per_device: int, with_deadline: bool,
                        with_attack: bool, defense):
        key = (rows_per_device, with_deadline, with_attack,
               defense.structure_key if defense is not None else None)
        built = self._stream_variants.get(key)
        if built is None:
            built = self._build_stream_step(
                rows_per_device, with_deadline=with_deadline,
                with_attack=with_attack, defense=defense,
            )
            self._stream_variants[key] = built
        return built

    def _prepare_stream(self, store, stream_rows: int,
                        participate=None, num_steps=None,
                        completion_time=None, deadline=None,
                        attack_scale=None, defense=None,
                        label_shift=None, label_classes=None):
        """Resolve one streamed round's plan: pad the store, normalize the
        per-client host arrays to the padded population, and return the
        layout (row segments per block) plus the compiled variant."""
        plan = self.plan
        cfg = self.config
        if defense is not None and not defense.enabled:
            defense = None
        self._stream_reject(defense)
        dpn = plan.dp
        R = int(stream_rows)
        if R % (dpn * cfg.block_clients) != 0:
            raise ValueError(
                f"stream_block_rows={R} must be a multiple of "
                f"dp*block_clients={dpn * cfg.block_clients}"
            )
        if deadline is None and completion_time is not None:
            raise ValueError("completion_time given without a deadline")
        if deadline is not None and completion_time is None:
            raise ValueError(
                "deadline given without completion_time; compute one "
                "with olearning_sim_tpu.engine.pacing.completion_times"
            )
        c_pad = pad_to_multiple(
            max(store.num_real_clients, store.padded_clients), R
        )
        store.pad_to(c_pad)
        cpd = c_pad // dpn
        rpd = R // dpn
        nb = c_pad // R

        def full(arr, fill, dtype):
            if arr is None:
                return None
            out = np.full(c_pad, fill, dtype)
            a = np.asarray(arr)
            out[: a.shape[0]] = a.astype(dtype, copy=False)
            return out

        participate = full(participate, 0.0, np.float32)
        num_steps = full(num_steps, cfg.max_local_steps, np.int32)
        completion_time = full(completion_time, np.inf, np.float32)
        attack_scale = full(attack_scale, 1.0, np.float32)
        if label_shift is not None and label_classes is None:
            raise ValueError(
                "label_shift needs label_classes (the drift modulus); "
                "the scenario layer passes the population's class count"
            )
        label_shift = full(label_shift, 0, np.int32)

        def segments(i):
            """Global row ranges [(start, stop)] per device for stream
            block ``i`` — the interleaved layout that keeps each device's
            accumulation chain identical to the resident program's."""
            return [(d * cpd + i * rpd, d * cpd + (i + 1) * rpd)
                    for d in range(dpn)]

        with_deadline = deadline is not None
        with_attack = attack_scale is not None
        partial_fn, finalize_fn, zero_acc_fn = self._stream_variant(
            rpd, with_deadline, with_attack, defense
        )
        extras_const = ()
        if defense is not None:
            clip = defense.clip_norm
            if clip is None or not np.isfinite(clip):
                clip = 3.0e38  # finite disabled sentinel — see sync path
            extras_const = (jnp.float32(clip),
                            jnp.float32(defense.trim_fraction))
        return {
            "c_pad": c_pad, "rpd": rpd, "nb": nb, "R": R,
            "segments": segments,
            "participate": participate, "num_steps": num_steps,
            "completion_time": completion_time, "deadline": deadline,
            "attack_scale": attack_scale if with_attack else None,
            "label_shift": label_shift, "label_classes": label_classes,
            "with_deadline": with_deadline, "with_attack": with_attack,
            "defense": defense, "extras_const": extras_const,
            "partial_fn": partial_fn, "finalize_fn": finalize_fn,
            "zero_acc_fn": zero_acc_fn,
        }

    def _place_stream_block(self, store, prep, i, feature_dtype):
        """Stage stream block ``i``: gather the interleaved host rows and
        place them sharded so device ``d`` receives exactly its
        monolithic row range's ``i``-th slice. Returns (placed tuple,
        extras tuple, bytes staged, row index array)."""
        segs = prep["segments"](i)
        parts = [store.rows(a, b) for a, b in segs]
        cat = {k: (np.concatenate([p[k] for p in parts])
                   if len(parts) > 1 else parts[0][k])
               for k in parts[0]}
        x = cat["x"]
        if feature_dtype is not None and jnp.issubdtype(
                np.asarray(x).dtype, jnp.floating):
            x = np.asarray(x).astype(feature_dtype)
        rows_idx = np.concatenate(
            [np.arange(a, b) for a, b in segs]
        ) if len(segs) > 1 else np.arange(segs[0][0], segs[0][1])
        y = cat["y"]
        if prep["label_shift"] is not None:
            # Non-IID label drift: the client's label mapping rotates by
            # its per-round shift. Labels are data, so drift never
            # retraces; a zero shift is an exact no-op.
            shift = prep["label_shift"][rows_idx]
            if shift.any():
                y = (np.asarray(y) + shift[:, None]) % int(
                    prep["label_classes"]
                )
                y = y.astype(cat["y"].dtype, copy=False)
        weight = cat["weight"]
        if prep["participate"] is not None:
            weight = weight * prep["participate"][rows_idx]
        steps = (prep["num_steps"][rows_idx]
                 if prep["num_steps"] is not None
                 else np.full(weight.shape[0], self.config.max_local_steps,
                              np.int32))
        sh = self.plan.client_sharding()
        put = lambda a: global_put(np.ascontiguousarray(a), sh)
        placed = (
            put(x), put(y),
            put(np.asarray(cat["num_samples"], np.int32)),
            put(np.asarray(steps, np.int32)),
            put(np.asarray(cat["client_uid"], np.int32)),
            put(np.asarray(weight, np.float32)),
        )
        extras = ()
        if prep["with_deadline"]:
            extras += (put(prep["completion_time"][rows_idx]),
                       jnp.float32(prep["deadline"]))
        if prep["with_attack"]:
            extras += (put(prep["attack_scale"][rows_idx]),)
        extras += prep["extras_const"]
        nbytes = sum(
            int(np.asarray(a).nbytes) for a in
            (x, cat["y"], cat["num_samples"], steps, cat["client_uid"],
             weight)
        )
        return placed, extras, nbytes, rows_idx

    def stream_round(self, state: ServerState, store,
                     stream_rows: Optional[int] = None,
                     participate=None, num_steps=None,
                     completion_time=None, deadline=None,
                     attack_scale=None, defense=None,
                     label_shift=None, label_classes=None,
                     feature_dtype=jnp.bfloat16, tracer=None):
        """Advance one FL round over a host-resident
        :class:`~olearning_sim_tpu.engine.client_data.HostClientStore`,
        streaming the cohort through the device in blocks of
        ``stream_rows`` clients with double-buffered host->device
        staging (the next block's placement is issued while the current
        block's compiled step is in flight) and the partial aggregates
        carried on device. Returns ``(state, metrics, StreamStats)``.

        Per-client inputs (``participate`` / ``num_steps`` /
        ``completion_time`` / ``attack_scale``) are HOST arrays of length
        ``num_real_clients`` (or the padded population); scalar knobs
        match :meth:`round_step`'s semantics exactly. ``feature_dtype``
        mirrors ``ClientDataset.place`` (bf16 features by default; pass
        ``None`` for dtype-preserving parity runs).

        Bitwise contract: for the same cohort, padded size, and
        ``block_clients``, a >=2-block streamed round produces bit-for-bit
        the params, metrics, and per-client losses of the resident
        single-program round (regression-tested).

        ``tracer`` — a :class:`~olearning_sim_tpu.telemetry.SpanTracer`
        (default tracer when None): each block emits a ``stream_stage``
        span around its host->device placement and a ``stream_step`` span
        around its partial-step dispatch, so the double-buffered overlap
        is visible in the Perfetto export next to the runner's round
        spans."""
        import time as _time

        from olearning_sim_tpu.telemetry import default_tracer, instrument

        tracer = tracer if tracer is not None else default_tracer()

        if stream_rows is None:
            raise ValueError(
                "stream_round needs stream_rows (scenario."
                "stream_block_rows when driven by engine params)"
            )
        prep = self._prepare_stream(
            store, stream_rows, participate=participate,
            num_steps=num_steps, completion_time=completion_time,
            deadline=deadline, attack_scale=attack_scale, defense=defense,
            label_shift=label_shift, label_classes=label_classes,
        )
        nb = prep["nb"]
        acc = prep["zero_acc_fn"]()
        partial_fn = prep["partial_fn"]

        transfer_s = 0.0
        first_transfer_s = 0.0
        transfer_bytes = 0
        block_bytes0 = 0
        losses = [None] * nb
        rowmaps = [None] * nb

        t0 = _time.perf_counter()
        with tracer.span("stream_stage", block=0):
            placed, extras, nbytes, rows_idx = self._place_stream_block(
                store, prep, 0, feature_dtype
            )
        first_transfer_s = _time.perf_counter() - t0
        transfer_s += first_transfer_s
        transfer_bytes += nbytes
        block_bytes0 = nbytes
        for i in range(nb):
            rowmaps[i] = rows_idx
            with tracer.span("stream_step", block=i):
                acc, losses[i] = partial_fn(
                    state.params, state.base_key, state.round_idx, acc,
                    *placed, *extras,
                )
            if i + 1 < nb:
                # Double buffering: stage the next block while the
                # current block's compiled step is in flight. HBM holds
                # at most two staged blocks (the previous block's
                # buffers die with their last reference).
                t0 = _time.perf_counter()
                with tracer.span("stream_stage", block=i + 1):
                    placed, extras, nbytes, rows_idx = \
                        self._place_stream_block(store, prep, i + 1,
                                                 feature_dtype)
                transfer_s += _time.perf_counter() - t0
                transfer_bytes += nbytes
        new_state, metrics = prep["finalize_fn"](state, acc)

        client_loss = np.full(prep["c_pad"], np.nan, np.float32)
        for i in range(nb):
            # The streamed round's designed host sync point (the
            # host_transfer analogue): all blocks + the finalize commit
            # are already dispatched, and the per-block loss arrays are
            # private to this walk.
            client_loss[rowmaps[i]] = np.asarray(
                jax.device_get(losses[i])  # lint: allow-host-sync
            )
        metrics = metrics.replace(client_loss=client_loss)

        overlap = None
        if nb > 1 and first_transfer_s > 0 and block_bytes0 > 0:
            rate = block_bytes0 / first_transfer_s
            est_rest = (transfer_bytes - block_bytes0) / rate
            seen_rest = transfer_s - first_transfer_s
            if est_rest > 0:
                overlap = float(np.clip(1.0 - seen_rest / est_rest,
                                        0.0, 1.0))
        params_bytes = sum(
            int(np.prod(l.shape, dtype=np.int64)) * l.dtype.itemsize
            for l in jax.tree.leaves(new_state.params)
        )
        opt_bytes = sum(
            int(np.prod(getattr(l, "shape", ()), dtype=np.int64))
            * getattr(l, "dtype", np.dtype(np.float32)).itemsize
            for l in jax.tree.leaves(new_state.opt_state)
        )
        acc_bytes = sum(
            int(np.prod(l.shape, dtype=np.int64)) * l.dtype.itemsize
            for l in jax.tree.leaves(acc)
        )
        stats = StreamStats(
            blocks=nb,
            block_rows=prep["R"],
            rows=prep["c_pad"],
            transfer_bytes=transfer_bytes,
            host_transfer_s=round(transfer_s, 6),
            overlap_fraction=overlap,
            peak_hbm_bytes_est=int(params_bytes + opt_bytes + acc_bytes
                                   + 2 * block_bytes0),
            state_bytes=store.state_bytes(),
        )
        instrument("ols_engine_host_transfer_seconds_total").labels(
            algorithm=self.algorithm.name
        ).inc(transfer_s)
        instrument("ols_engine_stream_blocks_total").labels(
            algorithm=self.algorithm.name
        ).inc(nb)
        instrument("ols_engine_client_state_bytes").labels(
            algorithm=self.algorithm.name
        ).set(store.state_bytes())
        return new_state, metrics, stats

    def lower_stream_step(self, state: ServerState, store,
                          stream_rows: int, feature_dtype=jnp.bfloat16,
                          **kwargs):
        """AOT-lower the streamed PARTIAL program for these arguments
        (block 0) without executing it — the streamed analogue of
        :meth:`lower_round_step`, consumed by ``analysis/grid``."""
        prep = self._prepare_stream(store, stream_rows, **kwargs)
        placed, extras, _, _ = self._place_stream_block(
            store, prep, 0, feature_dtype
        )
        acc = prep["zero_acc_fn"]()
        return prep["partial_fn"].lower(
            state.params, state.base_key, state.round_idx, acc,
            *placed, *extras,
        )

    # ----------------------------------------------------------------- eval
    def _build_evaluate(self):
        @jax.jit
        def evaluate(params, x, y):
            with jax.named_scope("evaluate"):
                losses, hits = self._sample_scores(
                    self.apply_fn(params, x), x, y)
            return losses.mean(), hits.mean()

        return evaluate

    def _build_evaluate_personal_auto(self):
        """Ditto personal eval on a model-parallel mesh: same blocked
        weighted-mean computation as the manual builder below, in pure
        GSPMD-auto land (why mp>1 takes it: the boundary choice in
        _build_round_step)."""
        block = self.config.block_clients * self.plan.dp
        apply_fn = self.apply_fn

        def make(vp_tree):
            @jax.jit
            def evaluate(vparams, x, y, num_samples, weight):
                c_total = x.shape[0]
                if c_total % block != 0:
                    raise ValueError(
                        f"clients ({c_total}) must be a multiple of "
                        f"block_clients*dp={block}; pad the dataset with "
                        f"ClientDataset.pad_for(plan, "
                        f"block=config.block_clients)"
                    )
                nb = c_total // block

                def blocked(a):
                    return a.reshape((nb, block) + a.shape[1:])

                def one(v, xc, yc, ns):
                    v = jax.tree.map(
                        lambda t: t.astype(jnp.float32)
                        if jnp.issubdtype(t.dtype, jnp.floating) else t,
                        v,
                    )
                    logits = apply_fn(v, xc)
                    valid = (jnp.arange(xc.shape[0]) < ns)
                    losses = optax.softmax_cross_entropy_with_integer_labels(
                        logits, yc
                    )
                    correct = (logits.argmax(-1) == yc)
                    d = jnp.maximum(ns, 1).astype(jnp.float32)
                    return (
                        jnp.where(valid, losses, 0.0).sum() / d,
                        jnp.where(valid, correct, False).sum() / d,
                    )

                def block_step(carry, inp):
                    sum_loss, sum_acc, sum_w = carry
                    bvp, bx, by, bns, bw = inp
                    loss_c, acc_c = jax.vmap(one)(bvp, bx, by, bns)
                    return (
                        sum_loss + (bw * loss_c).sum(),
                        sum_acc + (bw * acc_c).sum(),
                        sum_w + bw.sum(),
                    ), None

                init = (jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0))
                xs = (jax.tree.map(blocked, vparams), blocked(x), blocked(y),
                      blocked(num_samples), blocked(weight))
                (sum_loss, sum_acc, sum_w), _ = jax.lax.scan(
                    block_step, init, xs
                )
                w = jnp.maximum(sum_w, 1e-8)
                return sum_loss / w, sum_acc / w

            return evaluate

        return make

    def _build_evaluate_personal(self):
        if self.plan.mp > 1:
            return self._build_evaluate_personal_auto()
        cl = P("dp")
        rep = P()
        block = self.config.block_clients

        def shard_body(vparams, x, y, num_samples, weight):
            # Block the client axis exactly like the train path so peak
            # activation memory is bounded by block_clients * n_local, not
            # clients_per_device * n_local.
            c_local = x.shape[0]
            if c_local % block != 0:
                raise ValueError(
                    f"clients per device ({c_local}) must be a multiple of "
                    f"block_clients={block}; pad the dataset with "
                    f"ClientDataset.pad_for(plan, block=config.block_clients)"
                )
            nb = c_local // block

            def blocked(a):
                return a.reshape((nb, block) + a.shape[1:])

            def one(v, xc, yc, ns):
                # Metrics of record are precision-stable: eval always computes
                # in f32 regardless of the personal_dtype storage knob (the
                # train path casts to the global-param compute dtype the same
                # way).
                v = jax.tree.map(
                    lambda t: t.astype(jnp.float32)
                    if jnp.issubdtype(t.dtype, jnp.floating) else t,
                    v,
                )
                logits = self.apply_fn(v, xc)
                valid = (jnp.arange(xc.shape[0]) < ns)
                losses = optax.softmax_cross_entropy_with_integer_labels(logits, yc)
                correct = (logits.argmax(-1) == yc)
                denom = jnp.maximum(ns, 1).astype(jnp.float32)
                return (
                    jnp.where(valid, losses, 0.0).sum() / denom,
                    jnp.where(valid, correct, False).sum() / denom,
                )

            def block_step(carry, inp):
                sum_loss, sum_acc, sum_w = carry
                bvp, bx, by, bns, bw = inp
                loss_c, acc_c = jax.vmap(one)(bvp, bx, by, bns)
                return (
                    sum_loss + (bw * loss_c).sum(),
                    sum_acc + (bw * acc_c).sum(),
                    sum_w + bw.sum(),
                ), None

            init = _to_varying(
                (jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0)), "dp"
            )
            xs = (jax.tree.map(blocked, vparams), blocked(x), blocked(y),
                  blocked(num_samples), blocked(weight))
            (sum_loss, sum_acc, sum_w), _ = jax.lax.scan(block_step, init, xs)
            w_sum = jax.lax.psum(sum_w, "dp")
            loss = jax.lax.psum(sum_loss, "dp") / jnp.maximum(w_sum, 1e-8)
            acc = jax.lax.psum(sum_acc, "dp") / jnp.maximum(w_sum, 1e-8)
            return loss, acc

        def make(vp_tree):
            vp_spec = jax.tree.map(lambda _: cl, vp_tree)
            return jax.jit(
                jax.shard_map(
                    shard_body,
                    mesh=self.plan.mesh,
                    in_specs=(vp_spec, cl, cl, cl, cl),
                    out_specs=(rep, rep),
                    axis_names=frozenset({"dp"}),
                )
            )

        return make

    def evaluate_personal(self, personal: PersonalState, ds: ClientDataset) -> Tuple[float, float]:
        """Ditto's metric of record: each client's personalized model scored
        on its own local data (weight-averaged loss/accuracy)."""
        if self._evaluate_personal is None:
            self._evaluate_personal = self._build_evaluate_personal()(personal.params)
        loss, acc = self._evaluate_personal(
            personal.params, ds.x, ds.y, ds.num_samples, ds.weight
        )
        return float(loss), float(acc)

    def evaluate(self, params, x, y, stage=None) -> Tuple[float, float]:
        """Centralized eval of the global model, batched on device.
        ``stage`` — ``stage(name)`` gives a context manager entered around
        each batch's ``place`` (data to the device), ``compute`` (dispatch
        of the evaluate program) and ``fetch`` (the blocking read of loss
        and accuracy); the runner passes its span factory."""
        if stage is None:
            stage = lambda name: contextlib.nullcontext()  # noqa: E731
        bs = self.config.eval_batch_size
        n = x.shape[0]
        losses, accs, seen = [], [], 0
        for i in range(0, n, bs):
            with stage("place"):
                xb = jnp.asarray(x[i : i + bs])
                yb = jnp.asarray(y[i : i + bs])
            with stage("compute"):
                l, a = self._evaluate(params, xb, yb)
            with stage("fetch"):
                w = len(yb)
                losses.append(float(l) * w)
                accs.append(float(a) * w)
            seen += w
        return sum(losses) / seen, sum(accs) / seen


def _marked_lookup_tables(param_shapes, perturbation_shapes,
                          x) -> Optional[LookupTables]:
    """The lookup-only tables a model marks (``models/lookup.py``), read off
    its abstract evaluation on the input ``x``: every ``LOOKUP_ROWS``
    perturbation whose shape says it is the rows ``x`` itself looks up in
    the ``[rows, width]`` table beside it. ``None`` where the model marks
    nothing, or marks something else than a lookup by its integer input."""
    params = flatten_dict(param_shapes)
    tables = {path[:-1] + (TABLE,): rows
              for path, rows in flatten_dict(perturbation_shapes).items()
              if path[-1] == LOOKUP_ROWS}
    by_input = jnp.issubdtype(x.dtype, jnp.integer) and all(
        path in params and len(params[path].shape) == 2
        and rows.shape == x.shape + params[path].shape[1:]
        for path, rows in tables.items())
    if not tables or not by_input:
        return None
    return LookupTables(
        tuple(tables), sum(int(params[path].shape[0]) for path in tables))


def build_fedcore(
    model_name: str,
    algorithm: Algorithm,
    plan: MeshPlan,
    config: FedCoreConfig = FedCoreConfig(),
    model_overrides: Optional[dict] = None,
    input_shape: Optional[Tuple[int, ...]] = None,
    microbatches: Optional[int] = None,
) -> FedCore:
    """Convenience constructor from the model registry.

    ``microbatches`` — GPipe microbatch count for a pipeline-parallel
    plan (``plan.pp > 1``; default pp). Rejected on non-pp plans."""
    from olearning_sim_tpu.models import get_model
    from olearning_sim_tpu.models.registry import sown

    spec = get_model(model_name)
    model = spec.build(**(model_overrides or {}))
    in_shape = input_shape or spec.example_input_shape
    if microbatches is not None and plan.pp <= 1:
        raise ValueError(
            "microbatches only applies to pipeline parallelism — build "
            "the plan with make_mesh_plan(pp=...) (or the engine-params "
            "{'parallel': {'pp': N}} block)"
        )

    def _variables(params, rows):
        # ``rows``: the perturbation of a lookup-only table's looked-up rows
        # (models/lookup.py), passed by the by-rows local step alone;
        # without the collection the model's mark is a no-op.
        if rows is None:
            return {"params": params}
        return {"params": params, "perturbations": rows}

    def apply_fn(params, x, rows=None):
        return model.apply(_variables(params, rows), x)

    def init_params_fn(rng):
        dummy = jnp.zeros((1,) + in_shape, spec.input_dtype)
        return model.init(rng, dummy)["params"]

    # Models that sow an auxiliary loss (Switch-MoE load balancing) must not
    # lose it in the federated path: without mutable=["intermediates"] flax
    # silently drops the sow and the router trains with no balancing
    # pressure. Detect the sow by abstract evaluation and thread it into the
    # per-client loss as config.aux_loss_weight * sum(aux).
    def _apply_with_inter(params, x, rows=None):
        return model.apply(_variables(params, rows), x,
                           mutable=["intermediates"])

    apply_aux_fn = apply_stats_fn = describe_stats = lookup_tables = None
    shapes = None
    try:
        shapes = jax.eval_shape(init_params_fn, jax.random.key(0))
        dummy = jax.ShapeDtypeStruct((1,) + in_shape, spec.input_dtype)
        _, sown_shapes = jax.eval_shape(
            lambda p, x: model.apply(
                {"params": p}, x, mutable=["intermediates", "perturbations"]),
            shapes, dummy)
        inter_shapes = sown_shapes.get("intermediates", {})
        has_aux = bool(sown(inter_shapes, "aux_loss"))
        has_stats = (spec.work_counts is not None and jax.eval_shape(
            spec.work_counts.gather, inter_shapes) is not None)
        lookup_tables = _marked_lookup_tables(
            shapes, sown_shapes.get("perturbations", {}), dummy)
    except Exception:  # noqa: BLE001 — aux detection must never block a build
        has_aux = has_stats = False
    if has_stats and not has_aux:
        # The model's own work counts (``ModelSpec.work_counts``): one int32
        # array a forward pass; the round program sums them.
        describe_stats = spec.work_counts.describe

        def apply_stats_fn(params, x, rows=None):
            logits, inter = _apply_with_inter(params, x, rows)
            return logits, spec.work_counts.gather(inter)
    if has_aux:

        def apply_aux_fn(params, x, rows=None):
            logits, inter = _apply_with_inter(params, x, rows)
            leaves = sown(inter, "aux_loss")
            # MEAN over blocks, matching ep_train_step's aggregation, so the
            # same aux_loss_weight applies equal balancing pressure per
            # router in both training paths regardless of model depth.
            aux = sum(jnp.sum(a) for a in leaves) / len(leaves)
            return logits, aux

    param_specs = None
    if plan.mp > 1:
        # mp > 1 means the caller asked for tensor parallelism: derive the
        # Megatron-layout specs from the param shapes (transformer-block
        # tensors shard; everything else — and any model without such
        # blocks — stays replicated).
        from olearning_sim_tpu.parallel.tp import (
            sharded_fraction,
            tp_param_specs,
            warn_if_unsharded,
        )

        if shapes is None:  # aux detection failed before computing them
            shapes = jax.eval_shape(init_params_fn, jax.random.key(0))
        param_specs = tp_param_specs(shapes, plan.mp)
        warn_if_unsharded(shapes, param_specs, plan.mp, axis="mp")
        # Published per model so dashboards (and the tp-coverage analyzer)
        # can see how much of each family's parameter volume the mp axis
        # actually distributes.
        from olearning_sim_tpu.telemetry import instrument

        instrument("ols_engine_tp_sharded_ratio").labels(
            model=model_name
        ).set(sharded_fraction(shapes, param_specs))

    pp_train = None
    if plan.pp > 1:
        pp_train = (model, microbatches)

    return FedCore(apply_fn, init_params_fn, algorithm, plan, config,
                   param_specs=param_specs, apply_aux_fn=apply_aux_fn,
                   pp_train=pp_train, apply_stats_fn=apply_stats_fn,
                   describe_stats=describe_stats,
                   vmap_clients=spec.vmap_clients,
                   lookup_tables=lookup_tables)
