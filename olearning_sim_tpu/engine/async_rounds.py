"""Buffered asynchronous rounds: FedBuff-style staleness-weighted commits
compiled as ONE microbatch-scan program per round.

The synchronous engine (PR 3) closes every round at a deadline: stragglers
are *dropped* (their compute is spent, their update discarded) and the chip
idles from the K-th arrival until the round closes — zero utilization in
the tail (ROADMAP item 2, "the single biggest throughput lever"). This
module converts that tail into committed device-rounds:

- Clients are dispatched at round begin on the round's anchor model
  (version v0) and *arrive* in completion-time order (the pacing module's
  simulated arrivals — network release + device-class compute).
- Arrivals accumulate into a fixed-size buffer of ``buffer_size`` (M)
  updates; every M arrivals the server commits: the buffered deltas are
  aggregated with a staleness discount and the server optimizer steps.
  A client committing in window ``w`` has staleness ``s = w`` — exactly
  the number of server commits since its dispatch — so staleness is
  uniform within a buffer and rides as DATA (the window-assignment
  array), never a recompile.
- Staleness-weight schedules (FedBuff, Nguyen et al. 2022; Apodotiko,
  arxiv 2404.14033): ``constant`` (every commit full weight),
  ``polynomial`` (``(1+s)^-alpha``), and ``score`` (the polynomial
  discount times a per-client Apodotiko-style contribution score computed
  from the client's simulated speed). ``alpha`` / ``max_staleness`` /
  scores / window assignments are all data — per-round changes reuse the
  compiled program. Changing M (or the population) changes the compiled
  buffer capacity ``num_windows = ceil(C/M)`` and keys a new variant.

TPU-native shape: the whole asynchronous round — local training for every
selected client, per-window buffered aggregation, and ALL the sequential
server commits — is one jitted ``shard_map`` program. Local training runs
once over the population (every client anchors at v0, the FedBuff
dispatch model; the per-client train body is the same ``lax.scan`` over
local SGD steps the synchronous program uses), per-window weighted delta
sums are built with in-program ``segment_sum`` over the window-assignment
data, and a ``lax.scan`` over the W windows applies the
staleness-discounted server updates in arrival order. A crash therefore
always lands between *durably committed* rounds: the runner's checkpoint
holds the last committed server version and the commit clock rides
checkpoint meta, so a supervisor resume replays the identical commit
sequence bitwise (tests/test_async.py).

The defense pipeline composes per buffer: per-client L2 clipping runs in
the train scan exactly like the synchronous variant, while trimmed-mean /
median / Krum anomaly scores are computed per commit window over the
coordinate-sharded delta matrix (``defense.shard_client_deltas`` — the
same one-``all_to_all`` O(clients x params / dp) layout as PR 6), and the
cross-replica sharded server update (``FedCoreConfig.shard_server_update``)
keeps O(params/dp) optimizer state through the commit scan, stitching the
full params exactly once at round close.

The synchronous path is untouched: ``async_rounds`` only *adds* program
variants, and the async-off engine is byte-identical to the pre-async
build (regression-tested).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.sharding import PartitionSpec as P

from olearning_sim_tpu.engine import defense as defense_mod
from olearning_sim_tpu.engine.round_stages import (
    RoundMetrics,
    _flat_pad_leaf,
    _to_varying,
    _tree_where,
    client_block,
    next_state,
    server_commit,
)

SCHEDULES = ("constant", "polynomial", "score")

# Sentinel passed for a disabled max_staleness: every finite window index
# compares below it, so staleness dropping is bitwise off (same trick as
# the defense clip sentinel — a literal inf input would re-key the jit
# executable cache).
_NO_MAX_STALENESS = 3.0e38


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Knobs for buffered asynchronous rounds (engine params ``async``).

    ``buffer_size`` — M, the number of arrivals per server commit.
    ``max_staleness`` — commits beyond which a buffered update is dropped
    instead of committed (None disables; dropped clients are reported as
    ``stale_dropped``, distinct from deadline stragglers). ``schedule`` —
    staleness-weight schedule applied to each commit window:
    ``constant``, ``polynomial`` (``(1+s)^-staleness_alpha``), or
    ``score`` (polynomial discount x per-client Apodotiko-style speed
    score). ``staleness_alpha`` is data — per-round changes never
    recompile. ``speed_profiles`` / ``default_step_s`` / ``jitter`` feed
    the pacing completion-time model that orders arrivals (same semantics
    as DeadlineConfig's fields); a task may not configure ``deadline``
    and ``async`` together — ``max_staleness`` is the async engine's
    lateness control.
    """

    buffer_size: int = 64
    max_staleness: Optional[int] = None
    schedule: str = "polynomial"
    staleness_alpha: float = 0.5
    speed_profiles: Dict[str, float] = dataclasses.field(default_factory=dict)
    default_step_s: float = 0.1
    jitter: float = 0.0

    def __post_init__(self):
        if not isinstance(self.buffer_size, int) or self.buffer_size < 1:
            raise ValueError(
                f"async.buffer_size must be an int >= 1, got "
                f"{self.buffer_size!r}"
            )
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"async.schedule must be one of {SCHEDULES}, got "
                f"{self.schedule!r}"
            )
        if self.max_staleness is not None and (
            not isinstance(self.max_staleness, int) or self.max_staleness < 0
        ):
            raise ValueError(
                f"async.max_staleness must be an int >= 0 or null, got "
                f"{self.max_staleness!r}"
            )
        if self.staleness_alpha < 0.0:
            raise ValueError(
                f"async.staleness_alpha must be >= 0, got "
                f"{self.staleness_alpha}"
            )
        for fld in ("default_step_s", "jitter"):
            if getattr(self, fld) < 0:
                raise ValueError(f"async.{fld} must be >= 0")

    @classmethod
    def from_dict(cls, obj: Dict[str, Any]) -> "AsyncConfig":
        """Engine-params JSON shape::

            {"buffer_size": 64, "max_staleness": 8,
             "schedule": "polynomial", "staleness_alpha": 0.5,
             "speed_profiles": {"high": 0.05, "low": 0.4},
             "default_step_s": 0.1, "jitter": 0.1}
        """
        if not isinstance(obj, dict):
            raise TypeError(
                f"async config must be a JSON object, got "
                f"{type(obj).__name__}"
            )
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(obj) - known)
        if unknown:
            # A typo (bufer_size) must fail at submit time, not silently
            # run synchronous.
            raise ValueError(
                f"unknown async config keys: {unknown} "
                f"(known: {sorted(known)})"
            )
        kw: Dict[str, Any] = {}
        if obj.get("buffer_size") is not None:
            kw["buffer_size"] = int(obj["buffer_size"])
        if obj.get("max_staleness") is not None:
            kw["max_staleness"] = int(obj["max_staleness"])
        if obj.get("schedule") is not None:
            kw["schedule"] = str(obj["schedule"])
        for k in ("staleness_alpha", "default_step_s", "jitter"):
            if obj.get(k) is not None:
                kw[k] = float(obj[k])
        if "speed_profiles" in obj:
            kw["speed_profiles"] = {
                str(k): float(v) for k, v in obj["speed_profiles"].items()
            }
        return cls(**kw)

    def pacing_config(self):
        """The completion-time model as a DeadlineConfig (pacing's input
        type) — deadline-free, so only the arrival simulation applies."""
        from olearning_sim_tpu.engine.pacing import DeadlineConfig

        return DeadlineConfig(
            speed_profiles=dict(self.speed_profiles),
            default_step_s=self.default_step_s,
            jitter=self.jitter,
        )

    def num_windows(self, num_clients: int) -> int:
        """Compiled buffer capacity W for a (padded) population: the scan
        length of the commit loop. M keys the program variant through
        this value — two M values with equal W share the executable and
        differ purely in window-assignment data."""
        return max(1, int(math.ceil(num_clients / self.buffer_size)))


@dataclasses.dataclass
class AsyncRoundPlan:
    """One round's host-side async plan (the analogue of RoundPacing).

    ``window`` [C] int32 — each (padded) client's commit-window index in
    arrival order (-1 = not participating this round); ``score`` [C]
    float32 or None — per-client Apodotiko-style contribution scores
    (``schedule == "score"`` only); ``commit_time`` [W] float32 — the
    simulated time each window commits (its last member's arrival; inf
    for empty windows), the idle-accounting input; ``fill`` [W] int32 —
    arrivals per window (<= M; the tail window is usually partial).
    """

    config: AsyncConfig
    window: np.ndarray
    score: Optional[np.ndarray]
    num_windows: int
    commit_time: np.ndarray
    fill: np.ndarray

    @property
    def num_selected(self) -> int:
        return int((self.window >= 0).sum())

    def stale_dropped_mask(self) -> np.ndarray:
        """[C] bool — selected clients whose window exceeds max_staleness
        (their update is buffered but never committed)."""
        ms = self.config.max_staleness
        if ms is None:
            return np.zeros_like(self.window, bool)
        return (self.window >= 0) & (self.window > ms)

    def idle_seconds(self, completion: np.ndarray) -> float:
        """Simulated seconds committed updates spent waiting in the buffer
        (arrival -> their window's commit). The synchronous analogue —
        every on-time update waiting until round close — is what this
        engine drives toward ~0 (``ols_engine_idle_seconds_total``)."""
        real = min(len(completion), len(self.window))
        win = self.window[:real]
        committed = (win >= 0) & ~self.stale_dropped_mask()[:real]
        if not committed.any():
            return 0.0
        # Vectorized: this runs once per (population, round) and must stay
        # O(1) numpy passes — at million-client populations a Python
        # per-client loop is seconds of host work serialized against
        # device dispatch.
        ct = self.commit_time[win[committed]].astype(np.float64)
        comp = np.asarray(completion, np.float64)[committed]
        ok = np.isfinite(ct) & np.isfinite(comp)
        return float(np.clip(ct[ok] - comp[ok], 0.0, None).sum())


def plan_async_round(
    cfg: AsyncConfig,
    completion: np.ndarray,
    selected: np.ndarray,
    num_clients_padded: int,
) -> AsyncRoundPlan:
    """Assign commit windows in simulated-arrival order.

    ``completion`` [real] float32 simulated completion times
    (:func:`pacing.completion_times`); ``selected`` [real] bool — this
    round's participating clients. Deterministic: ties in completion time
    break by client index (``pacing.arrival_ranks``), which is what lets
    rollback/resume replay the identical commit sequence.
    """
    from olearning_sim_tpu.engine import pacing

    real = len(selected)
    if num_clients_padded < real:
        raise ValueError(
            f"padded population {num_clients_padded} smaller than the "
            f"{real} real clients in the selection mask"
        )
    ranks = pacing.arrival_ranks(completion, selected)
    window = np.full(num_clients_padded, -1, np.int32)
    window[:real] = np.where(
        ranks >= 0, ranks // cfg.buffer_size, -1
    ).astype(np.int32)
    num_windows = cfg.num_windows(num_clients_padded)

    # Per-window fill and commit time (latest finite member arrival)
    # without a Python loop over windows: O(C) numpy passes total, not
    # O(W·C) — the planning step is on the every-round hot path.
    win_r = window[:real]
    member = win_r >= 0
    fill = np.bincount(win_r[member], minlength=num_windows).astype(np.int32)
    commit_time = np.full(num_windows, np.inf, np.float32)
    ct = np.asarray(completion, np.float32)
    finite = member & np.isfinite(ct)
    if finite.any():
        latest = np.full(num_windows, -np.inf, np.float32)
        np.maximum.at(latest, win_r[finite], ct[finite])
        has = latest > -np.inf
        commit_time[has] = latest[has]

    score = None
    if cfg.schedule == "score":
        # Apodotiko-style contribution scores: faster clients (smaller
        # simulated completion) score higher. Normalized to mean 1 over
        # the selected cohort so the schedule reweights *within* the
        # buffer without changing the aggregate update magnitude.
        score = np.zeros(num_clients_padded, np.float32)
        sel = np.asarray(selected, bool)
        ct = np.asarray(completion, np.float32)
        pos = sel & np.isfinite(ct) & (ct > 0)
        if pos.any():
            inv = np.zeros(real, np.float32)
            inv[pos] = 1.0 / ct[pos]
            # A zero (or negative) finite completion is an instant
            # arrival: at least as fast as the fastest measured client —
            # it must land at the TOP of the score range, not fall
            # through to the floor. Non-finite completions (never
            # arrives) stay at inv=0 and clip to the floor, the slowest
            # score.
            inst = sel & np.isfinite(ct) & (ct <= 0)
            inv[inst] = inv[pos].max()
            scored = sel & np.isfinite(ct)
            mean = float(inv[scored].mean())
            if mean > 0:
                inv = inv / mean
            score[:real] = np.where(sel, np.clip(inv, 0.1, 10.0), 0.0)
        else:
            score[:real] = np.where(sel, 1.0, 0.0)

    return AsyncRoundPlan(
        config=cfg, window=window, score=score, num_windows=num_windows,
        commit_time=commit_time, fill=fill,
    )


def staleness_weights(schedule: str, alpha: float, num_windows: int,
                      max_staleness: Optional[int] = None) -> np.ndarray:
    """Numpy reference for the per-window staleness discount [W] — the
    oracle half of the in-jit computation (tests/test_async.py)."""
    w = np.arange(num_windows, dtype=np.float64)
    if schedule == "constant":
        sw = np.ones(num_windows)
    else:  # polynomial and score share the (1+s)^-alpha discount
        sw = (1.0 + w) ** (-float(alpha))
    if max_staleness is not None:
        sw = np.where(w > max_staleness, 0.0, sw)
    return sw.astype(np.float32)


def async_variant_key(num_windows: int, schedule: str, with_attack: bool,
                      defense) -> tuple:
    """The structural key of one async program variant (mirrors fedcore's
    ``(deadline, attack, defense)`` sync keys with an ``"async"`` tag):
    buffer capacity W and schedule are structure; every scalar knob
    (alpha, max_staleness, scores, window data) is data."""
    return ("async", int(num_windows), schedule, bool(with_attack),
            defense.structure_key if defense is not None else None)


class AsyncStats(struct.PyTreeNode):
    """Per-round async accounting exiting the compiled program.

    ``commits`` — windows that actually committed (non-empty, not
    staleness-dropped); ``committed_weight`` — total aggregation
    weight across committed windows; ``dropped_stale`` — participants
    whose window exceeded ``max_staleness`` (compute spent, update
    discarded — the async analogue of stragglers); ``buffer_fill`` —
    [W] per-window aggregation weight (the buffer-depth signal)."""

    commits: Any
    committed_weight: Any
    dropped_stale: Any
    buffer_fill: Any


# --------------------------------------------------------------- program
def build_async_round_step(core, num_windows: int, schedule: str,
                           with_attack: bool = False, defense=None):
    """Build the compiled buffered-async round program for one FedCore.

    Returns a jitted ``fn(state, x, y, num_samples, num_steps, uid,
    weight, commit_window, score, stale_alpha, max_staleness, [attack],
    [clip, trim]) -> (state, RoundMetrics, AsyncStats)``. ``score`` is
    a replicated zero scalar except under the ``score`` schedule, where
    it is the per-client [C] Apodotiko score array.
    """
    plan = core.plan
    cfg = core.config
    alg = core.algorithm
    mesh = plan.mesh
    dpn = plan.dp
    W = int(num_windows)
    shard_update = cfg.shard_server_update
    with_score = schedule == "score"
    defense_gather = defense is not None and defense.gathers_deltas
    defense_score = defense is not None and defense.score_enabled
    aggregator = defense.aggregator if defense is not None else "mean"
    robust_agg = aggregator in ("trimmed_mean", "median")
    trace_key = async_variant_key(W, schedule, with_attack, defense)
    if alg.personalized or alg.control_variates:
        raise ValueError(
            f"asynchronous buffered rounds do not support the "
            f"personalized/control-variate algorithm {alg.name!r} (per-"
            f"client state would need a version per commit window)"
        )

    def shard_body(params, opt_state, round_idx, base_key,
                   x, y, num_samples, num_steps, uid, weight,
                   window, score, stale_alpha, max_stale, *extras):
        # Trace-time probe (see fedcore: the no-retrace regression guard).
        core.trace_counts[trace_key] = core.trace_counts.get(trace_key, 0) + 1
        extras = list(extras)
        attack_scale = clip_norm = trim_fraction = None
        if with_attack:
            attack_scale = extras.pop(0)
        if defense is not None:
            clip_norm, trim_fraction = extras[0], extras[1]
            del extras[:2]
        c_local = x.shape[0]
        if c_local % cfg.block_clients != 0:
            raise ValueError(
                f"per-device client count {c_local} must be a multiple of "
                f"block_clients={cfg.block_clients}; pad the dataset with "
                f"ClientDataset.pad_for(plan, block=config.block_clients)"
            )
        nb = c_local // cfg.block_clients

        # Per-window staleness discount [W]: uniform within a window
        # (staleness == window index == commits since dispatch), so the
        # schedule is a vector over windows, entirely data-driven.
        widx = jnp.arange(W, dtype=jnp.float32)
        if schedule == "constant":
            sw_w = jnp.ones((W,), jnp.float32)
        else:
            sw_w = jnp.power(1.0 + widx, -stale_alpha)
        sw_w = jnp.where(widx <= max_stale, sw_w, 0.0)

        member = window >= 0
        stale_ok = jnp.logical_and(
            member, window.astype(jnp.float32) <= max_stale
        )
        # Dropped-for-staleness participants (compute spent, update never
        # committed) — the async analogue of deadline stragglers.
        dropped_stale = jax.lax.psum(
            jnp.logical_and(
                jnp.logical_and(weight > 0, member),
                jnp.logical_not(stale_ok),
            ).sum().astype(jnp.float32),
            "dp",
        )
        weight = jnp.where(stale_ok, weight, 0.0)
        wclamp = jnp.clip(window, 0, W - 1)

        def blocked(a):
            return a.reshape((nb, cfg.block_clients) + a.shape[1:])

        xs = (blocked(x), blocked(y), blocked(num_samples),
              blocked(num_steps), blocked(uid), blocked(weight),
              blocked(wclamp),
              blocked(score) if with_score else None,
              blocked(attack_scale) if with_attack else None)

        # The in-jit accumulation buffer only exists on the streaming
        # (weighted-mean) path: the gathering defense aggregators emit
        # per-client deltas from the scan instead, and carrying a dead
        # W x params buffer through it would waste that much HBM per
        # device for the whole round program.
        init = (jnp.zeros((W,), jnp.float32),
                jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0))
        if not defense_gather:
            zero_buf = jax.tree.map(
                lambda p: jnp.zeros((W,) + p.shape, jnp.float32), params
            )
            init = (zero_buf,) + init
        if defense is not None:
            init = init + (jnp.float32(0.0),)
        init = _to_varying(init, "dp")

        def _unpack(carry):
            rest = list(carry)
            buf = None if defense_gather else rest.pop(0)
            buf_w, sum_loss, sum_w, count = rest[:4]
            n_clip = rest[4] if defense is not None else None
            return buf, buf_w, sum_loss, sum_w, count, n_clip

        def _pack(buf, buf_w, sum_loss, sum_w, count, n_clip):
            carry = (buf_w, sum_loss, sum_w, count)
            if not defense_gather:
                carry = (buf,) + carry
            if defense is not None:
                carry = carry + (n_clip,)
            return carry

        def block_step(carry, inp):
            buf, buf_w, sum_loss, sum_w, count, n_clip = _unpack(carry)
            bx, by, bns, bst, buid, bw, bwin, bscore, batk = inp
            # The buffered stage always gates in float32: the window buffer
            # takes every client's delta, not a leaf-by-leaf weighted sum.
            blk = client_block(
                core._local_train, (None, 0, 0, 0, 0, 0, None, None),
                (params, bx, by, bns, bst, buid, base_key, round_idx), bw,
                vmap_clients=core.vmap_clients, attack_scale=batk,
                clip_norm=clip_norm, f32=True,
            )
            d32, bw_eff = blk.d32, blk.bw_eff
            defense_ys = None
            if defense is not None:
                n_clip = n_clip + blk.clipped
            if with_score:
                # Apodotiko contribution scores reweight clients inside
                # their buffer (the polynomial staleness discount applies
                # per window at commit time).
                with jax.named_scope("delta_transform"):
                    d32 = jax.tree.map(
                        lambda d: d * bscore.reshape(
                            (-1,) + (1,) * (d.ndim - 1)
                        ),
                        d32,
                    )
            with jax.named_scope("aggregate"):
                if defense_gather:
                    defense_ys = (d32, bw_eff)
                else:
                    # Buffered accumulation: each client's weighted delta
                    # lands in its commit window's slot (segment_sum over the
                    # window-assignment data — zero-weight rows are inert).
                    buf = jax.tree.map(
                        lambda b, d: b + jax.ops.segment_sum(
                            bw_eff.reshape((-1,) + (1,) * (d.ndim - 1)) * d,
                            bwin, num_segments=W,
                        ),
                        buf, d32,
                    )
                buf_w = buf_w + jax.ops.segment_sum(
                    bw_eff, bwin, num_segments=W
                )
                sum_w, sum_loss, count = blk.tally(sum_w, sum_loss, count,
                                                   loss_first=True)
            return (_pack(buf, buf_w, sum_loss, sum_w, count, n_clip),
                    (blk.losses, defense_ys))

        carry, (block_losses, defense_out) = jax.lax.scan(
            block_step, init, xs, unroll=min(cfg.block_unroll, nb)
        )
        buf, buf_w, sum_loss, sum_w, count, n_clip = _unpack(carry)
        if n_clip is None:
            n_clip = jnp.float32(0.0)
        client_loss = block_losses.reshape((c_local,))

        with jax.named_scope("aggregate"):
            buf_w = jax.lax.psum(buf_w, "dp")
            sum_loss = jax.lax.psum(sum_loss, "dp")
            sum_w = jax.lax.psum(sum_w, "dp")
            count = jax.lax.psum(count, "dp")
            if defense is not None:
                n_clip = jax.lax.psum(n_clip, "dp")

            anomaly_score = jnp.float32(0.0)
            # Per-window PRE-NORMALIZED aggregates feeding the commit scan:
            # ``delta_stack`` replicated [W, *param] leaves, or
            # ``delta_shard_stack`` [W, D_pad/dp] leaves under the sharded
            # server update. Robust aggregates are already normalized
            # statistics; the weighted-mean path divides by the window's
            # aggregation weight here.
            delta_stack = delta_shard_stack = None
            if defense_gather:
                d_pc, w_pc = defense_out
                w_flat = w_pc.reshape((c_local,))
                w_all = jax.lax.all_gather(w_flat, "dp", tiled=True)
                win_all = jax.lax.all_gather(
                    wclamp.reshape((c_local,)), "dp", tiled=True
                )
                shards = jax.tree.map(
                    lambda a: defense_mod.shard_client_deltas(
                        a.reshape((c_local,) + a.shape[2:]), "dp", dpn
                    ),
                    d_pc,
                )
                shard_leaves = jax.tree.leaves(shards)
                treedef = jax.tree.structure(shards)

                def win_scan(scores_acc, w):
                    mask_w = (win_all == w) & (w_all > 0)
                    center = [
                        defense_mod.robust_leaf_aggregate(
                            s, mask_w,
                            aggregator if robust_agg else "median",
                            trim_fraction,
                        )
                        for s in shard_leaves
                    ]
                    if defense_score:
                        partial = functools.reduce(
                            jnp.add,
                            [defense_mod.partial_distance_sq(s, c)
                             for s, c in zip(shard_leaves, center)],
                        )
                        scores_w = jnp.where(
                            mask_w, jnp.sqrt(jax.lax.psum(partial, "dp")), 0.0
                        )
                        scores_acc = jnp.where(mask_w, scores_w, scores_acc)
                    return scores_acc, (tuple(center) if robust_agg else ())

                # The scores follow the gathered masks, which all_gather types
                # device-varying; the carry has to start out typed the same.
                scores_all, win_aggs = jax.lax.scan(
                    win_scan,
                    _to_varying(jnp.zeros((c_local * dpn,), jnp.float32), "dp"),
                    jnp.arange(W, dtype=jnp.int32),
                )
                if defense_score:
                    anomaly_score = jax.lax.dynamic_slice(
                        scores_all, (jax.lax.axis_index("dp") * c_local,),
                        (c_local,),
                    )
                if robust_agg:
                    delta_shard_stack = jax.tree.unflatten(
                        treedef, list(win_aggs)
                    )
                    if not shard_update:
                        delta_stack = jax.tree.map(
                            lambda s, p: jax.vmap(
                                lambda sh: defense_mod.place_coordinate_shard(
                                    sh, "dp", dpn, p.shape
                                )
                            )(s),
                            delta_shard_stack, params,
                        )
                        delta_shard_stack = None
                else:
                    # Score-only defense keeps the weighted-mean aggregate:
                    # rebuild the (device-local) window buffer from the
                    # gathered clipped deltas so scoring composes with the
                    # streaming aggregation below (which does the psum).
                    buf = jax.tree.map(
                        lambda a, p: jax.ops.segment_sum(
                            w_flat[:, None] * a.reshape((c_local, -1)),
                            wclamp, num_segments=W,
                        ).reshape((W,) + p.shape),
                        d_pc, params,
                    )

            if delta_stack is None and delta_shard_stack is None:
                # Weighted-mean path: normalize each window by its weight.
                def normalize(b):
                    shape = (W,) + (1,) * (b.ndim - 1)
                    return b / jnp.maximum(buf_w, 1e-8).reshape(shape)

                if shard_update:
                    # psum_scatter both reduces the device-local partial sums
                    # over dp AND scatters the coordinates in one collective.
                    delta_shard_stack = jax.tree.map(
                        lambda b: jax.lax.psum_scatter(
                            jax.vmap(lambda l: _flat_pad_leaf(l, dpn))(b),
                            "dp", scatter_dimension=1, tiled=True,
                        ) / jnp.maximum(buf_w, 1e-8)[:, None],
                        buf,
                    )
                else:
                    delta_stack = jax.tree.map(
                        lambda b: normalize(jax.lax.psum(b, "dp")), buf
                    )

        with jax.named_scope("server_update"):
            # -------------------------------------------------- commit scan
            # Sequential staleness-discounted server commits, one per window,
            # in arrival order. Empty (or fully stale) windows are bitwise
            # no-ops via tree_where.
            def commit(carry, inp):
                p, op = carry
                d_w, w_w, sw = inp
                gate = (w_w > 0) & (sw > 0)
                committed = server_commit(alg.server_optimizer, p, op, d_w,
                                          scale=sw)
                p, op = _tree_where(gate, committed, (p, op))
                return (p, op), gate.astype(jnp.float32)

            if shard_update:
                def my_shard(p):
                    flat = _flat_pad_leaf(p, dpn)
                    s = flat.shape[0] // dpn
                    return jax.lax.dynamic_slice(
                        flat, (jax.lax.axis_index("dp") * s,), (s,)
                    )

                shard_params0 = jax.tree.map(my_shard, params)
                opt_in = jax.tree.map(
                    lambda l, sharded: l if sharded else _to_varying(l, "dp"),
                    opt_state, core._opt_sharded,
                )
                (shard_params, new_opt_state), gates = jax.lax.scan(
                    commit, (shard_params0, opt_in),
                    (delta_shard_stack, buf_w, sw_w),
                )
                new_opt_state = jax.tree.map(
                    lambda l, sharded: l if sharded else jax.lax.pmax(l, "dp"),
                    new_opt_state, core._opt_sharded,
                )
                new_params = jax.tree.map(
                    lambda s, p: defense_mod.place_coordinate_shard(
                        s, "dp", dpn, p.shape
                    ),
                    shard_params, params,
                )
            else:
                (new_params, new_opt_state), gates = jax.lax.scan(
                    commit, (params, opt_state), (delta_stack, buf_w, sw_w),
                )

        metrics = RoundMetrics(
            mean_loss=sum_loss / jnp.maximum(sum_w, 1e-8),
            weight_sum=sum_w,
            clients_trained=count,
            client_loss=client_loss,
            personal_loss=jnp.float32(0.0),
            stragglers=jnp.float32(0.0),
            anomaly_score=anomaly_score,
            clipped=n_clip,
        )
        stats = AsyncStats(
            commits=gates.sum(),
            committed_weight=(buf_w * (sw_w > 0)).sum(),
            dropped_stale=dropped_stale,
            buffer_fill=buf_w,
        )
        return (new_params, new_opt_state, round_idx + 1, metrics, stats)

    rep = P()
    cl = P("dp")
    metrics_specs = RoundMetrics(
        mean_loss=rep, weight_sum=rep, clients_trained=rep, client_loss=cl,
        personal_loss=rep, stragglers=rep,
        anomaly_score=cl if defense_score else rep, clipped=rep,
        model_stats=rep,
    )
    stats_specs = AsyncStats(
        commits=rep, committed_weight=rep, dropped_stale=rep,
        buffer_fill=rep,
    )
    async_specs = (cl, cl if with_score else rep, rep, rep)
    attack_specs = (cl,) if with_attack else ()
    defense_specs = (rep, rep) if defense is not None else ()
    opt_spec = core._opt_spec if shard_update else rep

    shard_fn = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(rep, opt_spec, rep, rep, cl, cl, cl, cl, cl, cl)
        + async_specs + attack_specs + defense_specs,
        out_specs=(rep, opt_spec, rep, metrics_specs, stats_specs),
        axis_names=frozenset({"dp"}),
    )

    @functools.partial(jax.jit, donate_argnums=(0,))
    def async_round_step(state, x, y, num_samples, num_steps, uid, weight,
                         window, score, stale_alpha, max_stale, *extras):
        *new, metrics, stats = shard_fn(
            state.params, state.opt_state, state.round_idx, state.base_key,
            x, y, num_samples, num_steps, uid, weight,
            window, score, stale_alpha, max_stale, *extras,
        )
        return next_state(state, *new), metrics, stats

    return async_round_step
