"""Benchmarks of record (BASELINE.md). Runs on the TPU, in ONE process.

Headline: FL rounds/sec simulating 10k clients, 4-layer CNN on CIFAR-10
shapes (BASELINE: >=500 rounds/min over 10k clients on a v4-32).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

``vs_baseline`` is measured per-chip rounds/sec divided by the reference
target's per-chip rounds/sec. Per-chip math, stated explicitly: a v4-32 is
32 TensorCores = **16 chips** (2 cores/chip), so the target pro-rates to
500/60/16 = 0.521 rounds/sec per chip; >1.0 means beating the v4-32 target
chip-for-chip (ignoring that v4 has ~1.4x the bf16 peak of the v5e this
runs on — the conservative direction).

Then the breadth suite (``SUITE_FAMILIES``: the five BASELINE task families
at 1k clients plus the deadline and defense variants) runs in the same
process and lands in ``BENCH_suite.json``. ``OLS_BENCH_FAST=1`` runs the
headline only.

The script measures the chip and nothing else: when JAX finds no TPU it
exits non-zero before measuring, no family's shape depends on the backend,
and a family that raises ends the run with a non-zero exit. The chip
belongs to one process, so every mode runs its families in-process (no
children): ``--chips N`` runs on a mesh over the first N devices
(per-chip normalization reads the mesh size, not the host's device
count); ``--multichip`` banks the chips={1,2,4,8} plain+defended scaling
family, up to the host's chip count, into ``BENCH_multichip.json``;
``--modelparallel`` banks the large-model tensor-parallel mp={1,2,4} rows
(distilbert/vit_tiny/resnet18) into ``BENCH_modelparallel.json``;
``--async`` banks the buffered-async vs sync-deadline pair (committed
device-rounds/sec at straggler-heavy pacing) plus the 2-task multiplex
record into ``BENCH_async.json``; ``--trace`` banks the million-client
trace-driven scenario family (lazy host store + block-streamed rounds
under diurnal/spike/churn availability masks) into ``BENCH_trace.json``;
``--convergence`` banks the time-to-accuracy grid (rounds/seconds-to-
target-accuracy per (family x engine-config): sync vs async,
attacked+defended vs undefended, clean vs drift, resident vs streamed)
into ``BENCH_convergence.json``. Every record names the device it ran on
and carries the persistent compile cache's hit/miss counters
(engine/compile_cache.py).
"""

import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from olearning_sim_tpu.engine import (
    build_fedcore,
    ditto,
    fedadam,
    fedavg,
    fedprox,
    make_synthetic_dataset,
)
from olearning_sim_tpu.engine.client_data import make_synthetic_text_dataset
from olearning_sim_tpu.engine.compile_cache import (
    cache_stats,
    enable_compile_cache,
)
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

V4_32_CHIPS = 16  # 32 TensorCores / 2 cores per chip
BASELINE_ROUNDS_PER_SEC_PER_CHIP = 500.0 / 60.0 / V4_32_CHIPS


def run_family(plan, *, name, model, algorithm, num_clients, n_local,
               input_shape=None, text=False, num_classes=10, batch=32,
               local_steps=10, block=256, timed_rounds=3, unroll=1,
               block_unroll=1, carry=None, model_overrides=None,
               vocab_size=None, seq_len=None, deadline_frac=None,
               attack_frac=None, defense=None, shard_server=False,
               straggler_spike=None, async_buffer=None,
               async_schedule="polynomial", microbatches=None):
    """One benchmark family: build, warm, time. Returns the record dict.

    ``carry``: "bf16" runs local SGD with a bfloat16 params carry (halves
    the per-step carry bytes; parity-gated by test_bf16_carry_parity).
    ``OLS_BENCH_CARRY=bf16`` applies it to every family via main().

    ``deadline_frac``: run the deadline-masked round-step variant with a
    seeded synthetic completion-time array placed so that roughly this
    fraction of clients straggle past the deadline — measures the in-jit
    deadline masking overhead against the same family without it.

    ``attack_frac`` / ``defense``: run the adversarial-defense round-step
    variant — ``attack_frac`` of the clients ship sign-flipped deltas
    (seeded, in-jit) and ``defense`` (a DefenseConfig.from_dict dict)
    enables clipping / robust aggregation / anomaly scoring. The delta vs
    the same family without them is the in-jit robust-aggregation
    overhead.

    ``shard_server``: run with the cross-replica sharded server update
    (FedCoreConfig.shard_server_update — O(params/dp) optimizer state;
    the chips-scaling family's configuration).

    ``straggler_spike``: ``(frac, factor)`` — seeded straggler-heavy
    completion times (p95 >> median): that fraction of the real clients
    takes ``factor`` x the fast cohort's simulated time. Without
    ``async_buffer`` this runs the synchronous deadline-masked baseline
    (round closes at the fast cohort's tail; stragglers DROPPED in-jit).
    With ``async_buffer`` (= M) the buffered asynchronous program commits
    every M arrivals with ``async_schedule`` staleness weights instead —
    the same compute commits the stragglers rather than discarding them.
    The sync-vs-async pair on identical completion times is the
    BENCH_async.json headline (committed device-rounds/sec).

    The record's ``chips`` is the MESH size actually used (``--chips``
    subdivides the host), not the host's device count.
    """
    import jax.numpy as jnp

    if deadline_frac is not None and straggler_spike is not None:
        raise ValueError(
            "deadline_frac and straggler_spike are mutually exclusive "
            "pacing knobs: straggler_spike builds its own completion/"
            "deadline (sync) or async plan and would silently replace "
            "the deadline_frac pacing while the record still claimed it"
        )
    if async_buffer is not None and straggler_spike is None:
        raise ValueError(
            "async_buffer requires straggler_spike pacing (the async "
            "plan is built from its simulated arrivals); without it the "
            "family would silently run synchronously"
        )
    carry_dtype = jnp.bfloat16 if carry == "bf16" else None
    cfg = FedCoreConfig(batch_size=batch, max_local_steps=local_steps,
                        block_clients=block, step_unroll=unroll,
                        block_unroll=block_unroll, carry_dtype=carry_dtype,
                        shard_server_update=bool(shard_server))
    core = build_fedcore(model, algorithm, plan, cfg,
                         model_overrides=model_overrides,
                         input_shape=input_shape,
                         microbatches=microbatches)
    if text:
        ds = make_synthetic_text_dataset(
            seed=0, num_clients=num_clients, n_local=n_local,
            seq_len=seq_len, num_classes=num_classes, vocab_size=vocab_size,
            dirichlet_alpha=0.5,
        )
    else:
        ds = make_synthetic_dataset(
            seed=0, num_clients=num_clients, n_local=n_local,
            input_shape=input_shape, num_classes=num_classes,
            dirichlet_alpha=0.5,
        )
    ds = ds.pad_for(plan, block).place(plan)
    state = core.init_state(jax.random.key(0))
    personal = (core.init_personal(state, ds.num_clients)
                if core.algorithm.personalized else None)

    pace_kwargs = {}
    if deadline_frac is not None:
        # Seeded synthetic completion times in [0, 1) simulated seconds; the
        # deadline sits at the (1 - deadline_frac) quantile so ~that
        # fraction of clients is masked out in-jit each round.
        from olearning_sim_tpu.parallel.mesh import global_put

        comp = np.random.default_rng(0).random(ds.num_clients).astype(np.float32)
        pace_kwargs = dict(
            completion_time=global_put(comp, plan.client_sharding()),
            deadline=float(np.quantile(comp, 1.0 - float(deadline_frac))),
        )
    astats = None
    if straggler_spike is not None:
        # Straggler-heavy pacing: the fast cohort finishes inside 1.0
        # simulated second; ``frac`` of the real population takes
        # ``factor`` x that (p95 >> median). Seeded — the sync and async
        # entries of the pair see the IDENTICAL arrival process.
        from olearning_sim_tpu.parallel.mesh import global_put

        frac, factor = float(straggler_spike[0]), float(straggler_spike[1])
        real = ds.num_real_clients
        rng = np.random.default_rng(2)
        comp = (0.2 + 0.8 * rng.random(ds.num_clients)).astype(np.float32)
        slow = rng.choice(real, size=max(1, int(frac * real)), replace=False)
        comp[slow] *= factor
        if async_buffer is None:
            # Synchronous deadline-masked baseline: the round closes at
            # the fast cohort's tail, so every spiked client's update is
            # computed and then discarded in-jit (PR 3 semantics).
            pace_kwargs = dict(
                completion_time=global_put(comp, plan.client_sharding()),
                deadline=1.0,
            )
        else:
            from olearning_sim_tpu.engine.async_rounds import (
                AsyncConfig,
                plan_async_round,
            )

            acfg = AsyncConfig(buffer_size=int(async_buffer),
                               schedule=async_schedule)
            pace_kwargs["async_plan"] = plan_async_round(
                acfg, comp[:real], np.ones(real, bool), ds.num_clients
            )
    if attack_frac is not None:
        # Seeded sign-flip attack on ~attack_frac of the REAL population
        # (padding clients have zero weight — drawing them would dilute
        # the nominal fraction), applied to the deltas inside the
        # compiled program.
        from olearning_sim_tpu.parallel.mesh import global_put

        real = ds.num_real_clients
        scale = np.ones(ds.num_clients, np.float32)
        k = max(1, int(float(attack_frac) * real))
        idx = np.random.default_rng(1).choice(real, size=k, replace=False)
        scale[idx] = -1.0
        pace_kwargs["attack_scale"] = global_put(
            scale, plan.client_sharding()
        )
    if defense is not None:
        from olearning_sim_tpu.engine.defense import DefenseConfig

        defense = DefenseConfig.from_dict(dict(defense))
        pace_kwargs["defense"] = defense

    def step():
        nonlocal state, personal, astats
        if personal is not None:
            out = core.round_step(state, ds, personal=personal,
                                  **pace_kwargs)
            state, metrics, personal = out
        elif "async_plan" in pace_kwargs:
            state, metrics, astats = core.round_step(state, ds,
                                                     **pace_kwargs)
        else:
            state, metrics = core.round_step(state, ds, **pace_kwargs)
        return metrics

    # Warmup (compile + 1 round), ended by a host read of the result.
    t0 = time.perf_counter()
    metrics = step()
    float(metrics.mean_loss)
    compile_s = time.perf_counter() - t0

    times = []
    for _ in range(timed_rounds):
        t0 = time.perf_counter()
        metrics = step()
        loss = float(metrics.mean_loss)
        times.append(time.perf_counter() - t0)
    times = np.asarray(times)
    rps = 1.0 / times.mean()
    step_lat = times / (num_clients * local_steps)  # per client local step
    return {
        "family": name,
        "backend": jax.default_backend(),
        # The mesh the family actually ran on (per-chip normalization and
        # the chips-scaling curves read this), NOT len(jax.devices()) —
        # --chips subdivides the host.
        "chips": plan.n_devices,
        "carry": carry or "f32",
        "clients": num_clients,
        "local_steps": local_steps,
        "timed_rounds": timed_rounds,
        "rounds_per_sec": round(float(rps), 4),
        "device_rounds_per_sec": round(float(rps * num_clients), 1),
        "round_time_sec": round(float(times.mean()), 4),
        "client_step_latency_us_p50": round(float(np.percentile(step_lat, 50) * 1e6), 3),
        "client_step_latency_us_p90": round(float(np.percentile(step_lat, 90) * 1e6), 3),
        "compile_sec": round(compile_s, 1),
        "mean_loss": loss,
        **({"deadline_frac": float(deadline_frac),
            "stragglers": int(metrics.stragglers)}
           if deadline_frac is not None else {}),
        # Committed device-rounds/sec is the async headline's currency:
        # clients_trained counts only clients whose update actually
        # entered the server model (deadline masking zeroes straggler
        # weights BEFORE the count; the async program counts committed
        # buffer members), so one formula is honest for both modes.
        **({"straggler_spike": {"frac": float(straggler_spike[0]),
                                "factor": float(straggler_spike[1])},
            "committed_clients": int(metrics.clients_trained),
            "committed_device_rounds_per_sec": round(
                float(rps * int(metrics.clients_trained)), 1),
            "mode": "sync_deadline" if async_buffer is None else "async"}
           if straggler_spike is not None else {}),
        **({"async": {"buffer_size": int(async_buffer),
                      "schedule": async_schedule,
                      "windows": int(astats.buffer_fill.shape[0]),
                      "commits": int(astats.commits),
                      "stale_dropped": int(astats.dropped_stale)}}
           if astats is not None else {}),
        **({"attack_frac": float(attack_frac)}
           if attack_frac is not None else {}),
        **({"defense": defense.aggregator,
            "clipped": int(metrics.clipped)}
           if defense is not None else {}),
        **({"shard_server": True} if shard_server else {}),
        # Model-parallel provenance: the mesh's model axes, when present
        # (BENCH_modelparallel.json's scaling curves key on these).
        **({"mp": plan.mp} if plan.mp > 1 else {}),
        **({"pp": plan.pp,
            "microbatches": int(microbatches or plan.pp)}
           if plan.pp > 1 else {}),
    }


def require_tpu():
    """The device every record of this run names. This script measures the
    chip: without a TPU backend it fails here, before anything is built —
    a CPU timing is never written under a device metric's name."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"bench.py: JAX backend is {backend!r}, not tpu; nothing "
            f"measured"
        )
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices)}


HEADLINE_FAMILY = dict(
    name="fedavg_cifar10_cnn4_10k", model="cnn4",
    algorithm=("fedavg", dict(local_lr=0.05)), num_clients=10_000,
    n_local=20, input_shape=(32, 32, 3), num_classes=10, batch=32,
    local_steps=10, block=16, unroll=10, timed_rounds=3,
)


def main(chips=None):
    device = require_tpu()
    enable_compile_cache()
    fast = os.environ.get("OLS_BENCH_FAST") == "1"
    carry = ({"carry": "bf16"}
             if os.environ.get("OLS_BENCH_CARRY") == "bf16" else {})
    plan = _plan_for_chips(chips)

    headline = run_one_inprocess(plan, {**HEADLINE_FAMILY, **carry})
    headline["device"] = device
    rps = headline["rounds_per_sec"]
    per_chip = rps / headline["chips"]
    # The headline line goes out BEFORE the breadth suite runs: a suite
    # failure must not cost the already-measured metric of record.
    print(json.dumps({
        "metric": (
            f"FL rounds/sec, {headline['clients']} clients x "
            f"{headline['local_steps']} local steps, cnn4/CIFAR-10 shapes"
        ),
        "value": rps,
        "unit": "rounds/sec",
        "vs_baseline": round(per_chip / BASELINE_ROUNDS_PER_SEC_PER_CHIP, 4),
        "detail": {
            "chips": headline["chips"],
            "device": device,
            "baseline_chips_v4_32": V4_32_CHIPS,
            "baseline_rounds_per_sec_per_chip": round(
                BASELINE_ROUNDS_PER_SEC_PER_CHIP, 4
            ),
            "headline": headline,
            "suite_file": None if fast else "BENCH_suite.json",
            "resilience": headline["resilience"],
        },
    }), flush=True)

    if not fast:
        suite = [headline]
        for fam in SUITE_FAMILIES:
            record = run_one_inprocess(plan, {**fam, **carry})
            record["device"] = device
            suite.append(record)
            _bank(suite, "BENCH_suite.json")  # keep what is measured so far
    # The smoke config still flushes the registry (the overhead comparison
    # vs OLS_TELEMETRY=0 reads this artifact).
    _dump_telemetry()


def _dump_telemetry():
    """Flush the live metrics registry as a bench artifact (counters,
    gauges, per-phase histograms from in-process runs)."""
    from olearning_sim_tpu.telemetry import dump_json

    dump_json(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_metrics.json"
    ))


def _bank(obj, path_or_name):
    """Atomically bank a benchmark artifact (tmp write -> os.replace).

    Relative names resolve next to bench.py — the checked-in location
    the acceptance records and docs read. Returns the final path."""
    path = path_or_name
    if not os.path.isabs(path):
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), path
        )
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=1)
    os.replace(tmp, path)
    return path


# Breadth suite (algorithms by name so a family is plain data).
SUITE_FAMILIES = [
    dict(name="fedavg_mnist_mlp_1k", model="mlp2",
         algorithm=("fedavg", dict(local_lr=0.05)), num_clients=1000,
         n_local=20, input_shape=(28, 28, 1), block=64, unroll=10, batch=32,
         local_steps=10, timed_rounds=2),
    dict(name="fedavg_cifar10_cnn4_1k", model="cnn4",
         algorithm=("fedavg", dict(local_lr=0.05)), num_clients=1000,
         n_local=20, input_shape=(32, 32, 3), block=16, unroll=10, batch=32,
         local_steps=10, timed_rounds=2),
    # Deadline-masked variant of the mlp family: same work, 20% of clients
    # straggling past the round deadline — the delta vs fedavg_mnist_mlp_1k
    # is the in-jit masking + straggler-count overhead (should be noise).
    dict(name="fedavg_mnist_mlp_1k_deadline", model="mlp2",
         algorithm=("fedavg", dict(local_lr=0.05)), num_clients=1000,
         n_local=20, input_shape=(28, 28, 1), block=64, unroll=10, batch=32,
         local_steps=10, timed_rounds=2, deadline_frac=0.2),
    # Adversarial-defense variant of the mlp family: 10% of clients ship
    # sign-flipped deltas; the defense clips, aggregates by coordinate-wise
    # trimmed mean, and scores anomalies in-jit. The delta vs
    # fedavg_mnist_mlp_1k is the robust-aggregation overhead (the gather +
    # per-coordinate sorts — the one defense path that is NOT free).
    dict(name="fedavg_mnist_mlp_1k_defense", model="mlp2",
         algorithm=("fedavg", dict(local_lr=0.05)), num_clients=1000,
         n_local=20, input_shape=(28, 28, 1), block=64, unroll=10, batch=32,
         local_steps=10, timed_rounds=2, attack_frac=0.1,
         defense=dict(clip_norm=10.0, aggregator="trimmed_mean",
                      trim_fraction=0.15, anomaly_threshold=4.0)),
    # resnet/distilbert/vit block+unroll follow the headline's measured
    # lesson (small client blocks + full step unroll beat big blocks for
    # conv/attention models); these three families have no sweep of their
    # own yet.
    dict(name="fedprox_femnist_resnet18_1k", model="resnet18",
         algorithm=("fedprox", dict(local_lr=0.05, mu=0.01)),
         num_clients=1000, n_local=16, input_shape=(28, 28, 1),
         num_classes=62, block=16, batch=16, local_steps=5, unroll=5,
         timed_rounds=2),
    dict(name="fedadam_sent140_distilbert_1k", model="distilbert",
         algorithm=("fedadam", dict(local_lr=0.05)), num_clients=1000,
         n_local=8, text=True, seq_len=64, vocab_size=30522, num_classes=2,
         input_shape=(64,), block=8, batch=16, local_steps=5, unroll=5,
         timed_rounds=2),
    dict(name="ditto_cifar100_vit_tiny_1k", model="vit_tiny",
         algorithm=("ditto", dict(local_lr=0.05, lam=0.1)), num_clients=1000,
         n_local=16, input_shape=(32, 32, 3), num_classes=100, block=16,
         batch=16, local_steps=5, unroll=5, timed_rounds=2),
]


def make_algorithm(spec):
    name, kw = spec
    builders = {"fedavg": fedavg, "fedprox": fedprox, "fedadam": fedadam,
                "ditto": ditto}
    kw = dict(kw)
    lr = kw.pop("local_lr")
    return builders[name](lr, **kw)


def _resilience_counters():
    """Counters from the process-global resilience log (retries, rollbacks,
    quarantined clients, injected faults). Recorded per family so robustness
    regressions — a backend that suddenly needs retries to finish a round —
    show up in the perf trajectory, not just in ad-hoc logs."""
    from olearning_sim_tpu.resilience.events import global_log

    return dict(global_log().counters())



def run_one_inprocess(plan, fam):
    """Measure one family dict on ``plan`` — or, when the family pins its
    own mesh (``chips``/``mp``/``pp``: the scaling sweeps), on that."""
    fam = dict(fam)
    fam["algorithm"] = make_algorithm(fam["algorithm"])
    chips = fam.pop("chips", None)
    mp, pp = fam.pop("mp", 1), fam.pop("pp", 1)
    if chips or mp > 1 or pp > 1:
        plan = _plan_for_chips(chips, mp=mp, pp=pp)
    # The global log and the cache counters are process-cumulative and all
    # families share one process, so record the deltas or family N would
    # inherit families 1..N-1's retries and compiles.
    before, cache_before = _resilience_counters(), cache_stats()
    record = run_family(plan, **fam)
    after, cache_after = _resilience_counters(), cache_stats()
    record["resilience"] = {
        k: v - before.get(k, 0) for k, v in after.items()
        if v - before.get(k, 0)
    }
    record["compile_cache"] = {
        k: v - cache_before.get(k, 0) for k, v in cache_after.items()
    }
    return record


def _plan_for_chips(chips, mp=1, pp=1):
    """Mesh over the first ``chips`` devices (default: all) — the --chips
    knob that captures scaling curves on one host by subdividing it.
    ``mp``/``pp`` give the mesh its model axes (the modelparallel sweep's
    knobs): dp becomes ``chips // (mp * pp)``."""
    if not chips and mp == 1 and pp == 1:
        return make_mesh_plan()
    devices = jax.devices()
    n = int(chips) if chips else len(devices)
    if len(devices) < n:
        raise RuntimeError(
            f"--chips {chips}: host exposes only {len(devices)} devices"
        )
    return make_mesh_plan(devices=devices[:n], mp=int(mp), pp=int(pp))


# ---------------------------------------------------------- multichip
# The chips={1,2,4,8} scaling family: the SAME mlp family measured at every
# mesh size the host has chips for, plain and defended, with the
# cross-replica sharded server update on — each on a mesh over the first
# ``chips`` devices of this one process. Results land in
# BENCH_multichip.json.
MULTICHIP_CHIPS = (1, 2, 4, 8)
MULTICHIP_FAMILY = dict(
    name="fedavg_mnist_mlp_multichip", model="mlp2",
    algorithm=("fedavg", dict(local_lr=0.05)), num_clients=512, n_local=8,
    input_shape=(28, 28, 1), block=8, unroll=1, batch=8, local_steps=2,
    timed_rounds=2, shard_server=True,
)
MULTICHIP_DEFENSE = dict(clip_norm=10.0, aggregator="trimmed_mean",
                         trim_fraction=0.15, anomaly_threshold=4.0)


def _sweep_payload(device, entries, **fields):
    return {"captured_unix": round(time.time(), 1), "device": device,
            **fields, "entries": entries}


def run_multichip(out_name="BENCH_multichip.json"):
    """Capture the chips-scaling family; prints one JSON line per entry
    and banks the whole family atomically."""
    device = require_tpu()
    enable_compile_cache()
    entries = []
    for chips in (c for c in MULTICHIP_CHIPS if c <= device["count"]):
        for program, extra in (
            ("plain", {}),
            ("defended", {"attack_frac": 0.1,
                          "defense": MULTICHIP_DEFENSE}),
        ):
            fam = {**MULTICHIP_FAMILY, **extra, "chips": chips,
                   "name": f"{MULTICHIP_FAMILY['name']}_{program}_c{chips}"}
            record = run_one_inprocess(None, fam)
            record["program"] = program
            print(json.dumps(record), flush=True)
            entries.append(record)
    payload = _sweep_payload(
        device, entries, family=MULTICHIP_FAMILY["name"],
        note=("rounds/sec per mesh size for the plain and defended "
              "(clip+trimmed_mean+anomaly) programs with the sharded "
              "server update (methodology: docs/performance.md)."),
    )
    _bank(payload, out_name)
    return payload


# ------------------------------------------------------- modelparallel
# The large-model mp-scaling family: the three heavy suite families
# measured at tensor parallelism mp={1,2,4} (dp=1, so the curve isolates
# the mp axis), up to the host's chip count. resnet18 is included
# deliberately: conv towers shard ~0% under the Megatron tp rules, so its
# flat curve IS the tp-vs-pp selection guidance of docs/performance.md
# measured rather than asserted.
MODELPARALLEL_MP = (1, 2, 4)
MODELPARALLEL_MODELS = (
    "fedadam_sent140_distilbert_1k",
    "ditto_cifar100_vit_tiny_1k",
    "fedprox_femnist_resnet18_1k",
)


def run_modelparallel(out_name="BENCH_modelparallel.json"):
    """Capture the mp-scaling rows for the large client families; one
    JSON line per entry, banked atomically like the multichip sweep."""
    device = require_tpu()
    enable_compile_cache()
    families = {f["name"]: f for f in SUITE_FAMILIES}
    entries = []
    for name in MODELPARALLEL_MODELS:
        for mp in (m for m in MODELPARALLEL_MP if m <= device["count"]):
            # Pin the mesh to exactly mp devices so dp=1 in every row:
            # otherwise a 4-chip host would run the mp=1 row as dp=4 and
            # mp=2 as dp=2 x mp=2 — a fixed-4-chip dp-vs-mp tradeoff, not
            # the documented mp-axis isolation curve.
            fam = {**families[name], "mp": mp, "chips": mp,
                   "name": f"{name}_mp{mp}"}
            record = run_one_inprocess(None, fam)
            record["model"] = fam["model"]
            print(json.dumps(record), flush=True)
            entries.append(record)
    payload = _sweep_payload(
        device, entries,
        note=("rounds/sec at tensor parallelism mp={1,2,4} (dp=1) for "
              "the three heavy suite families. distilbert/vit shard "
              "their transformer blocks over mp; resnet18's conv "
              "towers stay replicated (tp-vs-pp selection guidance: "
              "docs/performance.md)."),
    )
    _bank(payload, out_name)
    return payload


# ------------------------------------------------------------- async
# ISSUE 8 / ROADMAP item 2: the buffered asynchronous engine's bench of
# record (BENCH_async.json). Two claims, one file:
#
#  1. fedavg_mnist_mlp_1k_async — at straggler-heavy pacing (half the
#     fleet 8x slower: p95 >> median) the buffered asynchronous program
#     commits >= 1.5x the device-rounds/sec of the synchronous
#     deadline-masked baseline on the SAME config and the IDENTICAL
#     seeded completion times: the sync program computes the stragglers'
#     updates and discards them at the deadline, the async program
#     commits them with staleness-discounted weights (engine/
#     async_rounds.py; semantics in docs/performance.md).
#  2. A 2-task multiplex record — two device-paced tasks driven by one
#     MultiTaskDispatcher (threaded interleave) vs the same two tasks run
#     serially. Each task's rounds wait out the simulated fleet's
#     wall-clock round trip (the operator-flow polling idle a device-
#     cloud engine actually sees); the dispatcher fills that idle with
#     the other task's compute, so aggregate committed device-rounds/sec
#     rises >= 1.3x without changing either task's math (bitwise-solo
#     guarantee tested in tests/test_async.py).
ASYNC_FAMILY = dict(
    name="fedavg_mnist_mlp_1k_async", model="mlp2",
    algorithm=("fedavg", dict(local_lr=0.05)), num_clients=1024, n_local=8,
    input_shape=(28, 28, 1), block=32, batch=8, local_steps=2,
    timed_rounds=3,
)
ASYNC_SPIKE = (0.5, 8.0)  # half the fleet 8x slower: p95 >> median
ASYNC_BUFFER = 128  # M: commit every 128 arrivals (8 windows over 1k)
MUX_ROUND_TRIP_S = float(os.environ.get("OLS_BENCH_MUX_ROUND_TRIP", "0.25"))
MUX_ROUNDS = 6


def _mux_runner(core, ds, task_id, rounds, round_trip_s, acfg):
    from olearning_sim_tpu.engine.runner import (
        DataPopulation,
        OperatorSpec,
        SimulationRunner,
    )

    def device_pace(runner, round_idx, operator, population):
        # The simulated fleet's wall-clock round trip (dispatch -> last
        # needed arrival): the operator-flow polling barrier a device-
        # cloud round actually blocks on. A one-task process idles here.
        time.sleep(round_trip_s)
        return {}

    pop = DataPopulation(
        name="data_0", dataset=ds, device_classes=["c"],
        class_of_client=np.zeros(ds.num_clients, int),
        nums=[ds.num_real_clients], dynamic_nums=[0],
    )
    return SimulationRunner(
        task_id=task_id, core=core, populations=[pop],
        operators=[OperatorSpec(name="train"),
                   OperatorSpec(name="device_pace", kind="custom",
                                custom_fn=device_pace)],
        rounds=rounds, async_config=acfg,
    )


def run_async_multiplex(round_trip_s=None, rounds=MUX_ROUNDS):
    """Aggregate throughput of 2 device-paced tasks under one threaded
    MultiTaskDispatcher vs the same tasks run serially (in-process)."""
    from olearning_sim_tpu.engine.async_rounds import AsyncConfig
    from olearning_sim_tpu.engine.runner import MultiTaskDispatcher

    round_trip_s = MUX_ROUND_TRIP_S if round_trip_s is None else round_trip_s
    plan = make_mesh_plan()
    cfg = FedCoreConfig(batch_size=8, max_local_steps=2, block_clients=16)
    core = build_fedcore(
        "mlp2", make_algorithm(("fedavg", {"local_lr": 0.05})), plan, cfg,
        input_shape=(28, 28, 1),
    )
    ds = make_synthetic_dataset(
        seed=0, num_clients=64, n_local=8, input_shape=(28, 28, 1),
        num_classes=10, dirichlet_alpha=0.5,
    ).pad_for(plan, 16).place(plan)
    acfg = AsyncConfig(buffer_size=16, schedule="polynomial",
                       default_step_s=0.05, jitter=0.1)

    # Warm the async program variant once: both measurements share the
    # core's variant cache, so neither pays compile.
    _mux_runner(core, ds, "mux-warm", 1, 0.0, acfg).run()

    def committed(history):
        return sum(h["train"]["data_0"]["committed"] for h in history)

    t0 = time.perf_counter()
    serial_committed = 0
    for tid in ("mux-serial-a", "mux-serial-b"):
        serial_committed += committed(
            _mux_runner(core, ds, tid, rounds, round_trip_s, acfg).run()
        )
    serial_s = time.perf_counter() - t0

    runners = [_mux_runner(core, ds, tid, rounds, round_trip_s, acfg)
               for tid in ("mux-a", "mux-b")]
    t0 = time.perf_counter()
    results = MultiTaskDispatcher(runners, interleave="thread").run()
    mux_s = time.perf_counter() - t0
    mux_committed = sum(committed(h) for h in results.values())

    serial_rate = serial_committed / serial_s
    mux_rate = mux_committed / mux_s
    return {
        "tasks": 2,
        "rounds_per_task": rounds,
        "device_paced": True,
        "round_trip_s": round_trip_s,
        "serial_seconds": round(serial_s, 3),
        "multiplex_seconds": round(mux_s, 3),
        "serial_device_rounds_per_sec": round(serial_rate, 1),
        "multiplex_device_rounds_per_sec": round(mux_rate, 1),
        "aggregate_speedup": round(mux_rate / serial_rate, 3),
    }


def run_async_bench(out_name="BENCH_async.json"):
    """Capture the async family pair + the 2-task multiplex record; one
    JSON line per entry, banked atomically like the multichip sweep."""
    device = require_tpu()
    enable_compile_cache()
    plan = make_mesh_plan()
    entries = []
    for mode, extra in (("sync", {}),
                        ("async", {"async_buffer": ASYNC_BUFFER})):
        fam = {**ASYNC_FAMILY, **extra,
               "straggler_spike": list(ASYNC_SPIKE),
               "name": f"{ASYNC_FAMILY['name']}_{mode}"}
        record = run_one_inprocess(plan, fam)
        print(json.dumps(record), flush=True)
        entries.append(record)
    speedup = round(
        entries[1]["committed_device_rounds_per_sec"]
        / entries[0]["committed_device_rounds_per_sec"], 3
    )
    mux = run_async_multiplex()
    print(json.dumps({"multiplex": mux}), flush=True)
    payload = _sweep_payload(
        device, entries, family=ASYNC_FAMILY["name"],
        note=("sync deadline-masked baseline vs buffered async on "
              "identical straggler-heavy completion times (headline: "
              "committed device-rounds/sec), plus 2 device-paced "
              "tasks multiplexed on one process vs serial "
              "(methodology: docs/performance.md)."),
        async_vs_sync_committed_device_rounds=speedup,
        multiplex=mux,
    )
    _bank(payload, out_name)
    return payload


# ------------------------------------------------- trace-driven scenarios
# ``--trace`` banks the million-client trace-driven scenario family
# (BENCH_trace.json): the cohort lives in a lazy HostClientStore (host
# memory O(chunk), never O(population)) and every round streams it
# through the chip in stream_rows-sized blocks with double-buffered
# placement (FedCore.stream_round) under a diurnal + flash-crowd
# availability trace (engine/scenario.py). Peak device bytes are
# O(block): the banked record carries both the streamed estimate and the
# bytes a resident population would have needed. Scenario grid rows
# (spike x churn x attack+clip) ride the same machinery at a smaller
# population.

TRACE_CLIENTS_1M = int(os.environ.get("OLS_BENCH_TRACE_CLIENTS",
                                      str(1 << 20)))
TRACE_STREAM_ROWS = int(os.environ.get("OLS_BENCH_TRACE_ROWS", "8192"))


def run_trace_family(*, name, num_clients, stream_rows, timed_rounds=2,
                     scenario=None, attack_frac=None, clip=None,
                     hidden=(32,), input_shape=(784,), n_local=4,
                     batch=4, local_steps=1, block=256, num_classes=10):
    """One streamed trace family: lazy synthetic store + scenario masks,
    timed through FedCore.stream_round. Returns the record dict."""
    from olearning_sim_tpu.engine.client_data import HostClientStore
    from olearning_sim_tpu.engine.defense import DefenseConfig
    from olearning_sim_tpu.engine.scenario import ScenarioConfig, ScenarioModel

    plan = make_mesh_plan()
    cfg = FedCoreConfig(batch_size=batch, max_local_steps=local_steps,
                        block_clients=block)
    if stream_rows % (plan.dp * block):
        stream_rows = plan.dp * block * max(
            1, stream_rows // (plan.dp * block)
        )
    core = build_fedcore(
        "mlp2", fedavg(0.05), plan, cfg,
        model_overrides={"hidden": list(hidden),
                         "num_classes": num_classes},
        input_shape=input_shape,
    )
    # Chunks aligned to the per-device segment (stream_rows / dp): the
    # streamed executor's interleaved layout then generates every chunk
    # exactly once per round (a block-sized chunk would be regenerated
    # dp times to serve dp segments).
    store = HostClientStore.synthetic(
        seed=0, num_clients=num_clients, n_local=n_local,
        input_shape=input_shape, num_classes=num_classes,
        chunk_rows=min(max(1, stream_rows // plan.dp), 8192),
    )
    state = core.init_state(jax.random.key(0))
    scen_cfg = (ScenarioConfig.from_dict(dict(scenario))
                if scenario else None)
    model = (ScenarioModel(scen_cfg, num_clients, seed=0)
             if scen_cfg is not None else None)

    def round_kwargs(r):
        kw = {}
        avail = num_clients
        if model is not None:
            tr = model.round_trace(r)
            kw["participate"] = tr.participate
            avail = tr.num_available
            if tr.label_shift is not None and tr.label_shift.any():
                kw.update(label_shift=tr.label_shift,
                          label_classes=num_classes)
        if attack_frac:
            k = max(1, int(float(attack_frac) * num_clients))
            idx = np.random.default_rng(1).choice(num_clients, size=k,
                                                  replace=False)
            scale = np.ones(num_clients, np.float32)
            scale[idx] = -1.0
            kw["attack_scale"] = scale
        if clip is not None:
            kw["defense"] = DefenseConfig(clip_norm=float(clip),
                                          aggregator="mean")
        return kw, avail

    # Warmup round (compile + first stream walk).
    t0 = time.perf_counter()
    kw, _ = round_kwargs(0)
    state, metrics, st = core.stream_round(
        state, store, stream_rows=stream_rows, **kw
    )
    loss = float(metrics.mean_loss)
    compile_s = time.perf_counter() - t0

    times, committed, stats = [], [], st
    avail_last = num_clients
    for r in range(1, 1 + timed_rounds):
        kw, avail_last = round_kwargs(r)
        t0 = time.perf_counter()
        state, metrics, stats = core.stream_round(
            state, store, stream_rows=stream_rows, **kw
        )
        loss = float(metrics.mean_loss)
        times.append(time.perf_counter() - t0)
        committed.append(int(metrics.clients_trained))
    times = np.asarray(times)
    rps = 1.0 / times.mean()
    per_client_bytes = (
        int(np.prod(input_shape)) * n_local * 2  # bf16 features
        + n_local * 4 + 3 * 4                    # labels + scalars
    )
    record = {
        "family": name,
        "backend": jax.default_backend(),
        "chips": plan.n_devices,
        "clients": num_clients,
        "logical_population": stats.rows,
        "stream_blocks": stats.blocks,
        "stream_block_rows": stats.block_rows,
        "local_steps": local_steps,
        "timed_rounds": timed_rounds,
        "rounds_per_sec": round(float(rps), 5),
        "round_time_sec": round(float(times.mean()), 3),
        "device_rounds_per_sec": round(float(rps * num_clients), 1),
        "committed_clients_last_round": committed[-1],
        "committed_device_rounds_per_sec": round(
            float(np.mean(committed) * rps), 1
        ),
        "compile_sec": round(compile_s, 1),
        "mean_loss": loss,
        # The O(block)-vs-O(population) claim, as numbers: what the
        # streamed walk keeps resident vs what placing the whole
        # population would have needed.
        "peak_hbm_bytes_est": stats.peak_hbm_bytes_est,
        "resident_population_bytes_est": per_client_bytes * num_clients,
        "host_transfer_s_per_round": stats.host_transfer_s,
        "transfer_bytes_per_round": stats.transfer_bytes,
        "transfer_overlap_fraction": stats.overlap_fraction,
        "host_state_bytes": stats.state_bytes,
        **({"scenario": dict(scenario),
            "available_last_round": avail_last}
           if scenario else {}),
        **({"attack_frac": float(attack_frac)} if attack_frac else {}),
        **({"defense": "clip", "clipped": int(metrics.clipped)}
           if clip is not None else {}),
    }
    return record


TRACE_SCENARIO_1M = {
    # One simulated day every ~144 rounds; diurnal swing around a 40%
    # mean with a flash crowd in the timed window.
    "round_seconds": 600.0,
    "online_base": 0.4,
    "online_amp": 0.3,
    "peak_hour": 20.0,
    "phase_jitter_hours": 3.0,
    "spikes": [{"round": 1, "rounds": 2, "boost": 2.0}],
}

TRACE_SCENARIO_GRID = dict(TRACE_SCENARIO_1M, leave_rate=0.002,
                           join_frac=0.1, drift_period_rounds=10)


def run_trace_bench(out_name="BENCH_trace.json"):
    """Capture the 1M-client streamed trace family + the scenario grid
    rows (spike x churn x attack+clip); banked atomically like the other
    sweeps."""
    device = require_tpu()
    enable_compile_cache()
    entries = []

    def _pop_tag(c):
        # 1048576 -> "1m", 65536 -> "65k": the family name must encode
        # the actual population even under OLS_BENCH_TRACE_CLIENTS
        # overrides (integer-dividing a sub-million count by 1e6 would
        # name every override "0m").
        return (f"{round(c / 1e6)}m" if c >= 10**6
                else f"{c // 1000}k" if c >= 1000 else str(c))

    fams = [
        dict(name=f"fedavg_mnist_mlp_{_pop_tag(TRACE_CLIENTS_1M)}_trace",
             num_clients=TRACE_CLIENTS_1M,
             stream_rows=TRACE_STREAM_ROWS,
             timed_rounds=1,
             scenario=TRACE_SCENARIO_1M),
        dict(name="fedavg_mnist_mlp_65k_trace_spike_churn",
             num_clients=1 << 16, stream_rows=TRACE_STREAM_ROWS,
             scenario=TRACE_SCENARIO_GRID),
        dict(name="fedavg_mnist_mlp_65k_trace_spike_churn_attack_clip",
             num_clients=1 << 16, stream_rows=TRACE_STREAM_ROWS,
             scenario=TRACE_SCENARIO_GRID, attack_frac=0.1, clip=0.05),
    ]
    for fam in fams:
        record = run_trace_family(**fam)
        print(json.dumps(record), flush=True)
        entries.append(record)
    payload = _sweep_payload(
        device, entries, family=fams[0]["name"],
        note=("Trace-driven scenario engine at million-client scale: "
              "lazy host store + block-streamed rounds "
              "(FedCore.stream_round) under diurnal/spike/churn "
              "availability masks; peak device bytes are O(stream "
              "block), not O(population) — compare "
              "peak_hbm_bytes_est vs resident_population_bytes_est "
              "(methodology: docs/performance.md)."),
    )
    _bank(payload, out_name)
    return payload


# ------------------------------------------------------------ convergence
# ``--convergence`` banks the time-to-accuracy grid (BENCH_convergence.json;
# ISSUE 13 / ROADMAP item 4): every row is ONE (family x engine-config)
# convergence run through the SimulationRunner + ConvergenceTracker
# (engine/convergence.py — the same harness the analysis/convergence_gate
# CI gate re-runs at a smaller scale), to a fixed seed and round budget,
# reporting target accuracy, rounds/simulated-seconds-to-target, final
# accuracy, and accuracy-per-device-round. The grid prices the platform's
# throughput levers in accuracy terms:
#
#   sync_deadline vs async_staleness  — what the async commit policy
#                                       costs (or doesn't) in quality;
#   attack_undefended vs
#   attack_trimmed_mean               — what the defense recovers under a
#                                       20% scale attack;
#   clean_resident vs streamed        — streamed execution is bitwise
#                                       resident execution, so the pair's
#                                       accuracy/rounds fields MUST agree
#                                       (asserted into the payload's
#                                       resident_vs_streamed_match; the
#                                       sim clock differs by design — the
#                                       streamed row carries a scenario
#                                       round clock);
#   drift_trace                       — what unmitigated label drift does
#                                       to a fixed-eval-set model.
#
# The accuracy/rounds fields are platform-independent for fixed seeds (the
# CI gate, analysis/convergence_gate, re-runs them on CPU at a smaller
# scale); the wall-clock fields are the chip's. Every row builds its own
# FedCore; only the on-disk XLA cache is shared between rows.

CONVERGENCE_BASE = dict(
    seed=7, num_clients=256, n_local=8, input_shape=(32,), num_classes=10,
    class_sep=2.5, eval_n=1024, rounds=24, batch=8, local_steps=6,
    block_clients=32, hidden=(32,), local_lr=0.3,
)
CONVERGENCE_TRACK = {
    "target_accuracy": 0.7,
    "eval_every": 1,
    "round_budget": 12,
    "sim_seconds_budget": 5.0,
}
# Completion-time model shared by the sync-deadline and async rows: the
# IDENTICAL speed distribution, so the pair isolates the commit policy
# (the deadline masks ~20% of arrivals as stragglers; the async engine
# commits them with staleness-discounted weights instead).
_CONV_PACING = dict(default_step_s=0.05, jitter=0.5)
_CONV_ATTACK = {"mode": "scale", "factor": 80.0, "fraction": 0.2}
_CONV_DEFENSE = {"clip_norm": 3.0, "aggregator": "trimmed_mean",
                 "trim_fraction": 0.25}

CONVERGENCE_FAMILIES = [
    dict(name="conv_mlp_clean_resident"),
    dict(name="conv_mlp_streamed", streamed=True),
    dict(name="conv_mlp_sync_deadline",
         deadline=dict(deadline_s=0.42, **_CONV_PACING)),
    dict(name="conv_mlp_async_staleness",
         async_config=dict(buffer_size=64, schedule="polynomial",
                           staleness_alpha=0.5, **_CONV_PACING)),
    dict(name="conv_mlp_attack_undefended", attack=dict(_CONV_ATTACK)),
    dict(name="conv_mlp_attack_trimmed_mean", attack=dict(_CONV_ATTACK),
         defense=dict(_CONV_DEFENSE)),
    dict(name="conv_mlp_drift_trace",
         scenario={"drift_period_rounds": 5, "round_seconds": 600.0}),
]


def run_convergence_bench(out_name="BENCH_convergence.json"):
    """Capture the (family x engine-config) convergence grid; one JSON
    line per row, banked atomically like the other sweeps."""
    from olearning_sim_tpu.engine.convergence import run_convergence_task

    device = require_tpu()
    enable_compile_cache()
    entries = []
    for fam in CONVERGENCE_FAMILIES:
        fam = dict(fam)
        name = fam.pop("name")
        record = run_convergence_task(
            name=name, convergence=dict(CONVERGENCE_TRACK),
            **CONVERGENCE_BASE, **fam,
        )
        # The full eval series stays out of the bank (it is the gate's
        # job); the banked row keeps the summary facts.
        record.pop("evals", None)
        print(json.dumps(record), flush=True)
        entries.append(record)
    by = {e["family"]: e for e in entries}

    def _pair(a, b, key="final_accuracy"):
        if by[a].get(key) is None or by[b].get(key) is None:
            return None
        return round(float(by[a][key]) - float(by[b][key]), 6)

    # The standing sanity claim, asserted rather than implied: the streamed
    # row's accuracy/rounds fields equal the resident row's EXACTLY
    # (streamed execution is bitwise resident execution; sim/wall clocks
    # are excluded — the streamed row carries a scenario round clock by
    # design). False = the bitwise contract broke and this artifact says
    # so loudly.
    streamed_matches_resident = all(
        by["conv_mlp_clean_resident"].get(k) == by["conv_mlp_streamed"].get(k)
        for k in ("final_accuracy", "best_accuracy",
                  "accuracy_at_round_budget", "reached",
                  "rounds_to_target", "device_rounds_committed")
    )
    payload = _sweep_payload(
        device, entries,
        target_accuracy=CONVERGENCE_TRACK["target_accuracy"],
        note=("Time-to-accuracy grid: per (family x engine-config) "
              "convergence run to a fixed seed/budget — rounds and "
              "simulated-seconds to the target accuracy, accuracy at "
              "fixed round budget, accuracy per device-round "
              "(methodology: docs/performance.md, Time-to-accuracy "
              "benching)."),
        # Headline deltas: positive = the first row is more accurate.
        async_minus_sync_final_accuracy=_pair(
            "conv_mlp_async_staleness", "conv_mlp_sync_deadline"),
        defended_minus_undefended_final_accuracy=_pair(
            "conv_mlp_attack_trimmed_mean", "conv_mlp_attack_undefended"),
        resident_vs_streamed_match=streamed_matches_resident,
    )
    _bank(payload, out_name)
    return payload


if __name__ == "__main__":
    if "--multichip" in sys.argv:
        run_multichip()
    elif "--modelparallel" in sys.argv:
        run_modelparallel()
    elif "--async" in sys.argv:
        run_async_bench()
    elif "--trace" in sys.argv:
        run_trace_bench()
    elif "--convergence" in sys.argv:
        run_convergence_bench()
    elif "--chips" in sys.argv:
        main(chips=int(sys.argv[sys.argv.index("--chips") + 1]))
    else:
        main()
