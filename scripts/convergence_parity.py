"""Full-scale accuracy-parity run: engine vs NumPy oracle to convergence.

BASELINE.md row: "Final accuracy vs CPU simulation | within ±0.3%".
This runs fedavg/cnn4 on CIFAR-10 shapes over a >=1k-client non-IID
population with per-round client sampling (cohorts preserve client uids,
so both sides draw identical RNG streams), evaluates both models on the
same held-out set as training progresses, and writes the record + curves
to ``PARITY_convergence.json`` at the repo root.
``tests/test_parity_cnn.py::test_convergence_artifact_within_baseline_bound``
enforces the committed artifact's bound in CI.

Run (CPU is fine; budget ~2 h for the default 45 rounds on a loaded box —
the artifact is rewritten after every eval, so an interrupt still leaves a
valid record at the last evaluated round):
    JAX_PLATFORMS=cpu python scripts/convergence_parity.py

``OLS_PARITY_CARRY=bf16`` switches the run into an engine-only A/B of the
bf16 local-SGD carry (FedCoreConfig.carry_dtype): the NumPy oracle is
skipped (the committed f32 artifact is the comparator) and the record goes
to ``PARITY_carry_bf16.json`` — convergence-scale gating evidence for the
perf lever beyond test_bf16_carry_parity's CI scale.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"
))

_ap = argparse.ArgumentParser()
_ap.add_argument("--class-sep", type=float,
                 default=float(os.environ.get("OLS_PARITY_SEP", "1.0")),
                 help="texture separation; 1.0 saturates ~99%% — use ~0.35 "
                      "for the non-saturated 60-80%% regime (VERDICT r3 #3)")
_ap.add_argument("--rounds", type=int,
                 default=int(os.environ.get("OLS_PARITY_ROUNDS", "45")))
_ap.add_argument("--backend", default=None,
                 help="'cpu' forces the CPU backend; 'tpu' (or any other "
                      "value) leaves the default hardware platform in place "
                      "for the engine leg — the NumPy oracle is host-side "
                      "either way, so this yields a TPU-vs-CPU numerics "
                      "parity record")
_ap.add_argument("--out", default=None,
                 help="artifact basename override (e.g. "
                      "PARITY_convergence_hard.json)")
_ap.add_argument("--carry", default=os.environ.get("OLS_PARITY_CARRY"),
                 help="'bf16' -> engine-only A/B of the bf16 local-SGD carry")
_ARGS = _ap.parse_args()

import jax

if _ARGS.backend == "cpu":
    jax.config.update("jax_platforms", "cpu")

import numpy as np

import cnn_oracle as oracle
from olearning_sim_tpu.engine import build_fedcore, fedavg
from olearning_sim_tpu.engine.client_data import (
    make_synthetic_texture_dataset,
    make_texture_eval_set,
)
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

NUM_CLIENTS = 1024
COHORT = 64
N_LOCAL = 20
BATCH = 32
STEPS = 10
LR = 0.1
SEP = _ARGS.class_sep
ROUNDS = _ARGS.rounds
NCLS = 10
SEED = 5
EVAL_EVERY = 5
CARRY = _ARGS.carry  # "bf16" -> engine-only A/B


def main():
    t0 = time.time()
    plan = make_mesh_plan()
    import jax.numpy as jnp

    cfg = FedCoreConfig(batch_size=BATCH, max_local_steps=STEPS,
                        block_clients=16,
                        carry_dtype=jnp.bfloat16 if CARRY == "bf16" else None)
    core = build_fedcore("cnn4", fedavg(LR), plan, cfg)
    # Textured (tiled per-class pattern) population: conv-learnable by
    # construction — Gaussian blobs are spatially incoherent and cnn4+GAP
    # provably stays at chance on them (see _class_textures docstring).
    ds_host = make_synthetic_texture_dataset(
        seed=SEED, num_clients=NUM_CLIENTS, n_local=N_LOCAL,
        input_shape=(32, 32, 3), num_classes=NCLS, dirichlet_alpha=0.5,
        class_sep=SEP,
    )
    ex, ey = make_texture_eval_set(SEED, 2000, (32, 32, 3), NCLS, class_sep=SEP)

    state = core.init_state(jax.random.key(0))
    base_key = jax.random.wrap_key_data(
        np.asarray(jax.random.key_data(state.base_key))
    )
    p = xs = ys = None
    if CARRY is None:  # the oracle state is dead weight in the A/B mode
        p = oracle.init_from_flax(jax.tree.map(np.asarray, state.params))
        xs = np.asarray(ds_host.x, np.float32)
        ys = np.asarray(ds_host.y)
    curves = []
    for r in range(ROUNDS):
        cohort = np.sort(np.random.default_rng([SEED, r]).choice(
            NUM_CLIENTS, size=COHORT, replace=False
        ))
        # Engine trains the cohort subset (take() preserves client uids, so
        # RNG streams are identical to full-population participation masks).
        sub = ds_host.take(cohort).pad_for(plan, cfg.block_clients).place(
            plan, feature_dtype=None
        )
        state, metrics = core.round_step(state, sub)
        loss = float(metrics.mean_loss)

        if CARRY is None:
            p = oracle.fedavg_round(
                p, xs[cohort], ys[cohort], ds_host.num_samples[cohort],
                ds_host.client_uid[cohort], ds_host.weight[cohort],
                base_key, r, steps=STEPS, batch=BATCH, lr=LR,
                num_classes=NCLS,
            )
        if (r + 1) % EVAL_EVERY == 0 or r == ROUNDS - 1:
            _, acc_e = core.evaluate(state.params, ex, ey)
            acc_o = (round(oracle.evaluate(p, ex, ey), 4)
                     if CARRY is None else None)
            curves.append({"round": r + 1, "loss_engine": round(loss, 4),
                           "acc_engine": round(float(acc_e), 4),
                           "acc_oracle": acc_o})
            print(f"round {r+1:3d}: loss={loss:.4f} acc_engine={acc_e:.4f} "
                  f"acc_oracle={acc_o} ({time.time()-t0:.0f}s)", flush=True)
            # Write the artifact after EVERY eval so a timeout/interrupt
            # still leaves a valid record at the last evaluated round.
            _write_record(curves, t0)

    rec = _write_record(curves, t0)
    print(json.dumps({k: v for k, v in rec.items() if k != "curves"}))


def _write_record(curves, t0):
    rec = {
        "task": "fedavg_cifar10_cnn4 (synthetic tiled-texture images, "
                "dirichlet 0.5 non-IID)",
        "num_clients": NUM_CLIENTS,
        "cohort": COHORT,
        "rounds": curves[-1]["round"],
        "local_steps": STEPS,
        "batch": BATCH,
        "lr": LR,
        "class_sep": SEP,
        "data": "tiled-texture synthetic",
        "final_acc_engine": curves[-1]["acc_engine"],
        "final_acc_oracle": curves[-1]["acc_oracle"],
        "final_delta": (
            round(abs(curves[-1]["acc_engine"] - curves[-1]["acc_oracle"]), 4)
            if curves[-1]["acc_oracle"] is not None else None
        ),
        "baseline_bound": 0.003,
        "engine_backend": jax.default_backend(),
        "wall_sec": round(time.time() - t0, 1),
        "curves": curves,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if CARRY == "bf16":
        rec["carry"] = "bf16"
        rec["note"] = ("engine-only A/B of the bf16 local-SGD carry; "
                       "compare final_acc_engine to the f32 artifact")
        name = "PARITY_carry_bf16"
    else:
        name = "PARITY_convergence"
    if _ARGS.out:
        name = _ARGS.out.removesuffix(".json")
    # Always keep the in-progress record in .partial.json; only publish the
    # gated name once the run satisfies the CI gate's minimum rounds, so a
    # mid-regeneration tree never carries (or destroys) a gate-passing
    # artifact.
    targets = [os.path.join(root, f"{name}.partial.json")]
    if rec["rounds"] >= 30:
        targets.append(os.path.join(root, f"{name}.json"))
    for out in targets:
        tmp = out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(rec, f, indent=1)
        os.replace(tmp, out)
    return rec


if __name__ == "__main__":
    main()
