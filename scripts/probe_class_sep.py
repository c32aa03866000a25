"""Calibrate class_sep for the non-saturated convergence-parity regime.

VERDICT r3 #3: the committed parity artifact saturates (99.6% final acc at
class_sep 1.0), which compresses engine-vs-oracle deltas toward zero. This
probes a few separations with short engine-only runs (256 clients, 12
rounds) so the full 1024-client/40-round artifact can be pointed at a
separation landing 60-80% final accuracy. Engine-only is fine for
calibration — data difficulty, not engine-vs-oracle agreement, is what is
being measured.

Run: JAX_PLATFORMS=cpu python scripts/probe_class_sep.py 0.35 0.22
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from olearning_sim_tpu.engine import build_fedcore, fedavg
from olearning_sim_tpu.engine.client_data import (
    make_synthetic_texture_dataset,
    make_texture_eval_set,
)
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

NUM_CLIENTS = 256
COHORT = 64
ROUNDS = 12
SEED = 5


def probe(sep, plan):
    cfg = FedCoreConfig(batch_size=32, max_local_steps=10, block_clients=16)
    core = build_fedcore("cnn4", fedavg(0.1), plan, cfg)
    ds = make_synthetic_texture_dataset(
        seed=SEED, num_clients=NUM_CLIENTS, n_local=20,
        input_shape=(32, 32, 3), num_classes=10, dirichlet_alpha=0.5,
        class_sep=sep,
    )
    ex, ey = make_texture_eval_set(SEED, 1000, (32, 32, 3), 10, class_sep=sep)
    state = core.init_state(jax.random.key(0))
    t0 = time.time()
    accs = []
    for r in range(ROUNDS):
        cohort = np.sort(np.random.default_rng([SEED, r]).choice(
            NUM_CLIENTS, size=COHORT, replace=False
        ))
        sub = ds.take(cohort).pad_for(plan, cfg.block_clients).place(
            plan, feature_dtype=None
        )
        state, metrics = core.round_step(state, sub)
        if (r + 1) % 4 == 0:
            _, acc = core.evaluate(state.params, ex, ey)
            accs.append({"round": r + 1, "acc": round(float(acc), 4)})
            print(f"sep={sep} round {r+1}: acc={acc:.4f} "
                  f"({time.time()-t0:.0f}s)", flush=True)
    return {"class_sep": sep, "curve": accs}


def main():
    seps = [float(a) for a in sys.argv[1:]] or [0.35, 0.22]
    plan = make_mesh_plan()
    out = []
    for sep in seps:
        out.append(probe(sep, plan))
        with open("/tmp/probe_class_sep.json", "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
