"""Tensor- and pipeline-parallel tour: the `mp` and `pp` mesh axes.

Part 1 — tensor parallelism INSIDE the federated round: the same
`build_fedcore` call that runs pure-dp rounds accepts a dp x mp mesh;
attention heads and FFN kernels split over `mp` (GSPMD: annotate the
weight shardings, XLA inserts the collectives), so a per-client model too
big for one chip's HBM trains across the `mp` group. The demo shows the
mp=2 round reproducing the mp=1 round's trajectory on identical data.

Part 2 — GPipe pipeline training of a centralized model: transformer
blocks stack over the `pp` axis (one stage per device group), micro-
batches stream through with `ppermute` bubbles, and one pipelined
optimizer step lands on the same params as a dense single-device step.

Runs on any 8-device mesh; for a quick local run:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/tensor_pipeline_parallel.py
"""

import _bootstrap  # noqa: F401 — repo path

import jax
import numpy as np
import optax

from olearning_sim_tpu.engine import build_fedcore, fedavg
from olearning_sim_tpu.engine.client_data import make_synthetic_text_dataset
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.models import get_model
from olearning_sim_tpu.parallel.mesh import make_mesh_plan
from olearning_sim_tpu.parallel.pipeline import (
    pp_place_params,
    pp_train_step,
)
from olearning_sim_tpu.parallel.tp import sharded_fraction, tp_param_specs

MODEL_KW = dict(
    model_overrides={
        "vocab_size": 128, "max_len": 16, "width": 64, "depth": 2,
        "heads": 4, "mlp_dim": 128, "num_classes": 2,
    },
    input_shape=(16,),
)


def federated_round(mp):
    plan = make_mesh_plan(dp=8 // mp, mp=mp)
    cfg = FedCoreConfig(batch_size=8, max_local_steps=3, block_clients=4)
    core = build_fedcore("distilbert", fedavg(0.1), plan, cfg, **MODEL_KW)
    ds = make_synthetic_text_dataset(
        seed=5, num_clients=32, n_local=8, seq_len=16, num_classes=2,
        vocab_size=128,
    ).pad_for(plan, cfg.block_clients).place(plan)
    state = core.init_state(jax.random.key(3))
    for _ in range(2):
        state, metrics = core.round_step(state, ds)
    return plan, state, float(metrics.mean_loss)


def main():
    # ---- Part 1: tensor-parallel federated rounds -----------------------
    _, _, loss1 = federated_round(mp=1)
    plan2, s2, loss2 = federated_round(mp=2)
    specs = tp_param_specs(jax.device_get(s2.params), mp=2)
    frac = sharded_fraction(s2.params, specs)
    print(f"mp=2 mesh dp={plan2.dp} x mp={plan2.mp}: "
          f"{frac:.0%} of param elements head/FFN-sharded")
    print(f"round loss: mp=1 {loss1:.4f} vs mp=2 {loss2:.4f}")
    assert abs(loss1 - loss2) < 2e-2 * max(1.0, abs(loss1)), \
        "tensor parallelism changed the training trajectory"

    # ---- Part 2: GPipe pipeline training --------------------------------
    spec = get_model("distilbert")
    dense = spec.build(vocab_size=96, max_len=32, width=64, depth=4,
                       heads=4, mlp_dim=128, num_classes=3)
    tokens = np.array(
        jax.random.randint(jax.random.key(1), (32, 32), 1, 96), np.int32
    )
    labels = np.asarray(tokens[:, 0] % 3, np.int32)
    params = dense.init(jax.random.key(0), tokens[:1])["params"]

    plan = make_mesh_plan(dp=2, mp=1, pp=4)   # 4 pipeline stages x 2-way data
    rest, stacked = pp_place_params(params, plan)
    opt = optax.adam(3e-3)
    opt_state = jax.jit(opt.init)((rest, stacked))
    losses = []
    for step in range(20):
        rest, stacked, opt_state, loss = pp_train_step(
            dense, rest, stacked, opt_state, tokens, labels, opt, plan
        )
        losses.append(float(loss))
        if (step + 1) % 10 == 0:
            print(f"pp step {step + 1}: loss={losses[-1]:.4f}")
    assert losses[-1] < losses[0], "pipeline failed to learn"
    print(f"ok: dp x mp federated rounds match, and the dp=2 x pp=4 "
          f"pipeline trains ({losses[0]:.3f} -> {losses[-1]:.3f})")


if __name__ == "__main__":
    main()
