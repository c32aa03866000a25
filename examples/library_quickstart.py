"""Engine-as-a-library quickstart: one compiled round program advances the
whole client population (the reference's per-phone subprocess loop,
``utils_run_task.py:481-514``, collapsed into one XLA program).

Runs anywhere jax runs; on a multi-device host the clients shard over dp.
"""

import _bootstrap  # noqa: F401 — repo path


import jax

from olearning_sim_tpu.engine import build_fedcore, fedavg, make_synthetic_dataset
from olearning_sim_tpu.engine.client_data import make_central_eval_set
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.parallel.mesh import make_mesh_plan


def main():
    plan = make_mesh_plan()  # all local devices as dp
    cfg = FedCoreConfig(batch_size=8, max_local_steps=5, block_clients=8)
    core = build_fedcore(
        "mlp2", fedavg(0.1), plan, cfg,
        model_overrides={"hidden": (64,), "num_classes": 4},
        input_shape=(16,),
    )
    ds = make_synthetic_dataset(
        seed=0, num_clients=256, n_local=16, input_shape=(16,),
        num_classes=4, class_sep=3.0, dirichlet_alpha=0.5,
    ).pad_for(plan, cfg.block_clients).place(plan)

    state = core.init_state(jax.random.key(0))
    for r in range(10):
        state, metrics = core.round_step(state, ds)
        print(f"round {r}: loss={float(metrics.mean_loss):.4f} "
              f"clients={int(metrics.clients_trained)}")

    x, y = make_central_eval_set(0, 512, (16,), 4, class_sep=3.0)
    loss, acc = core.evaluate(state.params, x, y)
    print(f"central eval: loss={loss:.4f} acc={acc:.3f}")


if __name__ == "__main__":
    main()
