"""Expert-parallel training tour: a Switch-MoE classifier over an ``ep``
mesh axis.

Each expert's FFN weights live physically on one slice of the ``ep``
axis (GSPMD auto mode: annotate the weight shardings, and XLA derives
the token all-to-alls — no hand-written dispatch collectives). The
router is replicated; the Switch load-balancing auxiliary loss keeps
expert assignment from collapsing. Per-device parameter memory for the
expert blocks scales as 1/ep, which is the whole point: the expert count
(and so model capacity) grows with the mesh, not with per-chip HBM.

Runs on any 8-device mesh; for a quick local run:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/moe_expert_parallel.py
"""

import _bootstrap  # noqa: F401 — repo path

import jax
import numpy as np
import optax

from olearning_sim_tpu.models import get_model
from olearning_sim_tpu.parallel.expert_parallel import (
    ep_place_params,
    ep_train_step,
    sharded_expert_fraction,
)
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

VOCAB, SEQ_LEN, CLASSES = 96, 32, 3


def main():
    plan = make_mesh_plan(dp=2, mp=1, ep=4)  # 8 devices: 2-way batch x 4-way experts
    print(f"mesh: dp={plan.dp} x ep={plan.ep} over {len(jax.devices())} devices")

    spec = get_model("moe_text")
    model = spec.build(vocab_size=VOCAB, max_len=SEQ_LEN, width=64, depth=2,
                      heads=4, mlp_dim=128, num_experts=4, num_classes=CLASSES)

    kt = jax.random.key(1)
    tokens = np.asarray(
        jax.random.randint(kt, (64, SEQ_LEN), 1, VOCAB), np.int32
    )
    labels = np.asarray(tokens[:, 0] % CLASSES, np.int32)

    params = model.init(jax.random.key(0), tokens[:1])["params"]
    params, specs = ep_place_params(params, plan)
    frac = sharded_expert_fraction(params, specs)
    print(f"{frac:.0%} of parameter elements physically sharded over ep")

    optimizer = optax.adam(3e-3)  # ONE instance: the compiled step caches on it
    opt_state = jax.jit(optimizer.init)(params)

    losses = []
    for step in range(30):
        params, opt_state, loss = ep_train_step(
            model, params, opt_state, tokens, labels, optimizer, plan
        )
        losses.append(float(loss))
        if (step + 1) % 10 == 0:
            print(f"step {step + 1}: loss={losses[-1]:.4f}")
    assert losses[-1] < losses[0], "MoE failed to learn"

    # The returned params keep their expert shardings across steps — no
    # silent gather-to-host replication in the update path.
    logits = model.apply({"params": jax.device_get(params)}, tokens)
    acc = float((np.argmax(np.asarray(logits), -1) == labels).mean())
    print(f"train-set accuracy after 30 steps: {acc:.3f}")
    print("ok: Switch-MoE trained with experts sharded over the ep axis")


if __name__ == "__main__":
    main()
