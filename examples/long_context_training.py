"""Long-context training tour: ring attention over an ``sp`` mesh axis.

Trains a distilbert-shaped classifier on sequences sharded 4-ways over
the mesh's sequence-parallel axis: each device holds L/4 of every
sequence, K/V chunks rotate around the ring with ``ppermute`` (ICI
neighbor links on a real TPU torus), and the [L, L] score matrix never
materializes on any device — per-device attention memory is O(L/sp) in
forward AND backward, so the max trainable L scales linearly with the
ring size. The same params evaluate under dense attention afterwards
(parameter-compatible modules), which is also this demo's correctness
check.

Runs on any 8-device mesh; for a quick local run:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context_training.py
"""

import _bootstrap  # noqa: F401 — repo path

import jax
import numpy as np
import optax

from olearning_sim_tpu.models import get_model
from olearning_sim_tpu.parallel.long_context import sp_evaluate, sp_train_step
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

VOCAB, SEQ_LEN, CLASSES = 96, 64, 3


def make_batch(key, n):
    """Token sequences whose label is recoverable ONLY by combining the
    first and last tokens: label = (head + tail) mod CLASSES, with the
    head code drawn at random — neither end alone carries any signal, so
    a model whose attention cannot span the full sequence (the ends live
    in DIFFERENT shards under sp=4) cannot beat chance. Codes are offset
    by +3 to stay clear of pad_id=0 and the special tokens."""
    kt, kl, ka = jax.random.split(key, 3)
    tokens = np.array(jax.random.randint(kt, (n, SEQ_LEN), 3, VOCAB), np.int32)
    labels = np.array(jax.random.randint(kl, (n,), 0, CLASSES), np.int32)
    head = np.array(jax.random.randint(ka, (n,), 0, CLASSES), np.int32)
    tokens[:, 0] = head + 3
    tokens[:, -1] = (labels - head) % CLASSES + 3
    return tokens, labels


def main():
    plan = make_mesh_plan(dp=2, mp=1, sp=4)   # 8 devices: 2-way batch x 4-way sequence
    print(f"mesh: dp={plan.dp} x sp={plan.sp} over {len(jax.devices())} devices")

    spec = get_model("distilbert")
    overrides = dict(vocab_size=VOCAB, max_len=SEQ_LEN, width=64, depth=2,
                     heads=4, mlp_dim=128, num_classes=CLASSES)
    ring = spec.build(**overrides, attention_impl="ring")
    dense = spec.build(**overrides)           # same param tree, dense attention

    tokens, labels = make_batch(jax.random.key(0), 64)
    # Init through the dense twin (ring modules need a live shard_map to
    # trace); the trees are parameter-compatible by construction.
    params = dense.init(jax.random.key(1), tokens[:1])["params"]
    optimizer = optax.adam(3e-3)
    opt_state = optimizer.init(params)

    for step in range(30):
        params, opt_state, loss = sp_train_step(
            ring, params, opt_state, tokens, labels, optimizer, plan
        )
        if (step + 1) % 10 == 0:
            print(f"step {step + 1}: loss={float(loss):.4f}")

    _, ring_acc = sp_evaluate(ring, params, tokens, labels, plan)
    # The SAME params under dense attention on one device: numerics match.
    logits = dense.apply({"params": params}, tokens)
    dense_acc = float((np.argmax(np.asarray(logits), -1) == labels).mean())
    print(f"train-set accuracy: ring(sp=4)={float(ring_acc):.3f} "
          f"dense(single-device)={dense_acc:.3f}")
    assert abs(float(ring_acc) - dense_acc) < 0.02, "ring/dense divergence"
    print("ok: ring-trained params evaluate identically under dense attention")


if __name__ == "__main__":
    main()
