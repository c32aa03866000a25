"""CNN family (BASELINE config 2 and the headline bench: 4-layer CNN on
CIFAR-10, 10k clients at >=500 rounds/min on a v4-32).

TPU-native design note: ``cnn4`` is all-convolutional — stride-2 convs
downsample instead of ``max_pool``. Profiling the compiled round on a v5e
chip showed max-pool's backward (``select_and_scatter``) dominating the
step at ~5ms per 4k-image block — 3x the cost of all the convs together —
while strided convs lower to clean MXU matmuls (83 TF/s measured vs 17).
A global-average-pool head replaces the big flatten->Dense layer for the
same reason: per-client Dense backward is a K=batch contraction (~16% MXU
tile utilization at batch 32), whereas conv weight-grads contract over
images x spatial positions. The reference has no fixed model zoo — models
live in user operator code (``ols_core/taskMgr/base/base_operator.py:15-52``);
these families realize BASELINE.json's configs.
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp

from olearning_sim_tpu.models.registry import ModelSpec, register_model


class CNN(nn.Module):
    """All-convolutional 4-layer CNN: three stride-2 conv blocks + GAP head,
    bfloat16 compute with fp32 logits (TPU best practice)."""

    features: Sequence[int] = (32, 64, 128)
    num_classes: int = 10

    @nn.compact
    def __call__(self, x):
        x = x.astype(jnp.bfloat16)
        for f in self.features:
            x = nn.Conv(f, (3, 3), strides=(2, 2), padding="SAME", dtype=jnp.bfloat16)(x)
            x = nn.relu(x)
        x = x.mean(axis=(1, 2))  # GAP: cheap fwd+bwd, no giant Dense
        return nn.Dense(self.num_classes, dtype=jnp.float32)(x)


register_model(
    ModelSpec(
        name="cnn4",
        builder=CNN,
        example_input_shape=(32, 32, 3),
        num_classes=10,
        defaults={"features": (32, 64, 128), "num_classes": 10},
    )
)
