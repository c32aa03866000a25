"""Plain float32 reference for the two-layer perceptron (``models/mlp.py``'s
``mlp2``): flatten, Dense(hidden) + ReLU, Dense(num_classes); the weighted
cross-entropy loss of one client's minibatch and its gradient, in
straightforward ``jax.numpy`` at ``highest`` matmul precision. No flax, no
engine code, no vmap over clients.

Departure from the program, on purpose: everything here is float32 (the
program casts its input and its hidden layer to bfloat16 and keeps the
output layer float32).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops

NAME = "mlp2"


def layers(model: dict) -> List[flops.Layer]:
    """Forward matmul-like layers of one sample, for benchmark/flops.py."""
    (hidden,) = model["hidden"]
    return [flops.dense("fc0", 1, model["input_size"], hidden,
                        input_grad=False),
            flops.dense("fc1", 1, hidden, model["num_classes"])]


def forward(params: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """x [B, ...] float32 -> logits [B, K]. ``params`` is the flat
    ``{path: array}`` form of the model's tree."""
    h = x.reshape((x.shape[0], -1)) @ params["Dense_0/kernel"] \
        + params["Dense_0/bias"]
    return jnp.maximum(h, 0.0) @ params["Dense_1/kernel"] \
        + params["Dense_1/bias"]


def _loss(params, x, y, sw):
    logp = jax.nn.log_softmax(forward(params, x), axis=-1)
    ce = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return (sw * ce).sum()


@jax.jit
def _value_and_grad(params, x, y, sw):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(_loss)(params, x, y, sw)


def loss_and_grad(params: Dict[str, jax.Array], x, y, sw
                  ) -> Tuple[float, Dict[str, jax.Array]]:
    """loss = sum_i sw_i * CE(logits_i, y_i) and its gradient."""
    loss, grads = _value_and_grad(
        params, jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.int32),
        jnp.asarray(sw, jnp.float32))
    return float(loss), grads


def prepare(params: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
