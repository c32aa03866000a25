"""One federated round in plain float32, one client after another: no vmap,
no client blocks, no engine code. The model's arithmetic comes from
``benchmark/reference/<model>.py`` (``prepare``, ``loss_and_grad``), the
server step from ``benchmark/reference/server_<algorithm>.py``.

The only thing shared with the program is the random stream that picks each
local step's minibatch — an input of the round, not the system under test:
``fold_in(fold_in(base_key, uid), round)`` then ``fold_in(key, step)`` ->
``randint(batch_size)`` over the client's valid samples. A minibatch with
repeats is the same gradient as its distinct samples weighted by how often
each was drawn, which is how the loss is written here.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import numpy as np


def minibatch_weights(base_key, uid: int, round_idx: int, step: int,
                      batch_size: int, num_samples: int, n_local: int
                      ) -> np.ndarray:
    """[n_local] multiplicity of each local sample in this step's minibatch,
    over ``batch_size``."""
    key = jax.random.fold_in(jax.random.fold_in(base_key, int(uid)),
                             int(round_idx))
    idx = np.asarray(jax.random.randint(
        jax.random.fold_in(key, step), (batch_size,), 0, max(int(num_samples), 1)))
    sw = np.zeros(n_local, np.float32)
    np.add.at(sw, idx, 1.0)
    return sw / np.float32(batch_size)


def local_sgd(model, params0: Dict[str, Any], x, y, *, num_samples: int,
              uid: int, base_key, round_idx: int, steps: int, batch_size: int,
              lr: float) -> Tuple[Dict[str, Any], float]:
    """One client's local SGD from the global model. Returns (delta, the
    mean of its steps' minibatch losses)."""
    p = dict(params0)
    losses = []
    for i in range(steps):
        sw = minibatch_weights(base_key, uid, round_idx, i, batch_size,
                               num_samples, len(y))
        loss, grads = model.loss_and_grad(p, x, y, sw)
        p = {k: p[k] - np.float32(lr) * grads[k] for k in p}
        losses.append(float(loss))
    return {k: p[k] - params0[k] for k in p}, float(np.mean(losses))


def reference_round(model, server, algorithm: Dict[str, Any],
                    params: Dict[str, np.ndarray], opt: Dict[str, Any],
                    clients: Sequence[Dict[str, Any]], base_key,
                    round_idx: int, steps: int, batch_size: int
                    ) -> Dict[str, Any]:
    """The round over ``clients`` (dicts with x, y, num_samples, uid,
    weight). Returns the change of the global parameters per leaf, the
    weighted-mean client delta and each client's loss."""
    p0 = model.prepare(params)
    total = float(sum(c["weight"] for c in clients))
    mean_delta = None
    losses = []
    for c in clients:
        delta, loss = local_sgd(
            model, p0, c["x"], c["y"], num_samples=c["num_samples"],
            uid=c["uid"], base_key=base_key, round_idx=round_idx,
            steps=steps, batch_size=batch_size, lr=algorithm["local_lr"])
        w = np.float32(c["weight"] / total)
        mean_delta = ({k: w * d for k, d in delta.items()}
                      if mean_delta is None else
                      {k: mean_delta[k] + w * delta[k] for k in delta})
        losses.append(loss)
    mean_delta = {k: np.asarray(v, np.float32) for k, v in mean_delta.items()}
    update, _ = server.step(mean_delta, opt, algorithm)
    return {"param_delta": update, "mean_delta": mean_delta,
            "client_loss": losses}
