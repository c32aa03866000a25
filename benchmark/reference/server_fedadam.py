"""FedAdam server step (Reddi et al. 2021, "Adaptive Federated
Optimization"), written from the published equations with Adam's bias
correction: the pseudo-gradient is the negative weighted-mean client delta.

    g = -mean_delta
    m = b1 m + (1 - b1) g            v = b2 v + (1 - b2) g^2
    t = count + 1
    update = -lr * (m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps)

``opt`` holds ``m``, ``v`` (flat ``{path: array}``) and ``count`` as read
from the state the round starts from.
"""

import numpy as np


def step(mean_delta, opt, algorithm):
    lr = float(algorithm["server_lr"])
    b1 = float(algorithm.get("b1", 0.9))
    b2 = float(algorithm.get("b2", 0.99))
    eps = float(algorithm.get("eps", 1e-3))
    t = int(opt["count"]) + 1
    update, m_new, v_new = {}, {}, {}
    for k, d in mean_delta.items():
        g = -np.asarray(d, np.float32)
        m = b1 * np.asarray(opt["m"][k], np.float32) + (1.0 - b1) * g
        v = b2 * np.asarray(opt["v"][k], np.float32) + (1.0 - b2) * g * g
        m_hat = m / (1.0 - b1 ** t)
        v_hat = v / (1.0 - b2 ** t)
        update[k] = (-lr * m_hat / (np.sqrt(v_hat) + eps)).astype(np.float32)
        m_new[k], v_new[k] = m, v
    return update, {"m": m_new, "v": v_new, "count": t}


def recover_mean_delta(opt_before, opt_after, algorithm):
    """The weighted-mean client delta as the optimizer got it, worked out
    from its first moment before and after the step:
    g = (m' - b1 m) / (1 - b1), mean_delta = -g."""
    b1 = float(algorithm.get("b1", 0.9))
    return {k: -(np.asarray(opt_after["m"][k], np.float32)
                 - b1 * np.asarray(opt_before["m"][k], np.float32)) / (1.0 - b1)
            for k in opt_before["m"]}
