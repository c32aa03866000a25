"""FedAvg server step (McMahan et al. 2017, "Communication-Efficient
Learning of Deep Networks from Decentralized Data"): the global parameters
move by the weighted-mean client delta, scaled by the server's rate.

    update = server_lr * mean_delta

The server keeps no memory: ``opt`` is None going in and coming out, and
there is no state to work the mean delta back out of, so
``recover_mean_delta`` reports nothing and the check compares the
parameters' change itself (which is the mean delta at ``server_lr`` 1, the
published algorithm).
"""

import numpy as np


def step(mean_delta, opt, algorithm):
    lr = np.float32(algorithm.get("server_lr", 1.0))
    return {k: (lr * np.asarray(d, np.float32)).astype(np.float32)
            for k, d in mean_delta.items()}, None


def recover_mean_delta(opt_before, opt_after, algorithm):
    return None
