"""Accuracy-parity oracle: the compiled TPU engine vs an independent NumPy
FedAvg implementation on the same seed and the same (real-format) MNIST data.

BASELINE.md's headline accuracy target is "within +-0.3% of the CPU
simulation"; this is the in-CI oracle for it (MNIST-MLP small scale; the
same harness runs the real archives when present). The oracle reproduces
the engine's per-client RNG streams (fold_in(fold_in(base_key, uid), round)
then fold_in(key, step) -> randint) so both sides draw identical minibatch
indices; all arithmetic is independent NumPy float32 (the engine computes
bf16 on the MXU — the tolerance absorbs exactly that rounding, nothing
else). Reference analogue: the per-phone subprocess loop it replaces,
``ols_core/taskMgr/utils/utils_run_task.py:481-514``.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from olearning_sim_tpu.engine import build_fedcore, fedavg
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.parallel.mesh import make_mesh_plan
from olearning_sim_tpu.data import load_population, clear_cache

from test_data import make_mnist_dir

C = 32          # clients
N_LOCAL = 40
BATCH = 16
STEPS = 5
ROUNDS = 10
HIDDEN = 64
LR = 0.05


def np_forward(params, x):
    h = np.maximum(x @ params["w1"] + params["b1"], 0.0)
    return h, h @ params["w2"] + params["b2"]


def np_softmax(z):
    z = z - z.max(axis=-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(axis=-1, keepdims=True)


def np_local_train(params, x, y, n, uid, base_key, round_idx,
                   correction=None):
    """One client's local SGD, multiplicity-weighted exactly like the engine
    these tests build (``sample_mode="multiplicity"``, set explicitly: what
    ``auto`` picks depends on the model's cost per row).
    ``correction`` (SCAFFOLD: c - c_i per param) is added to every step's
    gradient."""
    p = {k: v.copy() for k, v in params.items()}
    key = jax.random.fold_in(jax.random.fold_in(base_key, uid), round_idx)
    for i in range(STEPS):
        k = jax.random.fold_in(key, i)
        idx = np.asarray(jax.random.randint(k, (BATCH,), 0, n))
        sw = np.zeros(N_LOCAL, np.float32)
        np.add.at(sw, idx, 1.0)
        sw /= BATCH
        h, logits = np_forward(p, x)
        g_logits = (np_softmax(logits) - np.eye(10, dtype=np.float32)[y]) * sw[:, None]
        gh = (g_logits @ p["w2"].T) * (h > 0)
        grads = {"w1": x.T @ gh, "b1": gh.sum(0),
                 "w2": h.T @ g_logits, "b2": g_logits.sum(0)}
        for name in p:
            g = grads[name]
            if correction is not None:
                g = g + correction[name]
            p[name] = p[name] - LR * g
    return {k: p[k] - params[k] for k in params}


def np_fedavg_round(params, ds, base_key, round_idx):
    num = {k: np.zeros_like(v) for k, v in params.items()}
    den = 0.0
    xs = np.asarray(ds.x, np.float32).reshape(ds.num_clients, N_LOCAL, -1)
    ys = np.asarray(ds.y)
    for c in range(ds.num_clients):
        w = float(ds.weight[c])
        if w <= 0:
            continue
        delta = np_local_train(
            params, xs[c], ys[c], int(ds.num_samples[c]),
            int(ds.client_uid[c]), base_key, round_idx,
        )
        for k in num:
            num[k] += w * delta[k]
        den += w
    return {k: params[k] + num[k] / den for k in params}


@pytest.fixture(scope="module")
def mnist_population(tmp_path_factory):
    clear_cache()
    d = tmp_path_factory.mktemp("mnist_parity")
    make_mnist_dir(str(d), n=2400, seed=7, noise=96)
    ds, eval_data, _ = load_population(
        str(d), num_clients=C, n_local=N_LOCAL, scheme="iid", seed=11, eval_n=600,
    )
    return ds, eval_data


def test_engine_matches_numpy_oracle(mnist_population):
    ds_host, (ex, ey) = mnist_population
    plan = make_mesh_plan(dp=8)
    cfg = FedCoreConfig(batch_size=BATCH, max_local_steps=STEPS, block_clients=2,
                        sample_mode="multiplicity")
    core = build_fedcore(
        "mlp2", fedavg(LR), plan, cfg,
        model_overrides={"hidden": [HIDDEN], "num_classes": 10},
        input_shape=(28, 28, 1),
    )
    state = core.init_state(jax.random.key(0))
    # round_step donates state, so keep an undonated copy of the key for the
    # oracle's identical RNG draws.
    base_key = jax.random.wrap_key_data(np.asarray(jax.random.key_data(state.base_key)))

    # Oracle starts from the engine's initial params (parity of the training
    # dynamics; initialization is jax.nn's business).
    p0 = jax.tree.map(np.asarray, state.params)
    oracle = {
        "w1": np.asarray(p0["Dense_0"]["kernel"], np.float32),
        "b1": np.asarray(p0["Dense_0"]["bias"], np.float32),
        "w2": np.asarray(p0["Dense_1"]["kernel"], np.float32),
        "b2": np.asarray(p0["Dense_1"]["bias"], np.float32),
    }

    ds = ds_host.pad_for(plan, 2).place(plan, feature_dtype=None)
    for r in range(ROUNDS):
        state, metrics = core.round_step(state, ds)
        oracle = np_fedavg_round(oracle, ds_host, base_key, r)

    # Engine accuracy vs oracle accuracy on the held-out set.
    _, acc_engine = core.evaluate(state.params, ex.reshape(len(ex), -1).astype(np.float32)
                                  .reshape(len(ex), 28, 28, 1), ey)
    _, logits = np_forward(oracle, ex.reshape(len(ex), -1).astype(np.float32))
    acc_oracle = float((logits.argmax(-1) == ey).mean())
    assert abs(float(acc_engine) - acc_oracle) <= 0.003, (
        f"engine acc {float(acc_engine):.4f} vs oracle acc {acc_oracle:.4f}"
    )

    # Parameter-level agreement (loose: absorbs bf16 rounding, catches real
    # divergence like wrong weights/aggregation order).
    pe = jax.tree.map(np.asarray, state.params)
    for got, want in (
        (pe["Dense_0"]["kernel"], oracle["w1"]),
        (pe["Dense_1"]["kernel"], oracle["w2"]),
    ):
        rel = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-9)
        assert rel < 0.02, f"relative param divergence {rel:.4f}"


def test_oracle_learns(mnist_population):
    """Sanity: the oracle itself reaches non-trivial accuracy (so the parity
    assertion compares two *working* implementations)."""
    ds_host, (ex, ey) = mnist_population
    rng = np.random.default_rng(0)
    oracle = {
        "w1": rng.normal(0, 784 ** -0.5, (784, HIDDEN)).astype(np.float32),
        "b1": np.zeros(HIDDEN, np.float32),
        "w2": rng.normal(0, HIDDEN ** -0.5, (HIDDEN, 10)).astype(np.float32),
        "b2": np.zeros(10, np.float32),
    }
    base_key = jax.random.key(123)
    for r in range(ROUNDS):
        oracle = np_fedavg_round(oracle, ds_host, base_key, r)
    _, logits = np_forward(oracle, ex.reshape(len(ex), -1).astype(np.float32))
    acc = (logits.argmax(-1) == ey).mean()
    assert acc > 0.8, f"oracle failed to learn: acc={acc:.3f}"


# ----------------------------------------------------------------- SCAFFOLD
def np_local_train_scaffold(params, x, y, n, uid, base_key, round_idx, c, ci):
    """Oracle SCAFFOLD local loop: every step's gradient corrected by
    + c - c_i (shared SGD body); option-II refresh dci = -c - delta/(K*lr)."""
    correction = {k: c[k] - ci[k] for k in params}
    delta = np_local_train(params, x, y, n, uid, base_key, round_idx,
                           correction=correction)
    dci = {k: -c[k] - delta[k] / (STEPS * LR) for k in params}
    return delta, dci


def np_scaffold_round(params, ds, base_key, round_idx, c, cis,
                      total_clients=None):
    num = {k: np.zeros_like(v) for k, v in params.items()}
    sum_dc = {k: np.zeros_like(v) for k, v in params.items()}
    den = 0.0
    count = 0
    xs = np.asarray(ds.x, np.float32).reshape(ds.num_clients, N_LOCAL, -1)
    ys = np.asarray(ds.y)
    for cl in range(ds.num_clients):
        w = float(ds.weight[cl])
        if w <= 0:
            continue
        delta, dci = np_local_train_scaffold(
            params, xs[cl], ys[cl], int(ds.num_samples[cl]),
            int(ds.client_uid[cl]), base_key, round_idx, c, cis[cl],
        )
        for k in num:
            num[k] += w * delta[k]
            sum_dc[k] += w * dci[k]
            cis[cl][k] = cis[cl][k] + dci[k]
        den += w
        count += 1
    # The engine's N counts the PADDED population (fedcore docstring);
    # mirror it so the server-control scale matches at any client count.
    frac = count / (total_clients if total_clients else ds.num_clients)
    new_params = {k: params[k] + num[k] / den for k in params}
    new_c = {k: c[k] + frac * (sum_dc[k] / den) for k in params}
    return new_params, new_c


def test_scaffold_engine_matches_numpy_oracle(mnist_population):
    """The SCAFFOLD implementation (drift-corrected steps, option-II control
    refresh, weighted server-control update) agrees with an independent
    NumPy implementation on identical RNG streams."""
    from olearning_sim_tpu.engine import scaffold

    ds_host, (ex, ey) = mnist_population
    plan = make_mesh_plan(dp=8)
    cfg = FedCoreConfig(batch_size=BATCH, max_local_steps=STEPS,
                        block_clients=2, sample_mode="multiplicity")
    core = build_fedcore(
        "mlp2", scaffold(local_lr=LR), plan, cfg,
        model_overrides={"hidden": [HIDDEN], "num_classes": 10},
        input_shape=(28, 28, 1),
    )
    ds = ds_host.pad_for(plan, 2)
    state = core.init_state(jax.random.key(0))
    control = core.init_control(state, ds.num_clients)
    base_key = jax.random.wrap_key_data(
        np.asarray(jax.random.key_data(state.base_key))
    )

    p0 = jax.tree.map(np.asarray, state.params)
    oracle = {
        "w1": np.asarray(p0["Dense_0"]["kernel"], np.float32),
        "b1": np.asarray(p0["Dense_0"]["bias"], np.float32),
        "w2": np.asarray(p0["Dense_1"]["kernel"], np.float32),
        "b2": np.asarray(p0["Dense_1"]["bias"], np.float32),
    }
    oc = {k: np.zeros_like(v) for k, v in oracle.items()}
    ocis = [{k: np.zeros_like(v) for k, v in oracle.items()}
            for _ in range(ds_host.num_clients)]

    # N in the server-control update is the TRUE population (the engine
    # threads ds.num_real_clients in), so the oracle uses the same N and the
    # trajectory is invariant to dp/block padding.
    ds = ds.place(plan, feature_dtype=None)
    for r in range(ROUNDS):
        state, metrics, control = core.round_step(state, ds, control=control)
        oracle, oc = np_scaffold_round(oracle, ds_host, base_key, r, oc, ocis,
                                       total_clients=ds_host.num_clients)

    _, acc_engine = core.evaluate(
        state.params, ex.astype(np.float32).reshape(len(ex), 28, 28, 1), ey
    )
    _, logits = np_forward(oracle, ex.reshape(len(ex), -1).astype(np.float32))
    acc_oracle = float((logits.argmax(-1) == ey).mean())
    assert abs(float(acc_engine) - acc_oracle) <= 0.003, (
        f"engine acc {float(acc_engine):.4f} vs oracle acc {acc_oracle:.4f}"
    )

    pe = jax.tree.map(np.asarray, state.params)
    sc = jax.tree.map(np.asarray, control.server_control)
    for got, want in (
        (pe["Dense_0"]["kernel"], oracle["w1"]),
        (pe["Dense_1"]["kernel"], oracle["w2"]),
        (sc["Dense_0"]["kernel"], oc["w1"]),
        (sc["Dense_1"]["kernel"], oc["w2"]),
    ):
        rel = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-9)
        assert rel < 0.03, f"relative divergence {rel:.4f}"
