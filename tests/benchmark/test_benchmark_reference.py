"""The plain references against the program's models at a tiny size on the
CPU: forward, loss and gradients, and the server steps against optax."""

import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import config_contract
import tiny_preset
from benchmark import check, manifest
from benchmark.reference import fedround


def _weights(rng, n, batch):
    sw = np.zeros(n, np.float32)
    np.add.at(sw, rng.integers(0, n, batch), 1.0 / batch)
    return sw


def test_distilbert_reference_matches_the_flax_model_in_float32():
    from olearning_sim_tpu.models.transformer import TextTransformer

    ref = manifest.find_module("reference", "distilbert")
    model = TextTransformer(vocab_size=64, max_len=8, width=16, depth=2,
                            heads=2, mlp_dim=32, dtype=jnp.float32)
    assert nn.LayerNorm().epsilon == ref.LN_EPS
    rng = np.random.default_rng(1)
    tokens = rng.integers(1, 64, (6, 8)).astype(np.int32)
    tokens[0, 5:] = 0                      # padding is masked everywhere
    tokens[3, 2:] = 0
    y = rng.integers(0, 2, 6)
    sw = _weights(rng, 6, 4)
    params = model.init(jax.random.key(0), jnp.asarray(tokens[:1]))["params"]
    flat = ref.prepare(check.flatten(params))

    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: model.apply({"params": p}, t))(
            params, jnp.asarray(tokens))
    np.testing.assert_allclose(
        np.asarray(ref.forward(flat, jnp.asarray(tokens))), np.asarray(got),
        rtol=1e-4, atol=1e-5)

    def loss_fn(p):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            model.apply({"params": p}, jnp.asarray(tokens)), jnp.asarray(y))
        return (jnp.asarray(sw) * ce).sum()

    want_loss, want = jax.jit(jax.value_and_grad(loss_fn))(params)
    loss, grads = ref.loss_and_grad(flat, tokens, y, sw)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    worst = check.worst_leaf(
        {k: np.asarray(v) for k, v in grads.items()}, check.flatten(want))
    assert worst["rel_l2"] < 1e-3, worst
    # A pad position's embedding row gets no gradient except through id 0.
    assert set(grads) == set(check.flatten(want))


def _configs():
    with open(manifest.MANIFEST, encoding="utf-8") as f:
        return [c["name"] for c in json.load(f)["configs"]]


@pytest.mark.parametrize("name", _configs())
def test_the_programs_model_is_what_the_configuration_file_states(name):
    """What holds for any configuration: the task names a model of the
    registry; every size the file's ``model`` block states through
    ``model_keys`` is the size the program builds for the task as composed
    (registry defaults + the task's overrides); the task's input is the
    stated one; the model initialises; the reference and the server step
    have the functions the check and the FLOP count call. What is one
    model's (its tree, its head, its parameter total) is in that
    configuration's own ``test_config_<name>.py``."""
    config, task_model, spec = config_contract.load(name)
    stated = config["model"]
    built = {**spec.defaults, **task_model.get("overrides", {})}
    for file_key, program_key in config.get("model_keys", {}).items():
        assert built[program_key] == stated[file_key], file_key
    assert list(task_model["input_shape"]) == config_contract.stated_input(
        stated)
    assert config_contract.init_shapes(spec, task_model)
    reference = manifest.find_module("reference", config["reference"])
    for function in ("prepare", "loss_and_grad", "layers"):
        assert callable(getattr(reference, function)), function
    assert reference.layers(stated)
    server = manifest.find_module(
        "reference", "server_" + config["algorithm"]["name"])
    assert callable(server.step) and callable(server.recover_mean_delta)


@pytest.mark.parametrize("name", _configs())
def test_every_configuration_brings_its_contract_test_and_tiny_preset(name):
    """A configuration without a contract test of its own, or without the
    preset the CPU harness tests run it at, is refused here by name."""
    here = os.path.dirname(os.path.abspath(__file__))
    contract = os.path.join(here, f"test_config_{name}.py")
    assert os.path.exists(contract), (
        f"configuration {name!r} has no contract test: add {contract}")
    preset = tiny_preset.load(name)         # raises, naming the file
    for key in ("overrides", "model", "input_shape", "traffic", "limits",
                "why"):
        assert key in preset, (name, key)


def test_a_missing_tiny_preset_is_an_error_that_names_the_file():
    with pytest.raises(FileNotFoundError, match=r"data/tiny/no_such\.json"):
        tiny_preset.load("no_such")


def test_minibatch_weights_are_the_engines_stream():
    key = jax.random.key(3)
    sw = fedround.minibatch_weights(key, uid=5, round_idx=2, step=1,
                                    batch_size=8, num_samples=6, n_local=6)
    k = jax.random.fold_in(jax.random.fold_in(key, 5), 2)
    idx = np.asarray(jax.random.randint(jax.random.fold_in(k, 1), (8,), 0, 6))
    want = np.bincount(idx, minlength=6) / 8.0
    np.testing.assert_allclose(sw, want)
    assert sw.sum() == pytest.approx(1.0)


@pytest.mark.parametrize("b1,b2,eps", [(0.9, 0.99, 1e-3), (0.8, 0.999, 1e-8)])
def test_fedadam_server_step_matches_optax_over_three_rounds(b1, b2, eps):
    server = manifest.find_module("reference", "server_fedadam")
    algorithm = {"name": "fedadam", "server_lr": 0.001, "b1": b1, "b2": b2,
                 "eps": eps}

    def make():
        return optax.adam(0.001, b1=b1, b2=b2, eps=eps)

    rng = np.random.default_rng(2)
    params = {"a/kernel": rng.standard_normal((3, 4)).astype(np.float32),
              "b/bias": rng.standard_normal(4).astype(np.float32)}
    tx = make()
    state = tx.init(params)
    for _ in range(3):
        delta = {k: (1e-3 * rng.standard_normal(v.shape)).astype(np.float32)
                 for k, v in params.items()}
        before = check.adam_state(state)
        updates, state = tx.update(
            jax.tree.map(lambda d: -d, delta), state, params)
        got, _ = server.step(delta, before, algorithm)
        for k in params:
            np.testing.assert_allclose(got[k], np.asarray(updates[k]),
                                       rtol=2e-5, atol=1e-9)
        recovered = server.recover_mean_delta(
            before, check.adam_state(state), algorithm)
        for k in params:
            np.testing.assert_allclose(recovered[k], delta[k],
                                       rtol=1e-3, atol=1e-8)


@pytest.mark.parametrize("server_lr", [1.0, 0.5])
def test_fedavg_server_step_matches_optax_over_three_rounds(server_lr):
    server = manifest.find_module("reference", "server_fedavg")
    algorithm = {"name": "fedavg", "server_lr": server_lr}
    rng = np.random.default_rng(4)
    params = {"a/kernel": rng.standard_normal((3, 4)).astype(np.float32),
              "b/bias": rng.standard_normal(4).astype(np.float32)}
    tx = optax.sgd(server_lr)
    state = tx.init(params)
    assert check.adam_state(state) is None          # nothing to carry
    for _ in range(3):
        delta = {k: (1e-3 * rng.standard_normal(v.shape)).astype(np.float32)
                 for k, v in params.items()}
        updates, state = tx.update(
            jax.tree.map(lambda d: -d, delta), state, params)
        got, carried = server.step(delta, None, algorithm)
        assert carried is None
        for k in params:
            np.testing.assert_allclose(got[k], np.asarray(updates[k]),
                                       rtol=1e-6, atol=0)
        params = {k: params[k] + got[k] for k in params}
    assert server.recover_mean_delta(None, None, algorithm) is None


def test_worst_leaf_floors_small_leaves_at_the_median():
    ref = {"big": np.ones(100, np.float32), "mid": np.ones(4, np.float32),
           "tiny": np.full(4, 1e-6, np.float32)}
    prog = {k: v.copy() for k, v in ref.items()}
    prog["tiny"] = prog["tiny"] * 3          # 200% off, but all but zero
    out = check.worst_leaf(prog, ref)
    assert out["rel_l2"] == pytest.approx(2 * np.linalg.norm(ref["tiny"]) / 2.0)
    prog["big"] = prog["big"] * 1.5
    out = check.worst_leaf(prog, ref)
    assert out["leaf"] == "big" and out["rel_l2"] == pytest.approx(0.5)
    assert out["norm_gap"] == pytest.approx(0.5)
    prog["mid"][0] = np.nan
    assert check.worst_leaf(prog, ref)["rel_l2"] == np.inf


def test_sample_clients_is_seeded_and_keeps_both_ends():
    a = check.sample_clients(1000, 8, seed=2**31 + 5)
    assert a == check.sample_clients(1000, 8, seed=2**31 + 5)
    assert a != check.sample_clients(1000, 8, seed=6)
    assert a[0] == 0 and a[-1] == 999 and len(set(a)) == 8
    assert check.sample_clients(3, 8, seed=1) == [0, 1, 2]
