"""Model zoo: shapes, compile, and a tiny end-to-end round per family.

Covers the BASELINE config families beyond MLP/CNN: resnet18 (FEMNIST
shapes), vit_tiny (CIFAR-100 shapes), distilbert (Sent140 token shapes).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from olearning_sim_tpu.engine import (
    build_fedcore,
    fedavg,
    make_synthetic_dataset,
    make_synthetic_text_dataset,
)
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.models import get_model
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

# (name, tiny overrides, batch input shape override)
CASES = [
    ("resnet18", {"stage_features": (8, 16), "blocks_per_stage": (1, 1), "groups": 4}, None),
    ("vit_tiny", {"width": 16, "depth": 2, "heads": 2, "mlp_dim": 32}, None),
    (
        "distilbert",
        {"vocab_size": 97, "max_len": 16, "width": 16, "depth": 2, "heads": 2, "mlp_dim": 32},
        (16,),
    ),
]


@pytest.mark.parametrize("name,overrides,in_shape", CASES)
def test_forward_shapes(name, overrides, in_shape):
    spec = get_model(name)
    model = spec.build(**overrides)
    shape = in_shape or spec.example_input_shape
    x = jnp.zeros((2,) + shape, spec.input_dtype)
    params = model.init(jax.random.key(0), x)["params"]
    out = jax.jit(lambda p, x: model.apply({"params": p}, x))(params, x)
    assert out.shape == (2, spec.num_classes)
    assert out.dtype == jnp.float32
    assert bool(jnp.isfinite(out).all())


def test_full_geometry_param_counts():
    """The default geometries are the real model families, not toys."""
    counts = {}
    for name in ("resnet18", "vit_tiny", "distilbert"):
        spec = get_model(name)
        model = spec.build()
        x = jnp.zeros((1,) + spec.example_input_shape, spec.input_dtype)
        params = jax.eval_shape(lambda x: model.init(jax.random.key(0), x), x)["params"]
        counts[name] = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    assert 10.5e6 < counts["resnet18"] < 12.5e6     # ResNet-18 ~11.2M
    assert 5e6 < counts["vit_tiny"] < 7e6           # ViT-Ti ~5.6M (CIFAR patching)
    assert 55e6 < counts["distilbert"] < 75e6       # DistilBERT ~66M


def test_resnet_round_step():
    plan = make_mesh_plan()
    cfg = FedCoreConfig(batch_size=2, max_local_steps=2, block_clients=2)
    core = build_fedcore(
        "resnet18", fedavg(0.05), plan, cfg,
        model_overrides={"stage_features": (8, 16), "blocks_per_stage": (1, 1), "groups": 4},
    )
    ds = (
        make_synthetic_dataset(
            seed=0, num_clients=16, n_local=4, input_shape=(28, 28, 1), num_classes=62
        )
        .pad_for(plan, cfg.block_clients)
        .place(plan)
    )
    state = core.init_state(jax.random.key(0))
    state, metrics = core.round_step(state, ds)
    assert np.isfinite(float(metrics.mean_loss))
    assert int(metrics.clients_trained) == 16


def test_text_round_step():
    plan = make_mesh_plan()
    cfg = FedCoreConfig(batch_size=2, max_local_steps=2, block_clients=2)
    overrides = {"vocab_size": 97, "max_len": 16, "width": 16, "depth": 2, "heads": 2, "mlp_dim": 32}
    core = build_fedcore(
        "distilbert", fedavg(0.05), plan, cfg,
        model_overrides=overrides, input_shape=(16,),
    )
    ds = (
        make_synthetic_text_dataset(
            seed=0, num_clients=16, n_local=4, seq_len=16, num_classes=2, vocab_size=97
        )
        .pad_for(plan, cfg.block_clients)
        .place(plan)
    )
    state = core.init_state(jax.random.key(0))
    state, metrics = core.round_step(state, ds)
    assert np.isfinite(float(metrics.mean_loss))
    assert int(metrics.clients_trained) == 16


def test_task_bridge_drives_text_family():
    """A task JSON naming the token model gets the text population (int32
    tokens), not float features, end to end through the bridge."""
    import json as _json

    from tests.test_taskmgr import make_task_json
    from olearning_sim_tpu.engine.task_bridge import build_runner_from_taskconfig

    js = make_task_json(task_id="ttext", rounds=1, num_clients=8)
    op = js["operatorflow"]["operators"][0]
    op["logical_simulation"]["operator_params"] = _json.dumps({
        "model": {"name": "distilbert",
                  "overrides": {"vocab_size": 97, "max_len": 12, "width": 16,
                                "depth": 1, "heads": 2, "mlp_dim": 32},
                  "input_shape": [12]},
        "algorithm": {"name": "fedadam", "local_lr": 0.1},
        "fedcore": {"batch_size": 2, "max_local_steps": 2, "block_clients": 2},
        "data": {"synthetic": {"seed": 1, "n_local": 4, "num_classes": 2,
                               "vocab_size": 97}, "eval_n": 32},
    })
    runner = build_runner_from_taskconfig(js)
    history = runner.run()
    assert len(history) == 1
    rec = history[0]["train"]["data_0"]
    assert np.isfinite(rec["mean_loss"])
    assert rec["clients_trained"] == 8


def test_text_dataset_learnable_and_padded():
    ds = make_synthetic_text_dataset(
        seed=1, num_clients=8, n_local=6, seq_len=12, num_classes=2, vocab_size=101
    )
    assert ds.x.dtype == np.int32
    assert ds.x.min() >= 1  # 0 reserved for padding
    assert ds.x.max() < 101
    # class token bands differ: mean token id separates labels
    x0 = ds.x[ds.y == 0].mean()
    x1 = ds.x[ds.y == 1].mean()
    assert abs(x0 - x1) > 5


def test_moe_round_step():
    """The Switch-MoE family trains per-client through the compiled round
    program (routing is static-shaped one-hot einsums, so it vmaps)."""
    plan = make_mesh_plan()
    cfg = FedCoreConfig(batch_size=2, max_local_steps=2, block_clients=2)
    overrides = {"vocab_size": 97, "max_len": 16, "width": 16, "depth": 1,
                 "heads": 2, "mlp_dim": 32, "num_experts": 4}
    core = build_fedcore(
        "moe_text", fedavg(0.05), plan, cfg,
        model_overrides=overrides, input_shape=(16,),
    )
    ds = (
        make_synthetic_text_dataset(
            seed=0, num_clients=16, n_local=4, seq_len=16, num_classes=2,
            vocab_size=97,
        )
        .pad_for(plan, cfg.block_clients)
        .place(plan)
    )
    state = core.init_state(jax.random.key(0))
    state, metrics = core.round_step(state, ds)
    assert np.isfinite(float(metrics.mean_loss))
    assert int(metrics.clients_trained) == 16


def test_moe_aux_loss_threaded_into_fl_path():
    """build_fedcore detects the Switch router's sown aux loss and wires it
    into per-client training (ADVICE r2: without this the gate trains with
    zero balancing pressure federated); dense models get no aux plumbing."""
    plan = make_mesh_plan()
    cfg = FedCoreConfig(batch_size=2, max_local_steps=1, block_clients=2)
    overrides = {"vocab_size": 97, "max_len": 16, "width": 16, "depth": 2,
                 "heads": 2, "mlp_dim": 32, "num_experts": 4}
    core = build_fedcore("moe_text", fedavg(0.05), plan, cfg,
                         model_overrides=overrides, input_shape=(16,))
    assert core.apply_aux_fn is not None
    x = jnp.ones((3, 16), jnp.int32)
    state = core.init_state(jax.random.key(0))
    logits, aux = core.apply_aux_fn(state.params, x)
    # Mean over the 2 blocks, so aux is O(1) regardless of depth (matches
    # ep_train_step), and it must be differentiable wrt the gate kernel.
    assert np.isfinite(float(aux)) and float(aux) > 0.5
    g = jax.grad(lambda p: core.apply_aux_fn(p, x)[1])(state.params)
    gate_g = [np.abs(np.asarray(v)).sum()
              for k, v in jax.tree_util.tree_flatten_with_path(g)[0]
              if "gate" in str(k)]
    assert gate_g and max(gate_g) > 0.0

    dense = build_fedcore("mlp2", fedavg(0.05), plan, cfg)
    assert dense.apply_aux_fn is None


@pytest.mark.parametrize("impl", ["flash", "dence"])
def test_text_transformer_refuses_an_unknown_attention_impl(impl):
    """A value the family does not have is refused when the model is built,
    with the two it has: a stored task that names a removed implementation
    (or misspells one) does not quietly train the dense program."""
    with pytest.raises(ValueError, match="'dense' and 'ring'"):
        get_model("distilbert").build(attention_impl=impl)
