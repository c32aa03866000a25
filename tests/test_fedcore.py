"""End-to-end tests of the compiled FL round engine on the virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from olearning_sim_tpu.engine import (
    build_fedcore,
    fedadagrad,
    fedadam,
    fedavg,
    fedavgm,
    fedprox,
    fedyogi,
    make_synthetic_dataset,
)
from olearning_sim_tpu.engine.client_data import (
    make_central_eval_set,
    make_synthetic_text_dataset,
)
from olearning_sim_tpu.engine.fedcore import (
    FedCoreConfig,
    auto_uses_multiplicity,
)
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

INPUT_SHAPE = (16,)
NUM_CLASSES = 4
SEED = 7


def make_core(algorithm, num_clients=32, n_local=24, block=4, max_steps=5):
    plan = make_mesh_plan(dp=8, mp=1)
    cfg = FedCoreConfig(batch_size=8, max_local_steps=max_steps, block_clients=block)
    core = build_fedcore(
        "mlp2",
        algorithm,
        plan,
        cfg,
        model_overrides={"hidden": (32,), "num_classes": NUM_CLASSES},
        input_shape=INPUT_SHAPE,
    )
    ds = make_synthetic_dataset(
        SEED, num_clients, n_local, INPUT_SHAPE, NUM_CLASSES, class_sep=4.0
    ).pad_for(plan, block).place(plan)
    return core, ds, plan


@pytest.mark.parametrize("algorithm", [
    fedavg(0.1), fedprox(0.1, mu=0.05), fedadam(0.1),
    fedyogi(0.1), fedadagrad(0.1, server_lr=0.1), fedavgm(0.1),
])
def test_training_learns(algorithm):
    core, ds, _ = make_core(algorithm)
    state = core.init_state(jax.random.key(0))
    first_loss = None
    for _ in range(15):
        state, metrics = core.round_step(state, ds)
        if first_loss is None:
            first_loss = float(metrics.mean_loss)
    x_eval, y_eval = make_central_eval_set(SEED, 512, INPUT_SHAPE, NUM_CLASSES, class_sep=4.0)
    loss, acc = core.evaluate(state.params, x_eval, y_eval)
    assert float(metrics.mean_loss) < first_loss
    assert acc > 0.75, f"eval acc {acc} too low — engine not learning"


def test_determinism():
    core, ds, _ = make_core(fedavg(0.1))
    outs = []
    for _ in range(2):
        state = core.init_state(jax.random.key(3))
        for _ in range(3):
            state, _ = core.round_step(state, ds)
        outs.append(jax.tree.map(np.asarray, jax.device_get(state.params)))
    jax.tree.map(np.testing.assert_array_equal, outs[0], outs[1])


def test_masked_clients_are_inert():
    """Doubling the population but zero-masking the second half must give the
    same global model as the small population — participation masks implement
    the deviceflow churn semantics, so they must be exactly inert."""
    plan = make_mesh_plan(dp=8, mp=1)
    full = make_synthetic_dataset(SEED, 32, 24, INPUT_SHAPE, NUM_CLASSES, class_sep=4.0)

    core_a, _, _ = make_core(fedavg(0.1), num_clients=16, block=2)
    ds_a = full.take(np.arange(16)).pad_for(plan, 2).place(plan)
    state_a = core_a.init_state(jax.random.key(1))

    core_b, _, _ = make_core(fedavg(0.1), num_clients=32, block=2)
    ds_b = full.pad_for(plan, 2).place(plan)
    state_b = core_b.init_state(jax.random.key(1))

    participate = jnp.asarray((np.arange(ds_b.num_clients) < 16).astype(np.float32))
    participate = jax.device_put(participate, plan.client_sharding())

    for _ in range(3):
        state_a, _ = core_a.round_step(state_a, ds_a)
        state_b, m_b = core_b.round_step(state_b, ds_b, participate=participate)

    assert float(m_b.clients_trained) == 16
    a = jax.device_get(state_a.params)
    b = jax.device_get(state_b.params)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=2e-5, atol=2e-6),
        a, b,
    )


def test_hetero_num_steps():
    """Clients with num_steps=0 contribute zero delta (but keep weight)."""
    core, ds, plan = make_core(fedavg(0.1), num_clients=16, block=2)
    state = core.init_state(jax.random.key(2))
    p0 = jax.device_get(state.params)
    num_steps = jax.device_put(
        jnp.zeros((ds.num_clients,), jnp.int32), plan.client_sharding()
    )
    state, metrics = core.round_step(state, ds, num_steps=num_steps)
    p1 = jax.device_get(state.params)
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-7),
        p0, p1,
    )


def test_padding_weights_zero():
    plan = make_mesh_plan(dp=8, mp=1)
    ds = make_synthetic_dataset(SEED, 10, 8, INPUT_SHAPE, NUM_CLASSES).pad_for(plan, 2)
    assert ds.num_clients == 16
    w = np.asarray(ds.weight)
    assert (w[10:] == 0).all()
    assert (w[:10] > 0).all()


def _mode_case_mlp2():
    ds = make_synthetic_dataset(
        SEED, 32, 12, INPUT_SHAPE, NUM_CLASSES, class_sep=4.0,
        num_samples_range=(4, 12),  # heterogeneity: idx drawn in [0, n_c)
    )
    return ("mlp2", {"hidden": (32,), "num_classes": NUM_CLASSES},
            INPUT_SHAPE, ds)


def _mode_case_distilbert():
    # Token rows: int32, nothing for place() to cast, gathered by row.
    ds = make_synthetic_text_dataset(
        SEED, 16, 12, seq_len=8, num_classes=2, vocab_size=64,
        num_samples_range=(4, 12),
    )
    return ("distilbert", {"vocab_size": 64, "max_len": 8, "width": 16,
                           "depth": 1, "heads": 2, "mlp_dim": 32,
                           "num_classes": 2}, (8,), ds)


@pytest.mark.parametrize("case", [_mode_case_mlp2, _mode_case_distilbert],
                         ids=["mlp2", "distilbert"])
def test_gather_and_multiplicity_modes_agree(case):
    """The two minibatch realizations draw the same indices and must produce
    the same training trajectory (identical math up to float reduction
    order) — the exactness claim behind FedCoreConfig.sample_mode."""
    results = {}
    for mode in ("gather", "multiplicity"):
        plan = make_mesh_plan(dp=8, mp=1)
        cfg = FedCoreConfig(batch_size=8, max_local_steps=3, block_clients=4,
                            sample_mode=mode)
        model, overrides, input_shape, ds = case()
        core = build_fedcore(
            model, fedavg(0.1), plan, cfg,
            model_overrides=overrides, input_shape=input_shape,
        )
        ds = ds.pad_for(plan, 4).place(plan, feature_dtype=None)
        assert core.use_multiplicity(
            ds.x.shape[1], ds.x.shape[2:], ds.x.dtype
        ) == (mode == "multiplicity")
        state = core.init_state(jax.random.key(7))
        for _ in range(2):
            state, metrics = core.round_step(state, ds)
        results[mode] = (
            jax.device_get(state.params), float(metrics.mean_loss)
        )
    pg, lg = results["gather"]
    pm, lm = results["multiplicity"]
    assert lg == pytest.approx(lm, rel=1e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3),
        pg, pm,
    )


# (n_local, batch, bytes of one row, FLOPs to train one row) -> multiplicity?
# ISSUE 26's four families (float32 rows, as the issue priced them), the
# two clauses that shapes alone decide (no more rows than a gathered batch;
# more than twice as many), and the shapes PR 26 timed on the chip,
# bfloat16 rows as the task bridge places them: each on the side that was
# faster there.
@pytest.mark.parametrize("n_local,batch,row_bytes,row_flops,multiplicity", [
    pytest.param(24, 16, 256, 1.65e10, False, id="distilbert_24of16"),
    pytest.param(50, 32, 12_288, 1.55e7, True, id="cnn4_50of32"),
    pytest.param(6, 4, 32, 1.11e5, True, id="tiny_distilbert_6of4"),
    pytest.param(64, 32, 3_136, 9.53e5, True, id="mlp2_64of32"),
    pytest.param(16, 16, 256, 1.65e10, True, id="n_local_eq_batch"),
    pytest.param(8, 16, 256, 1.65e10, True, id="n_local_lt_batch"),
    pytest.param(65, 32, 3_136, 1.0, False, id="n_local_gt_twice_batch"),
    pytest.param(64, 32, 1_568, 9.53e5, True, id="chip_mlp2_64of32"),
    pytest.param(50, 32, 6_144, 1.55e7, True, id="chip_cnn4_50of32"),
    pytest.param(256, 32, 1_568, 9.53e5, False, id="chip_mlp2_256of32"),
    pytest.param(200, 32, 6_144, 1.55e7, False, id="chip_cnn4_200of32"),
    pytest.param(50, 32, 6_144, 2.19e9, False, id="chip_vit_tiny_50of32"),
    pytest.param(40, 20, 1_568, 2.73e9, False, id="chip_resnet18_40of20"),
])
def test_auto_sample_mode_rule(n_local, batch, row_bytes, row_flops,
                               multiplicity):
    priced = []

    def price():
        priced.append(row_flops)
        return row_flops

    assert auto_uses_multiplicity(
        n_local, batch, row_bytes, price) == multiplicity
    # The model is priced only where the shapes do not decide.
    assert bool(priced) == (batch < n_local <= 2 * batch)


def test_auto_sample_mode_prices_the_model():
    """Under ``auto`` the core prices one row of its own model (3 x the
    forward pass's multiply-adds) and decides once per shape."""
    plan = make_mesh_plan(dp=8, mp=1)
    core = build_fedcore(
        "mlp2", fedavg(0.1), plan, FedCoreConfig(batch_size=8),
        model_overrides={"hidden": (32,), "num_classes": NUM_CLASSES},
        input_shape=INPUT_SHAPE,
    )
    flops = core._row_train_flops(INPUT_SHAPE, np.float32)
    assert flops == 3 * 2 * (16 * 32 + 32 * NUM_CLASSES)
    assert core.use_multiplicity(8, INPUT_SHAPE, np.float32)   # clause 1
    assert core.use_multiplicity(12, INPUT_SHAPE, np.float32) == (
        auto_uses_multiplicity(12, 8, 16 * 4, lambda: flops))
    assert set(core._multiplicity) == {(8, INPUT_SHAPE), (12, INPUT_SHAPE)}


def test_unroll_knobs_do_not_change_results():
    """step_unroll / block_unroll are pure scheduling knobs: the RNG streams
    and arithmetic are identical, so the trajectory must match the rolled
    program (same reduction order — exact equality modulo XLA fusion, so
    assert tight allclose rather than bitwise)."""
    results = {}
    for tag, (su, bu) in {"rolled": (1, 1), "unrolled": (5, 2)}.items():
        plan = make_mesh_plan(dp=8, mp=1)
        cfg = FedCoreConfig(batch_size=8, max_local_steps=5, block_clients=2,
                            step_unroll=su, block_unroll=bu)
        core = build_fedcore(
            "mlp2", fedavg(0.1), plan, cfg,
            model_overrides={"hidden": (32,), "num_classes": NUM_CLASSES},
            input_shape=INPUT_SHAPE,
        )
        ds = make_synthetic_dataset(
            SEED, 32, 12, INPUT_SHAPE, NUM_CLASSES, class_sep=4.0
        ).pad_for(plan, 2).place(plan)
        state = core.init_state(jax.random.key(3))
        for _ in range(2):
            state, metrics = core.round_step(state, ds)
        results[tag] = (jax.device_get(state.params), float(metrics.mean_loss))
    (pr, lr), (pu, lu) = results["rolled"], results["unrolled"]
    assert lr == pytest.approx(lu, rel=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6),
        pr, pu,
    )


def test_the_launch_frame_keeps_frame_chunks_from_being_remapped():
    """``FedCore._launch`` calls a program from ``_call_in_roomy_frame``: a
    call pattern that crosses the end of a 16 KB chunk of frame memory again
    and again (every depth is tried, so one of them does) maps and unmaps a
    chunk, one page fault at least, at every crossing when called bare, and
    takes none of those faults below the roomy frame, which has a chunk of
    its own (``PERF.md`` section 7 item 8)."""
    import resource

    from olearning_sim_tpu.engine.fedcore import _call_in_roomy_frame

    def leaf(a=0, b=0, c=0, d=0, e=0, f=0, g=0, h=0):
        return a

    def descend(depth):
        if depth:
            return descend(depth - 1)
        for _ in range(50):
            leaf()

    def faults():
        before = resource.getrusage(resource.RUSAGE_THREAD).ru_minflt
        for depth in range(200):
            descend(depth)
        return resource.getrusage(resource.RUSAGE_THREAD).ru_minflt - before

    faults()  # whatever the first pass touches for the first time
    bare, roomy = faults(), _call_in_roomy_frame(faults)
    assert roomy <= max(16, bare // 4), (bare, roomy)
    assert _call_in_roomy_frame(lambda a, b: a - b, 3, 1) == 2
