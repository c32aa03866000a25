"""A lookup-only table trained by the rows a local step reads
(``models/lookup.py``, ``FedCore._masked_sgd``): the by-rows step gives the
dense step's round, the dense step stays wherever by-rows would not be its
result, and the lowered step holds no table-sized gradient.

The dense program comes from a test-local twin: the tiny DistilBERT built
with plain ``nn.Embed`` in the mark's place."""

import contextlib
import re
from unittest import mock

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from olearning_sim_tpu.engine import (
    build_fedcore,
    ditto,
    fedadam,
    fedavg,
    fedprox,
    scaffold,
)
from olearning_sim_tpu.engine.client_data import make_synthetic_text_dataset
from olearning_sim_tpu.engine.fedcore import FedCore, FedCoreConfig
from olearning_sim_tpu.models import transformer
from olearning_sim_tpu.parallel.mesh import make_mesh_plan

VOCAB, WIDTH, SEQ = 96, 16, 8
OVERRIDES = {"vocab_size": VOCAB, "max_len": SEQ, "width": WIDTH, "depth": 1,
             "heads": 2, "mlp_dim": 32, "num_classes": 2}
BLOCK = 2
TABLE = "['Embed_0']['embedding']"


@contextlib.contextmanager
def unmarked():
    """The twin: ``TextTransformer`` with ``nn.Embed`` where it marks its
    lookup. Hold it open while the twin builds, traces and runs."""
    with mock.patch.object(transformer, "LookupOnlyEmbed", nn.Embed):
        yield


def make_core(algorithm, dp=8, mp=1, pp=1, dtype=jnp.bfloat16, depth=1,
              **cfg):
    plan = make_mesh_plan(dp=dp, mp=mp, pp=pp)
    cfg = FedCoreConfig(**{"batch_size": 8, "max_local_steps": 3,
                           "block_clients": BLOCK, **cfg})
    core = build_fedcore(
        "distilbert", algorithm, plan, cfg,
        model_overrides={**OVERRIDES, "dtype": dtype, "depth": depth},
        input_shape=(SEQ,), microbatches=2 if pp > 1 else None)
    return core, plan


def make_ds(plan, vocab=VOCAB):
    # 12 rows of 8 tokens from a small vocabulary: every batch of 8 rows
    # repeats tokens, inside a row and across rows.
    return make_synthetic_text_dataset(
        7, 16, 12, seq_len=SEQ, num_classes=2, vocab_size=vocab,
        num_samples_range=(4, 12),
    ).pad_for(plan, BLOCK).place(plan)


def round_after(core, plan, **round_kw):
    ds = make_ds(plan, vocab=24)
    state = core.init_state(jax.random.key(0))
    before = jax.device_get(state.params)
    out = core.round_step(state, ds, **round_kw)
    after = jax.device_get(out[0].params)
    return after, jax.tree.map(np.subtract, after, before), out[1]


def leaves(tree):
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


# ------------------------------------------------- (a) by rows == dense
@pytest.mark.parametrize("case", [
    dict(sample_mode="gather"),
    dict(sample_mode="multiplicity"),
    dict(sample_mode="gather", max_local_steps=1),
    dict(sample_mode="gather", steps="hetero"),
    dict(sample_mode="gather", carry_dtype=jnp.bfloat16),
], ids=["gather", "multiplicity", "one_step", "inactive_steps", "carry_bf16"])
def test_a_round_by_rows_is_the_dense_round(case):
    """Batches of 8 rows x 8 tokens from 23 ids repeat tokens inside a row
    and across rows. The encoder computes in float32 here: in bfloat16 a
    last-bit difference in a table row flips an activation's rounding now
    and then, and three steps on the other leaves read 1e-4 apart."""
    case = dict(case)
    steps = case.pop("steps", None)
    one_step = case.get("max_local_steps") == 1

    def run():
        core, plan = make_core(fedavg(0.1), dtype=jnp.float32, **case)
        kw = {}
        if steps:
            # Clients stop after 0..3 of the 3 steps: the steps past a
            # client's own are inactive.
            kw["num_steps"] = jax.device_put(
                jnp.arange(16, dtype=jnp.int32) % 4, plan.client_sharding())
        after, delta, metrics = round_after(core, plan, **kw)
        return core, leaves(after), leaves(delta), float(metrics.mean_loss)

    core, rows, _, rows_loss = run()
    with unmarked():
        twin, dense, moved, dense_loss = run()
    assert core.row_updates is True and twin.lookup_tables is None
    assert twin.row_updates is False
    assert np.abs(moved[TABLE]).max() > 0

    def close(name, of_the_change, of_the_value):
        np.testing.assert_allclose(
            rows[name], dense[name], rtol=0, err_msg=name,
            # The floor: a key bias's gradient is zero (softmax ignores a
            # shift of the scores), what it holds is rounding of 1e-11.
            atol=(of_the_change * np.abs(moved[name]).max()
                  + of_the_value * np.abs(dense[name]).max() + 1e-9))

    if "carry_dtype" in case:
        # A bfloat16 carry rounds each step's table differently (a repeated
        # token's updates are added to the row one by one, not summed
        # first), and the later steps' gradients follow.
        for name in dense:
            close(name, 2.0 ** -5, 2.0 ** -8)
        return
    np.testing.assert_allclose(rows_loss, dense_loss, rtol=1e-6)
    for name in dense:
        if one_step and name != TABLE:
            # Gradients at the same parameters: the table's rows alone are
            # summed in another order.
            np.testing.assert_array_equal(rows[name], dense[name], name)
        else:
            # 1e-6 of the round's change, and the parameter's last bit.
            close(name, 1e-6, 2.0 ** -23)


@pytest.mark.parametrize("marked", [True, False], ids=["by_rows", "dense"])
def test_an_inactive_step_with_non_finite_gradients_freezes_every_row(marked):
    """Ids past the table read NaN rows (``jnp.take``'s fill), so every
    gradient of the step is NaN; a client with no step to run must come
    back bitwise where it started, the table's rows too."""
    with contextlib.nullcontext() if marked else unmarked():
        core, _ = make_core(fedavg(0.1), sample_mode="gather")
        params = core.init_params_fn(jax.random.key(0))
        x = jnp.full((12, SEQ), VOCAB + 3, jnp.int32)
        y = jnp.zeros((12,), jnp.int32)

        def train(steps):
            return jax.jit(lambda: core._local_train(
                params, x, y, jnp.int32(12), jnp.int32(steps), jnp.int32(0),
                jax.random.key(1), jnp.int32(0), varying=False))()

        frozen, loss = train(0)
        ran, _ = train(1)
    assert core.row_updates is marked
    assert np.isnan(float(loss))
    for name, leaf in leaves(frozen).items():
        assert not leaf.any(), name
    # The same step, active, does spread its NaNs: the gate is what froze.
    assert np.isnan(leaves(ran)["['pos_embedding']"]).any()


# ------------------------------------------------------ (b) fallbacks
def _lowered(core, plan, state_kw=None):
    ds = make_ds(plan)
    state = core.init_state(jax.random.key(0))
    kw = {}
    if state_kw == "control":
        kw["control"] = core.init_control(state, ds.num_clients)
    if state_kw == "personal":
        kw["personal"] = core.init_personal(state, ds.num_clients)
    return core.lower_round_step(state, ds, **kw).as_text()


@pytest.mark.parametrize("algorithm,mesh,state_kw", [
    (fedprox(0.1, mu=0.05), dict(), None),
    (scaffold(0.1), dict(), "control"),
    (fedavg(0.1), dict(dp=4, mp=2), None),
    (fedavg(0.1), dict(dp=4, pp=2, depth=2), None),
], ids=["fedprox", "scaffold", "mp2", "pp_rounds"])
def test_where_by_rows_is_not_the_dense_result_the_dense_program_stays(
        algorithm, mesh, state_kw):
    core, plan = make_core(algorithm, **mesh)
    text = _lowered(core, plan, state_kw)
    with unmarked():
        twin, twin_plan = make_core(algorithm, **mesh)
        twin_text = _lowered(twin, twin_plan, state_kw)
    assert text == twin_text
    assert core.row_updates is False
    # The model is marked all the same; the plan with an mp axis drops it.
    assert (core.lookup_tables is None) == (mesh.get("mp", 1) > 1)


def test_dittos_personal_branch_stays_dense_and_its_global_branch_goes_by_rows():
    def run():
        core, plan = make_core(ditto(0.1, lam=0.1), dtype=jnp.float32)
        ds = make_ds(plan, vocab=24)
        state = core.init_state(jax.random.key(0))
        personal = core.init_personal(state, ds.num_clients)
        state, _, personal = core.round_step(state, ds, personal=personal)
        return (core, leaves(jax.device_get(state.params)),
                leaves(jax.device_get(personal.params)))

    core, g_rows, v_rows = run()
    with unmarked():
        twin, g_dense, v_dense = run()
    # One _masked_sgd of the two fell back: the runner then counts every row.
    assert core.row_updates is False and core.lookup_tables is not None
    for name in v_dense:        # the pull toward w moves rows no step read
        np.testing.assert_array_equal(v_rows[name], v_dense[name], name)
    for name in g_dense:
        np.testing.assert_allclose(g_rows[name], g_dense[name], rtol=0,
                                   atol=1e-7, err_msg=name)


def test_the_mark_is_a_no_op_for_a_trainer_that_is_not_handed_it():
    """The marked model in a ``FedCore`` built without ``lookup_tables`` (any
    caller of the model's plain ``apply``) is the twin's program, and an
    unmarked model hands ``build_fedcore`` nothing."""
    core, plan = make_core(fedavg(0.1))
    plain = FedCore(core.apply_fn, core.init_params_fn, core.algorithm, plan,
                    core.config)
    with unmarked():
        twin, twin_plan = make_core(fedavg(0.1))
        twin_text = _lowered(twin, twin_plan)
    assert _lowered(plain, plan) == twin_text != _lowered(core, plan)
    assert plain.lookup_tables is None and plain.row_updates is False
    mlp = build_fedcore(
        "mlp2", fedavg(0.1), plan, FedCoreConfig(batch_size=8),
        model_overrides={"hidden": (8,), "num_classes": 3}, input_shape=(4,))
    assert mlp.lookup_tables is None


# ------------------------------------------------------ (c) structure
def _local_step_text(core, clients=BLOCK):
    """The lowered block of ``clients`` clients' local training alone."""
    params = jax.eval_shape(core.init_params_fn, jax.random.key(0))
    x = jax.ShapeDtypeStruct((clients, 12, SEQ), jnp.int32)
    y = jax.ShapeDtypeStruct((clients, 12), jnp.int32)
    per_client = jax.ShapeDtypeStruct((clients,), jnp.int32)
    return jax.jit(jax.vmap(
        lambda xc, yc, n, s, u, p: core._local_train(
            p, xc, yc, n, s, u, jax.random.key(1), jnp.int32(0),
            varying=False),
        in_axes=(0, 0, 0, 0, 0, None),
    )).lower(x, y, per_client, per_client, per_client, params).as_text()


def test_the_by_rows_step_holds_no_table_sized_gradient():
    table = rf"tensor<{BLOCK}x{VOCAB}x{WIDTH}xf32>"
    zero_fill = re.compile(
        rf"stablehlo\.broadcast_in_dim %\S+, dims = \[\] : "
        rf"\(tensor<f32>\) -> {table}")
    table_add = re.compile(rf"stablehlo\.add %\S+, %\S+ : {table}")

    core, _ = make_core(fedavg(0.1), sample_mode="gather")
    rows = _local_step_text(core)
    with unmarked():
        twin, _ = make_core(fedavg(0.1), sample_mode="gather")
        dense = _local_step_text(twin)
    assert zero_fill.search(dense) and table_add.search(dense)
    assert not zero_fill.search(rows) and not table_add.search(rows)
    # One scatter a step takes their place: into the carried table, of the
    # batch's 8 x 8 looked-up rows a client.
    assert re.search(
        rf"\"stablehlo\.scatter\"\(%\S+, %\S+, %\S+\).*\n(.*\n)*?.*"
        rf"\(tensor<{BLOCK}x{VOCAB}x{WIDTH}xf32>, tensor<{BLOCK}x8x{SEQ}x\d+xi32>, "
        rf"tensor<{BLOCK}x8x{SEQ}x{WIDTH}xf32>\) -> {table}", rows)


# ------------------------------------------- (d), (e) the work counts
def test_build_fedcore_reads_the_mark_off_the_model():
    core, _ = make_core(fedadam(0.1))
    assert core.lookup_tables.paths == (("Embed_0", "embedding"),)
    assert core.lookup_tables.rows_total == VOCAB
    assert core.row_updates is None          # nothing traced yet


def _run_two_rounds(core, plan, seq, vocab, block):
    from olearning_sim_tpu.engine.runner import (
        DataPopulation, OperatorSpec, SimulationRunner)
    from olearning_sim_tpu.telemetry import SpanTracer

    ds = make_synthetic_text_dataset(
        3, 8, 12, seq_len=seq, num_classes=2, vocab_size=vocab,
    ).pad_for(plan, block).place(plan)
    pop = DataPopulation(
        name="data_0", dataset=ds, device_classes=["high"],
        class_of_client=np.zeros(ds.num_clients, int), nums=[8],
        dynamic_nums=[0])
    tracer = SpanTracer()
    SimulationRunner(
        task_id="task_rows", core=core, populations=[pop],
        operators=[OperatorSpec(name="train")], rounds=2, tracer=tracer,
    ).run()
    transfers = [s.attrs for s in tracer.spans()
                 if s.name == "round.train.host_transfer"]
    assert len(transfers) == 2
    return transfers


@pytest.mark.parametrize("algorithm,mode,written", [
    (fedadam(0.1), "gather", 8 * SEQ),          # the batch's rows x ids a row
    (fedadam(0.1), "multiplicity", 12 * SEQ),   # every local row
    (fedprox(0.1, mu=0.05), "gather", VOCAB),   # the dense update: all rows
], ids=["gather", "multiplicity", "dense_fallback"])
def test_the_runner_counts_the_rows_a_step_writes(algorithm, mode, written):
    core, plan = make_core(algorithm, sample_mode=mode)
    for attrs in _run_two_rounds(core, plan, SEQ, VOCAB, BLOCK):
        assert attrs["table_rows_total"] == VOCAB
        assert attrs["table_rows_written_per_step"] == written


def test_a_tied_head_marks_nothing_and_its_span_carries_no_table_rows():
    """``lfm2`` reads its table in the head too (logits = h @ embed.T): its
    table gradient is dense by nature and it keeps ``nn.Embed``."""
    from olearning_sim_tpu.engine import from_config

    plan = make_mesh_plan(devices=jax.devices()[:1])
    core = build_fedcore(
        "lfm2", from_config("fedavg", local_lr=0.1), plan,
        FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=1,
                      task="next_token"),
        model_overrides=dict(
            vocab_size=128, max_len=16, width=32,
            layer_types=["conv", "full_attention", "conv"],
            num_dense_layers=1, heads=4, kv_heads=2, mlp_dim=96,
            moe_mlp_dim=48, num_experts=16, experts_per_token=4,
            held_experts=[0, 1]),
        input_shape=(16,))
    assert core.lookup_tables is None
    for attrs in _run_two_rounds(core, plan, 16, 128, 1):
        assert "moe_assignments_total" in attrs
        assert not [k for k in attrs if k.startswith("table_rows")]
    assert core.row_updates is False
