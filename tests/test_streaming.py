"""Block-streamed cohort execution (FedCore.stream_round + HostClientStore).

The headline regression: a >=2-block streamed round is BITWISE identical
to the resident single-program round on the same cohort — params,
metrics, RNG streams, and per-client losses — across the supported knob
compositions (plain / deadline / attack / clip defense / label drift),
with no retrace across rounds (scenario and stream knobs are data). Plus
store semantics (padding inertness, lazy determinism, per-client state),
the composition-matrix rejections, the runner's streamed+scenario task
path, and the crash-resume contract (scenario + stream cursor ride
checkpoint meta; a fresh runner over the same checkpoint finishes
bitwise).
"""

import dataclasses

import jax
import numpy as np
import pytest

from olearning_sim_tpu.engine import (
    build_fedcore,
    ditto,
    fedavg,
    make_synthetic_dataset,
    scaffold,
)
from olearning_sim_tpu.engine.client_data import (
    ClientDataset,
    HostClientStore,
    make_central_eval_set,
    make_synthetic_text_dataset,
)
from olearning_sim_tpu.engine.defense import DefenseConfig
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.engine.runner import (
    DataPopulation,
    OperatorSpec,
    SimulationRunner,
)
from olearning_sim_tpu.engine.scenario import ScenarioConfig, ScenarioModel
from olearning_sim_tpu.parallel.mesh import global_put, make_mesh_plan

NUM_CLIENTS = 64
INPUT_SHAPE = (8,)
N_LOCAL = 6
CLASSES = 4
STREAM_ROWS = 32  # 2 blocks at 64 clients


@pytest.fixture(scope="module")
def plan():
    return make_mesh_plan(dp=2)


@pytest.fixture(scope="module")
def core(plan):
    cfg = FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=4)
    return build_fedcore(
        "mlp2", fedavg(0.1), plan, cfg,
        model_overrides={"hidden": (16,), "num_classes": CLASSES},
        input_shape=INPUT_SHAPE,
    )


@pytest.fixture(scope="module")
def host_ds(plan, core):
    return make_synthetic_dataset(
        0, NUM_CLIENTS, N_LOCAL, INPUT_SHAPE, CLASSES
    ).pad_for(plan, core.config.block_clients)


@pytest.fixture(scope="module")
def placed_ds(plan, host_ds):
    return host_ds.place(plan)


def _param_leaves(state):
    return [np.asarray(l) for l in jax.tree.leaves(
        jax.device_get(state.params)
    )]


def _assert_states_bitwise(sa, sb):
    for a, b in zip(_param_leaves(sa), _param_leaves(sb)):
        np.testing.assert_array_equal(a, b)
    assert int(sa.round_idx) == int(sb.round_idx)


def _lfm2_case():
    """The tiny ``lfm2`` of tests/test_lfm2.py on one device: a model that
    takes its clients one at a time (``block_clients`` 1, no ``vmap``), the
    next-token task. (core, host dataset, plan, real clients, stream rows)"""
    plan = make_mesh_plan(devices=jax.devices()[:1])
    cfg = FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=1,
                        task="next_token")
    core = build_fedcore(
        "lfm2", fedavg(0.1), plan, cfg, input_shape=(16,),
        model_overrides=dict(
            vocab_size=128, max_len=16, width=64,
            layer_types=["conv", "full_attention", "conv"],
            num_dense_layers=1, heads=4, kv_heads=2, mlp_dim=96,
            moe_mlp_dim=48, num_experts=16, experts_per_token=4,
            held_experts=[0, 1]),
    )
    host = make_synthetic_text_dataset(
        seed=5, num_clients=4, n_local=6, seq_len=16, num_classes=2,
        vocab_size=128).pad_for(plan, 1)
    return core, host, plan, 4, 2


# ----------------------------------------------------- bitwise parity
@pytest.mark.parametrize("model", ["mlp2", "lfm2"])
def test_streamed_bitwise_parity_plain(model, request):
    """>=2 streamed blocks == the resident single program, bit for bit,
    over multiple rounds (params, metrics, per-client losses) — for a
    vmapped block of clients and for a model whose clients the block stage
    takes one at a time."""
    if model == "lfm2":
        core, host_ds, plan, n_real, stream_rows = _lfm2_case()
        placed_ds = host_ds.place(plan)
    else:
        core, host_ds, placed_ds, plan = (
            request.getfixturevalue(name)
            for name in ("core", "host_ds", "placed_ds", "plan"))
        n_real, stream_rows = NUM_CLIENTS, STREAM_ROWS
    sa = core.init_state(jax.random.key(0))
    sb = core.init_state(jax.random.key(0))
    store = HostClientStore.from_dataset(host_ds)
    part = (np.random.default_rng(7).random(n_real) < 0.8).astype(
        np.float32
    )
    part_pad = np.zeros(host_ds.num_clients, np.float32)
    part_pad[:n_real] = part
    for _ in range(2):
        sa, ma = core.round_step(
            sa, placed_ds,
            participate=global_put(part_pad, plan.client_sharding()),
        )
        sb, mb, stats = core.stream_round(
            sb, store, stream_rows=stream_rows, participate=part_pad
        )
        assert stats.blocks == host_ds.num_clients // stream_rows >= 2
        _assert_states_bitwise(sa, sb)
        assert float(ma.mean_loss) == float(mb.mean_loss)
        assert float(ma.weight_sum) == float(mb.weight_sum)
        assert int(ma.clients_trained) == int(mb.clients_trained)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(ma.client_loss)), mb.client_loss
        )
    # O(block) HBM: the streamed round's resident estimate is bounded by
    # two blocks + params/opt/accumulator, independent of population.
    assert stats.peak_hbm_bytes_est < 3 * (
        stats.transfer_bytes // stats.blocks
    ) + 4 * sum(l.nbytes for l in _param_leaves(sb)) * 4


def test_streamed_bitwise_parity_deadline_attack_clip(
    core, host_ds, placed_ds, plan
):
    """The composed variant (deadline masking + sign-flip attack + clip
    defense) streams bitwise too, with per-round knob changes."""
    rng = np.random.default_rng(3)
    part = (rng.random(host_ds.num_clients) < 0.9).astype(np.float32)
    comp = rng.random(host_ds.num_clients).astype(np.float32)
    atk = np.ones(host_ds.num_clients, np.float32)
    atk[:6] = -1.0
    dfs = DefenseConfig(clip_norm=0.05, aggregator="mean")
    sh = plan.client_sharding()
    sa = core.init_state(jax.random.key(1))
    sb = core.init_state(jax.random.key(1))
    store = HostClientStore.from_dataset(host_ds)
    for r in range(2):
        deadline = 0.6 + 0.1 * r
        sa, ma = core.round_step(
            sa, placed_ds, participate=global_put(part, sh),
            completion_time=global_put(comp, sh), deadline=deadline,
            attack_scale=global_put(atk, sh), defense=dfs,
        )
        sb, mb, _ = core.stream_round(
            sb, store, stream_rows=STREAM_ROWS, participate=part,
            completion_time=comp, deadline=deadline,
            attack_scale=atk, defense=dfs,
        )
        _assert_states_bitwise(sa, sb)
        assert int(ma.stragglers) == int(mb.stragglers) > 0
        assert int(ma.clipped) == int(mb.clipped) > 0
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(ma.client_loss)), mb.client_loss
        )


def test_streamed_label_drift_matches_shifted_resident(
    core, host_ds, plan
):
    """label_shift streamed == the resident program trained on host-
    shifted labels — drift is pure data."""
    shift = np.zeros(host_ds.num_clients, np.int32)
    shift[::3] = 1
    shift[::7] = 2
    y2 = (np.asarray(host_ds.y) + shift[:, None]) % CLASSES
    shifted = dataclasses.replace(host_ds, y=y2.astype(host_ds.y.dtype))
    sa = core.init_state(jax.random.key(2))
    sb = core.init_state(jax.random.key(2))
    sa, ma = core.round_step(sa, shifted.place(plan))
    store = HostClientStore.from_dataset(host_ds)
    sb, mb, _ = core.stream_round(
        sb, store, stream_rows=STREAM_ROWS,
        participate=np.ones(host_ds.num_clients, np.float32),
        label_shift=shift, label_classes=CLASSES,
    )
    _assert_states_bitwise(sa, sb)
    assert float(ma.mean_loss) == float(mb.mean_loss)


def test_stream_no_retrace_across_rounds(core, host_ds):
    """Scenario/stream knobs are data: round after round with different
    masks, deadlines, and attack scales, every stream program variant is
    traced exactly once."""
    store = HostClientStore.from_dataset(host_ds)
    state = core.init_state(jax.random.key(0))
    rng = np.random.default_rng(0)
    for r in range(3):
        state, _, _ = core.stream_round(
            state, store, stream_rows=STREAM_ROWS,
            participate=(rng.random(host_ds.num_clients) < 0.7).astype(
                np.float32
            ),
            completion_time=rng.random(host_ds.num_clients).astype(
                np.float32
            ),
            deadline=0.5 + 0.2 * r,
            attack_scale=np.ones(host_ds.num_clients, np.float32),
        )
    stream_counts = {k: v for k, v in core.trace_counts.items()
                     if k[0] in ("stream", "stream_finalize")}
    assert stream_counts, "stream variants never traced"
    assert all(v == 1 for v in stream_counts.values()), stream_counts


# ------------------------------------------------------------- the store
def test_store_padding_rows_are_inert():
    ds = make_synthetic_dataset(0, 10, 4, (8,), 3)
    store = HostClientStore.from_dataset(ds)
    store.pad_to(16)
    rows = store.rows(8, 16)
    assert rows["x"].shape == (8, 4, 8)
    np.testing.assert_array_equal(rows["weight"][2:], 0.0)
    np.testing.assert_array_equal(rows["num_samples"][2:], 1)
    np.testing.assert_array_equal(rows["client_uid"], np.arange(8, 16))
    with pytest.raises(IndexError):
        store.rows(0, 17)
    with pytest.raises(ValueError):
        store.pad_to(4)


def test_store_lazy_synthetic_deterministic_and_chunked():
    kw = dict(seed=5, num_clients=100, n_local=4, input_shape=(6,),
              num_classes=3, chunk_rows=32)
    a = HostClientStore.synthetic(**kw)
    b = HostClientStore.synthetic(**kw)
    ra = a.rows(20, 70)  # crosses two chunk boundaries
    rb = b.rows(20, 70)
    for k in ra:
        np.testing.assert_array_equal(ra[k], rb[k])
    # Chunk-crossing reads agree with two smaller reads.
    r1 = a.rows(20, 32)
    r2 = a.rows(32, 70)
    np.testing.assert_array_equal(
        ra["x"], np.concatenate([r1["x"], r2["x"]])
    )
    assert ra["client_uid"][0] == 20 and ra["client_uid"][-1] == 69
    # The lazy store pads beyond the logical population too.
    a.pad_to(128)
    tail = a.rows(96, 128)
    np.testing.assert_array_equal(tail["weight"][4:], 0.0)


def test_store_per_client_state():
    store = HostClientStore.synthetic(
        seed=0, num_clients=8, n_local=2, input_shape=(4,), num_classes=2
    )
    ema = store.ensure_state("pacing_ema", (), np.float32, fill=1.5)
    assert ema.shape == (8,) and (ema == 1.5).all()
    store.set_state_rows("pacing_ema", 2, 4, [0.5, 0.25])
    np.testing.assert_array_equal(
        store.state_rows("pacing_ema", 0, 5), [1.5, 1.5, 0.5, 0.25, 1.5]
    )
    store.ensure_state("strikes", (3,), np.int32)
    assert store.state_names() == ["pacing_ema", "strikes"]
    assert store.state_bytes() == 8 * 4 + 8 * 3 * 4
    # Padding grows state rows with zero fill.
    store.pad_to(12)
    assert store.ensure_state("pacing_ema", ()).shape == (12,)
    np.testing.assert_array_equal(store.state_rows("pacing_ema", 8, 12), 0)


# -------------------------------------------------- composition matrix
def test_stream_rejections(plan, host_ds, core):
    store = HostClientStore.from_dataset(host_ds)
    state = core.init_state(jax.random.key(0))
    with pytest.raises(ValueError, match="multiple of"):
        core.stream_round(state, store, stream_rows=12)
    with pytest.raises(ValueError, match="without a deadline"):
        core.stream_round(
            state, store, stream_rows=STREAM_ROWS,
            completion_time=np.zeros(NUM_CLIENTS, np.float32),
        )
    with pytest.raises(ValueError, match="clip_norm only"):
        core.stream_round(
            state, store, stream_rows=STREAM_ROWS,
            defense=DefenseConfig(aggregator="median"),
        )
    with pytest.raises(ValueError, match="needs label_classes"):
        core.stream_round(
            state, store, stream_rows=STREAM_ROWS,
            label_shift=np.ones(NUM_CLIENTS, np.int32),
        )
    with pytest.raises(ValueError, match="stream_rows"):
        core.stream_round(state, store)

    cfg = FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=4)
    overrides = {"hidden": (16,), "num_classes": CLASSES}
    personalized = build_fedcore("mlp2", ditto(0.1), plan, cfg,
                                 model_overrides=overrides,
                                 input_shape=INPUT_SHAPE)
    with pytest.raises(ValueError, match="personalized"):
        personalized.stream_round(
            personalized.init_state(jax.random.key(0)), store,
            stream_rows=STREAM_ROWS,
        )
    controlled = build_fedcore("mlp2", scaffold(0.1), plan, cfg,
                               model_overrides=overrides,
                               input_shape=INPUT_SHAPE)
    with pytest.raises(ValueError, match="control-variate"):
        controlled.stream_round(
            controlled.init_state(jax.random.key(0)), store,
            stream_rows=STREAM_ROWS,
        )
    sharded = build_fedcore(
        "mlp2", fedavg(0.1), plan,
        FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=4,
                      shard_server_update=True),
        model_overrides=overrides, input_shape=INPUT_SHAPE,
    )
    with pytest.raises(ValueError, match="shard_server_update"):
        sharded.stream_round(
            sharded.init_state(jax.random.key(0)), store,
            stream_rows=STREAM_ROWS,
        )


# --------------------------------------------------- runner integration
def _stream_runner(core, host_ds, scenario, *, rounds, task_id,
                   ckpt=None, resilience=None, eval_data=None,
                   tracer=None):
    pop = DataPopulation(
        name="data_0",
        dataset=host_ds,
        device_classes=["c0"],
        class_of_client=np.zeros(host_ds.num_clients, int),
        nums=[host_ds.num_clients],
        dynamic_nums=[0],
        eval_data=eval_data,
        store=HostClientStore.from_dataset(host_ds),
    )
    return SimulationRunner(
        task_id=task_id, core=core, populations=[pop],
        operators=[OperatorSpec(name="train")], rounds=rounds,
        checkpointer=ckpt, scenario=scenario, resilience=resilience,
        trace_seed=13, tracer=tracer,
    )


SCENARIO = ScenarioConfig(
    online_base=0.6, online_amp=0.3, leave_rate=0.01,
    drift_period_rounds=3, stream_block_rows=STREAM_ROWS,
)


def test_streamed_round_emits_nested_stream_spans(core, host_ds):
    """Per-block ``stream_stage`` (host->device placement) and
    ``stream_step`` (partial-step dispatch) spans nest under the runner's
    train-phase span, so the double-buffered transfer overlap is visible
    in the Perfetto export next to the round timeline."""
    from olearning_sim_tpu.telemetry import SpanTracer

    tracer = SpanTracer()
    runner = _stream_runner(core, host_ds, SCENARIO, rounds=1,
                            task_id="stream-spans", tracer=tracer)
    runner.run()
    stages = tracer.spans("stream_stage")
    steps = tracer.spans("stream_step")
    # 64 padded clients / 32 stream rows = 2 blocks: one step span per
    # block, one stage span per staged block (block 0 + the double-
    # buffered block 1).
    assert len(steps) == 2 and len(stages) == 2
    assert [s.attrs["block"] for s in steps] == [0, 1]
    assert [s.attrs["block"] for s in stages] == [0, 1]
    train_phase = [s for s in tracer.spans()
                   if s.name == "round.train.train"]
    assert len(train_phase) == 1
    # Every block span is parented inside the train phase span.
    assert all(s.parent_id == train_phase[0].span_id
               for s in stages + steps)


def test_runner_streamed_scenario_oracle(core, host_ds):
    """The runner's streamed train round reports exactly the scenario
    model's per-round availability, and the stream/scenario digests ride
    the history records (-> checkpoint meta)."""
    runner = _stream_runner(core, host_ds, SCENARIO, rounds=3,
                            task_id="stream-oracle")
    history = runner.run()
    model = ScenarioModel(SCENARIO, host_ds.num_clients, seed=13)
    for r, rec in enumerate(history):
        tr = model.round_trace(r)
        got = rec["train"]["data_0"]
        assert got["scenario"]["available"] == tr.num_available
        assert got["scenario"]["churned"] == tr.counts()["churned"]
        assert got["clients_trained"] == tr.num_available
        stream = got["stream"]
        assert stream["blocks"] == stream["cursor"] >= 2
        assert stream["block_rows"] == STREAM_ROWS


def test_runner_streamed_scenario_rejects_bad_compositions(core, host_ds):
    from olearning_sim_tpu.engine.async_rounds import AsyncConfig

    with pytest.raises(ValueError, match="async"):
        r = _stream_runner(core, host_ds, SCENARIO, rounds=1,
                           task_id="bad-async")
        SimulationRunner(
            task_id="bad-async2", core=core,
            populations=r.populations,
            operators=[OperatorSpec(name="train")], rounds=1,
            scenario=SCENARIO, async_config=AsyncConfig(buffer_size=4),
        )
    with pytest.raises(ValueError, match="clip-only"):
        r = _stream_runner(core, host_ds, SCENARIO, rounds=1,
                           task_id="bad-def")
        SimulationRunner(
            task_id="bad-def2", core=core, populations=r.populations,
            operators=[OperatorSpec(name="train")], rounds=1,
            scenario=SCENARIO,
            defense=DefenseConfig(aggregator="trimmed_mean",
                                  trim_fraction=0.1),
        )


def test_scenario_submit_validation():
    """The {"scenario": {...}} engine-params block is validated at
    submit like deadline/defense/async: unknown keys and the streamed
    composition matrix are rejected before any compile."""
    import copy
    import json
    import os

    from olearning_sim_tpu.taskmgr.codecs import json2taskconfig
    from olearning_sim_tpu.taskmgr.validation import validate_task_parameters

    cfg_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "configs", "fedavg_mnist_mlp_trace.json",
    )
    with open(cfg_path) as f:
        base = json.load(f)

    def verdict(extra):
        tj = copy.deepcopy(base)
        op = tj["operatorflow"]["operators"][0]["logical_simulation"]
        p = json.loads(op["operator_params"])
        p.update(extra)
        op["operator_params"] = json.dumps(p)
        return validate_task_parameters(json2taskconfig(tj))

    ok, msg = verdict({})
    assert ok, msg
    for extra, needle in (
        ({"scenario": {"online_bias": 1}}, "unknown scenario config keys"),
        ({"scenario": {"spikes": [{"boost": 2}]}}, "start 'round'"),
        ({"async": {"buffer_size": 8}}, "buffered async"),
        ({"algorithm": {"name": "ditto"}}, "personalized"),
        ({"defense": {"aggregator": "median"}}, "clip-only"),
        ({"parallel": {"mp": 2}}, "dp-only"),
        ({"fedcore": {"shard_server_update": True}},
         "replicated server update"),
    ):
        ok, msg = verdict(extra)
        assert not ok and needle in msg, (extra, msg)


def test_runner_streamed_resume_bitwise(core, host_ds, tmp_path):
    """Crash-resume acceptance: a streamed scenario run preempted
    mid-task recovers through the checkpoint (rollback replay), AND a
    supervisor-style FRESH runner over the same checkpoint directory
    finishes bitwise — the scenario trace is recomputed from the round
    index and the stream walk is round-atomic, so no extra state needs
    to survive beyond the checkpointed history."""
    from olearning_sim_tpu.checkpoint import RoundCheckpointer
    from olearning_sim_tpu.resilience import (
        FailurePolicy,
        FaultPlan,
        FaultSpec,
        ResilienceConfig,
        faults,
    )

    ROUNDS = 4
    ref = _stream_runner(core, host_ds, SCENARIO, rounds=ROUNDS,
                         task_id="stream-ck")
    ref.run()
    ref_state = ref.states["data_0"]

    # (a) HostPreemption mid-run: checkpoint rollback replays bitwise.
    ck1 = RoundCheckpointer(str(tmp_path / "ck1"), max_to_keep=8)
    pre = _stream_runner(
        core, host_ds, SCENARIO, rounds=ROUNDS, task_id="stream-ck",
        ckpt=ck1,
        resilience=ResilienceConfig(failure_policy=FailurePolicy.RETRY,
                                    max_round_retries=2,
                                    quarantine_after=None),
    )
    with faults.chaos(FaultPlan(seed=1, specs=[
        FaultSpec(point="runner.round_begin", rounds=[2],
                  error="preempt"),
    ])):
        h_pre = pre.run()
    assert [h["round"] for h in h_pre] == list(range(ROUNDS))
    _assert_states_bitwise(ref_state, pre.states["data_0"])

    # (b) Supervisor-style resume: run 3 rounds, then a FRESH runner over
    # the same checkpoint directory finishes rounds 3..4 bitwise.
    ck2a = RoundCheckpointer(str(tmp_path / "ck2"), max_to_keep=8)
    first = _stream_runner(core, host_ds, SCENARIO, rounds=ROUNDS - 1,
                           task_id="stream-ck", ckpt=ck2a)
    first.run()
    ck2a.wait()
    ck2b = RoundCheckpointer(str(tmp_path / "ck2"), max_to_keep=8)
    res = _stream_runner(core, host_ds, SCENARIO, rounds=ROUNDS,
                         task_id="stream-ck", ckpt=ck2b)
    h_res = res.run()
    # The resumed run replays nothing: it starts past the committed
    # rounds, and its history (restored + fresh) covers every round with
    # the stream cursor of each committed round intact.
    assert [h["round"] for h in h_res] == list(range(ROUNDS))
    assert all("stream" in h["train"]["data_0"] for h in h_res)
    _assert_states_bitwise(ref_state, res.states["data_0"])
