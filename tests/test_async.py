"""Buffered asynchronous rounds + multi-task dispatch: acceptance tests.

- async=off programs are byte-identical to the pre-async engine (the
  async subsystem only ADDS variants);
- staleness-weighted buffered aggregation matches an explicit numpy
  oracle built from per-client deltas (exact schedule weights, commit
  boundaries, max-staleness drops);
- a single-buffer constant-schedule async round reproduces the
  synchronous round's aggregate (the semantic anchor);
- every async knob (alpha, max_staleness, scores, window assignments) is
  data — per-round plans never retrace; M keys a distinct variant;
- the runner's async accounting (commits, staleness, buffer depth, tail
  idle) and the commit clock riding checkpoint meta (resume replays the
  commit sequence bitwise);
- MultiTaskDispatcher: cooperative interleave is bitwise the solo runs,
  fair-share ordering, lease claim/renew/fencing via the PR 4 columns;
- per-client local-step scan parity: the scanned (unroll=1) and unrolled
  step loops produce bitwise-identical rounds at steps <= 2.
"""

import json

import jax
import numpy as np
import pytest

from olearning_sim_tpu.engine import build_fedcore, fedavg, make_synthetic_dataset
from olearning_sim_tpu.engine.async_rounds import (
    AsyncConfig,
    async_variant_key,
    plan_async_round,
    staleness_weights,
)
from olearning_sim_tpu.engine.defense import DefenseConfig
from olearning_sim_tpu.engine import pacing
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.engine.runner import (
    DataPopulation,
    MultiTaskDispatcher,
    OperatorSpec,
    SimulationRunner,
)
from olearning_sim_tpu.parallel.mesh import make_mesh_plan
from olearning_sim_tpu.telemetry import MetricsRegistry

NUM_CLIENTS = 16
INPUT_SHAPE = (8,)


@pytest.fixture(scope="module")
def plan():
    return make_mesh_plan()


@pytest.fixture(scope="module")
def core(plan):
    cfg = FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=2)
    return build_fedcore(
        "mlp2", fedavg(0.1), plan, cfg,
        model_overrides={"hidden": (8,), "num_classes": 3},
        input_shape=INPUT_SHAPE,
    )


@pytest.fixture(scope="module")
def dataset(plan):
    return make_synthetic_dataset(
        7, NUM_CLIENTS, 6, INPUT_SHAPE, 3, class_sep=3.0
    ).pad_for(plan, 2).place(plan)


COMPLETION = np.linspace(0.5, 8.0, NUM_CLIENTS).astype(np.float32)


def _leaves(state):
    return jax.tree.leaves(jax.device_get(state.params))


_DELTA_CACHE = {}


def _client_deltas(core, dataset, key=0):
    """Per-client round deltas extracted one client at a time from the
    base synchronous program (see tests/test_defense.py) — every client
    anchors at the round-begin params, which is exactly the async
    engine's dispatch model, so the same deltas feed the buffered
    oracle."""
    from olearning_sim_tpu.parallel.mesh import global_put

    cache_key = (id(core), id(dataset), key)
    if cache_key in _DELTA_CACHE:
        return _DELTA_CACHE[cache_key]
    base = _leaves(core.init_state(jax.random.key(key)))
    deltas = []
    for c in range(dataset.num_clients):
        onehot = np.zeros(dataset.num_clients, np.float32)
        onehot[c] = 1.0
        st, _ = core.round_step(
            core.init_state(jax.random.key(key)), dataset,
            participate=global_put(onehot, core.plan.client_sharding()),
        )
        deltas.append([np.asarray(a, np.float64) - np.asarray(b, np.float64)
                       for a, b in zip(_leaves(st), base)])
    _DELTA_CACHE[cache_key] = (base, deltas)
    return base, deltas


# ------------------------------------------------------------- host plan
def test_arrival_ranks_and_plan_are_deterministic():
    completion = np.array([3.0, 1.0, 2.0, 2.0, np.inf, 5.0], np.float32)
    selected = np.array([1, 1, 1, 1, 1, 0], bool)
    ranks = pacing.arrival_ranks(completion, selected)
    # Ties (2.0 at clients 2,3) break by client index; inf sorts last;
    # non-selected get -1.
    np.testing.assert_array_equal(ranks, [3, 0, 1, 2, 4, -1])

    cfg = AsyncConfig(buffer_size=2)
    ap = plan_async_round(cfg, completion, selected, 8)
    np.testing.assert_array_equal(
        ap.window, [1, 0, 0, 1, 2, -1, -1, -1]
    )
    assert ap.num_windows == cfg.num_windows(8) == 4
    np.testing.assert_array_equal(ap.fill, [2, 2, 1, 0])
    # Window 0 commits at its last member's arrival (client 3 at 2.0).
    assert ap.commit_time[0] == pytest.approx(2.0)
    assert ap.commit_time[1] == pytest.approx(3.0)
    assert not np.isfinite(ap.commit_time[3])
    # Idle: client 1 waits 2.0-1.0, client 2 waits 2.0-2.0=0, client 0
    # waits 3.0-3.0=0, client 3 waits 3.0-2.0; client 4 (inf) adds 0.
    assert ap.idle_seconds(completion) == pytest.approx(2.0)

    ap2 = plan_async_round(AsyncConfig(buffer_size=2, max_staleness=1),
                           completion, selected, 8)
    np.testing.assert_array_equal(
        ap2.stale_dropped_mask()[:6], [False] * 4 + [True, False]
    )


def test_staleness_weight_schedules():
    np.testing.assert_allclose(staleness_weights("constant", 0.5, 3),
                               [1.0, 1.0, 1.0])
    np.testing.assert_allclose(
        staleness_weights("polynomial", 0.5, 3),
        [1.0, 2.0 ** -0.5, 3.0 ** -0.5], rtol=1e-6,
    )
    np.testing.assert_allclose(
        staleness_weights("polynomial", 0.5, 4, max_staleness=1),
        [1.0, 2.0 ** -0.5, 0.0, 0.0], rtol=1e-6,
    )


def test_async_config_validation():
    with pytest.raises(ValueError, match="buffer_size"):
        AsyncConfig(buffer_size=0)
    with pytest.raises(ValueError, match="schedule"):
        AsyncConfig(schedule="exponential")
    with pytest.raises(ValueError, match="max_staleness"):
        AsyncConfig(max_staleness=-1)
    with pytest.raises(ValueError, match="unknown async config keys"):
        AsyncConfig.from_dict({"bufer_size": 8})
    cfg = AsyncConfig.from_dict(
        {"buffer_size": 8, "max_staleness": 4, "schedule": "score",
         "speed_profiles": {"high": 0.05}}
    )
    assert cfg.buffer_size == 8 and cfg.schedule == "score"
    # The embedded completion model is a deadline-free DeadlineConfig.
    pc = cfg.pacing_config()
    assert not pc.enabled and pc.speed_profiles == {"high": 0.05}


def test_submit_validation_rejects_bad_async_combos():
    from test_taskmgr import make_task_json

    from olearning_sim_tpu.taskmgr.codecs import json2taskconfig
    from olearning_sim_tpu.taskmgr.validation import validate_task_parameters

    def with_params(extra):
        js = make_task_json("async-val", rounds=1)
        op = js["operatorflow"]["operators"][0]["logical_simulation"]
        params = json.loads(op["operator_params"])
        params.update(extra)
        op["operator_params"] = json.dumps(params)
        return json2taskconfig(json.dumps(js))

    ok, msg = validate_task_parameters(with_params(
        {"async": {"buffer_size": 8, "schedule": "polynomial"}}
    ))
    assert ok, msg
    ok, msg = validate_task_parameters(with_params(
        {"async": {"bufer_size": 8}}
    ))
    assert not ok and "async params invalid" in msg
    ok, msg = validate_task_parameters(with_params(
        {"async": {"buffer_size": 8},
         "deadline": {"deadline_s": 5.0}}
    ))
    assert not ok and "mutually exclusive" in msg
    # A deadline block that is present but disabled does not conflict.
    ok, msg = validate_task_parameters(with_params(
        {"async": {"buffer_size": 8},
         "deadline": {"jitter": 0.1}}
    ))
    assert ok, msg
    ok, msg = validate_task_parameters(with_params(
        {"async": {"buffer_size": 8},
         "algorithm": {"name": "ditto", "local_lr": 0.1}}
    ))
    assert not ok and "personalized" in msg


# --------------------------------------------------------------- fedcore
def test_async_off_path_untouched(core, dataset, plan):
    """Building an async variant must not perturb the synchronous
    program: the base variant object is unchanged and its lowered text is
    byte-identical to a pristine build's (the async=off bitwise
    regression — combined with the blessed budgets of the pre-async grid
    variants, this pins byte-identity to the PR 7 engine)."""
    base_before = core._round_step_variants[(False, False, None)]
    assert base_before is core._round_step
    text_before = core.lower_round_step(
        core.init_state(jax.random.key(0)), dataset
    ).as_text()

    ap = plan_async_round(AsyncConfig(buffer_size=4), COMPLETION,
                          np.ones(NUM_CLIENTS, bool), dataset.num_clients)
    core.round_step(core.init_state(jax.random.key(0)), dataset,
                    async_plan=ap)
    assert core._round_step_variants[(False, False, None)] is base_before
    text_after = core.lower_round_step(
        core.init_state(jax.random.key(0)), dataset
    ).as_text()
    assert text_before == text_after

    pristine = build_fedcore(
        "mlp2", fedavg(0.1), plan,
        FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=2),
        model_overrides={"hidden": (8,), "num_classes": 3},
        input_shape=INPUT_SHAPE,
    )
    text_pristine = pristine.lower_round_step(
        pristine.init_state(jax.random.key(0)), dataset
    ).as_text()
    assert text_pristine == text_after


def test_buffered_aggregation_matches_numpy_oracle(core, dataset):
    """Multi-window polynomial staleness weighting == the numpy oracle:
    sequential commits of staleness-discounted window means built from
    the extracted per-client deltas (fedavg SGD(1.0) server: each commit
    adds sw_w x window weighted mean)."""
    base, deltas = _client_deltas(core, dataset)
    weights = np.asarray(jax.device_get(dataset.weight), np.float64)
    acfg = AsyncConfig(buffer_size=4, schedule="polynomial",
                       staleness_alpha=0.7)
    ap = plan_async_round(acfg, COMPLETION, np.ones(NUM_CLIENTS, bool),
                          dataset.num_clients)
    s, m, st = core.round_step(core.init_state(jax.random.key(0)), dataset,
                               async_plan=ap)
    assert int(st.commits) == ap.num_windows == 4
    assert int(st.dropped_stale) == 0
    assert int(m.clients_trained) == NUM_CLIENTS

    sw = staleness_weights("polynomial", 0.7, ap.num_windows)
    cur = [np.asarray(b, np.float64) for b in base]
    for w in range(ap.num_windows):
        members = np.flatnonzero(ap.window == w)
        wsum = weights[members].sum()
        if wsum <= 0:
            continue
        for i in range(len(cur)):
            mean_d = sum(weights[c] * deltas[c][i] for c in members) / wsum
            cur[i] = cur[i] + float(sw[w]) * mean_d
    for got, exp in zip(_leaves(s), cur):
        np.testing.assert_allclose(np.asarray(got, np.float64), exp,
                                   rtol=2e-5, atol=1e-6)


def test_max_staleness_drops_late_windows(core, dataset):
    """Windows beyond max_staleness never commit: their members count as
    stale_dropped and the aggregate equals the oracle over the surviving
    windows only. Same compiled program — max_staleness is data."""
    base, deltas = _client_deltas(core, dataset)
    weights = np.asarray(jax.device_get(dataset.weight), np.float64)
    acfg = AsyncConfig(buffer_size=4, schedule="polynomial",
                       staleness_alpha=0.7, max_staleness=1)
    ap = plan_async_round(acfg, COMPLETION, np.ones(NUM_CLIENTS, bool),
                          dataset.num_clients)
    key = async_variant_key(ap.num_windows, "polynomial", False, None)
    traces = core.trace_counts.get(key)
    s, m, st = core.round_step(core.init_state(jax.random.key(0)), dataset,
                               async_plan=ap)
    assert core.trace_counts[key] == traces  # data change, no retrace
    assert int(st.commits) == 2
    assert int(st.dropped_stale) == 8  # windows 2 and 3

    sw = staleness_weights("polynomial", 0.7, ap.num_windows,
                           max_staleness=1)
    cur = [np.asarray(b, np.float64) for b in base]
    for w in range(2):
        members = np.flatnonzero(ap.window == w)
        wsum = weights[members].sum()
        for i in range(len(cur)):
            mean_d = sum(weights[c] * deltas[c][i] for c in members) / wsum
            cur[i] = cur[i] + float(sw[w]) * mean_d
    for got, exp in zip(_leaves(s), cur):
        np.testing.assert_allclose(np.asarray(got, np.float64), exp,
                                   rtol=2e-5, atol=1e-6)


def test_single_buffer_constant_schedule_matches_sync(core, dataset):
    """M >= cohort and a constant schedule: one commit of the whole
    cohort — the async program reproduces the synchronous round's
    aggregate (allclose; the programs differ structurally)."""
    acfg = AsyncConfig(buffer_size=dataset.num_clients, schedule="constant")
    ap = plan_async_round(acfg, COMPLETION, np.ones(NUM_CLIENTS, bool),
                          dataset.num_clients)
    assert ap.num_windows == 1
    s_async, m_async, st = core.round_step(
        core.init_state(jax.random.key(0)), dataset, async_plan=ap
    )
    s_sync, m_sync = core.round_step(
        core.init_state(jax.random.key(0)), dataset
    )
    assert int(st.commits) == 1
    assert int(m_async.clients_trained) == int(m_sync.clients_trained)
    for a, b in zip(_leaves(s_async), _leaves(s_sync)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_a_model_taken_one_client_at_a_time_runs_a_buffered_round():
    """The tiny ``lfm2`` of tests/test_lfm2.py (``block_clients`` 1, no
    ``vmap`` over clients, next-token task, one device): the buffered
    program's block stage takes its clients one at a time, as the resident
    program's does."""
    from test_streaming import _lfm2_case

    core, host, plan, n_real, _ = _lfm2_case()
    ds = host.place(plan)
    assert n_real == 4
    released = np.array([True, True, False, True])
    ap = plan_async_round(
        AsyncConfig(buffer_size=2, schedule="polynomial"),
        np.linspace(0.5, 2.0, 4).astype(np.float32), released,
        ds.num_clients)
    state, metrics, stats = core.round_step(
        core.init_state(jax.random.key(0)), ds, async_plan=ap,
        participate=jax.device_put(released.astype(np.float32),
                                   plan.client_sharding()))
    assert np.isfinite(float(metrics.mean_loss))
    assert int(metrics.clients_trained) == int(released.sum()) == 3
    assert int(stats.commits) == ap.num_windows == 2
    assert int(state.round_idx) == 1


def test_async_knobs_are_data_no_recompile(core, dataset):
    """Changing alpha / max_staleness / arrival order across rounds
    reuses the SAME compiled function with one trace (the lowered text is
    also byte-stable — the grid/retrace analyzer asserts that across the
    whole variant grid); changing M (a new window capacity) keys a
    distinct variant."""
    acfg_a = AsyncConfig(buffer_size=4, staleness_alpha=0.5)
    acfg_b = AsyncConfig(buffer_size=4, staleness_alpha=2.0,
                         max_staleness=2)
    ap_a = plan_async_round(acfg_a, COMPLETION, np.ones(NUM_CLIENTS, bool),
                            dataset.num_clients)
    ap_b = plan_async_round(acfg_b, COMPLETION[::-1].copy(),
                            np.ones(NUM_CLIENTS, bool), dataset.num_clients)
    key = async_variant_key(ap_a.num_windows, "polynomial", False, None)
    state = core.init_state(jax.random.key(0))
    state, _, _ = core.round_step(state, dataset, async_plan=ap_a)
    traces = core.trace_counts[key]
    fn = core._round_step_variants[key]
    state, _, _ = core.round_step(state, dataset, async_plan=ap_b)
    assert core.trace_counts[key] == traces == 1
    assert core._round_step_variants[key] is fn

    # A different M -> different window capacity -> keyed variant.
    acfg_m = AsyncConfig(buffer_size=8)
    ap_m = plan_async_round(acfg_m, COMPLETION, np.ones(NUM_CLIENTS, bool),
                            dataset.num_clients)
    assert async_variant_key(ap_m.num_windows, "polynomial", False,
                             None) != key


def test_async_rejects_bad_combinations(core, dataset):
    ap = plan_async_round(AsyncConfig(buffer_size=4), COMPLETION,
                          np.ones(NUM_CLIENTS, bool), dataset.num_clients)
    with pytest.raises(ValueError, match="mutually exclusive"):
        core.round_step(
            core.init_state(jax.random.key(0)), dataset, async_plan=ap,
            completion_time=dataset.weight, deadline=1.0,
        )
    wrong = plan_async_round(AsyncConfig(buffer_size=4), COMPLETION,
                             np.ones(NUM_CLIENTS, bool),
                             dataset.num_clients * 2)
    with pytest.raises(ValueError, match="different population"):
        core.round_step(core.init_state(jax.random.key(0)), dataset,
                        async_plan=wrong)
    with pytest.raises(ValueError, match="padded population"):
        plan_async_round(AsyncConfig(buffer_size=4), COMPLETION,
                         np.ones(NUM_CLIENTS, bool),
                         dataset.num_clients // 2)


# ------------------------------------------------------- local-step scan
def test_step_scan_parity_with_unrolled(plan, dataset):
    """The per-client train body's lax.scan over local SGD steps
    (step_unroll=1) matches the fully unrolled loop (step_unroll =
    max_local_steps) at steps <= 2, and both trace exactly once — unroll
    is purely a scheduling knob, never a semantics one. Parity is
    near-exact rather than bitwise: the math is identical, but XLA fuses
    (and so reassociates) the rolled and unrolled schedules differently,
    which perturbs the last float bit (observed max relative diff ~9e-8,
    under one f32 ULP); the tolerance below admits a couple of ULPs and
    nothing more."""
    outs = []
    for unroll in (1, 2):
        c = build_fedcore(
            "mlp2", fedavg(0.1), plan,
            FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=2,
                          step_unroll=unroll),
            model_overrides={"hidden": (8,), "num_classes": 3},
            input_shape=INPUT_SHAPE,
        )
        s, _ = c.round_step(c.init_state(jax.random.key(0)), dataset)
        assert c.trace_counts[(False, False, None)] == 1
        outs.append(_leaves(s))
    for a, b in zip(*outs):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   rtol=3e-7, atol=2e-9)


# ---------------------------------------------------------------- runner
def make_runner(core, dataset, *, rounds=3, task_id="async-task",
                async_config=None, registry=None, checkpointer=None,
                task_repo=None):
    pop = DataPopulation(
        name="data_0", dataset=dataset, device_classes=["c"],
        class_of_client=np.zeros(dataset.num_clients, int),
        nums=[NUM_CLIENTS], dynamic_nums=[0],
    )
    kwargs = {}
    if task_repo is not None:
        kwargs["task_repo"] = task_repo
    return SimulationRunner(
        task_id=task_id, core=core, populations=[pop],
        operators=[OperatorSpec(name="train")], rounds=rounds,
        async_config=async_config, registry=registry,
        checkpointer=checkpointer, **kwargs,
    )


ASYNC_CFG = AsyncConfig(buffer_size=4, schedule="polynomial",
                        staleness_alpha=0.5, default_step_s=0.5,
                        jitter=0.2)


def test_runner_async_accounting_and_telemetry(core, dataset):
    registry = MetricsRegistry()
    runner = make_runner(core, dataset, rounds=2, async_config=ASYNC_CFG,
                         registry=registry)
    history = runner.run()
    recs = [h["train"]["data_0"] for h in history]
    assert all(r["commits"] >= 1 for r in recs)
    assert all(r["windows"] == 4 for r in recs)
    assert all(r["buffer_size"] == 4 for r in recs)
    assert all(r["committed"] == NUM_CLIENTS for r in recs)
    assert all(r["idle_s"] >= 0 for r in recs)
    # The commit clock is cumulative and rides the round records.
    assert history[0]["async_clock"] == recs[0]["commits"]
    assert history[1]["async_clock"] == \
        recs[0]["commits"] + recs[1]["commits"]

    depth = registry.gauge(
        "ols_engine_buffer_depth", labels=("task_id",)
    ).labels(task_id="async-task")
    assert depth.value == pytest.approx(NUM_CLIENTS / recs[-1]["commits"])
    stale_hist = registry.histogram(
        "ols_engine_staleness_rounds", labels=("task_id",)
    ).labels(task_id="async-task")
    assert stale_hist.count == 2 * NUM_CLIENTS
    idle = registry.counter(
        "ols_engine_idle_seconds_total", labels=("task_id", "mode")
    ).labels(task_id="async-task", mode="async")
    assert idle.value == pytest.approx(sum(r["idle_s"] for r in recs))


def test_runner_rejects_async_with_deadline_or_personal(core, dataset):
    from olearning_sim_tpu.engine.pacing import DeadlineConfig

    with pytest.raises(ValueError, match="mutually exclusive"):
        SimulationRunner(
            task_id="bad", core=core,
            populations=[DataPopulation(
                name="data_0", dataset=dataset, device_classes=["c"],
                class_of_client=np.zeros(dataset.num_clients, int),
                nums=[NUM_CLIENTS], dynamic_nums=[0],
            )],
            operators=[OperatorSpec(name="train")], rounds=1,
            async_config=ASYNC_CFG,
            deadline=DeadlineConfig(deadline_s=5.0),
        )


def test_async_checkpoint_resume_replays_commit_sequence_bitwise(
        core, dataset, tmp_path):
    """A fresh runner resuming the task's checkpoint replays the
    remaining rounds' commit sequences bitwise: same per-round commit
    counts, same final model as an uninterrupted run, and a continuous
    commit clock (the async meta rides checkpoint meta)."""
    from olearning_sim_tpu.checkpoint import RoundCheckpointer

    full = make_runner(core, dataset, rounds=4, async_config=ASYNC_CFG,
                       task_id="async-ck")
    full_history = full.run()

    ck = str(tmp_path / "ck")
    first = make_runner(
        core, dataset, rounds=4, async_config=ASYNC_CFG,
        task_id="async-ck",
        checkpointer=RoundCheckpointer(ck, task_id="async-ck"),
    )
    first.begin()
    first.step()
    first.step()
    first.finish()
    assert first._loop is None

    resumed = make_runner(
        core, dataset, rounds=4, async_config=ASYNC_CFG,
        task_id="async-ck",
        checkpointer=RoundCheckpointer(ck, task_id="async-ck"),
    )
    resumed_history = resumed.run()
    assert [h["round"] for h in resumed_history] == [0, 1, 2, 3]
    assert resumed_history[0]["async_clock"] == \
        full_history[0]["async_clock"]
    assert [h["async_clock"] for h in resumed_history] == \
        [h["async_clock"] for h in full_history]
    for a, b in zip(_leaves(resumed.states["data_0"]),
                    _leaves(full.states["data_0"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ dispatcher
def test_dispatcher_cooperative_interleave_is_bitwise_solo(core, dataset):
    """Two tasks interleaved round-by-round on one process produce
    exactly the solo runs' histories and final models — task states are
    independent, so multiplexing never changes any task's math."""
    solo = {}
    for tid in ("mt-a", "mt-b"):
        r = make_runner(core, dataset, rounds=3, task_id=tid,
                        async_config=ASYNC_CFG)
        solo[tid] = (r.run(), _leaves(r.states["data_0"]))

    runners = [
        make_runner(core, dataset, rounds=3, task_id=tid,
                    async_config=ASYNC_CFG)
        for tid in ("mt-a", "mt-b")
    ]
    disp = MultiTaskDispatcher(runners, fair_share=False)
    results = sorted(results_key for results_key in disp.run())
    assert results == ["mt-a", "mt-b"]
    for r in runners:
        history, leaves = solo[r.task_id]
        assert [h["round"] for h in r.history] == \
            [h["round"] for h in history]
        assert [h["async_clock"] for h in r.history] == \
            [h["async_clock"] for h in history]
        for a, b in zip(_leaves(r.states["data_0"]), leaves):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class _FakeRunner:
    """A no-jax stand-in exposing the dispatcher's runner surface."""

    def __init__(self, task_id, rounds, clients):
        self.task_id = task_id
        self.rounds = rounds
        self.clients = clients
        self.done_rounds = 0
        self.turn_log = []
        self.stop_event = None
        self.finished = False

    def begin(self):
        pass

    def pending_device_rounds(self):
        return (self.rounds - self.done_rounds) * self.clients

    def step(self):
        self.done_rounds += 1
        self.turn_log.append(self.done_rounds)
        return self.done_rounds < self.rounds

    def finish(self):
        self.finished = True
        return [{"round": i} for i in range(self.done_rounds)]

    def run(self):
        self.begin()
        while self.step():
            pass
        return self.finish()


def test_dispatcher_fair_share_prefers_most_pending():
    big = _FakeRunner("big", rounds=4, clients=100)
    small = _FakeRunner("small", rounds=2, clients=10)
    order = []

    class Spy(MultiTaskDispatcher):
        def _pick(self, active, rotation):
            r = super()._pick(active, rotation)
            order.append(r.task_id)
            return r

    results = Spy([small, big], fair_share=True).run()
    # The big task (400 pending device-rounds) runs until its backlog
    # drops under the small task's, then service alternates by deficit.
    assert order[:4] == ["big"] * 4
    assert set(results) == {"big", "small"}
    assert big.finished and small.finished


def test_dispatcher_leases_claim_renew_release_and_fence():
    from olearning_sim_tpu.taskmgr.task_repo import TaskTableRepo

    repo = TaskTableRepo()
    a = _FakeRunner("lease-a", rounds=2, clients=10)
    b = _FakeRunner("lease-b", rounds=2, clients=10)
    disp = MultiTaskDispatcher([a, b], task_repo=repo, owner_id="disp-1",
                               lease_ttl_s=30.0, fair_share=False)

    # Another process already owns b with a live lease: claim fails and
    # b is fenced before a single round runs.
    repo.add_task("lease-b")
    assert repo.claim_lease("lease-b", "other-owner", ttl_s=60.0)
    results = disp.run()
    assert disp.fenced == ["lease-b"]
    assert b.done_rounds == 0 and not b.finished
    assert "lease-a" in results and a.finished
    # a's lease was released on finish; b's still belongs to the other.
    assert repo.lease_info("lease-a")[0] == ""
    assert repo.lease_info("lease-b")[0] == "other-owner"

    # Mid-run steal: the victim is fenced at its next turn (cooperative
    # heartbeat) and its history is not reported.
    repo2 = TaskTableRepo()
    c = _FakeRunner("lease-c", rounds=4, clients=10)

    class Thief(MultiTaskDispatcher):
        def _pick(self, active, rotation):
            r = super()._pick(active, rotation)
            if r.task_id == "lease-c" and r.done_rounds == 1:
                # Simulate a supervisor reclaiming after perceived death.
                repo2.claim_lease("lease-c", "supervisor", ttl_s=60.0,
                                  now=__import__("time").time() + 120.0)
            return r

    disp2 = Thief([c], task_repo=repo2, owner_id="disp-2",
                  lease_ttl_s=0.001, fair_share=False)
    results2 = disp2.run()
    assert disp2.fenced == ["lease-c"]
    assert results2 == {}
    assert not c.finished and c.done_rounds >= 1


def test_dispatcher_cooperative_isolates_task_failure():
    """One task failing under its failure policy must not abandon the
    other tasks mid-run: the healthy task still finishes (checkpoint
    commit + lease release), and the failure is re-raised after — the
    same isolation the threaded mode gives via per-thread workers. The
    failed task's lease is left to TTL-expire for the supervisor."""
    from olearning_sim_tpu.taskmgr.task_repo import TaskTableRepo

    class _Exploding(_FakeRunner):
        def step(self):
            if self.done_rounds >= 1:
                raise RuntimeError("retry budget exhausted")
            return super().step()

    repo = TaskTableRepo()
    bad = _Exploding("iso-bad", rounds=4, clients=10)
    good = _FakeRunner("iso-good", rounds=3, clients=10)
    disp = MultiTaskDispatcher([bad, good], task_repo=repo,
                               owner_id="disp-iso", lease_ttl_s=30.0,
                               fair_share=False)
    with pytest.raises(RuntimeError, match="retry budget exhausted"):
        disp.run()
    assert good.finished and good.done_rounds == good.rounds
    assert not bad.finished
    # The healthy task's lease was released on finish; the failed task's
    # is still held (TTL disposition belongs to the supervisor).
    assert repo.lease_info("iso-good")[0] == ""
    assert repo.lease_info("iso-bad")[0] == "disp-iso"


def test_dispatcher_cooperative_isolates_begin_and_finish_failure():
    """The isolation covers the whole task lifecycle, not just step():
    a task whose begin() or finish() raises (checkpoint-commit wait,
    resilience persistence) must not abandon its co-tasks — threaded
    mode runs both inside the worker's try. The failed task's lease is
    left to TTL-expire; the healthy task still finishes + releases."""
    from olearning_sim_tpu.taskmgr.task_repo import TaskTableRepo

    class _BadBegin(_FakeRunner):
        def begin(self):
            raise RuntimeError("restore failed")

    class _BadFinish(_FakeRunner):
        def finish(self):
            raise RuntimeError("commit wait failed")

    for bad in (_BadBegin("iso-bad", rounds=2, clients=10),
                _BadFinish("iso-bad", rounds=1, clients=10)):
        repo = TaskTableRepo()
        good = _FakeRunner("iso-good", rounds=3, clients=10)
        disp = MultiTaskDispatcher([bad, good], task_repo=repo,
                                   owner_id="disp-iso", lease_ttl_s=30.0,
                                   fair_share=False)
        with pytest.raises(RuntimeError, match="failed"):
            disp.run()
        assert good.finished and good.done_rounds == good.rounds
        assert repo.lease_info("iso-good")[0] == ""
        assert repo.lease_info("iso-bad")[0] == "disp-iso"


# --------------------------------------------------------------- defense
@pytest.mark.slow
def test_async_defended_windows_match_numpy_oracle(core, dataset):
    """Robust aggregation composes per buffer: each window's trimmed-mean
    statistic over its own members (staleness-discounted at commit)
    matches the numpy oracle from extracted deltas."""
    base, deltas = _client_deltas(core, dataset)
    trim = 0.2
    acfg = AsyncConfig(buffer_size=4, schedule="polynomial",
                       staleness_alpha=0.7)
    ap = plan_async_round(acfg, COMPLETION, np.ones(NUM_CLIENTS, bool),
                          dataset.num_clients)
    s, m, st = core.round_step(
        core.init_state(jax.random.key(0)), dataset, async_plan=ap,
        defense=DefenseConfig(aggregator="trimmed_mean",
                              trim_fraction=trim),
    )
    assert int(st.commits) == ap.num_windows
    sw = staleness_weights("polynomial", 0.7, ap.num_windows)
    cur = [np.asarray(b, np.float64) for b in base]
    for w in range(ap.num_windows):
        members = np.flatnonzero(ap.window == w)
        n = len(members)
        k = int(np.floor(trim * n))
        for i in range(len(cur)):
            stacked = np.stack([deltas[c][i] for c in members])
            srt = np.sort(stacked, axis=0)
            agg = srt[k:n - k].mean(axis=0)
            cur[i] = cur[i] + float(sw[w]) * agg
    for got, exp in zip(_leaves(s), cur):
        np.testing.assert_allclose(np.asarray(got, np.float64), exp,
                                   rtol=2e-5, atol=1e-6)


@pytest.mark.slow
def test_async_shard_server_update_parity(plan, dataset):
    """The cross-replica sharded server update composes with async
    commits: allclose to the replicated async program, O(params/dp) opt
    state layout preserved."""
    acfg = AsyncConfig(buffer_size=4, schedule="polynomial")
    ap = plan_async_round(acfg, COMPLETION, np.ones(NUM_CLIENTS, bool),
                          dataset.num_clients)
    outs = []
    for shard in (False, True):
        c = build_fedcore(
            "mlp2", fedavg(0.1), plan,
            FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=2,
                          shard_server_update=shard),
            model_overrides={"hidden": (8,), "num_classes": 3},
            input_shape=INPUT_SHAPE,
        )
        s, _, st = c.round_step(c.init_state(jax.random.key(0)), dataset,
                                async_plan=ap)
        assert int(st.commits) == ap.num_windows
        outs.append(_leaves(s))
    for a, b in zip(*outs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_score_schedule_upweights_fast_clients(core, dataset):
    """Apodotiko-style scores: with the score schedule, a fast client's
    delta is weighted above a slow same-window client's, and the plan's
    scores normalize to mean ~1 over the cohort."""
    acfg = AsyncConfig(buffer_size=8, schedule="score",
                       staleness_alpha=0.5)
    ap = plan_async_round(acfg, COMPLETION, np.ones(NUM_CLIENTS, bool),
                          dataset.num_clients)
    assert ap.score is not None
    sel = ap.window[:NUM_CLIENTS] >= 0
    assert float(np.mean(ap.score[:NUM_CLIENTS][sel])) == pytest.approx(
        1.0, abs=0.05
    )
    # Faster completion -> larger score (inverse-time, clipped).
    assert ap.score[0] > ap.score[NUM_CLIENTS - 1]
    s, m, st = core.round_step(core.init_state(jax.random.key(0)), dataset,
                               async_plan=ap)
    assert int(st.commits) == 2


@pytest.mark.slow
def test_dispatcher_threaded_matches_solo(core, dataset):
    """interleave="thread": per-task results are still bitwise the solo
    runs (threads share no task state), with leases renewed by the
    heartbeat daemon."""
    from olearning_sim_tpu.taskmgr.task_repo import TaskTableRepo

    solo = {}
    for tid in ("thr-a", "thr-b"):
        r = make_runner(core, dataset, rounds=3, task_id=tid,
                        async_config=ASYNC_CFG)
        solo[tid] = (r.run(), _leaves(r.states["data_0"]))
    repo = TaskTableRepo()
    runners = [
        make_runner(core, dataset, rounds=3, task_id=tid,
                    async_config=ASYNC_CFG, task_repo=repo)
        for tid in ("thr-a", "thr-b")
    ]
    disp = MultiTaskDispatcher(runners, task_repo=repo,
                               owner_id="disp-thr", interleave="thread")
    results = disp.run()
    assert sorted(results) == ["thr-a", "thr-b"]
    assert disp.fenced == []
    for r in runners:
        _, leaves = solo[r.task_id]
        for a, b in zip(_leaves(r.states["data_0"]), leaves):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert repo.lease_info(r.task_id)[0] == ""  # released on finish
