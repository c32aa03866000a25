"""Trace compiler: schedules -> per-client masks, and engine integration."""

import json

import jax
import numpy as np
import pytest

import interval_schedule_oracle as oracle
from interval_schedule_oracle import no_plans  # noqa: F401  (a fixture)
from olearning_sim_tpu.deviceflow import compile_trace
from olearning_sim_tpu.engine import build_fedcore, fedavg, make_synthetic_dataset
from olearning_sim_tpu.engine.fedcore import FedCoreConfig
from olearning_sim_tpu.parallel.mesh import make_mesh_plan


def flow_timing(total, timings, amounts, drop=None):
    spec = {
        "use": True,
        "time_type": "relative",
        "timings": timings,
        "amounts": amounts,
    }
    if drop:
        spec["drop_simulation"] = drop
    return {
        "flow_dispatch": {
            "use_strategy": True,
            "total_dispatch_amount": total,
            "specific_timing": spec,
        }
    }


def test_none_strategy_all_participate():
    tr = compile_trace(None, 100, 0)
    assert tr.num_released == 100
    assert tr.num_dropped == 0
    assert (tr.arrival_time == 0).all()


def test_flow_schedule_maps_to_clients():
    tr = compile_trace(flow_timing(60, [0, 5, 10], [10, 20, 30]), 100, 0, seed=1)
    assert tr.num_released == 60
    # 40 clients never released this round
    assert np.isinf(tr.arrival_time).sum() == 40
    # arrival times take exactly the scheduled values
    finite = tr.arrival_time[np.isfinite(tr.arrival_time)]
    vals, counts = np.unique(finite, return_counts=True)
    assert list(vals) == [0.0, 5.0, 15.0]
    assert list(counts) == [10, 20, 30]
    assert tr.round_duration() == 15.0


def test_drops_reduce_participation():
    tr = compile_trace(
        flow_timing(100, [0], [100], drop={"drop_amounts": [30]}), 100, 0, seed=2
    )
    assert tr.num_released == 70
    assert tr.num_dropped == 30


def test_determinism_and_round_variation():
    a = compile_trace(flow_timing(50, [0], [50]), 100, 3, seed=5)
    b = compile_trace(flow_timing(50, [0], [50]), 100, 3, seed=5)
    assert (a.participate == b.participate).all()
    c = compile_trace(flow_timing(50, [0], [50]), 100, 4, seed=5)
    assert not (a.participate == c.participate).all()  # reshuffled per round


def test_real_time_drop_probability():
    s = {
        "real_time_dispatch": {
            "use_strategy": True,
            "drop_simulation": {"drop_probability": 0.3},
        }
    }
    tr = compile_trace(s, 2000, 0, seed=3)
    assert 0.6 < tr.num_released / 2000 < 0.8
    assert tr.num_dropped == 2000 - tr.num_released


def test_surplus_schedule_truncated():
    # schedule releases more messages than clients -> surplus ignored
    tr = compile_trace(flow_timing(500, [0], [500]), 100, 0)
    assert tr.num_released == 100


# --------------------------------------------- ClientTrace accessors
def test_round_duration_and_num_released_direct():
    """Direct unit coverage of the ClientTrace accessors (previously
    only exercised transitively through compile_trace)."""
    from olearning_sim_tpu.deviceflow import ClientTrace

    tr = ClientTrace(
        participate=np.array([1, 0, 1, 1], np.float32),
        arrival_time=np.array([2.0, np.inf, 7.5, 0.0], np.float32),
        dropped=np.array([0, 1, 0, 0], bool),
    )
    assert tr.num_released == 3
    assert tr.num_dropped == 1
    # Duration = last FINITE arrival; the never-released inf is ignored.
    assert tr.round_duration() == 7.5


def test_all_dropped_trace_has_zero_duration():
    """Every scheduled message dropped: nothing released, nothing
    arrives, duration 0 (not inf, not an empty-max crash)."""
    tr = compile_trace(
        flow_timing(50, [0], [50], drop={"drop_amounts": [50]}), 50, 0,
        seed=4,
    )
    assert tr.num_released == 0
    assert tr.num_dropped == 50
    assert np.isinf(tr.arrival_time).all()
    assert tr.round_duration() == 0.0


def test_empty_population_trace():
    """A zero-client population compiles to empty arrays with sane
    accessors for every strategy shape."""
    for strategy in (None, flow_timing(10, [0], [10])):
        tr = compile_trace(strategy, 0, 0, seed=1)
        assert tr.participate.shape == (0,)
        assert tr.num_released == 0
        assert tr.num_dropped == 0
        assert tr.round_duration() == 0.0


def test_empty_schedule_trace():
    """A schedule that releases nothing leaves the whole population
    offline (participate 0, arrival inf)."""
    tr = compile_trace(flow_timing(0, [], []), 20, 0, seed=2)
    assert tr.num_released == 0
    assert np.isinf(tr.arrival_time).all()
    assert not tr.dropped.any()
    assert tr.round_duration() == 0.0


def test_trace_drives_engine():
    """Full integration: churn trace -> participation mask -> round_step."""
    plan = make_mesh_plan(dp=8)
    cfg = FedCoreConfig(batch_size=4, max_local_steps=2, block_clients=2)
    core = build_fedcore(
        "mlp2", fedavg(0.1), plan, cfg,
        model_overrides={"hidden": (16,), "num_classes": 4}, input_shape=(8,),
    )
    ds = make_synthetic_dataset(0, 64, 8, (8,), 4).pad_for(plan, 2).place(plan)
    state = core.init_state(jax.random.key(0))

    tr = compile_trace(flow_timing(40, [0, 2], [20, 20]), ds.num_clients, 0, seed=9)
    participate = jax.device_put(tr.participate, plan.client_sharding())
    state, metrics = core.round_step(state, ds, participate=participate)
    assert int(metrics.clients_trained) == 40


# ------------------------------------------------- the kept curve plan
@pytest.mark.parametrize("seed", oracle.SEEDS)
@pytest.mark.parametrize("name", list(oracle.GRID))
def test_kept_plan_gives_the_trace_of_integrating_every_round(
        name, seed, no_plans):
    """The runner's path: the strategy parsed anew every round, the
    generator ``compile_trace``'s own. The permutation is drawn after the
    drops, so an equal trace says the generator stood where it stood."""
    spec = json.dumps(oracle.GRID[name])
    clients = 128
    for round_idx in oracle.ROUNDS:
        args = (clients, round_idx)
        kw = dict(task_id="task", operator="train", seed=seed, now=oracle.NOW)
        got = compile_trace(json.loads(spec), *args, **kw)
        with oracle.as_before():
            want = compile_trace(json.loads(spec), *args, **kw)
        np.testing.assert_array_equal(got.participate, want.participate)
        np.testing.assert_array_equal(got.arrival_time, want.arrival_time)
        np.testing.assert_array_equal(got.dropped, want.dropped)
        assert got.curve_plan_hits + got.curve_plan_builds == 1
        assert (want.curve_plan_hits, want.curve_plan_builds) == (0, 0)
    if name != "zero_area":
        assert got.num_released + got.num_dropped == min(
            clients, oracle.GRID[name]["flow_dispatch"]["total_dispatch_amount"])


def test_trace_says_whether_the_rounds_plan_was_found_or_built(no_plans):
    spec = json.dumps(oracle.GRID["one_interval_drop_probability"])
    found = [(tr.curve_plan_hits, tr.curve_plan_builds) for tr in (
        compile_trace(json.loads(spec), 128, r, seed=1) for r in range(4))]
    assert found == [(0, 1), (1, 0), (1, 0), (1, 0)]
    # nothing to integrate: no strategy, explicit timings, real-time dispatch
    real_time = {"real_time_dispatch": {"use_strategy": True}}
    for s in (None, flow_timing(50, [0], [50]), real_time):
        tr = compile_trace(s, 128, 0, seed=1)
        assert (tr.curve_plan_hits, tr.curve_plan_builds) == (0, 0)
