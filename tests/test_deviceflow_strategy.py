"""Schedule-synthesis semantics vs the reference grammar
(``ols_core/deviceflow/non_grpc/strategy.py``)."""

import copy
import json
import math
import types
from datetime import datetime

import numpy as np
import pytest

import interval_schedule_oracle as oracle
from interval_schedule_oracle import no_plans  # noqa: F401  (a fixture)
from olearning_sim_tpu.deviceflow import strategy
from olearning_sim_tpu.deviceflow.strategy import (
    EMPTY_SCHEDULE,
    analyze_flow_strategy,
    analyze_real_time_strategy,
    is_real_time_dispatch,
)


def flow(spec):
    return {"flow_dispatch": {"use_strategy": True, **spec}}


RNG = lambda: np.random.default_rng(0)


def test_real_time_detection_and_params():
    s = {
        "real_time_dispatch": {
            "use_strategy": True,
            "dispatch_batch_sizes": [10, 20],
            "drop_simulation": {"drop_probability": 0.25},
        }
    }
    assert is_real_time_dispatch(s)
    plan = analyze_real_time_strategy(s)
    assert plan.batch_sizes == [10, 20]
    assert plan.drop_probability == 0.25
    assert not is_real_time_dispatch(flow({}))


def test_disabled_or_malformed_gives_empty():
    assert analyze_flow_strategy({"flow_dispatch": {"use_strategy": False}}, "t_op_0").empty
    assert analyze_flow_strategy(flow({"total_dispatch_amount": 0}), "t_op_0").empty
    # both timing and interval set -> empty (strategy.py:48-49)
    both = flow({
        "total_dispatch_amount": 10,
        "specific_timing": {"use": True},
        "specific_interval": {"use": True},
    })
    assert analyze_flow_strategy(both, "t_op_0").empty


def test_specific_timing_relative():
    s = flow({
        "total_dispatch_amount": 60,
        "specific_timing": {
            "use": True,
            "time_type": "relative",
            "timings": [0, 5, 10],
            "amounts": [10, 20, 30],
        },
    })
    sched = analyze_flow_strategy(s, "t_op_0", rng=RNG())
    assert sched.timings == [0.0, 5.0, 10.0]
    assert sched.amounts == [10, 20, 30]
    assert sched.total_sent == 60
    assert sched.total_dropped == 0
    assert sched.absolute_times() == [0.0, 5.0, 15.0]


def test_specific_timing_absolute_rounds_and_past_filtering():
    # Round 1 of a multi-round absolute schedule; first time point is in the
    # past relative to `now` and must be filtered (strategy.py:136-158).
    s = flow({
        "total_dispatch_amount": 30,
        "specific_timing": {
            "use": True,
            "time_type": "absolute",
            "time_zone": "UTC",
            "timings": [
                ["2026-01-01 00:00:01", "2026-01-01 00:00:02"],
                ["2026-01-01 00:00:00", "2026-01-01 00:01:00", "2026-01-01 00:02:00"],
            ],
            "amounts": [10, 20],
        },
    })
    # round 1 has 3 timings but only 2 amounts -> empty (len mismatch)
    now = datetime(2026, 1, 1, 0, 0, 30)
    assert analyze_flow_strategy(s, "t_op_1", rng=RNG(), now=now).empty

    s["flow_dispatch"]["specific_timing"]["timings"][1] = [
        "2026-01-01 00:00:00",
        "2026-01-01 00:01:00",
    ]
    sched = analyze_flow_strategy(s, "t_op_1", rng=RNG(), now=now)
    # the 00:00:00 point is 30s in the past -> dropped along with its amount
    assert sched.amounts == [20]
    assert sched.timings == [30.0]


def test_timing_drop_probability_extremes_and_determinism():
    base = {
        "total_dispatch_amount": 40,
        "specific_timing": {
            "use": True,
            "time_type": "relative",
            "timings": [0, 1],
            "amounts": [20, 20],
            "drop_simulation": {"drop_probability": [0.0, 1.0]},
        },
    }
    sched = analyze_flow_strategy(flow(base), "t_op_0", rng=RNG())
    assert sched.drop_lists[0] == []
    assert sched.drop_lists[1] == list(range(20))

    base["specific_timing"]["drop_simulation"] = {"drop_probability": [0.5, 0.5]}
    a = analyze_flow_strategy(flow(base), "t_op_0", rng=np.random.default_rng(42))
    b = analyze_flow_strategy(flow(base), "t_op_0", rng=np.random.default_rng(42))
    assert a.drop_lists == b.drop_lists


def test_timing_drop_amounts():
    s = flow({
        "total_dispatch_amount": 30,
        "specific_timing": {
            "use": True,
            "time_type": "relative",
            "timings": [0, 1],
            "amounts": [10, 20],
            "drop_simulation": {"drop_amounts": [3, 20]},
        },
    })
    sched = analyze_flow_strategy(s, "t_op_0", rng=RNG())
    assert len(sched.drop_lists[0]) == 3
    assert sched.drop_lists[0] == sorted(sched.drop_lists[0])
    # drop_amount >= amount drops everything (strategy.py:303-307)
    assert sched.drop_lists[1] == list(range(20))
    # both drop mechanisms at once -> empty schedule (strategy.py:101-102)
    s["flow_dispatch"]["specific_timing"]["drop_simulation"] = {
        "drop_probability": [0, 0],
        "drop_amounts": [0, 0],
    }
    assert analyze_flow_strategy(s, "t_op_0", rng=RNG()).empty


def interval_spec(intervals, domains, functions, total, drop=None, **kw):
    spec = {
        "total_dispatch_amount": total,
        "specific_interval": {
            "use": True,
            "time_type": kw.get("time_type", "relative"),
            "intervals": intervals,
            "dispatch_rules": {"domains": domains, "functions": functions},
        },
    }
    if drop:
        spec["specific_interval"]["drop_simulation"] = drop
    if "time_zone" in kw:
        spec["specific_interval"]["time_zone"] = kw["time_zone"]
    return flow(spec)


def test_interval_constant_rate_uniform_split():
    # rate 1 over 10 seconds -> 10 equal slots of total/10 each.
    s = interval_spec([[0, 10]], [[0.0, 10.0]], ["1"], 100)
    sched = analyze_flow_strategy(s, "t_op_0", rng=RNG())
    assert sched.amounts == [10] * 10
    assert sched.timings == [0.0] + [1.0] * 9
    assert sched.total_sent == 100


def test_interval_total_preserved_for_odd_totals():
    # residual-carry integerization preserves the exact total
    # (strategy.py:361-382).
    for total in (7, 31, 97, 1000):
        s = interval_spec([[0, 7]], [[0.0, 6.28]], ["math.sin(t)+1"], total)
        sched = analyze_flow_strategy(s, "t_op_0", rng=RNG())
        assert sched.total_sent == total, total


def test_interval_multi_interval_proportional_split():
    # two intervals, rates 1 and 3 over equal lengths -> 25%/75% split.
    s = interval_spec(
        [[0, 10], [10, 20]],
        [[0.0, 10.0], [0.0, 10.0]],
        ["1", "3"],
        200,
    )
    sched = analyze_flow_strategy(s, "t_op_0", rng=RNG())
    assert sched.total_sent == 200
    assert sum(sched.amounts[:10]) == 50
    assert sum(sched.amounts[10:]) == 150


def test_interval_negative_rate_sends_nothing():
    s = interval_spec([[0, 5]], [[0.0, 5.0]], ["-1"], 50)
    assert analyze_flow_strategy(s, "t_op_0", rng=RNG()).empty


def test_interval_spike_shape():
    # A gaussian-bump spike: most traffic lands mid-interval.
    s = interval_spec(
        [[0, 20]], [[-3.0, 3.0]], ["math.exp(-t*t)"], 1000
    )
    sched = analyze_flow_strategy(s, "t_op_0", rng=RNG())
    assert sched.total_sent == 1000
    mid = sum(sched.amounts[8:12])
    assert mid > 500, f"spike not concentrated: {sched.amounts}"


def test_interval_drop_amounts_distribution():
    s = interval_spec(
        [[0, 10]], [[0.0, 10.0]], ["1"], 100, drop={"drop_amounts": [40]}
    )
    sched = analyze_flow_strategy(s, "t_op_0", rng=RNG())
    assert sched.total_dropped == 40


def test_interval_absolute_time():
    now = datetime(2026, 1, 1, 0, 0, 0)
    # absolute intervals are per-round indexable: one list of [start, end]
    # pairs per round (validate_parameters.py:146-151)
    s = interval_spec(
        [[["2026-01-01 00:00:10", "2026-01-01 00:00:15"]]],
        [[0.0, 5.0]],
        ["2"],
        50,
        time_type="absolute",
        time_zone="UTC",
    )
    sched = analyze_flow_strategy(s, "t_op_0", rng=RNG(), now=now)
    assert sched.total_sent == 50
    assert sched.timings[0] == 10.0  # waits until the absolute start
    assert len(sched.amounts) == 5


def test_json_string_input():
    s = json.dumps(interval_spec([[0, 4]], [[0.0, 4.0]], ["1"], 8))
    sched = analyze_flow_strategy(s, "t_op_0", rng=RNG())
    assert sched.total_sent == 8


# ------------------------------------------------- the kept curve plan
def fresh(spec):
    """The strategy as the runner hands it over: parsed anew every round."""
    return json.loads(json.dumps(spec))


def counts(sched):
    return sched.curve_plan_hits, sched.curve_plan_builds


@pytest.mark.parametrize("seed", oracle.SEEDS)
@pytest.mark.parametrize("name", list(oracle.GRID))
def test_kept_plan_gives_the_schedule_of_integrating_every_round(
        name, seed, no_plans):
    spec = oracle.GRID[name]
    builds = 0
    for round_idx in oracle.ROUNDS:
        flow_id = f"task_train_{round_idx}"
        rng = np.random.default_rng([seed, round_idx])
        got = analyze_flow_strategy(fresh(spec), flow_id, rng=rng,
                                    now=oracle.NOW)
        want_rng = np.random.default_rng([seed, round_idx])
        with oracle.as_before():
            want = analyze_flow_strategy(fresh(spec), flow_id, rng=want_rng,
                                         now=oracle.NOW)
        assert got.timings == want.timings
        assert got.amounts == want.amounts
        assert got.drop_lists == want.drop_lists
        assert [type(a) for a in got.amounts] == [int] * len(got.amounts)
        # the plan drew nothing: the generator stands where it stood
        assert rng.random() == want_rng.random()
        assert sum(counts(got)) == 1 and counts(want) == (0, 0)
        builds += got.curve_plan_builds
    # one build a distinct interval list: 1 where relative, 3 and 2 in the
    # two absolute strategies
    assert builds == len(no_plans) == {
        "absolute_one_interval": 3, "absolute_three_intervals": 2}.get(name, 1)


def test_second_call_with_an_equal_strategy_evaluates_nothing(
        no_plans, monkeypatch):
    calls = []

    def sin(x):
        calls.append(x)
        return math.sin(x)

    monkeypatch.setattr(strategy, "math", types.SimpleNamespace(sin=sin))
    spec = json.dumps(oracle.GRID["one_interval_drop_probability"])
    first = analyze_flow_strategy(json.loads(spec), "t_op_0", rng=RNG())
    assert counts(first) == (0, 1)
    assert len(calls) == 20 * (strategy.AREA_CALCULATION_NUM + 1)
    del calls[:]
    # another parse, another round, another generator: the values are the key
    second = analyze_flow_strategy(json.loads(spec), "t_op_1",
                                   rng=np.random.default_rng(9))
    assert counts(second) == (1, 0) and calls == []
    assert second.amounts == first.amounts
    assert second.drop_lists != first.drop_lists      # the draws are the round's
    # any of the four values makes another plan
    other = json.loads(spec)
    other["flow_dispatch"]["total_dispatch_amount"] = 127
    assert counts(analyze_flow_strategy(other, "t_op_0", rng=RNG())) == (0, 1)
    assert len(calls) == 20 * (strategy.AREA_CALCULATION_NUM + 1)


def test_a_callers_changes_to_a_schedule_stay_its_own(no_plans):
    spec = oracle.GRID["three_intervals_drop_probability"]
    kept = copy.deepcopy(analyze_flow_strategy(fresh(spec), "t_op_0", rng=RNG()))
    mine = analyze_flow_strategy(fresh(spec), "t_op_0", rng=RNG())
    assert counts(mine) == (1, 0)
    mine.timings[:] = [99.0]
    mine.amounts[:] = [0] * len(mine.amounts)
    mine.drop_lists[0].append(-1)
    del mine.drop_lists[1:]
    again = analyze_flow_strategy(fresh(spec), "t_op_0", rng=RNG())
    assert counts(again) == (1, 0)
    assert (again.timings, again.amounts, again.drop_lists) == (
        kept.timings, kept.amounts, kept.drop_lists)


@pytest.mark.parametrize("expression, pure", [
    ("math.sin(t/6.0)+1.1", True),
    ("np.abs(np.cos(t))", True),
    ("(lambda x: x*x)(t)", True),
    ("np.random.random()", False),
    ("1+np.random.default_rng(0).random()", False),
    ("(lambda: np.random.random())()", False),
    ("[np.random.random() for _ in (1,)][0]", False),
    ("np.__dict__['ran'+'dom'].random()", False),
])
def test_an_expression_that_names_random_or_a_dunder_is_not_pure(
        expression, pure):
    assert strategy._compile_rate(expression)[1] is pure


def test_an_expression_naming_random_is_built_every_call(no_plans):
    spec = oracle.interval_strategy(
        [[0, 4]], [[0.0, 4.0]], ["1+np.random.random()"], 40)
    for round_idx in range(3):
        sched = analyze_flow_strategy(fresh(spec), f"t_op_{round_idx}", rng=RNG())
        assert counts(sched) == (0, 1) and sched.total_sent == 40
    assert len(no_plans) == 0
    # one such interval among pure ones keeps the whole plan out
    spec = oracle.interval_strategy(
        [[0, 4], [4, 8]], [[0.0, 4.0]] * 2, ["1", "1+np.random.random()"], 40)
    for _ in range(2):
        assert counts(analyze_flow_strategy(fresh(spec), "t_op_0", rng=RNG())) == (0, 1)
    assert len(no_plans) == 0


@pytest.mark.parametrize("expression", ["1/(t-t)", "math.sqrt(-1-t)", "t[0]", "{}[t]"])
def test_an_error_that_gave_an_empty_schedule_still_does_every_call(
        expression, no_plans):
    spec = oracle.interval_strategy([[0, 4]], [[0.0, 4.0]], [expression], 40)
    for _ in range(2):
        got = analyze_flow_strategy(fresh(spec), "t_op_0", rng=RNG())
        with oracle.as_before():
            want = analyze_flow_strategy(fresh(spec), "t_op_0", rng=RNG())
        assert got is EMPTY_SCHEDULE and want is EMPTY_SCHEDULE
    assert len(no_plans) == 0


@pytest.mark.parametrize("expression, error", [
    ("undefined(t)", NameError),            # not one of the errors caught
    ("abs(t)", NameError),                  # builtins are emptied
    ("1 +", SyntaxError),
    ("math.nope(t)", AttributeError),
])
def test_an_error_that_propagated_still_does_every_call(
        expression, error, no_plans):
    spec = oracle.interval_strategy([[0, 4]], [[0.0, 4.0]], [expression], 40)
    for _ in range(2):
        with pytest.raises(error):
            analyze_flow_strategy(fresh(spec), "t_op_0", rng=RNG())
        with oracle.as_before(), pytest.raises(error):
            analyze_flow_strategy(fresh(spec), "t_op_0", rng=RNG())
    assert len(no_plans) == 0


def test_errors_keep_their_order_across_intervals(no_plans):
    # The first interval's ZeroDivisionError is met before the second's
    # expression is looked at, so its SyntaxError never shows; an interval
    # with no slot evaluates nothing, so neither does its NameError.
    for intervals, functions in (([[0, 4], [4, 8]], ["1/(t-t)", "1 +"]),
                                 ([[4, 4], [4, 8]], ["1", "1 +"]),
                                 ([[5, 4], [4, 8]], ["undefined", "1"])):
        spec = oracle.interval_strategy(intervals, [[0.0, 4.0]] * 2, functions, 40)
        got = analyze_flow_strategy(fresh(spec), "t_op_0", rng=RNG())
        with oracle.as_before():
            want = analyze_flow_strategy(fresh(spec), "t_op_0", rng=RNG())
        assert (got.timings, got.amounts) == (want.timings, want.amounts)


def test_values_that_do_not_hash_are_built_every_call(no_plans):
    # Not JSON's to give, but a caller's dict may: numpy scalars in arrays.
    args = (40, [[0, 4]], [[np.array(0.0), np.array(4.0)]], ["1"])
    for _ in range(2):
        sched = strategy._interval_schedule(*args, {}, RNG())
        assert counts(sched) == (0, 1) and sched.amounts == [10] * 4
    assert len(no_plans) == 0


def test_the_memo_holds_at_most_its_bound(no_plans):
    limit = strategy.CURVE_PLAN_LIMIT

    def call(i):
        # a long absolute schedule: another interval list every round
        return strategy._interval_schedule(
            10, [[0, 1 + i % 3]], [[0.0, 1.0 + i]], ["1"], {}, RNG())

    for i in range(limit + 10):
        assert counts(call(i)) == (0, 1)
        assert len(no_plans) <= limit
    assert len(no_plans) == limit
    assert counts(call(limit + 9)) == (1, 0)          # the newest is kept
    assert counts(call(10)) == (1, 0)                 # the oldest kept; now the newest
    assert counts(call(9)) == (0, 1)                  # the one before it went
    assert counts(call(11)) == (0, 1)                 # and 11 went for 9: least lately used
    assert counts(call(10)) == (1, 0)
    assert len(no_plans) == limit


def test_schedules_without_a_curve_report_no_plan():
    timing = flow({"total_dispatch_amount": 10, "specific_timing": {
        "use": True, "time_type": "relative", "timings": [0], "amounts": [10]}})
    assert counts(analyze_flow_strategy(timing, "t_op_0", rng=RNG())) == (0, 0)
    assert counts(EMPTY_SCHEDULE) == (0, 0)
    a = analyze_flow_strategy(oracle.GRID["zero_area"], "t_op_0", rng=RNG())
    assert a.empty and a == EMPTY_SCHEDULE and sum(counts(a)) == 1
