"""What ``deviceflow.strategy._interval_schedule`` was before it kept its
curve plans: every call parses and evaluates the rate expression at every
point (``eval`` of the string, 101 points a slot) and integrates anew. The
memoised function is held to this one, element for element, over
``GRID`` (tests/test_deviceflow_strategy.py, tests/test_trace_compiler.py).
"""

import contextlib
import math
from datetime import datetime

import numpy as np
import pytest

from olearning_sim_tpu.deviceflow import strategy
from olearning_sim_tpu.deviceflow.strategy import (
    AREA_CALCULATION_NUM,
    EMPTY_SCHEDULE,
    DispatchSchedule,
    _drop_lists,
)


def _eval_rate(expression, t):
    return float(eval(expression, {"__builtins__": {}},
                      {"math": math, "np": np, "t": t}))


def interval_schedule(total, intervals, domains, functions, drop_spec, rng):
    t_list, area_list = [], []
    for interval, domain, fn in zip(intervals, domains, functions):
        ilen = interval[1] - interval[0]
        dlen = domain[1] - domain[0]
        seconds = list(range(int(interval[0]), int(interval[1]) + 1))
        dom_pts = [domain[0] + dlen / ilen * (s - seconds[0]) for s in seconds]
        areas = []
        for i in range(len(dom_pts) - 1):
            ts = np.linspace(dom_pts[i], dom_pts[i + 1],
                             num=AREA_CALCULATION_NUM + 1)
            ys = [_eval_rate(fn, float(t)) for t in ts]
            area = 0.0
            for j in range(1, len(ys)):
                seg = 0.5 * (ys[j] + ys[j - 1]) * (1.0 / AREA_CALCULATION_NUM)
                if seg > 0:
                    area += seg
            areas.append(area)
        t_list.append(seconds[:-1])
        area_list.append(areas)

    totals = [sum(a) for a in area_list]
    grand = sum(totals)
    if grand <= 0:
        return EMPTY_SCHEDULE

    amount_per_interval = [round(t / grand * total) for t in totals]
    amount_per_interval[-1] = total - sum(amount_per_interval[:-1])
    per_interval_sends = []
    for k, areas in enumerate(area_list):
        target = amount_per_interval[k]
        ideal = [a / totals[k] * target for a in areas]
        sends, carry = [], 0.0
        for v in ideal:
            acc = carry + v
            if round(acc) > 0:
                sends.append(int(round(acc)))
                carry = acc - round(acc)
            else:
                sends.append(0)
                carry = acc
        per_interval_sends.append(sends)

    if "drop_probability" in drop_spec:
        probs = drop_spec.get("drop_probability", [])
        expanded = []
        for k, sends in enumerate(per_interval_sends):
            expanded.extend([probs[k]] * len(sends))
        drop_spec = {"drop_probability": expanded}
    elif "drop_amounts" in drop_spec:
        amounts_in = drop_spec.get("drop_amounts", [])
        expanded = []
        for k, sends in enumerate(per_interval_sends):
            total_k = sum(sends)
            d = int(amounts_in[k])
            if d == 0:
                expanded.extend([0] * len(sends))
            elif d >= total_k:
                expanded.extend(sends)
            else:
                chosen = sorted(
                    rng.choice(total_k, size=d, replace=False).tolist())
                pos, out = 0, []
                for s in sends:
                    out.append(sum(1 for c in chosen if pos <= c < pos + s))
                    pos += s
                expanded.extend(out)
        drop_spec = {"drop_amounts": expanded}

    flat_times, flat_amounts = [], []
    for seconds, sends in zip(t_list, per_interval_sends):
        flat_times.extend(seconds)
        flat_amounts.extend(sends)
    timings = [float(flat_times[0])] + [
        float(flat_times[i] - flat_times[i - 1])
        for i in range(1, len(flat_times))
    ]
    drop_lists = _drop_lists(flat_amounts, drop_spec, rng) if drop_spec else [
        [] for _ in flat_amounts
    ]
    return DispatchSchedule(timings, flat_amounts, drop_lists)


@contextlib.contextmanager
def as_before():
    """Inside, ``analyze_flow_strategy`` and ``compile_trace`` integrate
    every call, as they did."""
    kept = strategy._interval_schedule
    strategy._interval_schedule = interval_schedule
    try:
        yield
    finally:
        strategy._interval_schedule = kept


@pytest.fixture
def no_plans():
    """A test of the memo starts and ends with no plan kept."""
    strategy._curve_plans.clear()
    yield strategy._curve_plans
    strategy._curve_plans.clear()


# ------------------------------------------------------------------ the grid
ROUNDS = range(6)
SEEDS = (3, 2**31 + 11)
NOW = datetime(2026, 1, 1, 0, 0, 0)   # before every absolute interval below


def interval_strategy(intervals, domains, functions, total, drop=None,
                      time_type="relative"):
    spec = {
        "use": True, "time_type": time_type, "time_zone": "UTC",
        "intervals": intervals,
        "dispatch_rules": {"domains": domains, "functions": functions},
    }
    if drop:
        spec["drop_simulation"] = drop
    return {"flow_dispatch": {"use_strategy": True,
                              "total_dispatch_amount": total,
                              "specific_interval": spec}}


def _clock(second):
    return f"2026-01-01 00:{second // 60:02d}:{second % 60:02d}"


def _absolute(rounds):
    """Per round a list of [start, end] second pairs -> the grammar's
    wall-clock strings."""
    return [[[_clock(a), _clock(b)] for a, b in pairs] for pairs in rounds]


SPIKE = "math.sin(t/6.0)+1.1"       # benchmark/traffic/128_spike.json's curve
THREE = dict(intervals=[[0, 6], [6, 10], [14, 20]],
             domains=[[0.0, 6.0], [0.0, 2.0], [-3.0, 3.0]],
             functions=["math.sin(t)+1.1", "3", "math.exp(-t*t)"])

GRID = {
    "one_interval_drop_probability": interval_strategy(
        [[0, 20]], [[0.0, 120.0]], [SPIKE], 128,
        drop={"drop_probability": [0.05]}),
    "one_interval_total_1": interval_strategy(
        [[0, 20]], [[0.0, 120.0]], [SPIKE], 1,
        drop={"drop_probability": [0.5]}),
    "one_interval_total_5000": interval_strategy(
        [[0, 20]], [[0.0, 120.0]], [SPIKE], 5000,
        drop={"drop_amounts": [250]}),
    "three_intervals_no_drop": interval_strategy(total=128, **THREE),
    "three_intervals_drop_probability": interval_strategy(
        total=128, drop={"drop_probability": [0.1, 0.0, 0.3]}, **THREE),
    "three_intervals_drop_amounts": interval_strategy(
        total=128, drop={"drop_amounts": [5, 0, 1000]}, **THREE),
    "negative_rate_stretch": interval_strategy(
        [[0, 13]], [[0.0, 6.5]], ["math.sin(t)"], 128,
        drop={"drop_probability": [0.2]}),
    "zero_area": interval_strategy(
        [[0, 5]], [[0.0, 5.0]], ["-1"], 50,
        drop={"drop_probability": [0.2]}),
    "numpy_and_leading_blanks": interval_strategy(
        [[0, 8]], [[0.0, 3.0]], [" \tnp.abs(np.cos(t))+0.01*t"], 128,
        drop={"drop_amounts": [7]}),
    # Rounds 0, 2 and 4 share one interval list, 1 and 5 another, 3 its own.
    "absolute_one_interval": interval_strategy(
        _absolute([[(10, 20)], [(10, 25)], [(30, 40)], [(5, 12)], [(50, 60)],
                   [(40, 55)]]),
        [[0.0, 30.0]], [SPIKE], 128, drop={"drop_probability": [0.05]},
        time_type="absolute"),
    "absolute_three_intervals": interval_strategy(
        _absolute([[(10, 16), (16, 20), (24, 30)]] * 3
                  + [[(10, 14), (20, 26), (26, 30)]] * 3),
        THREE["domains"], THREE["functions"], 128,
        drop={"drop_amounts": [3, 3, 3]}, time_type="absolute"),
}
