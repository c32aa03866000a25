"""The window, round and percentile arithmetic on synthetic spans."""

import dataclasses
import math

import pytest

from benchmark import window as win


@dataclasses.dataclass
class Span:
    name: str
    start_s: float
    duration_s: float
    attrs: dict


def make_spans(round_starts, task="t", train=0.6, evaluate=0.2):
    """Rounds starting at ``round_starts``: a train operator with phases,
    then an evaluate operator; other tasks' and non-round spans mixed in."""
    spans = []
    for idx, t in enumerate(round_starts):
        a = {"task_id": task, "round_idx": idx}
        spans += [
            Span("round.train.select", t + 0.01, 0.02, a),
            Span("round.train.train", t + 0.03, 0.01, a),
            Span("round.train.host_transfer", t + 0.04, train - 0.05, a),
            Span("round.train", t, train, a),
            Span("round.evaluate.eval", t + train + 0.01, evaluate - 0.02, a),
            Span("round.evaluate", t + train + 0.005, evaluate, a),
            Span("round.round.checkpoint", t + train + evaluate + 0.01, 0.001, a),
            Span("round.train", t, train, {"task_id": "other", "round_idx": idx}),
            Span("grpc.call", t, 0.1, {}),
        ]
    return spans


def test_rounds_from_spans_intervals_include_gaps():
    starts = [0.0, 1.0, 2.5, 3.5]
    rounds = win.rounds_from_spans(make_spans(starts), "t")
    assert [r.idx for r in rounds] == [0, 1, 2, 3]
    assert [r.seconds for r in rounds] == [1.0, 1.5, 1.0, None]
    assert rounds[0].operators == {"train": 0.6, "evaluate": 0.2}
    assert rounds[0].phases[("train", "host_transfer")] == pytest.approx(0.55)
    assert ("round", "checkpoint") in rounds[0].phases
    assert len(rounds[0].spans) == 7


def test_window_opens_after_warmup_and_closes_on_a_round_boundary():
    starts = [0.0, 5.0, 6.0, 7.0, 8.0, 9.5, 10.5, 11.5]
    rounds = win.rounds_from_spans(make_spans(starts), "t")
    w = win.select_window(rounds, warmup_rounds=1, seconds=4.0)
    assert (w.open, w.close) == (5.0, 9.5)          # first start >= 5 + 4
    assert [r.idx for r in w.rounds] == [1, 2, 3, 4]
    assert w.seconds == 4.5 and all(r.end is not None for r in w.rounds)
    assert sum(r.seconds for r in w.rounds) == pytest.approx(w.seconds)
    # Not yet closed: no round has started late enough.
    assert win.select_window(rounds[:5], 1, 4.0) is None
    assert win.window_close_round(rounds, 1, 4.0).idx == 5
    # A round of the window that left no spans: no window, not a short one.
    holed = [r for r in rounds if r.idx != 3]
    assert win.select_window(holed, 1, 4.0) is None


def test_percentile_is_linear_interpolation():
    values = [1.0, 2.0, 3.0, 4.0, 10.0]
    assert win.percentile(values, 50) == 3.0
    assert win.percentile(values, 90) == pytest.approx(7.6)
    assert win.percentile([5.0], 90) == 5.0
    assert win.percentile([1.0, 2.0], 50) == 1.5
    with pytest.raises(ValueError):
        win.percentile([], 50)


def _record(idx, trained=10, released=10, loss=1.0, eval_loss=0.5, acc=0.5):
    return {"round": idx,
            "train": {"p": {"mean_loss": loss, "clients_trained": trained,
                            "released": released, "dropped": 10 - released}},
            "evaluate": {"p": {"eval_loss": eval_loss, "eval_acc": acc}}}


def _window(n=4):
    rounds = win.rounds_from_spans(make_spans([float(i) for i in range(n + 2)]), "t")
    return win.select_window(rounds, 1, float(n - 0.5))


@pytest.mark.parametrize("bad,reason", [
    (_record(2, loss=math.nan), "mean_loss"),
    (_record(2, trained=9), "clients_trained"),
    (_record(2, eval_loss=math.inf), "eval_loss"),
    (_record(2, acc=1.5), "eval_acc"),
    (None, "no record"),
])
def test_judge_rounds_counts_failures(bad, reason):
    w = _window()
    history = [_record(i) for i in range(6) if i != 2] + ([bad] if bad else [])
    verdict = win.judge_rounds(w, history, ["train"], ["evaluate"],
                               all_clients=10)
    assert verdict["attempted"] == len(w.rounds) == 4
    assert len(verdict["failed"]) == 1 and reason in verdict["failed"][0]
    assert verdict["device_rounds"] == 30


def test_judge_rounds_follows_the_records_released_count_under_a_trace():
    w = _window()
    history = [_record(i, trained=8, released=8) for i in range(6)]
    assert win.judge_rounds(w, history, ["train"], ["evaluate"]) == {
        "attempted": 4, "failed": [], "device_rounds": 32}
    # ... but where no trace withholds anyone, 8 of 10 is a failed round.
    assert len(win.judge_rounds(w, history, ["train"], ["evaluate"],
                                all_clients=10)["failed"]) == 4
