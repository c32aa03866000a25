"""From the runner's spans and history to rounds, the window and the
end-to-end numbers. Pure arithmetic on plain values (no JAX, no program
import), so the tests drive it with synthetic spans.

Definitions (PERF.md section 2):

- A round's first operator span is the earliest ``round.<operator>`` span
  (two name parts; ``round.<operator>.<phase>`` spans are phases) with that
  ``round_idx`` for the task. A round's time is the interval from its first
  operator span's start to the next round's first operator span's start, so
  gaps between operators and between rounds are inside it.
- The window opens at the start of round ``warmup_rounds`` and closes at
  the first round start that is at least ``seconds`` later. Every round in
  it is whole, and its length (>= ``seconds``, by less than one round) is
  the denominator of throughput: no round is cut, so the rate has no step
  of one round in it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Iterable, List, Optional, Sequence


@dataclasses.dataclass
class RoundTiming:
    idx: int
    start: float                      # first operator span's start
    end: Optional[float] = None       # next round's start (None: last seen)
    operators: Dict[str, float] = dataclasses.field(default_factory=dict)
    # (operator, phase) -> summed seconds
    phases: Dict[tuple, float] = dataclasses.field(default_factory=dict)
    # (name, start, duration) of every span of the round, for gap labels
    spans: List[tuple] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> Optional[float]:
        return None if self.end is None else self.end - self.start


@dataclasses.dataclass
class Window:
    open: float
    close: float
    rounds: List[RoundTiming]

    @property
    def seconds(self) -> float:
        return self.close - self.open


def rounds_from_spans(spans: Iterable[Any], task_id: str) -> List[RoundTiming]:
    """Round timings of ``task_id`` in round order. ``spans`` are objects
    with ``name``, ``start_s``, ``duration_s`` and ``attrs`` (the program's
    ``telemetry.Span``)."""
    by_idx: Dict[int, RoundTiming] = {}
    for s in spans:
        if not s.name.startswith("round.") or s.attrs.get("task_id") != task_id:
            continue
        idx = s.attrs.get("round_idx")
        if idx is None:
            continue
        parts = s.name.split(".")
        r = by_idx.setdefault(int(idx), RoundTiming(int(idx), math.inf))
        r.spans.append((s.name, s.start_s, s.duration_s))
        if len(parts) == 2:
            r.operators[parts[1]] = r.operators.get(parts[1], 0.0) + s.duration_s
            r.start = min(r.start, s.start_s)
        elif len(parts) == 3:
            key = (parts[1], parts[2])
            r.phases[key] = r.phases.get(key, 0.0) + s.duration_s
    rounds = [by_idx[i] for i in sorted(by_idx) if math.isfinite(by_idx[i].start)]
    for r, nxt in zip(rounds, rounds[1:]):
        if nxt.idx == r.idx + 1:
            r.end = nxt.start
    return rounds


def window_close_round(rounds: Sequence[RoundTiming], warmup_rounds: int,
                       seconds: float) -> Optional[RoundTiming]:
    """The round whose start closes the window, once it has been seen."""
    opener = next((r for r in rounds if r.idx == warmup_rounds), None)
    if opener is None:
        return None
    return next((r for r in rounds
                 if r.idx > warmup_rounds and r.start >= opener.start + seconds),
                None)


def select_window(rounds: Sequence[RoundTiming], warmup_rounds: int,
                  seconds: float) -> Optional[Window]:
    closer = window_close_round(rounds, warmup_rounds, seconds)
    if closer is None:
        return None
    inside = [r for r in rounds if warmup_rounds <= r.idx < closer.idx]
    if len(inside) != closer.idx - warmup_rounds or any(
            r.end is None for r in inside):
        return None    # a round of the window left no spans
    return Window(open=inside[0].start, close=closer.start, rounds=inside)


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def judge_rounds(window: Window, history: Sequence[Dict[str, Any]],
                 train_ops: Sequence[str], eval_ops: Sequence[str],
                 all_clients: Optional[int] = None) -> Dict[str, Any]:
    """``attempted`` rounds of the window, the ``failed`` among them with
    the reason, and the device-rounds completed. A round fails on a missing
    record, a non-finite ``mean_loss``/``eval_loss``, an ``eval_acc``
    outside [0, 1], or ``clients_trained`` different from what the record's
    own ``released`` count implies, or from ``all_clients`` where that is
    given (cells with no deviceflow strategy: every client, every round)."""
    records = {rec["round"]: rec for rec in history}
    failed: List[str] = []
    device_rounds = 0
    for r in window.rounds:
        rec = records.get(r.idx)
        why = None
        trained = 0
        if rec is None:
            why = "no record"
        else:
            for op in train_ops:
                for pop, t in (rec.get(op) or {}).items():
                    if not math.isfinite(t.get("mean_loss", math.nan)):
                        why = f"{op}/{pop}: mean_loss not finite"
                    elif t["clients_trained"] != (
                            t["released"] if all_clients is None
                            else all_clients):
                        why = (f"{op}/{pop}: clients_trained="
                               f"{t['clients_trained']}, released="
                               f"{t['released']}, expected {all_clients}")
                    else:
                        trained += int(t["clients_trained"])
                if op not in rec:
                    why = f"no {op} record"
            for op in eval_ops:
                for pop, e in (rec.get(op) or {}).items():
                    loss, acc = e.get("eval_loss"), e.get("eval_acc")
                    if loss is None or not math.isfinite(loss):
                        why = f"{op}/{pop}: eval_loss not finite"
                    elif acc is None or not 0.0 <= acc <= 1.0:
                        why = f"{op}/{pop}: eval_acc {acc} outside [0, 1]"
                if op not in rec:
                    why = f"no {op} record"
        if why is not None:
            failed.append(f"round {r.idx}: {why}")
        else:
            device_rounds += trained      # a failed round's work counts nothing
    return {"attempted": len(window.rounds), "failed": failed,
            "device_rounds": device_rounds}
