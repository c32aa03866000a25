"""The program's own span tree, read for the per-layer metrics that the
harness's ``RunContext`` does not carry: spans of this submission, by name,
from ``olearning_sim_tpu.telemetry.default_tracer()`` — the tracer whose
clock the harness stamps ``ctx.t_submitted``, ``ctx.t_running`` and
``ctx.window`` on.

The tree (docs/observability.md): ``task.queue_wait``, ``bridge.build`` with
``bridge.generate`` / ``bridge.place`` / ``bridge.build_fedcore``,
``bridge.init_state``, ``compile.trace`` / ``.lower`` / ``.backend`` /
``.cache_load``, and ``round.<operator>.<phase>[.<stage>]``, all carrying
``task_id``; ``round.<operator>.host_transfer`` carries the work counts.

A program without the tree (the parent of the PR that added it) leaves no
``bridge.build`` span: :func:`task_spans` then returns None and every
reader built on it reports nothing, without raising. With the tree, a sum
over no spans is 0.0 (nothing compiled in this process, no evaluate
operator): a value, not a gap.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.window import percentile

# One process may run a task id twice (control.py), so only spans of this
# submission count: those that start after submitTask returned, less the
# moment by which ``task.queue_wait`` — stamped when the submit was
# accepted, inside the RPC — precedes it. An earlier run of the same id
# ended before its own check round, many seconds back.
SUBMIT_SLACK_S = 1.0
ROOT = "bridge.build"


def task_spans(ctx) -> Optional[Dict[str, List[Any]]]:
    """name -> spans of this submission (``telemetry.Span`` objects, in
    start order), or None where the program records no span tree."""
    from olearning_sim_tpu.telemetry import default_tracer

    task_id = ctx.task["task_id"]
    by_name: Dict[str, List[Any]] = {}
    for s in default_tracer().spans():
        if (s.attrs.get("task_id") == task_id
                and s.start_s >= ctx.t_submitted - SUBMIT_SLACK_S):
            by_name.setdefault(s.name, []).append(s)
    if ROOT not in by_name:
        return None
    for spans in by_name.values():
        spans.sort(key=lambda s: s.start_s)
    return by_name


def seconds(ctx, *names: str, until: Optional[float] = None
            ) -> Optional[float]:
    """Summed duration of this submission's spans with one of ``names``,
    those started before ``until`` (span clock) where given."""
    by_name = task_spans(ctx)
    if by_name is None:
        return None
    return sum(s.duration_s for n in names for s in by_name.get(n, ())
               if until is None or s.start_s < until)


def window_round_ms(ctx, phase: str, stage: Optional[str] = None
                    ) -> Optional[List[float]]:
    """Per round of the window, the milliseconds in ``round.<operator>
    .<phase>`` spans (any operator), or in their ``.<stage>`` children.
    None where the program has no stages (no span tree)."""
    if task_spans(ctx) is None:
        return None
    want = 3 if stage is None else 4
    out = []
    for r in ctx.window.rounds:
        total = 0.0
        for name, _start, duration in r.spans:
            parts = name.split(".")
            if (len(parts) == want and parts[2] == phase
                    and (stage is None or parts[3] == stage)):
                total += duration
        out.append(1e3 * total)
    return out


def median(values: Optional[List[float]]) -> Optional[float]:
    return None if values is None else percentile(values, 50)
