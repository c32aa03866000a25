"""What the set-up and device-memory readers under ``layer_metrics/`` share:
the program's ``session.start`` span, the ``program`` attribute of a task's
``compile.*`` spans, and the device-memory stamps on the spans at whose
edges the owner of device memory changes (``docs/observability.md``, "Where
a task's set-up and memory go"). All from
``olearning_sim_tpu.telemetry.default_tracer()``, the tracer whose clock the
harness stamps ``ctx.t_submitted`` and ``ctx.window`` on.

A program without the span or the attribute (the parent of the PR that
added them), and a backend whose allocator keeps no statistics (CPU),
leave nothing to read: every function then returns None and the reader
built on it leaves its metric out, without raising.
"""

from __future__ import annotations

from typing import Any, List, Optional

from benchmark import program_spans

COMPILE = ("compile.trace", "compile.lower", "compile.backend",
           "compile.cache_load")
ROUND_PROGRAM = "round_step"


def serving_span(ctx) -> Optional[Any]:
    """The last ``session.start`` span that began before the submit and
    carries ``process_age_s`` (how long the process had lived when the
    session started): the session this run's task was submitted to."""
    from olearning_sim_tpu.telemetry import default_tracer

    spans = [s for s in default_tracer().spans("session.start")
             if s.start_s < ctx.t_submitted and "process_age_s" in s.attrs]
    return max(spans, key=lambda s: s.start_s, default=None)


def setup_compiles(ctx) -> Optional[List[Any]]:
    """This submission's ``compile.*`` spans that began before the window
    opened and name their ``program``; None where none does."""
    by_name = program_spans.task_spans(ctx)
    if by_name is None:
        return None
    spans = [s for name in COMPILE for s in by_name.get(name, ())
             if s.start_s < ctx.window.open and "program" in s.attrs]
    return spans or None


def _stamp(by_name, name: str, attr: str) -> Optional[int]:
    """``attr`` of this submission's last span called ``name``."""
    spans = [s for s in by_name.get(name, ()) if attr in s.attrs]
    return spans[-1].attrs[attr] if spans else None


def memory_marks(ctx) -> Optional[List[int]]:
    """Bytes on the fullest chip at the four edges, in order: in use when
    ``bridge.build`` opened (other tasks' buffers), in use when the last
    ``bridge.place`` closed (+ the resident data), in use when
    ``bridge.init_state`` closed (+ the server state), and the allocator's
    peak on the last ``host_transfer`` or ``eval`` phase span of the
    window's last round (+ what the programs add while they run: scratch,
    outputs, evaluation batches). Differences of neighbours are the three
    parts; the last mark is what the harness reads right after as the
    run's peak. None where a stamp is missing."""
    by_name = program_spans.task_spans(ctx)
    if by_name is None:
        return None
    last_round = ctx.window.rounds[-1].idx
    answered = [s for name, spans in by_name.items()
                if name.count(".") == 2
                and name.endswith((".host_transfer", ".eval"))
                for s in spans
                if s.attrs.get("round_idx") == last_round
                and "device_peak_bytes" in s.attrs]
    marks = [
        _stamp(by_name, "bridge.build", "device_bytes_in_use_before"),
        _stamp(by_name, "bridge.place", "device_bytes_in_use"),
        _stamp(by_name, "bridge.init_state", "device_bytes_in_use"),
        max(answered, key=lambda s: s.start_s).attrs["device_peak_bytes"]
        if answered else None,
    ]
    return None if None in marks else marks


def memory_part_gb(ctx, part: int) -> Optional[float]:
    """Part ``part`` of the peak in GB (10^9 bytes, as
    ``device.hbm_peak_gb``): 1 the resident data, 2 the server state, 3
    what the programs add."""
    marks = memory_marks(ctx)
    return None if marks is None else (marks[part] - marks[part - 1]) / 1e9
