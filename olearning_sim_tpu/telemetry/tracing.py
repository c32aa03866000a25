"""Span tracer: causal, parent-linked wall-clock spans -> Perfetto JSON.

``jax.profiler`` answers "what did XLA do" at op granularity; this module
answers "what did the *program* do" — which task, which round, which
operator, phase and stage — at host granularity. Every span also enters a
``jax.profiler.TraceAnnotation`` of the same name, so whenever a profiler
session is running (``PerformanceManager.start_trace``, a benchmark's own)
the spans are host events of the profile itself, on the profiler's clock,
next to the device ops. :meth:`SpanTracer.export` writes the spans alone as
Chrome ``trace_event`` JSON, for runs without a profiler.

Usage::

    tracer = SpanTracer()            # or default_tracer()
    with tracer.span("round.train", round_idx=3, operator="train"):
        ...                          # nested spans parent-link automatically

Spans carry monotonic wall-clock durations, a per-tracer span id, the
enclosing span's id (``parent_id``), and free-form attributes rendered as
trace-event ``args``. Nesting is tracked per thread (a contextvar-free
``threading.local`` stack — spans never cross threads, matching the
trace_event ``B``/``E`` model Perfetto reconstructs per tid).
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import sys
import threading
import time
from typing import Any, Dict, Iterable, List, Optional


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, or None in a process that has not
    imported JAX: no profiler session can be running there, and a span must
    not be what imports JAX."""
    profiler = sys.modules.get("jax.profiler")
    return getattr(profiler, "TraceAnnotation", None)


def process_age_s() -> Optional[float]:
    """How long this process has lived, in seconds, by the kernel's own
    record: the start time in ``/proc/self/stat`` (field 22, clock ticks
    after boot) against ``/proc/uptime``. It counts what ran before the
    program's first line: the interpreter's start and, read later, the
    imports and the backend's initialisation. None where there is no
    ``/proc``."""
    try:
        with open("/proc/self/stat") as f:
            # The command name (field 2) may hold spaces and brackets:
            # count fields from its closing bracket, where field 3 starts.
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime_s = float(f.read().split()[0])
        return uptime_s - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def _peak_bytes(stats: Dict[str, Any]) -> int:
    """The allocator's peak of live buffers plus its peak reservation,
    where a running program's scratch memory is held."""
    return (int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)))


def stamp_device_memory(span: Optional["Span"], suffix: str = "",
                        devices: Optional[Iterable[Any]] = None
                        ) -> Optional[int]:
    """Set on ``span`` what the allocator of the fullest local device
    holds now and the most it has held: ``device_bytes_in_use`` and
    ``device_peak_bytes`` (``peak_bytes_in_use + peak_bytes_reserved``: a
    running program's scratch memory is in the reservation), each with
    ``suffix`` appended. Called at the edges where the owner of device
    memory changes (data placed, state made, a round answered), so that
    the differences between two stamps say who holds what. Returns the
    peak. Sets nothing and returns None on a disabled tracer's span, in a
    process that has not imported JAX (a span must not be what imports it)
    and where the backend keeps no statistics (CPU). ``devices`` is for
    the tests; the default is ``jax.local_devices()``."""
    if span is None:
        return None
    if devices is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        devices = jax.local_devices()
    kept = [stats for stats in (d.memory_stats() for d in devices) if stats]
    if not kept:
        return None
    fullest = max(kept, key=_peak_bytes)
    peak = _peak_bytes(fullest)
    span.attrs["device_bytes_in_use" + suffix] = int(
        fullest.get("bytes_in_use", 0))
    span.attrs["device_peak_bytes" + suffix] = peak
    return peak


@dataclasses.dataclass
class Span:
    name: str
    span_id: int
    parent_id: Optional[int]
    start_s: float          # monotonic start (tracer epoch-relative)
    duration_s: float = 0.0
    thread_id: int = 0
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_trace_event(self) -> Dict[str, Any]:
        """Chrome trace_event complete-event (``ph: X``) form; timestamps in
        microseconds per the spec."""
        args = {k: v for k, v in self.attrs.items()}
        args["span_id"] = self.span_id
        if self.parent_id is not None:
            args["parent_id"] = self.parent_id
        return {
            "name": self.name,
            "ph": "X",
            "cat": "runner",
            "ts": round(self.start_s * 1e6, 3),
            "dur": round(self.duration_s * 1e6, 3),
            "pid": os.getpid(),
            "tid": self.thread_id,
            "args": args,
        }


class _ActiveSpan:
    def __init__(self, tracer: "SpanTracer", span: Span):
        self._tracer = tracer
        self.span = span
        self._t0 = 0.0
        self._annotation = None

    def __enter__(self) -> Span:
        self._t0 = time.perf_counter()
        self._tracer._stack().append(self.span)
        # With no profiler session running a TraceMe is a flag check; with
        # one, the span is a host event of the profile, attributes as stats
        # (those set after entry are on the Span only).
        annotation = _trace_annotation()
        if annotation is not None:
            self._annotation = annotation(self.span.name, **self.span.attrs)
            self._annotation.__enter__()
        return self.span

    def __exit__(self, exc_type, exc, tb):
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        self.span.duration_s = time.perf_counter() - self._t0
        if exc_type is not None:
            self.span.attrs["error"] = f"{exc_type.__name__}: {str(exc)[:200]}"
        stack = self._tracer._stack()
        if stack and stack[-1] is self.span:
            stack.pop()
        self._tracer._finish(self.span)
        return False


class _NullSpanCtx:
    """Returned by a disabled tracer: zero bookkeeping, reusable."""

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullSpanCtx()


class SpanTracer:
    """Thread-safe span recorder with a bounded finished-span window.

    ``keep_last`` bounds memory for long runs (structured forensics keep the
    tail; exported files should be flushed per run/trace window anyway).
    """

    def __init__(self, keep_last: int = 65536, enabled: bool = True):
        self.keep_last = keep_last
        self.enabled = enabled
        self._lock = threading.Lock()
        self._local = threading.local()
        # A deque drops its oldest entry in O(1) once full.
        self._spans: collections.deque = collections.deque(maxlen=keep_last)
        self._next_id = 1
        self._epoch = time.perf_counter()

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **attrs: Any):
        """``with tracer.span("round.train", round_idx=3): ...`` — opens a
        span parented to the innermost open span on this thread."""
        if not self.enabled:
            return _NULL_CTX
        stack = self._stack()
        parent = stack[-1].span_id if stack else None
        with self._lock:
            span_id = self._next_id
            self._next_id += 1
        return _ActiveSpan(self, Span(
            name=name, span_id=span_id, parent_id=parent,
            start_s=time.perf_counter() - self._epoch,
            thread_id=threading.get_ident() & 0x7FFFFFFF,
            attrs=dict(attrs),
        ))

    def current(self) -> Optional[Span]:
        """The innermost span open on the calling thread, or None."""
        stack = self._stack()
        return stack[-1] if stack else None

    def record(self, name: str, start_s: float, duration_s: float,
               **attrs: Any) -> Optional[Span]:
        """A finished span stamped after the fact (an interval somebody
        else timed, e.g. JAX's compile events): ``start_s`` is on this
        tracer's clock (:meth:`now`), the parent is the innermost span open
        on the calling thread. Returns the span, or None when disabled."""
        if not self.enabled:
            return None
        parent = self.current()
        with self._lock:
            span_id = self._next_id
            self._next_id += 1
        span = Span(
            name=name, span_id=span_id,
            parent_id=parent.span_id if parent is not None else None,
            start_s=start_s, duration_s=duration_s,
            thread_id=threading.get_ident() & 0x7FFFFFFF,
            attrs=dict(attrs),
        )
        self._finish(span)
        return span

    def _finish(self, span: Span) -> None:
        with self._lock:
            self._spans.append(span)

    # ---------------------------------------------------------------- reads
    def spans(self, name: Optional[str] = None) -> List[Span]:
        with self._lock:
            out = list(self._spans)
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def now(self) -> float:
        """Tracer-relative clock (same scale as ``Span.start_s``) — a
        watermark for windowed exports."""
        return time.perf_counter() - self._epoch

    # --------------------------------------------------------------- export
    def to_trace_events(
        self, since_s: Optional[float] = None
    ) -> List[Dict[str, Any]]:
        """``since_s`` (tracer-relative, from :meth:`now`) limits the export
        to spans started after the watermark — e.g. only the spans inside
        one XLA trace window, not the whole process history."""
        return [
            s.to_trace_event() for s in self.spans()
            if since_s is None or s.start_s >= since_s
        ]

    def to_perfetto_json(self, since_s: Optional[float] = None) -> str:
        """Chrome/Perfetto ``trace_event`` JSON (object form with
        ``traceEvents``, the shape both UIs and TensorBoard accept)."""
        return json.dumps({
            "traceEvents": self.to_trace_events(since_s),
            "displayTimeUnit": "ms",
        })

    def export(self, path: str, since_s: Optional[float] = None) -> str:
        """Write the Perfetto JSON next to (typically) an XLA trace dir;
        returns ``path``. Parent directories are created."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            f.write(self.to_perfetto_json(since_s))
        return path


_DEFAULT = SpanTracer()


def default_tracer() -> SpanTracer:
    """The process-wide tracer (what instrumented modules use when no tracer
    is injected)."""
    return _DEFAULT


def set_default_tracer(tracer: SpanTracer) -> SpanTracer:
    global _DEFAULT
    old, _DEFAULT = _DEFAULT, tracer
    return old
