"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle,
per-program device time, collective time, the operations that took most
time, and the idle gaps labelled by what the runner was doing.

Kept with the benchmark so that every PR computes the same numbers the same
way; ``tests/benchmark/test_benchmark_trace_reduce.py`` checks it against a
small recorded trace. Reads the file with ``jax.profiler.ProfileData``, and
with ``xplane_reader.py`` for the one thing that leaves out: the stats of an
event's metadata, where an operation's scope path is kept.

What a TPU trace looks like (looked at by hand before this was written, PR
24): one plane per chip named ``/device:TPU:<n>``; on it the line
``XLA Modules`` has one event per execution of a compiled program (named
``jit_<function>(<fingerprint>)``; executions in flight when the stretch
starts or ends are clipped or missing, so nothing here counts them), the
line ``XLA Ops`` one event per HLO operation executed, named by its whole
HLO text (``%fusion.12 = bf16[..]{..} fusion(..), kind=.., calls=..``),
control-flow ops (``%while.3 = ...``) enclosing their bodies' ops. The
host's threads are lines of the plane ``/host:CPU``;
``jax.profiler.TraceAnnotation`` events appear there on a ``python`` line.
All planes share one clock (nanoseconds from the profiler's start).

Definitions:

- the stretch: the interval the harness names — whole rounds, from one
  round's start to a later round's start, given on the host's clock and
  moved onto the profiler's by the sync annotation — and every event is
  clipped to it, so busy and idle time are those of whole rounds, the waits
  at a round's edges included. Without one (the recorded test trace): first
  event start to last event end over all device planes;
- busy: the union of the ``XLA Ops`` events' intervals on a device (a
  ``while`` op spans its whole loop, so the union — not the sum — is used);
- idle share: 1 - busy / stretch;
- a program's device time: the sum of its ``XLA Modules`` events;
- an operation's program: the ``XLA Modules`` event of the same device
  whose interval holds the operation's start, named without its
  fingerprint (``jit_round_step``); ``""`` where none does. The device says
  it, not the ``op_name``: a kernel the compiler makes inside a ``while``
  (its grouped matmul with the local steps as a loop) arrives with no path
  at all, as the evaluation's does, and only the program tells the two
  apart (PR 46);
- an operation's time: the sum of its events, control-flow containers
  (``while``, ``conditional``, ``call``) left out because their bodies'
  operations are counted themselves;
- collective time: operations whose name starts with ``all-reduce``,
  ``all-gather``, ``all-to-all``, ``reduce-scatter`` or
  ``collective-permute``;
- an operation's scope path: the components of its ``op_name`` (the
  ``tf_op`` stat of its event metadata, ``<op_name>:<type>``, e.g.
  ``jit(round_step)/while/body/closed_call/client_train/closed_call/
  jvp(LFM2)/layers_1/moe/moe.experts/mul:``) with the tracing machinery
  stripped: ``jit(..)`` / ``pjit`` components and the control-flow words
  (``while``, ``body``, ``cond``, ``branch_<n>_fun``, ``closed_call``,
  ``shard_map``, ``checkpoint``, ``rematted_computation``) are dropped, and
  the wrappers ``jvp(..)``, ``transpose(..)``, ``vmap(..)`` and their like
  are peeled off what they wrap (``transpose(jvp(moe.experts))`` ->
  ``moe.experts``), so that the forward, backward and recomputed operations
  of one scope land on it. Module names (``LFM2``, ``layers_1``) stay, and
  so does the last component, the primitive (``mul``): a kernel the
  compiler renames and strips of its scope (its grouped matmul arrives as
  ``.../client_train/closed_call/ragged-dot-none``) is still found by it.
  Which pass an operation belongs to stays beside the path:
  ``recomputed`` where the raw path holds ``rematted_computation``, else
  ``backward`` where it holds a ``transpose(..)``, else ``forward``;
- **a fusion carries one ``op_name``, its root's: its whole time goes to that
  path.** Containers stay left out, as for an operation's time;
- a scope of the program's: one of ``PROGRAM_SCOPES`` (the round's stages
  and the next-token loss) or a component shaped ``<model or
  mechanism>.<kernel>`` (lower case, one dot: ``moe.experts``), which is how
  a model names its kernels (``docs/observability.md``), so a new one is
  found with no edit here. **An operation whose path holds none goes to
  ``unscoped``**: one with no ``op_name``, one the compiler made outside
  every scope (parameter copies of ``jit(round_step)``), every one of a
  trace read without its metadata.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC_EVENT = "benchmark_sync"
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute")
OP_NAME_STAT = "tf_op"
UNSCOPED = "unscoped"
PROGRAM_SCOPES = ("client_train", "delta_transform", "aggregate",
                  "server_update", "evaluate", "lm_loss")
KERNEL_SCOPE = re.compile(r"^[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*$")
FORWARD, BACKWARD, RECOMPUTED = "forward", "backward", "recomputed"
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
# Components that are no scope: control flow, and the empty string a peeled
# ``jit(..)`` leaves.
_DROPPED = re.compile(r"^(while|body|cond|branch_\d+_fun|closed_call|shard_map"
                      r"|checkpoint|rematted_computation|pjit|)$")


_HLO = re.compile(r"^%?([\w.\-]+) = (\(?[a-z]\w*\[[^\]]*\])?[^ ]* ?.*?([a-z][\w\-]*)\(")


def short_name(event_name: str) -> str:
    """``%fusion.12 = bf16[2,8]{1,0:T(..)} fusion(..)`` -> ``fusion.12
    bf16[2,8]``: the instruction's name and result shape, without layouts
    and operands."""
    m = _HLO.match(event_name)
    if m is None:
        return event_name[:80]
    return (m.group(1) + (" " + m.group(2).lstrip("(") if m.group(2) else ""))[:80]


def _base(event_name: str) -> str:
    """The instruction's kind from its name: ``%all-reduce.4 = ...`` ->
    ``all-reduce``; ``%while.150 = ...`` -> ``while``."""
    name = event_name.split(" = ")[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", name)


def is_scope(component: str) -> bool:
    return component in PROGRAM_SCOPES or bool(KERNEL_SCOPE.match(component))


def scope_path(op_name: str) -> Tuple[Tuple[str, ...], str]:
    """An ``op_name`` -> (scope path, FORWARD / BACKWARD / RECOMPUTED); see
    the module docstring."""
    name, colon, kind = op_name.rpartition(":")
    raw = (name if colon and "/" not in kind else op_name).split("/")
    which = FORWARD
    path = []
    for component in raw:
        while True:
            m = _WRAPPED.match(component)
            if m is None:
                break
            if m.group(1) == "transpose":
                which = BACKWARD
            component = "" if m.group(1) in ("jit", "pjit") else m.group(2)
        if not _DROPPED.match(component):
            path.append(component)
    if "rematted_computation" in raw:
        which = RECOMPUTED
    return tuple(path), which


def innermost_scope(path: Sequence[str]) -> str:
    return next((c for c in reversed(path) if is_scope(c)), UNSCOPED)


def _holds_in_order(path: Sequence[str], components: Sequence[str]) -> bool:
    rest = iter(path)
    return all(c in rest for c in components)


def _largest(total: Dict[str, float], limit: int) -> List[List]:
    return [[n, s] for n, s in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:limit]]


@dataclasses.dataclass
class DeviceTrace:
    index: int
    busy_s: float
    start_s: float                      # first op start (trace clock)
    end_s: float                        # last op end
    modules: Dict[str, Tuple[int, float]]     # name -> (executions, seconds)
    ops: Dict[str, float]                     # name -> seconds
    collective_s: float
    gaps: List[Tuple[float, float]]           # idle (start, end), trace clock
    # (scope path, FORWARD / BACKWARD / RECOMPUTED) -> seconds of the ops
    # counted in ``ops``; the empty path where an op has no op_name.
    scopes: Dict[Tuple[Tuple[str, ...], str], float] = dataclasses.field(
        default_factory=dict)
    # program -> the same seconds by the same keys, of the operations that
    # ran inside that program (``""``: inside none the trace recorded).
    programs: Dict[str, Dict[Tuple[Tuple[str, ...], str], float]] = (
        dataclasses.field(default_factory=dict))


@dataclasses.dataclass
class TraceSummary:
    devices: List[DeviceTrace]
    start_s: float
    end_s: float
    sync_s: Optional[float]             # SYNC_EVENT's start on the trace clock

    @property
    def window_s(self) -> float:
        return self.end_s - self.start_s

    @property
    def busy_s(self) -> float:
        """Mean over the devices used."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def idle_share(self, device: DeviceTrace) -> float:
        return 1.0 - device.busy_s / self.window_s

    @property
    def worst_idle_share(self) -> float:
        return max(self.idle_share(d) for d in self.devices)

    @property
    def busy_share(self) -> float:
        """Busy share of the stretch, mean over the devices."""
        return self.busy_s / self.window_s

    @property
    def collective_share(self) -> float:
        """Collective-operation time over the stretch, mean over devices."""
        return (sum(d.collective_s for d in self.devices)
                / len(self.devices) / self.window_s)

    def module_seconds(self, pattern: str) -> Tuple[int, float]:
        """(executions, seconds) of the recorded program executions whose
        name matches ``pattern``, mean over devices. For looking at a
        trace; no metric is built on it (see the module docstring)."""
        rx = re.compile(pattern)
        n = s = 0.0
        for d in self.devices:
            for name, (count, seconds) in d.modules.items():
                if rx.search(name):
                    n += count
                    s += seconds
        return int(round(n / len(self.devices))), s / len(self.devices)

    def top_ops(self, limit: int = 10) -> List[List]:
        total: Dict[str, float] = {}
        for d in self.devices:
            for name, seconds in d.ops.items():
                total[name] = total.get(name, 0.0) + seconds / len(self.devices)
        return _largest(total, limit)

    def _scopes(self, program: Optional[str] = None):
        """(path, which, seconds as a mean over the devices) of every entry,
        or of those of the operations that ran inside ``program``."""
        for d in self.devices:
            entries = d.scopes if program is None else d.programs.get(
                program, {})
            for (path, which), seconds in entries.items():
                yield path, which, seconds / len(self.devices)

    def scope_seconds(self, *components: str, which: Optional[str] = None,
                      program: Optional[str] = None) -> float:
        """Seconds, mean over devices, of the operations whose scope path
        holds all of ``components`` in this order (``("client_train",
        "moe.experts")`` is the train part of that kernel), all of them or
        only the FORWARD, BACKWARD or RECOMPUTED ones, in every program or
        only inside ``program`` (``"jit_round_step"``). ``UNSCOPED`` alone:
        those whose path holds no scope of the program's."""
        if components == (UNSCOPED,):
            return sum(s for p, w, s in self._scopes(program)
                       if not any(map(is_scope, p)))
        return sum(s for p, w, s in self._scopes(program)
                   if which in (None, w) and _holds_in_order(p, components))

    @property
    def op_seconds(self) -> float:
        """The plain sum of the operations' times, mean over devices: more
        than ``busy_s`` by what ran beside another operation."""
        return sum(s for _, _, s in self._scopes())

    def top_scopes(self, limit: int = 12) -> List[List]:
        """Seconds by the innermost scope of the program's on each
        operation's path, ``UNSCOPED`` among them, largest first."""
        total: Dict[str, float] = {}
        for path, _, seconds in self._scopes():
            name = innermost_scope(path)
            total[name] = total.get(name, 0.0) + seconds
        return _largest(total, limit)


def _union(intervals: Sequence[Tuple[float, float]]
           ) -> Tuple[float, List[Tuple[float, float]]]:
    """Total covered length and the gaps between the merged intervals."""
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def _sync_s(profile) -> Optional[float]:
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SYNC_EVENT:
                        return ev.start_ns * 1e-9
    return None


def reduce_profile(profile, host_interval: Optional[Tuple[float, float]] = None,
                   sync_host_s: Optional[float] = None,
                   metadata: Sequence = ()) -> TraceSummary:
    """``profile`` is a ``jax.profiler.ProfileData``. ``host_interval`` is
    the stretch (start, end) on the host clock and ``sync_host_s`` that
    clock's reading at SYNC_EVENT; both or neither. ``metadata`` is
    ``xplane_reader.read_planes`` of the same bytes; without it every
    operation is ``UNSCOPED``."""
    metadata_of = {plane.name: plane for plane in metadata}
    sync_s = _sync_s(profile)
    clip = None
    if host_interval is not None:
        if sync_s is None or sync_host_s is None:
            raise ValueError("a stretch on the host clock needs the clock "
                             "sync event, and the trace holds none")
        clip = (host_interval[0] - sync_host_s + sync_s,
                host_interval[1] - sync_host_s + sync_s)
    devices: List[DeviceTrace] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is None:
            continue
        intervals: List[Tuple[float, float]] = []
        ops: Dict[str, float] = {}
        scopes: Dict[Tuple[Tuple[str, ...], str], float] = {}
        programs: Dict[str, Dict[Tuple[Tuple[str, ...], str], float]] = {}
        modules: Dict[str, Tuple[int, float]] = {}
        collective = 0.0
        meta = metadata_of.get(plane.name)
        lines = {line.name: line for line in plane.lines}
        # The programs' executions first: (start, end, name), by start.
        executions = []
        for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines
                   else ()):
            name = ev.name.split("(")[0]
            n, s = modules.get(name, (0, 0.0))
            modules[name] = (n + 1, s + ev.duration_ns * 1e-9)
            executions.append((ev.start_ns * 1e-9,
                               (ev.start_ns + ev.duration_ns) * 1e-9, name))
        executions.sort()
        execution_starts = [x[0] for x in executions]
        if OPS_LINE in lines:
            events = list(lines[OPS_LINE].events)
            ids = (meta.line_event_ids.get(OPS_LINE, []) if meta
                   else [None] * len(events))
            if len(ids) != len(events):
                raise ValueError(
                    f"{plane.name}: {len(events)} operations and "
                    f"{len(ids)} metadata ids: not the same trace")
            scope_of: Dict[Optional[int], Tuple] = {None: ((), FORWARD)}
            for ev, metadata_id in zip(events, ids):
                began = s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if clip is not None:
                    s, e = max(s, clip[0]), min(e, clip[1])
                    if e <= s:
                        continue
                intervals.append((s, e))
                base = _base(ev.name)
                if base in CONTAINERS:
                    continue
                key = short_name(ev.name)
                ops[key] = ops.get(key, 0.0) + (e - s)
                scope = scope_of.get(metadata_id)
                if scope is None:
                    if meta.event_names.get(metadata_id) != ev.name:
                        raise ValueError(
                            f"{plane.name}: metadata {metadata_id} is not "
                            f"{ev.name[:60]!r}: not the same trace")
                    scope = scope_of[metadata_id] = scope_path(
                        meta.stat(metadata_id, OP_NAME_STAT) or "")
                scopes[scope] = scopes.get(scope, 0.0) + (e - s)
                i = bisect.bisect_right(execution_starts, began) - 1
                inside = programs.setdefault(
                    executions[i][2] if i >= 0 and began < executions[i][1]
                    else "", {})
                inside[scope] = inside.get(scope, 0.0) + (e - s)
                if base.startswith(COLLECTIVES):
                    collective += e - s
        if not intervals:
            continue
        busy, gaps = _union(intervals)
        start = min(s for s, _ in intervals)
        end = max(e for _, e in intervals)
        if clip is not None:        # the waits at the stretch's edges count
            gaps = ([(clip[0], start)] if start > clip[0] else []) + gaps + (
                [(end, clip[1])] if end < clip[1] else [])
            start, end = clip
        devices.append(DeviceTrace(
            index=int(m.group(1)), busy_s=busy, start_s=start, end_s=end,
            modules=modules, ops=ops, collective_s=collective, gaps=gaps,
            scopes=scopes, programs=programs))
    if not devices:
        raise ValueError("the trace holds no device plane with XLA Ops events"
                         + (" inside the stretch" if clip else ""))
    devices.sort(key=lambda d: d.index)
    return TraceSummary(
        devices=devices, start_s=min(d.start_s for d in devices),
        end_s=max(d.end_s for d in devices), sync_s=sync_s)


def reduce_file(path: str,
                host_interval: Optional[Tuple[float, float]] = None,
                sync_host_s: Optional[float] = None) -> TraceSummary:
    from jax.profiler import ProfileData

    from benchmark import xplane_reader

    with open(path, "rb") as f:
        data = f.read()
    return reduce_profile(ProfileData.from_serialized_xspace(data),
                          host_interval, sync_host_s,
                          xplane_reader.read_planes(data))


def label_gaps(summary: TraceSummary,
               host_spans: Sequence[Tuple[str, float, float]],
               sync_host_s: Optional[float], limit: int = 10) -> List[List]:
    """Idle seconds of the worst device by what the runner was doing: each
    gap goes to the innermost (latest-started) runner span that covers its
    middle on the host clock, or to ``between rounds``. ``host_spans`` are
    (name, start, duration) on the host clock; ``sync_host_s`` is the host
    clock at SYNC_EVENT, which ties the two clocks together."""
    device = max(summary.devices, key=lambda d: summary.idle_share(d))
    if summary.sync_s is None or sync_host_s is None:
        total = sum(e - s for s, e in device.gaps)
        return [["unlabelled (no clock sync event in the trace)", total]]
    offset = sync_host_s - summary.sync_s
    spans = sorted(host_spans, key=lambda s: s[1])
    by_label: Dict[str, float] = {}
    for s, e in device.gaps:
        mid = (s + e) / 2 + offset
        label = "between rounds"
        for name, start, duration in spans:
            if start > mid:
                break
            if mid <= start + duration:
                label = name            # later-started covering span wins
        by_label[label] = by_label.get(label, 0.0) + (e - s)
    return [[n, s] for n, s in sorted(by_label.items(),
                                      key=lambda kv: -kv[1])[:limit]]
