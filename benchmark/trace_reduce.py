"""From a profiler trace (``.xplane.pb``) to numbers: device busy and idle,
per-program device time, collective time, the operations that took most
time, and the idle gaps labelled by what the runner was doing.

Kept with the benchmark so that every PR computes the same numbers the same
way; ``tests/benchmark/test_benchmark_trace_reduce.py`` checks it against a
small recorded trace. Reads the file with ``jax.profiler.ProfileData`` and
nothing else.

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
- an operation's time: the sum of its events, control-flow containers
  (``while``, ``conditional``, ``call``) left out because their bodies'
  operations are counted themselves;
- collective time: operations whose name starts with ``all-reduce``,
  ``all-gather``, ``all-to-all``, ``reduce-scatter`` or
  ``collective-permute``.
"""

from __future__ import annotations

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
        return [[n, s] for n, s in sorted(total.items(),
                                          key=lambda kv: -kv[1])[:limit]]


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
                   sync_host_s: Optional[float] = None) -> TraceSummary:
    """``profile`` is a ``jax.profiler.ProfileData``. ``host_interval`` is
    the stretch (start, end) on the host clock and ``sync_host_s`` that
    clock's reading at SYNC_EVENT; both or neither."""
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
        modules: Dict[str, Tuple[int, float]] = {}
        collective = 0.0
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
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
                    if base.startswith(COLLECTIVES):
                        collective += e - s
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    name = ev.name.split("(")[0]
                    n, s = modules.get(name, (0, 0.0))
                    modules[name] = (n + 1, s + ev.duration_ns * 1e-9)
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
            modules=modules, ops=ops, collective_s=collective, gaps=gaps))
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

    return reduce_profile(ProfileData.from_file(path), host_interval,
                          sync_host_s)


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
