"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-op
device time and idle gaps attributed to the harness's host spans.

Device planes are those named ``/device:<kind>:<n>``; their op line
(``XLA Ops``) holds one event per operation run on the device.  Busy time is
the union of those events inside the traced window, taken per device and
averaged over devices.  Ops can nest (a loop and the ops of its body), so
each op's time is its self time: its duration less the part that ops nested
in it cover.  The window is the extent of the harness's ``window`` span on
the host plane, and each stretch of the window in which a device ran
nothing is charged to the harness span (``submit``, ``engine_step``,
``wait_arrival``) open on the host at that moment, or to ``other``.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "window"
HOST_SPANS = ("submit", "engine_step", "wait_arrival")
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OP_LINES = ("XLA Ops",)

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclass
class Reduced:
    window_s: float
    busy_s: float                        # mean over devices
    devices: int
    op_self_s: Dict[str, float] = field(default_factory=dict)   # summed over devices
    op_calls: Dict[str, int] = field(default_factory=dict)
    idle_by_span: Dict[str, float] = field(default_factory=dict)  # mean over devices
    gaps: List[Tuple[str, float]] = field(default_factory=list)   # longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, pattern: str) -> Tuple[int, float]:
        """Calls and device seconds (summed over devices) of the ops whose
        name matches ``pattern``."""
        rx = re.compile(pattern)
        calls = sum(n for k, n in self.op_calls.items() if rx.search(k))
        secs = sum(s for k, s in self.op_self_s.items() if rx.search(k))
        return calls, secs


def union(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, as disjoint
    sorted intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def complement(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of ``[lo, hi]`` that the disjoint sorted ``busy``
    intervals leave uncovered."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> Tuple[Dict[str, float], Dict[str, int]]:
    """Per-name self time (ns) and call count of possibly nested events
    ``(name, start, end)``."""
    secs: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    stack: List[list] = []               # [name, start, end, nested_ns]

    def close(item):
        name, s, e, nested = item
        secs[name] += (e - s) - nested

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
        calls[name] += 1
    while stack:
        close(stack.pop())
    return dict(secs), dict(calls)


def charge_gaps(gaps: Sequence[Interval], spans: Sequence[Tuple[str, float, float]]
                ) -> Dict[str, float]:
    """Seconds of ``gaps`` under each host span (disjoint spans assumed);
    uncovered time goes to ``other``."""
    out: Dict[str, float] = defaultdict(float)
    spans = sorted(spans, key=lambda x: x[1])
    j = 0
    for gs, ge in gaps:
        covered = 0.0
        while j < len(spans) and spans[j][2] <= gs:
            j += 1
        k = j
        while k < len(spans) and spans[k][1] < ge:
            name, s, e = spans[k]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                out[name] += ov / 1e9
                covered += ov
            k += 1
        out["other"] += (ge - gs - covered) / 1e9
    return dict(out)


_HLO = re.compile(r"^%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def short_name(op: str) -> str:
    """``name (kind)`` of an op whose trace name is its whole HLO text, such
    as ``%fusion.220 = bf16[...] fusion(...)``; other names unchanged."""
    m = _HLO.match(op)
    if not m:
        return op
    kind = m.group(2)
    if 'tpu_custom_call' in op:
        kind = "custom-call tpu_custom_call"
    return f"{m.group(1)} ({kind})"


def _span_at(spans: Sequence[Tuple[str, float, float]], t: float) -> str:
    for name, s, e in spans:
        if s <= t < e:
            return name
    return "other"


def reduce_planes(planes, host_spans: Sequence[str] = HOST_SPANS) -> Reduced:
    """Reduce profiler planes (``ProfileData.planes``) to a :class:`Reduced`."""
    window = None
    spans: List[Tuple[str, float, float]] = []
    device_events: List[List[Tuple[str, float, float]]] = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name in host_spans:
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
        elif _DEVICE_PLANE.match(plane.name):
            evs = [(ev.name, ev.start_ns, ev.end_ns)
                   for line in plane.lines if line.name in OP_LINES
                   for ev in line.events]
            device_events.append(evs)
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span on a host plane")
    if not device_events:
        raise ValueError("trace has no device plane with an op line")
    lo, hi = window
    spans.sort(key=lambda x: x[1])
    busy_total = 0.0
    op_self: Dict[str, float] = defaultdict(float)
    op_calls: Dict[str, int] = defaultdict(int)
    idle: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for evs in device_events:
        inside = [(n, s, e) for n, s, e in evs if e > lo and s < hi]
        busy = union([(s, e) for _, s, e in inside], lo, hi)
        busy_total += sum(e - s for s, e in busy) / 1e9
        secs, calls = self_times(inside)
        for k, v in secs.items():
            op_self[k] += v / 1e9
        for k, v in calls.items():
            op_calls[k] += v
        free = complement(busy, lo, hi)
        for k, v in charge_gaps(free, spans).items():
            idle[k] += v / len(device_events)
        free.sort(key=lambda g: g[0] - g[1])
        gaps.extend((_span_at(spans, s), (e - s) / 1e9) for s, e in free[:10])
    n = len(device_events)
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy_total / n,
                   devices=n, op_self_s=dict(op_self),
                   op_calls=dict(op_calls), idle_by_span=dict(idle),
                   gaps=gaps[:10])


def reduce_file(path: str) -> Reduced:
    import jax
    return reduce_planes(jax.profiler.ProfileData.from_file(str(path)).planes)
