"""Reduce the program's ``engine.*`` profiler spans, on the device's clock.

``Engine.step`` opens the spans ``engine.step``, ``engine.prefill`` (one per
admission), ``engine.decode`` with its children ``engine.decode.dispatch``
and ``engine.decode.sync``, and ``engine.retire``; their keyword args come
back as the trace events' stats.  This module keeps those inside the
harness's ``window`` span, and reads the device planes' ``XLA Modules``
line (one event per program execution) beside the ``XLA Ops`` line that
:mod:`bench.trace_reduce` reads.

The profiler converts device times to the host's timebase, but not exactly:
the two clocks can differ by about a millisecond, which is most of the
host's gap between two decode steps.  Each decode step is paired with the
module execution that overlaps most with its interval from the
``engine.decode.dispatch`` start to the ``engine.decode.sync`` end.  The
offset δ added to device times is bracketed by causality: an execution
starts after its dispatch began (``lo`` = max of dispatch start − execution
start) and ends before the host holds its result (``hi`` = min of sync end −
execution end).  That bracket is about as wide as the host's work around a
dispatch, so where the TPU runtime's own host events are in the trace
(host tracer level 1) it is narrowed by the same rule: an execution starts
after its program was put on the device's queue (``DoEnqueueProgram``) and
ends before the runtime tells the host it is done
(``tpu::System::Execute=>Done``).  δ is the least shift causality needs in
the narrower bracket: its low end where the device's clock reads early, its
high end where it reads late, and none where the bracket holds 0.  With an
empty bracket nothing is shifted and the readers that need the shift read
nothing.

The device's idle stretches inside ``engine.step`` spans, after the shift,
are charged to the innermost ``engine.*`` span open on the host then.  The
reduction of :mod:`bench.trace_reduce` is left as it is.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench.trace_reduce import (OP_LINES, WINDOW_SPAN, _DEVICE_PLANE,
                                charge_gaps, complement, union)

PREFIX = "engine."
STEP, DECODE = "engine.step", "engine.decode"
DISPATCH, SYNC = "engine.decode.dispatch", "engine.decode.sync"
MODULE_LINES = ("XLA Modules",)
# host events of the TPU runtime: a program enters the device's queue; the
# host learns that an execution has finished
ENQUEUE, DONE = "DoEnqueueProgram", "tpu::System::Execute=>Done"

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclass
class Span:
    name: str
    start: float                         # ns
    end: float
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9


@dataclass
class EngineTrace:
    window: Interval
    spans: List[Span]                    # engine.* spans inside the window
    paired: int                          # decode steps paired with a module
    lo: Optional[float] = None           # ns, from the engine's spans;
    hi: Optional[float] = None           # None when nothing paired
    lo_rt: Optional[float] = None        # ns, from the runtime's events;
    hi_rt: Optional[float] = None        # None when the trace has none
    ops: List[List[Interval]] = field(default_factory=list, repr=False)

    @property
    def bracket(self) -> Tuple[Optional[float], Optional[float]]:
        """The narrower of the two brackets (their intersection)."""
        if self.lo is None or self.lo_rt is None:
            return self.lo, self.hi
        return max(self.lo, self.lo_rt), min(self.hi, self.hi_rt)

    @property
    def aligned(self) -> bool:
        lo, hi = self.bracket
        return lo is not None and lo <= hi

    @property
    def delta(self) -> Optional[float]:
        """The shift of least size in the bracket (ns); None if empty."""
        lo, hi = self.bracket
        return min(max(0.0, lo), hi) if self.aligned else None

    def idle_by_span(self, delta: Optional[float] = None
                     ) -> Optional[Dict[str, float]]:
        """Device idle (seconds, mean over devices) inside ``engine.step``
        spans, with device times shifted by ``delta`` (default
        :attr:`delta`), by innermost open span; None if the bracket is
        empty."""
        if not self.aligned:
            return None
        return idle_split(self.ops, self.spans, self.window,
                          self.delta if delta is None else delta)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]


def decode_steps(spans: Sequence[Span]) -> List[Interval]:
    """For each ``engine.decode`` span that holds a dispatch and a sync
    span: (dispatch start, sync end)."""
    spans = sorted(spans, key=lambda s: (s.start, -s.end))
    starts = [s.start for s in spans]
    out = []
    for d in spans:
        if d.name != DECODE:
            continue
        kids = spans[bisect.bisect_left(starts, d.start):
                     bisect.bisect_right(starts, d.end)]
        disp = [k for k in kids if k.name == DISPATCH and k.end <= d.end]
        sync = [k for k in kids if k.name == SYNC and k.end <= d.end]
        if disp and sync:
            out.append((disp[0].start, sync[-1].end))
    return out


def pair(steps: Sequence[Interval], modules: Sequence[Interval]
         ) -> List[Tuple[Interval, Interval]]:
    """Each step with the module execution (disjoint, any order) that
    overlaps it most; steps that overlap none are left out."""
    modules = sorted(modules)
    ends = [e for _, e in modules]
    out = []
    for s, e in steps:
        best, most = None, 0.0
        for i in range(bisect.bisect_right(ends, s), len(modules)):
            m = modules[i]
            if m[0] >= e:
                break
            ov = min(e, m[1]) - max(s, m[0])
            if ov > most:
                best, most = m, ov
        if best is not None:
            out.append(((s, e), best))
    return out


def span_bracket(pairs: Sequence[Tuple[Interval, Interval]]
                 ) -> Tuple[Optional[float], Optional[float]]:
    """(lo, hi) of the offset δ that causality allows on every pair."""
    if not pairs:
        return None, None
    lo = max(st[0] - m[0] for st, m in pairs)
    hi = min(st[1] - m[1] for st, m in pairs)
    return lo, hi


def runtime_bracket(pairs: Sequence[Tuple[Interval, Interval]],
                    enqueues: Sequence[float], dones: Sequence[float]
                    ) -> Tuple[Optional[float], Optional[float]]:
    """(lo, hi) of δ from the runtime's host events (start times) inside
    each pair's step interval: the first enqueue, and the last "done" that
    follows it, are the execution's own or bound it more loosely."""
    enqueues, dones = sorted(enqueues), sorted(dones)
    los, his = [], []
    for (s, e), (ms, me) in pairs:
        i = bisect.bisect_left(enqueues, s)
        j = bisect.bisect_right(dones, e) - 1
        if i < len(enqueues) and j >= 0 and enqueues[i] <= dones[j]:
            los.append(enqueues[i] - ms)
            his.append(dones[j] - me)
    return (max(los), min(his)) if los else (None, None)


def innermost(spans: Sequence[Span]) -> List[Tuple[str, float, float]]:
    """Disjoint ``(name, start, end)`` segments covering each
    ``engine.step`` span, each named by the innermost span open there.
    Spans are properly nested (one host thread); spans outside any
    ``engine.step`` are left out."""
    out: List[Tuple[str, float, float]] = []
    stack: List[Span] = []
    t = 0.0

    def emit(name, s, e):
        if e > s:
            out.append((name, s, e))

    for sp in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= sp.start:
            top = stack.pop()
            emit(top.name, t, top.end)
            t = top.end
        if not stack and sp.name != STEP:
            continue
        if stack:
            emit(stack[-1].name, t, sp.start)
        stack.append(sp)
        t = sp.start
    while stack:
        top = stack.pop()
        emit(top.name, t, top.end)
        t = top.end
    return out


def idle_split(ops: Sequence[Sequence[Interval]], spans: Sequence[Span],
               window: Interval, delta: float) -> Dict[str, float]:
    """Seconds (mean over devices) in which a device ran nothing, with its
    op intervals shifted by ``delta``, under each innermost engine span."""
    lo, hi = window
    segs = innermost(spans)
    out: Dict[str, float] = defaultdict(float)
    for evs in ops:
        busy = union([(s + delta, e + delta) for s, e in evs], lo, hi)
        for k, v in charge_gaps(complement(busy, lo, hi), segs).items():
            if k != "other":             # idle outside every engine.step
                out[k] += v / len(ops)
    return dict(out)


def reduce_planes(planes) -> EngineTrace:
    """Reduce profiler planes (``ProfileData.planes``) to an
    :class:`EngineTrace`."""
    window = None
    spans: List[Span] = []
    ops: List[List[Interval]] = []
    modules: List[List[Interval]] = []
    runtime: Dict[str, List[float]] = {ENQUEUE: [], DONE: []}
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith(PREFIX):
                        spans.append(Span(ev.name, ev.start_ns, ev.end_ns,
                                          dict(ev.stats)))
                    elif ev.name in runtime:
                        runtime[ev.name].append(ev.start_ns)
        elif _DEVICE_PLANE.match(plane.name):
            ops.append([(ev.start_ns, ev.end_ns) for line in plane.lines
                        if line.name in OP_LINES for ev in line.events])
            modules.append([(ev.start_ns, ev.end_ns) for line in plane.lines
                            if line.name in MODULE_LINES
                            for ev in line.events])
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span on a host plane")
    spans = [s for s in spans if window[0] <= s.start and s.end <= window[1]]
    steps = decode_steps(spans)
    pairs = [p for mods in modules for p in pair(steps, mods)]
    lo, hi = span_bracket(pairs)
    lo_rt, hi_rt = runtime_bracket(pairs, runtime[ENQUEUE], runtime[DONE])
    return EngineTrace(window, spans, len(pairs), lo, hi, lo_rt, hi_rt, ops)


def reduce_file(path: str) -> EngineTrace:
    import jax
    return reduce_planes(jax.profiler.ProfileData.from_file(str(path)).planes)
