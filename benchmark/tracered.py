"""From a profiler trace and the program's spans to numbers.

The reduction is the benchmark's own, so every PR computes the same number
the same way: device busy time as the union of the intervals in which an
operation ran, the idle share, operation sums by name, and the idle gaps
named by the innermost program span that covers them.

Clocks. Device and host events of one ``.xplane.pb`` share the profiler's
clock. The program's spans are on ``time.perf_counter_ns``. The harness
emits one ``jax.profiler.TraceAnnotation`` (``ANCHOR``) carrying the
``perf_counter_ns`` reading taken as it opens; the difference between that
reading and the annotation's start in the trace maps spans onto the trace.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ANCHOR = "bench.anchor"
#: the lines of a device plane, in order of preference, whose events are
#: single operations (busy time is their union)
OP_LINES = ("XLA Ops",)
#: the line whose events are whole compiled programs
MODULE_LINES = ("XLA Modules",)

Interval = Tuple[float, float]


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


@dataclass
class HostSpan:
    """A program span moved onto the trace's clock."""
    name: str
    start_ns: float
    end_ns: float
    attrs: Dict[str, str] = field(default_factory=dict)


@dataclass
class Trace:
    events: List[Event]
    #: (annotation start on the trace clock, perf_counter_ns it carried)
    anchor: Optional[Tuple[float, float]] = None
    #: busy intervals by plane, worked out once (a train has ~500 000 events)
    _busy: Dict[str, List[Interval]] = field(default_factory=dict, repr=False)

    def device_planes(self) -> List[str]:
        return sorted({e.plane for e in self.events})

    def lines(self, plane: str) -> List[str]:
        return sorted({e.line for e in self.events if e.plane == plane})


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper()


def read_xplane(logdir: str) -> Trace:
    """Every device-plane event and the anchor of the newest trace under
    ``logdir`` (as ``jax.profiler.start_trace`` lays it out)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(files[-1])
    events: List[Event] = []
    anchor = None
    for plane in data.planes:
        dev = is_device_plane(plane.name)
        host = plane.name.startswith("/host:")
        if not (dev or host):
            continue
        for line in plane.lines:
            for ev in line.events:
                if dev:
                    events.append(Event(plane.name, line.name, ev.name,
                                        float(ev.start_ns),
                                        float(ev.duration_ns)))
                elif ev.name == ANCHOR and anchor is None:
                    stats = dict(ev.stats)
                    if "t_ns" in stats:
                        anchor = (float(ev.start_ns), float(stats["t_ns"]))
    return Trace(events, anchor)


def pick_line(trace: Trace, plane: str, wanted: Sequence[str]) -> Optional[str]:
    have = trace.lines(plane)
    for w in wanted:
        if w in have:
            return w
    return None


def op_events(trace: Trace, plane: str) -> List[Event]:
    """The single-operation events of a device plane: its ``XLA Ops`` line,
    or, where the trace has none, every event of the plane."""
    line = pick_line(trace, plane, OP_LINES)
    return [e for e in trace.events if e.plane == plane
            and (line is None or e.line == line)]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1][1] = hi
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


def busy_intervals(trace: Trace, plane: str) -> List[Interval]:
    if plane not in trace._busy:
        trace._busy[plane] = merge((e.start_ns, e.start_ns + e.dur_ns)
                                   for e in op_events(trace, plane))
    return trace._busy[plane]


def busy_seconds(trace: Trace, windows: Sequence[Interval]) -> float:
    """Seconds in which an operation ran on the device inside ``windows``
    (trace clock, ns), averaged over the device planes that ran anything."""
    per_plane = []
    for plane in trace.device_planes():
        busy = busy_intervals(trace, plane)
        if not busy:
            continue
        per_plane.append(sum(total(clip(busy, lo, hi))
                             for lo, hi in windows) / 1e9)
    return sum(per_plane) / len(per_plane) if per_plane else 0.0


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` given disjoint sorted ``busy``."""
    out, at = [], lo
    for a, b in clip(busy, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def op_sums(events: Iterable[Event], pattern: str = ".*",
            line: Optional[str] = None,
            windows: Optional[Sequence[Interval]] = None
            ) -> Dict[str, float]:
    """Summed seconds by event name, for names matching ``pattern`` on
    ``line`` (a regular expression too), events starting inside
    ``windows`` where given."""
    rx, lrx = re.compile(pattern), re.compile(line) if line else None
    out: Dict[str, float] = {}
    for e in events:
        if lrx is not None and not lrx.fullmatch(e.line):
            continue
        if not rx.search(e.name):
            continue
        if windows is not None and not any(
                lo <= e.start_ns < hi for lo, hi in windows):
            continue
        out[e.name] = out.get(e.name, 0.0) + e.dur_ns / 1e9
    return out


def short_name(name: str) -> str:
    """A program's name without its fingerprint: ``jit_prog(123)`` and
    ``jit_prog.4`` both read ``jit_prog``."""
    return re.sub(r"(\(\d+\)|\.\d+)+$", "", name)


def top_ops(trace: Trace, windows: Sequence[Interval], n: int = 10
            ) -> List[List]:
    """The ``n`` device operations that took most time: whole programs where
    the trace has a modules line, single operations otherwise; seconds
    averaged over the device planes."""
    planes = [p for p in trace.device_planes() if busy_intervals(trace, p)]
    sums: Dict[str, float] = {}
    for plane in planes:
        line = pick_line(trace, plane, MODULE_LINES)
        evs = ([e for e in trace.events
                if e.plane == plane and e.line == line]
               if line else op_events(trace, plane))
        for e in evs:
            if any(lo <= e.start_ns < hi for lo, hi in windows):
                k = short_name(e.name)
                sums[k] = sums.get(k, 0.0) + e.dur_ns / 1e9
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(len(planes), 1)] for k, v in ranked]


def to_trace_clock(spans: Iterable, anchor: Tuple[float, float],
                   epoch_ns: float) -> List[HostSpan]:
    """Program spans (``ts_ns``/``dur_ns`` relative to the tracer's epoch,
    itself a ``perf_counter_ns`` reading) on the trace's clock."""
    trace_start, perf_ns = anchor
    off = trace_start - perf_ns
    out = []
    for s in spans:
        if s.dur_ns is None:
            continue
        a = epoch_ns + s.ts_ns + off
        label = s.name
        stage = s.attrs.get("stage") if hasattr(s, "attrs") else None
        if stage:
            label = f"{s.name}_{stage}_"
        out.append(HostSpan(label, a, a + s.dur_ns,
                            {k: str(v) for k, v in s.attrs.items()}))
    return out


def innermost_timeline(spans: Sequence[HostSpan]) -> List[Tuple[float, float, str]]:
    """Disjoint sorted segments, each named by the innermost span covering
    it (the latest-started span still open)."""
    marks = sorted({t for s in spans for t in (s.start_ns, s.end_ns)})
    by_start = sorted(spans, key=lambda s: s.start_ns)
    out, open_, i = [], [], 0
    for lo, hi in zip(marks, marks[1:]):
        while i < len(by_start) and by_start[i].start_ns <= lo:
            open_.append(by_start[i])
            i += 1
        open_ = [s for s in open_ if s.end_ns > lo]
        if open_:
            inner = max(open_, key=lambda s: (s.start_ns, -s.end_ns))
            out.append((lo, hi, inner.name))
    return out


def name_gaps(idle: Sequence[Interval], spans: Sequence[HostSpan],
              n: int = 10, unnamed: str = "_no_host_span_") -> List[List]:
    """Idle seconds by the innermost program span covering them: the ``n``
    largest totals."""
    timeline = innermost_timeline(spans)
    sums: Dict[str, float] = {}
    j = 0
    for a, b in idle:
        covered = 0.0
        while j < len(timeline) and timeline[j][1] <= a:
            j += 1
        k = j
        while k < len(timeline) and timeline[k][0] < b:
            lo, hi, name = timeline[k]
            ov = min(b, hi) - max(a, lo)
            if ov > 0:
                sums[name] = sums.get(name, 0.0) + ov / 1e9
                covered += ov
            k += 1
        rest = (b - a) - covered
        if rest > 0:
            sums[unnamed] = sums.get(unnamed, 0.0) + rest / 1e9
    return [[k, v] for k, v in
            sorted(sums.items(), key=lambda kv: -kv[1])[:n]]
