"""Per-layer metric readers, driven by ``layer_metrics/<name>.json``.

Each file names a ``read.kind`` and its parameters; the kinds below are
small general reducers over what a traced run collected (``Readings``):
the program's spans, the profiler's device events, ``jax.monitoring``
events and ``memory_stats``. A reader that finds nothing to read returns
None and the harness leaves the metric out of the line. A kind that is not
listed here is loaded from ``reader_kinds/<kind>.py`` beside the cell's
``layer_metrics/`` directory, a file with a ``read(spec, readings)``
function, so a later PR adds a kind as a new file. A reader is written
once: its manifest entry lists every cell that reads it, under one name.
"""
from __future__ import annotations

import importlib.util
import os
import re
import statistics
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from . import tracered

OpNs = Tuple[int, int]     # (start, end) in perf_counter_ns


@dataclass
class Readings:
    ops: List[OpNs] = field(default_factory=list)
    traced: List[OpNs] = field(default_factory=list)
    spans: List[Any] = field(default_factory=list)
    epoch_ns: int = 0
    trace: Optional[tracered.Trace] = None
    #: (phase, event name, value) of every jax.monitoring event seen
    monitoring: List[Tuple[str, str, float]] = field(default_factory=list)
    memory: Dict[str, float] = field(default_factory=dict)

    # -- clocks ------------------------------------------------------------
    def span_bounds(self, s) -> Tuple[int, int]:
        a = self.epoch_ns + int(s.ts_ns)
        return a, a + int(s.dur_ns or 0)

    def traced_windows(self) -> List[Tuple[float, float]]:
        """The traced operations on the trace's clock."""
        if self.trace is None or self.trace.anchor is None:
            return []
        t0, p0 = self.trace.anchor
        return [(a - p0 + t0, b - p0 + t0) for a, b in self.traced]


def _matches(span, spec: Dict[str, Any]) -> bool:
    if not re.fullmatch(spec["name"], span.name):
        return False
    for key, rx in spec.get("attrs", {}).items():
        if not re.fullmatch(rx, str(span.attrs.get(key, ""))):
            return False
    return span.dur_ns is not None


def _in_op(r: Readings, span, op: OpNs) -> bool:
    a, b = r.span_bounds(span)
    return op[0] <= a and b <= op[1]


def _median(xs: Sequence[float]) -> Optional[float]:
    return float(statistics.median(xs)) if xs else None


def span_sum(spec, r: Readings) -> Optional[float]:
    """Median over the window's operations of the summed seconds of the
    matching spans inside each."""
    hits = [s for s in r.spans if _matches(s, spec)]
    if not hits or not r.ops:
        return None
    return _median([sum(s.dur_ns for s in hits if _in_op(r, s, op)) / 1e9
                    for op in r.ops])


def _busy_on_trace(r: Readings) -> Dict[str, List[Tuple[float, float]]]:
    return {p: tracered.busy_intervals(r.trace, p)
            for p in r.trace.device_planes()}


def span_minus_device(spec, r: Readings) -> Optional[float]:
    """Per traced operation: seconds of the matching spans (their union)
    in which no operation ran on the device."""
    wins = r.traced_windows()
    if not wins:
        return None
    t0, p0 = r.trace.anchor
    busy = tracered.merge(iv for ivs in _busy_on_trace(r).values()
                          for iv in ivs)
    vals = []
    for op, win in zip(r.traced, wins):
        cover = tracered.merge(
            (a - p0 + t0, b - p0 + t0)
            for a, b in (r.span_bounds(s) for s in r.spans
                         if _matches(s, spec) and _in_op(r, s, op)))
        if not cover:
            continue
        idle = sum(tracered.total(tracered.gaps(busy, a, b))
                   for a, b in cover)
        vals.append(idle / 1e9)
    return _median(vals)


def device_op_sum(spec, r: Readings) -> Optional[float]:
    """Seconds of the device events whose name matches ``pattern`` (on the
    lines matching ``line``), per traced operation, averaged over the
    device planes that ran any."""
    wins = r.traced_windows()
    if not wins:
        return None
    planes = [p for p, b in _busy_on_trace(r).items() if b]
    sums = tracered.op_sums(r.trace.events, spec["pattern"],
                            spec.get("line"), wins)
    if not sums or not planes:
        return None
    return sum(sums.values()) / len(planes) / len(wins)


def after_last_device_op(spec, r: Readings) -> Optional[float]:
    """Per traced operation: seconds from the end of the last device event
    matching ``pattern`` (on the lines matching ``line``) to the end of
    the operation."""
    wins = r.traced_windows()
    if not wins:
        return None
    rx, lrx = re.compile(spec["pattern"]), re.compile(spec["line"])
    ends = sorted(e.start_ns + e.dur_ns for e in r.trace.events
                  if lrx.fullmatch(e.line) and rx.search(e.name))
    vals = []
    for lo, hi in wins:
        inside = [t for t in ends if lo <= t <= hi]
        if inside:
            vals.append((hi - inside[-1]) / 1e9)
    return _median(vals)


def device_busy(spec, r: Readings) -> Optional[float]:
    wins = r.traced_windows()
    if not wins:
        return None
    busy = tracered.busy_seconds(r.trace, wins)
    return busy / len(wins) if busy > 0 else None


def device_idle_pct(spec, r: Readings) -> Optional[float]:
    wins = r.traced_windows()
    if not wins:
        return None
    busy = tracered.busy_seconds(r.trace, wins)
    window = sum(b - a for a, b in wins) / 1e9
    return 100.0 * (1.0 - busy / window) if busy > 0 and window > 0 else None


def memory_stat(spec, r: Readings) -> Optional[float]:
    v = r.memory.get(spec["key"])
    return None if v is None else float(v) * float(spec.get("scale", 1.0))


def _monitored(spec, r: Readings) -> List[float]:
    names = set(spec["events"])
    return [v for phase, ev, v in r.monitoring
            if ev in names and phase == spec.get("phase", "setup")]


def monitoring_sum(spec, r: Readings) -> Optional[float]:
    return float(sum(_monitored(spec, r))) if r.monitoring else None


def monitoring_count(spec, r: Readings) -> Optional[float]:
    return float(len(_monitored(spec, r))) if r.monitoring else None


KINDS: Dict[str, Callable[[Dict[str, Any], Readings], Optional[float]]] = {
    f.__name__: f for f in (
        span_sum, span_minus_device, device_op_sum,
        after_last_device_op, device_busy,
        device_idle_pct, memory_stat, monitoring_sum, monitoring_count)}


_HERE = os.path.dirname(os.path.abspath(__file__))
_ADDED: Dict[str, Callable] = {}


def reader_for(kind: str, bench_dir: Optional[str] = None) -> Callable:
    if kind in KINDS:
        return KINDS[kind]
    if not re.fullmatch(r"[A-Za-z0-9_]+", kind):
        raise ValueError(f"bad reader kind {kind!r}")
    path = os.path.join(bench_dir or _HERE, "reader_kinds", kind + ".py")
    if path not in _ADDED:
        spec = importlib.util.spec_from_file_location(
            f"benchmark_reader_kind_{kind}", path)
        if spec is None or not os.path.exists(path):
            raise ValueError(f"no reader kind {kind!r}: {path} is missing")
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _ADDED[path] = module.read
    return _ADDED[path]


def read_metric(spec: Dict[str, Any], r: Readings) -> Optional[float]:
    return reader_for(spec["read"]["kind"],
                      spec.get("bench_dir"))(spec["read"], r)
