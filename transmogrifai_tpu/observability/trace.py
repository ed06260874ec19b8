"""Hierarchical tracing: spans with monotonic timestamps and a bounded buffer.

The reference observes its runs through a Spark listener (reference:
utils/.../spark/OpSparkListener.scala:55-110 — per-stage task metrics pushed
by the cluster scheduler); a JAX process has no cluster scheduler to listen
to, so the spans are emitted by the framework itself at every interesting
boundary: ``workflow.train`` → ``stage.fit``/``stage.transform`` (per layer),
inside the model selector ``selector.prepare`` / ``sweep.family`` (per
candidate family, the dispatch) / ``sweep.collect`` (the wait) /
``selector.refit`` / ``selector.evaluate``, inside the scoring plan
``plan.stage_inputs`` / ``plan.segment`` / ``plan.collect``,
``score.micro_batch`` (per serving batch); docs/observability.md has the
whole table. A span never adds a sync: one that launches asynchronous device
work covers the launch, and the wait belongs to the span around the statement
where the program blocks anyway. Fault recoveries (robustness/) land as span
*events* on whatever span is open, so a trace shows retries and quarantines
in line with the work they interrupted.

Cost model: a disabled tracer is one env/flag check per ``span()`` call —
no Span objects, no buffer writes — so the always-compiled call sites add
nothing measurable to the hot paths (the same discipline as
``robustness/faults.py`` sites). Enabled, finished spans go into a bounded
ring (``TG_TRACE_MAX_SPANS``, default 65536) so a long-lived scorer cannot
grow without bound; drops are counted, never silent.

Switches: ``TG_TRACE=1`` enables tracing process-wide;
:func:`enable_tracing` overrides programmatically (``None`` returns control
to the env). State is process-global by design — like the reference's one
listener per SparkContext — and :func:`reset` gives tests a clean slate.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

#: env switch: truthy value enables tracing process-wide
TRACE_ENV = "TG_TRACE"

_FALSY = ("", "0", "false", "False", "no")

_enabled_override: Optional[bool] = None


def tracing_enabled() -> bool:
    """True when spans are being recorded (TG_TRACE, unless overridden)."""
    if _enabled_override is not None:
        return _enabled_override
    return os.environ.get(TRACE_ENV, "") not in _FALSY


def enable_tracing(on: Optional[bool]) -> None:
    """Force tracing on/off from code (the CLI and tests); ``None`` hands
    control back to the ``TG_TRACE`` environment switch."""
    global _enabled_override
    _enabled_override = None if on is None else bool(on)


@contextmanager
def forced_tracing():
    """Tracing on inside the block whatever the switches say, and as it
    was after: a profiled run (``OpWorkflow.with_profiler``) records its
    spans without the caller having to set ``TG_TRACE``."""
    global _enabled_override
    prev = _enabled_override
    _enabled_override = True
    try:
        yield
    finally:
        _enabled_override = prev


class Span:
    """One timed operation. ``ts_ns``/``dur_ns`` are monotonic-clock
    nanoseconds relative to the owning tracer's epoch; ``dur_ns`` is None
    while open (and stays None for instant events). ``events`` are
    point-in-time annotations: ``(name, ts_ns, attrs)``."""

    __slots__ = ("name", "cat", "span_id", "parent_id", "root_id", "ts_ns",
                 "dur_ns", "attrs", "events", "tid", "leaf")

    def __init__(self, name: str, cat: str, span_id: int,
                 parent_id: Optional[int], ts_ns: int, tid: int,
                 attrs: Optional[Dict[str, Any]] = None,
                 root_id: Optional[int] = None):
        self.name = name
        self.cat = cat
        self.span_id = span_id
        self.parent_id = parent_id
        #: id of the outermost span this one nests under (its own id for a
        #: root): every span of one train() or score() shares it
        self.root_id = span_id if root_id is None else root_id
        self.ts_ns = ts_ns
        self.dur_ns: Optional[int] = None
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self.events: List[Tuple[str, int, Dict[str, Any]]] = []
        self.tid = tid
        #: a leaf span records no children: what its thread opens while it
        #: is open gets NULL_SPAN (``span(..., leaf=True)``)
        self.leaf = False

    def set_attr(self, **kv: Any) -> "Span":
        self.attrs.update(kv)
        return self

    def add_event(self, name: str, **attrs: Any) -> "Span":
        self.events.append((name, _now_rel_ns(), attrs))
        return self

    @property
    def seconds(self) -> float:
        return (self.dur_ns or 0) / 1e9

    def to_json(self) -> Dict[str, Any]:
        return {
            "name": self.name, "cat": self.cat, "id": self.span_id,
            "parent": self.parent_id, "root": self.root_id,
            "tsNs": self.ts_ns,
            "durNs": self.dur_ns, "tid": self.tid, "attrs": dict(self.attrs),
            "events": [{"name": n, "tsNs": t, "attrs": dict(a)}
                       for n, t, a in self.events],
        }


class _NullSpan:
    """Yielded by :func:`span` when tracing is off: every method is a no-op
    so call sites never need an enabled check around attribute writes."""

    __slots__ = ()

    def set_attr(self, **kv: Any) -> "_NullSpan":
        return self

    def add_event(self, name: str, **attrs: Any) -> "_NullSpan":
        return self

    seconds = 0.0


NULL_SPAN = _NullSpan()


class Tracer:
    """Span collector: per-thread open-span stacks (spans nest within a
    thread), one shared bounded ring of finished spans."""

    def __init__(self, max_spans: Optional[int] = None):
        if max_spans is None:
            max_spans = int(os.environ.get("TG_TRACE_MAX_SPANS", "65536"))
        self.max_spans = max(1, int(max_spans))
        self.spans: deque = deque(maxlen=self.max_spans)
        self.dropped = 0
        #: wall-clock anchor for the monotonic epoch (export metadata)
        self.epoch_unix = time.time()
        self.epoch_ns = time.perf_counter_ns()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()

    # -- span lifecycle ------------------------------------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def start(self, name: str, cat: str = "",
              attrs: Optional[Dict[str, Any]] = None) -> Span:
        st = self._stack()
        parent = st[-1] if st else None
        s = Span(name, cat, next(self._ids),
                 parent.span_id if parent else None,
                 time.perf_counter_ns() - self.epoch_ns,
                 threading.get_ident(), attrs,
                 parent.root_id if parent else None)
        st.append(s)
        return s

    def end(self, s: Span) -> None:
        s.dur_ns = (time.perf_counter_ns() - self.epoch_ns) - s.ts_ns
        st = self._stack()
        if s in st:          # tolerate out-of-order ends (generator exits)
            st.remove(s)
        self._append(s)

    def instant(self, name: str, attrs: Optional[Dict[str, Any]] = None
                ) -> Span:
        """A free-standing point event (no open span to attach to)."""
        s = Span(name, "event", next(self._ids), None,
                 time.perf_counter_ns() - self.epoch_ns,
                 threading.get_ident(), attrs)
        self._append(s)
        return s

    def _append(self, s: Span) -> None:
        with self._lock:
            if len(self.spans) == self.spans.maxlen:
                self.dropped += 1
            self.spans.append(s)
        # span close summary into the always-on flight recorder: when a
        # run is traced, the black box sees the traced world too — a
        # post-mortem bundle then carries the span names/durations of the
        # seconds before the trigger (observability/blackbox.py)
        from . import blackbox as _blackbox
        if _blackbox.blackbox_enabled():
            _blackbox.record("span", name=s.name, cat=s.cat,
                             durNs=s.dur_ns)

    # -- queries -------------------------------------------------------------
    def current(self) -> Optional[Span]:
        st = self._stack()
        return st[-1] if st else None

    def finished(self) -> List[Span]:
        with self._lock:
            return list(self.spans)

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()
            self.dropped = 0


_TRACER = Tracer()


def tracer() -> Tracer:
    return _TRACER


def set_tracer(t: Tracer) -> Tracer:
    global _TRACER
    _TRACER = t
    return t


def reset() -> None:
    """Fresh tracer + env-driven enablement (test isolation; see
    tests/conftest.py)."""
    global _TRACER, _enabled_override
    _TRACER = Tracer()
    _enabled_override = None


_ANNOTATION = None


def _annotation(name: str):
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION(name)


def _now_rel_ns() -> int:
    return time.perf_counter_ns() - _TRACER.epoch_ns


def fullest_device_stats() -> Optional[Dict[str, int]]:
    """``memory_stats()`` of the local device with the most
    ``bytes_in_use``: every chip of a mesh is asked, not chip 0. None where
    the backend keeps no allocator statistics (the CPU). It does not wait
    for the device: the numbers are what is allocated at dispatch."""
    import jax
    stats = [d.memory_stats() for d in jax.local_devices()]
    stats = [s for s in stats if s]
    if not stats:
        return None
    return max(stats, key=lambda s: s.get("bytes_in_use", 0))


def live_device_bytes() -> int:
    """Bytes in use on the fullest local device; where the backend keeps
    no statistics, the bytes of the live arrays' shards on it."""
    stats = fullest_device_stats()
    if stats is not None:
        return int(stats.get("bytes_in_use", 0))
    import jax
    per_device: Dict[Any, int] = {}
    seen = set()      # a shard's view is a live array of its own: once each
    for a in jax.live_arrays():
        if a.is_deleted():
            continue
        for sh in a.addressable_shards:
            buf = (sh.device, sh.data.unsafe_buffer_pointer())
            if buf not in seen:
                seen.add(buf)
                per_device[sh.device] = (per_device.get(sh.device, 0)
                                         + int(sh.data.nbytes))
    return max(per_device.values(), default=0)


@contextmanager
def span(name: str, cat: str = "", *, hbm: bool = False, leaf: bool = False,
         **attrs: Any):
    """``with span("stage.fit", uid=...) as s:`` — records one Span when
    tracing is enabled; otherwise yields the inert :data:`NULL_SPAN`.

    ``hbm=True`` (a layer boundary) adds ``hbmLiveStart`` / ``hbmLiveEnd``,
    :func:`live_device_bytes` read before the span opens and after it
    closes, outside its own seconds. ``leaf=True`` records the span and
    nothing its thread opens inside it (the plan's zero-row probe runs
    the stages' own code: its cost is the one span's)."""
    if not tracing_enabled():
        yield NULL_SPAN
        return
    t = _TRACER
    cur = t.current()
    if cur is not None and cur.leaf:
        yield NULL_SPAN
        return
    if hbm:
        attrs["hbmLiveStart"] = live_device_bytes()
    s = t.start(name, cat, attrs)
    s.leaf = leaf
    try:
        # the same name on the profiler's own clock: a jax.profiler session
        # opened by anyone (a benchmark, an operator) shows the program's
        # spans beside the device lines; costs nothing measurable with no
        # session open
        with _annotation(name):
            yield s
    finally:
        t.end(s)
        if hbm:
            s.attrs["hbmLiveEnd"] = live_device_bytes()


def add_event(name: str, **attrs: Any) -> None:
    """Annotate the current thread's open span (or record a free-standing
    instant event when none is open). No-op when tracing is disabled —
    the robustness choke points call this unconditionally."""
    if not tracing_enabled():
        return
    s = _TRACER.current()
    if s is not None:
        s.add_event(name, **attrs)
    else:
        _TRACER.instant(name, attrs)
