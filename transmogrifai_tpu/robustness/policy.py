"""Retry policies and fault accounting.

The reference rides Spark's ``spark.task.maxFailures`` + lineage
recomputation; here retries are explicit: :class:`RetryPolicy` re-runs a
named operation on *transient* failures (device-transfer hiccups, link
resets, injected :class:`~.faults.TransientFaultError`) with exponential
backoff and deterministic jitter, and every recovery — retry, quarantine,
skipped checkpoint — is recorded as a :class:`FaultReport` in the
train-scoped :class:`FaultLog` that ``OpWorkflowModel.summary()["faults"]``
surfaces.
"""
from __future__ import annotations

import contextlib
import contextvars
import hashlib
import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional

from ..observability import blackbox as _blackbox
from ..observability import metrics as _obs_metrics
from ..observability import trace as _obs_trace
from .faults import InjectedFaultError, TransientFaultError
from .resources import is_resource_exhausted

#: substrings (lowercased) marking an error transient: the gRPC-style
#: status codes surfaced by jax/PJRT transfer failures plus socket-level
#: resets
TRANSIENT_PATTERNS = (
    "unavailable", "deadline exceeded", "deadline_exceeded", "data_loss",
    "connection reset", "connection refused", "broken pipe", "socket",
    "temporarily", "transfer failed", "resource temporarily",
)


def is_transient_error(exc: BaseException) -> bool:
    """Default transient-vs-fatal classification: explicit transient marker
    types, OS-level I/O interruptions, and runtime errors whose message
    carries a retryable transport status. Everything else — ValueError,
    shape/trace errors, injected fatal faults — is fatal: retrying a
    deterministic program on the same inputs cannot fix those.

    Resource exhaustion is checked FIRST and is never transient: an XLA
    ``RESOURCE_EXHAUSTED`` / host ``MemoryError`` / ``ENOMEM`` is
    deterministic at a given allocation size — re-running the identical
    allocation re-exhausts identically, so blind retry only triples the
    failure latency. (The "resource temporarily"/OSError heuristics below
    used to classify genuine exhaustion as retryable.) The downshift
    paths (robustness/resources.py) split the work instead."""
    if is_resource_exhausted(exc):
        return False
    if isinstance(exc, InjectedFaultError):
        return False
    if isinstance(exc, (TransientFaultError, ConnectionError, TimeoutError,
                        BrokenPipeError, InterruptedError)):
        return True
    if isinstance(exc, OSError):
        return True
    msg = str(exc).lower()
    # XlaRuntimeError (jaxlib) carries the PJRT status in its message
    if type(exc).__name__ == "XlaRuntimeError" or isinstance(exc, RuntimeError):
        return any(p in msg for p in TRANSIENT_PATTERNS)
    return False


@dataclass
class FaultReport:
    """One recovery event. ``kind``: ``retry`` (operation succeeded after
    ``attempts - 1`` retries), ``quarantine`` (candidate/family excluded
    from selection), ``checkpoint_skipped`` (corrupt/incomplete checkpoint
    detected and ignored on resume), ``restored`` (a fitted stage or sweep
    candidate rehydrated from a verified checkpoint instead of refitting),
    ``plan_fallback`` (a fused transform run raised and degraded to eager
    per-stage dispatch, plan.py), or ``fatal`` (retries exhausted /
    unretryable)."""
    site: str
    kind: str
    detail: Dict[str, Any] = field(default_factory=dict)
    attempts: int = 1

    @property
    def retries(self) -> int:
        return max(0, self.attempts - 1)

    def to_json(self) -> Dict[str, Any]:
        return {"site": self.site, "kind": self.kind,
                "attempts": self.attempts, "retries": self.retries,
                "detail": dict(self.detail)}


_CURRENT_LOG: "contextvars.ContextVar[Optional[FaultLog]]" = \
    contextvars.ContextVar("tg_fault_log", default=None)


#: ring bound for FaultLog.reports; a long-lived serving process under
#: sustained faults (an open breaker degrades every batch) must not grow
#: fault memory without bound — oldest reports drop, counted
FAULTS_MAX_ENV = "TG_FAULTS_MAX"
DEFAULT_FAULTS_MAX = 1024


def _faults_max() -> int:
    try:
        return max(1, int(os.environ.get(FAULTS_MAX_ENV, "")
                          or DEFAULT_FAULTS_MAX))
    except ValueError:
        return DEFAULT_FAULTS_MAX


class FaultLog:
    """Accumulator of :class:`FaultReport` records — train-scoped for
    ``OpWorkflow.train`` (activated around the whole fit), serve-scoped for
    ``serving.ServingRuntime`` (one per runtime).

    Components deep in the stack (validators, transfer helpers, checkpoint
    loader) record through the ambient :meth:`record` without threading the
    log through every signature; recording without an active log is a
    no-op, so library code never needs to guard. ``reports`` is a ring
    bounded by ``TG_FAULTS_MAX`` (default 1024): the newest reports win,
    drops are counted in :attr:`dropped` and the
    ``tg_faults_dropped_total`` counter — sustained serving faults must
    not leak memory."""

    def __init__(self, max_reports: Optional[int] = None):
        self.max_reports = (max(1, int(max_reports))
                            if max_reports is not None else _faults_max())
        self.reports: Deque[FaultReport] = deque()
        self.dropped = 0

    @contextlib.contextmanager
    def activate(self):
        token = _CURRENT_LOG.set(self)
        try:
            yield self
        finally:
            _CURRENT_LOG.reset(token)

    def add(self, report: FaultReport) -> None:
        """Append with the ring bound applied (the instance-level entry
        point; the serving runtime records here directly — its batcher
        thread has no ambient log)."""
        while len(self.reports) >= self.max_reports:
            self.reports.popleft()
            self.dropped += 1
            _obs_metrics.inc_counter(
                "tg_faults_dropped_total",
                help="fault reports dropped by the TG_FAULTS_MAX ring "
                "(docs/robustness.md)")
        self.reports.append(report)
        _emit_fault_observability(report)

    @staticmethod
    def current() -> Optional["FaultLog"]:
        """The ambient (activated) log of THIS thread, or None. Worker
        threads never see the consumer's ambient log (contextvars are
        per-thread) — components that record from their own threads
        capture this on the owning thread and ``add()`` directly (the
        serving batcher, the stream input engine's chunk cache)."""
        return _CURRENT_LOG.get()

    @staticmethod
    def record(report: FaultReport) -> None:
        log = _CURRENT_LOG.get()
        if log is not None:
            log.add(report)
        else:
            _emit_fault_observability(report)

    def of_kind(self, kind: str) -> List[FaultReport]:
        return [r for r in self.reports if r.kind == kind]

    def to_json(self) -> Dict[str, Any]:
        """The ``summary()["faults"]`` section (schema: docs/robustness.md)."""
        return {
            "quarantined": [r.to_json() for r in self.of_kind("quarantine")],
            "retries": [r.to_json() for r in self.of_kind("retry")],
            "checkpointsSkipped": [r.to_json()
                                   for r in self.of_kind("checkpoint_skipped")],
            "restored": [r.to_json() for r in self.of_kind("restored")],
            # fused transform runs that raised and degraded to eager
            # per-stage dispatch (docs/plan.md "Fallback semantics")
            "planFallbacks": [r.to_json()
                              for r in self.of_kind("plan_fallback")],
            # serve batches scored through the eager per-row fallback
            # (breaker open / dispatch failure; docs/serving.md)
            "breakerDegraded": [r.to_json()
                                for r in self.of_kind("breaker_degraded")],
            # drift-monitor events: contained fold/verdict failures plus
            # refit outcomes (drift_refit / drift_refit_failed;
            # docs/serving.md "Drift monitoring & self-healing")
            "drift": [r.to_json() for r in self.reports
                      if r.kind.startswith("drift_")],
            # adaptive degradation after resource exhaustion: row-batch
            # bisects, flush splits, chunk-budget halvings, grid splits
            # (docs/robustness.md "Resource exhaustion & watchdog")
            "oomDownshifts": [r.to_json()
                              for r in self.of_kind("oom_downshift")],
            # threads caught wedged by the watchdog or left alive past a
            # join(timeout=...) at close — never discarded silently
            "threadStalls": [r.to_json()
                             for r in self.of_kind("thread_stalled")],
            # stale run sentinels found on resume: a PREVIOUS process
            # owning this checkpoint dir exited uncleanly (SIGKILL, node
            # loss, the OOM killer — oomKillSuspected when its last phase
            # was device work; docs/robustness.md "Cross-process kill
            # detection")
            "uncleanExits": [r.to_json()
                             for r in self.of_kind("unclean_exit")],
            "fatal": [r.to_json() for r in self.of_kind("fatal")],
            # ring accounting: reports evicted under TG_FAULTS_MAX
            "droppedReports": self.dropped,
        }


def _emit_fault_observability(report: FaultReport) -> None:
    # observability choke point: every recovery anywhere in the stack
    # becomes a span event on whatever span is open (a trace shows the
    # quarantine in line with the sweep it interrupted) and a counter
    # keyed by kind (bounded cardinality; the site goes on the event
    # only). Both are no-ops when observability is off. The ALWAYS-ON
    # flight recorder (observability/blackbox.py) gets the same record —
    # one hook here puts every FaultLog event (retries, quarantines,
    # breaker degradations, downshifts, stalls, unclean exits, drift
    # events) into the black box, stamped with the ambient correlation
    # id when a run owns one.
    _blackbox.record("fault." + report.kind, site=report.site,
                     attempts=report.attempts)
    _obs_trace.add_event("fault." + report.kind, site=report.site,
                         attempts=report.attempts)
    _obs_metrics.inc_counter(
        "tg_faults_total", help="fault recoveries by kind "
        "(docs/robustness.md)", kind=report.kind)


@dataclass
class RetryPolicy:
    """Exponential backoff + deterministic jitter over transient failures.

    ``attempt_deadline``: an attempt whose wall-clock exceeds it is not
    retried even on a transient error — a stuck link that ate the whole
    budget should fail loud, not double the hang. ``classify`` overrides
    the default :func:`is_transient_error`."""
    max_retries: int = 2
    base_delay: float = 0.05
    max_delay: float = 2.0
    jitter: float = 0.25
    attempt_deadline: Optional[float] = None
    classify: Optional[Callable[[BaseException], bool]] = None

    def is_transient(self, exc: BaseException) -> bool:
        return (self.classify or is_transient_error)(exc)

    def delay_for(self, attempt: int, site: str) -> float:
        """Deterministic backoff: exponential in the attempt number, jittered
        by a hash of (site, attempt) — reproducible across runs, while
        distinct sites still decorrelate (no thundering herd on a shared
        coordinator)."""
        d = min(self.max_delay, self.base_delay * (2.0 ** attempt))
        if self.jitter:
            h = hashlib.md5(f"{site}:{attempt}".encode()).digest()
            frac = h[0] / 255.0
            d *= 1.0 + self.jitter * frac
        return d

    def execute(self, fn: Callable[[], Any], site: str) -> Any:
        """Run ``fn``; on transient failure back off and retry up to
        ``max_retries`` times. Success after >=1 retry records a ``retry``
        FaultReport; exhaustion or a fatal error records ``fatal`` and
        re-raises the last exception."""
        errors: List[str] = []
        attempt = 0
        while True:
            t0 = time.monotonic()
            try:
                out = fn()
            except Exception as e:
                elapsed = time.monotonic() - t0
                errors.append(f"{type(e).__name__}: {e}")
                over_deadline = (self.attempt_deadline is not None
                                 and elapsed > self.attempt_deadline)
                if (not self.is_transient(e) or attempt >= self.max_retries
                        or over_deadline):
                    FaultLog.record(FaultReport(
                        site=site, kind="fatal", attempts=attempt + 1,
                        detail={"errors": errors,
                                "overDeadline": over_deadline}))
                    raise
                delay = self.delay_for(attempt, site)
                _obs_trace.add_event("retry.backoff", site=site,
                                     attempt=attempt + 1,
                                     delaySecs=round(delay, 4))
                _obs_metrics.observe(
                    "tg_retry_backoff_seconds", delay,
                    help="backoff sleeps between transient-failure retries")
                time.sleep(delay)
                attempt += 1
                continue
            if attempt:
                FaultLog.record(FaultReport(
                    site=site, kind="retry", attempts=attempt + 1,
                    detail={"errors": errors}))
            return out
