"""Deterministic fault-injection harness.

Every recovery path in the framework is exercised through *named sites*
compiled into the production code (``inject``/``poison`` calls). A site is
completely inert — one dict lookup on an empty dict — unless a
:class:`FaultSpec` is armed for it, either programmatically
(:func:`configure` / the :func:`injected` context manager, used by the
``chaos``-marked tests) or via environment::

    TG_CHAOS=1 TG_FAULTS='{"distributed.to_host": {"mode": "raise", "nth": 1, "count": 2}}'

The env path is gated on ``TG_CHAOS`` so a leaked ``TG_FAULTS`` can never
arm sites in a production process; ``tests/conftest.py`` additionally
asserts no sites are active around every non-chaos test.

Determinism: sites fire purely on call counters (fail the Nth..Nth+count-1
matching calls) — no clocks, no randomness — so a chaos test replays the
exact same fault sequence on every run.

Injection sites (see docs/robustness.md for the full table):

===========================  ====================================================
site                         fires in
===========================  ====================================================
``validator.family_fit``     per model family, before its sweep branch dispatches
``hist.build``               per tree family, before its histogram programs
                             build or dispatch (histeng/engine.py chaos_gate;
                             a raise quarantines the family like
                             ``validator.family_fit``)
``validator.fold_metrics``   per family, on the host (F, G) CV metric matrix
                             (``nan`` mode poisons candidate metrics)
``selector.refit``           before the winner's full-data refit
``dag.stage_fit``            before each estimator fit in the DAG
``distributed.to_host``      before each guarded device→host transfer
``distributed.device_put``   before each guarded host→device placement
``plan.segment_execute``     before each fused transform-plan segment runs
                             (plan.py; a raise here exercises the planned→
                             eager fallback — ``plan.*`` sites deliberately
                             do NOT disable the planner the way other armed
                             sites do)
``serve.enqueue``            in ``ServingRuntime.submit``, before admission
                             (serving/runtime.py; models the admission layer
                             failing — surfaces as a typed error to the one
                             caller, the runtime stays up)
``serve.flush``              in the batcher, after deadline shedding and
                             before dispatch (a raise degrades the batch to
                             the eager per-row path)
``serve.dispatch``           before the compiled micro-batch dispatch (a
                             raise feeds the per-model circuit breaker and
                             degrades the batch to the eager path; like
                             ``plan.*``, ``serve.*`` sites do NOT disable
                             the transform planner)
``serve.complete``           in the pipelined completer, before flattening
                             a device result (fires only with
                             ``TG_SERVE_PIPELINE`` > 1; the failure counts
                             against the *dispatching* flush and the batch
                             degrades to the eager path)
``stream.read``              in the chunk-feed producer thread, before each
                             chunk is pulled from the ChunkSource
                             (streaming/feed.py; errors — preemption
                             included — forward through the bounded queue
                             and re-raise in the consumer)
``stream.upload``            in the producer, before the chunk's packed
                             host→device upload (``to_device``)
``stream.cache``             in a producer worker, on every transformed-
                             chunk cache lookup (streaming/cache.py) — a
                             raise models a corrupt/evicted entry and
                             degrades to the typed recompute fallback
                             (bit-equal, never wrong data); preemption
                             kills mid-lookup and resumes bit-exactly
``stream.fold``              in the consumer, before a chunk folds into the
                             estimator's monoid state (key = pass id);
                             ``mode: "preempt"`` here is the canonical
                             kill-mid-epoch test — resume continues from
                             the last committed chunk bit-exactly
``drift.fold``               in the drift monitor, before a scored
                             micro-batch folds into the per-feature
                             scoring sketches (serving/drift.py; a raise
                             is contained by the runtime's crash-isolation
                             fence — typed ``drift_fold_failed``, zero
                             request impact; ``drift.*`` sites keep the
                             transform planner active like ``serve.*``)
``drift.verdict``            before a drift verdict pass compares the
                             scoring sketches against the training
                             baseline (contained in the monitor — typed
                             ``drift_verdict_failed``, fold state intact)
``drift.refit``              in the background refit thread, before the
                             refit hook runs (a raise means no new model:
                             typed ``drift_refit_failed``, the old model
                             keeps serving, breaker untouched)
``oom.plan``                 before each fused transform-plan segment runs
                             (plan.py; ``mode: "oom"`` raises a typed
                             :class:`~.resources.ResourceExhaustedError`
                             — the planned run bisects the row batch to
                             smaller padding buckets, bit-equal by
                             construction; ``oom.*`` sites keep the
                             planner active like ``plan.*``/``serve.*``)
``oom.serve``                before the compiled micro-batch dispatch in
                             the serve batcher (serving/runtime.py; an
                             exhausted flush splits in half down to
                             singletons — requests degrade in latency,
                             never fail, and the breaker counts only
                             non-resource faults)
``oom.stream``               in the chunk-feed producer, before the packed
                             host→device upload (streaming/feed.py; the
                             trainer halves the chunk row budget and
                             continues from the committed-row prefix)
``oom.sweep``                before a family's fused sweep program
                             dispatches (validators.py; the packed (F·G)
                             grid splits in half and fold metrics merge —
                             the family is downshifted, not quarantined)
``fleet.route``              in the front door, on the routing hop to the
                             selected replica (serving/frontdoor.py; a
                             raise fails the request over to another
                             replica within the bounded failover budget
                             — typed shed when exhausted; ``fleet.*``
                             sites keep the planner active like
                             ``serve.*``)
``fleet.replica_kill``       in the front door, as a request routes to
                             the selected replica (a raise kills that
                             replica — queued requests fail over to
                             survivors with zero lost futures, and a
                             ``replica_lost`` post-mortem bundle dumps)
``fleet.probe``              in the fleet health-probe pass, before a
                             replica's ``health()`` read (consecutive
                             failures walk the ejection ladder; healthy
                             probes readmit)
``aot.load``                 in the AOT program store, after an entry is
                             found and before its artifact loads
                             (programstore/store.py; models a corrupt /
                             truncated / stale-jaxlib artifact — the
                             dispatch falls back to the trace path
                             bit-equally with a typed ``aot_fallback``
                             record and an ``aot-miss`` ledger cause;
                             ``aot.*`` sites keep the planner active
                             like ``plan.*`` — the store lives inside
                             the planner's segment dispatch)
``net.accept``               in the network edge, per connection right
                             after the socket accept (serving/netedge.py;
                             a raise drops the connection as a typed
                             ``accept_fault`` shed with a
                             ``net_accept_refused`` FaultLog record —
                             nothing was submitted, nothing can be lost;
                             ``net.*`` sites are not ``keeps_planner``:
                             while one is armed scoring runs eager)
``net.read``                 per request, before the frame/body is read
                             off the socket (a raise models the read
                             path dying mid-request: the peer observes a
                             disconnect, the edge accounts a typed
                             ``read_fault`` shed + ``net_read_shed``)
``net.write``                per response, before the bytes are written
                             back (by this point every submitted future
                             has already resolved — the peer sees a
                             mid-request disconnect, the edge accounts a
                             typed ``write_fault`` shed +
                             ``net_write_shed``; never a lost future)
``place.assign``             in the placement bin-pack, per model as it
                             is assigned to a replica
                             (serving/placement.py; a raise leaves the
                             model cold — typed ``place_assign_failed``
                             — and it pages in on first demand, zero
                             request impact; ``place.*`` sites keep the
                             planner active like ``fleet.*``)
``place.evict``              before an LRU victim's runtime unloads (a
                             raise skips the eviction — the predicted
                             capacity is advisory — with a typed
                             ``place_evict_failed``; the page-in
                             proceeds anyway)
``place.pagein``             in the single-flight page-in leader,
                             before the cold model's runtime loads (a
                             raise fails the page-in typed —
                             ``place_pagein_failed`` — and the front
                             door retries within its bounded failover
                             budget: typed shed when exhausted, never
                             a lost future)
===========================  ====================================================

Preemption sites (``mode: "preempt"`` — raise :class:`SimulatedPreemption`,
a *BaseException* that models the process being killed: no ``except
Exception`` recovery path may swallow it, exactly like a real SIGTERM):

===========================  ====================================================
``preempt.stage_fit``        mid-DAG, before an estimator's fit starts
``preempt.checkpoint_write`` inside a stage-checkpoint write, between the
                             payload files and the manifest commit
``preempt.sweep``            mid-sweep, before a model family's branch runs
``preempt.refit``            after the sweep, before the winner's refit
===========================  ====================================================
"""
from __future__ import annotations

import contextlib
import json
import logging
import os
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..observability import metrics as _obs_metrics

logger = logging.getLogger(__name__)

#: chaos gate: the env-driven spec (TG_FAULTS) is honored only when this is
#: set, so fault hooks can never arm themselves in a production process
CHAOS_ENV = "TG_CHAOS"
#: JSON dict {site: spec-dict} (see FaultSpec fields)
SPEC_ENV = "TG_FAULTS"


class TransientFaultError(RuntimeError):
    """Injected error classified transient by RetryPolicy (a stand-in for
    device-transfer hiccups: UNAVAILABLE / DEADLINE_EXCEEDED / link resets)."""


class InjectedFaultError(RuntimeError):
    """Injected error classified fatal (never retried)."""


class SimulatedPreemption(BaseException):
    """A deterministic stand-in for the process being killed (TPU
    preemption, SIGTERM, OOM-kill). Derives from ``BaseException`` — like
    ``KeyboardInterrupt`` — so quarantine/retry handlers (``except
    Exception``) can never absorb it: the only valid recovery is a fresh
    process calling ``train(resume=True)``."""


@dataclass
class FaultSpec:
    """One armed site.

    ``mode``: ``"raise"`` (throw from :func:`inject`), ``"nan"`` (poison
    the array passed to :func:`poison`), ``"preempt"`` (throw
    :class:`SimulatedPreemption` — a simulated process kill), or
    ``"oom"`` (throw :class:`~.resources.ResourceExhaustedError` — a
    simulated device/host allocation failure the adaptive downshift
    paths recover from).
    ``nth``/``count``: fire on matching calls nth..nth+count-1 (1-based).
    ``key``: only fire when the call's ``key`` matches (None = any).
    ``index``: nan mode — flat index to poison; None poisons the whole
    array. ``transient``: raise mode — throw :class:`TransientFaultError`
    (retryable) vs :class:`InjectedFaultError`.
    """
    site: str
    mode: str = "raise"
    nth: int = 1
    count: int = 1
    key: Optional[str] = None
    index: Optional[int] = 0
    transient: bool = True


@dataclass(frozen=True)
class SiteSpec:
    """One *registered* chaos site — the machine-readable row behind the
    docstring table above and the docs/robustness.md site tables (a test
    asserts all three agree, so the inventory can never silently rot).

    ``modes``: injection modes the site supports. ``module``: the file
    whose production code compiles the ``inject``/``poison`` call in.
    ``scenarios``: campaign scenario names that exercise the site
    (first entry is the canonical one the coverage pass uses —
    robustness/campaign.py). ``recovery``: the promised recovery, prose.
    ``bit_equal``: True when the promise is that a run recovering from
    this fault produces results **bit-identical** to the fault-free run
    (the campaign's strongest oracle); False when recovery legitimately
    alters the result (e.g. a quarantined candidate changes selection) —
    such divergence must then be visible in fault accounting, never
    silent. ``keeps_planner``: True when arming the site leaves the
    transform planner on (plan.planning_applicable): the site targets the
    planner itself, its segment dispatch, or a layer above it, so the
    eager stand-in would disable exactly the path under test; an armed
    site without it runs the eager per-stage path, whose retry/quarantine
    semantics it exercises."""
    name: str
    modes: Tuple[str, ...]
    module: str
    scenarios: Tuple[str, ...]
    recovery: str
    bit_equal: bool = True
    keeps_planner: bool = False


def _site(name, modes, module, scenarios, recovery, bit_equal=True,
          keeps_planner=False):
    return SiteSpec(name, tuple(modes.split("|")), module,
                    tuple(scenarios.split("|")), recovery, bit_equal,
                    keeps_planner)


#: the machine-readable site inventory (docs/robustness.md carries the
#: human tables; tests/test_campaign.py asserts they agree and that every
#: site here is armed by at least one tier-1 test — no dead chaos sites)
ALL_SITES: Dict[str, SiteSpec] = {s.name: s for s in (
    _site("validator.family_fit", "raise", "impl/tuning/validators.py",
          "sweep|train",
          "family quarantined; the other families still race",
          bit_equal=False),
    _site("hist.build", "raise", "histeng/engine.py", "sweep|train",
          "tree family quarantined before its histogram programs "
          "dispatch; the other families still race",
          bit_equal=False),
    _site("validator.fold_metrics", "nan", "impl/tuning/validators.py",
          "sweep|train",
          "poisoned candidates quarantined, sweep continues",
          bit_equal=False),
    _site("selector.refit", "raise", "impl/selector/model_selector.py",
          "train",
          "winner quarantined; next-ranked finite candidate refits",
          bit_equal=False),
    _site("dag.stage_fit", "raise", "dag.py", "train",
          "stage fit retried under the fault policy (transient), else "
          "typed failure"),
    _site("distributed.to_host", "raise", "parallel/distributed.py",
          "sweep|transfer|train",
          "device->host transfer retried (transient); a fatal transfer "
          "fault quarantines the consuming family", bit_equal=False),
    _site("distributed.device_put", "raise", "parallel/distributed.py",
          "transfer|mesh_sweep",
          "host->device placement retried (transient); a fatal placement "
          "fault quarantines the consuming family", bit_equal=False),
    _site("plan.segment_execute", "raise", "plan.py", "train|serve",
          "planned run falls back to eager per-stage dispatch, bit-equal",
          keeps_planner=True),
    _site("serve.enqueue", "raise", "serving/runtime.py", "serve",
          "typed error to the one caller; the runtime stays up",
          keeps_planner=True),
    _site("serve.flush", "raise", "serving/runtime.py", "serve",
          "batch degrades to the eager per-row path, bit-equal",
          keeps_planner=True),
    _site("serve.dispatch", "raise", "serving/runtime.py", "serve",
          "breaker counts the failure; batch degrades eager, bit-equal",
          keeps_planner=True),
    _site("serve.complete", "raise", "serving/runtime.py", "serve",
          "pipelined completion-side failure: the breaker counts it "
          "against the dispatching flush; batch degrades eager, "
          "bit-equal (fires only with TG_SERVE_PIPELINE > 1)",
          keeps_planner=True),
    _site("stream.read", "raise|preempt", "streaming/feed.py", "stream",
          "error forwards through the queue; preemption resumes "
          "bit-exactly from the last committed chunk"),
    _site("stream.upload", "raise|preempt", "streaming/feed.py", "stream",
          "error forwards through the queue; resume bit-exact"),
    _site("stream.cache", "raise|preempt", "streaming/cache.py", "stream",
          "corrupt/evicted entry falls back to a typed bit-equal "
          "recompute from source; preemption resumes bit-exactly"),
    _site("stream.fold", "raise|preempt", "streaming/trainer.py", "stream",
          "fold retried/resumed from the committed state, bit-exact"),
    _site("drift.fold", "raise", "serving/drift.py", "serve|serve_heal",
          "contained by the runtime fence; zero request impact",
          keeps_planner=True),
    _site("drift.verdict", "raise", "serving/drift.py", "serve|serve_heal",
          "contained in the monitor; fold state intact", keeps_planner=True),
    _site("drift.refit", "raise", "serving/registry.py", "serve_heal",
          "no swap; the old model keeps serving, breaker untouched",
          keeps_planner=True),
    _site("oom.plan", "oom", "plan.py", "train|serve",
          "row batch bisects to smaller padding buckets, bit-equal",
          keeps_planner=True),
    _site("oom.serve", "oom", "serving/runtime.py", "serve|serve_heal",
          "flush splits down to singletons; zero failed requests, "
          "bit-equal records", keeps_planner=True),
    _site("oom.stream", "oom", "streaming/feed.py", "stream",
          "chunk row budget halves from the committed-row prefix; prep "
          "folds bit-equal, tree edges within documented tolerance",
          bit_equal=False, keeps_planner=True),
    _site("oom.sweep", "oom", "impl/tuning/validators.py", "sweep|train",
          "packed grid splits and fold metrics merge (identical winner); "
          "exhaustion persisting to a single config quarantines the "
          "family", bit_equal=False, keeps_planner=True),
    _site("fleet.route", "raise", "serving/frontdoor.py", "fleet|density",
          "request fails over to another replica (bounded budget); "
          "typed shed when exhausted — never a lost future",
          keeps_planner=True),
    _site("fleet.replica_kill", "raise", "serving/frontdoor.py",
          "fleet|density",
          "replica killed mid-flight; queued requests fail over to "
          "survivors, replica_lost post-mortem dumped, zero lost — "
          "under placement, models whose only warm copy died page in "
          "on a survivor", keeps_planner=True),
    _site("fleet.probe", "raise", "serving/frontdoor.py", "fleet|density",
          "probe failure counted; consecutive failures eject the "
          "replica, healthy probes readmit it — requests unaffected",
          keeps_planner=True),
    _site("aot.load", "raise", "programstore/store.py", "serve_heal",
          "bad AOT artifact falls back to the trace path bit-equally; "
          "typed aot_fallback recorded, ledger build classified "
          "aot-miss — never a request error", keeps_planner=True),
    _site("net.accept", "raise", "serving/netedge.py", "net",
          "connection dropped at accept as a typed accept_fault shed; "
          "net_accept_refused recorded, nothing submitted, zero lost"),
    _site("net.read", "raise", "serving/netedge.py", "net",
          "read path dies mid-request; peer sees a disconnect, edge "
          "accounts a typed read_fault shed (net_read_shed)"),
    _site("net.write", "raise", "serving/netedge.py", "net",
          "write path dies mid-response after every future resolved; "
          "typed write_fault shed (net_write_shed), never a lost future"),
    _site("place.assign", "raise", "serving/placement.py", "density",
          "model left cold by the bin-pack (place_assign_failed); it "
          "pages in on first demand — zero request impact",
          keeps_planner=True),
    _site("place.evict", "raise", "serving/placement.py", "density",
          "eviction skipped (capacity prediction is advisory) with a "
          "typed place_evict_failed; the page-in proceeds anyway",
          keeps_planner=True),
    _site("place.pagein", "raise", "serving/placement.py", "density",
          "page-in fails typed (place_pagein_failed); the front door "
          "retries within the bounded failover budget — typed shed "
          "when exhausted, never a lost future", keeps_planner=True),
    _site("preempt.stage_fit", "preempt", "dag.py", "train|stream",
          "train(resume=True) restores verified stages, bit-exact"),
    _site("preempt.checkpoint_write", "preempt", "persistence.py",
          "train|stream",
          "torn checkpoint detected by manifest; resume refits it"),
    _site("preempt.sweep", "preempt", "impl/tuning/validators.py", "train",
          "persisted sweep state replays bit-exactly on resume"),
    _site("preempt.refit", "preempt", "impl/selector/model_selector.py",
          "train",
          "resume replays the sweep from disk and goes straight to refit"),
)}


def sites_for_scenario(scenario: str) -> List[str]:
    """Registered sites a campaign scenario can exercise (sorted)."""
    return sorted(n for n, s in ALL_SITES.items()
                  if scenario in s.scenarios)


_LOCK = threading.Lock()
_SPECS: Dict[str, FaultSpec] = {}
_CALLS: Dict[str, int] = {}
#: (site, mode) -> times an armed spec actually APPLIED its fault (raised /
#: poisoned) — always-on process-local accounting the campaign engine reads
#: for per-schedule coverage; mirrored into the gated
#: ``tg_chaos_injections_total{site,mode}`` counter (zero writes when
#: metrics are off). Reset by clear()/configure().
_FIRED: Dict[Tuple[str, str], int] = {}
_ENV_LOADED = False


def _load_env() -> None:
    global _ENV_LOADED
    if _ENV_LOADED:
        return
    _ENV_LOADED = True
    raw = os.environ.get(SPEC_ENV)
    if not raw:
        return
    if not os.environ.get(CHAOS_ENV):
        logger.warning(
            "%s is set but %s is not: ignoring fault-injection spec (sites "
            "stay inert outside chaos runs)", SPEC_ENV, CHAOS_ENV)
        return
    configure(json.loads(raw))


def configure(specs: Dict[str, Dict[str, Any]]) -> None:
    """Arm sites from {site: spec-dict}; resets all call counters."""
    with _LOCK:
        for site, kv in specs.items():
            _SPECS[site] = FaultSpec(site=site, **kv)
        _CALLS.clear()
        _FIRED.clear()


def clear() -> None:
    """Disarm every site and reset counters."""
    with _LOCK:
        _SPECS.clear()
        _CALLS.clear()
        _FIRED.clear()


def fired_counts() -> Dict[str, Dict[str, int]]:
    """{site: {mode: n}} faults actually applied since the last
    configure()/clear() — the campaign engine's per-schedule coverage
    accounting (armed-but-never-fired sites are invisible here)."""
    with _LOCK:
        out: Dict[str, Dict[str, int]] = {}
        for (site, mode), n in _FIRED.items():
            out.setdefault(site, {})[mode] = n
        return out


def _record_fired(site: str, mode: str) -> None:
    with _LOCK:
        _FIRED[(site, mode)] = _FIRED.get((site, mode), 0) + 1
    # an applied chaos fault is part of the incident narrative — the
    # flight recorder must show the injection next to the recovery it
    # provoked (observability/blackbox.py)
    from ..observability import blackbox as _blackbox
    _blackbox.record("chaos.injection", site=site, mode=mode)
    _obs_metrics.inc_counter(
        "tg_chaos_injections_total",
        help="chaos faults actually applied, by site and mode "
        "(docs/robustness.md 'Chaos campaigns')", site=site, mode=mode)


def active_sites() -> List[str]:
    """Names of currently-armed sites (empty in production)."""
    _load_env()
    return sorted(_SPECS)


@contextlib.contextmanager
def injected(specs: Dict[str, Dict[str, Any]]):
    """Arm ``specs`` for the duration of the block, then disarm everything
    (the chaos tests' entry point)."""
    configure(specs)
    try:
        yield
    finally:
        clear()


def _fires(site: str, key: Optional[str]) -> Optional[FaultSpec]:
    spec = _SPECS.get(site)
    if spec is None:
        return None
    if spec.key is not None and key != spec.key:
        return None
    with _LOCK:
        n = _CALLS.get(site, 0) + 1
        _CALLS[site] = n
    if spec.nth <= n < spec.nth + spec.count:
        return spec
    return None


def inject(site: str, key: Optional[str] = None) -> None:
    """Raise the armed fault for ``site`` if its spec fires on this call.
    Inert (one falsy dict check) when nothing is armed."""
    if not _SPECS and _ENV_LOADED:
        return
    _load_env()
    spec = _fires(site, key)
    if spec is None or spec.mode not in ("raise", "preempt", "oom"):
        return
    _record_fired(site, spec.mode)
    if spec.mode == "preempt":
        raise SimulatedPreemption(
            f"simulated preemption at site '{site}'"
            + (f" (key={key})" if key else ""))
    if spec.mode == "oom":
        from .resources import ResourceExhaustedError
        raise ResourceExhaustedError(
            f"injected resource exhaustion at site '{site}'"
            + (f" (key={key})" if key else ""), site=site)
    exc = TransientFaultError if spec.transient else InjectedFaultError
    raise exc(f"injected fault at site '{site}'"
              + (f" (key={key})" if key else ""))


def poison(site: str, arr: np.ndarray, key: Optional[str] = None) -> np.ndarray:
    """Return ``arr`` with NaN poisoning applied if the armed ``nan`` spec
    for ``site`` fires on this call; otherwise return ``arr`` untouched."""
    if not _SPECS and _ENV_LOADED:
        return arr
    _load_env()
    spec = _fires(site, key)
    if spec is None or spec.mode != "nan":
        return arr
    _record_fired(site, spec.mode)
    out = np.array(arr, dtype=np.float64 if arr.dtype.kind != "f"
                   else arr.dtype, copy=True)
    if spec.index is None:
        out[...] = np.nan
    else:
        out.reshape(-1)[spec.index] = np.nan
    return out
