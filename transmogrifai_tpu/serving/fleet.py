"""Shared-nothing replica fleet: the worker side of the horizontal
serving layer (docs/serving.md "Replica fleet & front door").

One :class:`~.runtime.ServingRuntime` is a single failure domain: kill
the process (or wedge its batcher) and every queued request dies with
it. ROADMAP item 2 asks for the layer above — N worker replicas, each a
full :class:`~.registry.ModelRegistry` (own queues, batcher threads,
breakers, serve-local metrics, drift monitors), sharing **nothing** but
the saved model artifact. This module owns the replica lifecycle; the
routing/failover/admission brain lives in :mod:`~.frontdoor`.

Two replica kinds behind one duck-typed surface (``submit`` / ``health``
/ ``queue_depth`` / ``swap`` / ``kill`` / ``close``):

* :class:`Replica` — **in-process** (tier-1): a ModelRegistry in this
  process. Deterministic, fast to spawn, and failure-injectable —
  ``kill()`` models a replica crash by closing the registry without
  draining, so every queued request's future fails (the front door
  fails them over to a survivor). Used by the tier-1 tests and the
  chaos-campaign ``fleet`` scenario.
* :class:`SubprocessReplica` — **out-of-process** (``TG_FLEET_SUBPROCESS=1``
  / ``FleetConfig.subprocess``; the multi-process soak): a
  ``python -m transmogrifai_tpu.serving.replica_worker`` child
  serving a saved model dir over a JSON-lines stdio protocol. A real
  process boundary — ``kill()`` is a SIGKILL, and the reader thread
  failing every pending future with :class:`ReplicaLostError` is
  exactly what a production TCP disconnect looks like.

Replica states (the front door's routing predicate):

``active``    routed; probed.
``draining``  rolling deploy in progress — skipped by the router when a
              healthier peer exists (a single-replica fleet keeps
              routing to it: ``registry.swap`` is itself zero-loss).
``ejected``   probe ladder tripped (breaker open / stalled / degraded
              readiness / consecutive probe failures) — no new traffic,
              still probed; readmitted after consecutive healthy probes.
``dead``      killed or vanished — futures failed over, never probed
              back in.
``retired``   scaled down gracefully (drained first; autoscale floor
              TG_FLEET_MIN).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from .registry import ModelRegistry
from .runtime import (
    DeadlineExceededError, OverloadError, RuntimeStoppedError, ServeConfig,
    ServingError,
)

#: replica states (see module docstring)
ACTIVE = "active"
DRAINING = "draining"
EJECTED = "ejected"
DEAD = "dead"
RETIRED = "retired"


class ReplicaLostError(ServingError):
    """The replica serving this request died (process kill, closed
    registry, broken pipe). The front door fails the request over to a
    survivor — callers only ever see this wrapped in the typed shed the
    failover budget produces when NO survivor remains."""


class ChipHeldError(ReplicaLostError):
    """A subprocess replica cannot start: this process has initialised
    JAX on an accelerator, and an accelerator belongs to one process at
    a time — the worker would fail or hang claiming it. The subprocess
    tier needs one chip per worker and a parent that stays off JAX
    (docs/serving.md "Replica fleet & front door")."""


def _parent_holds_accelerator() -> Optional[str]:
    """The accelerator platform this process has already initialised
    (None: jax not imported, no backend initialised yet, or CPU). Never
    initialises a backend itself — that would claim the chip."""
    if "jax" not in sys.modules:
        return None
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return None
    import jax
    platform = jax.default_backend()
    return None if platform == "cpu" else platform


class AdmissionRefusedError(OverloadError):
    """Pre-flight admission control refused the request: the predicted
    flush bytes exceed ``TG_DEVICE_BUDGET`` even at the minimum padding
    bucket — dispatching would exhaust the device, so the request is
    shed *before* any replica (or scorer) sees it. A typed
    :class:`~.runtime.OverloadError`, so loadgen/campaign accounting
    buckets it as a shed, never a failure."""


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


@dataclass
class FleetConfig:
    """Fleet knobs; every field has a ``TG_FLEET_*`` / ``TG_DEVICE_BUDGET``
    environment default (docs/serving.md "Replica fleet & front door")."""
    #: autoscale floor/ceiling (replica count)
    min_replicas: int = 1
    max_replicas: int = 4
    #: health-probe cadence (ms); 0 disables the background probe thread
    #: (tests drive ``probe_now()`` synchronously)
    probe_interval_ms: float = 200.0
    #: consecutive probe FAILURES (raise/timeout) before ejection
    probe_failures: int = 3
    #: consecutive healthy probes before an ejected replica readmits
    readmit_probes: int = 2
    #: per-request failover budget: how many times a request may be
    #: re-dispatched after its replica fails before it sheds typed
    max_failovers: int = 2
    #: device-memory budget (bytes) admission control enforces per flush;
    #: 0 disables admission control
    device_budget: int = 0
    #: windowed-p99 weight in the routing score (queue-depth equivalents
    #: per millisecond of p99)
    p99_weight: float = 0.05
    #: run the autoscale step on the probe cadence
    autoscale: bool = True
    #: spawn subprocess replicas (saved-model path required)
    subprocess: bool = False
    #: subprocess spawn budget (jax import + model load + warm)
    spawn_timeout_s: float = 180.0

    @classmethod
    def from_env(cls) -> "FleetConfig":
        return cls(
            min_replicas=_env_int("TG_FLEET_MIN", 1),
            max_replicas=_env_int("TG_FLEET_MAX", 4),
            probe_interval_ms=_env_float("TG_FLEET_PROBE_MS", 200.0),
            probe_failures=_env_int("TG_FLEET_PROBE_FAILURES", 3),
            readmit_probes=_env_int("TG_FLEET_READMIT_PROBES", 2),
            max_failovers=_env_int("TG_FLEET_MAX_FAILOVERS", 2),
            device_budget=_env_int("TG_DEVICE_BUDGET", 0),
            p99_weight=_env_float("TG_FLEET_P99_WEIGHT", 0.05),
            subprocess=bool(_env_int("TG_FLEET_SUBPROCESS", 0)),
            spawn_timeout_s=_env_float("TG_FLEET_SPAWN_TIMEOUT_S", 180.0),
        )


@dataclass
class _Probe:
    """Per-replica probe-ladder bookkeeping (owned by the front door's
    probe pass; see docs/serving.md for the ladder)."""
    failures: int = 0
    healthy: int = 0
    #: cached windowed p99 (ms) per model from the last healthy probe —
    #: the routing score's latency term
    p99_ms: Dict[str, float] = field(default_factory=dict)
    #: cached per-model scale hints from the last healthy probe
    scale_hints: Dict[str, str] = field(default_factory=dict)


class Replica:
    """One in-process worker: a full ModelRegistry under a replica id."""

    kind = "inproc"

    def __init__(self, rid: str, models: Dict[str, Any],
                 config: Optional[ServeConfig] = None,
                 warm: Optional[bool] = None):
        self.rid = rid
        self.state = ACTIVE
        self.probe = _Probe()
        self.routed = 0
        self._dead = False
        self.registry = ModelRegistry(config)
        for name, src in models.items():
            if isinstance(src, str):
                # manifest-verified load + warm pre-trace by default: the
                # replica's first flush must hit warm plan caches
                self.registry.load(name, src,
                                   warm=True if warm is None else warm)
            else:
                self.registry.register(name, src, warm=bool(warm))

    @property
    def dead(self) -> bool:
        return self._dead

    def submit(self, model: str, row: Dict[str, Any],
               deadline_ms: Optional[float] = None,
               tenant: Optional[str] = None) -> Future:
        if self._dead:
            raise ReplicaLostError(f"replica '{self.rid}' is dead")
        return self.registry.submit(model, row, deadline_ms=deadline_ms,
                                    tenant=tenant)

    def queue_depth(self, model: str) -> int:
        if self._dead:
            raise ReplicaLostError(f"replica '{self.rid}' is dead")
        return self.registry.runtime(model).queue_depth()

    def health(self) -> Dict[str, Any]:
        if self._dead:
            raise ReplicaLostError(f"replica '{self.rid}' is dead")
        return self.registry.health()

    def swap(self, model: str, model_or_path: Any) -> None:
        """Rolling-deploy hook: ``registry.swap`` is itself zero-loss
        (new runtime warmed + started before the entry flips; the old
        one drains after)."""
        self.registry.swap(model, model_or_path)

    # -- model mobility (the placement layer's page-in/evict hooks) ----------
    def load(self, name: str, src: Any,
             warm: Optional[bool] = None) -> None:
        """Page a model in: manifest-verified load (a *deserialize* via
        the AOT program store when the manifest carries one — not a
        compile) or registration of a live model object."""
        if self._dead:
            raise ReplicaLostError(f"replica '{self.rid}' is dead")
        if isinstance(src, str):
            self.registry.load(name, src,
                               warm=True if warm is None else warm)
        else:
            self.registry.register(name, src, warm=bool(warm))

    def unload(self, name: str, drain: bool = True) -> None:
        """Page a model out: close its runtime (draining queued work by
        default). The saved-model artifact and its AOT program store
        entry stay — a later page-in deserializes."""
        self.registry.unregister(name, drain=drain)

    def resident(self) -> List[str]:
        """Models currently warm on this replica."""
        return self.registry.names()

    def kill(self) -> None:
        """Simulate a replica crash: no drain — every queued request's
        future fails (RuntimeStoppedError), which the front door
        classifies as replica loss and fails over. Flushes already in
        the pipelined dataplane (dispatched, awaiting completion) still
        resolve with real records via the completer drain — so with
        ``TG_SERVE_PIPELINE`` > 1 a kill loses zero futures either way:
        in-flight work completes, queued work fails over."""
        self._dead = True
        self.state = DEAD
        self.registry.close(drain=False)

    def close(self, drain: bool = True) -> None:
        self._dead = True
        self.registry.close(drain=drain)


# -- subprocess replicas ------------------------------------------------------

#: typed-error names the worker protocol maps back to typed classes, so
#: a shed inside the child stays a typed shed in the parent
_TYPED_BY_NAME = {
    "OverloadError": OverloadError,
    "DeadlineExceededError": DeadlineExceededError,
    "RuntimeStoppedError": RuntimeStoppedError,
    "AdmissionRefusedError": AdmissionRefusedError,
}


class SubprocessReplica:
    """One out-of-process worker speaking the replica_worker JSON-lines
    protocol over stdio (``TG_FLEET_SUBPROCESS``; docs/serving.md).

    Parent-side state is three pieces: a write lock (requests are
    single-line JSON), a pending-futures map keyed by request id, and a
    ``tg-fleet-io[rid]`` reader thread that resolves futures as result
    lines arrive — and fails every pending future with
    :class:`ReplicaLostError` when the pipe closes (child death IS the
    failure signal; no separate liveness protocol)."""

    kind = "subprocess"

    def __init__(self, rid: str, models: Dict[str, str],
                 config: Optional[ServeConfig] = None,
                 warm: Optional[bool] = None,
                 spawn_timeout_s: float = 180.0):
        for name, src in models.items():
            if not isinstance(src, str):
                raise ValueError(
                    f"subprocess replicas need saved-model paths; model "
                    f"'{name}' was passed a live object")
        self.rid = rid
        self.state = ACTIVE
        self.probe = _Probe()
        self.routed = 0
        self._dead = False
        self._seq = 0
        self._wlock = threading.Lock()
        self._plock = threading.Lock()
        self._pending: Dict[int, Future] = {}
        cmd = [sys.executable, "-m",
               "transmogrifai_tpu.serving.replica_worker"]
        for name, path in models.items():
            cmd += ["--model", f"{name}={path}"]
        cfg = config or ServeConfig.from_env()
        cmd += ["--max-batch", str(cfg.max_batch),
                "--queue-max", str(cfg.max_queue),
                "--max-wait-ms", str(cfg.max_wait_ms)]
        # the worker inherits this process's environment — and with it
        # the device JAX would pick here. No CPU default is slipped in: a
        # replica silently scoring on the host is not the fleet asked for
        held = _parent_holds_accelerator()
        if held is not None:
            raise ChipHeldError(
                f"subprocess replica '{rid}' would need the {held} device "
                f"this process already holds; one chip serves one process "
                f"— use in-process replicas, or spawn the fleet from a "
                f"parent that has not initialised JAX, one chip per worker")
        self._proc = subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True)
        self._ready = threading.Event()
        self._reader = threading.Thread(
            target=self._read_loop, name=f"tg-fleet-io[{rid}]", daemon=True)
        self._reader.start()
        # the reader also sets _ready when the pipe closes: a worker that
        # dies during start-up fails the spawn at once, not at the timeout
        ready = self._ready.wait(timeout=spawn_timeout_s)
        if not ready or self._dead:
            self.kill()
            raise ReplicaLostError(
                f"subprocess replica '{rid}' "
                + (f"exited during start-up (code {self._proc.returncode}; "
                   f"run `{' '.join(cmd[1:])}` by hand for its error)"
                   if ready else
                   f"not ready within {spawn_timeout_s:.0f}s"))

    @property
    def dead(self) -> bool:
        return self._dead

    # -- protocol -------------------------------------------------------------
    def _send(self, msg: Dict[str, Any]) -> None:
        line = json.dumps(msg, separators=(",", ":"))
        with self._wlock:
            if self._dead or self._proc.stdin is None:
                raise ReplicaLostError(f"replica '{self.rid}' is dead")
            try:
                self._proc.stdin.write(line + "\n")
                self._proc.stdin.flush()
            except (BrokenPipeError, OSError, ValueError) as e:
                raise ReplicaLostError(
                    f"replica '{self.rid}' pipe closed: {e}") from e

    def _call(self, msg: Dict[str, Any]) -> Future:
        with self._plock:
            self._seq += 1
            rid = self._seq
            fut: Future = Future()
            self._pending[rid] = fut
        try:
            self._send({**msg, "id": rid})
        except ReplicaLostError:
            with self._plock:
                self._pending.pop(rid, None)
            raise
        return fut

    def _read_loop(self) -> None:
        out = self._proc.stdout
        try:
            for line in out:
                try:
                    msg = json.loads(line)
                except ValueError:
                    continue
                if msg.get("ready"):
                    self._ready.set()
                    continue
                fut = None
                with self._plock:
                    fut = self._pending.pop(msg.get("id"), None)
                if fut is None:
                    continue
                err = msg.get("error")
                if err is not None:
                    cls = _TYPED_BY_NAME.get(err.get("type"),
                                             ReplicaLostError)
                    _try_set_exception(fut, cls(err.get("msg", "")))
                elif "health" in msg:
                    _try_set_result(fut, msg["health"])
                else:
                    _try_set_result(fut, msg.get("record"))
        finally:
            # pipe closed: the child is gone — every pending request's
            # future fails AS replica loss, which the front door fails
            # over (zero lost futures even on SIGKILL)
            self._dead = True
            self._ready.set()
            with self._plock:
                pending = list(self._pending.values())
                self._pending.clear()
            for fut in pending:
                _try_set_exception(fut, ReplicaLostError(
                    f"replica '{self.rid}' died with the request in "
                    f"flight"))

    # -- replica surface ------------------------------------------------------
    def submit(self, model: str, row: Dict[str, Any],
               deadline_ms: Optional[float] = None,
               tenant: Optional[str] = None) -> Future:
        return self._call({"op": "submit", "model": model, "row": row,
                           "deadlineMs": deadline_ms, "tenant": tenant})

    def queue_depth(self, model: str) -> int:
        # parent-side proxy: requests written but not yet resolved — the
        # honest load signal without a synchronous round-trip per pick
        if self._dead:
            raise ReplicaLostError(f"replica '{self.rid}' is dead")
        with self._plock:
            return len(self._pending)

    def health(self, timeout: float = 10.0) -> Dict[str, Any]:
        return self._call({"op": "health"}).result(timeout=timeout)

    def swap(self, model: str, model_or_path: Any) -> None:
        if not isinstance(model_or_path, str):
            raise ValueError("subprocess replicas swap saved-model paths")
        self._call({"op": "swap", "model": model,
                    "path": model_or_path}).result(timeout=180.0)

    def kill(self) -> None:
        self._dead = True
        self.state = DEAD
        try:
            self._proc.kill()
        except OSError:
            pass
        self._proc.wait(timeout=10)

    def close(self, drain: bool = True) -> None:
        if self._dead:
            return
        try:
            self._send({"op": "close"})
            self._proc.wait(timeout=30)
        except (ReplicaLostError, subprocess.TimeoutExpired):
            self.kill()
            return
        self._dead = True


def _try_set_result(fut: Future, value: Any) -> None:
    try:
        fut.set_result(value)
    except InvalidStateError:
        pass


def _try_set_exception(fut: Future, exc: BaseException) -> None:
    try:
        fut.set_exception(exc)
    except InvalidStateError:
        pass


def build_replica(rid: str, models: Dict[str, Any],
                  config: Optional[ServeConfig] = None,
                  fleet_config: Optional[FleetConfig] = None,
                  warm: Optional[bool] = None):
    """The fleet's replica factory: subprocess when the flag asks for it
    (and every model is a saved path), in-process otherwise."""
    fc = fleet_config or FleetConfig.from_env()
    if fc.subprocess and all(isinstance(s, str) for s in models.values()):
        return SubprocessReplica(rid, models, config=config, warm=warm,
                                 spawn_timeout_s=fc.spawn_timeout_s)
    return Replica(rid, models, config=config, warm=warm)
