"""Content-addressed AOT program store + sessions + the ``aot.load`` site.

ROADMAP item 1's surviving gap: warm start (PR 6) pre-traces at
``registry.load()``, so a fresh process still pays the full Python trace
+ XLA compile of every serve program at load — and PR 14's replica fleet
multiplied that by N. This module generalizes ``utils/jax_cache.py``
from a per-process XLA byte cache into a **framework-level artifact
shared across processes and replicas**: serialized ``jax.export``
programs (transform-plan segments — the serve scorer included — and the
fused sweep programs), keyed by

    (segment fingerprint x padding bucket x jaxlib version x device kind)

and stored content-addressed next to the model (``<model>/programs/``,
entries recorded in a ``programs`` section of ``MANIFEST.json``) or in a
cross-model store (``TG_AOT_STORE``). ``registry.load()`` opens a
*session* over the manifest entries before any trace is attempted; the
plan executor consults :func:`lookup` at each segment's first dispatch
per bucket and dispatches the deserialized program instead of tracing.
A fleet's replica 1 populates (its traced warm dispatches are *offered*
back through :func:`offer_segment` under a :func:`capture` scope);
replicas 2..N deserialize — the fleet compiles once total.

The fallback ladder is the contract (docs/serving.md "AOT cold start &
the program store"): a store hit dispatches bit-identically to the
traced program (same StableHLO, same compiler — asserted in
tests/test_programstore.py); **any** mismatch — absent entry, jaxlib or
device-kind drift, corrupt/truncated blob, deserialization failure, or
the deterministic ``aot.load`` chaos fault — degrades to the existing
trace path with a typed FaultLog ``aot_fallback`` record, a
``tg_aot_miss_total{reason}`` count, and the resulting build classified
``aot-miss`` in the compile ledger. Never an error on a request path.

Concurrency: every write goes through ``manifest.atomic_write_bytes``
(tmp + fsync + rename) and blobs are content-addressed by sha256, so
two replicas populating the same store race benignly — both write the
same bytes under the same name, the rename is atomic, and the manifest
merge is last-writer-wins over identical entries. The store is bounded:
:meth:`ProgramStore.gc` evicts oldest-first past ``TG_AOT_STORE_MAX``
entries / ``TG_AOT_STORE_MAX_BYTES``.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from . import aot as _aot

logger = logging.getLogger(__name__)

#: master switch: TG_AOT=0 disables every store path (lookup, capture,
#: save-time populate) process-wide
AOT_ENV = "TG_AOT"
#: save-time populate switch (default on): ``save_model`` drives the
#: serve scorer once under a capture scope so the saved model ships its
#: programs; TG_AOT_SAVE=0 defers population to the first warm load
AOT_SAVE_ENV = "TG_AOT_SAVE"
#: cross-model store directory (sweep programs at train time; also
#: consulted by plan lookups). Unset = model-dir stores only.
STORE_ENV = "TG_AOT_STORE"
#: store bounds (oldest-first GC past either)
STORE_MAX_ENV = "TG_AOT_STORE_MAX"
STORE_MAX_BYTES_ENV = "TG_AOT_STORE_MAX_BYTES"
DEFAULT_STORE_MAX = 128
DEFAULT_STORE_MAX_BYTES = 512 * 1024 * 1024

#: store subdirectory inside a model dir
PROGRAMS_DIR = "programs"
#: MANIFEST.json ``programs`` section format version
PROGRAMS_VERSION = 1

_FALSY = ("0", "false", "False", "no", "off")

_enabled_override: Optional[bool] = None


def aot_enabled() -> bool:
    """True when the AOT program store is active (default on;
    ``TG_AOT=0`` disables, :func:`enable_aot` overrides)."""
    if _enabled_override is not None:
        return _enabled_override
    return os.environ.get(AOT_ENV, "1") not in _FALSY


def enable_aot(on: Optional[bool]) -> None:
    """Force the store on/off from code (benches, tests); ``None`` hands
    control back to the ``TG_AOT`` environment switch."""
    global _enabled_override
    _enabled_override = None if on is None else bool(on)


def save_populate_enabled() -> bool:
    return (aot_enabled()
            and os.environ.get(AOT_SAVE_ENV, "1") not in _FALSY)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class StoreEntryError(RuntimeError):
    """A store entry failed integrity verification (missing blob, size or
    sha256 mismatch). Internal — always converted into a typed fallback,
    never surfaced to a request."""


def key_id(fingerprint: str, bucket: int) -> str:
    return f"{fingerprint}@{int(bucket)}"


class ProgramStore:
    """One on-disk store directory: content-addressed blobs
    (``<sha256[:32]>.bin``) plus one small JSON meta per entry
    (``<keyhash>.json``) carrying the full key, integrity fields and a
    best-effort hit count. All writes are atomic
    (``manifest.atomic_write_bytes``)."""

    def __init__(self, dirpath: str):
        self.dirpath = dirpath

    @staticmethod
    def _meta_name(kid: str) -> str:
        return hashlib.sha256(kid.encode("utf-8")).hexdigest()[:24] + ".json"

    def _meta_path(self, kid: str) -> str:
        return os.path.join(self.dirpath, self._meta_name(kid))

    # -- read ----------------------------------------------------------------
    def entries(self) -> Dict[str, Dict[str, Any]]:
        """``{keyId: meta}`` over every readable meta in the store
        (unreadable metas are skipped — debris, not errors)."""
        out: Dict[str, Dict[str, Any]] = {}
        if not os.path.isdir(self.dirpath):
            return out
        for fname in sorted(os.listdir(self.dirpath)):
            if not fname.endswith(".json"):
                continue
            try:
                with open(os.path.join(self.dirpath, fname)) as fh:
                    meta = json.load(fh)
            except (OSError, ValueError):
                continue
            kid = meta.get("keyId") if isinstance(meta, dict) else None
            if kid:
                out[kid] = meta
        return out

    def get(self, kid: str) -> Optional[Dict[str, Any]]:
        try:
            with open(self._meta_path(kid)) as fh:
                meta = json.load(fh)
        except (OSError, ValueError):
            return None
        return meta if isinstance(meta, dict) else None

    def read_blob(self, meta: Dict[str, Any]) -> bytes:
        """The entry's verified program bytes; :class:`StoreEntryError`
        on any integrity problem (the caller's typed-fallback trigger)."""
        fname = meta.get("file")
        if not fname:
            raise StoreEntryError("entry has no blob file recorded")
        path = os.path.join(self.dirpath, str(fname))
        try:
            with open(path, "rb") as fh:
                blob = fh.read()
        except OSError as e:
            raise StoreEntryError(f"blob unreadable: {e}") from e
        if len(blob) != int(meta.get("size", -1)):
            raise StoreEntryError(
                f"blob size {len(blob)} != recorded {meta.get('size')} "
                f"(truncated artifact)")
        sha = hashlib.sha256(blob).hexdigest()
        if sha != meta.get("sha256"):
            raise StoreEntryError(
                f"blob sha256 {sha[:12]}... != recorded "
                f"{str(meta.get('sha256'))[:12]}... (corrupt artifact)")
        return blob

    # -- write ---------------------------------------------------------------
    def put(self, key: Dict[str, Any], blob: bytes) -> Dict[str, Any]:
        """Write one entry (idempotent: same key + same bytes land on the
        same names; the atomic rename makes concurrent writers benign).
        ``key`` must carry fingerprint/bucket/jaxlib/deviceKind/component;
        returns the persisted meta."""
        from ..manifest import atomic_write_bytes
        os.makedirs(self.dirpath, exist_ok=True)
        sha = hashlib.sha256(blob).hexdigest()
        kid = key_id(key["fingerprint"], key["bucket"])
        blob_name = sha[:32] + ".bin"
        meta = {
            "keyId": kid,
            "fingerprint": str(key["fingerprint"]),
            "bucket": int(key["bucket"]),
            "jaxlib": str(key["jaxlib"]),
            "deviceKind": str(key["deviceKind"]),
            "component": str(key.get("component", "plan-segment")),
            "identity": str(key.get("identity", "")),
            "planIdent": key.get("planIdent"),
            "sha256": sha,
            "size": len(blob),
            "file": blob_name,
            "createdUnix": time.time(),
            "hits": 0,
        }
        blob_path = os.path.join(self.dirpath, blob_name)
        # content-addressing makes an existing file *normally* skippable,
        # but a corrupted/truncated file at that name breaks the
        # assumption — the self-heal re-export would silently keep the
        # bad bytes. Skip only a verified match; rewrite otherwise.
        existing_ok = False
        try:
            if os.path.getsize(blob_path) == len(blob):
                with open(blob_path, "rb") as fh:
                    existing_ok = (hashlib.sha256(fh.read()).hexdigest()
                                   == sha)
        except OSError:
            existing_ok = False
        if not existing_ok:
            atomic_write_bytes(blob_path, blob)
        atomic_write_bytes(
            self._meta_path(kid),
            json.dumps(meta, indent=1).encode("utf-8"))
        return meta

    def touch(self, kid: str) -> None:
        """Best-effort hit-count bump (once per process per program — the
        deserialize moment, never the dispatch hot path)."""
        meta = self.get(kid)
        if meta is None:
            return
        meta["hits"] = int(meta.get("hits", 0)) + 1
        try:
            from ..manifest import atomic_write_bytes
            atomic_write_bytes(
                self._meta_path(kid),
                json.dumps(meta, indent=1).encode("utf-8"))
        except OSError:
            pass  # a read-only store still serves hits

    # -- maintenance ---------------------------------------------------------
    def verify(self) -> List[str]:
        """``['<keyId>: <reason>', ...]`` integrity problems over every
        entry (empty = clean). ``cli.py programs`` exits non-zero on any."""
        problems: List[str] = []
        for kid, meta in sorted(self.entries().items()):
            try:
                self.read_blob(meta)
            except StoreEntryError as e:
                problems.append(f"{kid}: {e}")
        return problems

    def total_bytes(self) -> int:
        return sum(int(m.get("size", 0)) for m in self.entries().values())

    def gc(self, max_entries: Optional[int] = None,
           max_bytes: Optional[int] = None) -> List[str]:
        """Evict oldest-first past the bounds (``TG_AOT_STORE_MAX`` /
        ``TG_AOT_STORE_MAX_BYTES`` defaults); returns evicted keyIds.
        Orphaned blobs (no surviving meta references them) are removed
        with their last meta."""
        max_entries = (max_entries if max_entries is not None
                       else _env_int(STORE_MAX_ENV, DEFAULT_STORE_MAX))
        max_bytes = (max_bytes if max_bytes is not None
                     else _env_int(STORE_MAX_BYTES_ENV,
                                   DEFAULT_STORE_MAX_BYTES))
        entries = self.entries()
        ordered = sorted(entries.items(),
                         key=lambda kv: kv[1].get("createdUnix", 0.0))
        removed: List[str] = []
        total = sum(int(m.get("size", 0)) for _, m in ordered)
        while ordered and (len(ordered) > max(1, max_entries)
                           or total > max(1, max_bytes)):
            kid, meta = ordered.pop(0)
            total -= int(meta.get("size", 0))
            removed.append(kid)
            try:
                os.remove(self._meta_path(kid))
            except OSError:
                pass
            blob = meta.get("file")
            if blob and not any(m.get("file") == blob for _, m in ordered):
                try:
                    os.remove(os.path.join(self.dirpath, str(blob)))
                except OSError:
                    pass
        return removed


# ---------------------------------------------------------------------------
# Sessions: the read side registry.load() opens before any trace
# ---------------------------------------------------------------------------

class _Session:
    """One opened store: verified-at-lookup entries + the plan identity
    hashes the store claims to cover (the plan-build zero-record gate)."""

    def __init__(self, store: ProgramStore, entries: Dict[str, Dict[str, Any]],
                 plan_idents: Tuple[str, ...], origin: str):
        self.store = store
        self.entries = dict(entries)
        self.plan_idents = set(plan_idents)
        self.origin = origin
        #: (keyId) -> deserialized callable, one per process
        self.loaded: Dict[str, Callable] = {}


_LOCK = threading.Lock()
_SESSIONS: Dict[str, _Session] = {}
_CAPTURES: List["_Capture"] = []
_STATS: Dict[str, Any] = {"hits": {}, "misses": {}, "exports": 0,
                          "exportErrors": 0}


def _bump(kind: str, label: str, n: int = 1) -> None:
    with _LOCK:
        bucket = _STATS[kind]
        bucket[label] = bucket.get(label, 0) + n


def stats() -> Dict[str, Any]:
    """Process-local accounting (always on, like ``faults.fired_counts``):
    ``{"hits": {component: n}, "misses": {reason: n}, "exports": n,
    "exportErrors": n}`` plus totals."""
    with _LOCK:
        out = {"hits": dict(_STATS["hits"]),
               "misses": dict(_STATS["misses"]),
               "exports": _STATS["exports"],
               "exportErrors": _STATS["exportErrors"]}
    out["hitsTotal"] = sum(out["hits"].values())
    out["missesTotal"] = sum(out["misses"].values())
    return out


def snapshot() -> Dict[str, Any]:
    """The post-mortem bundle's ``aot`` section + ``cli doctor``'s
    "programs" block source."""
    with _LOCK:
        sessions = [{"origin": s.origin, "dir": s.store.dirpath,
                     "entries": len(s.entries),
                     "planIdents": len(s.plan_idents),
                     "loaded": len(s.loaded)}
                    for s in _SESSIONS.values()]
        captures = len(_CAPTURES)
    return {"enabled": aot_enabled(),
            "sessions": sessions, "captures": captures, "stats": stats()}


def sessions_active() -> bool:
    if _SESSIONS:
        return True
    return bool(os.environ.get(STORE_ENV)) and aot_enabled()


def active_captures() -> List[str]:
    with _LOCK:
        return [c.store.dirpath for c in _CAPTURES]


def close_sessions() -> None:
    with _LOCK:
        _SESSIONS.clear()


def reset() -> None:
    """Test isolation: drop sessions/captures/stats and any forced
    override (tests/conftest.py ``_no_programstore_leak``)."""
    global _enabled_override
    with _LOCK:
        _SESSIONS.clear()
        _CAPTURES.clear()
        _STATS["hits"] = {}
        _STATS["misses"] = {}
        _STATS["exports"] = 0
        _STATS["exportErrors"] = 0
    _enabled_override = None


def open_model_session(model_dir: str) -> Optional[_Session]:
    """Open (or refresh) the session over ``model_dir``'s manifest
    ``programs`` section — called by ``registry.load``/``swap`` BEFORE
    the warm pre-trace so every lookup can hit. Returns None (and opens
    nothing) when the store is disabled, or the manifest
    carries no (or a corrupt) ``programs`` section — all of which simply
    mean the existing trace path runs."""
    if not aot_enabled():
        return None
    try:
        from ..manifest import CheckpointManifest
        from ..persistence import FORMAT_VERSION
        manifest, err = CheckpointManifest.load(model_dir, FORMAT_VERSION)
        if err is not None:
            return None
        section = manifest.programs
        entries = section.get("entries")
        if not isinstance(entries, dict) or not entries:
            return None
        idents = tuple(str(x) for x in section.get("planIdents", ())
                       if isinstance(x, str))
        store = ProgramStore(os.path.join(model_dir, PROGRAMS_DIR))
        sess = _Session(store, {str(k): dict(v)
                                for k, v in entries.items()
                                if isinstance(v, dict)},
                        idents, origin=model_dir)
        with _LOCK:
            _SESSIONS[store.dirpath] = sess
        from ..observability import blackbox as _blackbox
        _blackbox.record("aot.session", dir=model_dir,
                         entries=len(sess.entries))
        return sess
    except Exception as e:  # a bad store must never fail a model load
        logger.warning("AOT session open failed for %s (%s: %s); "
                       "serving will trace", model_dir,
                       type(e).__name__, e)
        return None


def open_env_session() -> Optional[_Session]:
    """The cross-model store pointed at by ``TG_AOT_STORE`` (sweep
    programs at train time live here; opened lazily on first use, entries
    read from the store metas — there is no manifest for it)."""
    d = os.environ.get(STORE_ENV)
    if not d or not aot_enabled():
        return None
    store = ProgramStore(d)
    with _LOCK:
        sess = _SESSIONS.get(store.dirpath)
    if sess is not None:
        return sess
    sess = _Session(store, store.entries(), (), origin="env")
    with _LOCK:
        _SESSIONS[store.dirpath] = sess
    return sess


# ---------------------------------------------------------------------------
# The read path: lookup + the fallback ladder
# ---------------------------------------------------------------------------

def _record_miss(kid: str, component: str, reason: str,
                 ledger_key: Optional[str], detail: Dict[str, Any],
                 fault: bool) -> None:
    """One rung of the fallback ladder: count it, classify the build the
    caller is about to pay as ``aot-miss``, and — for genuine artifact
    faults (mismatch / corrupt / deserialize / injected) — leave the
    typed FaultLog ``aot_fallback`` record the chaos oracles assert on.
    A plain ``absent`` miss is the populate path, not a fault."""
    _bump("misses", reason)
    from ..observability import blackbox as _blackbox
    from ..observability import ledger as _ledger
    from ..observability import metrics as _obs_metrics
    _obs_metrics.inc_counter(
        "tg_aot_miss_total", reason=reason, component=component,
        help="AOT program-store misses by reason (docs/serving.md "
        "'AOT cold start & the program store')")
    _ledger.note_aot_miss(ledger_key or kid, f"aot-miss ({reason})")
    _blackbox.record("aot.miss", key=kid, component=component,
                     reason=reason)
    if fault:
        from ..robustness.policy import FaultLog, FaultReport
        FaultLog.record(FaultReport(
            site="aot.load", kind="aot_fallback",
            detail={"key": kid, "component": component, "reason": reason,
                    **detail}))
        logger.warning("AOT artifact %s unusable (%s); falling back to "
                       "the trace path", kid, reason)


def lookup(fingerprint: str, bucket: int, component: str = "plan-segment",
           ledger_key: Optional[str] = None) -> Optional[Callable]:
    """Resolve one program from the open sessions. Returns the
    deserialized callable (bit-identical dispatch to the traced program)
    or None — in which case the caller traces, and the resulting ledger
    build (recorded under ``ledger_key``) classifies as ``aot-miss``
    when any session was active. Never raises on a request path."""
    if not aot_enabled():
        return None
    open_env_session()
    with _LOCK:
        sessions = list(_SESSIONS.values())
    if not sessions:
        return None
    kid = key_id(fingerprint, bucket)
    entry = None
    sess = None
    for s in sessions:
        cached = s.loaded.get(kid)
        if cached is not None:
            return cached
        e = s.entries.get(kid)
        if e is not None and entry is None:
            entry, sess = e, s
    if entry is None:
        _record_miss(kid, component, "absent", ledger_key,
                     {}, fault=False)
        return None
    try:
        # deterministic chaos entry: models a corrupt / truncated /
        # stale-jaxlib artifact discovered at load (docs/robustness.md)
        from ..robustness import faults
        faults.inject("aot.load", key=kid)
        want_jaxlib = _aot.current_jaxlib()
        if str(entry.get("jaxlib")) != want_jaxlib:
            _record_miss(kid, component, "jaxlib-mismatch", ledger_key,
                         {"entry": entry.get("jaxlib"),
                          "current": want_jaxlib}, fault=True)
            return None
        want_device = _aot.current_device_kind()
        if str(entry.get("deviceKind")) != want_device:
            _record_miss(kid, component, "device-kind-mismatch",
                         ledger_key,
                         {"entry": entry.get("deviceKind"),
                          "current": want_device}, fault=True)
            return None
        try:
            blob = sess.store.read_blob(entry)
        except StoreEntryError as e:
            _record_miss(kid, component, "corrupt", ledger_key,
                         {"error": str(e)[:200]}, fault=True)
            return None
        fn = _aot.load_callable(blob)
    except Exception as e:
        # injected faults land here too: any throw on the load path is
        # one typed fallback, never a request error
        _record_miss(kid, component, "deserialize-error", ledger_key,
                     {"error": f"{type(e).__name__}: {e}"[:200]},
                     fault=True)
        return None
    sess.loaded[kid] = fn
    _bump("hits", component)
    from ..observability import blackbox as _blackbox
    from ..observability import metrics as _obs_metrics
    _obs_metrics.inc_counter(
        "tg_aot_hits_total", component=component,
        help="AOT program-store hits (deserialized programs dispatched "
        "instead of traced; docs/serving.md)")
    _blackbox.record("aot.hit", key=kid, component=component,
                     bytes=entry.get("size"))
    sess.store.touch(kid)
    return fn


def plan_covered(plan_ident: str) -> bool:
    """True when any open session claims this plan identity — the plan's
    assembly is then an AOT hit, not a ledger build (plan.get_plan)."""
    if not aot_enabled():
        return False
    with _LOCK:
        return any(plan_ident in s.plan_idents for s in _SESSIONS.values())


def record_plan_hit(plan_ident: str) -> None:
    _bump("hits", "plan")
    from ..observability import blackbox as _blackbox
    from ..observability import metrics as _obs_metrics
    _obs_metrics.inc_counter(
        "tg_aot_hits_total", component="plan",
        help="AOT program-store hits (deserialized programs dispatched "
        "instead of traced; docs/serving.md)")
    _blackbox.record("aot.hit", key=plan_ident, component="plan")


def note_plan_miss(ledger_key: str) -> None:
    """A plan build with sessions active but no coverage: classify it
    ``aot-miss`` (plan.get_plan calls this right before record_build)."""
    _record_miss(ledger_key, "plan", "absent", ledger_key, {},
                 fault=False)


# ---------------------------------------------------------------------------
# The write path: capture scopes + offers
# ---------------------------------------------------------------------------

class _Capture:
    """One populate scope: offers export into ``store`` and, when the
    store lives inside a model dir, flush() commits the entries into the
    model's MANIFEST ``programs`` section (atomic rewrite)."""

    def __init__(self, store: ProgramStore, manifest_dir: Optional[str]):
        self.store = store
        self.manifest_dir = manifest_dir
        self.pending: Dict[str, Dict[str, Any]] = {}
        self.plan_idents: List[str] = []

    def flush(self) -> int:
        """Commit pending entries to the manifest + bound the store.
        Never raises — population is strictly best-effort."""
        try:
            self.store.gc()
            if self.manifest_dir is None or not self.pending:
                return len(self.pending)
            from ..manifest import CheckpointManifest
            from ..persistence import FORMAT_VERSION
            manifest, err = CheckpointManifest.load(self.manifest_dir,
                                                    FORMAT_VERSION)
            if err is not None:
                return 0
            section = manifest.programs if isinstance(
                manifest.programs, dict) else {}
            entries = dict(section.get("entries", {})
                           if isinstance(section.get("entries"), dict)
                           else {})
            entries.update(self.pending)
            idents = [str(x) for x in section.get("planIdents", ())
                      if isinstance(x, str)]
            for pi in self.plan_idents:
                if pi not in idents:
                    idents.append(pi)
            manifest.programs = {
                "version": PROGRAMS_VERSION,
                "jaxlib": _aot.current_jaxlib(),
                "deviceKind": _aot.current_device_kind(),
                "entries": entries,
                "planIdents": idents,
            }
            manifest.save()
            return len(self.pending)
        except Exception as e:
            logger.warning("AOT capture flush failed for %s (%s: %s)",
                           self.store.dirpath, type(e).__name__, e)
            return 0


@contextlib.contextmanager
def capture(model_dir: str):
    """Populate scope over ``model_dir``: traced first-bucket dispatches
    inside the block are exported into ``<model_dir>/programs/`` and
    committed into the manifest ``programs`` section on exit. No-op
    context when the store is disabled."""
    if not aot_enabled():
        yield None
        return
    cap = _Capture(ProgramStore(os.path.join(model_dir, PROGRAMS_DIR)),
                   manifest_dir=model_dir)
    with _LOCK:
        _CAPTURES.append(cap)
    try:
        yield cap
    finally:
        with _LOCK:
            if cap in _CAPTURES:
                _CAPTURES.remove(cap)
        cap.flush()


def offer_segment(fingerprint: str, bucket: int, jitted_fn: Callable,
                  args: Tuple[Any, ...], component: str = "plan-segment",
                  identity: str = "", plan_ident: Optional[str] = None
                  ) -> int:
    """A dispatch site just *traced* a program the store did not have:
    export + persist it into every active capture scope (and the
    ``TG_AOT_STORE`` cross-model store when configured). One flag check
    when nothing is active; export failures are counted, never raised.
    Returns the number of stores written."""
    kid = key_id(fingerprint, bucket)
    with _LOCK:
        # a capture that already holds this key skips the (re-)export;
        # the env store is refreshed (overwriting heals stale-jaxlib
        # entries the lookup just refused)
        targets: List[Tuple[ProgramStore, Optional[_Capture]]] = [
            (c.store, c) for c in _CAPTURES if kid not in c.pending]
    env_sess = open_env_session() if os.environ.get(STORE_ENV) else None
    if env_sess is not None:
        targets.append((env_sess.store, None))
    if not targets or not aot_enabled():
        return 0
    key = {"fingerprint": fingerprint, "bucket": int(bucket),
           "jaxlib": _aot.current_jaxlib(),
           "deviceKind": _aot.current_device_kind(),
           "component": component, "identity": identity,
           "planIdent": plan_ident}
    try:
        blob = _aot.export_bytes(jitted_fn, args)
    except Exception as e:
        with _LOCK:
            _STATS["exportErrors"] += 1
        logger.warning("AOT export failed for %s (%s: %s); the program "
                       "stays process-local", kid, type(e).__name__, e)
        return 0
    written = 0
    for store, cap in targets:
        try:
            meta = store.put(key, blob)
        except OSError as e:
            logger.warning("AOT store write failed in %s (%s: %s)",
                           store.dirpath, type(e).__name__, e)
            continue
        written += 1
        if cap is not None:
            cap.pending[kid] = meta
            if plan_ident and plan_ident not in cap.plan_idents:
                cap.plan_idents.append(plan_ident)
        else:
            env_sess.entries[kid] = meta
    if written:
        with _LOCK:
            _STATS["exports"] += 1
        from ..observability import blackbox as _blackbox
        _blackbox.record("aot.export", key=kid, component=component,
                         bytes=len(blob), stores=written)
    return written


def offer_plan_ident(plan_ident: str) -> None:
    """Record a plan identity as covered in every active capture (called
    by plan.get_plan when a capture scope is active, so a populated
    manifest can suppress the plan-build ledger record next load)."""
    with _LOCK:
        for cap in _CAPTURES:
            if plan_ident not in cap.plan_idents:
                cap.plan_idents.append(plan_ident)


# ---------------------------------------------------------------------------
# Save-time population (persistence.save_model)
# ---------------------------------------------------------------------------

def serve_plan_for(model, rows: int):
    """The model's serve-path transform plan (built or fetched from the
    plan LRU with exactly the key ``compiled_score_function`` uses), or
    None when planning is off / infeasible."""
    from .. import plan as _plan
    from ..local.scoring import serve_table_builder
    table = serve_table_builder(model)([{} for _ in range(max(1, rows))])
    return _plan.get_plan(
        model.stages, table, keep_intermediates=False,
        extra_keep=[f.name for f in model.result_features], cat="score")


def populate_for_save(model, path: str, rows: Optional[int] = None) -> int:
    """Export the model's serve-path programs into ``<path>/programs/``
    + the manifest ``programs`` section at *save* time, so a fresh
    process's ``registry.load`` deserializes instead of tracing
    (``save_model`` calls this after the manifest commits; TG_AOT_SAVE=0
    defers population to the first warm load). The export reconstructs
    each segment's traced avals from the plan's zero-row probe — no
    dispatch, no device work. Returns segments exported; never raises."""
    if not save_populate_enabled():
        return 0
    try:
        from .. import plan as _plan
        from ..observability import ledger as _ledger
        from ..serving.warmup import _warm_rows
        with _ledger.subsystem_scope("serve"):
            p = serve_plan_for(model, _warm_rows(rows))
        if p is None:
            return 0
        with capture(path):
            return _plan.export_plan_programs(p)
    except Exception as e:
        logger.warning("AOT save-time populate failed for %s (%s: %s); "
                       "the first warm load will populate instead",
                       path, type(e).__name__, e)
        return 0
