"""Streaming input engine: parallel chunk preparation + cached replay.

While the consumer folds chunk N, a pool of ``TG_STREAM_WORKERS``
producer threads (default min(4, cores); ``1`` reproduces the round-7
serial feed thread-for-thread) prepares
the chunks behind it. Each worker *claims* the next schedule index —
gated on the same slot semaphore as always, so device residency stays
O(prefetch + 1 chunks), never O(dataset) — then runs read (chaos site
``stream.read``) + upstream host-side transform for its claim, while a
single ordered **committer** thread performs the packed host→device
uploads (``FeatureTable.to_device()``; chaos sites ``stream.upload`` /
``oom.stream``) and queue puts strictly in schedule order. Claims are
serialized under one lock, so fault-injection counters, chunk delivery
order, monoid fold results, and checkpoint/resume semantics are all
bit-identical to the serial feed at ANY worker count.

A :class:`~.cache.ChunkCache` (``TG_STREAM_CACHE_BYTES`` host LRU +
optional sha256-verified ``TG_STREAM_CACHE_DIR`` disk tier) short-cuts
the whole prep: a transformed chunk is a pure function of (source
fingerprint × chunk index × fitted-transform identity × chunk rows), so
repeat passes replay packed host blocks instead of re-reading and
re-transforming — and skip the upload entirely (every in-tree fold
consumes host numpy views, so a cache hit is byte-equal input with zero
h2d traffic; chaos site ``stream.cache`` = corrupt/evicted entry, which
falls back to a typed bit-equal recompute).

Accounting (:class:`FeedStats`) is what a stream run reports:
uploaded bytes, per-stage seconds (read / transform / upload — also
observed as ``tg_stream_stage_seconds{stage=...}``), cache hits/misses,
peak concurrently-resident device bytes (the O(chunk) claim, asserted
in tests), and the overlap fraction — the share of consumer wall-clock
NOT stalled waiting on the feed.

Error contract: any exception in a worker or the committer —
``SimulatedPreemption`` (a BaseException, modeling a kill mid-read/
mid-upload) included — is forwarded through the queue in schedule order
(chunks claimed before the failing one still deliver; the FIRST error
in schedule order wins) and re-raises in the consumer thread, so a
streamed ``train()`` dies exactly like an in-core one would, with the
last committed chunk checkpoint intact. Resource exhaustion
(``oom.stream``, or a real ``RESOURCE_EXHAUSTED`` from the packed
upload) forwards the same way; the trainer catches it, drains this pool
(``close()``), and re-chunks at half the row budget
(robustness/resources.py).

Hang contract: every worker beats its own watchdog heart
(robustness/watchdog.py, ``TG_WATCHDOG_S``), as does the committer. A
thread wedged inside a dead reader, a hung transform, or a stuck upload
stops beating; the stall is recorded (``thread_stalled`` +
``tg_watchdog_stalls_total``) and the feed ABORTS — the queue is
drained and the typed error put in its place, so a consumer blocked on
an empty OR full queue wakes deterministically instead of spinning.
``close()`` likewise records (never silently discards) any thread that
outlives its join timeout.
"""
from __future__ import annotations

import os
import queue
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Any, Iterable, Iterator, List, Optional, Sequence, Union

import numpy as np

from ..manifest import sentinel_phase as _sentinel_phase
from ..observability import blackbox as _blackbox
from ..observability import devicemem as _devicemem
from ..observability import metrics as _obs_metrics
from ..robustness import faults
from ..robustness import watchdog as _watchdog
from ..robustness.watchdog import WatchdogStallError
from ..table import DEVICE_KINDS, FeatureTable
from .cache import ChunkCache, chunk_cache_key, pack_table
from .source import Chunk, ChunkSource

PREFETCH_ENV = "TG_STREAM_PREFETCH"
DEFAULT_PREFETCH = 1
WORKERS_ENV = "TG_STREAM_WORKERS"

#: live feeds (weak) — the conftest no-leak fixture asserts none survive
_LIVE: "weakref.WeakSet[DeviceFeed]" = weakref.WeakSet()


def live_feeds() -> List["DeviceFeed"]:
    return [f for f in list(_LIVE) if not f.closed]


def env_prefetch(prefetch: Optional[int] = None) -> int:
    if prefetch is not None:
        return max(1, int(prefetch))
    try:
        return max(1, int(os.environ.get(PREFETCH_ENV, "")
                          or DEFAULT_PREFETCH))
    except ValueError:
        return DEFAULT_PREFETCH


def env_workers(workers: Optional[int] = None) -> int:
    """Producer pool size: TG_STREAM_WORKERS, default min(4, cores).
    Note that concurrency is additionally gated by the slot semaphore —
    at most ``prefetch`` chunks are ever in flight, so real parallel
    prep needs ``TG_STREAM_PREFETCH >= workers`` (docs/streaming.md
    "Input engine")."""
    if workers is not None:
        return max(1, int(workers))
    try:
        raw = os.environ.get(WORKERS_ENV, "")
        if raw:
            return max(1, int(raw))
    except ValueError:
        pass
    return max(1, min(4, os.cpu_count() or 1))


def device_bytes(table: FeatureTable) -> int:
    """Bytes of device-kind column storage a chunk pins while resident.
    Masks charge their FULL element count × itemsize — a (n, d) validity
    mask is n·d bytes resident, not n."""
    total = 0
    for name in table.column_names:
        col = table[name]
        if col.kind not in DEVICE_KINDS:
            continue
        vals = col.values
        total += int(np.dtype(getattr(vals, "dtype", np.float32)).itemsize
                     * int(np.prod(np.shape(vals))))
        if col.mask is not None:
            m = col.mask
            total += int(np.dtype(getattr(m, "dtype", np.bool_)).itemsize
                         * int(np.prod(np.shape(m))))
    return total


@dataclass
class FeedStats:
    chunks: int = 0
    rows: int = 0
    upload_bytes: int = 0
    max_chunk_bytes: int = 0
    peak_device_bytes: int = 0
    peak_resident_chunks: int = 0
    read_seconds: float = 0.0
    transform_seconds: float = 0.0
    upload_seconds: float = 0.0
    wait_seconds: float = 0.0
    wall_seconds: float = 0.0
    cache_hits: int = 0
    cache_misses: int = 0

    def overlap_fraction(self) -> float:
        """Share of consumer wall-clock NOT stalled on the feed: 1.0 means
        read+transform+upload hid entirely behind fold compute."""
        if self.wall_seconds <= 0:
            return 0.0
        return max(0.0, 1.0 - self.wait_seconds / self.wall_seconds)

    def merge(self, other: "FeedStats") -> "FeedStats":
        self.chunks += other.chunks
        self.rows += other.rows
        self.upload_bytes += other.upload_bytes
        self.max_chunk_bytes = max(self.max_chunk_bytes,
                                   other.max_chunk_bytes)
        self.peak_device_bytes = max(self.peak_device_bytes,
                                     other.peak_device_bytes)
        self.peak_resident_chunks = max(self.peak_resident_chunks,
                                        other.peak_resident_chunks)
        self.read_seconds += other.read_seconds
        self.transform_seconds += other.transform_seconds
        self.upload_seconds += other.upload_seconds
        self.wait_seconds += other.wait_seconds
        self.wall_seconds += other.wall_seconds
        self.cache_hits += other.cache_hits
        self.cache_misses += other.cache_misses
        return self

    def to_json(self) -> dict:
        return {
            "chunks": self.chunks, "rows": self.rows,
            "uploadBytes": self.upload_bytes,
            "maxChunkBytes": self.max_chunk_bytes,
            "peakDeviceBytes": self.peak_device_bytes,
            "peakResidentChunks": self.peak_resident_chunks,
            "readSeconds": round(self.read_seconds, 4),
            "transformSeconds": round(self.transform_seconds, 4),
            "uploadSeconds": round(self.upload_seconds, 4),
            "waitSeconds": round(self.wait_seconds, 4),
            "overlapFraction": round(self.overlap_fraction(), 4),
            "cacheHits": self.cache_hits,
            "cacheMisses": self.cache_misses,
        }


class DeviceFeed:
    """Iterate device-resident chunks prepared by the input engine.

    ``chunks`` is either a :class:`~.source.ChunkSource` (engine mode —
    enables the transformed-chunk cache and, for random-access sources,
    parallel reads) or any iterable of :class:`Chunk` (legacy mode:
    reads stay sequential under the claim lock, transforms still
    parallelize). Usage (always close — ``with`` or the trainer's
    finally)::

        with DeviceFeed(source, transforms=models, start=k) as feed:
            for chunk in feed:
                ...fold chunk.table...
    """

    _SENTINEL = object()

    def __init__(self, chunks: Union[ChunkSource, Iterable[Chunk]],
                 transforms: Sequence[Any] = (),
                 prefetch: Optional[int] = None,
                 to_device: bool = True,
                 workers: Optional[int] = None,
                 cache: Optional[ChunkCache] = None,
                 cache_ident: str = "",
                 start: int = 0):
        if isinstance(chunks, ChunkSource):
            self._source: Optional[ChunkSource] = chunks
            self._start = int(start)
            self._it: Optional[Iterator[Chunk]] = None
            self._it_pos = self._start
        else:
            self._source = None
            self._start = 0
            self._it = iter(chunks)
            self._it_pos = 0
        self._transforms = list(transforms)
        self.workers = env_workers(workers)
        self.prefetch = env_prefetch(prefetch)
        self._to_device = to_device
        # the cache needs index-addressed claims — source mode only
        self._cache = cache if self._source is not None else None
        self._cache_ident = cache_ident
        if self._cache is not None:
            # bind the owning run's fault log now, on the consumer thread
            # — cache fallbacks recorded from producer threads would
            # otherwise miss the ambient (per-thread) log
            from ..robustness.policy import FaultLog
            self._cache.bind_log(FaultLog.current())
        self._random_access = bool(getattr(self._source, "random_access",
                                           False))
        self.stats = FeedStats()
        self._q: "queue.Queue" = queue.Queue(maxsize=self.prefetch + 1)
        #: production gate: the pool may hold at most ``prefetch`` chunks
        #: beyond the one being consumed — a worker acquires a slot BEFORE
        #: claiming an index (so before any read/transform/cache fetch),
        #: the consumer releases one per take. This is what keeps
        #: residency O(prefetch + 1) regardless of the worker count: with
        #: W workers but P slots, at most min(W, P) preps run concurrently.
        self._slots = threading.Semaphore(self.prefetch)
        self._stop = threading.Event()
        self._resident = 0           # device bytes of yielded-but-live chunks
        self._resident_chunks = 0
        self._lock = threading.Lock()
        self._prev_bytes = 0
        self.closed = False
        self._stall_error: Optional[BaseException] = None
        self._t0 = time.perf_counter()
        # claim/commit plane: workers claim monotonically increasing
        # sequence numbers under _claim_lock (seq s ↔ schedule index
        # start+s in source mode) and deposit results keyed by seq;
        # the committer consumes them strictly in seq order.
        self._claim_lock = threading.Lock()
        self._next_seq = 0
        self._ready = threading.Condition()
        self._results: dict = {}
        self._halt_seq: Optional[int] = None   # first end/error seq
        # flight-recorder correlation: captured HERE on the constructing
        # (consumer/train) thread — contextvars do not cross into the
        # producer threads, so they stamp their events with the owning
        # run's id explicitly (observability/blackbox.py)
        self._corr = _blackbox.current_correlation()
        # hang watchdog: every pool thread beats its own heart; a wedge
        # (dead reader, hung transform, stuck upload) stops that thread's
        # beats → the feed aborts with a typed error instead of hanging
        # the consumer
        self._heart = _watchdog.register(
            "tg-stream-feed", kind="stream.producer",
            on_stall=self._on_watchdog_stall)
        self._worker_hearts = [
            _watchdog.register(f"tg-stream-w{i}", kind="stream.producer",
                               on_stall=self._on_watchdog_stall)
            for i in range(self.workers)]
        self._thread = threading.Thread(
            target=self._commit_loop, name="tg-stream-feed", daemon=True)
        self._workers = [
            threading.Thread(target=self._work, args=(i,),
                             name=f"tg-stream-w{i}", daemon=True)
            for i in range(self.workers)]
        _LIVE.add(self)
        self._thread.start()
        for t in self._workers:
            t.start()

    def _on_watchdog_stall(self, heart, waited: float) -> None:
        """Watchdog stall response (scanner thread): abort the feed. The
        wedged thread cannot be killed, but the consumer must not wait on
        it forever — drain the queue and put the typed error in its
        place, so a consumer blocked on EITHER an empty or a full queue
        wakes deterministically (a bare ``put_nowait`` could drop on a
        full queue, leaving the consumer to spin until it polled
        ``_stall_error``)."""
        err = WatchdogStallError(
            f"stream feed producer stalled {waited:.1f}s "
            f"(> TG_WATCHDOG_S); aborting the feed")
        self._stall_error = err
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        try:  # wake a consumer blocked on the (now drained) queue
            self._q.put_nowait((self._SENTINEL, err))
        except queue.Full:
            pass

    # -- claim plane (workers) ------------------------------------------------
    def _key(self, index: int) -> str:
        return chunk_cache_key(self._source.fingerprint(), index,
                               self._cache_ident, self._source.chunk_rows)

    def _read_locked(self, index: int) -> Chunk:
        """Sequential read at ``index`` (claim lock held). After cache
        hits skipped ahead, the shared iterator reopens at the miss."""
        if self._it is None or self._it_pos != index:
            self._it = iter(self._source.chunks(index))
            self._it_pos = index
        chunk = next(self._it)
        self._it_pos = index + 1
        return chunk

    def _claim(self):
        """Claim the next schedule index. Returns ``(seq, index, chunk,
        packed)`` — ``packed`` set on a cache hit, ``chunk`` set when the
        read had to happen under the lock (sequential sources), both
        ``None`` for a random-access read the worker performs outside the
        lock — or ``None`` when there is nothing left to claim."""
        with self._claim_lock:
            if self._stop.is_set():
                return None
            with self._ready:
                if (self._halt_seq is not None
                        and self._next_seq >= self._halt_seq):
                    return None
            seq = self._next_seq
            self._next_seq += 1
            index = self._start + seq if self._source is not None else seq
            try:
                # ordered by claim → fault counters are schedule-
                # deterministic at any worker count
                faults.inject("stream.read")
                if self._cache is not None:
                    t0 = time.perf_counter()
                    packed = self._cache.get(self._key(index))
                    if packed is not None:
                        self._add_stage("read", time.perf_counter() - t0)
                        if not self._random_access:
                            self._it = None  # iterator is now behind
                        return seq, index, None, packed
                if self._random_access:
                    if index >= self._source.num_chunks:
                        self._finish(seq, ("end", None, False))
                        return None
                    return seq, index, None, None
                t0 = time.perf_counter()
                chunk = self._read_locked(index)
                self._add_stage("read", time.perf_counter() - t0)
                return seq, index, chunk, None
            except StopIteration:
                self._finish(seq, ("end", None, False))
                return None
            except BaseException as e:  # noqa: BLE001 — preemption forwards
                self._finish(seq, ("err", e, False))
                return None

    def _finish(self, seq: int, result) -> None:
        with self._ready:
            self._results[seq] = result
            if result[0] != "ok" and (self._halt_seq is None
                                      or seq < self._halt_seq):
                # first end/error in SCHEDULE order wins: chunks claimed
                # before it still deliver, later claims never start
                self._halt_seq = seq
            self._ready.notify_all()

    def _add_stage(self, stage: str, dt: float) -> None:
        with self._lock:
            if stage == "read":
                self.stats.read_seconds += dt
            elif stage == "transform":
                self.stats.transform_seconds += dt
            else:
                self.stats.upload_seconds += dt
        if _obs_metrics.metrics_enabled():
            _obs_metrics.observe(
                "tg_stream_stage_seconds", dt, stage=stage,
                help="seconds per chunk per input-engine stage")

    def _work(self, wid: int) -> None:
        heart = self._worker_hearts[wid]
        try:
            while not self._stop.is_set():
                heart.beat()
                if not self._slots.acquire(timeout=0.1):
                    continue
                claim = self._claim()
                if claim is None:
                    self._slots.release()
                    return
                seq, index, chunk, packed = claim
                try:
                    if packed is not None:
                        table = packed.unpack()
                        self._finish(seq, ("ok", Chunk(
                            index, self._source.chunk_id(index), table),
                            True))
                        continue
                    if chunk is None:  # random-access read, outside the lock
                        t0 = time.perf_counter()
                        chunk = self._source.read_chunk(index)
                        self._add_stage("read", time.perf_counter() - t0)
                    t0 = time.perf_counter()
                    table = chunk.table
                    for model in self._transforms:
                        table = model.transform(table)
                    if self._cache is not None:
                        self._cache.put(self._key(chunk.index),
                                        pack_table(table))
                    self._add_stage("transform", time.perf_counter() - t0)
                    self._finish(seq, ("ok", Chunk(
                        chunk.index, chunk.chunk_id, table), False))
                except BaseException as e:  # noqa: BLE001
                    self._finish(seq, ("err", e, False))
                    return
        finally:
            heart.close()

    # -- commit plane (single ordered committer) ------------------------------
    def _commit_loop(self) -> None:
        expected = 0
        try:
            while not self._stop.is_set():
                with self._ready:
                    while (expected not in self._results
                           and not self._stop.is_set()):
                        self._heart.beat()
                        self._ready.wait(timeout=0.1)
                    if self._stop.is_set():
                        return
                    kind, payload, from_cache = self._results.pop(expected)
                expected += 1
                self._heart.beat()
                if kind == "end":
                    self._put((self._SENTINEL, None))
                    return
                if kind == "err":
                    self._put((self._SENTINEL, payload))
                    return
                chunk = payload
                t0 = time.perf_counter()
                # crash evidence: an OOM-killed process dies right here —
                # the run sentinel's phase names the packed upload
                # (module-global ambient, so this committer thread sees
                # the trainer's sentinel)
                _sentinel_phase("device_upload")
                faults.inject("stream.upload")
                # chaos: a RESOURCE_EXHAUSTED here models the packed chunk
                # upload not fitting on the device — it forwards through
                # the queue and the trainer halves the chunk row budget
                faults.inject("oom.stream")
                table = chunk.table
                if self._to_device and not from_cache:
                    table = table.to_device()
                nbytes = device_bytes(table)
                self._add_stage("upload", time.perf_counter() - t0)
                with self._lock:
                    if from_cache:
                        # a hit is delivered as host views of the cached
                        # packed blocks — nothing crossed the h2d link
                        self.stats.cache_hits += 1
                    else:
                        self.stats.upload_bytes += nbytes
                        if self._cache is not None:
                            self.stats.cache_misses += 1
                    self._resident += nbytes
                    self._resident_chunks += 1
                    self.stats.max_chunk_bytes = max(
                        self.stats.max_chunk_bytes, nbytes)
                    self.stats.peak_device_bytes = max(
                        self.stats.peak_device_bytes, self._resident)
                    self.stats.peak_resident_chunks = max(
                        self.stats.peak_resident_chunks,
                        self._resident_chunks)
                _blackbox.record("stream.upload", corr=self._corr,
                                 chunk=chunk.index, bytes=nbytes,
                                 fromCache=from_cache)
                if not from_cache:
                    # device-memory observatory: the packed upload's
                    # shape-derived bytes (the chunk-residency
                    # prediction) + measured live-buffer peak where the
                    # backend reports it
                    _devicemem.record_dispatch("stream", nbytes,
                                               rows=chunk.rows)
                    _devicemem.sample_measured("stream")
                self._put((Chunk(chunk.index, chunk.chunk_id, table),
                           nbytes))
        except BaseException as e:  # noqa: BLE001 — preemption must forward
            self._put((self._SENTINEL, e))
        finally:
            # a finished committer has nothing left to stall on; keeping
            # the heart open would flag a slow CONSUMER as a feed stall
            self._heart.close()

    def _put(self, item) -> None:
        while not self._stop.is_set():
            self._heart.beat()
            try:
                self._q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    # -- consumer -------------------------------------------------------------
    def __iter__(self) -> Iterator[Chunk]:
        return self

    def __next__(self) -> Chunk:
        self._release_prev()
        t0 = time.perf_counter()
        while True:
            try:
                item, extra = self._q.get(timeout=0.1)
                break
            except queue.Empty:
                if self._stall_error is not None:
                    # watchdog abort: the pool is wedged — fail the
                    # consumer with the typed error instead of waiting
                    err = self._stall_error
                    self.close()
                    raise err
                if not self._thread.is_alive() and self._q.empty():
                    raise RuntimeError(
                        "stream feed producer died without a sentinel")
        self.stats.wait_seconds += time.perf_counter() - t0
        self._slots.release()
        if item is self._SENTINEL:
            self.stats.wall_seconds = time.perf_counter() - self._t0
            if extra is not None:
                self.close()
                raise extra
            self.close()
            raise StopIteration
        self._prev_bytes = extra
        self.stats.chunks += 1
        self.stats.rows += item.rows
        return item

    def _release_prev(self) -> None:
        if self._prev_bytes:
            with self._lock:
                self._resident -= self._prev_bytes
                self._resident_chunks -= 1
            self._prev_bytes = 0

    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        self._stop.set()
        with self._ready:
            self._ready.notify_all()
        # drain so a blocked committer put() unblocks and exits
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
        for t in self._workers:
            t.join(timeout=2.0)
        for t in [self._thread] + self._workers:
            if t.is_alive():
                # never discard a still-alive pool thread silently: record
                # the stall (thread_stalled FaultLog +
                # tg_watchdog_stalls_total) so it surfaces in
                # summary()["faults"]["threadStalls"]
                _watchdog.report_thread_stalled(
                    site="stream.close", thread_name=t.name,
                    waited_s=5.0 if t is self._thread else 2.0)
        self._heart.close()
        for h in self._worker_hearts:
            h.close()
        if self.stats.wall_seconds == 0.0:
            self.stats.wall_seconds = time.perf_counter() - self._t0
        if _obs_metrics.metrics_enabled():
            _obs_metrics.inc_counter(
                "tg_stream_chunks_total", float(self.stats.chunks),
                help="chunks consumed through the streaming device feed")
            _obs_metrics.inc_counter(
                "tg_stream_rows_total", float(self.stats.rows),
                help="rows consumed through the streaming device feed")
            _obs_metrics.observe(
                "tg_stream_wait_seconds", self.stats.wait_seconds,
                help="consumer seconds stalled waiting on the chunk feed")

    def __enter__(self) -> "DeviceFeed":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
