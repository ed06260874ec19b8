"""Transformed-chunk cache — skip read+transform+pack on repeat passes.

A transformed chunk is a pure function of four identities::

    (source fingerprint) x (chunk index) x (fitted-transform identity) x
    (chunk row budget)

so once the first pass over a :class:`~.source.ChunkSource` has paid
read + upstream transform + pack for chunk ``i``, every later pass with
the same upstream models can replay the exact bytes instead of redoing
the work. The streaming GBT makes ``1 + trees x (depth + 1)`` passes over
the identical transformed stream — this cache is what turns that
amplification from "re-prepare everything" into "re-read host blocks".

Two bounded tiers:

* **host tier** — packed per-dtype blocks (the same layout
  ``FeatureTable.to_device`` transfers, so accounting and byte-equality
  checks are exact), LRU under ``TG_STREAM_CACHE_BYTES`` (default 256
  MiB; ``0`` disables);
* **disk tier** (optional) — one npz per chunk under
  ``TG_STREAM_CACHE_DIR``, written atomically and sha256-verified on
  every read exactly like manifest files (manifest.atomic_write_bytes),
  so entries survive a kill and a ``resume=True`` train skips the prep
  its predecessor already paid for.

Safety contract: the cache can only ever be *slower*, never *wrong*. A
miss, an evicted entry, a sha mismatch, a header/key mismatch, or the
``stream.cache`` chaos site firing all take the same typed fallback —
recompute the chunk from source (bit-equal by the determinism contract)
and record ``stream_cache_fallback`` in the fault log. Unpacked columns
are numpy views into the packed blocks, so byte-equality of cached vs
recomputed chunks is assertable (and asserted — tests/test_stream_engine
.py, plus spot-checks in the chaos campaign's ``stream`` scenario).
"""
from __future__ import annotations

import hashlib
import importlib
import io
import json
import os
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Mapping, Optional, Sequence

import numpy as np

from ..observability import metrics as _obs_metrics
from ..robustness import faults
from ..robustness.policy import FaultLog, FaultReport
from ..table import Column, FeatureTable

CACHE_BYTES_ENV = "TG_STREAM_CACHE_BYTES"
CACHE_DIR_ENV = "TG_STREAM_CACHE_DIR"
DEFAULT_CACHE_BYTES = 256 * 1024 * 1024


def env_cache_bytes(max_bytes: Optional[int] = None) -> int:
    if max_bytes is not None:
        return max(0, int(max_bytes))
    try:
        raw = os.environ.get(CACHE_BYTES_ENV, "")
        return max(0, int(raw)) if raw else DEFAULT_CACHE_BYTES
    except ValueError:
        return DEFAULT_CACHE_BYTES


def env_cache_dir() -> Optional[str]:
    return os.environ.get(CACHE_DIR_ENV) or None


def transform_identity(models: Sequence[Any]) -> str:
    """Stable digest of the *fitted* upstream transform stack: the same
    serialized form model persistence commits (class + uid + full fitted
    state, arrays hashed by content). Anything that refuses to serialize
    hashes as process-unique — degrading to a guaranteed miss, never to a
    wrong hit."""
    from ..persistence import _Arrays, stage_to_json
    h = hashlib.sha256()
    for m in models:
        arrays = _Arrays()
        try:
            d = stage_to_json(m, arrays)
            h.update(json.dumps(d, sort_keys=True, default=repr).encode())
            for k in sorted(arrays.store):
                a = arrays.store[k]
                h.update(f"{k}:{a.dtype}:{a.shape}".encode())
                h.update(np.ascontiguousarray(a).tobytes())
        except Exception:
            h.update(f"opaque:{type(m).__name__}:{id(m)}".encode())
    return h.hexdigest()[:16]


def chunk_cache_key(source_fingerprint: str, index: int, ident: str,
                    chunk_rows: int) -> str:
    return f"{source_fingerprint[:16]}:{ident}:{chunk_rows}:{index:06d}"


@dataclass
class PackedChunk:
    """One transformed chunk in packed per-dtype form.

    ``header`` is JSON-able (it IS the disk header): row count, dtype
    block order, and a column directory (name, feature-type path, dtype,
    shape, mask offset flag, JSON-able metadata). ``blocks`` hold the
    concatenated flattened values per dtype; ``mask_block`` concatenates
    every present mask. ``extra_meta`` carries non-JSON-able column
    metadata (e.g. ``vector_meta`` objects) by reference — host tier
    only; a disk-restored chunk keeps the JSON-able subset (fold
    consumers read values; schema metadata comes from the probe table).
    """
    header: Dict[str, Any]
    blocks: Dict[str, np.ndarray]
    mask_block: Optional[np.ndarray]
    key_values: Optional[np.ndarray] = None
    extra_meta: Dict[str, Mapping[str, Any]] = field(default_factory=dict)

    @property
    def rows(self) -> int:
        return int(self.header["rows"])

    @property
    def nbytes(self) -> int:
        total = sum(int(b.nbytes) for b in self.blocks.values())
        if self.mask_block is not None:
            total += int(self.mask_block.nbytes)
        if self.key_values is not None:
            total += int(self.key_values.nbytes)
        return total

    def content_sha(self) -> str:
        """Digest of the packed payload bytes — the byte-equality probe
        tests compare cached vs recomputed chunks on."""
        h = hashlib.sha256()
        h.update(json.dumps(self.header, sort_keys=True).encode())
        for dt in self.header["dtypes"]:
            h.update(np.ascontiguousarray(self.blocks[dt]).tobytes())
        if self.mask_block is not None:
            h.update(np.ascontiguousarray(self.mask_block).tobytes())
        return h.hexdigest()

    def unpack(self) -> FeatureTable:
        """Rebuild the FeatureTable; column values/masks are views into
        the packed blocks (the base buffers stay alive under the views,
        so a later LRU eviction cannot invalidate a delivered chunk)."""
        offs = {dt: 0 for dt in self.blocks}
        moff = 0
        cols: Dict[str, Column] = {}
        for d in self.header["cols"]:
            dt = d["dtype"]
            shape = tuple(d["shape"])
            size = int(np.prod(shape)) if shape else 1
            vals = self.blocks[dt][offs[dt]:offs[dt] + size].reshape(shape)
            offs[dt] += size
            mask = None
            if d["masked"]:
                n = int(d["mask_size"])
                mask = self.mask_block[moff:moff + n].reshape(
                    tuple(d["mask_shape"]))
                moff += n
            meta = dict(d.get("meta") or {})
            meta.update(self.extra_meta.get(d["name"], {}))
            mod, _, qual = d["type"].rpartition(":")
            ftype = getattr(importlib.import_module(mod), qual)
            cols[d["name"]] = Column(ftype, vals, mask, meta)
        return FeatureTable(cols, self.rows, self.key_values)


def pack_table(table: FeatureTable) -> Optional[PackedChunk]:
    """Pack a (host-side, transformed) chunk table; ``None`` when the
    chunk is not cacheable — any object-dtype column (un-vectorized
    text/map payloads) or non-numpy storage makes the whole chunk
    uncacheable rather than partially cached."""
    key_values = table.key
    if key_values is not None:
        key_values = np.asarray(key_values)
        if key_values.dtype == object:
            return None
    directory: List[Dict[str, Any]] = []
    by_dtype: "OrderedDict[str, List[np.ndarray]]" = OrderedDict()
    masks: List[np.ndarray] = []
    extra_meta: Dict[str, Mapping[str, Any]] = {}
    for name in table.column_names:
        col = table[name]
        vals = col.values
        if not isinstance(vals, np.ndarray) or vals.dtype == object:
            return None
        mask = None if col.mask is None else np.asarray(col.mask)
        jsonable: Dict[str, Any] = {}
        opaque: Dict[str, Any] = {}
        for k, v in dict(col.metadata).items():
            try:
                json.dumps({k: v})
                jsonable[k] = v
            except (TypeError, ValueError):
                opaque[k] = v
        if opaque:
            extra_meta[name] = opaque
        directory.append({
            "name": name,
            "type": f"{col.feature_type.__module__}:"
                    f"{col.feature_type.__qualname__}",
            "dtype": str(vals.dtype), "shape": list(vals.shape),
            "masked": mask is not None,
            "mask_size": 0 if mask is None else int(mask.size),
            "mask_shape": [] if mask is None else list(mask.shape),
            "meta": jsonable,
        })
        by_dtype.setdefault(str(vals.dtype), []).append(
            np.ascontiguousarray(vals).reshape(-1))
        if mask is not None:
            masks.append(np.ascontiguousarray(mask).reshape(-1))
    blocks = {dt: (np.concatenate(parts) if len(parts) > 1 else parts[0])
              for dt, parts in by_dtype.items()}
    mask_block = (np.concatenate(masks) if len(masks) > 1
                  else masks[0] if masks else None)
    header = {"rows": table.num_rows, "dtypes": list(blocks),
              "cols": directory}
    return PackedChunk(header, blocks, mask_block, key_values, extra_meta)


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    stores: int = 0
    evictions: int = 0
    skipped: int = 0          # uncacheable chunks (object columns)
    fallbacks: int = 0        # corrupt/chaos entries recomputed from source
    disk_hits: int = 0
    hit_bytes: int = 0
    host_bytes: int = 0       # current host-tier residency

    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def to_json(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "stores": self.stores, "evictions": self.evictions,
                "skipped": self.skipped, "fallbacks": self.fallbacks,
                "diskHits": self.disk_hits, "hitBytes": self.hit_bytes,
                "hostBytes": self.host_bytes,
                "hitRate": round(self.hit_rate(), 4)}


class CorruptCacheEntry(RuntimeError):
    """A disk-tier entry failed sha256/header verification. Internal —
    ``ChunkCache.get`` converts it into the typed recompute fallback."""


class ChunkCache:
    """Bounded two-tier transformed-chunk cache (host LRU + sha-verified
    disk). Thread-safe: producer workers get/put concurrently."""

    def __init__(self, max_bytes: Optional[int] = None,
                 disk_dir: Optional[str] = None):
        self.max_bytes = env_cache_bytes(max_bytes)
        self.disk_dir = disk_dir
        self.stats = CacheStats()
        self._lock = threading.Lock()
        self._host: "OrderedDict[str, PackedChunk]" = OrderedDict()
        # fallback reports happen on PRODUCER threads, which never see the
        # consumer's ambient FaultLog (contextvars are per-thread) — the
        # feed binds the owning run's log here at construction
        self._log: Optional[FaultLog] = None

    def bind_log(self, log: Optional[FaultLog]) -> None:
        """Bind the owning run's FaultLog so worker-thread fallbacks land
        in its accounting (DeviceFeed calls this on the consumer thread)."""
        if log is not None:
            self._log = log

    @classmethod
    def from_env(cls, disk_dir: Optional[str] = None,
                 ) -> Optional["ChunkCache"]:
        """The workflow's constructor: host budget from
        TG_STREAM_CACHE_BYTES, disk tier from TG_STREAM_CACHE_DIR (the
        conventional spot is ``<checkpoint dir>/stream_cache`` so cached
        prep survives a kill next to the fold states it matches).
        Returns ``None`` when both tiers are disabled."""
        max_bytes = env_cache_bytes()
        disk = env_cache_dir() or disk_dir
        if max_bytes <= 0 and not disk:
            return None
        return cls(max_bytes=max_bytes, disk_dir=disk)

    # -- lookup ---------------------------------------------------------------
    def get(self, key: str) -> Optional[PackedChunk]:
        """Packed chunk for ``key`` or ``None`` (miss → caller recomputes
        from source). Every failure mode inside — the ``stream.cache``
        chaos site, a sha256/header mismatch on the disk tier — degrades
        to the same typed recompute fallback; preemption (a
        BaseException) propagates like any other kill."""
        try:
            faults.inject("stream.cache")
            with self._lock:
                entry = self._host.get(key)
                if entry is not None:
                    self._host.move_to_end(key)
            if entry is None and self.disk_dir:
                entry = self._disk_read(key)
                if entry is not None:
                    self.stats.disk_hits += 1
                    self._host_insert(key, entry)
        except CorruptCacheEntry as e:
            self._fallback(key, str(e))
            entry = None
        except Exception as e:  # chaos raise — recompute, never wrong data
            self._fallback(key, f"{type(e).__name__}: {e}")
            entry = None
        if entry is None:
            self.stats.misses += 1
            _obs_metrics.inc_counter(
                "tg_stream_cache_misses_total", 1.0,
                help="transformed-chunk cache misses (chunk recomputed)")
            return None
        self.stats.hits += 1
        self.stats.hit_bytes += entry.nbytes
        _obs_metrics.inc_counter(
            "tg_stream_cache_hits_total", 1.0,
            help="transformed-chunk cache hits (read+transform skipped)")
        return entry

    def _fallback(self, key: str, reason: str) -> None:
        self.stats.fallbacks += 1
        report = FaultReport(
            site="stream.cache", kind="stream_cache_fallback",
            detail={"key": key, "reason": reason[:200]})
        if self._log is not None:
            self._log.add(report)
        else:
            FaultLog.record(report)

    # -- store ----------------------------------------------------------------
    def put(self, key: str, packed: Optional[PackedChunk]) -> None:
        if packed is None:
            self.stats.skipped += 1
            return
        self.stats.stores += 1
        self._host_insert(key, packed)
        if self.disk_dir:
            try:
                self._disk_write(key, packed)
            except OSError as e:
                self._fallback(key, f"disk store failed: {e}")

    def _host_insert(self, key: str, packed: PackedChunk) -> None:
        if self.max_bytes <= 0 or packed.nbytes > self.max_bytes:
            return
        with self._lock:
            prev = self._host.pop(key, None)
            if prev is not None:
                self.stats.host_bytes -= prev.nbytes
            while (self._host
                   and self.stats.host_bytes + packed.nbytes
                   > self.max_bytes):
                _, evicted = self._host.popitem(last=False)
                self.stats.host_bytes -= evicted.nbytes
                self.stats.evictions += 1
            self._host[key] = packed
            self.stats.host_bytes += packed.nbytes

    # -- disk tier ------------------------------------------------------------
    def _paths(self, key: str) -> "tuple[str, str]":
        fname = f"chunk_{hashlib.sha256(key.encode()).hexdigest()[:24]}.npz"
        path = os.path.join(self.disk_dir, fname)
        return path, path + ".sha256"

    def _disk_write(self, key: str, packed: PackedChunk) -> None:
        from ..manifest import atomic_write_bytes
        os.makedirs(self.disk_dir, exist_ok=True)
        path, shapath = self._paths(key)
        if os.path.exists(path):
            return
        header = dict(packed.header)
        header["key"] = key
        arrays = {"__header__": np.frombuffer(
            json.dumps(header, sort_keys=True).encode(), dtype=np.uint8)}
        for i, dt in enumerate(packed.header["dtypes"]):
            arrays[f"block_{i}"] = packed.blocks[dt]
        if packed.mask_block is not None:
            arrays["mask"] = packed.mask_block
        if packed.key_values is not None:
            arrays["key_values"] = packed.key_values
        buf = io.BytesIO()
        np.savez_compressed(buf, **arrays)
        data = buf.getvalue()
        sha = atomic_write_bytes(path, data)
        atomic_write_bytes(shapath, sha.encode())

    def _disk_read(self, key: str) -> Optional[PackedChunk]:
        path, shapath = self._paths(key)
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                data = f.read()
            with open(shapath, "rb") as f:
                want = f.read().decode().strip()
        except OSError as e:
            raise CorruptCacheEntry(f"unreadable entry: {e}")
        got = hashlib.sha256(data).hexdigest()
        if got != want:
            self._evict_disk(path, shapath)
            raise CorruptCacheEntry(
                f"sha256 mismatch ({got[:12]} != {want[:12]})")
        try:
            with np.load(io.BytesIO(data), allow_pickle=False) as z:
                header = json.loads(bytes(z["__header__"]).decode())
                if header.pop("key", None) != key:
                    raise CorruptCacheEntry("entry key mismatch")
                blocks = {dt: z[f"block_{i}"]
                          for i, dt in enumerate(header["dtypes"])}
                mask = z["mask"] if "mask" in z.files else None
                kv = z["key_values"] if "key_values" in z.files else None
        except (ValueError, KeyError, OSError) as e:
            self._evict_disk(path, shapath)
            raise CorruptCacheEntry(f"undecodable entry: {e}")
        return PackedChunk(header, blocks, mask, kv)

    @staticmethod
    def _evict_disk(path: str, shapath: str) -> None:
        for p in (path, shapath):
            try:
                os.remove(p)
            except OSError:
                pass
