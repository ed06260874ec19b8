"""Sweep-level checkpointing: per-candidate results survive preemption.

Stage checkpoints (persistence.py) make the DAG resumable at estimator
granularity — but the ModelSelector is ONE estimator whose fit sweeps
families × grids × folds, the most expensive single fit of the train path.
A preemption mid-sweep used to lose every already-evaluated candidate.

This module persists one record per evaluated candidate batch (a model
family's whole fused branch — the unit of execution on device) into
``sweep_<selector-uid>.json`` inside the workflow checkpoint dir, committed
atomically through the shared :class:`~..manifest.CheckpointManifest`. A
resumed ``train()`` replays matching records (fold metrics restored
bit-exactly via the recorded dtype) and dispatches only the remainder; the
winner selection then recomputes deterministically from the merged metrics.

Records are keyed by a candidate fingerprint — family, canonical grid,
fold/metric configuration, row count and a sha256 of the label vector and
fold assignment — so a checkpoint from different data, folds, or sweep
fidelity can never be replayed onto this run.

The reference has no analog: Spark re-runs the whole selector fit from
lineage. Persist-and-skip is strictly stronger for hour-long sweeps on
preemptible capacity.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ...manifest import CheckpointManifest
from ...robustness.policy import FaultLog, FaultReport

logger = logging.getLogger(__name__)

SWEEP_STATE_VERSION = 1


def sweep_fingerprint(X, y, val_masks: np.ndarray, *, problem: str,
                      metric_name: str, num_classes: int,
                      larger_better: bool, exact: bool,
                      max_eval_rows: Optional[int]) -> Dict[str, Any]:
    """What one sweep's records may be replayed onto: the fold and metric
    configuration, the sweep's fidelity, the table's shape and a sha256 of
    the labels and of the (F, n) validation masks. Taken of the rows as the
    caller handed them, BEFORE the validator pads them to their bucket."""
    F, n = val_masks.shape
    return {
        "n": int(n), "F": int(F), "problem": problem,
        "d": int(X.shape[-1]) if X.ndim > 1 else 1,
        "metric": metric_name, "numClasses": int(num_classes),
        "largerBetter": bool(larger_better), "exact": bool(exact),
        "maxEvalRows": max_eval_rows,
        "yhash": hashlib.sha256(
            np.ascontiguousarray(np.asarray(y)[:n]).tobytes()).hexdigest(),
        "foldHash": hashlib.sha256(
            np.ascontiguousarray(val_masks).tobytes()).hexdigest(),
    }


def candidate_key(family: str, grid: List[Dict[str, Any]],
                  fingerprint: Dict[str, Any]) -> str:
    """Stable fingerprint of one family's sweep branch: the family, its
    canonical grid, and the run fingerprint (fold config, metric, data
    hashes). Any difference → different key → no replay."""
    doc = {"family": family,
           "grid": [sorted((k, repr(v)) for k, v in g.items()) for g in grid],
           "fingerprint": {k: fingerprint[k] for k in sorted(fingerprint)}}
    return hashlib.sha256(
        json.dumps(doc, sort_keys=True).encode("utf-8")).hexdigest()


def params_hash(hyper: Dict[str, Any]) -> str:
    """sha256 of one candidate's canonical hyperparameter dict — the
    identity a restored record is matched and audited by."""
    return hashlib.sha256(json.dumps(
        sorted((k, repr(v)) for k, v in hyper.items())).encode()).hexdigest()


class SweepCheckpoint:
    """Durable per-candidate sweep state for one selector stage.

    ``restore``/``persist`` are what a sweep calls: they own every field
    of a whole-family record, over ``get``/``put``::

        {"family": "OpGBTClassifier",
         "grid": [...hyper dicts...],
         "paramsHashes": ["<sha256 per grid point>"],
         "metricName": "AuPR",
         "foldMetrics": [[...], ...],   # (F, G), null for non-finite
         "dtype": "float32",            # restores metrics bit-exactly
         "quarantined": false,          # family branch threw pre-dispatch
         "reason": null}

    Every ``put`` rewrites the state file atomically and commits it through
    the directory manifest, so the file always holds a consistent prefix of
    the sweep and a torn write is impossible.
    """

    def __init__(self, ckpt_dir: str, owner_uid: str,
                 manifest: Optional[CheckpointManifest] = None):
        from ...persistence import open_checkpoint_manifest
        self.ckpt_dir = ckpt_dir
        self.owner_uid = owner_uid
        self.fname = f"sweep_{owner_uid}.json"
        self.path = os.path.join(ckpt_dir, self.fname)
        self.manifest = manifest or open_checkpoint_manifest(ckpt_dir)
        self._state: Dict[str, Any] = {"sweepStateVersion": SWEEP_STATE_VERSION,
                                       "candidates": {}}
        self._load()

    def _load(self) -> None:
        if not os.path.isfile(self.path):
            return
        reason = None
        if self.manifest.sweeps.get(self.owner_uid):
            reason = self.manifest.verify_file(self.fname)
        elif self.manifest.files or self.manifest.stages:
            reason = "sweep state has no manifest completion record"
        if reason is not None:
            FaultLog.record(FaultReport(
                site="persistence.sweep", kind="checkpoint_skipped",
                detail={"uid": self.owner_uid, "file": self.path,
                        "reason": reason, "error": reason}))
            return
        try:
            with open(self.path) as fh:
                doc = json.load(fh)
            if doc.get("sweepStateVersion") != SWEEP_STATE_VERSION:
                raise ValueError(
                    f"sweep state version {doc.get('sweepStateVersion')!r}")
            self._state = doc
        except (OSError, ValueError) as e:
            FaultLog.record(FaultReport(
                site="persistence.sweep", kind="checkpoint_skipped",
                detail={"uid": self.owner_uid, "file": self.path,
                        "reason": f"{type(e).__name__}: {e}",
                        "error": f"{type(e).__name__}: {e}"}))

    # -- record access -------------------------------------------------------
    def get(self, cand_key: str) -> Optional[Dict[str, Any]]:
        return self._state["candidates"].get(cand_key)

    def put(self, cand_key: str, record: Dict[str, Any]) -> None:
        from ...manifest import atomic_write_bytes
        self._state["candidates"][cand_key] = record
        data = json.dumps(self._state).encode("utf-8")
        sha = atomic_write_bytes(self.path, data)
        self.manifest.record_file(self.fname, sha, len(data))
        self.manifest.complete_sweep(self.owner_uid, self.fname)
        self.manifest.save()

    # -- one family's record, whole ------------------------------------------
    def persist(self, cand_key: str, family: str,
                grid: List[Dict[str, Any]], metric_name: str,
                fold_metrics: np.ndarray,
                reason: Optional[str] = None) -> None:
        """Commit one family's evaluated branch: its (F, G) fold metrics
        (NaN throughout where the fit threw) and, for a quarantined family,
        the ``reason``."""
        self.put(cand_key, {
            "family": family,
            "grid": [dict(g) for g in grid],
            "paramsHashes": [params_hash(g) for g in grid],
            "metricName": metric_name,
            **self.encode_metrics(fold_metrics),
            "quarantined": reason is not None,
            "reason": reason,
        })

    def restore(self, cand_key: str, F: int, G: int
                ) -> Optional[Tuple[np.ndarray, Optional[str]]]:
        """``(fold_metrics, quarantine_reason_or_None)`` of a persisted
        branch, or None where there is no record or its metrics are not
        this sweep's (F, G). A replay is filed in the fault log."""
        rec = self.get(cand_key)
        if rec is None:
            return None
        fold_metrics = self.decode_metrics(rec)
        if fold_metrics.shape != (F, G):
            return None
        quarantined = bool(rec.get("quarantined"))
        FaultLog.record(FaultReport(
            site="sweep.candidate", kind="restored",
            detail={"family": rec.get("family"), "configs": G,
                    "candidateKey": cand_key[:16],
                    "quarantined": quarantined}))
        logger.info("sweep resume: restored %d %s candidate(s) from "
                    "checkpoint", G, rec.get("family"))
        reason = None
        if quarantined:
            reason = rec.get("reason") or "restored quarantined candidate"
        return fold_metrics, reason

    # -- metric (de)hydration ------------------------------------------------
    @staticmethod
    def encode_metrics(fold_metrics: np.ndarray) -> Dict[str, Any]:
        """JSON-safe (F, G) metrics: non-finite → null/str markers, dtype
        kept so decoding reproduces the array bit-for-bit (float32 → python
        float widens exactly; json repr round-trips float64 exactly)."""
        fm = np.asarray(fold_metrics)

        def enc(v: float):
            if np.isnan(v):
                return None
            if np.isinf(v):
                return "inf" if v > 0 else "-inf"
            return float(v)
        return {"foldMetrics": [[enc(v) for v in row] for row in fm],
                "dtype": str(fm.dtype)}

    @staticmethod
    def decode_metrics(record: Dict[str, Any]) -> np.ndarray:
        def dec(v):
            if v is None:
                return np.nan
            if v == "inf":
                return np.inf
            if v == "-inf":
                return -np.inf
            return v
        rows = [[dec(v) for v in row] for row in record["foldMetrics"]]
        return np.asarray(rows, dtype=np.dtype(record.get("dtype",
                                                          "float64")))
