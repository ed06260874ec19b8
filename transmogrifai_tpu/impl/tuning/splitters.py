"""Data splitters: test reservation + class balancing / cutting.

(reference: core/.../impl/tuning/Splitter.scala:62-100, DataSplitter.scala,
DataBalancer.scala, DataCutter.scala)
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import numpy as np


@dataclass
class PreparedData:
    """Outcome of pre-validation preparation: row indices into the original
    arrays (resampling expressed as indices, possibly repeated for upsampling)
    plus metadata about what was done."""
    indices: np.ndarray
    summary: Dict[str, Any] = field(default_factory=dict)
    label_mapping: Optional[Dict[int, int]] = None  # DataCutter re-indexing


class Splitter:
    """Base: reserve a test fraction, prepare train data
    (reference Splitter.scala:62-100)."""

    def __init__(self, reserve_test_fraction: float = 0.1, seed: int = 42):
        if not 0.0 <= reserve_test_fraction < 1.0:
            raise ValueError("reserve_test_fraction must be in [0, 1)")
        self.reserve_test_fraction = reserve_test_fraction
        self.seed = seed
        self.summary: Dict[str, Any] = {}

    def split(self, n: int) -> Tuple[np.ndarray, np.ndarray]:
        """(train_idx, test_idx) random split."""
        rng = np.random.RandomState(self.seed)
        perm = rng.permutation(n)
        n_test = int(round(n * self.reserve_test_fraction))
        return np.sort(perm[n_test:]), np.sort(perm[:n_test])

    def pre_validation_prepare(self, y: np.ndarray) -> PreparedData:
        """Estimate and apply balancing/cutting on the train split
        (reference preValidationPrepare). Default: identity."""
        return PreparedData(indices=np.arange(len(y)))

    def validation_prepare(self, y: np.ndarray) -> PreparedData:
        """Preparation applied before the final refit on full train data
        (reference validationPrepare). Default: same as pre-validation."""
        return self.pre_validation_prepare(y)


class DataSplitter(Splitter):
    """Plain random split, regression problems (reference DataSplitter.scala:62-85)."""


class DataBalancer(Splitter):
    """Binary classification balancer (reference DataBalancer.scala:125-163,
    estimate :208): if the positive fraction is below ``sample_fraction``,
    down-sample the majority class (and optionally up-sample the minority) so
    positives make up ~sample_fraction of the result, capped at
    ``max_training_sample`` rows."""

    def __init__(self, sample_fraction: float = 0.1,
                 max_training_sample: int = 1_000_000,
                 already_balanced_fraction_cutoff: float = 0.3, **kw):
        super().__init__(**kw)
        self.sample_fraction = sample_fraction
        self.max_training_sample = max_training_sample
        self.already_balanced_fraction_cutoff = already_balanced_fraction_cutoff

    def pre_validation_prepare(self, y: np.ndarray) -> PreparedData:
        rng = np.random.RandomState(self.seed)
        pos_idx = np.nonzero(y > 0.5)[0]
        neg_idx = np.nonzero(y <= 0.5)[0]
        n_pos, n_neg = len(pos_idx), len(neg_idx)
        n = n_pos + n_neg
        small, big = (pos_idx, neg_idx) if n_pos <= n_neg else (neg_idx, pos_idx)
        frac = len(small) / max(n, 1)
        summary: Dict[str, Any] = {
            "positiveCount": int(n_pos), "negativeCount": int(n_neg),
            "minorityFraction": frac, "balanced": False,
        }
        if frac >= min(self.sample_fraction, self.already_balanced_fraction_cutoff) \
                or len(small) == 0:
            idx = np.arange(n)
            if n > self.max_training_sample:
                idx = np.sort(rng.choice(n, self.max_training_sample, replace=False))
                summary["downsampledTo"] = self.max_training_sample
            self.summary = summary
            return PreparedData(indices=idx, summary=summary)
        # downsample majority so minority fraction ≈ sample_fraction
        target_big = int(len(small) * (1.0 - self.sample_fraction) / self.sample_fraction)
        target_big = max(min(target_big, len(big)), len(small))
        big_keep = rng.choice(big, target_big, replace=False)
        idx = np.sort(np.concatenate([small, big_keep]))
        if len(idx) > self.max_training_sample:
            idx = np.sort(rng.choice(idx, self.max_training_sample, replace=False))
        summary.update({"balanced": True,
                        "downsampledMajorityTo": int(target_big),
                        "resultSize": int(len(idx))})
        self.summary = summary
        return PreparedData(indices=idx, summary=summary)


class DataCutter(Splitter):
    """Multiclass label cutter (reference DataCutter.scala:85,170): keep at
    most ``max_label_categories`` labels and only labels with at least
    ``min_label_fraction``; drop rows with other labels and re-index labels
    to a dense 0..K-1 range."""

    def __init__(self, max_label_categories: int = 100,
                 min_label_fraction: float = 0.0, **kw):
        super().__init__(**kw)
        if min_label_fraction >= 0.5:
            raise ValueError("min_label_fraction must be < 0.5")
        self.max_label_categories = max_label_categories
        self.min_label_fraction = min_label_fraction

    def pre_validation_prepare(self, y: np.ndarray) -> PreparedData:
        labels, counts = np.unique(y.astype(np.int64), return_counts=True)
        frac = counts / counts.sum()
        order = np.argsort(-counts)
        kept = [labels[i] for i in order[: self.max_label_categories]
                if frac[i] >= self.min_label_fraction]
        kept_set = set(int(k) for k in kept)
        if not kept_set:
            raise ValueError("DataCutter dropped all labels")
        mask = np.isin(y.astype(np.int64), list(kept_set))
        mapping = {int(lab): i for i, lab in enumerate(sorted(kept_set))}
        summary = {"labelsKept": sorted(kept_set),
                   "labelsDropped": sorted(set(int(l) for l in labels) - kept_set),
                   "rowsKept": int(mask.sum())}
        self.summary = summary
        return PreparedData(indices=np.nonzero(mask)[0], summary=summary,
                            label_mapping=mapping)


class LabelIndex:
    """A ``label_mapping`` (original label -> dense class index, what
    :class:`DataCutter` hands out) as arrays: the one statement of the label
    (de-)indexing that the selector's fit, its evaluation and every scoring
    path of the fitted model share (reference DataCutter + PredictionDeIndexer).

    * :meth:`forward`: ``mapping.get(int(v), -1)`` for every ``v``: the value
      truncated toward zero as ``int()`` does, ``-1`` for a label that was not
      kept. One ``searchsorted`` over the sorted kept labels, so labels may be
      any integers (negative, seven digits): no table the size of the largest.
    * :meth:`inverse` / :meth:`inverse_device`: ``inverse.get(int(v), int(v))``:
      a dense index with an entry becomes its original label, one without
      (negative, beyond the table, a gap in a hand-made mapping) passes through
      truncated. One lookup in a dense table over ``0..K-1``.

    Results are float32, the dtype of the label and prediction columns.
    Build it through :func:`label_index`, which keeps one per mapping."""

    def __init__(self, mapping: Dict[int, int]):
        self.kept = np.array(sorted(int(k) for k in mapping), dtype=np.int64)
        self.dense = np.array([int(mapping[int(k)]) for k in self.kept],
                              dtype=np.int64)
        if self.dense.min() < 0:
            raise ValueError("label_mapping: a dense class index is >= 0")
        self.table = np.arange(int(self.dense.max()) + 1, dtype=np.float32)
        self.table[self.dense] = self.kept

    def forward(self, labels) -> np.ndarray:
        v = np.asarray(labels).astype(np.int64)
        pos = np.minimum(np.searchsorted(self.kept, v), len(self.kept) - 1)
        return np.where(self.kept[pos] == v, self.dense[pos],
                        -1).astype(np.float32)

    def inverse(self, dense) -> np.ndarray:
        i = np.asarray(dense).astype(np.int64)
        inside = (i >= 0) & (i < len(self.table))
        return np.where(inside, self.table[np.where(inside, i, 0)],
                        i).astype(np.float32)

    def inverse_device(self, dense):
        """:meth:`inverse` of a device array, jit-traceable."""
        import jax.numpy as jnp
        i = dense.astype(jnp.int32)
        inside = (i >= 0) & (i < len(self.table))
        return jnp.where(inside, jnp.take(jnp.asarray(self.table),
                                          jnp.where(inside, i, 0)),
                         i.astype(jnp.float32))


@functools.lru_cache(maxsize=64)
def _label_index(items: Tuple[Tuple[int, int], ...]) -> LabelIndex:
    return LabelIndex(dict(items))


def label_index(mapping: Optional[Dict[int, int]]) -> Optional[LabelIndex]:
    """The :class:`LabelIndex` of ``mapping``, built once per mapping (at most
    ``max_label_categories`` pairs make the key); None where there is no
    mapping to apply."""
    if not mapping:
        return None
    return _label_index(tuple(mapping.items()))
