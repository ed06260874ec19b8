"""RawFeatureFilter — pre-DAG screening of raw features.

Mirrors the reference (reference:
core/src/main/scala/com/salesforce/op/filters/RawFeatureFilter.scala): before
any stage fits, compare each raw feature's training distribution against the
scoring distribution and the label, and blacklist features (or individual map
keys) that are too empty, too shifted, or leak the label through their null
pattern. Metrics (getRawFeatureFilterMetrics:207-291): fill rates, fill
rate delta/ratio between train and score, Jensen-Shannon divergence, and
null-indicator↔label correlation (leakage). Exclusion reasons (:302+)
drive the blacklists; the cleaned table plus
``RawFeatureFilterResults`` feed the workflow (OpWorkflow.scala:524-563).

The null-label correlations for ALL features are computed in one jitted
device pass (a (n, F) null-indicator matrix against the label — the TPU
re-expression of the reference's per-partition monoid reduce).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..features import Feature
from ..table import Column, FeatureTable
from .distribution import (
    FeatureDistribution, column_distributions, compare_distributions,
    fill_numeric_bins,
)


@dataclass
class FeatureMetrics:
    """Per-feature (or per map key) filter metrics (reference
    RawFeatureFilterMetrics)."""
    name: str
    key: Optional[str]
    train_fill_rate: float
    score_fill_rate: Optional[float] = None
    fill_rate_delta: Optional[float] = None
    fill_ratio_diff: Optional[float] = None
    js_divergence: Optional[float] = None
    null_label_correlation: Optional[float] = None
    exclusion_reasons: List[str] = field(default_factory=list)

    @property
    def full_name(self) -> str:
        return self.name if self.key is None else f"{self.name}[{self.key}]"


@dataclass
class RawFeatureFilterResults:
    """Config + metrics + decisions (reference RawFeatureFilterResults.scala)."""
    config: Dict[str, Any]
    metrics: List[FeatureMetrics]
    excluded_features: List[str]
    excluded_map_keys: Dict[str, List[str]]

    def to_json(self) -> Dict[str, Any]:
        def clean(d: Dict[str, Any]) -> Dict[str, Any]:
            return {k: (None if isinstance(v, float) and not np.isfinite(v) else v)
                    for k, v in d.items()}
        return {
            "config": self.config,
            "metrics": [clean(vars(m)) for m in self.metrics],
            "excludedFeatures": self.excluded_features,
            "excludedMapKeys": self.excluded_map_keys,
        }


class RawFeatureFilter:
    """Screens raw features before the DAG fits (reference
    RawFeatureFilter.scala ctor params :60-108)."""

    def __init__(self,
                 score_reader=None,
                 score_table: Optional[FeatureTable] = None,
                 bins: int = 100,
                 min_fill_rate: float = 0.001,
                 max_fill_difference: float = 0.90,
                 max_fill_ratio_diff: float = 20.0,
                 max_js_divergence: float = 0.90,
                 max_correlation: float = 0.90,
                 correlation_type: str = "pearson",
                 protected_features: Sequence[str] = (),
                 text_bins: int = 255):
        self.score_reader = score_reader
        self.score_table = score_table
        self.bins = bins
        self.min_fill_rate = min_fill_rate
        self.max_fill_difference = max_fill_difference
        self.max_fill_ratio_diff = max_fill_ratio_diff
        self.max_js_divergence = max_js_divergence
        self.max_correlation = max_correlation
        self.correlation_type = correlation_type
        self.protected_features = set(protected_features)
        self.text_bins = text_bins
        self.mesh = None

    def set_mesh(self, mesh) -> "RawFeatureFilter":
        """Shard the numeric distribution stats over a mesh's 'data' axis.

        RFF is the FIRST full pass over raw data (reference monoid reduce
        over RDD partitions, RawFeatureFilter.scala:135-196) — without this
        it is a single-host serial bottleneck before any sharded work
        starts. Numeric columns batch into one row-sharded device pass
        (count/min/max/sum + exact CDF-diff histograms); string/map columns
        remain host work by design (SURVEY §2.9 host boundary)."""
        self.mesh = mesh
        return self

    # -- distribution computation (reference computeFeatureStats:135-196) ----
    def _distributions(self, table: FeatureTable, features: Sequence[Feature],
                       ) -> Dict[str, List[FeatureDistribution]]:
        out: Dict[str, List[FeatureDistribution]] = {}
        numeric: List[Feature] = []
        for f in features:
            if f.is_response:
                continue
            col = table.get(f.name)
            if col is None:
                continue
            if self.mesh is not None and col.kind in (
                    "real", "binary", "integral", "date"):
                numeric.append(f)
                continue
            out[f.name] = column_distributions(
                f.name, col, self.bins, self.text_bins)
        if numeric:
            out.update(self._device_numeric_distributions(table, numeric))
        return out

    def _device_numeric_distributions(
            self, table: FeatureTable, feats: Sequence[Feature],
            ) -> Dict[str, List[FeatureDistribution]]:
        """All numeric columns in ONE row-sharded device stats pass: per-
        column count/nulls/min/max/sum, with the binned distributions
        batch-filled later (``_batch_fill_device_bins`` — one program for
        every feature, one sync). Columns are f64-centered on host before
        the f32 cast so epoch-millis-scale values keep full precision in
        the shifted frame. Counting is EXACT (CDF diff) — a tighter
        estimator than the host SPDT sketch's interpolated density, so a
        metric sitting within the sketch's approximation error of a
        threshold can decide differently with a mesh attached; fill rates
        and summaries are bit-matched."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .distribution import Summary

        n = table.num_rows
        n_data = self.mesh.shape["data"]
        n_pad = -(-max(n, 1) // n_data) * n_data
        V = np.zeros((n_pad, len(feats)), np.float32)
        M = np.zeros((n_pad, len(feats)), bool)
        shifts = np.zeros(len(feats), np.float64)
        for j, f in enumerate(feats):
            col = table[f.name]
            vals = np.asarray(col.values, np.float64)
            valid = col.valid_mask()
            if valid.any():
                shifts[j] = float(np.median(vals[valid]))
            V[:n, j] = (vals - shifts[j]).astype(np.float32)
            M[:n, j] = valid
        sh = NamedSharding(self.mesh, P("data", None))
        V_d = jax.device_put(jnp.asarray(V), sh)
        M_d = jax.device_put(jnp.asarray(M), sh)
        self._stats_input_sharding = str(V_d.sharding.spec)

        @jax.jit
        def stats(v, m):
            # counts stay int32 (exact past 2^24 — a float stack would
            # round them on 100M-row tables); the three float stats fuse
            # into one (3, d) array so the host pays TWO transfers, not
            # four (each is a blocking device->host sync)
            cnt = m.astype(jnp.int32).sum(axis=0)
            vs = jnp.where(m, v, 0.0)
            fl = jnp.stack((jnp.where(m, v, jnp.inf).min(axis=0),
                            jnp.where(m, v, -jnp.inf).max(axis=0),
                            vs.sum(axis=0)))
            return cnt, fl

        cnt_d, fl_d = stats(V_d, M_d)
        cnt = np.asarray(cnt_d)
        mn, mx, sm = np.asarray(fl_d)

        out: Dict[str, List[FeatureDistribution]] = {}
        for j, f in enumerate(feats):
            c = float(cnt[j])
            out[f.name] = [FeatureDistribution(
                name=f.name, count=float(n), nulls=float(n) - c,
                summary=Summary(
                    float(mn[j]) + shifts[j] if c else np.inf,
                    float(mx[j]) + shifts[j] if c else -np.inf,
                    float(sm[j]) + shifts[j] * c, c),
                is_numeric=True, device_data=(V_d, M_d, j, shifts[j]))]
        return out

    @staticmethod
    def _batch_fill_device_bins(train_dists, score_dists, max_bins: int,
                                ) -> None:
        """Fill every device-backed dist's binned distribution in ONE
        program per table (a lax.map over columns) + one sync each — the
        per-feature path would cost two link round-trips per feature."""
        from .distribution import numeric_bin_edges

        groups: Dict[int, List[Tuple[Any, np.ndarray]]] = {}
        handles: Dict[int, Tuple[Any, Any]] = {}
        for name, dlist in train_dists.items():
            for d in dlist:
                if d.device_data is None:
                    continue
                sd = None
                if score_dists is not None:
                    sd = next((s for s in score_dists.get(name, [])
                               if s.key == d.key), None)
                edges = numeric_bin_edges(d, sd, max_bins)
                for dist in (d, sd):
                    if dist is None or dist.device_data is None:
                        continue
                    V_d, M_d, j, shift = dist.device_data
                    if edges is None:
                        dist.device_data = None
                        continue
                    groups.setdefault(id(V_d), []).append(
                        (dist, (edges - shift).astype(np.float32)))
                    handles[id(V_d)] = (V_d, M_d)
        if not groups:
            return
        import jax
        import jax.numpy as jnp

        @jax.jit
        def batched_cdf(v, m, cols, edges):
            def one(args):
                vj, mj, ej = args
                le = (vj[:, None] <= ej[None, :]) & mj[:, None]
                return le.astype(jnp.float32).sum(axis=0)
            return jax.lax.map(
                one, (v[:, cols].T, m[:, cols].T, edges))

        for gid, pairs in groups.items():
            V_d, M_d = handles[gid]
            cols = jnp.asarray([p[0].device_data[2] for p in pairs],
                               dtype=jnp.int32)
            edges = jnp.asarray(np.stack([p[1] for p in pairs]))
            cdfs = np.asarray(batched_cdf(V_d, M_d, cols, edges))
            for (dist, _), cs in zip(pairs, cdfs):
                dist.distribution = np.diff(cs)
                dist.device_data = None

    def _null_label_correlations(self, table: FeatureTable,
                                 features: Sequence[Feature],
                                 label: Optional[Column],
                                 dists: Dict[str, List[FeatureDistribution]],
                                 ) -> Dict[str, float]:
        """One device pass: corr(null indicator, label) for every feature/key
        (reference PreparedFeatures null-label vectors + Pearson)."""
        if label is None:
            return {}
        import jax.numpy as jnp
        from ..ops.stats import pearson_correlation, spearman_correlation

        y = np.asarray(label.values, dtype=np.float32)
        cols: List[np.ndarray] = []
        names: List[str] = []
        for f in features:
            if f.is_response or f.name not in dists:
                continue
            col = table[f.name]
            if col.kind == "map":
                valid = col.valid_mask()
                # one key-set per row, shared across all of the feature's keys;
                # a key present with a None/NaN value counts as NULL, matching
                # the fill-rate definition in column_distributions
                def _row_keys(v) -> frozenset:
                    if v is None:
                        return frozenset()
                    return frozenset(
                        str(k) for k, x in v.items()
                        if x is not None
                        and not (isinstance(x, float) and np.isnan(x)))
                row_keys = [
                    _row_keys(col.values[i]) if valid[i] else frozenset()
                    for i in range(len(col))]
                for d in dists[f.name]:
                    ind = np.array([0.0 if d.key in ks else 1.0
                                    for ks in row_keys], dtype=np.float32)
                    cols.append(ind)
                    names.append(d.full_name)
            else:
                ind = (~col.valid_mask()).astype(np.float32)
                cols.append(ind)
                names.append(f.name)
        if not cols:
            return {}
        X = jnp.asarray(np.stack(cols, axis=1))
        yd = jnp.asarray(y)
        # correlations are not pad-invariant, so shard only when the row
        # count divides the 'data' axis evenly (always true for the padded
        # stats pass; here rows come straight from the reader)
        if (self.mesh is not None
                and X.shape[0] % self.mesh.shape["data"] == 0):
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            X = jax.device_put(X, NamedSharding(self.mesh, P("data", None)))
            yd = jax.device_put(yd, NamedSharding(self.mesh, P("data")))
        corr_fn = (spearman_correlation
                   if self.correlation_type == "spearman"
                   else pearson_correlation)
        corrs = np.asarray(corr_fn(X, yd))
        return {n: float(c) for n, c in zip(names, corrs)}

    # -- main entry (reference generateFilteredRaw) --------------------------
    def filter_raw(self, table: FeatureTable, raw_features: Sequence[Feature],
                   ) -> Tuple[FeatureTable, List[Feature], RawFeatureFilterResults]:
        train_dists = self._distributions(table, raw_features)

        score_table = self.score_table
        if score_table is None and self.score_reader is not None:
            score_table = self.score_reader.generate_table(
                [f for f in raw_features if not f.is_response])
        score_dists = (self._distributions(score_table, raw_features)
                       if score_table is not None else None)

        label_col = next((table[f.name] for f in raw_features
                          if f.is_response and f.name in table), None)
        null_corr = self._null_label_correlations(
            table, raw_features, label_col, train_dists)
        # mesh path: bin every device-backed distribution in one batched
        # program per table before the per-feature metric loop
        self._batch_fill_device_bins(train_dists, score_dists, self.bins)

        metrics: List[FeatureMetrics] = []
        excluded_features: List[str] = []
        excluded_map_keys: Dict[str, List[str]] = {}

        for f in raw_features:
            if f.is_response or f.name not in train_dists:
                continue
            f_metrics: List[FeatureMetrics] = []
            for d in train_dists[f.name]:
                sd = None
                if score_dists is not None:
                    sd = next((s for s in score_dists.get(f.name, [])
                               if s.key == d.key), None)
                if d.is_numeric and sd is None:
                    fill_numeric_bins(d, sd, self.bins)
                m = FeatureMetrics(
                    name=f.name, key=d.key,
                    train_fill_rate=d.fill_fraction(),
                    null_label_correlation=null_corr.get(d.full_name))
                if sd is not None:
                    # the shared train-vs-score comparison (also the drift
                    # monitor's math, serving/drift.py). fill_ratio inf
                    # (one side completely empty) must EXCEED the
                    # threshold, matching the reference's
                    # Double.PositiveInfinity compare
                    cmp = compare_distributions(d, sd, self.bins)
                    m.score_fill_rate = cmp["scoreFill"]
                    m.fill_rate_delta = cmp["fillDelta"]
                    m.fill_ratio_diff = cmp["fillRatio"]
                    m.js_divergence = cmp["jsDivergence"]
                self._apply_exclusions(m, sd is not None)
                f_metrics.append(m)
                metrics.append(m)

            # a map feature with NO discovered keys (all rows empty) would
            # otherwise produce zero metrics and dodge the fill checks an
            # equally-empty scalar feature fails — fall back to whole-column
            # fill rates
            whole_column_fallback = not f_metrics
            if whole_column_fallback:
                col = table[f.name]
                m = FeatureMetrics(
                    name=f.name, key=None,
                    train_fill_rate=(float(col.valid_mask().mean())
                                     if len(col) else 0.0))
                if score_table is not None and f.name in score_table.column_names:
                    scol = score_table[f.name]
                    m.score_fill_rate = (float(scol.valid_mask().mean())
                                         if len(scol) else 0.0)
                    m.fill_rate_delta = abs(m.train_fill_rate - m.score_fill_rate)
                    lo = min(m.train_fill_rate, m.score_fill_rate)
                    hi = max(m.train_fill_rate, m.score_fill_rate)
                    m.fill_ratio_diff = float(np.inf) if lo == 0 else hi / lo
                self._apply_exclusions(m, m.score_fill_rate is not None)
                f_metrics.append(m)
                metrics.append(m)

            if f.name in self.protected_features:
                for m in f_metrics:
                    if m.exclusion_reasons:
                        m.exclusion_reasons = [
                            r + " (protected, kept)" for r in m.exclusion_reasons]
                continue
            is_map = table[f.name].kind == "map" and not whole_column_fallback
            if is_map and len(f_metrics) > 0:
                bad_keys = [m.key for m in f_metrics
                            if m.exclusion_reasons and m.key is not None]
                all_bad = bad_keys and len(bad_keys) == len(f_metrics)
                if all_bad:
                    excluded_features.append(f.name)
                elif bad_keys:
                    excluded_map_keys[f.name] = bad_keys
            elif any(m.exclusion_reasons for m in f_metrics):
                excluded_features.append(f.name)

        results = RawFeatureFilterResults(
            config={
                "bins": self.bins, "minFillRate": self.min_fill_rate,
                "maxFillDifference": self.max_fill_difference,
                "maxFillRatioDiff": self.max_fill_ratio_diff,
                "maxJSDivergence": self.max_js_divergence,
                "maxCorrelation": self.max_correlation,
                "correlationType": self.correlation_type,
            },
            metrics=metrics,
            excluded_features=sorted(excluded_features),
            excluded_map_keys=excluded_map_keys,
        )

        cleaned = self._clean_table(table, excluded_features, excluded_map_keys)
        blacklist = [f for f in raw_features if f.name in set(excluded_features)]
        return cleaned, blacklist, results

    def _apply_exclusions(self, m: FeatureMetrics, has_score: bool) -> None:
        """Reference ColumnStatistics/ExclusionReasons logic (:302+)."""
        if m.train_fill_rate < self.min_fill_rate:
            m.exclusion_reasons.append(
                f"train fill rate {m.train_fill_rate:.4f} below "
                f"{self.min_fill_rate}")
        if has_score:
            if m.score_fill_rate is not None and m.score_fill_rate < self.min_fill_rate:
                m.exclusion_reasons.append(
                    f"score fill rate {m.score_fill_rate:.4f} below "
                    f"{self.min_fill_rate}")
            if m.fill_rate_delta is not None and m.fill_rate_delta > self.max_fill_difference:
                m.exclusion_reasons.append(
                    f"fill rate delta {m.fill_rate_delta:.4f} above "
                    f"{self.max_fill_difference}")
            if m.fill_ratio_diff is not None and m.fill_ratio_diff > self.max_fill_ratio_diff:
                m.exclusion_reasons.append(
                    f"fill ratio diff {m.fill_ratio_diff:.2f} above "
                    f"{self.max_fill_ratio_diff}")
            if m.js_divergence is not None and m.js_divergence > self.max_js_divergence:
                m.exclusion_reasons.append(
                    f"JS divergence {m.js_divergence:.4f} above "
                    f"{self.max_js_divergence}")
        if (m.null_label_correlation is not None
                and abs(m.null_label_correlation) > self.max_correlation):
            m.exclusion_reasons.append(
                f"null-label correlation {m.null_label_correlation:.4f} above "
                f"{self.max_correlation} (leakage)")

    @staticmethod
    def _clean_table(table: FeatureTable, excluded: List[str],
                     excluded_keys: Dict[str, List[str]]) -> FeatureTable:
        out = table.drop([n for n in excluded if n in table.column_names])
        for name, keys in excluded_keys.items():
            if name not in out.column_names:
                continue
            col = out[name]
            gone = set(keys)
            vals = np.empty(len(col), dtype=object)
            for i, v in enumerate(col.values):
                vals[i] = (None if v is None
                           else {k: x for k, x in v.items() if str(k) not in gone})
            mask = np.array([v is not None and len(v) > 0 for v in vals])
            out = out.with_column(name, Column(col.feature_type, vals, mask,
                                               col.metadata))
        return out
