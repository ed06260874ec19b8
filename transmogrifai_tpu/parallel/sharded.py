"""Sharded execution of FeatureTable stats and ModelSelector sweeps.

The hot path (SURVEY §3.3): a ``|families| × |grid| × |folds|`` sweep. On one
chip it is a vmapped fit; across chips the batch axis shards over 'model' and
the row axis over 'data'. We annotate shardings with ``NamedSharding`` and let
pjit/XLA insert the psum collectives the reference got from Spark shuffles.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .collectives import reduce_scatter
from .mesh import data_parallel_sharding as row_sharding


def _pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def shard_table(table, mesh: Mesh):
    """Re-place every device-resident column row-sharded over 'data'.

    Rows are padded (with invalid/masked slots) to a multiple of the data-axis
    size so shards are equal — the analog of Spark repartitioning. Device-kind
    columns upload PACKED: all same-dtype columns stack into one (n_pad, W)
    block and all masks into one (n_pad, M) bool block, each transferred once
    with sharded layout (``P('data', None)``) and split back into per-column
    on-device views — O(dtypes) transfers instead of one per column, and the
    shards land directly on their owning chips (no replicate-then-reshard
    hop).
    """
    from ..observability import metrics as _obs_metrics
    from ..table import Column, FeatureTable
    from ..utils.padding import pad_rows, padded_valid_mask
    from .distributed import retrying_device_put
    n_data = mesh.shape["data"]
    n = table.num_rows
    n_pad = _pad_to(max(n, n_data), n_data)
    pad = n_pad - n

    # gather the packable device-kind columns: per-dtype value planes
    # (width-1 columns count as width-1 planes) + one shared mask plane list
    by_dtype: dict = {}
    masked: list = []
    for name in table.column_names:
        col = table[name]
        if col.kind not in ("real", "binary", "vector", "prediction"):
            continue
        v = pad_rows(col.values, n_pad)
        by_dtype.setdefault(str(v.dtype), []).append(
            (name, v.reshape(n_pad, -1)))
        if pad or col.mask is not None:
            masked.append((name, padded_valid_mask(col.mask, n, n_pad)))

    # byte accounting (tg_transfer_bytes_total) happens once inside
    # retrying_device_put — only the upload COUNT is recorded here
    transfers = 0
    dev_vals: dict = {}
    for dt, parts in by_dtype.items():
        host = (np.concatenate([v for _, v in parts], axis=1)
                if len(parts) > 1 else parts[0][1])
        block = retrying_device_put(
            jnp.asarray(host),
            NamedSharding(mesh, P("data", None)), site="shard_table.upload")
        transfers += 1
        off = 0
        for name, v in parts:
            w = v.shape[1]
            dev_vals[name] = block[:, off:off + w]
            off += w
    dev_masks: dict = {}
    if masked:
        mhost = np.stack([m for _, m in masked], axis=1)     # (n_pad, M)
        mblock = retrying_device_put(
            jnp.asarray(mhost),
            NamedSharding(mesh, P("data", None)), site="shard_table.upload")
        transfers += 1
        for i, (name, _) in enumerate(masked):
            dev_masks[name] = mblock[:, i]
    if transfers:
        _obs_metrics.inc_counter(
            "tg_device_transfer_total", float(transfers),
            help="host→device uploads (packed: see docs/plan.md)")

    cols = {}
    for name in table.column_names:
        col = table[name]
        vals, mask = col.values, col.mask
        if name in dev_vals:
            v = np.asarray(col.values)
            vals = (dev_vals[name] if v.ndim > 1
                    else dev_vals[name].reshape(n_pad))
            mask = dev_masks.get(name)
        elif pad:
            vals = pad_rows(vals, n_pad)
            mask = padded_valid_mask(mask, n, n_pad)
        cols[name] = Column(col.feature_type, vals, mask, col.metadata)
    key = table.key
    if key is not None and pad:
        key = np.concatenate([key, np.full(pad, None, dtype=object)])
    return FeatureTable(cols, num_rows=n_pad, key=key)


#: rows of the result each chip receives per step of ``take_rows``: a step's
#: temporary is ``data x TAKE_BLOCK`` rows on every chip
TAKE_BLOCK = 65536


def place_rows(host, mesh: Mesh, site: str = "mesh.place"):
    """Upload a HOST array with its rows sharded over 'data': every chip
    is sent its own rows and no chip ever holds the whole array (a
    ``jnp.asarray`` first would land it whole on the first device). The
    row count must divide by the data axis. Spanned as ``mesh.place``."""
    from ..observability.trace import span
    from .distributed import retrying_device_put
    host = np.asarray(host)
    with span("mesh.place", cat="train", bytes=int(host.nbytes),
              shards=int(mesh.shape["data"]), path="host_shards",
              site=site):
        return retrying_device_put(host, row_sharding(mesh, host.ndim),
                                   site=site)


@partial(jax.jit, static_argnames=("n_pad", "mesh"))
def pad_rows_sharded(X, n_pad: int, mesh: Mesh):
    """``jnp.pad`` of the row axis with zeros to ``n_pad`` (a multiple of
    the data axis) with the result's rows sharded over 'data', whatever
    ``X``'s placement: one program, no whole copy on any chip."""
    out = jnp.pad(X, ((0, n_pad - X.shape[0]),) + ((0, 0),) * (X.ndim - 1))
    return jax.lax.with_sharding_constraint(out, row_sharding(mesh, X.ndim))


@partial(jax.jit, static_argnames=("block", "mesh"))
def _take_rows(X, idx2, block: int, mesh: Mesh):
    n_data = mesh.shape["data"]
    n_local = X.shape[0] // n_data
    m_local = idx2.shape[1]
    steps = -(-m_local // block)
    tail = (1,) * (X.ndim - 1)

    def local(x, idx):                # x: this chip's rows; idx: (D, m_l)
        lo = jax.lax.axis_index("data") * n_local

        def step(j, out):
            # the last step starts early and does some rows again, so that
            # every step is ``block`` rows and ``out`` is never sliced
            at = jnp.minimum(j * block, m_local - block)
            want = jax.lax.dynamic_slice_in_dim(
                idx, at, block, axis=1).reshape(-1) - lo
            mine = (want >= 0) & (want < n_local)
            rows = jnp.where(mine.reshape((-1,) + tail),
                             x[jnp.clip(want, 0, n_local - 1)],
                             jnp.zeros((), x.dtype))
            # every row is held by exactly one chip: the sum is that row
            got = reduce_scatter(rows, "data")
            return jax.lax.dynamic_update_slice_in_dim(out, got, at, axis=0)

        out = jnp.zeros((m_local,) + x.shape[1:], x.dtype)
        return jax.lax.fori_loop(0, steps, step, out)

    spec = P("data", *([None] * (X.ndim - 1)))
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, P()),
                         out_specs=spec, check_vma=False)(X, idx2)


def take_rows(X, idx, mesh: Mesh, block: int = TAKE_BLOCK,
              site: str = "mesh.take_rows"):
    """``X[idx]`` for ``X`` with rows sharded over 'data', the result's
    rows sharded alike (chip k holds ``idx``'s k-th quarter, in order): the
    row gather of a table no chip holds whole. ``len(idx)`` must divide by
    the data axis: callers pad it, with row 0 under a False mask or with
    -1, which no chip holds and so comes back as a row of zeros.

    Every chip looks up, among ``data x block`` wanted rows at a time, those
    it holds (zeros elsewhere) and a reduce-scatter hands each chip its
    ``block`` of the sum: the values are the rows' own bits, no chip holds
    more than its shard of the result plus one step's temporary, and the
    rows cross the chip-to-chip links once. An eager ``X[idx]`` is
    partitioned as a masked gather and an all-reduce: the whole result on
    every chip."""
    n_data = mesh.shape["data"]
    idx = np.asarray(idx)
    m = int(idx.shape[0])
    if m % n_data:
        raise ValueError(f"take_rows: {m} rows do not divide by the data "
                         f"axis ({n_data})")
    if X.shape[0] % n_data:          # a table that could not be sharded
        X = pad_rows_sharded(X, _pad_to(X.shape[0], n_data), mesh)
    idx2 = idx.astype(np.int32).reshape(n_data, m // n_data)
    if not isinstance(X, jax.Array) or X.sharding != row_sharding(mesh,
                                                                  X.ndim):
        X = jax.device_put(X, row_sharding(mesh, X.ndim))
    from ..observability.trace import span
    block = max(min(int(block), m // n_data), 1)
    # the launch, not the gather: a span adds no sync
    with span("mesh.take_rows", cat="train", rows=m, rowsPerChip=m // n_data,
              rowBytes=int(np.prod(X.shape[1:], dtype=np.int64))
              * X.dtype.itemsize,
              shards=int(n_data), steps=-(-(m // n_data) // block),
              site=site):
        return _take_rows(X, idx2, block=block, mesh=mesh)


def sharded_fit_batch(family, X, y, weights, grid: Dict[str, jnp.ndarray],
                      num_classes: int, mesh: Mesh):
    """Run ``family.fit_batch`` with the config batch sharded over 'model' and
    rows over 'data'. Returns (params, scores) both model-sharded.

    The B axis is padded to a multiple of the model-axis size with repeated
    configurations (harmless: they are discarded by the caller's argmax over
    the original B prefix)."""
    n_model = mesh.shape["model"]
    B, n = weights.shape
    B_pad = _pad_to(B, n_model)
    if B_pad != B:
        idx = jnp.arange(B_pad) % B  # wrap-around repeat covers reps > B
        weights = weights[idx]
        grid = {k: v[idx] for k, v in grid.items()}

    x_sh = NamedSharding(mesh, P("data", None))
    row_sh = NamedSharding(mesh, P("data"))
    w_sh = NamedSharding(mesh, P("model", "data"))
    g_sh = NamedSharding(mesh, P("model"))
    X = jax.device_put(X, x_sh)
    y = jax.device_put(y, row_sh)
    weights = jax.device_put(weights, w_sh)
    grid = {k: jax.device_put(v, g_sh) for k, v in grid.items()}

    params = family.fit_batch(X, y, weights, grid, num_classes)
    scores = family.predict_batch(params, X, num_classes)
    return params, scores, B  # B = original (unpadded) batch size


def shard_rows(X, mask, mesh: Mesh):
    """Row-shard (X, mask) over 'data', padding to an equal-shard length.

    Pad rows carry mask=False so every masked kernel ignores them; callers
    that had no mask get the synthetic validity mask back. Returns
    (X_sharded, mask_sharded, original_n)."""
    X = jnp.asarray(X)
    n = X.shape[0]
    n_data = mesh.shape["data"]
    n_pad = _pad_to(max(n, n_data), n_data)
    if mask is None:
        mask = jnp.ones((n,), bool)
    mask = jnp.asarray(mask)
    if n_pad != n:
        X = jnp.pad(X, ((0, n_pad - n),) + ((0, 0),) * (X.ndim - 1))
        mask = jnp.pad(mask, ((0, n_pad - n),)
                       + ((0, 0),) * (mask.ndim - 1))
    X = jax.device_put(X, NamedSharding(
        mesh, P("data", *([None] * (X.ndim - 1)))))
    mask = jax.device_put(mask, NamedSharding(
        mesh, P("data", *([None] * (mask.ndim - 1)))))
    return X, mask, n


def sharded_col_stats(X, mask, mesh: Mesh):
    """colStats over row-sharded data — the reference's
    ``mllib.stat.Statistics.colStats`` (SanityChecker.scala:574-576) as one
    pjit program whose sums psum over ICI."""
    from ..ops.stats import col_stats
    X, mask, _ = shard_rows(X, mask, mesh)
    return col_stats(X, mask)
