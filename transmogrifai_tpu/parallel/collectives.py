"""Thin collectives layer over XLA's ICI/DCN primitives.

The reference's distributed-communication backend is Spark shuffle + netty RPC
+ Kryo broadcast (reference: utils/.../kryo/OpKryoRegistrator.scala; monoid
``reduce``/``reduceByKey`` calls throughout, e.g. SanityChecker.scala:433-440).
Here every cross-row reduction is an XLA collective over the named mesh —
psum/all_gather ride ICI within a slice, DCN across slices — and "collect to
driver" becomes a host_gather of an already-small device array.

These wrappers are for use inside ``jax.shard_map``-mapped functions; under
plain ``pjit`` XLA inserts equivalent collectives automatically from sharding
annotations, which is the preferred path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map  # noqa: F401  (re-exported: one name for callers)


def axis_size(axis_name: str) -> int:
    """STATIC size of a mapped mesh axis from inside ``shard_map`` (drives
    Python-level hop loops, so it must be a concrete int, not a traced
    ``psum(1)``)."""
    return jax.lax.axis_size(axis_name)


def psum(x, axis_name: str = "data"):
    return jax.lax.psum(x, axis_name)


def pmean(x, axis_name: str = "data"):
    return jax.lax.pmean(x, axis_name)


def pmax(x, axis_name: str = "data"):
    return jax.lax.pmax(x, axis_name)


def all_gather(x, axis_name: str = "data", axis: int = 0, tiled: bool = True):
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str = "data", scatter_dimension: int = 0):
    return jax.lax.psum_scatter(x, axis_name,
                                scatter_dimension=scatter_dimension,
                                tiled=True)


def host_gather(x) -> np.ndarray:
    """Fully replicate/gather a (small) device array back to the host — the
    analog of Spark ``collect()`` for summaries/vocabularies."""
    return np.asarray(jax.device_get(x))


def ring_allreduce(x, axis_name: str = "data"):
    """Bandwidth-optimal ring all-reduce built from ``ppermute`` hops.

    The explicit form of what XLA's psum lowers to on an ICI ring (the
    scaling-book recipe): reduce-scatter around the ring (N−1 hops, each
    device accumulating one shard), then all-gather the reduced shards
    (N−1 more hops). Shard-count = axis size; the leading axis of ``x``
    must be divisible by it. Use inside ``shard_map``; prefer plain psum
    unless you need to overlap the hops with compute — this exists so the
    comm layer's semantics are testable against psum hop by hop.
    """
    n = axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    shards = jnp.reshape(x, (n,) + (x.shape[0] // n,) + x.shape[1:])
    right = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter: after hop h, each device holds the running sum of
    # shard (idx - h) from its h left neighbors
    acc = shards
    send = shards[(idx - 0) % n]
    for h in range(1, n):
        recv = jax.lax.ppermute(send, axis_name, right)
        k = (idx - h) % n
        summed = acc[k] + recv
        acc = acc.at[k].set(summed)
        send = summed
    # device idx now owns the fully reduced shard (idx + 1) % n
    own = (idx + 1) % n
    # all-gather: circulate the reduced shards around the ring
    out = acc
    send = acc[own]
    for h in range(1, n):
        recv = jax.lax.ppermute(send, axis_name, right)
        k = (own - h) % n
        out = out.at[k].set(recv)
        send = recv
    return jnp.reshape(out, x.shape)


def reduce_by_key(values, keys, num_keys: int, axis_name: str = "data"):
    """Monoid ``reduceByKey`` over row-sharded data — the reference's
    contingency/vocabulary pattern (SanityChecker.scala:433-440): each
    device segment-sums its local rows by key, then one psum merges the
    per-key partials across the mesh. values: (rows_local, ...) with
    leading row axis; keys: (rows_local,) int32 in [0, num_keys)."""
    local = jax.ops.segment_sum(values, keys, num_segments=num_keys)
    return jax.lax.psum(local, axis_name)


def broadcast_from_primary(x, axis_name: str = "data"):
    """Value of ``x`` on device 0 of the axis, on every device — the analog
    of a Spark driver broadcast (fitted vocab/thresholds out to workers)."""
    idx = jax.lax.axis_index(axis_name)
    zeroed = jnp.where(idx == 0, x, jnp.zeros_like(x))
    return jax.lax.psum(zeroed, axis_name)
