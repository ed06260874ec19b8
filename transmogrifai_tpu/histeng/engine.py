"""Histogram engine: one tree-growth primitive for in-core, streaming, mesh.

``build_node_hist`` produces (node, feature, bin) sufficient statistics for
histogram tree growth behind one contract with three backends:

=============  ==========================  ==================================
backend        selected when               implementation
=============  ==========================  ==================================
``xla``        device arrays (default      K-blocked one-hot einsum with
               off-TPU, or pallas          pinned combine order
               disabled)                   (`kernels._hist_xla_pinned`)
``pallas``     device arrays on TPU with   VMEM one-hot expansion kernel
               TG_TREE_PALLAS unset/1      (`kernels._hist_pallas`)
``host``       numpy inputs                flat-index ``np.bincount``,
                                           bit-equal to StreamingGBT's
                                           legacy inline block (`host`)
=============  ==========================  ==================================

Determinism: the xla backend's K row blocks (K = TG_HIST_SHARDS, default 8)
and explicit pairwise combine make the contraction's floating-point result a
pinned expression — the same bits single-device and with rows sharded over a
mesh 'data' axis. The fused sweep path activates `engine_mesh` around its
program traces so the blocks carry 'data'-axis sharding constraints; tree
sweeps are then bit-identical across topologies the way linear families
already were (docs/trees.md).

Env knobs: TG_HIST_SHARDS (pinned block count, default 8; 0/1 → plain
einsum), TG_TREE_PALLAS (0/1 forces the ``xla``/``pallas`` backend). Both
are read at trace time.

Chaos: ``chaos_gate(family)`` is the host-side ``hist.build`` fault site —
the fused sweep dispatcher calls it once per tree-family program dispatch,
and a raise there quarantines that family exactly like
``validator.family_fit`` (typed error, NaN placeholder, other families keep
racing). Divergence from the fault-free baseline is allowed
(``bit_equal=False``): the quarantined family's metrics are gone, so the
winner may legitimately differ.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Sequence

import numpy as np

from .host import bin_codes_host, build_node_hist_host, node_stat_sums
from .kernels import (_ENGINE_MESH, _hist_shards, _make, current_engine_mesh,
                      hist_matmul, node_hist_matmul, pinned_row_sum)

__all__ = [
    "build_hist", "build_node_hist", "bin_codes_host", "chaos_gate",
    "node_stat_sums",
    "clear_engine_caches", "current_engine_mesh", "engine_mesh",
    "engine_probe", "hist_matmul", "node_hist_matmul", "pinned_row_sum",
]


@contextmanager
def engine_mesh(mesh):
    """Activate ``mesh`` as the engine's sharding target for the duration of
    the block (``None``: no-op). Must wrap the *trace* from outside (the
    first call of a jitted fit / fused program, and any re-trace such as AOT
    export; ``jax.set_mesh`` refuses to be entered inside a trace) — the
    kernels read the context at trace time, like their env knobs.

    ``jax.set_mesh`` puts the mesh into jax's trace context, which is part
    of every jit cache key: a fit first traced single-device (Mosaic
    kernels, no row-block constraints) is re-traced here instead of being
    replayed from the trace cache into a sharded program."""
    if mesh is None:
        yield
        return
    import jax
    token = _ENGINE_MESH.set(mesh)
    try:
        with jax.set_mesh(mesh):
            yield
    finally:
        _ENGINE_MESH.reset(token)


def build_hist(codes, A, n_bins: int, exact: bool = False):
    """Flat-stat histogram build: hist[a, f·nb + b] = Σ_s A[s,a]·1[codes=b].

    The engine entry point for callers that fold node structure into the
    stat columns themselves (`models/trees.py` `_grow_tree`, diagonal leaf
    sums). See `kernels.hist_matmul` for the full contract.
    """
    return hist_matmul(codes, A, n_bins, exact=exact)


def build_node_hist(codes, node, stats: Sequence, n_bins: int, *,
                    n_nodes: int = 1, stride: int = 1):
    """(node, feature, bin) sufficient statistics — the one tree-growth
    primitive shared by in-core growers, StreamingGBT, and the mesh sweep.

    Device backends (jax inputs): ``codes`` (S, d) int32 row-major bin
    codes, ``node`` (S, T) int32 current slot per tree (values < 0 never
    match), ``stats``: k arrays (S, T) of per-tree row statistics,
    ``stride``: slot-id multiplier (2 = heap left-children). Returns
    (k, n_nodes, T, d, n_bins) f32 on device. ``codes`` (S, T, d_sub) gives
    every tree its own columns (a forest's drawn feature subsets, sentinel
    code ``n_bins`` where a tree has fewer): the same pinned contraction
    batched over trees, d_sub wide; returns (k, n_nodes, T, d_sub, n_bins).

    Host backend (numpy inputs): ``codes`` (d, n)
    int64 feature-major from `bin_codes_host` (feature-major on purpose —
    the bincount traversal order, and so the f64 sums bit for bit, depend
    on it), ``node`` (n,) int64, ``stats``: k entries each ``None``
    (unweighted count) or (n,) f64 weights; ``stride`` must be 1. Returns
    (k, n_nodes, d, n_bins) f64 — no tree axis, streamed growth is
    single-tree per pass.

    To shard the build's row blocks over a mesh's 'data' axis, trace the
    caller under `engine_mesh` (from outside any jit: it enters
    ``jax.set_mesh``).
    """
    if (isinstance(codes, np.ndarray) and codes.dtype.kind in "iu"
            and isinstance(node, np.ndarray)):
        if stride != 1:
            raise ValueError("host histogram backend is stride-1 only")
        return build_node_hist_host(codes, node, stats, n_bins, n_nodes)
    flat = node_hist_matmul(codes, node, list(stats), n_nodes, n_bins,
                            stride=stride)
    k = len(stats)
    T = node.shape[1]
    d = codes.shape[-1]
    return flat.reshape(k, n_nodes, T, d, n_bins)


def chaos_gate(family_name: str) -> None:
    """Fault site ``hist.build`` — fires before a tree family's histogram
    programs dispatch in the fused sweep; a raise quarantines the family
    (robustness/faults.py three-way table, docs/robustness.md)."""
    from ..robustness import faults
    faults.inject("hist.build", key=family_name)


def clear_engine_caches() -> None:
    """Drop the engine's own caches (the lru factory of custom_vmap
    contractions). Traced jit programs are unaffected — this exists so the
    per-test no-leak fixture can bound cross-test state."""
    _make.cache_clear()


def engine_probe() -> dict:
    """Invariant probe for the `oracles` no-leak check: the mesh context
    must be None between dispatches (a leaked context would silently shard
    the next single-device trace) and the factory cache stays bounded."""
    return {
        "mesh_ctx": current_engine_mesh(),
        "factory_cache": _make.cache_info().currsize,
        "shards": _hist_shards(),
    }
