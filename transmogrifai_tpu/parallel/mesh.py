"""Device-mesh construction.

Replaces the reference's Spark cluster topology (driver + executors, reference:
core/.../OpWorkflowRunner.scala, utils/.../spark/) with a named
``jax.sharding.Mesh``. Axis conventions:

* ``data``  — row axis of the FeatureTable (P1 in SURVEY §2.10): every
  per-row map and monoid reduce shards here; XLA turns reduces into psum
  over ICI.
* ``model`` — the hyperparameter × fold batch axis of ModelSelector sweeps
  (P2): each chip fits its slice of configurations independently.

Multi-host: under ``jax.distributed`` the same code sees the global device
list, ICI within a slice and DCN across slices — nothing here changes.
"""
from __future__ import annotations

import os

from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: sweep-engagement cost model (docs/parallel.md "The downgrade cost
#: model"). Engaging the mesh prices in per-program collectives (psums over
#: every cross-row reduce of the fit), cross-device layout moves around the
#: config axis, and the GSPMD partitioner's fixed per-program overhead —
#: none of which shrink with the problem. The thresholds' origin: timed on
#: eight VIRTUAL CPU devices of one host (shared cores, so the ratio
#: isolates overhead from parallel win; rounds 6-20): at 8192 rows/chip the
#: sharded sweep executed ~2.5x the single-device fused wall there; the
#: overhead first fell inside run-to-run noise above ~16k rows per chip and
#: a handful of configs per model shard. The TPU has not confirmed them:
#: the one four-chip cell (`train-airline-10m-mesh`, PR 34: the sweep's
#: 1 M-row table, 250 000 rows a chip, 135 configs) lies far above both and
#: engages; it says nothing about where the line is, and a tree family's
#: sweep fit reads 8192 sampled rows (2048 a chip) whatever the table, a
#: case the model does not price (PERF.md section 7; ROADMAP S8). Below the
#: thresholds the sweep
#: transparently downgrades to the single-device fused path — bit-identical
#: results, observable via tg_mesh_downgrade_total + span event.
MESH_MIN_ROWS_PER_CHIP_ENV = "TG_MESH_MIN_ROWS_PER_CHIP"
MESH_MIN_CONFIGS_PER_CHIP_ENV = "TG_MESH_MIN_CONFIGS_PER_CHIP"
MESH_FORCE_ENV = "TG_MESH_FORCE"
DEFAULT_MIN_ROWS_PER_CHIP = 16384
DEFAULT_MIN_CONFIGS_PER_CHIP = 4


def sweep_mesh_decision(mesh: Mesh, n_rows: int,
                        n_configs: int) -> Tuple[bool, Dict[str, object]]:
    """Engage-or-downgrade decision for a ``|configs| × rows`` sweep.

    Returns ``(engage, detail)``; ``detail`` carries the measured sizes and
    thresholds for the downgrade span event. ``TG_MESH_FORCE=1`` pins the
    mesh on regardless (mesh-path tests, chip_smoke.py); setting either
    threshold env var to 0 disables that axis of the check."""
    if os.environ.get(MESH_FORCE_ENV, "") in ("1", "true"):
        return True, {"forced": True}
    min_rows = int(os.environ.get(MESH_MIN_ROWS_PER_CHIP_ENV,
                                  DEFAULT_MIN_ROWS_PER_CHIP))
    min_cfg = int(os.environ.get(MESH_MIN_CONFIGS_PER_CHIP_ENV,
                                 DEFAULT_MIN_CONFIGS_PER_CHIP))
    rows_per_chip = n_rows / max(mesh.shape.get("data", 1), 1)
    cfg_per_chip = n_configs / max(mesh.shape.get("model", 1), 1)
    detail = {
        "rowsPerChip": int(rows_per_chip), "minRowsPerChip": min_rows,
        "configsPerChip": int(cfg_per_chip), "minConfigsPerChip": min_cfg,
        "meshShape": dict(mesh.shape),
    }
    engage = rows_per_chip >= min_rows and cfg_per_chip >= min_cfg
    return engage, detail


def mesh_span_attrs(mesh: Optional[Mesh], engaged: bool,
                    rows: int) -> Dict[str, object]:
    """What a span says of the mesh its program was given: the axes asked
    for (1 and 1 without a mesh), whether it ``engaged`` (the sweep's cost
    model may say no), and ``rowsPerChip``: ``rows``, the rows of the table
    the program reads, over the data axis where it engaged."""
    shape = mesh.shape if mesh is not None else {}
    n_data = int(shape.get("data", 1))
    return {"meshData": n_data, "meshModel": int(shape.get("model", 1)),
            "engaged": bool(engaged),
            "rowsPerChip": int(rows) // (n_data if engaged else 1)}


@dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape; axes sized -1 absorb remaining devices."""
    data: int = -1
    model: int = 1

    def resolve(self, n_devices: int) -> Tuple[int, int]:
        data, model = self.data, self.model
        if data == -1 and model == -1:
            raise ValueError("only one mesh axis may be -1")
        if model == -1:
            model = n_devices // max(data, 1)
        if data == -1:
            data = n_devices // max(model, 1)
        if data * model != n_devices:
            raise ValueError(
                f"mesh {data}x{model} does not cover {n_devices} devices")
        return data, model


def make_mesh(spec: MeshSpec = MeshSpec(),
              devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    data, model = spec.resolve(len(devices))
    arr = np.asarray(devices).reshape(data, model)
    return Mesh(arr, axis_names=("data", "model"))


def default_mesh() -> Mesh:
    """All visible devices on the data axis (pure data parallelism)."""
    return make_mesh(MeshSpec(data=-1, model=1))


def data_parallel_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Shard axis 0 (rows) over 'data', replicate the rest."""
    return NamedSharding(mesh, P("data", *([None] * (ndim - 1))))
