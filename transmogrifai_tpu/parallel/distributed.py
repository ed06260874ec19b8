"""Multi-host bootstrap over jax.distributed.

The reference scales out through Spark's driver/executor cluster (reference:
OpWorkflowRunner/OpApp submitting to a Spark master; shuffle + netty RPC as
the communication backend, SURVEY §2.10 P5). Here the cluster substrate is
``jax.distributed``: each host process calls :func:`initialize`, after which
``jax.devices()`` is the GLOBAL device list and the same ``Mesh``-based code
(mesh.py, sharded.py) spans hosts — XLA routes collectives over ICI within a
TPU slice and DCN across slices. Nothing else in the framework changes
between one chip and a multi-host pod: that is the point of the design.

Typical pod usage (one process per host)::

    from transmogrifai_tpu.parallel import distributed, make_mesh, MeshSpec
    distributed.initialize()              # env-driven on TPU pods
    mesh = make_mesh(MeshSpec(data=-1, model=4))
    workflow.with_mesh(mesh).train()
"""
from __future__ import annotations

import logging
import os
from typing import Optional

import jax

logger = logging.getLogger(__name__)


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join (or bootstrap) the multi-host runtime.

    On TPU pods all three arguments are discovered from the environment by
    ``jax.distributed.initialize`` (TPU metadata); on CPU/GPU clusters pass
    them explicitly or via ``JAX_COORDINATOR_ADDRESS`` /
    ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``. Idempotent: a second call in
    the same process is a no-op, and single-process runs (no coordinator
    discoverable) are left untouched."""
    # already-initialized check WITHOUT touching jax.process_count(): that
    # would initialize the XLA backend, after which jax.distributed refuses
    # to start (it must run before any backend init)
    if jax.distributed.is_initialized():
        return
    coordinator_address = (coordinator_address
                           or os.environ.get("JAX_COORDINATOR_ADDRESS"))
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])
    if coordinator_address is None and num_processes is None:
        # TPU pod: fully env-discovered; plain single process: nothing to do.
        # Failures here are LOGGED, not swallowed — a wedged pod bootstrap
        # must be visible even though single-process fallback is legitimate
        try:
            jax.distributed.initialize()
        except Exception as e:  # pragma: no cover - env specific
            logger.warning(
                "jax.distributed auto-discovery failed (%s: %s); continuing "
                "single-process. If this host is part of a pod, set "
                "JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / "
                "JAX_PROCESS_ID explicitly.", type(e).__name__, e)
        return
    # explicitly configured coordinator: fail loud — a typo'd address or a
    # missing peer must never silently degrade a pod job to one host
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def _count_transfer_bytes(arr, direction: str) -> None:
    """Fold one successful link crossing into the transfer accounting
    (tg_transfer_bytes_total{direction=h2d|d2h}) — zero-write when metrics
    are off, so the hot path pays nothing un-observed. Device→device
    re-placements count as h2d: the packed-upload A/B wants every
    placement visible."""
    from ..observability import metrics as _obs_metrics
    if not _obs_metrics.metrics_enabled():
        return
    nbytes = getattr(arr, "nbytes", None)
    if nbytes:
        _obs_metrics.inc_counter(
            "tg_transfer_bytes_total", float(nbytes), direction=direction,
            help="bytes moved across the host<->device link")


def fetch_to_host(arr, policy=None, site: str = "distributed.to_host"):
    """Device→host transfer guarded by a retry policy.

    The host link can fail transiently (UNAVAILABLE / connection resets)
    where the device work did not; a failed metric transfer used to abort
    the whole sweep even though the device result was
    intact and re-readable. Retries re-issue only the transfer — device
    state is untouched. Deterministic fault site: ``distributed.to_host``."""
    import numpy as np

    from ..robustness import faults
    from ..robustness.policy import RetryPolicy
    policy = policy or RetryPolicy(base_delay=0.01)

    def pull():
        faults.inject(site)
        return np.asarray(arr)

    out = policy.execute(pull, site=site)
    _count_transfer_bytes(out, "d2h")
    return out


def retrying_device_put(x, sharding=None, policy=None,
                        site: str = "distributed.device_put"):
    """Host→device placement guarded by a retry policy (the dual of
    :func:`fetch_to_host`). Fault site: ``distributed.device_put``."""
    from ..robustness import faults
    from ..robustness.policy import RetryPolicy
    policy = policy or RetryPolicy(base_delay=0.01)

    def put():
        faults.inject(site)
        return (jax.device_put(x, sharding) if sharding is not None
                else jax.device_put(x))

    out = policy.execute(put, site=site)
    _count_transfer_bytes(out, "h2d")
    return out


def is_primary() -> bool:
    """True on the process that should write models/metrics (the reference's
    driver role)."""
    return jax.process_index() == 0


def barrier(name: str = "sync") -> None:
    """Cross-host synchronization point (e.g. before reading a model another
    host just wrote)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)
