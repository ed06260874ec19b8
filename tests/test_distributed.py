"""Two-process jax.distributed bootstrap over local CPU (VERDICT r2 #9).

The analog of the reference's Spark driver/executor bootstrap
(OpWorkflowRunner.scala:70-459): two REAL processes join through
``parallel.distributed.initialize``, agree on process roles, run a global
row-sharded reduction spanning both hosts' devices, and synchronize with
``barrier``. This is the closest a single machine gets to a pod — the same
code paths jax.distributed uses across TPU hosts, minus ICI.
"""
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_WORKER = textwrap.dedent("""
    import os, sys
    port, pid = sys.argv[1], int(sys.argv[2])
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    sys.path.insert(0, {repo!r})
    from transmogrifai_tpu.parallel import distributed

    distributed.initialize(coordinator_address=f"127.0.0.1:{{port}}",
                           num_processes=2, process_id=pid)
    assert jax.process_count() == 2, jax.process_count()
    assert distributed.is_primary() == (pid == 0)

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()           # global: one cpu device per process
    assert len(devs) == 2, devs
    mesh = Mesh(np.array(devs), ("data",))
    sh = NamedSharding(mesh, P("data", None))
    full = np.arange(32, dtype=np.float32).reshape(8, 4)
    local = full[pid * 4:(pid + 1) * 4]
    arr = jax.make_array_from_process_local_data(sh, local, full.shape)
    out = jax.jit(lambda a: a.sum(axis=0),
                  out_shardings=NamedSharding(mesh, P(None)))(arr)
    np.testing.assert_allclose(np.asarray(out), full.sum(axis=0))
    distributed.barrier("test-done")
    print(f"proc {{pid}} OK", flush=True)
""")


def test_two_process_cpu_cluster(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = tmp_path / "worker.py"
    script.write_text(_WORKER.format(repo=repo))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(port), str(pid)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        cwd=str(tmp_path))
        for pid in (0, 1)]
    outs = []
    for pid, p in enumerate(procs):
        out, _ = p.communicate(timeout=150)
        outs.append(out.decode())
    if any("Multiprocess computations aren't implemented on the CPU backend"
           in o for o in outs):
        # environment-bound: this jaxlib's CPU PJRT client has no
        # cross-process collective support (the sharded jit sum spanning
        # both hosts' devices is exactly the capability being probed) —
        # the bootstrap/role/barrier layer above it cannot be exercised
        # end-to-end without it. Runs unskipped on TPU pods and on jaxlib
        # builds with the CPU collectives plugin (gloo/mpi).
        pytest.skip("jaxlib CPU backend lacks multiprocess collectives "
                    "(XLA: 'Multiprocess computations aren't implemented on "
                    "the CPU backend')")
    for pid, p in enumerate(procs):
        assert p.returncode == 0, f"proc {pid} failed:\n{outs[pid][-3000:]}"
    assert "proc 0 OK" in outs[0]
    assert "proc 1 OK" in outs[1]


def test_initialize_logs_on_autodiscovery_failure(monkeypatch, caplog):
    """Auto-discovery failures are logged, never silently swallowed."""
    import logging

    import jax

    from transmogrifai_tpu.parallel import distributed

    def boom(*a, **k):
        raise RuntimeError("no coordinator here")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
    with caplog.at_level(logging.WARNING,
                         logger="transmogrifai_tpu.parallel.distributed"):
        distributed.initialize()
    assert any("auto-discovery failed" in r.message for r in caplog.records)


def test_initialize_explicit_coordinator_fails_loud(monkeypatch):
    """An explicitly configured coordinator must raise on failure."""
    import jax

    from transmogrifai_tpu.parallel import distributed

    def boom(*a, **k):
        raise RuntimeError("bad coordinator")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    with pytest.raises(RuntimeError, match="bad coordinator"):
        distributed.initialize(coordinator_address="127.0.0.1:1",
                               num_processes=2, process_id=0)
