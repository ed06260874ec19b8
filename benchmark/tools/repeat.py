"""Diagnostics on the chip, not part of a benchmark run.

    python3 benchmark/tools/repeat.py --workload <cell> --seeds 1,2,3 \
        --ops 10 [--control 3]

For each seed, in ONE process: the cell's set-up, then ``--ops`` timed
operations with the program's spans on, printing for each its wall, what
the process spent meanwhile (CPU seconds, page faults, context switches:
an operation that runs long with none of these was waiting, not working),
the span metrics of that one operation, the winner and whether anything
compiled; then the cell's checks (every number beside its limit). For the
first ``--control`` seeds the same numbers follow with the controls in the
program's place: the bfloat16 reference and, where the traffic kind has
one, the program's own lower-precision path. This is how PERF.md's ten-train
diagnosis and the limits' readings were taken. It does not set a traffic
file's ``process_env`` as ``run.py`` does: start it under the settings you
want to read.
"""
import time

_T0 = time.perf_counter()

import argparse     # noqa: E402
import gc           # noqa: E402
import os           # noqa: E402
import resource     # noqa: E402
import statistics   # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _spent(before, after) -> str:
    """What the process (all threads) used between two ``getrusage``."""
    d = {k: getattr(after, k) - getattr(before, k) for k in (
        "ru_utime", "ru_stime", "ru_minflt", "ru_majflt", "ru_nvcsw",
        "ru_nivcsw")}
    return (f"cpu {d['ru_utime']:.2f}+{d['ru_stime']:.2f}s faults "
            f"{d['ru_minflt']}/{d['ru_majflt']} switches "
            f"{d['ru_nvcsw']}/{d['ru_nivcsw']}")


def main(argv=None, root: str = ROOT, log=print) -> int:
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import harness, readers, workflows
    from benchmark.kinds import common

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--ops", type=int, default=10)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    cell = harness.load_cell(root, harness.load_manifest(root),
                             args.workload)
    harness.configure_jax(root)
    if jax.default_backend() != "tpu":
        print("repeat: no TPU", file=sys.stderr)
        return 2
    monitor = harness.Monitor().install()
    workflows.enable_metrics()
    log(f"device {harness.device_info()} imports "
        f"{time.perf_counter() - _T0:.2f}s")
    span_specs = [s for s in cell.per_layer
                  if s["read"]["kind"].startswith("span_")
                  and s["read"]["kind"] != "span_minus_device"]

    for index, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t_seed = time.perf_counter()
        monitor.phase = f"setup{seed}"
        ctx = harness.Context(cell, seed, 0.0, False, monitor, log)
        loop = harness.loop_for(cell.traffic["kind"])(ctx)
        loop.setup()
        log(f"seed {seed}: set-up {time.perf_counter() - t_seed:.2f}s, "
            f"{monitor.count(monitor.phase, harness.COMPILE_EVENT)} "
            f"programs built")
        walls = []
        for i in range(args.ops):
            monitor.phase = f"op{seed}.{i}"
            workflows.enable_spans(True)
            loop.prepare_op()
            if cell.traffic.get("collect_garbage_between_ops", True):
                gc.collect()
            used = resource.getrusage(resource.RUSAGE_SELF)
            a = time.perf_counter_ns()
            loop.op()
            b = time.perf_counter_ns()
            used = _spent(used, resource.getrusage(resource.RUSAGE_SELF))
            r = readers.Readings(ops=[(a, b)], monitoring=monitor.events)
            r.spans, r.epoch_ns = workflows.finished_spans()
            workflows.enable_spans(False)
            vals = {s["name"]: readers.read_metric(s, r) for s in span_specs}
            rep = getattr(loop, "reports", None)
            walls.append((b - a) / 1e9)
            log(f"  op {i}: wall {walls[-1]:.4f}s {used} "
                + " ".join(f"{k} {v:.4f}" for k, v in vals.items()
                           if v is not None)
                + f" built {monitor.count(monitor.phase, harness.COMPILE_EVENT)}"
                + (f" {rep[-1]['family']} {rep[-1]['hyper']}" if rep else ""))
        if walls:
            log(f"seed {seed}: walls median {statistics.median(walls):.4f} "
                f"min {min(walls):.4f} max {max(walls):.4f}; memory "
                f"{harness.memory_peak(cell.chips).get('peak_bytes_in_use')}")
        monitor.phase = f"check{seed}"
        t_check = time.perf_counter()
        for c in loop.check():
            log("  " + c.line())
        log(f"  the checks took {time.perf_counter() - t_check:.2f}s")
        if index < args.control:
            for c in common.control_checks(loop):
                log("  control(bf16 reference) " + c.line())
            if hasattr(loop, "program_control"):
                for c in loop.program_control():
                    log("  control(program's sweep path) " + c.line())
        del loop
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
