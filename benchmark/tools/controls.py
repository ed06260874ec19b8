"""Diagnostics on the chip, not part of a benchmark run: the controls of a
cell whose traffic kind brings its own (``Loop.controls()``), beside the
sound checks, for several seeds in ONE process.

    python3 benchmark/tools/controls.py --workload <cell> --seeds 1,2

For each seed: the cell's set-up (one warm operation), one more operation,
the cell's checks (every number beside its limit), then the same numbers
with each control in the program's place. This is how the limits of
``train-kddcup99`` were read (PERF.md, section 2). Like ``repeat.py`` it
does not set a traffic file's ``process_env``.
"""
import argparse
import gc
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None, root: str = ROOT, log=print) -> int:
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import harness, workflows

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import jax
    cell = harness.load_cell(root, harness.load_manifest(root),
                             args.workload)
    harness.configure_jax(root)
    if jax.default_backend() != "tpu":
        print("controls: no TPU", file=sys.stderr)
        return 2
    monitor = harness.Monitor().install()
    workflows.enable_metrics()
    log(f"device {harness.device_info()}")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        loop = harness.loop_for(cell.traffic["kind"])(
            harness.Context(cell, seed, 0.0, False, monitor, log))
        loop.setup()
        loop.prepare_op()
        loop.op()
        log(f"seed {seed}: set-up and one operation "
            f"{time.perf_counter() - t0:.2f}s")
        t0 = time.perf_counter()
        for c in loop.check():
            log("  " + c.line())
        log(f"  the checks took {time.perf_counter() - t0:.2f}s")
        for name, checks in loop.controls().items():
            t0 = time.perf_counter()
            for c in checks:
                log(f"  control({name}) " + c.line())
            log(f"  control({name}) took {time.perf_counter() - t0:.2f}s")
        del loop
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
