"""``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of BENCHMARK.json on the machine it is
started on. The last line of its standard output is the result. It runs on
a TPU only: where JAX finds none, or fewer chips than the cell asks for, it
exits non-zero and prints no result.

A traffic file may state a ``process_env``: settings the process that
offers this traffic is started with, such as glibc's allocator thresholds
(read only at start-up). Where the environment differs from it, this
script sets it and starts itself again in the same process (``exec``),
before anything else is imported. Set-up is counted from the first start.
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_T0 = "TG_BENCH_T0"      # perf_counter (system-wide monotonic) at first start


def process_env(argv) -> dict:
    """The ``process_env`` of the traffic file of the cell ``--workload``
    names; empty where the arguments name no cell (``harness.main`` then
    says what is wrong)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import manifest
    name = None
    for i, a in enumerate(argv):
        if a == "--workload" and i + 1 < len(argv):
            name = argv[i + 1]
        elif a.startswith("--workload="):
            name = a.split("=", 1)[1]
    try:
        traffic = manifest.cell_files(ROOT, manifest.load_manifest(ROOT),
                                      name)[2]
        return {str(k): str(v) for k, v in manifest.load_json(traffic).get(
            "process_env", {}).get("set", {}).items()}
    except (KeyError, StopIteration, OSError, ValueError):
        return {}


def main() -> int:
    want = process_env(sys.argv[1:])
    if any(os.environ.get(k) != v for k, v in want.items()):
        os.environ.update(want)
        os.environ[_T0] = repr(time.perf_counter())
        os.execv(sys.executable, [sys.executable] + sys.argv)
    t_start = float(os.environ.pop(_T0, None) or time.perf_counter())
    from benchmark import harness
    return harness.main(sys.argv[1:], t_start, ROOT)


if __name__ == "__main__":
    sys.exit(main())
