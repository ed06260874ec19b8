"""Where a cell's files are, by the names in ``BENCHMARK.json``. Standard
library only: ``run.py`` reads a traffic file's ``process_env`` with this
before anything else is imported."""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Tuple


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell_files(root: str, manifest: Dict[str, Any], name: str
               ) -> Tuple[Dict[str, Any], str, str, str]:
    """(workload entry, configuration file, traffic file, the directory
    that holds ``configs/``, ``traffic/`` and ``layer_metrics/``)."""
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has {sorted(by_name)})")
    w = by_name[name]
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == w["config"])
    config = os.path.join(root, cfg_entry["file"])
    bench_dir = os.path.dirname(os.path.dirname(config))
    return w, config, os.path.join(bench_dir, "traffic",
                                   w["traffic"] + ".json"), bench_dir
