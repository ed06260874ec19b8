"""Every file the manifest names loads, and the manifest keeps to the
contract's forms: names, units, keys, bounds, chips. A per-layer entry is one
reader with the cells that read it listed in it: every (entry, cell) pairing
loads, no reader stands twice, ``layer_metrics/`` holds one file an entry, and
the contract's caps are held here, once."""
import ast
import functools
import glob
import importlib.util
import json
import os
import re

import pytest

from benchmark import harness, readers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest(ROOT)


def test_keys_are_exactly_the_contracts(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_every_name_and_unit_has_the_contracts_form(manifest):
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[section]:
            assert NAME.fullmatch(e["name"]), e["name"]
            names.append((section[:3] == "con", e["name"]))
    assert len(set(names)) == len(names)
    for w in manifest["workloads"]:
        assert NAME.fullmatch(w["config"]) and NAME.fullmatch(w["traffic"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    metric_names = [m["name"] for m in
                    manifest["end_to_end"] + manifest["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for c in manifest["configs"]:
        for key in c["reduced"]:
            assert NAME.fullmatch(key)


def test_setup_s_is_there_and_every_arrow_ends_on_a_metric(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in cells
            assert harness._in_cell(e2e[m["moves"]], w)
    for w in cells:
        mine = [m for m in manifest["end_to_end"] if harness._in_cell(m, w)]
        assert len(mine) >= 2        # setup_s and one other


def test_every_cell_loads_with_its_files(manifest):
    for w in manifest["workloads"]:
        cell = harness.load_cell(ROOT, manifest, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        assert cell.per_layer, w["name"]
        assert set(cell.traffic["reports"]) <= {m["name"]
                                                for m in cell.end_to_end}
        harness.loop_for(cell.traffic["kind"])
        for spec in cell.per_layer:
            from benchmark import readers
            readers.reader_for(spec["read"]["kind"], spec["bench_dir"])


def test_every_data_file_is_json_and_is_used(manifest):
    used = {os.path.join(ROOT, c["file"]) for c in manifest["configs"]}
    used |= {os.path.join(ROOT, "benchmark", "traffic",
                          w["traffic"] + ".json")
             for w in manifest["workloads"]}
    used |= {os.path.join(ROOT, "benchmark", "layer_metrics",
                          m["name"] + ".json")
             for m in manifest["per_layer"]}
    for sub in ("configs", "traffic", "layer_metrics"):
        for path in glob.glob(os.path.join(ROOT, "benchmark", sub, "*")):
            with open(path) as f:
                d = json.load(f)
            assert d["name"] == os.path.splitext(os.path.basename(path))[0]
            assert path in used, f"{path} is named by no entry"


def test_configurations_state_their_cuts(manifest):
    for c in manifest["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        if "rows" in c["reduced"]:
            assert cfg["rows"] < cfg["source_rows"]


# -- one entry a reader, with its cells listed in it --------------------------

def _pairings():
    m = harness.load_manifest(ROOT)
    cells = [w["name"] for w in m["workloads"]]
    return [(e["name"], c) for e in m["per_layer"]
            for c in e.get("workloads", cells)]


@pytest.fixture(scope="module")
def cells(manifest):
    return {w["name"]: harness.load_cell(ROOT, manifest, w["name"])
            for w in manifest["workloads"]}


def _benchmark_imports(module, seen):
    """The benchmark's own modules in the import closure of ``module``,
    read from the source: what a process that runs one traffic kind has
    imported, whatever this test process has."""
    path = os.path.join(ROOT, *module.split(".")) + ".py"
    if module in seen or not os.path.exists(path):
        return seen
    seen.add(module)
    package = module.rpartition(".")[0]
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = importlib.util.resolve_name(
                "." * node.level + (node.module or ""), package)
            found = [base] + [f"{base}.{a.name}" for a in node.names]
        else:
            continue
        for name in found:
            if name.startswith("benchmark."):
                _benchmark_imports(name, seen)
    return seen


@functools.lru_cache(maxsize=None)
def _kinds_a_cell_can_read(traffic_kind):
    """Reader kinds a run of this traffic kind finds: the readers' own, the
    files under ``reader_kinds/``, and those a module registers in
    ``readers.KINDS`` when the traffic kind imports it."""
    harness.loop_for(traffic_kind)
    imported = _benchmark_imports(f"benchmark.kinds.{traffic_kind}", set())
    files = {os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(ROOT, "benchmark", "reader_kinds", "*.py"))}
    return files | {k for k, f in readers.KINDS.items()
                    if f.__module__ in imported | {"benchmark.readers"}}


@pytest.mark.parametrize("name,cell_name", _pairings())
def test_a_cell_loads_each_entry_that_lists_it(manifest, cells, name,
                                               cell_name):
    (entry,) = [e for e in manifest["per_layer"] if e["name"] == name]
    cell = cells[cell_name]
    (spec,) = [s for s in cell.per_layer if s["name"] == name]
    # the file agrees with its entry key for key, the cells listed too
    assert {k: spec[k] for k in entry} == entry
    assert set(spec) == set(entry) | {"what", "read", "bench_dir"}
    assert len(spec["what"]) > 20
    # the cell reports the end-to-end metric that the entry moves
    assert entry["moves"] in {m["name"] for m in cell.end_to_end}
    assert entry["moves"] in set(cell.traffic["reports"]) | {"setup_s"}
    # the reader's kind is there in a process that runs this cell's traffic
    # kind, and says nothing of an empty run
    assert spec["read"]["kind"] in _kinds_a_cell_can_read(
        cell.traffic["kind"])
    assert readers.read_metric(spec, readers.Readings()) is None


def test_no_two_entries_share_a_reader(manifest):
    """A reader under a second name is a copy: list the cell in the entry
    that is there."""
    seen = {}
    for e in manifest["per_layer"]:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               e["name"] + ".json")) as f:
            read = json.dumps(json.load(f)["read"], sort_keys=True)
        key = (read,) + tuple(e[k] for k in ("unit", "better", "source",
                                             "layer", "moves"))
        assert key not in seen, f"{e['name']} is {seen[key]}'s reader"
        seen[key] = e["name"]


def test_layer_metrics_holds_exactly_one_file_an_entry(manifest):
    held = sorted(os.listdir(os.path.join(ROOT, "benchmark",
                                          "layer_metrics")))
    assert held == sorted(e["name"] + ".json" for e in manifest["per_layer"])


def test_every_cell_named_in_a_workloads_list_is_a_cell(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    for e in manifest["end_to_end"] + manifest["per_layer"]:
        listed = e.get("workloads", cells)
        assert listed and set(listed) <= set(cells), e["name"]
        # in the manifest's order of cells, each once
        assert listed == [c for c in cells if c in listed], e["name"]


def test_the_manifest_stays_inside_the_contracts_counts(manifest):
    """The caps, here and nowhere else: a PR that adds a cell or a metric
    edits no test."""
    assert len(manifest["per_layer"]) <= 128
    assert len(manifest["workloads"]) <= 24 and len(manifest["configs"]) <= 24
    assert len(manifest["end_to_end"]) <= 16
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    for e in manifest["configs"] + manifest["workloads"]:
        assert len(e["why"]) <= 200 and len(e.get("source", "")) <= 200
