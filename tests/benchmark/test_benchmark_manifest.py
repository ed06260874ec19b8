"""Every file the manifest names loads, and the manifest keeps to the
contract's forms: names, units, keys, bounds, chips."""
import glob
import json
import os
import re

import pytest

from benchmark import harness

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
