"""AOT program store (transmogrifai_tpu/programstore/; docs/serving.md
"AOT cold start & the program store"): save-time populate → zero-compile
zero-retrace load with bit-equal outputs, the full fallback ladder (key
mismatch per component — fingerprint, bucket, jaxlib version, device
kind — plus corrupt blobs and the deterministic ``aot.load`` chaos site)
with the right ledger cause and a typed ``aot_fallback`` record, the
MANIFEST ``programs`` round-trip + corrupt-section tolerance, the store
GC bound, two-process populate-race safety over the atomic tmp+rename
writes, the cross-process sweep-program cache (``TG_AOT_STORE``), and
``cli.py programs`` list/verify/gc."""
import json
import os
import shutil
import subprocess
import sys
import threading

import numpy as np
import pandas as pd
import pytest

import transmogrifai_tpu as tg
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu import plan as plan_mod
from transmogrifai_tpu.impl.selector.factories import (
    BinaryClassificationModelSelector,
)
from transmogrifai_tpu.impl.tuning import validators as _validators
from transmogrifai_tpu.local import micro_batch_score_function
from transmogrifai_tpu.manifest import CheckpointManifest
from transmogrifai_tpu.observability import ledger as lg
from transmogrifai_tpu.persistence import FORMAT_VERSION, load_model
from transmogrifai_tpu.programstore import PROGRAMS_DIR, ProgramStore
from transmogrifai_tpu.programstore import store as ps
from transmogrifai_tpu.robustness import faults
from transmogrifai_tpu.robustness.policy import FaultLog
from transmogrifai_tpu.serving import ModelRegistry, ServeConfig
from transmogrifai_tpu.workflow import OpWorkflow

pytestmark = pytest.mark.aot


def _train_model(n=300, seed=7, d=2):
    rng = np.random.RandomState(seed)
    cols = {f"x{i + 1}": rng.randn(n) for i in range(d)}
    y = (sum(cols.values()) > 0).astype(float)
    df = pd.DataFrame({**cols, "y": y})
    label = FeatureBuilder.RealNN("y").extract_field().as_response()
    feats = [FeatureBuilder.Real(c).extract_field().as_predictor()
             for c in sorted(cols)]
    checked = tg.transmogrify(feats).sanity_check(label)
    pred = (BinaryClassificationModelSelector.with_cross_validation(
        seed=seed,
        models=[("OpLogisticRegression",
                 [{"regParam": 0.01, "elasticNetParam": 0.0}])])
        .set_input(label, checked).get_output())
    return (OpWorkflow().set_input_dataset(df)
            .set_result_features(pred).train())


def _rows(n, seed=3):
    rng = np.random.RandomState(seed)
    return [{"x1": float(rng.randn()), "x2": float(rng.randn())}
            for _ in range(n)]


@pytest.fixture(scope="module")
def model():
    return _train_model()


@pytest.fixture(scope="module")
def saved(model, tmp_path_factory):
    """One populated saved-model dir per module: ``save_model`` exports
    the serve programs into ``programs/`` + the manifest section."""
    path = str(tmp_path_factory.mktemp("aot") / "model")
    model.save(path)
    return path


@pytest.fixture(scope="module")
def baseline(model):
    return micro_batch_score_function(model)(_rows(6))


def _copy(saved, tmp_path):
    dst = str(tmp_path / "model")
    shutil.copytree(saved, dst)
    return dst


def _manifest_doc(path):
    with open(os.path.join(path, "MANIFEST.json")) as fh:
        return json.load(fh)


def _write_manifest_doc(path, doc):
    with open(os.path.join(path, "MANIFEST.json"), "w") as fh:
        json.dump(doc, fh, indent=1)


def _load_and_score(path, rows, cfg=None):
    """registry.load + score through the runtime; returns (records,
    runtime fault-log kinds, warm_info)."""
    cfg = cfg or ServeConfig(max_batch=256, max_queue=64, max_wait_ms=1.0)
    with ModelRegistry(cfg) as reg:
        rt = reg.load("m", path)
        recs = [reg.score("m", r, timeout=30) for r in rows]
        kinds = [r.kind for r in rt.fault_log.reports]
        info = dict(rt.warm_info or {})
    return recs, kinds, info


# ---------------------------------------------------------------------------
# The happy path: populate at save, deserialize at load, zero compiles
# ---------------------------------------------------------------------------

def test_save_populates_store_and_manifest(saved):
    progdir = os.path.join(saved, PROGRAMS_DIR)
    assert os.path.isdir(progdir)
    store = ProgramStore(progdir)
    entries = store.entries()
    assert entries, "save_model must export the serve-plan segments"
    assert store.verify() == []
    section = _manifest_doc(saved).get("programs", {})
    assert section.get("version") == 1
    assert set(section.get("entries", {})) == set(entries)
    assert section.get("planIdents"), "the plan identity must be covered"
    for meta in entries.values():
        assert meta["component"] == "plan-segment"
        assert meta["bucket"] == 256
        assert meta["jaxlib"] and meta["deviceKind"]


def test_aot_load_zero_compiles_and_bit_equal(saved, baseline):
    """The acceptance gate: with a populated store, ``registry.load()``
    + the first real request record ZERO CompileLedger builds, and every
    AOT-scored record is bit-identical to the freshly traced scorer."""
    plan_mod.clear_plan_cache()
    lg.ledger().clear()
    mark = lg.ledger().mark()
    recs, _kinds, info = _load_and_score(saved, _rows(6))
    built = lg.ledger().since(mark)
    assert built == [], json.dumps([r.to_json() for r in built], indent=1)
    assert recs == baseline
    assert info["aotHits"] >= 2 and info["aotMisses"] == 0
    assert info["compiles"] == 0
    st = ps.stats()
    assert st["hits"].get("plan-segment", 0) >= 2
    assert st["hits"].get("plan", 0) >= 1


def test_aot_disabled_falls_back_to_trace(saved, baseline, monkeypatch):
    monkeypatch.setenv("TG_AOT", "0")
    plan_mod.clear_plan_cache()
    lg.ledger().clear()
    mark = lg.ledger().mark()
    recs, _kinds, info = _load_and_score(saved, _rows(6))
    built = lg.ledger().since(mark)
    assert built, "TG_AOT=0 must trace like the pre-store warm path"
    assert all(r.cause == "cold" for r in built)
    assert recs == baseline
    assert info["aotHits"] == 0


# ---------------------------------------------------------------------------
# The fallback ladder: one rung per key component + corrupt artifacts
# ---------------------------------------------------------------------------

def _tamper_entries(path, **fields):
    doc = _manifest_doc(path)
    for meta in doc["programs"]["entries"].values():
        meta.update(fields)
    _write_manifest_doc(path, doc)


def test_jaxlib_mismatch_falls_back_typed(saved, baseline, tmp_path):
    path = _copy(saved, tmp_path)
    _tamper_entries(path, jaxlib="0.0.0-stale")
    plan_mod.clear_plan_cache()
    lg.ledger().clear()
    mark = lg.ledger().mark()
    recs, kinds, info = _load_and_score(path, _rows(6))
    assert recs == baseline
    assert "aot_fallback" in kinds
    # every SEGMENT missed (the plan-ident coverage hit is plan-level
    # bookkeeping, not a program)
    assert info["aotMisses"] >= 2
    assert ps.stats()["hits"].get("plan-segment", 0) == 0
    causes = {r.cause for r in lg.ledger().since(mark)
              if r.identity.endswith(("seg0", "seg1", "seg2"))}
    assert causes == {"aot-miss"}
    misses = ps.stats()["misses"]
    assert misses.get("jaxlib-mismatch", 0) >= 1


def test_device_kind_mismatch_falls_back_typed(saved, baseline, tmp_path):
    path = _copy(saved, tmp_path)
    _tamper_entries(path, deviceKind="tpu/TPU v9")
    plan_mod.clear_plan_cache()
    lg.ledger().clear()
    recs, kinds, _info = _load_and_score(path, _rows(6))
    assert recs == baseline
    assert "aot_fallback" in kinds
    assert ps.stats()["misses"].get("device-kind-mismatch", 0) >= 1


def test_fingerprint_mismatch_is_absent_miss(saved, baseline, tmp_path):
    """A schema the store was never populated for (different fingerprint
    => different key) misses `absent` — the populate path, no FaultLog
    noise — and the traced build still classifies aot-miss."""
    path = _copy(saved, tmp_path)
    doc = _manifest_doc(path)
    doc["programs"]["entries"] = {
        f"bogus{i}@256": dict(meta, keyId=f"bogus{i}@256",
                              fingerprint=f"bogus{i}")
        for i, meta in enumerate(doc["programs"]["entries"].values())}
    _write_manifest_doc(path, doc)
    plan_mod.clear_plan_cache()
    lg.ledger().clear()
    mark = lg.ledger().mark()
    recs, kinds, _info = _load_and_score(path, _rows(6))
    assert recs == baseline
    assert "aot_fallback" not in kinds  # absent is not a fault
    assert ps.stats()["misses"].get("absent", 0) >= 1
    seg_causes = {r.cause for r in lg.ledger().since(mark)
                  if "/seg" in r.identity}
    assert seg_causes == {"aot-miss"}


def test_bucket_miss_on_new_padding_bucket(saved, baseline):
    """The store holds bucket 256; a 300-row batch lands in bucket 512 —
    an absent miss for that key, traced bit-equal, while 256-bucket
    flushes keep hitting."""
    sess = ps.open_model_session(saved)
    assert sess is not None
    model2 = load_model(saved)
    plan_mod.clear_plan_cache()
    lg.ledger().clear()
    big = _rows(300, seed=11)
    out_aot = micro_batch_score_function(model2)(big)
    assert ps.stats()["misses"].get("absent", 0) >= 1
    seg_builds = [r for r in lg.ledger().entries() if "/seg" in r.identity]
    assert seg_builds and {r.bucket for r in seg_builds} == {512}
    assert {r.cause for r in seg_builds} == {"aot-miss"}
    ps.enable_aot(False)
    try:
        plan_mod.clear_plan_cache()
        out_traced = micro_batch_score_function(model2)(big)
    finally:
        ps.enable_aot(None)
    assert out_aot == out_traced


def test_corrupt_blob_falls_back_typed(saved, baseline, tmp_path):
    path = _copy(saved, tmp_path)
    progdir = os.path.join(path, PROGRAMS_DIR)
    for fname in os.listdir(progdir):
        if fname.endswith(".bin"):
            with open(os.path.join(progdir, fname), "r+b") as fh:
                fh.truncate(16)  # truncated artifact
    store = ProgramStore(progdir)
    assert store.verify(), "verify() must flag the truncated blobs"
    plan_mod.clear_plan_cache()
    lg.ledger().clear()
    recs, kinds, _info = _load_and_score(path, _rows(6))
    assert recs == baseline
    assert "aot_fallback" in kinds
    assert ps.stats()["misses"].get("corrupt", 0) >= 1
    # the fallback warm re-traced AND re-exported under the capture
    # scope: the store heals itself — content-addressed blob names are
    # REWRITTEN when the bytes on disk fail verification (a plain
    # exists-check would silently keep the truncated file), so the next
    # load deserializes again with zero builds
    assert store.verify() == []
    ps.close_sessions()
    plan_mod.clear_plan_cache()
    lg.ledger().clear()
    mark = lg.ledger().mark()
    recs2, kinds2, info2 = _load_and_score(path, _rows(6))
    assert recs2 == baseline
    assert lg.ledger().since(mark) == []
    assert info2["aotHits"] >= 2 and "aot_fallback" not in kinds2


@pytest.mark.chaos
def test_chaos_aot_load_site_bit_equal(saved, baseline):
    """The ``aot.load`` chaos site: an injected artifact fault at load
    degrades that segment to the trace path — bit-equal records, typed
    ``aot_fallback``, never an error to a request."""
    plan_mod.clear_plan_cache()
    lg.ledger().clear()
    with faults.injected({"aot.load": {"mode": "raise", "nth": 1,
                                       "count": 1}}):
        recs, kinds, info = _load_and_score(saved, _rows(6))
    assert recs == baseline
    assert "aot_fallback" in kinds
    assert info["aotMisses"] >= 1
    assert ps.stats()["misses"].get("deserialize-error", 0) >= 1


# ---------------------------------------------------------------------------
# Manifest round-trip + tolerance
# ---------------------------------------------------------------------------

def test_manifest_programs_roundtrip(saved):
    m, err = CheckpointManifest.load(saved, FORMAT_VERSION)
    assert err is None
    assert m.programs.get("entries")
    m.save()
    m2, err2 = CheckpointManifest.load(saved, FORMAT_VERSION)
    assert err2 is None
    assert m2.programs == m.programs
    # the programs/ subdir is manifest-indexed, never orphan debris
    assert "programs" not in m2.unrecorded_files()


def test_corrupt_programs_section_tolerated(saved, baseline, tmp_path):
    """A garbled ``programs`` value must not block the load — the
    session just doesn't open and the warm path traces."""
    path = _copy(saved, tmp_path)
    doc = _manifest_doc(path)
    doc["programs"] = "garbage"
    _write_manifest_doc(path, doc)
    m, err = CheckpointManifest.load(path, FORMAT_VERSION)
    assert err is None and m.programs == {}
    plan_mod.clear_plan_cache()
    lg.ledger().clear()
    recs, _kinds, info = _load_and_score(path, _rows(6))
    assert recs == baseline
    assert info["aotHits"] == 0 and info["ok"]


# ---------------------------------------------------------------------------
# Store mechanics: GC bound + two-process populate race
# ---------------------------------------------------------------------------

def test_store_gc_bound(tmp_path):
    store = ProgramStore(str(tmp_path / "store"))
    for i in range(12):
        meta = store.put({"fingerprint": f"f{i:02d}", "bucket": 256,
                          "jaxlib": "x", "deviceKind": "cpu/cpu",
                          "component": "plan-segment"},
                         bytes([i]) * 100)
        # distinct createdUnix ordering for deterministic eviction
        meta["createdUnix"] = float(i)
        path = os.path.join(store.dirpath, store._meta_name(meta["keyId"]))
        with open(path, "w") as fh:
            json.dump(meta, fh)
    removed = store.gc(max_entries=5)
    assert len(removed) == 7
    assert removed == [f"f{i:02d}@256" for i in range(7)]
    left = store.entries()
    assert len(left) == 5 and store.verify() == []
    # byte bound too
    removed2 = store.gc(max_entries=100, max_bytes=250)
    assert len(store.entries()) == 2 and removed2


_RACE_SCRIPT = """
import sys, json
sys.path.insert(0, {root!r})
from transmogrifai_tpu.programstore.store import ProgramStore
store = ProgramStore({dirpath!r})
who = sys.argv[1]
for i in range(40):
    blob = (who + str(i % 8)).encode() * 50
    store.put({{"fingerprint": "fp%d" % (i % 8), "bucket": 256,
               "jaxlib": "x", "deviceKind": "cpu/cpu",
               "component": "plan-segment"}}, blob)
print("done")
"""


def test_two_process_populate_race_is_safe(tmp_path):
    """Two processes hammering the same store with overlapping keys
    (atomic tmp+rename writes): every surviving entry must verify —
    torn blobs/metas are impossible by construction."""
    d = str(tmp_path / "race")
    root = os.path.dirname(os.path.dirname(os.path.abspath(
        tg.__file__)))
    script = _RACE_SCRIPT.format(root=root, dirpath=d)
    procs = [subprocess.Popen([sys.executable, "-c", script, who],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE)
             for who in ("a", "b")]
    for p in procs:
        out, err = p.communicate(timeout=60)
        assert p.returncode == 0, err.decode()
        assert b"done" in out
    store = ProgramStore(d)
    assert len(store.entries()) == 8
    assert store.verify() == []


def test_concurrent_thread_offers_single_store(tmp_path, model):
    """In-process race: parallel captures into one store stay
    consistent (the fleet's replicas share the model dir)."""
    store_dir = str(tmp_path / "m")
    os.makedirs(store_dir)
    # minimal manifest so capture flush has a target
    CheckpointManifest(store_dir, FORMAT_VERSION).save()
    errs = []

    def _populate():
        try:
            ps.populate_for_save(model, store_dir)
        except Exception as e:  # pragma: no cover - the assertion target
            errs.append(e)
    threads = [threading.Thread(target=_populate) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs
    store = ProgramStore(os.path.join(store_dir, PROGRAMS_DIR))
    assert store.entries() and store.verify() == []


# ---------------------------------------------------------------------------
# Sweep programs: the cross-model TG_AOT_STORE cache
# ---------------------------------------------------------------------------

def test_sweep_programs_cached_across_processes(tmp_path, monkeypatch):
    """Two identical trains with TG_AOT_STORE set: the first populates
    the fused sweep program, the second (fused cache + ledger cleared —
    a fresh process in miniature) deserializes it — zero sweep-subsystem
    builds, bit-equal scored outputs."""
    monkeypatch.setenv("TG_AOT_STORE", str(tmp_path / "sweepstore"))
    # the module fixture's train may have left the same (family, grid)
    # program in the in-process fused LRU — a hit there would skip the
    # build AND the offer; clear it so the first train genuinely builds
    _validators._FUSED_CACHE.clear()
    m1 = _train_model(seed=21)
    assert ps.stats()["exports"] >= 1
    st = ProgramStore(str(tmp_path / "sweepstore"))
    sweep_entries = [m for m in st.entries().values()
                     if m["component"] == "sweep"]
    assert sweep_entries
    _validators._FUSED_CACHE.clear()
    plan_mod.clear_plan_cache()
    lg.ledger().clear()
    ps.close_sessions()
    mark = lg.ledger().mark()
    m2 = _train_model(seed=21)
    sweep_builds = [r for r in lg.ledger().since(mark)
                    if r.subsystem == "sweep"]
    assert sweep_builds == [], [r.to_json() for r in sweep_builds]
    assert ps.stats()["hits"].get("sweep", 0) >= 1
    rows = _rows(8, seed=5)
    # result feature NAMES carry in-process uid counters; the scored
    # VALUES must be bit-equal
    r1 = micro_batch_score_function(m1)(rows)
    r2 = micro_batch_score_function(m2)(rows)
    assert ([list(r.values()) for r in r1]
            == [list(r.values()) for r in r2])


def test_a_second_row_bucket_gets_its_own_sweep_program(tmp_path,
                                                        monkeypatch):
    """The fused cache's key holds the table's shape and the fold gather's,
    as the store's fingerprint and the ledger's record do. Keyed without
    them, a store hit for the first table's bucket sat in the cache under a
    key the second table's sweep matched too: it was handed an executable
    deserialized for other rows, which threw, and the family was
    quarantined."""
    from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
    from transmogrifai_tpu.models.api import MODEL_REGISTRY
    models = [(MODEL_REGISTRY["OpLogisticRegression"],
               [{"regParam": 0.01, "elasticNetParam": 0.0},
                {"regParam": 0.1, "elasticNetParam": 0.0}])]

    def sweep(n):
        rng = np.random.RandomState(n)
        X = rng.randn(n, 3).astype(np.float32)
        y = (X.sum(axis=1) > 0).astype(np.float32)
        return OpCrossValidation(num_folds=3, seed=1).validate(
            models, X, y, "binary", "AuPR", True, 2)

    _validators._FUSED_CACHE.clear()
    alone = {n: sweep(n) for n in (300, 1000)}      # no store: the reference
    monkeypatch.setenv("TG_AOT_STORE", str(tmp_path / "sweepstore"))
    _validators._FUSED_CACHE.clear()
    first = sweep(768)      # builds for 1024 rows, 256 a fold, and exports
    _validators._FUSED_CACHE.clear()
    lg.ledger().clear()
    mark = lg.ledger().mark()
    again = sweep(768)      # the store's executable, into the fused cache
    assert ps.stats()["hits"].get("sweep", 0) == 1
    np.testing.assert_array_equal(again.results[0].fold_metrics,
                                  first.results[0].fold_metrics)
    # 512 rows; then 1024 rows again, but 512 a fold: not that executable
    for n in (300, 1000):
        got = sweep(n)
        assert got.quarantined == []
        np.testing.assert_array_equal(got.results[0].fold_metrics,
                                      alone[n].results[0].fold_metrics)
    built = [r for r in lg.ledger().since(mark) if r.subsystem == "sweep"]
    assert [(r.bucket, r.fingerprint["foldRows"]) for r in built] == [
        (512, 256), (1024, 512)]
    assert len(ProgramStore(str(tmp_path / "sweepstore")).entries()) == 3


# ---------------------------------------------------------------------------
# cli programs + warm report + ledger unit
# ---------------------------------------------------------------------------

def test_cli_programs_list_verify_gc(saved, tmp_path, capsys):
    from transmogrifai_tpu.cli import run_programs
    report = run_programs(saved, as_json=True)
    assert report["corrupt"] == []
    assert report["entries"] and report["manifestEntries"] >= 2
    for row in report["entries"]:
        assert row["sizeBytes"] > 0 and row["ageS"] >= 0
        assert "hits" in row
    capsys.readouterr()
    # corrupt one ENTRY-referenced blob -> non-zero exit
    path = _copy(saved, tmp_path)
    progdir = os.path.join(path, PROGRAMS_DIR)
    store = ProgramStore(progdir)
    meta = next(iter(store.entries().values()))
    with open(os.path.join(progdir, meta["file"]), "ab") as fh:
        fh.write(b"xx")
    with pytest.raises(SystemExit):
        run_programs(path)
    capsys.readouterr()


def test_ledger_aot_miss_unit():
    led = lg.CompileLedger()
    led.note_aot_miss("k1", "aot-miss (corrupt)")
    rec = led.record_build("serve", identity="p/seg0", key="k1",
                           fingerprint=[["c", "float32", [], True]])
    assert rec.cause == "aot-miss" and rec.diff == ["aot-miss (corrupt)"]
    # near-miss forensics still win over the aot note when a baseline
    # exists: a schema change after an AOT load names the column
    led.note_aot_miss("k2", "aot-miss (absent)")
    rec2 = led.record_build("serve", identity="p/seg0", key="k2",
                            fingerprint=[["c", "float64", [], True]])
    assert rec2.cause == "schema-change"
    assert any("float64" in d for d in rec2.diff)


def test_postmortem_bundle_carries_aot_section(saved, tmp_path,
                                               monkeypatch):
    monkeypatch.setenv("TG_POSTMORTEM_DIR", str(tmp_path / "pm"))
    from transmogrifai_tpu.observability import postmortem as pm
    recs, _kinds, _info = _load_and_score(saved, _rows(2))
    path = pm.trigger("breaker_open", detail={"model": "m"})
    assert path is not None
    doc = pm.read_bundle(path)
    assert pm.validate_bundle(doc) == []
    assert doc["schemaVersion"] == pm.SCHEMA_VERSION
    aot = doc["aot"]
    assert aot["enabled"] and aot["sessions"]
    assert aot["stats"]["hitsTotal"] >= 1
    # doctor renders the programs block without raising
    from transmogrifai_tpu.cli import run_doctor
    run_doctor(path, as_json=False)
