"""The text stage's child spans (PR 41): ``text.cardinality`` under
``SmartTextVectorizer``'s fit, ``text.hash``, ``text.pivot`` and
``text.concat`` under its model's transform, with their attributes; nothing
recorded with tracing off; the native and the Python path give the same
block, bit for bit; and ``benchmark/reference_text.py``'s tokenizer agrees
with the program's on awkward strings."""
import numpy as np
import pytest

from benchmark import reference_text
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.impl.feature import vectorizers
from transmogrifai_tpu.impl.feature.vectorizers import (
    SmartTextVectorizer, tokenize_hash_texts, tokenize_text)
from transmogrifai_tpu.observability import trace as ot
from transmogrifai_tpu.table import FeatureTable
from transmogrifai_tpu.types import Text
from transmogrifai_tpu.utils import text_native

AWKWARD = [
    "", " ", "!!!", "one", "Two  words", "tabs\tand\nnewlines\r\n",
    "under_score_d and __dunder__", "digits 123 4five 6_7",
    "runs,,,of...separators!!! ;; here", "MiXeD CaSe ÉCOLE Straße",
    "ünïcode café naïve", "İstanbul ǅ", "emoji 😀 between", "'quoted' \"text\"",
    "hy-phen-ated em—dash", "a" * 140, "ab " * 46 + "ab",
    "trailing separator... ", "... leading", "数字 漢字 かな"]


def _table(n=200):
    rng = np.random.RandomState(3)
    docs = [f"word{i} Token{rng.randint(50)} filler, tail" for i in range(n)]
    docs[7], docs[11], docs[13] = "café au lait", None, "naïve Ünïcode row"
    kinds = [("red", "green", "blue")[i % 3] for i in range(n)]
    return FeatureTable.from_columns({"doc": (Text, docs),
                                      "kind": (Text, kinds)}), docs


def _fit_transform(table):
    feats = [FeatureBuilder(name, Text).extract_field().as_predictor()
             for name in ("doc", "kind")]
    model = SmartTextVectorizer(num_hashes=64).set_input(*feats).fit(table)
    return model, model.transform_column(table)


@pytest.fixture
def traced():
    ot.enable_tracing(True)
    ot.tracer().clear()
    try:
        yield ot.tracer()
    finally:
        ot.enable_tracing(None)
        ot.tracer().clear()


def _named(tracer, name):
    return [s for s in tracer.finished() if s.name == name]


def test_the_fit_has_a_cardinality_span_a_column(traced):
    table, _ = _table()
    _fit_transform(table)
    doc, kind = _named(traced, "text.cardinality")
    assert doc.attrs == {"column": "doc", "rows": 200, "distinct": 199,
                         "plan": "hash"}
    assert kind.attrs == {"column": "kind", "rows": 200, "distinct": 3,
                          "plan": "pivot"}


@pytest.mark.skipif(not text_native.native_available(),
                    reason="no native toolchain")
def test_the_transform_has_hash_pivot_and_concat_spans(traced):
    table, docs = _table()
    _, out = _fit_transform(table)
    (hashed,) = _named(traced, "text.hash")
    tokens = sum(len(tokenize_text(d)) for d in docs)
    assert hashed.attrs == {"column": "doc", "rows": 200, "bins": 64,
                            "path": "native", "pyRows": 2, "tokens": tokens,
                            "bytes": 200 * 64 * 4}
    (pivot,) = _named(traced, "text.pivot")
    assert pivot.attrs == {"column": "kind", "rows": 200, "levels": 3}
    (concat,) = _named(traced, "text.concat")
    # two blocks and a null column each
    assert concat.attrs == {"columns": 4,
                            "bytes": int(np.asarray(out.values).nbytes)}
    assert np.asarray(out.values).shape == (200, 64 + 1 + 3 + 1 + 1)
    # children of the stage's own transform where the workflow opens one
    assert all(s.dur_ns is not None for s in traced.finished())


def test_nothing_is_recorded_with_tracing_off(monkeypatch):
    made = []
    real = ot.Span.__init__
    monkeypatch.setattr(ot.Span, "__init__",
                        lambda self, *a, **k: (made.append(1),
                                               real(self, *a, **k))[1])
    assert not ot.tracing_enabled()
    table, _ = _table()
    _fit_transform(table)
    assert made == [] and ot.tracer().finished() == []


@pytest.mark.skipif(not text_native.native_available(),
                    reason="no native toolchain")
@pytest.mark.parametrize("docs", [
    ["plain ascii rows", "Only, ASCII! here...", "x"],
    ["café", "naïve Ünïcode", "数字 and ascii"],
    AWKWARD + [None]], ids=["ascii", "non_ascii", "mixed"])
def test_the_native_and_the_python_path_give_the_same_block(docs, traced,
                                                            monkeypatch):
    native, path, py_rows, tokens = vectorizers._tokenize_hash_counted(
        docs, 32)
    assert path == "native"
    assert py_rows == sum(d is not None and not d.isascii() for d in docs)
    monkeypatch.setattr(text_native, "tokenize_hash_native",
                        lambda *a, **k: None)
    plain, path, all_rows, same = vectorizers._tokenize_hash_counted(docs, 32)
    assert (path, all_rows, same) == ("python", len(docs), tokens)
    assert native.dtype == plain.dtype and np.array_equal(native, plain)
    assert np.array_equal(tokenize_hash_texts(docs, 32), plain)


def test_a_missing_toolchain_is_said_once(monkeypatch, caplog):
    """No compiler: the reason is logged once and every later call goes the
    Python way without another attempt."""
    import subprocess

    def no_compiler(*a, **k):
        raise FileNotFoundError("g++")

    monkeypatch.setattr(text_native, "_lib", None)
    monkeypatch.setattr(text_native, "_lib_failed", False)
    monkeypatch.setattr(text_native, "_LIB_PATH",
                        text_native._LIB_PATH + ".absent")
    monkeypatch.setattr(subprocess, "run", no_compiler)
    with caplog.at_level("WARNING", logger=text_native.logger.name):
        assert text_native.tokenize_hash_native(["a b"], 8) is None
        assert text_native.tokenize_hash_native(["a b"], 8) is None
    said = [r for r in caplog.records if "native text kernels" in r.message]
    assert len(said) == 1 and "FileNotFoundError" in said[0].getMessage()


@pytest.mark.parametrize("doc", AWKWARD + [None])
def test_the_references_tokenizer_agrees_with_the_programs(doc):
    assert reference_text.tokenize(doc) == tokenize_text(doc)
    want = np.asarray(tokenize_hash_texts([doc], 512))
    assert np.array_equal(reference_text.hash_counts([doc], 512), want)
