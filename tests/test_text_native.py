"""Native text-kernel tests (native/text_ops.cpp via utils/text_native.py):
crc32 hashing parity with the Python path, fused tokenize+hash parity on
ASCII, Unicode rows routed back to Python, and the integrated
hash_token_lists / tokenize_hash_texts entries."""
import numpy as np
import pytest

from transmogrifai_tpu.impl.feature.vectorizers import (
    _hash_token, hash_token_lists, tokenize_hash_texts, tokenize_text,
)
from transmogrifai_tpu.utils import text_native


def _py_hash(token_lists, nh, binary=False):
    out = np.zeros((len(token_lists), nh), dtype=np.float32)
    for i, toks in enumerate(token_lists):
        for t in toks or ():
            out[i, _hash_token(t, nh)] += 1.0
    if binary:
        np.minimum(out, 1.0, out=out)
    return out


def test_hash_token_lists_matches_python_reference():
    tl = [["hello", "world", "hello"], None, [], ["the quick", "héllo", "_x"]]
    for binary in (False, True):
        got = hash_token_lists(tl, 64, binary=binary)
        assert np.array_equal(got, _py_hash(tl, 64, binary))


@pytest.mark.skipif(not text_native.native_available(),
                    reason="no native toolchain")
def test_native_hash_parity_directly():
    tl = [["a", "bb", "ccc"], ["a"], None]
    got = text_native.hash_token_lists_native(tl, 32)
    assert np.array_equal(got, _py_hash(tl, 32))


def test_tokenize_hash_texts_parity():
    docs = ["Hello, World! hello_x", None, "", "Café au lait",
            "a b ccc dd", "MiXeD CaSe 123", "tab\tand\nnewline"]
    for mtl in (1, 2):
        got = tokenize_hash_texts(docs, 32, min_token_length=mtl)
        want = _py_hash([tokenize_text(d, mtl) for d in docs], 32)
        assert np.array_equal(got, want)


@pytest.mark.skipif(not text_native.native_available(),
                    reason="no native toolchain")
def test_non_ascii_rows_flagged():
    res = text_native.tokenize_hash_native(["plain ascii", "Café"], 16)
    counts, needs_py, tokens = res
    assert tokens == 2
    assert not needs_py[0] and needs_py[1]
    # flagged row left zero for the caller
    assert counts[1].sum() == 0


def test_smart_text_vectorizer_uses_fused_path():
    # end-to-end through the stage: hashing branch output must equal the
    # pure-python tokenize+hash for a high-cardinality text feature
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.impl.feature.vectorizers import SmartTextVectorizer
    from transmogrifai_tpu.table import FeatureTable
    from transmogrifai_tpu.types import Text
    rng = np.random.RandomState(0)
    docs = ["word%d token%d filler" % (i, rng.randint(1000))
            for i in range(50)] + [None, "ünïcode row"]
    f = FeatureBuilder("t", Text).extract_field().as_predictor()
    tbl = FeatureTable.from_columns({"t": (Text, docs)})
    model = (SmartTextVectorizer(max_cardinality=10, num_hashes=16,
                                 track_nulls=False)
             .set_input(f).fit(tbl))
    got = np.asarray(model.transform_column(tbl).values)
    want = _py_hash([tokenize_text(d, 1) if d else [] for d in docs], 16)
    assert np.array_equal(got, want)
