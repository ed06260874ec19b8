"""Rows from ``--seed`` for a table with a free-text column (``label_rule.kind``
``lexicon``): documents of words drawn from a generated vocabulary, beside an
identifier of many levels, a timestamp and a picklist. Reuses ``datagen.py``'s
level names, skews, seed streams and ``Generated`` and
``datagen_regression.py``'s timestamp.

The seed draws the rows only. The vocabulary, the lexicon's weights and the
identifier's per-level effects are drawn once from ``label_rule.rule_seed``,
never from ``--seed``; counts, skews, shares and rhythms are the file's. So
every seed gives the same derived width, the same compiled shapes and the
same work.

Column kinds:

* ``Text``: a document is ``words.min`` to ``words.max`` words (one plus a
  beta-binomial count: ``a``, ``b``), each drawn Zipf (``word_skew``) from
  ``vocabulary`` lower-case ASCII words of ``word_letters`` letters, joined
  by separators drawn from ``separators`` with ``separator_probs``; a word's
  first letter is upper-cased with probability ``capital_share``; the
  document ends at the last whole word within ``max_chars`` characters. On
  ``non_ascii_share`` of the rows one word has one letter replaced by an
  accented one (so that row is not ASCII and the word is a type of its own,
  outside the lexicon). One ``join`` a row; everything before it is bulk
  numpy over a flat array of word draws.
* ``ID`` / ``PickList``: ``datagen.py``'s levels, names and skew
  (``levels[codes]``: one object a level).
* ``DateTime``: ``datagen_regression.py``'s epoch milliseconds.

The label (``lexicon``): Bernoulli of a logistic rule whose logit is
``intercept`` plus the sum of a document's kept words' weights
(``lexicon_share`` of the word types carry one, normal of ``weight_scale``;
the others 0), plus ``amplitude * sin(2 pi (hour - phase) / 24)`` of a
timestamp's hour, plus per-level effects (normal of ``scale``) on the
``top`` commonest levels of an identifier: linear in what a logistic model
over hashed word counts, a pivot and a timestamp's circle reads.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

from .datagen import (_LABEL_STREAM, Generated, _draw_codes, _rng,
                      level_names)
from .datagen_regression import _HOUR_MS, _draw_datetime

_VOCAB_STREAM = 3_000_003
_ACCENTS = {"a": "á", "e": "é", "i": "í", "o": "ó",
            "u": "ú", "n": "ñ", "c": "ç"}
_OTHER_ACCENT = "ü"


def vocabulary(col: Dict[str, Any], rule_seed: int) -> np.ndarray:
    """The column's word types, commonest first: distinct lower-case ASCII
    words drawn once from ``rule_seed`` (object array of str)."""
    k = int(col["vocabulary"])
    lo, hi = (int(v) for v in col["word_letters"])
    rng = np.random.default_rng([int(rule_seed), _VOCAB_STREAM])
    words: Dict[str, None] = {}
    while len(words) < k:
        m = 2 * (k - len(words))
        lengths = rng.integers(lo, hi + 1, m)
        letters = rng.integers(0, 26, (m, hi)).astype(np.uint8) + ord("a")
        for row, n in zip(letters, lengths):
            words.setdefault(row[:n].tobytes().decode("ascii"))
            if len(words) == k:
                break
    return np.array(list(words), dtype=object)


def lexicon(rule: Dict[str, Any], col_index: int, k: int) -> np.ndarray:
    """(k,) float64 weight of every word type: each a normal of
    ``base_scale`` (0 where the term states none), and ``lexicon_share`` of
    them a normal of ``weight_scale`` more; from ``rule_seed``."""
    term = next(t for t in rule["terms"] if t["kind"] == "lexicon")
    rng = np.random.default_rng([int(rule["rule_seed"]), col_index])
    carries = rng.random(k) < float(term["lexicon_share"])
    strong = rng.normal(0.0, float(term["weight_scale"]), k)
    base = rng.normal(0.0, 1.0, k) * float(term.get("base_scale", 0.0))
    return np.where(carries, strong, 0.0) + base


def _accented(word: str, at: int) -> str:
    return word[:at] + _ACCENTS.get(word[at], _OTHER_ACCENT) + word[at + 1:]


def _draw_text(col, rng, n, vocab) -> Tuple[np.ndarray, np.ndarray,
                                            np.ndarray, np.ndarray]:
    """(documents, flat word ids of the kept words with -1 where a word was
    accented, each document's first index into them, its kept count)."""
    w = col["words"]
    lo, hi = int(w["min"]), int(w["max"])
    share = rng.beta(float(w["a"]), float(w["b"]), n)
    want = lo + rng.binomial(hi - lo, share)
    first = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(want, out=first[1:])
    total = int(first[-1])
    k = len(vocab)
    cdf = np.cumsum(1.0 / np.arange(1, k + 1) ** float(col["word_skew"]))
    cdf /= cdf[-1]
    ids = np.minimum(np.searchsorted(cdf, rng.random(total), side="right"),
                     k - 1).astype(np.int32)
    seps = np.array(list(col["separators"]), dtype=object)
    sep_cdf = np.cumsum(np.asarray(col["separator_probs"], dtype=np.float64))
    sep = np.minimum(np.searchsorted(sep_cdf / sep_cdf[-1], rng.random(total),
                                     side="right"), len(seps) - 1)
    cap = rng.random(total) < float(col["capital_share"])
    # the document ends at the last whole word within max_chars: a word is
    # kept where the document's length up to and including it fits
    word_len = np.fromiter(map(len, vocab), dtype=np.int64, count=k)
    sep_len = np.fromiter(map(len, seps), dtype=np.int64, count=len(seps))
    ends = np.cumsum(word_len[ids] + sep_len[sep]) - sep_len[sep]
    row = np.repeat(np.arange(n), want)
    before = np.r_[0, np.cumsum(word_len[ids] + sep_len[sep])][first[:-1]]
    fits = ends - before[row] <= int(col["max_chars"])
    kept = np.bincount(row, weights=fits, minlength=n).astype(np.int64)
    kept = np.maximum(kept, 1)          # (a first word always fits)
    last = first[:-1] + kept - 1
    # every (word, capital, separator-or-none) piece once; a document's
    # pieces are one gather and its string one join
    n_sep = len(seps) + 1
    tails = list(seps) + [""]
    pieces_of = np.array([wd + t for wd in vocab for t in tails]
                         + [wd.capitalize() + t for wd in vocab
                            for t in tails], dtype=object)
    sep[last] = n_sep - 1
    pieces = pieces_of[(cap.astype(np.int64) * k + ids) * n_sep + sep]
    ids = ids.copy()
    # the rows that are not ASCII: one of their kept words gets an accent
    special = np.flatnonzero(rng.random(n) < float(col["non_ascii_share"]))
    where = first[special] + (rng.random(len(special))
                              * kept[special]).astype(np.int64)
    letter = rng.random(len(special))
    for at, u in zip(where.tolist(), letter.tolist()):
        word = vocab[ids[at]]
        word = _accented(word, int(u * len(word)))
        pieces[at] = (word.capitalize() if cap[at] else word) + tails[sep[at]]
        ids[at] = -1
    flat = pieces.tolist()
    docs = np.empty(n, dtype=object)
    docs[:] = ["".join(flat[a:a + m])
               for a, m in zip(first[:-1].tolist(), kept.tolist())]
    return docs, ids, first[:-1], kept


def _kept_sum(values: np.ndarray, first: np.ndarray, kept: np.ndarray
              ) -> np.ndarray:
    """Sum of ``values`` over each document's kept words."""
    run = np.r_[0.0, np.cumsum(values)]
    return run[first + kept] - run[first]


def generate(config: Dict[str, Any], seed: int, rows: int) -> Generated:
    """``rows`` rows of ``config``'s schema from ``seed``."""
    cols: List[Dict[str, Any]] = config["columns"]
    rule = config["label_rule"]
    if rule["kind"] != "lexicon":
        raise ValueError(f"unknown label rule {rule['kind']!r}")
    n = int(rows)
    by_name = {c["name"]: (i, c) for i, c in enumerate(cols)}
    out: Dict[str, np.ndarray] = {}
    types: Dict[str, str] = {}
    codes: Dict[str, np.ndarray] = {}
    words: Dict[str, Tuple[np.ndarray, np.ndarray, np.ndarray]] = {}
    for i, col in enumerate(cols):
        rng = _rng(seed, i)
        name = col["name"]
        types[name] = col["type"]
        if col["type"] in ("PickList", "ID"):
            names = (np.array([str(v) for v in col["names"]], dtype=object)
                     if "names" in col else level_names(col))
            codes[name] = _draw_codes(col, rng, n)
            out[name] = names[codes[name]]
        elif col["type"] == "DateTime":
            out[name] = _draw_datetime(col, rng, n)
        elif col["type"] == "Text":
            vocab = vocabulary(col, int(rule["rule_seed"]))
            out[name], *words[name] = _draw_text(col, rng, n, vocab)
        else:
            raise ValueError(f"unknown column type {col['type']!r}")

    z = np.full(n, float(rule["intercept"]), dtype=np.float64)
    for term in rule["terms"]:
        i, col = by_name[term["column"]]
        if term["kind"] == "lexicon":
            ids, first, kept = words[col["name"]]
            weight = np.r_[lexicon(rule, i, int(col["vocabulary"])), 0.0]
            z += _kept_sum(weight[ids], first, kept)      # -1: accented, 0
        elif term["kind"] == "hour_of_day":
            hour = (out[col["name"]] // _HOUR_MS) % 24
            z += float(term["amplitude"]) * np.sin(
                2.0 * np.pi * (hour - float(term["phase"])) / 24.0)
        elif term["kind"] == "top_level_effects":
            top = int(term["top"])
            eff = np.r_[np.random.default_rng(
                [int(rule["rule_seed"]), i]).normal(
                    0.0, float(term["scale"]), top), 0.0]
            z += eff[np.minimum(codes[col["name"]], top)]
        else:
            raise ValueError(f"unknown term kind {term['kind']!r}")
    true_prob = 1.0 / (1.0 + np.exp(-z))
    y = (_rng(seed, _LABEL_STREAM).random(n) < true_prob).astype(np.float32)
    return Generated(out, types, y, true_prob)
