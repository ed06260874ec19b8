"""The plain reference of a table with a free-text column: numpy and the
standard library, nothing of the program imported (``reference.py`` and
``reference_regression.py`` are, for the slots they already recompute: the
pivots of a picklist or an identifier with OTHER and null indicators, the
four circular periods of a timestamp).

What it adds is the hashed block of a ``Text`` column, by the stated rule:

* ``tokenize``: a document is lower-cased (``str.lower``) and split on
  ``[^\\w]+`` under Unicode rules (letters, digits and the underscore of any
  script are word characters); tokens of at least one character are kept. A
  null document has no token;
* a token's bin is ``zlib.crc32`` of its UTF-8 bytes modulo the number of
  bins (the ``hash_<j>`` slots of the vector's metadata: 512 at the
  program's defaults), and a document's row holds the COUNT of its tokens in
  each bin, float32. Counts are small integers: exact in float32 and in
  bfloat16 alike, so rounding the block to bfloat16 does not move it (the
  bfloat16 control moves the fit and the score, not this block);
* the column's null indicator is 1 where the document is null.

The winner's score and the retraining of the sweep's best L2 logistic point
are ``reference.py``'s (``class1_score``, ``fit_logistic``, ``logistic_prob``,
``cv_aupr``), over this matrix.

``hash_counts`` takes the controls' variants: another ``modulus`` (a token's
bin is ``crc32 % modulus``), ``lower=False`` (no lower-casing) and
``binary=True`` (presence in place of counts). Each moves the block by 1 or
more on some row.
"""
from __future__ import annotations

import re
import zlib
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from . import reference, reference_regression

NULL_INDICATOR = reference.NULL_INDICATOR
#: (parent feature, indicator value or None, descriptor value or None)
Slot = Tuple[str, Optional[str], Optional[str]]

_SPLIT = re.compile(r"[^\w]+")          # str pattern: Unicode \w
_HASH = re.compile(r"hash_(\d+)")
#: the program's column kinds that pivot as a picklist does
_PIVOTED = ("PickList", "ID")


def tokenize(doc: Optional[str], lower: bool = True) -> List[str]:
    if doc is None:
        return []
    return [t for t in _SPLIT.split(doc.lower() if lower else doc) if t]


class _Bins(dict):
    """token -> bin, computed once a distinct token."""

    def __init__(self, modulus: int):
        super().__init__()
        self.modulus = int(modulus)

    def __missing__(self, token: str) -> int:
        b = self[token] = zlib.crc32(token.encode("utf-8")) % self.modulus
        return b


def hash_counts(docs: Iterable[Optional[str]], bins: int,
                modulus: Optional[int] = None, lower: bool = True,
                binary: bool = False, block: int = 65536) -> np.ndarray:
    """(n, bins) float32: the count of each document's tokens by bin."""
    docs = list(docs)
    out = np.zeros((len(docs), int(bins)), dtype=np.float32)
    bin_of = _Bins(int(bins) if modulus is None else modulus)
    for lo in range(0, len(docs), block):
        part = docs[lo:lo + block]
        cols: List[int] = []
        lens: List[int] = []
        for d in part:
            toks = tokenize(d, lower)
            lens.append(len(toks))
            cols.extend(map(bin_of.__getitem__, toks))
        flat = (np.repeat(np.arange(len(part), dtype=np.int64), lens)
                * int(bins) + np.asarray(cols, dtype=np.int64))
        counts = np.bincount(flat, minlength=len(part) * int(bins))
        out[lo:lo + len(part)] = counts.reshape(len(part), int(bins))
    if binary:
        np.minimum(out, 1.0, out=out)
    return out


def _is_text(types: Dict[str, str], slot: Slot) -> bool:
    return types[slot[0]] == "Text"


def feature_matrix(raw: Dict[str, np.ndarray], types: Dict[str, str],
                   full_slots: Sequence[Slot], kept_slots: Sequence[Slot],
                   **hash_options) -> np.ndarray:
    """(n, len(kept_slots)) float32 design matrix from raw columns and the
    slot descriptions ``(parent, indicator, descriptor)`` of the vector
    before the sanity checker (``full_slots``) and of the vector the model
    reads (``kept_slots``). ``hash_options`` go to ``hash_counts`` (the
    controls')."""
    rest_types = {k: ("PickList" if v in _PIVOTED else v)
                  for k, v in types.items() if v != "Text"}
    rest = reference_regression.feature_matrix(
        {k: raw[k] for k in rest_types}, rest_types,
        [s for s in full_slots if not _is_text(types, s)],
        [s for s in kept_slots if not _is_text(types, s)])
    n = len(next(iter(raw.values())))
    out = np.zeros((n, len(kept_slots)), dtype=np.float32)
    n_bins: Dict[str, int] = {}
    for parent, _, desc in full_slots:
        if types[parent] == "Text" and desc and _HASH.fullmatch(desc):
            n_bins[parent] = max(n_bins.get(parent, 0),
                                 int(_HASH.fullmatch(desc).group(1)) + 1)
    blocks: Dict[str, np.ndarray] = {}
    j_rest = 0
    for j, slot in enumerate(kept_slots):
        parent, ind, desc = slot
        if not _is_text(types, slot):
            out[:, j] = rest[:, j_rest]
            j_rest += 1
        elif ind == NULL_INDICATOR:
            out[:, j] = np.equal(raw[parent], None)
        elif desc and _HASH.fullmatch(desc):
            if parent not in blocks:
                blocks[parent] = hash_counts(raw[parent], n_bins[parent],
                                             **hash_options)
            out[:, j] = blocks[parent][:, int(_HASH.fullmatch(desc).group(1))]
        else:
            raise ValueError(f"no plain form of the Text slot {slot!r}")
    return out
