"""ctypes binding for the native text kernels (native/text_ops.cpp).

Host-side replacement for the reference's executor-parallel JVM text path
(Lucene tokenization + Spark HashingTF — reference TextTokenizer.scala:196,
OPCollectionHashingVectorizer.scala:398). Token hashing is bit-identical to
the Python fallback (both are zlib crc32 over UTF-8 bytes); the fused
tokenize+hash path handles pure-ASCII documents natively and returns the
non-ASCII rows to the caller for the Unicode-aware Python tokenizer.

Compiled on first use with ``g++ -O2 -shared -lz`` into
``native/_build/libtextops.so`` (same lifecycle as the streaming histogram
library); without a toolchain every entry point degrades to pure Python.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_HERE, "native", "text_ops.cpp")
_BUILD_DIR = os.path.join(_HERE, "native", "_build")
_LIB_PATH = os.path.join(_BUILD_DIR, "libtextops.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False

_I64P = ctypes.POINTER(ctypes.c_int64)
_F32P = ctypes.POINTER(ctypes.c_float)
_U8P = ctypes.POINTER(ctypes.c_uint8)


def _build_lib() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            os.makedirs(_BUILD_DIR, exist_ok=True)
            if (not os.path.exists(_LIB_PATH)
                    or os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC)):
                subprocess.run(
                    ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                     _SRC, "-o", _LIB_PATH, "-lz"],
                    check=True, capture_output=True, timeout=120)
            lib = ctypes.CDLL(_LIB_PATH)
            lib.tg_hash_tokens.argtypes = [
                ctypes.c_char_p, _I64P, ctypes.c_int64, _I64P,
                ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, _F32P]
            lib.tg_tokenize_hash_count.argtypes = [
                ctypes.c_char_p, _I64P, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int32, _F32P, _U8P]
            lib.tg_tokenize_hash_count.restype = ctypes.c_int64
            _lib = lib
        except Exception as e:
            _lib_failed = True
            # said once: the flag above keeps every later call off this path
            said = (getattr(e, "stderr", None) or b"")[-400:]
            logger.warning(
                "native text kernels unavailable, every document goes "
                "through the Python tokenizer: %s: %s %s", type(e).__name__,
                e, said.decode("utf-8", "replace"))
        return _lib


def native_available() -> bool:
    return _build_lib() is not None


def hash_token_lists_native(
        token_lists: Sequence[Optional[Sequence[str]]], num_hashes: int,
        binary: bool = False) -> Optional[np.ndarray]:
    """(n, num_hashes) float32 token-count rows, or None when the native
    library is unavailable. Exact crc32 parity with the Python path."""
    lib = _build_lib()
    if lib is None:
        return None
    n = len(token_lists)
    enc: List[bytes] = []
    doc_starts = np.zeros(n + 1, dtype=np.int64)
    for i, toks in enumerate(token_lists):
        if toks:
            enc.extend(t.encode("utf-8") for t in toks)
        doc_starts[i + 1] = len(enc)
    tok_offs = np.zeros(len(enc) + 1, dtype=np.int64)
    np.cumsum([len(e) for e in enc], out=tok_offs[1:])
    buf = b"".join(enc)
    out = np.zeros((n, num_hashes), dtype=np.float32)
    lib.tg_hash_tokens(
        buf, tok_offs.ctypes.data_as(_I64P), len(enc),
        doc_starts.ctypes.data_as(_I64P), n,
        np.int32(num_hashes), np.int32(1 if binary else 0),
        out.ctypes.data_as(_F32P))
    return out


def tokenize_hash_native(
        docs: Sequence[Optional[str]], num_hashes: int,
        min_token_length: int = 1, binary: bool = False):
    """Fused tokenize+hash for a document batch.

    Returns (counts (n, num_hashes) float32, needs_py bool (n,), tokens) —
    rows flagged in needs_py are untouched zeros (non-ASCII or degenerate
    docs) and must be filled by the Python tokenizer path; ``tokens`` is how
    many tokens the kernel counted in the rows it kept. Returns None when
    the native library is unavailable.
    """
    lib = _build_lib()
    if lib is None:
        return None
    n = len(docs)
    enc = [(d.encode("utf-8") if isinstance(d, str) else b"") for d in docs]
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(e) for e in enc], out=offs[1:])
    buf = b"".join(enc)
    out = np.zeros((n, num_hashes), dtype=np.float32)
    needs_py = np.zeros(n, dtype=np.uint8)
    tokens = lib.tg_tokenize_hash_count(
        buf, offs.ctypes.data_as(_I64P), n, np.int32(num_hashes),
        np.int32(min_token_length), np.int32(1 if binary else 0),
        out.ctypes.data_as(_F32P), needs_py.ctypes.data_as(_U8P))
    return out, needs_py.astype(bool), int(tokens)
