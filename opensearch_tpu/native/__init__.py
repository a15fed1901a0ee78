"""ctypes loader + wrappers for the native host kernels (SURVEY §2.10).

Builds `_opensearch_native.so` from the adjacent C++ source with g++ on first
import (cached; rebuilt when the source is newer). Everything here has a
pure-Python/numpy fallback at its call sites — if the toolchain or the build
is unavailable, `available()` returns False and callers take the fallback.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import tempfile
from typing import List, Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "opensearch_native.cpp")
_SO = os.path.join(_HERE, "_opensearch_native.so")

_lib = None
_tried = False


def _build() -> bool:
    try:
        # build to a temp name + atomic rename so concurrent importers never
        # dlopen a half-written .so
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_HERE)
        os.close(fd)
        res = subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", tmp, _SRC],
            capture_output=True, timeout=120)
        if res.returncode != 0:
            os.unlink(tmp)
            return False
        os.replace(tmp, _SO)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def _load():
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    try:
        if (not os.path.exists(_SO)
                or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
            if not _build():
                return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            # stale/foreign-arch artifact: rebuild from source and retry once
            if not _build():
                return None
            lib = ctypes.CDLL(_SO)
    except OSError:
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.osn_murmur3.restype = ctypes.c_uint32
    lib.osn_murmur3.argtypes = [u8p, ctypes.c_int64, ctypes.c_uint32]
    lib.osn_tokenize_ascii.restype = ctypes.c_int64
    lib.osn_tokenize_ascii.argtypes = [u8p, ctypes.c_int64, i32p,
                                       ctypes.c_int64]
    lib.osn_pack_new.restype = ctypes.c_void_p
    lib.osn_pack_new.argtypes = [ctypes.c_int32]
    lib.osn_pack_free.restype = None
    lib.osn_pack_free.argtypes = [ctypes.c_void_p]
    lib.osn_pack_add.restype = ctypes.c_int32
    lib.osn_pack_add.argtypes = [ctypes.c_void_p, u8p, ctypes.c_int64,
                                 ctypes.c_int64, i32p, i32p]
    lib.osn_pack_finish.restype = ctypes.c_int32
    lib.osn_pack_finish.argtypes = [ctypes.c_void_p]
    lib.osn_pack_dims.restype = None
    lib.osn_pack_dims.argtypes = [ctypes.c_void_p, i64p]
    lib.osn_pack_export.restype = None
    lib.osn_pack_export.argtypes = [ctypes.c_void_p, i64p, i32p, f32p, i64p,
                                    i32p, u8p, i64p]
    lib.osn_maxscore_topk.restype = ctypes.c_int64
    lib.osn_maxscore_topk.argtypes = [i64p, i32p, f32p, f32p, f32p, f32p,
                                      i32p, ctypes.c_int32, ctypes.c_int32,
                                      ctypes.c_int32, u8p, i32p, f32p, i64p]
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def _u8(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def murmur3(data: bytes, seed: int = 0) -> int:
    lib = _load()
    buf = np.frombuffer(data, dtype=np.uint8) if data else np.zeros(1, np.uint8)
    return int(lib.osn_murmur3(_u8(buf), len(data), seed & 0xFFFFFFFF))


def tokenize_ascii(text: str) -> np.ndarray:
    """(ntok, 2) int32 array of (start, end) offsets; ASCII input only."""
    lib = _load()
    raw = text.encode("ascii")
    buf = np.frombuffer(raw, dtype=np.uint8) if raw else np.zeros(1, np.uint8)
    cap = len(raw) // 2 + 1
    out = np.empty((cap, 2), dtype=np.int32)
    n = lib.osn_tokenize_ascii(_u8(buf), len(raw), _ptr(out, ctypes.c_int32),
                               cap)
    return out[:n]


class Packer:
    """Accumulate a token stream, emit the CSR postings layout of
    index/segment.py::build_segment. Tokens are passed as a single
    NUL-joined string per add() call (NULs inside a token are rejected with
    ValueError so the caller can fall back)."""

    def __init__(self, with_positions: bool):
        self._lib = _load()
        self._h = self._lib.osn_pack_new(1 if with_positions else 0)
        self.with_positions = with_positions

    def add(self, tokens_joined: str, ntok: int, doc_of: np.ndarray,
            positions: Optional[np.ndarray]) -> None:
        if ntok == 0:
            return
        raw = tokens_joined.encode("utf-8")
        buf = np.frombuffer(raw, dtype=np.uint8)
        doc_of = np.ascontiguousarray(doc_of, dtype=np.int32)
        posp = None
        if positions is not None:
            positions = np.ascontiguousarray(positions, dtype=np.int32)
            posp = _ptr(positions, ctypes.c_int32)
        rc = self._lib.osn_pack_add(self._h, _u8(buf), len(raw), ntok,
                                    _ptr(doc_of, ctypes.c_int32), posp)
        if rc != 0:
            raise ValueError("token stream contained embedded NUL")

    def finish(self):
        """-> (vocab: list[str], starts i64, doc_ids i32, tfs f32,
        pos_starts i64|None, positions i32|None)"""
        lib = self._lib
        lib.osn_pack_finish(self._h)
        dims = np.zeros(4, dtype=np.int64)
        lib.osn_pack_dims(self._h, _ptr(dims, ctypes.c_int64))
        nterms, npost, npos, vbytes = (int(x) for x in dims)
        starts = np.zeros(nterms + 1, dtype=np.int64)
        doc_ids = np.zeros(max(npost, 1), dtype=np.int32)
        tfs = np.zeros(max(npost, 1), dtype=np.float32)
        pos_starts = np.zeros(npost + 1, dtype=np.int64)
        positions = np.zeros(max(npos, 1), dtype=np.int32)
        vocab_buf = np.zeros(max(vbytes, 1), dtype=np.uint8)
        vocab_offs = np.zeros(nterms + 1, dtype=np.int64)
        lib.osn_pack_export(
            self._h, _ptr(starts, ctypes.c_int64),
            _ptr(doc_ids, ctypes.c_int32), _ptr(tfs, ctypes.c_float),
            _ptr(pos_starts, ctypes.c_int64), _ptr(positions, ctypes.c_int32),
            _u8(vocab_buf), _ptr(vocab_offs, ctypes.c_int64))
        raw = vocab_buf.tobytes()[:vbytes]
        vocab = [raw[vocab_offs[i]:vocab_offs[i + 1]].decode("utf-8")
                 for i in range(nterms)]
        if not self.with_positions:
            return vocab, starts, doc_ids[:npost], tfs[:npost], None, None
        return (vocab, starts, doc_ids[:npost], tfs[:npost], pos_starts,
                positions[:npos])

    def close(self) -> None:
        if self._h:
            self._lib.osn_pack_free(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def maxscore_topk(starts: np.ndarray, doc_ids: np.ndarray, tfs: np.ndarray,
                  kdoc: np.ndarray, idf: np.ndarray, ub: np.ndarray,
                  qterms: np.ndarray, msm: int, k: int,
                  filt: Optional[np.ndarray] = None):
    """Skipping (MaxScore/conjunction) BM25 top-k over one CSR field — the
    Lucene-BulkScorer-class CPU scorer chip_smoke.py holds its pages to,
    also a parity oracle for tests. qterms: i32[nt] term rows (-1 pad).
    msm: minimum matching terms (nt = conjunction). filt: optional
    u8[ndocs] 0/1 mask.
    -> (docs i32[k] (-1 pad), scores f32[k], total int — exact for the
    conjunction path, -1 when the MaxScore path early-terminated)."""
    lib = _load()
    if len(qterms) > 64:
        raise ValueError("maxscore_topk supports at most 64 query terms")
    starts = np.ascontiguousarray(starts, np.int64)
    doc_ids = np.ascontiguousarray(doc_ids, np.int32)
    tfs = np.ascontiguousarray(tfs, np.float32)
    kdoc = np.ascontiguousarray(kdoc, np.float32)
    idf = np.ascontiguousarray(idf, np.float32)
    ub = np.ascontiguousarray(ub, np.float32)
    qterms = np.ascontiguousarray(qterms, np.int32)
    fptr = None
    if filt is not None:
        filt = np.ascontiguousarray(filt, np.uint8)
        fptr = _u8(filt)
    k = min(k, 256)
    out_docs = np.empty(k, np.int32)
    out_scores = np.empty(k, np.float32)
    out_total = np.zeros(1, np.int64)
    lib.osn_maxscore_topk(
        _ptr(starts, ctypes.c_int64), _ptr(doc_ids, ctypes.c_int32),
        _ptr(tfs, ctypes.c_float), _ptr(kdoc, ctypes.c_float),
        _ptr(idf, ctypes.c_float), _ptr(ub, ctypes.c_float),
        _ptr(qterms, ctypes.c_int32), len(qterms), msm, k, fptr,
        _ptr(out_docs, ctypes.c_int32), _ptr(out_scores, ctypes.c_float),
        _ptr(out_total, ctypes.c_int64))
    return out_docs, out_scores, int(out_total[0])


def term_upper_bounds(starts: np.ndarray, doc_ids: np.ndarray,
                      tfs: np.ndarray, kdoc: np.ndarray,
                      idf: np.ndarray) -> np.ndarray:
    """Per-term MaxScore upper bounds idf_t * max_d tf/(tf+kdoc[d]),
    vectorized on host (one pass over the postings)."""
    contrib = tfs / (tfs + kdoc[doc_ids])
    nterms = len(starts) - 1
    ub = np.zeros(nterms, np.float32)
    nonempty = np.flatnonzero(np.diff(starts) > 0)
    if len(nonempty):
        maxes = np.maximum.reduceat(contrib, starts[nonempty])
        ub[nonempty] = maxes.astype(np.float32)
    return ub * idf[:nterms]
