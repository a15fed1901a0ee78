"""Immutable index segments: CSR posting blocks + columnar doc values in HBM.

This replaces Lucene's segment files (reference: `index/codec/`, Lucene
Lucene101PostingsFormat / DocValuesFormat / StoredFieldsFormat). Layout is
TPU-first instead of disk-first:

- Postings for one field are a CSR matrix over (term row -> doc postings):
  `starts[t]..starts[t+1]` indexes flat `doc_ids` / `tfs` arrays. Flat arrays
  are padded to power-of-two lengths so XLA sees a small set of static shapes
  across segments (compile-cache friendly); padded doc_ids hold an
  out-of-range sentinel so scatter `mode=drop` ignores them.
- Term frequencies are stored as f32 (exact for tf < 2^24) so the BM25
  tf-saturation runs on the VPU with no decode step — the analog of Lucene's
  "impacts" but kept separate from the per-doc length norm so k1/b/avgdl stay
  query-time parameters (similarity parity with reference
  `index/similarity/`).
- Doc values are dense columns: the long family (long/date/boolean/ip-lo...)
  is stored as exact (hi,lo) i32 pairs (TPU jit default is 32-bit; the pair
  compare keeps 64-bit range semantics exact), floats as f32, keywords as a
  doc-major CSR of segment-local ordinals + per-doc min-ord for sorting.
- Stored fields (`_source`) stay on host (the device never needs them; the
  fetch phase is host-side, reference `search/fetch/FetchPhase.java`).
"""

from __future__ import annotations

import json
import math
import os
import time
from dataclasses import dataclass, field as dc_field
from functools import cached_property
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.ingest_obs import note_stage
from ..utils.metrics import METRICS, CounterGroup
from .mappings import FLOAT_TYPES, GEO_TYPES, FieldType, Mappings

INT32_SENTINEL = np.int32(2**31 - 1)  # padded doc_id -> dropped by scatter

# `live_recounts`: full passes over a segment's live mask to count it, one a
# whole-mask assignment (`Segment.live`'s setter). A delete and a read of
# `live_count` make none, so a served window reads 0 here.
SEGMENT_STATS = CounterGroup(METRICS, "segment", {"live_recounts": 0})

# ---------------------------------------------------------------------
# segment codec versions (docs/INDEX_FORMAT.md)
# ---------------------------------------------------------------------
#
# v1: CSR postings carry (doc_id i32, tf f32); every query re-derives the
#     BM25 tf-saturation from tf + the doc-length column on the device.
# v2: additionally carries a per-field *impact plane*: the BM25
#     tf-saturation tf/(tf + k1·(1-b+b·dl/avgdl)) pre-evaluated at build
#     time under nominal similarity params and quantized to u8/u16 with
#     ONE global per-field scale (BM25S-style eager scoring, arxiv
#     2407.03618), plus a per-128-posting block-max sidecar enabling
#     MaxScore/block-max pruning (GPUSparse, arxiv 2606.26441). The query
#     hot path becomes gather -> scatter-add over integer impacts with no
#     per-query tf/doclen math (search/impactpath.py); exactness vs the
#     f32 oracle is re-established by a certify-or-escalate ladder whose
#     margin folds in the quantization error (ImpactPlane.quant_err).
#     v1 segments still load and serve — the codec is version-gated
#     everywhere (oslint OSL507: consult Segment.codec_version).
CODEC_V1 = 1
CODEC_V2 = 2
IMPACT_BLOCK = 128        # postings per block-max sidecar entry
IMPACT_K1 = 1.2           # nominal build-time similarity params; query-time
IMPACT_B = 0.75           # drift is bounded by ImpactPlane.drift_bound


def default_codec_version() -> int:
    """Codec of NEW segments (refresh/merge): v2. v1 is what an older
    commit on disk loads as and what `Segment.drop_impacts` demotes to;
    nothing builds it. (The benchmark's corpus builders call this.)"""
    return CODEC_V2


def default_impact_bits() -> int:
    """Impact quantization width: 16 (default, error ~scale/2^17) or 8
    via OPENSEARCH_TPU_IMPACT_BITS=8 (half the plane bytes; the wider
    error folds into the same serve margin)."""
    return 8 if os.environ.get("OPENSEARCH_TPU_IMPACT_BITS") == "8" else 16


@dataclass
class ImpactPlane:
    """Quantized eager BM25 impacts for one field's CSR postings (codec
    v2). `q[i]` dequantizes through the designated helpers
    (ops/scoring.py `dequant_impact`/`dequant_impact_np`, oslint OSL507)
    to `q[i] * scale` ~= tf_i/(tf_i + k1·(1-b+b·dl_i/avgdl)) evaluated at
    the BUILD-time nominal (k1, b, avgdl). The block sidecar stores, per
    IMPACT_BLOCK-posting run of each row, the max quantized impact — an
    exact upper bound in the quantized domain, so host/device pruning
    decisions against it carry no extra error term."""

    q: np.ndarray             # u8/u16[P] quantized impacts, CSR-flat
    scale: float              # dequant scale: impact ~= q * scale
    bits: int                 # 8 | 16
    k1: float                 # build-time nominal similarity params
    b: float
    avgdl: float
    dl_max: int               # max doc length seen (drift bound input)
    block_starts: np.ndarray  # i64[nterms+1] block-CSR row pointers
    block_off: np.ndarray     # i64[nblocks] flat element start per block
    block_max: np.ndarray     # u8/u16[nblocks] max q per block
    # "bm25": q dequantizes to the BM25 tf-saturation under the baked
    #   nominal (k1, b, avgdl) — query-time drift priced by drift_bound.
    # "feature": q dequantizes DIRECTLY to the model-assigned feature
    #   weight of a rank_features/sparse_vector posting (opt-in
    #   `index_impacts` mapping param) — weights are query-independent,
    #   so the only serve error is the quantization half-step
    #   (quant_err); drift_bound must never be consulted.
    kind: str = "bm25"

    @property
    def qmax(self) -> int:
        return (1 << self.bits) - 1

    @property
    def nbytes(self) -> int:
        return int(self.q.nbytes + self.block_max.nbytes
                   + self.block_off.nbytes + self.block_starts.nbytes)

    def quant_err(self) -> float:
        """Sound per-posting |exact f32 impact − q·scale| bound at the
        BUILD params: half a quantization step plus f32 slack for the
        dequant multiply."""
        top = np.float32(self.scale) * np.float32(self.qmax)
        return float(self.scale) * 0.5 + 2.0 * float(np.spacing(top))

    def drift_bound(self, k1q: float, bq: float, avgdlq: float) -> float:
        """Sound bound on |f_query − f_build| per posting when query-time
        (k1, b, avgdl) differ from the baked build params: with
        k(dl) = k1·(1-b+b·dl/avgdl) linear in dl, Δk is maximized at a dl
        endpoint, and tf/((tf+ka)(tf+kb)) ≤ 1/(√ka+√kb)² (or its tf=1
        value when the unconstrained max lies below tf=1)."""
        if (float(k1q) == float(self.k1) and float(bq) == float(self.b)
                and float(avgdlq) == float(self.avgdl)):
            return 0.0

        def k_of(dl, k1, b, avg):
            return k1 * (1.0 - b + b * dl / max(avg, 1e-9))

        dk = max(abs(k_of(0.0, k1q, bq, avgdlq)
                     - k_of(0.0, self.k1, self.b, self.avgdl)),
                 abs(k_of(float(self.dl_max), k1q, bq, avgdlq)
                     - k_of(float(self.dl_max), self.k1, self.b,
                            self.avgdl)))
        ka = max(k_of(0.0, k1q, bq, avgdlq), 0.0)
        kb = max(k_of(0.0, self.k1, self.b, self.avgdl), 0.0)
        if ka * kb >= 1.0:
            g = 1.0 / (math.sqrt(ka) + math.sqrt(kb)) ** 2
        else:
            g = 1.0 / ((1.0 + ka) * (1.0 + kb))
        return min(dk * g, 1.0)

    def row_block_range(self, row: int) -> Tuple[int, int]:
        return int(self.block_starts[row]), int(self.block_starts[row + 1])


def build_impact_plane(pb: "PostingsBlock", dl: Optional[np.ndarray],
                       avgdl: Optional[float] = None,
                       bits: Optional[int] = None) -> Optional[ImpactPlane]:
    """Quantize one field's eager impacts + block-max sidecar (the codec
    v2 build step, shared by refresh, merge and direct corpus wrappers).
    The f32 expression mirrors the host oracle's per-posting arithmetic
    (search/fastpath.py `_exact_rescore`) so the quantization-error bound
    is measured against the exact serve domain."""
    if pb.size == 0:
        return None
    bits = default_impact_bits() if bits is None else int(bits)
    tfs = pb.tfs.astype(np.float32)
    if dl is not None:
        dl_of = dl[pb.doc_ids].astype(np.float32)
        dl_max = int(dl.max()) if len(dl) else 0
    else:
        dl_of = np.zeros(pb.size, np.float32)
        dl_max = 0
    if avgdl is None:
        pos = dl_of[dl_of > 0]
        avgdl = float(pos.mean()) if len(pos) else 1.0
    avgdl = max(float(avgdl), 1e-9)
    from ..ops.device_merge import quantize_impacts, use_device_impacts
    qmax = (1 << bits) - 1
    if use_device_impacts(pb.size):
        q32, scale = quantize_impacts(tfs, dl_of, IMPACT_K1, IMPACT_B,
                                      avgdl, qmax)
        q = q32.astype(np.uint8 if bits == 8 else np.uint16)
    else:
        kfac = IMPACT_K1 * (1.0 - IMPACT_B + IMPACT_B * dl_of / avgdl)
        imp = tfs / (tfs + kfac)
        m = float(imp.max()) if len(imp) else 0.0
        scale = (m / qmax) if m > 0 else 1.0
        q = np.minimum(np.round(imp / np.float32(scale)), qmax).astype(
            np.uint8 if bits == 8 else np.uint16)
    block_starts, block_off, block_max = _impact_sidecar(pb, q)
    return ImpactPlane(q=q, scale=float(scale), bits=bits,
                       k1=IMPACT_K1, b=IMPACT_B, avgdl=float(avgdl),
                       dl_max=dl_max, block_starts=block_starts,
                       block_off=block_off, block_max=block_max)


def _impact_sidecar(pb: "PostingsBlock", q: np.ndarray):
    """Per-IMPACT_BLOCK-posting block-max sidecar over one quantized
    plane: (block_starts i64[nterms+1], block_off i64[nblocks],
    block_max u8/u16[nblocks])."""
    lens = np.diff(pb.starts)
    nblk = -(-lens // IMPACT_BLOCK)           # ceil; empty rows -> 0 blocks
    block_starts = np.zeros(len(lens) + 1, np.int64)
    np.cumsum(nblk, out=block_starts[1:])
    nblocks = int(block_starts[-1])
    if nblocks:
        # flat element offset of each block: row start + j*IMPACT_BLOCK
        row_of_blk = np.repeat(np.arange(len(lens), dtype=np.int64), nblk)
        j = np.arange(nblocks, dtype=np.int64) - block_starts[row_of_blk]
        block_off = pb.starts[row_of_blk].astype(np.int64) \
            + j * IMPACT_BLOCK
        block_max = np.maximum.reduceat(q, block_off)
    else:
        block_off = np.zeros(0, np.int64)
        block_max = np.zeros(0, q.dtype)
    return block_starts, block_off, block_max


def build_feature_impact_plane(pb: "PostingsBlock",
                               bits: Optional[int] = None
                               ) -> Optional[ImpactPlane]:
    """Quantize one rank_features/sparse_vector field's model-assigned
    weights into a codec-v2 impact plane (`kind="feature"`, opt-in via
    the `index_impacts` mapping param). The CSR "tf" slot of a feature
    field IS the weight, so the plane stores round(w / scale) with one
    global scale — the learned-sparse dot product then serves through
    the SAME block-max prune → integer gather → certify-or-escalate
    ladder as BM25 impacts (GPUSparse, arxiv 2606.26441), with
    quantization as the only error source (no similarity-param drift:
    weights are query-independent). Mapping-level validation guarantees
    positive weights; a degenerate all-zero plane declines."""
    if pb.size == 0:
        return None
    bits = default_impact_bits() if bits is None else int(bits)
    qmax = (1 << bits) - 1
    w = pb.tfs.astype(np.float32)
    m = float(w.max()) if len(w) else 0.0
    if m <= 0.0:
        return None
    scale = m / qmax
    q = np.minimum(np.round(w / np.float32(scale)), qmax).astype(
        np.uint8 if bits == 8 else np.uint16)
    block_starts, block_off, block_max = _impact_sidecar(pb, q)
    return ImpactPlane(q=q, scale=float(scale), bits=bits,
                       k1=0.0, b=0.0, avgdl=1.0, dl_max=0,
                       block_starts=block_starts, block_off=block_off,
                       block_max=block_max, kind="feature")

# memory accounting for the per-segment DEVICE column cache
# (`device_arrays` HBM residency) goes through the HBM ledger
# (obs/hbm_ledger.py), the single source of truth for device memory: the
# Node wires its fielddata breaker into the LEDGER and every residency
# build registers an attributed allocation there — the breaker charge is
# derived from the registration (oslint OSL506). Charged once per
# (segment, device) pytree build, released by a weakref finalizer when
# the segment is GC'd (segments are immutable and replaced wholesale on
# refresh/merge) or eagerly by `drop_device`.


def _tree_nbytes(tree) -> int:
    """Total array bytes of a (nested dict of) arrays pytree."""
    if isinstance(tree, dict):
        return sum(_tree_nbytes(v) for v in tree.values())
    return int(getattr(tree, "nbytes", 0))


def next_pow2(n: int, floor: int = 16) -> int:
    n = max(int(n), floor)
    return 1 << (n - 1).bit_length()


class _BuildLock:
    """Reentrant per-segment build lock that also exposes its hold depth.
    Pressure eviction (`Segment.evict_device`) must refuse a segment
    whose build is in flight, but the evictor frequently runs ON the
    builder's own thread (ledger register -> `_evict_lru` -> evictor,
    all inside a build's critical section) — a bare RLock's reentrant
    acquire would succeed there and let a mid-build plane be dropped.
    The depth counter is only mutated while the lock is held, so reading
    `depth > 1` after a successful acquire is exact.

    The static concurrency pass models this wrapper as a reentrant lock
    kind ("BuildLock"), so the build path's re-entry is exempt from the
    OSL701 self-deadlock rule while its nesting over the HBM ledger
    stays a committed edge in lock_order.json."""

    __slots__ = ("_lock", "depth")

    def __init__(self) -> None:
        import threading
        self._lock = threading.RLock()
        self.depth = 0

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._lock.acquire(blocking, timeout)
        if ok:
            self.depth += 1
        return ok

    def release(self) -> None:
        self.depth -= 1
        self._lock.release()

    def __enter__(self) -> "_BuildLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()


class _DevicePut:
    """jnp stand-in whose asarray lands on a specific device (replica
    re-hosting path in Segment.device_arrays)."""

    def __init__(self, device):
        self.device = device

    def asarray(self, x):
        import jax
        # transfer helper: every caller (device_arrays/pruned_arrays
        # builds) registers the residency with the ledger
        return jax.device_put(np.asarray(x), self.device)  # oslint: disable=OSL506


def _pad_to(arr: np.ndarray, size: int, fill) -> np.ndarray:
    out = np.full(size, fill, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


def split_i64(vals: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """i64 -> (hi i32, lo i32-with-offset-binary) such that lexicographic
    (hi, lo) compare == signed 64-bit compare. lo is biased by 2^31 so a plain
    signed compare works on the low word."""
    v = vals.astype(np.int64)
    hi = (v >> 32).astype(np.int32)
    lo = ((v & 0xFFFFFFFF) - (1 << 31)).astype(np.int64).astype(np.int32)
    return hi, lo


@dataclass
class PostingsBlock:
    """CSR postings for one indexed field."""

    field: str
    vocab: List[str]                    # row -> term (sorted)
    terms: Dict[str, int]               # term -> row
    starts: np.ndarray                  # i64[nterms+1] host row pointers
    doc_ids: np.ndarray                 # i32[P] host
    tfs: np.ndarray                     # f32[P] host
    # optional positional data: pos_starts aligned with postings flat index
    pos_starts: Optional[np.ndarray] = None   # i64[P+1]
    positions: Optional[np.ndarray] = None    # i32[total_positions]
    # codec v2: quantized eager impacts + block-max sidecar (None on v1
    # segments and non-text planes — consumers must version-gate)
    impact: Optional[ImpactPlane] = None

    @property
    def nterms(self) -> int:
        return len(self.vocab)

    @property
    def size(self) -> int:
        return int(self.starts[-1])

    def row(self, term: str) -> int:
        """Row for term, or -1 when absent (maps to the guaranteed-empty
        padding row on device)."""
        return self.terms.get(term, -1)

    def doc_freq(self, term: str) -> int:
        r = self.terms.get(term)
        if r is None:
            return 0
        return int(self.starts[r + 1] - self.starts[r])

    def row_slice(self, row: int) -> Tuple[int, int]:
        return int(self.starts[row]), int(self.starts[row + 1])


def rows_in_order(values: np.ndarray,
                  present: np.ndarray) -> Optional[np.ndarray]:
    """`values` with every row that has none (`present` false) taking the
    value of the nearest row before it that has one (the type's least
    before the first), a non-decreasing array a binary search can read,
    where the values are non-decreasing in row order; None where they are
    not. The rows without a value take no part: the running maximum
    forward-fills them, and a row in order is one that is its own running
    maximum. The one predicate a date plane's `planes.run_starts` and a
    column's `NumericColumn.in_row_order` ask."""
    floats = values.dtype.kind == "f"
    if present.all():
        filled = values
        ordered = not (values[1:] < values[:-1]).any()
    else:
        least = -np.inf if floats else np.iinfo(values.dtype).min
        filled = np.maximum.accumulate(np.where(present, values, least))
        ordered = not (present & (values < filled)).any()
    if not ordered or (floats and np.isnan(filled).any()):  # (no order
        return None                                         # holds a NaN)
    return filled


@dataclass
class NumericColumn:
    field: str
    kind: str                 # "int" (long family, exact i64) | "float"
    values: np.ndarray        # host i64 or f64
    present: np.ndarray       # bool[ndocs]

    _sort_ords: Optional[np.ndarray] = None
    _min_max: Optional[Tuple[float, float]] = None

    @cached_property
    def in_row_order(self) -> Optional[np.ndarray]:
        """`rows_in_order` of the column, computed once (the column is
        immutable; one pass, and no copy where every row has a value):
        what `compiler.row_span` searches for a range's first and last
        row. None for a column in no row order."""
        return rows_in_order(self.values, self.present)

    @property
    def min_max(self) -> Tuple[float, float]:
        """(min, max) of the present values, computed once: the column is
        immutable, and `compiler.can_match` asks on every range of every
        request (two passes over the column: 0.45 s at 49M rows)."""
        if self._min_max is None:
            if not self.present.any():
                self._min_max = (0.0, 0.0)
            else:
                vals = self.values[self.present]
                self._min_max = (float(vals.min()), float(vals.max()))
        return self._min_max

    def sort_ords(self) -> np.ndarray:
        """Per-doc rank of the value among the segment's distinct values —
        exact i32 sort keys for device top-k even when values need 64 bits
        (see SURVEY §2.5 sort). Missing docs get rank -1."""
        if self._sort_ords is None:
            ords = np.full(len(self.values), -1, dtype=np.int32)
            if self.present.any():
                uniq = np.unique(self.values[self.present])
                ords[self.present] = np.searchsorted(uniq, self.values[self.present]).astype(np.int32)
            self._sort_ords = ords
        return self._sort_ords


@dataclass
class KeywordColumn:
    field: str
    vocab: List[str]          # sorted distinct values
    starts: np.ndarray        # i64[ndocs+1] doc-major CSR
    ords: np.ndarray          # i32[total_values]
    doc_of_value: np.ndarray  # i32[total_values] (doc id per flat value)
    min_ord: np.ndarray       # i32[ndocs], -1 = missing

    @property
    def present(self) -> np.ndarray:
        return self.min_ord >= 0


@dataclass
class GeoColumn:
    field: str
    lat: np.ndarray           # f32[ndocs]
    lon: np.ndarray           # f32[ndocs]
    present: np.ndarray


@dataclass
class ShapeColumn:
    """geo_shape storage: host-resident shape specs + per-doc bbox columns.

    The TPU split (vs the reference's Lucene BKD tesselation,
    `index/mapper/GeoShapeFieldMapper.java`): bboxes give a vectorized
    numpy prefilter; exact relations (search/geo.py) run on the host over
    bbox survivors at plan-prepare time; the result is a per-(segment,
    query) boolean mask uploaded as a plan param — static device shapes,
    and the mask rides the (segment, plan) filter cache."""

    field: str
    specs: list                    # per-doc: list of GeoJSON/WKT specs or None
    minx: np.ndarray               # f64[ndocs] bbox columns
    miny: np.ndarray
    maxx: np.ndarray
    maxy: np.ndarray
    present: np.ndarray            # bool[ndocs]
    _parsed: Any = None            # lazy per-doc merged Shape cache

    def shape(self, doc: int):
        """Merged Shape for one doc (multiple values = one collection)."""
        from ..search.geo import Shape, parse_shape
        if self._parsed is None:
            self._parsed = [None] * len(self.specs)
        s = self._parsed[doc]
        if s is None and self.specs[doc]:
            parts = [parse_shape(sp) for sp in self.specs[doc]]
            if len(parts) == 1:
                s = parts[0]
            else:
                s = Shape()
                s.points = np.concatenate([p.points for p in parts])
                for p in parts:
                    s.lines += p.lines
                    s.polys += p.polys
                s.finish()
            self._parsed[doc] = s
        return s

    def bbox_candidates(self, qbbox) -> np.ndarray:
        """bool[ndocs]: docs whose bbox overlaps the query bbox."""
        qminx, qminy, qmaxx, qmaxy = qbbox
        return (self.present & (self.minx <= qmaxx) & (self.maxx >= qminx)
                & (self.miny <= qmaxy) & (self.maxy >= qminy))


@dataclass
class VectorColumn:
    """Dense vectors for kNN search, row-major [ndocs, dims] (brute-force
    exact kNN runs as one MXU matmul per segment — see ops/knn; the
    reference's k-NN plugin uses HNSW/faiss, approximate)."""

    field: str
    values: np.ndarray        # f32[ndocs, dims]
    present: np.ndarray       # bool[ndocs]
    similarity: str = "cosine"
    # ANN method from the mapping ({"name": "ivf", "nlist", "nprobe"});
    # None = exact scan only (see ops/ann.py for the IVF design)
    method: Optional[dict] = None
    # unit-norm copy for cosine (precomputed at build)
    _normed: Optional[np.ndarray] = None
    _ivf: Any = None

    def normed(self) -> np.ndarray:
        if self._normed is None:
            n = np.linalg.norm(self.values, axis=1, keepdims=True)
            self._normed = (self.values / np.maximum(n, 1e-12)).astype(np.float32)
        return self._normed

    def ivf(self, mat=None):
        """Lazily built balanced-IVF index (deterministic: same data ->
        same index, so persistence only records the method, not arrays).
        `mat`: the column's resident device matrix (`device_arrays`' "mat":
        the scored rows, padded), which the build then reads where it
        lies; without it the host rows are put on the device for the
        build and dropped after."""
        if self._ivf is None and self.method and self.method.get("name") == "ivf":
            from ..ops.ann import build_ivf
            if mat is None:
                mat = self.normed() if self.similarity == "cosine" \
                    else self.values
            self._ivf = build_ivf(mat, self.present,
                                  nlist=self.method.get("nlist"),
                                  nprobe=self.method.get("nprobe"))
        return self._ivf


@dataclass
class TextFieldStats:
    doc_count: int = 0        # docs containing this field
    sum_dl: int = 0           # total tokens across docs


@dataclass
class NestedBlock:
    """Block-join children for one nested path: a full child-space Segment
    (its docs are the nested objects, fields keyed by dotted path) plus the
    child->parent doc map. The reference stores children as adjacent Lucene
    docs in the parent's block (NestedObjectMapper/ToParentBlockJoinQuery);
    here the child space is its own CSR segment and the join is a device
    scatter-reduce over `parent_of`."""

    child: "Segment"
    parent_of: np.ndarray  # i32[child.ndocs], nondecreasing (doc order)

    def children_of(self, parent_doc: int) -> Tuple[int, int]:
        # the needle in the map's own dtype: a Python int makes numpy cast
        # the whole map a call (38 ms at 18.9M children, twice a hit)
        doc = self.parent_of.dtype.type(parent_doc)
        a = int(np.searchsorted(self.parent_of, doc, side="left"))
        b = int(np.searchsorted(self.parent_of, doc, side="right"))
        return a, b


def _read_only(a: np.ndarray) -> np.ndarray:
    v = a.view()
    v.flags.writeable = False
    return v


class Segment:
    """One immutable searchable unit (analog of a Lucene segment + its
    SegmentReader, reference `index/engine/Engine.java#acquireSearcher`).

    **Who writes `live`, the one part that is not immutable.** Two forms,
    both on the write side (the engine's write lock, or before the segment
    is published to a searcher), so `live_count` is only ever written where
    the mask is and a reader never writes either:

    - `delete_doc(local_doc)` clears one row. `live_count` steps down by one
      where the row was live and stays where it was not (a second delete of
      one row counts once); `live_gen` steps up and every device copy of the
      mask is marked dirty, as before the count was kept.
    - `seg.live = mask` replaces the whole mask (`load`, `reorder`, merge's
      temporary swap on nested children, builders that wrap ready-made
      arrays). The segment takes the array over: the caller keeps no
      reference it writes through (an array that is not writeable, or not
      bool, is copied). The assignment counts the mask once
      (`segment.live_recounts` in `METRICS` steps by one) and touches
      neither `live_gen` nor the device plane's dirty flags.

    `seg.live` reads as a numpy bool array that is NOT writeable: an element
    written from outside would leave `live_count` stale, so it raises. A
    reader that needs a writeable mask takes a copy. `live_count` is that
    kept integer: exact at every instant, O(1), never a sum over the mask."""

    _seq = 0

    def __init__(self, name: str, ndocs: int,
                 postings: Dict[str, PostingsBlock],
                 numeric_cols: Dict[str, NumericColumn],
                 keyword_cols: Dict[str, KeywordColumn],
                 geo_cols: Dict[str, GeoColumn],
                 doc_lens: Dict[str, np.ndarray],
                 text_stats: Dict[str, TextFieldStats],
                 ids: List[str], sources: List[dict],
                 seq_nos: Optional[np.ndarray] = None,
                 vector_cols: Optional[Dict[str, VectorColumn]] = None,
                 nested: Optional[Dict[str, NestedBlock]] = None,
                 shape_cols: Optional[Dict[str, ShapeColumn]] = None,
                 stored_vals: Optional[list] = None,
                 codec_version: int = CODEC_V1):
        Segment._seq += 1
        self.uid = Segment._seq  # stable identity (id() can be reused post-GC)
        self.name = name
        self.ndocs = ndocs
        self.postings = postings
        self.numeric_cols = numeric_cols
        self.keyword_cols = keyword_cols
        self.geo_cols = geo_cols
        self.vector_cols = vector_cols or {}
        self.shape_cols = shape_cols or {}
        # per-doc {field: [raw values]} for store=true fields (reference
        # stored fields, independent of _source)
        self.stored_vals = stored_vals
        # term_vector offsets per field -> per-doc [(term, pos, start, end)]
        self.term_vectors: Optional[Dict[str, list]] = None
        self.doc_lens = doc_lens
        self.text_stats = text_stats
        self.nested: Dict[str, NestedBlock] = nested or {}
        self.ids = ids
        self.sources = sources
        self.seq_nos = seq_nos if seq_nos is not None else np.zeros(ndocs, dtype=np.int64)
        # the mask, the view of it that `live` hands out and the number of
        # set rows go together: here, in `live`'s setter and in `delete_doc`
        self._live = np.ones(ndocs, dtype=bool)
        self._live_ro = _read_only(self._live)
        self._live_count = ndocs
        self.live_gen = 0
        self.id2doc: Dict[str, int] = {d: i for i, d in enumerate(ids)}
        # per-device host->HBM residency: key None = process default device;
        # replicas re-host the SAME immutable arrays on their own device
        # (segment replication, reference indices/replication/)
        self._device_cache: Dict[Any, dict] = {}
        # the positional planes of the text fields, by the same key, beside
        # the pytree (`device_positions`); swapped with it
        self._device_positions: Dict[Any, dict] = {}
        self._device_live_dirty: Dict[Any, bool] = {}
        # segment codec (CODEC_V1 | CODEC_V2): consumers branching on the
        # posting layout consult this attribute (oslint OSL507)
        self.codec_version = int(codec_version)
        # v2 fields whose f32 tf plane has been promoted back onto the
        # device (exact-scoring programs on codec-v2 segments request it
        # lazily via ensure_device_tfs; the hot impact path never does)
        self._tf_promoted: set = set()

    # ---------------- arrival-order tie ranks ----------------

    def tie_ranks(self) -> Optional[np.ndarray]:
        """Arrival-rank tie-break plane, or None when internal doc order
        IS arrival order (every segment the BP reorder pass has not
        touched — ids are assigned in write order and merges
        concatenate, so seq_nos ascend with doc id). After the merge-time
        doc-id reorder (index/reorder.py) score ties must still break in
        a layout-invariant order — the reorder parity contract: the same
        corpus indexed with and without the permutation serves
        byte-identical pages — so serving-path selections/sorts key ties
        on rank-of-seq_no instead of the (permuted) internal id. Lazy,
        cached; i64[ndocs] when present."""
        if "_tie_rank" not in self.__dict__:
            # gate on the explicit reorder marker, NOT a seq_no shape
            # heuristic: ordinary tiered merges concatenate segments in
            # live_count order, so never-reordered segments routinely
            # carry non-monotonic seq_nos — inferring "reordered" from
            # that would change their historical tie semantics (and tax
            # every query with the tie machinery). apply_permutation
            # pins the exact plane; this branch only reconstructs it
            # for marked segments reloaded without a persisted plane.
            s = np.asarray(self.seq_nos, np.int64)
            if not self.__dict__.get("_reordered") or len(s) < 2 \
                    or bool(np.all(np.diff(s) >= 0)):
                self.__dict__["_tie_rank"] = None
            else:
                tr = np.empty(len(s), np.int64)
                tr[np.argsort(s, kind="stable")] = np.arange(
                    len(s), dtype=np.int64)
                self.__dict__["_tie_rank"] = tr
        return self.__dict__["_tie_rank"]

    # ---------------- codec v2: impact planes ----------------

    def build_impacts(self, bits: Optional[int] = None,
                      feature_fields: Sequence[str] = ()) -> None:
        """Build quantized impact planes for every text-scored field
        (fields with a doc-length column) and stamp the segment codec v2.
        `feature_fields` names rank_features/sparse_vector fields whose
        mapping opted into `index_impacts`: those get a FEATURE plane
        (model-assigned weights quantized directly, kind="feature") so
        `neural_sparse` serves through the impact ladder.
        Idempotent; used by build_segment/merge and by direct CSR corpus
        wrappers (benchmark/corpus.py, scripts/hbm_report.py)."""
        feature_fields = set(feature_fields)
        for f, pb in self.postings.items():
            if pb.impact is not None:
                continue
            if f in feature_fields and f not in self.doc_lens:
                pb.impact = build_feature_impact_plane(pb, bits=bits)
                continue
            if f not in self.doc_lens:
                continue
            st = self.text_stats.get(f)
            avgdl = (st.sum_dl / st.doc_count
                     if st is not None and st.doc_count > 0 else None)
            pb.impact = build_impact_plane(pb, self.doc_lens.get(f),
                                           avgdl=avgdl, bits=bits)
        for blk in self.nested.values():
            blk.child.build_impacts(bits=bits)
        self.codec_version = CODEC_V2

    def drop_impacts(self) -> None:
        """Demote to codec v1 (compat/ablation path): planes dropped,
        device residency rebuilt with the tf plane on next use."""
        for pb in self.postings.values():
            pb.impact = None
        for blk in self.nested.values():
            blk.child.drop_impacts()
        self.codec_version = CODEC_V1
        self._tf_promoted = set()
        self.drop_device()

    # ---------------- live docs / deletes ----------------

    @property
    def live(self) -> np.ndarray:
        return self._live_ro

    @live.setter
    def live(self, mask: np.ndarray) -> None:
        mask = np.asarray(mask, dtype=bool)
        if not mask.flags.writeable:
            mask = mask.copy()
        SEGMENT_STATS.inc("live_recounts")
        self._live = mask
        self._live_ro = _read_only(mask)
        self._live_count = int(np.count_nonzero(mask))

    def delete_doc(self, local_doc: int) -> None:
        if self._live[local_doc]:
            self._live[local_doc] = False
            self._live_count -= 1
        for k in self._device_live_dirty:
            self._device_live_dirty[k] = True
        self.live_gen += 1  # invalidates live-dependent host caches

    @property
    def live_count(self) -> int:
        return self._live_count

    # ---------------- device residency ----------------

    @property
    def ndocs_pad(self) -> int:
        return next_pow2(self.ndocs)

    def kw_multi_valued(self, field: str) -> bool:
        """Whether some document holds two values of keyword `field` or
        more, which is whether the column holds more values than
        documents that have one: a pass over `min_ord`, once a segment
        (`planes.drop_segment_planes` forgets it with a rematerialized
        field), and no temporary the size of the row pointers (their
        differences are 133 MB of fresh pages at 2^24 rows: as slow as the
        two planes' padding the single-valued form saves). It decides the
        column's form on the device (`_kw_field_arrays`) and a
        composite's over it."""
        cache = self.__dict__.setdefault("_kw_multi_cache", {})
        if field not in cache:
            col = self.keyword_cols[field]
            cache[field] = len(col.ords) > int(
                np.count_nonzero(col.min_ord >= 0))
        return cache[field]

    def device_arrays(self, device=None) -> dict:
        """The pytree of device-resident arrays consumed by `ops` kernels.
        Shapes are padded to pow2 buckets. The structure follows the
        mapping and, for a keyword column, what the segment observes in its
        own data (`kw_multi_valued`: one plane where no document holds two
        values, three where one does), so segments of one index mostly
        share a compiled plan and two that differ there compile one each.
        `device`: re-host on a specific device (replica placement); None =
        the process default."""
        import jax
        import jax.numpy as jnp

        key = device
        from ..obs.hbm_ledger import LEDGER
        # recency signal for LRU pressure eviction (lock-free hot path)
        LEDGER.touch(self, key)
        # SNAPSHOT the cache dict: pressure eviction (evict_device ->
        # drop_device) swaps `_device_cache` for a fresh dict rather than
        # mutating it, so a reader holding this reference keeps a valid
        # entry even when the evictor fires between its membership check
        # and its deref — the arrays stay alive until the last consumer
        # drops them
        cache = self._device_cache
        if key not in cache:
            # per-SEGMENT build lock: two request threads racing the same
            # (segment, device) miss would otherwise both build and both
            # charge the breaker (only one dict entry wins but both
            # finalizers release — a persistent double-charge), while
            # builds of DIFFERENT segments still overlap. dict.setdefault
            # is atomic under the GIL, so every racer gets the same lock;
            # reentrant because a parent's build recurses into nested
            # children (child locks are acquired parent->child, acyclic).
            lock = self.__dict__.setdefault(
                "_device_build_lock", _BuildLock())
            with lock:
                # the evictor takes this same lock, so the re-read below
                # cannot race a drop of THIS segment's residency
                cache = self._device_cache
                if key not in cache:
                    self._build_device_arrays(key, device)
                    cache = self._device_cache
        entry = cache[key]
        # `"live" not in entry` backstops a torn (old-cache, new-dirty)
        # pair: a stale reader's dirty=False write must never leave a
        # freshly rebuilt entry serving without its live plane
        if self._device_live_dirty.get(key, True) or "live" not in entry:
            live = _pad_to(self.live.astype(np.float32), self.ndocs_pad,
                           np.float32(0))
            entry["live"] = (
                # constant-size live plane, charged by the
                # _build_device_arrays ledger registration
                jnp.asarray(live) if device is None
                else jax.device_put(live, device))  # oslint: disable=OSL506
            self._device_live_dirty[key] = False
        return entry

    def _build_device_arrays(self, key, device) -> None:
        """Build + breaker-charge one (segment, device) cache entry.
        Caller holds _DEVICE_BUILD_LOCK and has re-checked the cache, so
        exactly one thread ever charges a given entry."""
        _t_dev = time.perf_counter()
        import jax.numpy as jnp

        if device is not None:
            jnp = _DevicePut(device)  # route jnp.asarray onto the device
        dpad = self.ndocs_pad
        post = {f: _post_field_arrays(
                    pb, jnp,
                    with_tfs=(pb.impact is None or f in self._tf_promoted))
                for f, pb in self.postings.items()}
        ncols = {f: _num_field_arrays(col, dpad, jnp)
                 for f, col in self.numeric_cols.items()}
        kcols = {f: _kw_field_arrays(col, dpad, jnp, self.kw_multi_valued(f))
                 for f, col in self.keyword_cols.items()}
        vcols = {}
        for f, col in self.vector_cols.items():
            dims = col.values.shape[1]
            dpad128 = ((dims + 127) // 128) * 128  # MXU lane alignment
            # the one padded host copy; dropped once it is on the device
            mat = np.zeros((dpad, dpad128), np.float32)
            mat[: self.ndocs, :dims] = (col.normed() if col.similarity
                                        == "cosine" else col.values)
            vcols[f] = {
                "mat": jnp.asarray(mat),
                "present": jnp.asarray(_pad_to(col.present, dpad, False)),
            }
            del mat
            # built from the resident matrix: the vectors are on the
            # device once, under the build as under the queries
            ivf = col.ivf(vcols[f]["mat"])
            if ivf is not None:
                # nlist padded pow2; padding rows are invalid (cvalid
                # False -> -inf centroid score, fill 0)
                lpad = next_pow2(ivf.nlist)
                cent = np.zeros((lpad, dpad128), np.float32)
                cent[: ivf.nlist, : ivf.centroids.shape[1]] = ivf.centroids
                cvalid = np.zeros(lpad, bool)
                cvalid[: ivf.nlist] = True
                vcols[f]["ivf_centroids"] = jnp.asarray(cent)
                vcols[f]["ivf_cvalid"] = jnp.asarray(cvalid)
                # the rows once more, in list order (ops/ann.py): a probe
                # reads a list as one dense window of this matrix
                from ..ops.ann import list_rows
                vcols[f]["ivf_ids"] = jnp.asarray(ivf.order)
                vcols[f]["ivf_rows"] = list_rows(vcols[f]["mat"],
                                                 vcols[f]["ivf_ids"])
                vcols[f]["ivf_offset"] = jnp.asarray(
                    _pad_to(ivf.offset, lpad, np.int32(0)))
                vcols[f]["ivf_fill"] = jnp.asarray(
                    _pad_to(ivf.fill, lpad, np.int32(0)))
        gcols = {f: _geo_field_arrays(col, dpad, jnp)
                 for f, col in self.geo_cols.items()}
        dls = {f: jnp.asarray(_pad_to(dl.astype(np.float32), dpad, np.float32(0)))
               for f, dl in self.doc_lens.items()}
        # NOTE: values must all be arrays — plain ints would become traced
        # jit arguments and poison static shape derivation downstream
        nst = {}
        for path, blk in self.nested.items():
            carr = dict(blk.child.device_arrays(device))
            cpad = blk.child.ndocs_pad
            # padded children carry live=0, so every scatter-reduce
            # contribution from padding is identically zero; they name the
            # last child's parent, so the plane stays nondecreasing (the
            # join declares its indices sorted: `compiler.emit`, "nested")
            last = blk.parent_of[-1] if len(blk.parent_of) else 0
            carr["parent"] = jnp.asarray(
                _pad_to(blk.parent_of.astype(np.int32), cpad, np.int32(last)))
            nst[path] = carr
        self._device_cache[key] = {
            "postings": post, "numeric": ncols, "keyword": kcols, "geo": gcols,
            "vector": vcols, "doc_lens": dls, "nested": nst,
        }
        # the positional planes of the fields that hold positions: resident
        # with the segment, beside the pytree and not in it (a phrase
        # program is handed its field's planes by `compiler.prepare`; no
        # other program's signature knows them)
        pos_planes = {f: _position_planes(pb, jnp)
                      for f, pb in self.postings.items()
                      if pb.positions is not None and len(pb.positions)}
        self._device_positions[key] = pos_planes
        # attributed only while a refresh/merge build is collecting —
        # lazy query-time promotion hits the no-op path
        note_stage("device_promote", time.perf_counter() - _t_dev)
        from ..obs.hbm_ledger import LEDGER
        # register THIS segment's new device residency with the HBM
        # ledger (which derives the breaker charge): every group built
        # above, the per-path "parent" maps, and the live plane
        # (constant size across dirty rebuilds). The nested children's
        # own arrays are registered by their recursive device_arrays()
        # calls — counting them here would double-bill. Codec v2 splits
        # the quantized impact planes out into their own `impact_postings`
        # tenant (and the host block-max sidecar into an advisory
        # `block_max` tenant) so the format rev's footprint delta is a
        # first-class ledger observable.
        imp_bytes = sum(int(fa["impacts"].nbytes)
                        for fa in post.values() if "impacts" in fa)
        # dense-vector residency is its own tenant pair (ISSUE 15: kNN
        # as a first-class serving citizen needs its HBM bytes visible):
        # the doc matrices under `vector_columns`, the balanced-IVF
        # probe structures (centroids + validity, the rows in list order
        # with their ids, offsets and fills) under `ann_ivf` — both
        # still charged, just attributed
        ivf_bytes = sum(int(a.nbytes) for v in vcols.values()
                        for k2, a in v.items() if k2.startswith("ivf_"))
        vec_bytes = _tree_nbytes(vcols) - ivf_bytes
        nbytes = sum(_tree_nbytes(self._device_cache[key][g])
                     for g in ("postings", "numeric", "keyword",
                               "geo", "doc_lens"))
        nbytes -= imp_bytes
        nbytes += sum(int(c["parent"].nbytes)
                      for c in nst.values())
        nbytes += self.ndocs_pad * 4          # live plane (f32)
        allocs = []
        try:
            # evictor: under breaker pressure the ledger may call
            # evict_device (weakly held) to reclaim this whole plane
            # group — the entry rebuilds transparently on next use
            allocs.append(LEDGER.register(
                "segment_columns", nbytes, owner=self, segment=self,
                device=key, label=f"segment-device[{self.name}]",
                evictor=self.evict_device))
            if vec_bytes:
                allocs.append(LEDGER.register(
                    "vector_columns", vec_bytes, owner=self,
                    segment=self, device=key,
                    label=f"segment-vectors[{self.name}]",
                    evictor=self.evict_device))
            if ivf_bytes:
                allocs.append(LEDGER.register(
                    "ann_ivf", ivf_bytes, owner=self, segment=self,
                    device=key, label=f"segment-ivf[{self.name}]",
                    evictor=self.evict_device))
            if pos_planes:
                allocs.append(LEDGER.register(
                    "position_planes", _tree_nbytes(pos_planes), owner=self,
                    segment=self, device=key,
                    label=f"segment-positions[{self.name}]",
                    evictor=self.evict_device))
            if imp_bytes:
                allocs.append(LEDGER.register(
                    "impact_postings", imp_bytes, owner=self, segment=self,
                    device=key, label=f"segment-impacts[{self.name}]",
                    evictor=self.evict_device))
                sidecar = sum(pb.impact.block_max.nbytes
                              + pb.impact.block_off.nbytes
                              + pb.impact.block_starts.nbytes
                              for pb in self.postings.values()
                              if pb.impact is not None)
                # the sidecar is HOST-resident plan metadata (the XLA
                # prune selects blocks before launch); advisory so the
                # byte is visible per tenant without billing the breaker
                allocs.append(LEDGER.register(
                    "block_max", sidecar, owner=self, segment=self,
                    device=key, charge=False,
                    label=f"segment-blockmax[{self.name}]"))
        except Exception:
            # tripped mid-way: roll back what was charged and drop the
            # entry so a later retry re-attempts instead of serving free
            for a in allocs:
                LEDGER.release(a)
            del self._device_cache[key]
            del self._device_positions[key]
            raise
        self.__dict__.setdefault("_hbm_allocs", {}).setdefault(
            key, []).extend(allocs)
        # full-residency promotion: the partial per-field arrays this
        # device key accumulated via pruned_arrays() are now redundant —
        # the full pytree supersedes them (pruned_arrays serves from it
        # on every later call). Drop them and release their ledger
        # charges, or the overlapping term arrays stay double-counted
        # for the segment's lifetime.
        fcache = self.__dict__.get("_field_device_cache")
        if fcache:
            for ck in [c for c in fcache if c[0] == key]:
                del fcache[ck]
        fallocs = self.__dict__.get("_field_device_allocs")
        if fallocs:
            for ck in [c for c in fallocs if c[0] == key]:
                LEDGER.release(fallocs.pop(ck))
        self._device_live_dirty[key] = True

    def device_positions(self, field: str, device=None) -> Optional[dict]:
        """The resident positional planes of `field` ({"doc", "pos"}: one
        slot a position, in postings order, so a term's positions are one
        window sorted by (doc, position); and their fence levels,
        {"doc_f1", "pos_f1", ...}: `_position_planes`), promoted with the
        segment's device arrays on `device` and dropped with them; None
        where the field holds no position. The same discipline as `device_arrays`:
        `_device_positions` is swapped, never emptied in place, so the
        dict read here stays whole whatever is dropped meanwhile, and a
        miss promotes and reads under the build lock, which the pressure
        evictor has to take too: nothing is evicted between the two."""
        pb = self.postings.get(field)
        if pb is None or pb.positions is None or not len(pb.positions):
            return None
        planes = self._device_positions
        if device not in planes:
            lock = self.__dict__.setdefault(
                "_device_build_lock", _BuildLock())
            with lock:
                self.device_arrays(device)
                planes = self._device_positions
        return planes[device].get(field)

    def ensure_device_tfs(self, field: str, device=None) -> None:
        """Promote the f32 tf plane of one codec-v2 field back onto the
        device. The v2 layout ships (doc_ids, quantized impacts) only —
        the BM25 hot path never touches tf — but exact-scoring program
        variants (non-BM25 similarities, combined_fields BM25F, the
        impact ladder's dense escalation) still need it. Called at
        prepare time (host side, before any launch); one upload per
        (segment, field), every current and future device key included."""
        pb = self.postings.get(field)
        if pb is None or pb.impact is None or field in self._tf_promoted:
            return
        import jax
        import jax.numpy as _jnp
        from ..obs.hbm_ledger import LEDGER
        lock = self.__dict__.setdefault(
            "_device_build_lock", _BuildLock())
        with lock:
            if field in self._tf_promoted:
                return
            ppad = next_pow2(pb.size)
            tf_host = _pad_to(pb.tfs.astype(np.float32), ppad,
                              np.float32(0))
            for key, cache in self._device_cache.items():
                fa = cache["postings"].get(field)
                if fa is None or "tfs" in fa:
                    continue
                arr = (_jnp.asarray(tf_host) if key is None
                       else jax.device_put(tf_host, key))
                alloc = LEDGER.register(
                    "postings_tfs", int(arr.nbytes), owner=self,
                    segment=self, device=key,
                    label=f"segment-tfs[{self.name}][{field}]",
                    evictor=self.evict_device)
                fa["tfs"] = arr
                self.__dict__.setdefault("_hbm_allocs", {}).setdefault(
                    key, []).append(alloc)
            # future device builds include the plane from the start
            self._tf_promoted.add(field)

    def pruned_arrays(self, device, needs: Dict[str, set]) -> dict:
        """Device arrays for ONLY the named fields — the filter-mask path
        uses this so building a status-term mask never ships the body
        postings to HBM (device_arrays is all-or-nothing; jit argument
        pruning happens after the transfer already paid). Per-field device
        arrays are cached and ledger-registered as `partial_columns`; a
        later full device_arrays() build PROMOTES this partial residency —
        the per-field arrays are dropped and their charges released, so
        overlapping term arrays are never double-counted.
        `needs` keys: postings / numeric / keyword / geo -> field sets."""
        key = device
        from ..obs.hbm_ledger import LEDGER
        LEDGER.touch(self, key)
        if key in self._device_cache:
            # the full pytree already exists: serve from it (no extra HBM)
            return self.device_arrays(device)
        # the SAME per-segment build lock device_arrays takes: two racing
        # partial builds of one field must not both register (the loser's
        # charge would leak until segment GC), and the full build's
        # promotion sweep iterates these dicts under this lock
        lock = self.__dict__.setdefault(
            "_device_build_lock", _BuildLock())
        with lock:
            return self._pruned_arrays_locked(key, device, needs)

    def _pruned_arrays_locked(self, key, device, needs: Dict[str, set]
                              ) -> dict:
        import jax
        import jax.numpy as _jnp

        from ..obs.hbm_ledger import LEDGER

        if key in self._device_cache:
            # a racing full build won: serve the promoted pytree
            return self.device_arrays(device)
        jnp = _DevicePut(device) if device is not None else _jnp
        cache = self.__dict__.setdefault("_field_device_cache", {})
        allocs = self.__dict__.setdefault("_field_device_allocs", {})
        dpad = self.ndocs_pad

        def field(group: str, f: str, builder):
            k = (key, group, f)
            if k not in cache:
                arrs = builder()
                allocs[k] = LEDGER.register(
                    "partial_columns", _tree_nbytes(arrs), owner=self,
                    segment=self, device=key,
                    label=f"segment-partial[{self.name}][{group}.{f}]",
                    evictor=self.evict_device)
                cache[k] = arrs
            return cache[k]

        out: Dict[str, Any] = {"postings": {}, "numeric": {}, "keyword": {},
                               "geo": {}, "vector": {}, "doc_lens": {},
                               "nested": {}}
        for f in needs.get("postings", ()):
            pb = self.postings.get(f)
            if pb is not None:
                # filter-mask views never score: no tf plane (v1 fields
                # keep it — their layout has nothing else) and no impacts
                out["postings"][f] = field(
                    "postings", f, lambda pb=pb: _post_field_arrays(
                        pb, jnp, with_tfs=False, with_impacts=False))
        for f in needs.get("numeric", ()):
            col = self.numeric_cols.get(f)
            if col is not None:
                out["numeric"][f] = field(
                    "numeric", f,
                    lambda col=col: _num_field_arrays(col, dpad, jnp))
        for f in needs.get("keyword", ()):
            col = self.keyword_cols.get(f)
            if col is not None:
                out["keyword"][f] = field(
                    "keyword", f,
                    lambda col=col, f=f: _kw_field_arrays(
                        col, dpad, jnp, self.kw_multi_valued(f)))
        for f in needs.get("geo", ()):
            col = self.geo_cols.get(f)
            if col is not None:
                out["geo"][f] = field(
                    "geo", f,
                    lambda col=col: _geo_field_arrays(col, dpad, jnp))
        for f in needs.get("doc_lens", ()):
            dl = self.doc_lens.get(f)
            if dl is not None:
                out["doc_lens"][f] = field(
                    "doc_lens", f, lambda dl=dl: jnp.asarray(
                        _pad_to(dl.astype(np.float32), dpad, np.float32(0))))
        lk = (key, "#live", self.live_gen)
        if lk not in cache:
            for stale in [c for c in cache if c[1] == "#live"]:
                del cache[stale]
                LEDGER.release(allocs.pop(stale, None))
            live = _pad_to(self.live.astype(np.float32), self.ndocs_pad,
                           np.float32(0))
            arr = (jax.device_put(live, device) if device is not None
                   else _jnp.asarray(live))
            allocs[lk] = LEDGER.register(
                "partial_columns", int(arr.nbytes), owner=self,
                segment=self, device=key,
                label=f"segment-partial[{self.name}][live]")
            cache[lk] = arr
        out["live"] = cache[lk]
        return out

    def evict_device(self) -> bool:
        """Pressure-eviction hook (obs/hbm_ledger.py `_evict_lru`): drop
        this segment's device residency UNLESS a build is in flight —
        the ledger calls this with its own lock held, so blocking on the
        build lock here would invert the (build lock -> ledger lock)
        order every `_build_device_arrays` takes. A depth check backs up
        the non-blocking acquire: the evictor often runs on the builder's
        OWN thread (a build's ledger registration triggers eviction of a
        sibling this thread is also mid-building, e.g. a nested parent),
        where the reentrant acquire would succeed. Returns True when the
        residency was actually released."""
        held = []
        try:
            # drop_device recurses into nested children, and the
            # compiler builds child planes (ensure_device_tfs) under the
            # CHILD's lock only — so the whole family must be idle, not
            # just the parent, or a pressure evict rips a plane out from
            # under a mid-flight child build
            stack = [self]
            while stack:
                s = stack.pop()
                lock = s.__dict__.setdefault(
                    "_device_build_lock", _BuildLock())
                if not lock.acquire(blocking=False):
                    return False
                held.append(lock)
                if lock.depth > 1:  # this thread is building this segment
                    return False
                stack.extend(blk.child for blk in s.nested.values())
            self.drop_device()
            return True
        finally:
            for lock in reversed(held):
                lock.release()

    def drop_device(self) -> None:
        from ..obs.hbm_ledger import LEDGER
        self._device_cache = {}
        self._device_positions = {}
        self._device_live_dirty = {}
        self.__dict__.pop("_field_device_cache", None)
        # a match_phrase_prefix's merged unions (compiler._union_pairs)
        for held in self.__dict__.pop("_phrase_unions", {}).values():
            LEDGER.release(held[3])
        # eager release: the arrays are gone NOW, so the ledger (and the
        # derived breaker charge) must not wait for the segment's GC
        for allocs in self.__dict__.pop("_hbm_allocs", {}).values():
            for alloc in allocs:
                LEDGER.release(alloc)
        for alloc in self.__dict__.pop("_field_device_allocs", {}).values():
            LEDGER.release(alloc)
        for blk in self.nested.values():
            blk.child.drop_device()

    # ---------------- persistence (flush/commit) ----------------

    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        arrays: Dict[str, np.ndarray] = {"live": self.live, "seq_nos": self.seq_nos}
        tr = self.__dict__.get("_tie_rank")
        if tr is not None:
            # persist the pinned arrival plane verbatim: the seq_no
            # reconstruction on load is only an approximation when the
            # pre-permutation concatenation wasn't seq-ascending (tiered
            # merges order inputs by live_count) or seq_nos are
            # degenerate (direct-CSR corpora default to zeros) — the
            # plane must be byte-identical across a restart or tie pages
            # drift from their replicas
            arrays["tie_rank"] = tr
        meta: Dict[str, Any] = {"name": self.name, "ndocs": self.ndocs,
                                "codec": self.codec_version,
                                # BP reorder pass already ran (index/
                                # reorder.py) — without this, the first
                                # force_merge after a restart re-merges
                                # and re-reorders an already-clustered
                                # segment (~minutes at 1M docs)
                                "reordered": bool(
                                    self.__dict__.get("_reordered", False)),
                                "postings": {}, "numeric": {}, "keyword": {}, "geo": {},
                                "impacts": {},
                                "text_stats": {f: [s.doc_count, s.sum_dl]
                                               for f, s in self.text_stats.items()}}
        derived = self.__dict__.get("_derived_names", set())
        for f, pb in self.postings.items():
            if f in derived:
                continue   # derived fields are query-time only, never persisted
            key = f"post__{f}"
            arrays[f"{key}__starts"] = pb.starts
            arrays[f"{key}__doc_ids"] = pb.doc_ids
            arrays[f"{key}__tfs"] = pb.tfs
            if pb.pos_starts is not None:
                arrays[f"{key}__pos_starts"] = pb.pos_starts
                arrays[f"{key}__positions"] = pb.positions
            if pb.impact is not None:
                ip = pb.impact
                arrays[f"imp__{f}__q"] = ip.q
                arrays[f"imp__{f}__bstarts"] = ip.block_starts
                arrays[f"imp__{f}__boff"] = ip.block_off
                arrays[f"imp__{f}__bmax"] = ip.block_max
                meta["impacts"][f] = {"scale": ip.scale, "bits": ip.bits,
                                      "k1": ip.k1, "b": ip.b,
                                      "avgdl": ip.avgdl,
                                      "dl_max": ip.dl_max,
                                      "kind": ip.kind}
            meta["postings"][f] = {"vocab_file": True, "positional": pb.pos_starts is not None}
            with open(os.path.join(path, f"vocab__{f.replace('/', '_')}.txt"), "w") as fh:
                fh.write("\n".join(pb.vocab))
        for f, col in self.numeric_cols.items():
            if f in derived:
                continue
            arrays[f"num__{f}__values"] = col.values
            arrays[f"num__{f}__present"] = col.present
            meta["numeric"][f] = {"kind": col.kind}
        for f, col in self.keyword_cols.items():
            if f in derived:
                continue
            arrays[f"kw__{f}__starts"] = col.starts
            arrays[f"kw__{f}__ords"] = col.ords
            arrays[f"kw__{f}__docs"] = col.doc_of_value
            arrays[f"kw__{f}__min_ord"] = col.min_ord
            meta["keyword"][f] = {"vocab_file": True}
            with open(os.path.join(path, f"kwvocab__{f.replace('/', '_')}.txt"), "w") as fh:
                fh.write("\n".join(col.vocab))
        for f, col in self.geo_cols.items():
            arrays[f"geo__{f}__lat"] = col.lat
            arrays[f"geo__{f}__lon"] = col.lon
            arrays[f"geo__{f}__present"] = col.present
        for f, col in self.vector_cols.items():
            arrays[f"vec__{f}__values"] = col.values
            arrays[f"vec__{f}__present"] = col.present
            meta["vector"] = meta.get("vector", {})
            meta["vector"][f] = {"similarity": col.similarity,
                                 "method": col.method}
        for f, dl in self.doc_lens.items():
            arrays[f"dl__{f}"] = dl
        meta["shape"] = sorted(self.shape_cols)
        for f, col in self.shape_cols.items():
            arrays[f"shape__{f}__bbox"] = np.stack(
                [col.minx, col.miny, col.maxx, col.maxy])
            arrays[f"shape__{f}__present"] = col.present
            with open(os.path.join(path,
                                   f"shapes__{f.replace('/', '_')}.json"),
                      "w") as fh:
                json.dump(col.specs, fh)
        meta["nested"] = sorted(self.nested)
        for npath, blk in self.nested.items():
            sub = os.path.join(path, f"nested__{npath.replace('/', '_')}")
            blk.child.save(sub)
            arrays[f"nested__{npath}__parent"] = blk.parent_of
        np.savez_compressed(os.path.join(path, "arrays.npz"), **arrays)
        with open(os.path.join(path, "meta.json"), "w") as fh:
            json.dump(meta, fh)
        with open(os.path.join(path, "stored.jsonl"), "w") as fh:
            for i, src in enumerate(self.sources):
                rec = {"_id": self.ids[i], "_source": src}
                if self.stored_vals and self.stored_vals[i]:
                    rec["_stored"] = self.stored_vals[i]
                fh.write(json.dumps(rec) + "\n")
        if self.term_vectors:
            with open(os.path.join(path, "term_vectors.json"), "w") as fh:
                json.dump({f: col for f, col in self.term_vectors.items()},
                          fh)

    @classmethod
    def load(cls, path: str) -> "Segment":
        with open(os.path.join(path, "meta.json")) as fh:
            meta = json.load(fh)
        arrays = np.load(os.path.join(path, "arrays.npz"), allow_pickle=False)
        ids, sources, stored_vals = [], [], []
        any_stored = False
        with open(os.path.join(path, "stored.jsonl")) as fh:
            for line in fh:
                rec = json.loads(line)
                ids.append(rec["_id"])
                sources.append(rec["_source"])
                sv = rec.get("_stored")
                any_stored = any_stored or bool(sv)
                stored_vals.append(sv)
        postings = {}
        for f, pmeta in meta["postings"].items():
            with open(os.path.join(path, f"vocab__{f.replace('/', '_')}.txt")) as fh:
                content = fh.read()
                vocab = content.split("\n") if content else []
            key = f"post__{f}"
            postings[f] = PostingsBlock(
                field=f, vocab=vocab, terms={t: i for i, t in enumerate(vocab)},
                starts=arrays[f"{key}__starts"], doc_ids=arrays[f"{key}__doc_ids"],
                tfs=arrays[f"{key}__tfs"],
                pos_starts=arrays.get(f"{key}__pos_starts"),
                positions=arrays.get(f"{key}__positions"))
            im = meta.get("impacts", {}).get(f)
            if im is not None:
                postings[f].impact = ImpactPlane(
                    q=arrays[f"imp__{f}__q"], scale=float(im["scale"]),
                    bits=int(im["bits"]), k1=float(im["k1"]),
                    b=float(im["b"]), avgdl=float(im["avgdl"]),
                    dl_max=int(im["dl_max"]),
                    block_starts=arrays[f"imp__{f}__bstarts"],
                    block_off=arrays[f"imp__{f}__boff"],
                    block_max=arrays[f"imp__{f}__bmax"],
                    kind=str(im.get("kind", "bm25")))
        numeric = {f: NumericColumn(f, m["kind"], arrays[f"num__{f}__values"],
                                    arrays[f"num__{f}__present"])
                   for f, m in meta["numeric"].items()}
        keyword = {}
        for f in meta["keyword"]:
            with open(os.path.join(path, f"kwvocab__{f.replace('/', '_')}.txt")) as fh:
                content = fh.read()
                kvocab = content.split("\n") if content else []
            keyword[f] = KeywordColumn(f, kvocab, arrays[f"kw__{f}__starts"],
                                       arrays[f"kw__{f}__ords"], arrays[f"kw__{f}__docs"],
                                       arrays[f"kw__{f}__min_ord"])
        geo = {f: GeoColumn(f, arrays[f"geo__{f}__lat"], arrays[f"geo__{f}__lon"],
                            arrays[f"geo__{f}__present"])
               for f in meta["geo"]}
        vectors = {f: VectorColumn(f, arrays[f"vec__{f}__values"],
                                   arrays[f"vec__{f}__present"],
                                   m.get("similarity", "cosine"),
                                   method=m.get("method"))
                   for f, m in meta.get("vector", {}).items()}
        doc_lens = {k[len("dl__"):]: arrays[k] for k in arrays.files if k.startswith("dl__")}
        shapes = {}
        for f in meta.get("shape", []):
            with open(os.path.join(path,
                                   f"shapes__{f.replace('/', '_')}.json")) as fh:
                specs = json.load(fh)
            bbox = arrays[f"shape__{f}__bbox"]
            shapes[f] = ShapeColumn(f, specs, bbox[0], bbox[1], bbox[2],
                                    bbox[3], arrays[f"shape__{f}__present"])
        nested = {}
        for npath in meta.get("nested", []):
            sub = os.path.join(path, f"nested__{npath.replace('/', '_')}")
            nested[npath] = NestedBlock(cls.load(sub),
                                        arrays[f"nested__{npath}__parent"])
        seg = cls(meta["name"], meta["ndocs"], postings, numeric, keyword, geo, doc_lens,
                  {f: TextFieldStats(dc, sd) for f, (dc, sd) in meta["text_stats"].items()},
                  ids, sources, seq_nos=arrays["seq_nos"], vector_cols=vectors,
                  nested=nested, shape_cols=shapes,
                  stored_vals=stored_vals if any_stored else None,
                  # pre-rev metas carry no codec entry: those are v1
                  # segments and keep serving unchanged
                  codec_version=int(meta.get("codec", CODEC_V1)))
        seg.live = arrays["live"].copy()
        if meta.get("reordered"):
            seg.__dict__["_reordered"] = True
            # pin exactly what was saved: a reordered segment persists
            # its plane verbatim (save()), and a no-op-marked segment
            # (pass ran, nothing clustered) has none — reconstructing
            # one from seq_nos here would invent a tie order the
            # pre-restart process never served
            seg.__dict__["_tie_rank"] = (arrays["tie_rank"]
                                         if "tie_rank" in arrays else None)
        seg.id2doc = {d: i for i, d in enumerate(ids) if seg.live[i]}
        tv_path = os.path.join(path, "term_vectors.json")
        if os.path.exists(tv_path):
            with open(tv_path) as fh:
                raw = json.load(fh)
            seg.term_vectors = {
                f: [[tuple(e) for e in col] if col else None
                    for col in cols]
                for f, cols in raw.items()}
        return seg


def _post_field_arrays(pb: "PostingsBlock", jnp, with_tfs: bool = True,
                       with_impacts: bool = True) -> dict:
    """Device arrays of one CSR postings field. Codec v2 fields ship the
    quantized impact plane instead of the f32 tf plane (callers decide
    via `with_tfs`; exact-scoring programs promote tf back lazily through
    Segment.ensure_device_tfs) — the resident postings bytes per slot drop
    from 8 (doc+tf) to 5/6 (doc+u8/u16 impact)."""
    ppad = next_pow2(pb.size)
    rpad = next_pow2(pb.nterms + 2)
    starts = _pad_to(pb.starts.astype(np.int32), rpad, np.int32(pb.size))
    out = {
        "starts": jnp.asarray(starts),
        "doc_ids": jnp.asarray(_pad_to(pb.doc_ids.astype(np.int32), ppad, INT32_SENTINEL)),
    }
    if with_tfs or pb.impact is None:
        out["tfs"] = jnp.asarray(
            _pad_to(pb.tfs.astype(np.float32), ppad, np.float32(0)))
    if with_impacts and pb.impact is not None:
        out["impacts"] = jnp.asarray(
            _pad_to(pb.impact.q, ppad, pb.impact.q.dtype.type(0)))
    return out


def position_slots(n: int) -> int:
    """Slots of a positional plane of `n` positions: the next power of two
    (segments of like size share their programs), and past 2^24 the next
    multiple of an eighth of it (a plane of 666M positions is 2.7 GB, not
    the 4.3 of 2^30 slots)."""
    pow2 = next_pow2(n, floor=128)  # whole rows of `ops.positions.ROW`
    if pow2 <= 1 << 24:
        return pow2
    step = pow2 >> 3
    return -(-int(n) // step) * step


def _position_planes(pb: "PostingsBlock", jnp) -> dict:
    """Device planes of one field's positions, in postings order: `pos` the
    positions as the host holds them, `doc` the document of each (the
    posting's doc id, once a position). The padding's doc is the sentinel,
    past every document. Beside each plane its fence levels, made on the
    device from the plane (`ops.positions.fences`: `doc_f1` every 128th
    slot of `doc`, `doc_f2` every 16,384th ...; flat keys, every value an
    array): what a probe of the phrase join's search reads a row of."""
    from ..ops.positions import fences, plane_key
    n, slots = len(pb.positions), position_slots(len(pb.positions))
    doc = np.empty(slots, np.int32)
    doc[n:] = INT32_SENTINEL
    step = 1 << 24              # postings a pass: no temporary a plane long
    for lo in range(0, pb.size, step):
        at = pb.pos_starts[lo: min(lo + step, pb.size) + 1]
        doc[at[0]: at[-1]] = np.repeat(
            pb.doc_ids[lo: lo + len(at) - 1].astype(np.int32, copy=False),
            np.diff(at))
    out = {"doc": jnp.asarray(doc)}
    del doc
    out["pos"] = jnp.asarray(_pad_to(pb.positions.astype(np.int32, copy=False),
                                     slots, np.int32(0)))
    for plane in ("doc", "pos"):
        for k, level in enumerate(fences(out[plane]), 1):
            out[plane_key(plane, k)] = level
    return out


def _num_field_arrays(col: "NumericColumn", dpad: int, jnp) -> dict:
    if col.kind in ("int", "uint"):
        hi, lo = split_i64(col.values)
        # unsigned_long stores biased i64 (order-exact); the f32
        # agg/script view unbiases back to the real magnitude
        f32v = (col.values.astype(np.float64) + float(1 << 63)
                if col.kind == "uint" else col.values).astype(np.float32)
        return {
            "hi": jnp.asarray(_pad_to(hi, dpad, np.int32(0))),
            "lo": jnp.asarray(_pad_to(lo, dpad, np.int32(0))),
            "f32": jnp.asarray(_pad_to(f32v, dpad, np.float32(0))),
            "present": jnp.asarray(_pad_to(col.present, dpad, False)),
        }
    return {
        "f32": jnp.asarray(_pad_to(col.values.astype(np.float32), dpad, np.float32(0))),
        "present": jnp.asarray(_pad_to(col.present, dpad, False)),
    }


def _kw_field_arrays(col: "KeywordColumn", dpad: int, jnp,
                     multi_valued: bool) -> dict:
    """A keyword column on the device. Where no document holds two values
    (`Segment.kw_multi_valued`) it is its ordinals by document and nothing
    else: `min_ord` is then `ords` with a -1 where a document has none, and
    `doc_of_value` the row numbers of the others, so a group-by counts
    `min_ord` under the mask (`ops.aggs.counts_by_value` reads the form off
    this dict's keys, and with them it is part of a program's jit key)."""
    out = {"min_ord": jnp.asarray(_pad_to(col.min_ord, dpad, np.int32(-1)))}
    if multi_valued:
        vpad = next_pow2(len(col.ords))
        out["ords"] = jnp.asarray(_pad_to(col.ords, vpad, np.int32(-1)))
        out["doc_of_value"] = jnp.asarray(
            _pad_to(col.doc_of_value, vpad, INT32_SENTINEL))
    return out


def _geo_field_arrays(col: "GeoColumn", dpad: int, jnp) -> dict:
    return {
        "lat": jnp.asarray(_pad_to(col.lat, dpad, np.float32(0))),
        "lon": jnp.asarray(_pad_to(col.lon, dpad, np.float32(0))),
        "present": jnp.asarray(_pad_to(col.present, dpad, False)),
    }


def _pack_postings_python(parsed_docs: list, with_positions: bool) -> Dict[str, PostingsBlock]:
    """Pure-Python postings pack (dict accumulate -> sort -> CSR). Reference
    semantics: one posting per (term, doc) with tf; positions flattened in
    ascending order per posting."""
    field_term_docs: Dict[str, Dict[str, dict]] = {}
    field_term_pos: Dict[str, Dict[str, dict]] = {}
    for doc_i, pd in enumerate(parsed_docs):
        for fname, terms in pd.terms.items():
            td = field_term_docs.setdefault(fname, {})
            for t in terms:
                postings = td.setdefault(t, {})
                postings[doc_i] = postings.get(doc_i, 0) + 1
        if with_positions:
            for fname, tps in pd.positions.items():
                tp = field_term_pos.setdefault(fname, {})
                for t, p in tps:
                    tp.setdefault(t, {}).setdefault(doc_i, []).append(p)

    postings: Dict[str, PostingsBlock] = {}
    for fname, term_docs in field_term_docs.items():
        vocab = sorted(term_docs)
        terms = {t: i for i, t in enumerate(vocab)}
        lens = np.fromiter((len(term_docs[t]) for t in vocab), dtype=np.int64, count=len(vocab))
        starts = np.zeros(len(vocab) + 1, dtype=np.int64)
        np.cumsum(lens, out=starts[1:])
        total = int(starts[-1])
        doc_ids = np.empty(total, dtype=np.int32)
        tfs = np.empty(total, dtype=np.float32)
        pos_chunks: List[List[int]] = []
        pos_lens = np.zeros(total, dtype=np.int64) if with_positions else None
        k = 0
        tp = field_term_pos.get(fname, {})
        for t in vocab:
            d = term_docs[t]
            for doc_i in sorted(d):
                doc_ids[k] = doc_i
                tfs[k] = d[doc_i]
                if with_positions:
                    plist = tp.get(t, {}).get(doc_i, [])
                    pos_lens[k] = len(plist)
                    pos_chunks.append(plist)
                k += 1
        pos_starts = positions = None
        if with_positions:
            pos_starts = np.zeros(total + 1, dtype=np.int64)
            np.cumsum(pos_lens, out=pos_starts[1:])
            positions = np.fromiter((p for chunk in pos_chunks for p in chunk),
                                    dtype=np.int32, count=int(pos_starts[-1]))
        postings[fname] = PostingsBlock(fname, vocab, terms, starts, doc_ids, tfs,
                                        pos_starts, positions)
    return postings


def pack_postings(parsed_docs: list, with_positions: bool) -> Dict[str, PostingsBlock]:
    """Pack buffered per-doc term lists into CSR PostingsBlocks. Uses the
    native C++ packer (native/opensearch_native.cpp: intern -> sort ->
    CSR scan) when built; falls back to the Python path per-field otherwise
    (bit-identical output — tests/test_native.py asserts parity)."""
    from .. import native

    if not native.available():
        return _pack_postings_python(parsed_docs, with_positions)

    # flatten the token stream per field
    field_tokens: Dict[str, List[str]] = {}
    field_counts: Dict[str, List[Tuple[int, int]]] = {}
    field_pos: Dict[str, List[int]] = {}
    fallback_fields: set = set()
    for doc_i, pd in enumerate(parsed_docs):
        for fname, terms in pd.terms.items():
            bucket = field_tokens.setdefault(fname, [])  # empty lists still
            if not terms:                                # register the field
                continue
            bucket.extend(terms)
            field_counts.setdefault(fname, []).append((doc_i, len(terms)))
            if with_positions:
                pl = pd.positions.get(fname)
                if pl is not None:
                    if len(pl) != len(terms):
                        fallback_fields.add(fname)  # mis-aligned stream
                    field_pos.setdefault(fname, []).extend(p for _, p in pl)

    out: Dict[str, PostingsBlock] = {}
    python_fields: List[str] = []
    for fname, tokens in field_tokens.items():
        joined = "\x00".join(tokens)
        if fname in fallback_fields or (
                tokens and joined.count("\x00") != len(tokens) - 1):
            python_fields.append(fname)  # embedded NUL in a token
            continue
        pairs = field_counts.get(fname, [])
        docs = np.fromiter((d for d, _ in pairs), np.int32, count=len(pairs))
        cnts = np.fromiter((c for _, c in pairs), np.int64, count=len(pairs))
        doc_of = np.repeat(docs, cnts)
        has_pos = with_positions and fname in field_pos
        if has_pos and len(field_pos[fname]) != len(tokens):
            # positions for some docs but not others — mis-aligned stream,
            # take the Python fallback (same as the len(pl) != len(terms) guard)
            python_fields.append(fname)
            continue
        pos_arr = (np.fromiter(field_pos[fname], np.int32, count=len(tokens))
                   if has_pos else None)
        packer = native.Packer(with_positions=has_pos)
        packer.add(joined, len(tokens), doc_of, pos_arr)
        vocab, starts, doc_ids, tfs, pos_starts, positions = packer.finish()
        packer.close()
        if with_positions and not has_pos:
            # fields indexed without positions (keyword/ip) still carry an
            # all-empty positions CSR when the segment is positional — same
            # as the Python path
            pos_starts = np.zeros(len(doc_ids) + 1, dtype=np.int64)
            positions = np.empty(0, dtype=np.int32)
        out[fname] = PostingsBlock(fname, vocab, {t: i for i, t in enumerate(vocab)},
                                   starts, doc_ids, tfs, pos_starts, positions)
    if python_fields:
        sub = [type(pd)(doc_id=pd.doc_id, source=pd.source, routing=pd.routing,
                        terms={f: pd.terms[f] for f in python_fields if f in pd.terms},
                        positions={f: pd.positions[f] for f in python_fields
                                   if f in pd.positions})
               for pd in parsed_docs]
        out.update(_pack_postings_python(sub, with_positions))
    return out


def _numeric_kind(mappings: Mappings, fname: str) -> str:
    """Storage kind of one numeric doc-value column — shared by the
    in-memory and streaming builders so the two paths cannot diverge."""
    ft = mappings.resolve_field(fname)
    if fname.endswith(("#lo", "#hi")) and ft is None:
        # range-field bound columns: member type decides the kind
        from .mappings import RANGE_MEMBER
        rft = mappings.resolve_field(fname[:-3])
        member = RANGE_MEMBER.get(rft.type) if rft is not None else None
        return "float" if member in ("float", "double") else "int"
    if ft is not None and ft.type == "unsigned_long":
        return "uint"        # biased i64: exact order, unbiased f32 view
    return "float" if (ft is not None and ft.type in FLOAT_TYPES) else "int"


def feature_impact_fields(mappings: Mappings, fields) -> List[str]:
    """The subset of feature-postings fields whose mapping opted into
    `index_impacts` (rank_features/sparse_vector only) — the fields that
    get a codec-v2 FEATURE impact plane at build/merge time."""
    out = []
    for f in sorted(fields):
        ft = mappings.resolve_field(f)
        if ft is not None and getattr(ft, "index_impacts", False):
            out.append(f)
    return out


def build_segment(name: str, parsed_docs: list, mappings: Mappings,
                  seq_nos: Optional[List[int]] = None,
                  with_positions: bool = True) -> Segment:
    """Build an immutable segment from buffered parsed docs (the refresh path,
    analog of Lucene DWPT flush driven by reference
    `index/engine/InternalEngine.java#refresh`)."""
    ndocs = len(parsed_docs)
    ids = [d.doc_id for d in parsed_docs]
    sources = ([d.source for d in parsed_docs]
               if getattr(mappings, "source_enabled", True)
               else [{} for _ in parsed_docs])
    stored_vals = ([dict(d.stored) if d.stored else None
                    for d in parsed_docs]
                   if any(d.stored for d in parsed_docs) else None)
    term_vectors = None
    if any(d.offsets for d in parsed_docs):
        term_vectors = {}
        for doc_i, pd in enumerate(parsed_docs):
            for fname, offs in pd.offsets.items():
                col = term_vectors.setdefault(fname, [None] * ndocs)
                col[doc_i] = offs

    # ---- inverted fields ----
    doc_lens: Dict[str, np.ndarray] = {}
    text_stats: Dict[str, TextFieldStats] = {}
    for doc_i, pd in enumerate(parsed_docs):
        for fname, terms in pd.terms.items():
            ft = mappings.resolve_field(fname)
            if ft is not None and ft.type == "text":
                stats = text_stats.setdefault(fname, TextFieldStats())
                stats.doc_count += 1
                stats.sum_dl += len(terms)
                dl = doc_lens.setdefault(fname, np.zeros(ndocs, dtype=np.int64))
                dl[doc_i] = len(terms)

    _t_pack = time.perf_counter()
    postings = pack_postings(parsed_docs, with_positions)
    note_stage("pack", time.perf_counter() - _t_pack)

    # ---- feature postings (rank_features / sparse_vector): CSR rows are
    # features, "tf" carries the feature weight — the device scores them with
    # the same gather->scatter pass as terms (reference mapper-extras encodes
    # weights in the term frequency the same way) ----
    feat_fields = {f for pd in parsed_docs for f in pd.features}
    for fname in sorted(feat_fields):
        feat_docs: Dict[str, List[Tuple[int, float]]] = {}
        for doc_i, pd in enumerate(parsed_docs):
            for feat, w in pd.features.get(fname, {}).items():
                feat_docs.setdefault(feat, []).append((doc_i, w))
        vocab = sorted(feat_docs)
        terms = {t: i for i, t in enumerate(vocab)}
        starts = np.zeros(len(vocab) + 1, dtype=np.int64)
        flat: List[Tuple[int, float]] = []
        for i, t in enumerate(vocab):
            flat.extend(feat_docs[t])
            starts[i + 1] = len(flat)
        doc_ids = np.fromiter((d for d, _ in flat), np.int32, count=len(flat))
        tfs = np.fromiter((w for _, w in flat), np.float32, count=len(flat))
        postings[fname] = PostingsBlock(fname, vocab, terms, starts, doc_ids, tfs)

    # ---- doc values ----
    numeric_cols: Dict[str, NumericColumn] = {}
    keyword_cols: Dict[str, KeywordColumn] = {}
    geo_cols: Dict[str, GeoColumn] = {}
    num_fields = {f for pd in parsed_docs for f in pd.numerics}
    kw_fields = {f for pd in parsed_docs for f in pd.keywords}
    geo_fields = {f for pd in parsed_docs for f in pd.geos}
    vec_fields = {f for pd in parsed_docs for f in pd.vectors}

    for fname in num_fields:
        kind = _numeric_kind(mappings, fname)
        dtype = np.float64 if kind == "float" else np.int64
        values = np.zeros(ndocs, dtype=dtype)
        present = np.zeros(ndocs, dtype=bool)
        for doc_i, pd in enumerate(parsed_docs):
            vals = pd.numerics.get(fname)
            if vals:
                values[doc_i] = vals[0]
                present[doc_i] = True
        numeric_cols[fname] = NumericColumn(fname, kind, values, present)

    for fname in kw_fields:
        value_set = set()
        for pd in parsed_docs:
            value_set.update(pd.keywords.get(fname, ()))
        vocab = sorted(value_set)
        ord_of = {v: i for i, v in enumerate(vocab)}
        starts = np.zeros(ndocs + 1, dtype=np.int64)
        flat_ords: List[int] = []
        flat_docs: List[int] = []
        min_ord = np.full(ndocs, -1, dtype=np.int32)
        for doc_i, pd in enumerate(parsed_docs):
            vals = pd.keywords.get(fname, ())
            ords = sorted(ord_of[v] for v in set(vals))
            for o in ords:
                flat_ords.append(o)
                flat_docs.append(doc_i)
            if ords:
                min_ord[doc_i] = ords[0]
            starts[doc_i + 1] = len(flat_ords)
        keyword_cols[fname] = KeywordColumn(
            fname, vocab, starts, np.asarray(flat_ords, dtype=np.int32),
            np.asarray(flat_docs, dtype=np.int32), min_ord)

    for fname in geo_fields:
        lat = np.zeros(ndocs, dtype=np.float32)
        lon = np.zeros(ndocs, dtype=np.float32)
        present = np.zeros(ndocs, dtype=bool)
        for doc_i, pd in enumerate(parsed_docs):
            vals = pd.geos.get(fname)
            if vals:
                lat[doc_i], lon[doc_i] = vals[0]
                present[doc_i] = True
        geo_cols[fname] = GeoColumn(fname, lat, lon, present)

    vector_cols: Dict[str, VectorColumn] = {}
    for fname in vec_fields:
        ft = mappings.resolve_field(fname)
        dims = next(len(pd.vectors[fname]) for pd in parsed_docs
                    if fname in pd.vectors)
        values = np.zeros((ndocs, dims), np.float32)
        present = np.zeros(ndocs, bool)
        for doc_i, pd in enumerate(parsed_docs):
            vec = pd.vectors.get(fname)
            if vec is not None:
                values[doc_i] = vec
                present[doc_i] = True
        vector_cols[fname] = VectorColumn(
            fname, values, present,
            ft.vector_similarity if ft is not None else "cosine",
            method=ft.vector_method if ft is not None else None)

    shape_cols: Dict[str, ShapeColumn] = {}
    shape_fields = {f for pd in parsed_docs for f in pd.shapes}
    for fname in shape_fields:
        specs: list = [None] * ndocs
        minx = np.full(ndocs, np.inf)
        miny = np.full(ndocs, np.inf)
        maxx = np.full(ndocs, -np.inf)
        maxy = np.full(ndocs, -np.inf)
        present = np.zeros(ndocs, bool)
        for doc_i, pd in enumerate(parsed_docs):
            vals = pd.shapes.get(fname)  # [(spec, bbox)] from mapping parse
            if not vals:
                continue
            specs[doc_i] = [sp for sp, _bx in vals]
            present[doc_i] = True
            for _sp, bx in vals:
                minx[doc_i] = min(minx[doc_i], bx[0])
                miny[doc_i] = min(miny[doc_i], bx[1])
                maxx[doc_i] = max(maxx[doc_i], bx[2])
                maxy[doc_i] = max(maxy[doc_i], bx[3])
        shape_cols[fname] = ShapeColumn(fname, specs, minx, miny, maxx, maxy,
                                        present)

    # ---- nested blocks: child docs become their own CSR segment ----
    nested_paths = {p for pd in parsed_docs for p in pd.nested}
    nested: Dict[str, NestedBlock] = {}
    for npath in sorted(nested_paths):
        child_docs: List[Any] = []
        parent_of: List[int] = []
        for doc_i, pd in enumerate(parsed_docs):
            for child in pd.nested.get(npath, ()):
                child_docs.append(child)
                parent_of.append(doc_i)
        child_seg = build_segment(f"{name}/{npath}", child_docs, mappings,
                                  with_positions=with_positions)
        nested[npath] = NestedBlock(child_seg,
                                    np.asarray(parent_of, dtype=np.int32))

    seq = np.asarray(seq_nos, dtype=np.int64) if seq_nos is not None else None
    seg = Segment(name, ndocs, postings, numeric_cols, keyword_cols, geo_cols,
                  doc_lens, text_stats, ids, sources, seq_nos=seq,
                  vector_cols=vector_cols, nested=nested,
                  shape_cols=shape_cols, stored_vals=stored_vals)
    # codec v2: eager quantized impacts + block-max sidecar per
    # text-scored field (nested children recurse in build_impacts),
    # plus FEATURE planes for rank_features/sparse_vector fields
    # whose mapping opted into index_impacts (learned-sparse on the
    # impact ladder, docs/HYBRID.md)
    _t_q = time.perf_counter()
    seg.build_impacts(feature_fields=feature_impact_fields(
        mappings, feat_fields))
    note_stage("quantize", time.perf_counter() - _t_q)
    # term_vector=with_positions_offsets fields: per-doc (term, pos, start,
    # end) for the FVH path (host-only, like _source)
    seg.term_vectors = term_vectors
    return seg


# ---------------------------------------------------------------------
# streaming segment build (chunked posting accumulation, spill-and-merge)
# ---------------------------------------------------------------------
#
# The in-memory build (`build_segment` -> `pack_postings`) flattens the
# WHOLE doc buffer's token stream into Python lists before packing: at
# north-star scale (1M-8.8M docs, ~56 tokens/doc) that is hundreds of
# millions of Python string references — tens of GB of transient host
# memory for a segment whose final CSR arrays are ~1 GB. The streaming
# builder bounds the transient: docs are packed in fixed-size CHUNKS
# (each chunk through the same `pack_postings` native/python packer),
# every chunk's CSR + doc-value planes SPILL to disk, and `finish()`
# merges the sorted chunk runs into the final arrays with a vectorized
# run-scatter — no global sort, because chunk doc ranges are disjoint
# and ascending, so per-term concatenation in chunk order IS (term, doc)
# order. Peak host memory ~= final arrays + one chunk.
#
# Output is BIT-IDENTICAL to `build_segment` on the same docs
# (tests/test_stream_build.py pins it array-for-array): same vocab
# union, same CSR layout, same tf/position values, same doc-value
# columns, same text stats — and therefore the same codec-v2 impact
# planes, since those derive from (tf, dl, avgdl) alone.
#
# Scope: the streaming-eligible families are text/keyword-ish postings,
# numeric / keyword / geo / vector doc values and doc lengths — the
# north-star corpus shape. Docs carrying nested blocks, geo shapes,
# term-vector offsets or rank-features raise: those buffers are
# host-object-heavy either way, and the refresh path routes them to the
# in-memory build (`Engine.refresh` checks eligibility first).


def stream_eligible(parsed_docs) -> bool:
    """True when every doc uses only streaming-supported field families."""
    return not any(pd.nested or pd.shapes or pd.offsets or pd.features
                   for pd in parsed_docs if pd is not None)


class StreamingSegmentBuilder:
    """Bounded-memory segment construction: `add()` docs, `finish()` the
    Segment. One chunk of parsed docs is resident at a time; chunk CSRs
    spill to `spill_dir` (a private temp dir by default)."""

    def __init__(self, name: str, mappings: Mappings,
                 chunk_docs: int = 8192, spill_dir: Optional[str] = None,
                 with_positions: bool = True):
        import tempfile
        self.name = name
        self.mappings = mappings
        self.chunk_docs = max(int(chunk_docs), 1)
        self.with_positions = with_positions
        self._own_dir = spill_dir is None
        self._dir = spill_dir or tempfile.mkdtemp(prefix="ostpu_stream_")
        os.makedirs(self._dir, exist_ok=True)
        self._chunk: list = []
        self._chunks: list = []      # per-chunk meta dicts
        self._ndocs = 0
        self.ids: List[str] = []
        self.sources: List[dict] = []
        self._stored: list = []
        self._any_stored = False
        self._text_stats: Dict[str, TextFieldStats] = {}
        self._vec_sim: Dict[str, tuple] = {}
        self._npz_cache: Dict[int, Any] = {}
        self._finished = False

    # ---------------- ingest ----------------

    def add(self, parsed) -> None:
        if parsed.nested or parsed.shapes or parsed.offsets \
                or parsed.features:
            raise ValueError(
                "streaming build supports text/numeric/keyword/geo/vector "
                "families only; nested/shape/term_vector/feature docs take "
                "the in-memory build (see Engine.refresh eligibility gate)")
        self._chunk.append(parsed)
        if len(self._chunk) >= self.chunk_docs:
            self._flush_chunk()

    def add_many(self, parsed_iter) -> None:
        for pd in parsed_iter:
            self.add(pd)

    @property
    def ndocs(self) -> int:
        return self._ndocs + len(self._chunk)

    def _flush_chunk(self) -> None:
        docs = self._chunk
        self._chunk = []
        if not docs:
            return
        _t_spill = time.perf_counter()
        base = self._ndocs
        n = len(docs)
        self._ndocs += n
        arrays: Dict[str, np.ndarray] = {}
        meta = {"base": base, "n": n, "post": {}, "num": {}, "kw": {},
                "geo": [], "vec": {}, "dl": []}

        src_on = getattr(self.mappings, "source_enabled", True)
        for pd in docs:
            self.ids.append(pd.doc_id)
            self.sources.append(pd.source if src_on else {})
            sv = dict(pd.stored) if pd.stored else None
            self._any_stored = self._any_stored or bool(sv)
            self._stored.append(sv)

        # ---- text stats + per-chunk doc lengths (mirrors build_segment) --
        dl_f: Dict[str, np.ndarray] = {}
        for di, pd in enumerate(docs):
            for fname, terms in pd.terms.items():
                ft = self.mappings.resolve_field(fname)
                if ft is not None and ft.type == "text":
                    st = self._text_stats.setdefault(fname,
                                                     TextFieldStats())
                    st.doc_count += 1
                    st.sum_dl += len(terms)
                    dl = dl_f.setdefault(fname, np.zeros(n, np.int64))
                    dl[di] = len(terms)
        for fname, dl in dl_f.items():
            arrays[f"dl__{len(meta['dl'])}"] = dl
            meta["dl"].append(fname)

        # ---- postings: one packer run per chunk ----
        for fi, (fname, pb) in enumerate(
                sorted(pack_postings(docs, self.with_positions).items())):
            key = f"post__{fi}"
            arrays[f"{key}__starts"] = pb.starts
            arrays[f"{key}__doc_ids"] = pb.doc_ids
            arrays[f"{key}__tfs"] = pb.tfs
            positional = pb.pos_starts is not None
            if positional:
                arrays[f"{key}__pos_starts"] = pb.pos_starts
                arrays[f"{key}__positions"] = pb.positions
            meta["post"][fname] = {"i": fi, "vocab": pb.vocab,
                                   "positional": positional}

        # ---- doc values ----
        num_fields = {f for pd in docs for f in pd.numerics}
        for fi, fname in enumerate(sorted(num_fields)):
            kind = _numeric_kind(self.mappings, fname)
            dtype = np.float64 if kind == "float" else np.int64
            values = np.zeros(n, dtype=dtype)
            present = np.zeros(n, dtype=bool)
            for di, pd in enumerate(docs):
                vals = pd.numerics.get(fname)
                if vals:
                    values[di] = vals[0]
                    present[di] = True
            arrays[f"num__{fi}__values"] = values
            arrays[f"num__{fi}__present"] = present
            meta["num"][fname] = {"i": fi, "kind": kind}

        kw_fields = {f for pd in docs for f in pd.keywords}
        for fi, fname in enumerate(sorted(kw_fields)):
            value_set = set()
            for pd in docs:
                value_set.update(pd.keywords.get(fname, ()))
            vocab = sorted(value_set)
            ord_of = {v: i for i, v in enumerate(vocab)}
            starts = np.zeros(n + 1, dtype=np.int64)
            flat_ords: List[int] = []
            flat_docs: List[int] = []
            min_ord = np.full(n, -1, dtype=np.int32)
            for di, pd in enumerate(docs):
                vals = pd.keywords.get(fname, ())
                ords = sorted(ord_of[v] for v in set(vals))
                for o in ords:
                    flat_ords.append(o)
                    flat_docs.append(di)
                if ords:
                    min_ord[di] = ords[0]
                starts[di + 1] = len(flat_ords)
            arrays[f"kw__{fi}__starts"] = starts
            arrays[f"kw__{fi}__ords"] = np.asarray(flat_ords, np.int32)
            arrays[f"kw__{fi}__docs"] = np.asarray(flat_docs, np.int32)
            arrays[f"kw__{fi}__min_ord"] = min_ord
            meta["kw"][fname] = {"i": fi, "vocab": vocab}

        geo_fields = {f for pd in docs for f in pd.geos}
        for fi, fname in enumerate(sorted(geo_fields)):
            lat = np.zeros(n, dtype=np.float32)
            lon = np.zeros(n, dtype=np.float32)
            present = np.zeros(n, dtype=bool)
            for di, pd in enumerate(docs):
                vals = pd.geos.get(fname)
                if vals:
                    lat[di], lon[di] = vals[0]
                    present[di] = True
            arrays[f"geo__{fi}__lat"] = lat
            arrays[f"geo__{fi}__lon"] = lon
            arrays[f"geo__{fi}__present"] = present
            meta["geo"].append(fname)

        vec_fields = {f for pd in docs for f in pd.vectors}
        for fi, fname in enumerate(sorted(vec_fields)):
            ft = self.mappings.resolve_field(fname)
            dims = next(len(pd.vectors[fname]) for pd in docs
                        if fname in pd.vectors)
            self._vec_sim.setdefault(fname, (
                dims,
                ft.vector_similarity if ft is not None else "cosine",
                ft.vector_method if ft is not None else None))
            values = np.zeros((n, dims), np.float32)
            present = np.zeros(n, bool)
            for di, pd in enumerate(docs):
                vec = pd.vectors.get(fname)
                if vec is not None:
                    values[di] = vec
                    present[di] = True
            arrays[f"vec__{fi}__values"] = values
            arrays[f"vec__{fi}__present"] = present
            meta["vec"][fname] = {"i": fi}

        np.savez(os.path.join(self._dir, f"chunk{len(self._chunks)}.npz"),
                 **arrays)
        self._chunks.append(meta)
        note_stage("spill", time.perf_counter() - _t_spill)

    # ---------------- merge ----------------

    # open .npz handles kept during finish(): each holds an OS file
    # descriptor, so cap well under common ulimits (an 8.8M-doc build is
    # ~1075 chunks); merge loops walk chunks in ascending order, so FIFO
    # eviction drops exactly the handles not needed soon
    _NPZ_CACHE_FDS = 64

    def _chunk_arrays(self, ci: int):
        # one open NpzFile per chunk while it is being visited: members
        # load lazily, but every np.load re-parses the zip central
        # directory — the merge loops visit each chunk up to 3x per field
        arrs = self._npz_cache.get(ci)
        if arrs is None:
            while len(self._npz_cache) >= self._NPZ_CACHE_FDS:
                old = next(iter(self._npz_cache))
                try:
                    self._npz_cache.pop(old).close()
                except Exception:
                    pass
            arrs = np.load(os.path.join(self._dir, f"chunk{ci}.npz"),
                           allow_pickle=False)
            self._npz_cache[ci] = arrs
        return arrs

    def _merge_postings_field(self, fname: str) -> PostingsBlock:
        """Spill-and-merge of one field's chunk CSR runs: union vocab,
        then a vectorized run-scatter per chunk. Chunk doc ranges are
        disjoint ascending, so filling runs in chunk order lands every
        row in (doc ascending) order — identical to the global pack."""
        from .merge import _ranges_gather

        chunks = [(ci, m["post"][fname]) for ci, m in
                  enumerate(self._chunks) if fname in m["post"]]
        vocab = sorted({t for _ci, pm in chunks for t in pm["vocab"]})
        new_row_of = {t: i for i, t in enumerate(vocab)}
        nterms = len(vocab)
        positional = self.with_positions
        lens_u = np.zeros(nterms, np.int64)
        row_maps = {}
        for ci, pm in chunks:
            rm = np.fromiter((new_row_of[t] for t in pm["vocab"]),
                             np.int64, count=len(pm["vocab"]))
            row_maps[ci] = rm
            arrs = self._chunk_arrays(ci)
            clens = np.diff(arrs[f"post__{pm['i']}__starts"])
            np.add.at(lens_u, rm, clens)
        starts = np.zeros(nterms + 1, np.int64)
        np.cumsum(lens_u, out=starts[1:])
        total = int(starts[-1])
        doc_ids = np.empty(total, np.int32)
        tfs = np.empty(total, np.float32)
        plens = np.zeros(total, np.int64) if positional else None
        filled = np.zeros(nterms, np.int64)
        dsts = {}
        for ci, pm in chunks:
            arrs = self._chunk_arrays(ci)
            key = f"post__{pm['i']}"
            cstarts = arrs[f"{key}__starts"]
            clens = np.diff(cstarts)
            rm = row_maps[ci]
            run_dst = starts[rm] + filled[rm]
            pc = int(cstarts[-1])
            dst = (np.repeat(run_dst, clens)
                   + np.arange(pc, dtype=np.int64)
                   - np.repeat(cstarts[:-1], clens))
            base = self._chunks[ci]["base"]
            doc_ids[dst] = arrs[f"{key}__doc_ids"] + np.int32(base)
            tfs[dst] = arrs[f"{key}__tfs"]
            if positional:
                plens[dst] = np.diff(arrs[f"{key}__pos_starts"])
            filled[rm] += clens
            dsts[ci] = dst
        pos_starts = positions = None
        if positional:
            pos_starts = np.zeros(total + 1, np.int64)
            np.cumsum(plens, out=pos_starts[1:])
            positions = np.empty(int(pos_starts[-1]), np.int32)
            for ci, pm in chunks:
                arrs = self._chunk_arrays(ci)
                key = f"post__{pm['i']}"
                dst = dsts[ci]
                cplens = np.diff(arrs[f"{key}__pos_starts"])
                idx = _ranges_gather(pos_starts[:-1][dst], cplens)
                positions[idx] = arrs[f"{key}__positions"]
        return PostingsBlock(fname, vocab, new_row_of, starts, doc_ids,
                             tfs, pos_starts, positions)

    def finish(self, seq_nos: Optional[List[int]] = None) -> Segment:
        assert not self._finished
        self._finished = True
        self._flush_chunk()
        _t_merge = time.perf_counter()
        ndocs = self._ndocs
        try:
            post_fields = sorted({f for m in self._chunks
                                  for f in m["post"]})
            postings = {f: self._merge_postings_field(f)
                        for f in post_fields}

            numeric_cols: Dict[str, NumericColumn] = {}
            for f in sorted({f for m in self._chunks for f in m["num"]}):
                kind = next(m["num"][f]["kind"] for m in self._chunks
                            if f in m["num"])
                dtype = np.float64 if kind == "float" else np.int64
                values = np.zeros(ndocs, dtype=dtype)
                present = np.zeros(ndocs, dtype=bool)
                for ci, m in enumerate(self._chunks):
                    nm = m["num"].get(f)
                    if nm is None:
                        continue
                    arrs = self._chunk_arrays(ci)
                    sl = slice(m["base"], m["base"] + m["n"])
                    values[sl] = arrs[f"num__{nm['i']}__values"]
                    present[sl] = arrs[f"num__{nm['i']}__present"]
                numeric_cols[f] = NumericColumn(f, kind, values, present)

            keyword_cols: Dict[str, KeywordColumn] = {}
            for f in sorted({f for m in self._chunks for f in m["kw"]}):
                vocab = sorted({v for m in self._chunks
                                if f in m["kw"]
                                for v in m["kw"][f]["vocab"]})
                ord_of = {v: i for i, v in enumerate(vocab)}
                starts = np.zeros(ndocs + 1, np.int64)
                ord_parts, doc_parts = [], []
                min_ord = np.full(ndocs, -1, np.int32)
                counts = np.zeros(ndocs, np.int64)
                for ci, m in enumerate(self._chunks):
                    km = m["kw"].get(f)
                    if km is None:
                        continue
                    arrs = self._chunk_arrays(ci)
                    remap = np.fromiter(
                        (ord_of[v] for v in km["vocab"]), np.int64,
                        count=len(km["vocab"]))
                    cords = arrs[f"kw__{km['i']}__ords"]
                    cdocs = arrs[f"kw__{km['i']}__docs"]
                    cstarts = arrs[f"kw__{km['i']}__starts"]
                    cmin = arrs[f"kw__{km['i']}__min_ord"]
                    base = m["base"]
                    # monotone remap keeps per-doc ord order + min identity
                    ord_parts.append(remap[cords].astype(np.int32)
                                     if len(cords) else
                                     np.empty(0, np.int32))
                    doc_parts.append((cdocs + np.int32(base)))
                    counts[base: base + m["n"]] = np.diff(cstarts)
                    sl = min_ord[base: base + m["n"]]
                    sel = cmin >= 0
                    sl[sel] = remap[cmin[sel]].astype(np.int32)
                np.cumsum(counts, out=starts[1:])
                ords = (np.concatenate(ord_parts) if ord_parts
                        else np.empty(0, np.int32))
                docs_flat = (np.concatenate(doc_parts) if doc_parts
                             else np.empty(0, np.int32))
                keyword_cols[f] = KeywordColumn(f, vocab, starts,
                                                ords.astype(np.int32),
                                                docs_flat.astype(np.int32),
                                                min_ord)

            geo_cols: Dict[str, GeoColumn] = {}
            for f in sorted({f for m in self._chunks for f in m["geo"]}):
                lat = np.zeros(ndocs, np.float32)
                lon = np.zeros(ndocs, np.float32)
                present = np.zeros(ndocs, bool)
                for ci, m in enumerate(self._chunks):
                    if f not in m["geo"]:
                        continue
                    fi = m["geo"].index(f)
                    arrs = self._chunk_arrays(ci)
                    sl = slice(m["base"], m["base"] + m["n"])
                    lat[sl] = arrs[f"geo__{fi}__lat"]
                    lon[sl] = arrs[f"geo__{fi}__lon"]
                    present[sl] = arrs[f"geo__{fi}__present"]
                geo_cols[f] = GeoColumn(f, lat, lon, present)

            vector_cols: Dict[str, VectorColumn] = {}
            for f in sorted({f for m in self._chunks for f in m["vec"]}):
                dims, sim, method = self._vec_sim[f]
                values = np.zeros((ndocs, dims), np.float32)
                present = np.zeros(ndocs, bool)
                for ci, m in enumerate(self._chunks):
                    vm = m["vec"].get(f)
                    if vm is None:
                        continue
                    arrs = self._chunk_arrays(ci)
                    sl = slice(m["base"], m["base"] + m["n"])
                    values[sl] = arrs[f"vec__{vm['i']}__values"]
                    present[sl] = arrs[f"vec__{vm['i']}__present"]
                vector_cols[f] = VectorColumn(f, values, present, sim,
                                              method=method)

            doc_lens: Dict[str, np.ndarray] = {}
            for f in sorted({f for m in self._chunks for f in m["dl"]}):
                dl = np.zeros(ndocs, np.int64)
                for ci, m in enumerate(self._chunks):
                    if f not in m["dl"]:
                        continue
                    arrs = self._chunk_arrays(ci)
                    fi = m["dl"].index(f)
                    dl[m["base"]: m["base"] + m["n"]] = arrs[f"dl__{fi}"]
                doc_lens[f] = dl

            seq = (np.asarray(seq_nos, dtype=np.int64)
                   if seq_nos is not None else None)
            seg = Segment(self.name, ndocs, postings, numeric_cols,
                          keyword_cols, geo_cols, doc_lens,
                          self._text_stats, self.ids, self.sources,
                          seq_nos=seq, vector_cols=vector_cols,
                          stored_vals=(self._stored if self._any_stored
                                       else None))
            note_stage("chunk_merge", time.perf_counter() - _t_merge)
            # no feature_fields here BY INVARIANT: docs carrying
            # rank_features are not stream-eligible
            # (`stream_eligible` rejects pd.features), so the
            # refresh path routes them to `build_segment`, which
            # derives the index_impacts opt-in from the mappings.
            # If streaming ever learns feature postings, thread
            # `feature_impact_fields(self.mappings, ...)` through
            # here or big-buffer refreshes silently lose the plane
            # (and merges of such segments lose the opt-in forever).
            _t_q = time.perf_counter()
            seg.build_impacts()
            note_stage("quantize", time.perf_counter() - _t_q)
            seg.term_vectors = None
            return seg
        finally:
            self._cleanup()

    def _cleanup(self) -> None:
        import shutil
        for arrs in self._npz_cache.values():
            try:
                arrs.close()
            except Exception:
                pass
        self._npz_cache = {}
        if self._own_dir:
            shutil.rmtree(self._dir, ignore_errors=True)
        else:
            # remove by directory listing, not by self._chunks count: an
            # aborted build (exception in add/_flush_chunk) may have
            # spilled more chunk files than _chunks records, and a
            # persistent engine spill_dir would otherwise retain them
            # forever (each failed refresh can strand a buffer's worth)
            for fn in os.listdir(self._dir):
                if fn.startswith("chunk") and fn.endswith(".npz"):
                    try:
                        os.remove(os.path.join(self._dir, fn))
                    except OSError:
                        pass


def build_segment_streaming(name: str, parsed_docs, mappings: Mappings,
                            seq_nos: Optional[List[int]] = None,
                            chunk_docs: int = 8192,
                            spill_dir: Optional[str] = None,
                            with_positions: bool = True) -> Segment:
    """Streaming counterpart of `build_segment` (same output, bounded
    transient memory): accepts any iterable of parsed docs."""
    b = StreamingSegmentBuilder(name, mappings, chunk_docs=chunk_docs,
                                spill_dir=spill_dir,
                                with_positions=with_positions)
    try:
        b.add_many(parsed_docs)
    except BaseException:
        # finish() cleans up after itself; a failure BEFORE finish must
        # too, or a persistent spill_dir (Engine.refresh) strands every
        # already-spilled chunk of the aborted buffer on disk
        b._cleanup()
        raise
    return b.finish(seq_nos=seq_nos)
