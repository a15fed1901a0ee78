"""BP-style impact-clustered doc-id reordering (codec v2 merge pass).

Block-max pruning (search/impactpath.py, ops/pallas_bm25 impact kernel)
prices every 128-posting block at `w_t · scale · block_max` and skips the
cheap ones. On a corpus indexed in arrival order the per-block maxima are
near-uniform — every block of a queried term contains SOME high-impact
posting — so only skewed/single-term query shapes ever skip (equal-idf
multi-term mixes skip next to nothing on the synthetic corpus of
docs/BENCH_CORPUS.md).
Reordering doc ids so documents with similar high-impact terms are
ADJACENT concentrates each term's impact mass into few blocks, which is
the classic block-max force multiplier (recursive graph bisection /
"BP", Dhulipala et al. KDD'16; BM25S eager impacts, arxiv 2407.03618;
GPUSparse block metadata, arxiv 2606.26441).

The pass runs at merge time (index/merge.py), AFTER the merged impact
planes are built, and has three stages:

1. **Signature construction.** One field carries the clustering signal:
   the largest codec-v2 text field. Terms are filtered to the
   informative band (df >= REORDER_MIN_DF, df <= ndocs/2 — ubiquitous
   terms discriminate nothing and cost the most) and capped by
   cumulative postings (REORDER_MAX_POSTINGS × ndocs) / term count
   (REORDER_MAX_TERMS), richest-df first. Each doc's signature is its
   sparse (term -> dequantized impact) vector over that band — the
   *impact* weighting is what distinguishes this from plain BP: two docs
   sharing a term at high impact pull together harder than two sharing
   it at tf=1 in a long doc.
2. **Recursive bisection.** Each node splits its doc range in half and
   runs swap passes: per term, the weighted log-gap cost delta of moving
   one posting across the cut; per doc, the impact-weighted sum over its
   signature; the two half-orders pair off best-gain-first and swap
   while the pair gain is positive. Stable sorts + arrival-order
   tie-breaks keep the whole pass DETERMINISTIC — replicas re-running
   the same merge produce byte-identical segments (the PR-9 replication
   contract). Cost: O(levels · passes · P_sig) with
   levels = log2(ndocs/leaf); the defaults bound P_sig by 8·ndocs so the
   pass is ~linear in the corpus and strictly merge-time (never on the
   query path).
3. **Permutation application.** `apply_permutation` rebuilds the segment
   wholesale: postings doc ids are remapped and re-sorted per row (the
   O(P log P) sort rides ops/device_merge.merge_sorted_runs past the
   device threshold — the same two-key lax.sort the merge itself uses),
   positions regathered, quantized impact planes PERMUTED (the (tf, dl)
   multiset per term is invariant, so q and scale carry over; only the
   block-max sidecar is recomputed over the new layout), doc-value
   columns / stored fields / _ids / seq_nos / nested blocks remapped.
   Query-time scoring is doc-id-agnostic, so the host oracle and every
   serving tier see the same pages (tests/test_reorder.py pins parity
   across refresh and replica failover).

Skipped when: the segment is below REORDER_MIN_DOCS (block pruning can't
win anything under a few hundred blocks), no codec-v2 impact plane
exists (v1 segments), the signature band is empty, or
OPENSEARCH_TPU_REORDER=0 pins the pass off (rollback / ablation knob:
tests/test_reorder.py runs both arms through it).
"""

from __future__ import annotations

import os
from typing import List, Optional, Tuple

import numpy as np

from ..utils.metrics import METRICS
from .segment import (CODEC_V1, CODEC_V2, IMPACT_BLOCK, KeywordColumn,
                      NestedBlock, NumericColumn, PostingsBlock, Segment)

# signature band + cost knobs (docs/CODEC.md documents the model)
REORDER_MIN_DOCS = 1 << 15     # below this, dense scoring is already cheap
REORDER_MIN_DF = 4             # rarer terms: no block to cluster
REORDER_MAX_DENSITY = 8        # terms on > ndocs/8 docs carry no signal:
#                                they appear in most blocks whatever the
#                                order, and would eat the posting budget
#                                that buys mid-df concentration
REORDER_MAX_TERMS = 8192       # signature width cap
REORDER_MAX_POSTINGS = 12      # x ndocs: signature posting-mass cap
REORDER_LEAF = IMPACT_BLOCK    # stop splitting at one block of docs
REORDER_PASSES = 6             # swap passes per bisection node
REORDER_MAX_DEPTH = 20         # hard recursion bound (2^20 leaves)
_GAIN_TOL = 1e-9               # zero-gain swaps would oscillate forever


def enabled() -> bool:
    return os.environ.get("OPENSEARCH_TPU_REORDER", "1") != "0"


def min_docs() -> int:
    return int(os.environ.get("OPENSEARCH_TPU_REORDER_MIN_DOCS",
                              REORDER_MIN_DOCS))


def _pick_field(seg: Segment) -> Optional[str]:
    """The clustering signal field: the largest codec-v2 text plane."""
    best = None
    best_size = 0
    for f, pb in seg.postings.items():
        if pb.impact is None or f not in seg.doc_lens:
            continue
        if pb.size > best_size:
            best, best_size = f, pb.size
    return best


def _signature(seg: Segment, field: str
               ) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Doc-major sparse impact signatures over the informative term band.

    -> (dstarts i64[ndocs+1], feat i32[Psig], w f32[Psig]) with postings
    doc-contiguous, or None when the band is empty."""
    from ..ops.scoring import dequant_impact_np

    pb = seg.postings[field]
    plane = pb.impact
    lens = np.diff(pb.starts)
    band = np.nonzero((lens >= REORDER_MIN_DF)
                      & (lens <= max(seg.ndocs // REORDER_MAX_DENSITY,
                                     1)))[0]
    if not len(band):
        return None
    # richest-df first under the posting-mass + width caps: high-df terms
    # span the most blocks, so clustering them pays the most skips
    order = band[np.argsort(-lens[band], kind="stable")]
    cum = np.cumsum(lens[order])
    budget = REORDER_MAX_POSTINGS * seg.ndocs
    keep_n = int(np.searchsorted(cum, budget, side="right"))
    keep_n = max(1, min(keep_n, REORDER_MAX_TERMS))
    sel = order[:keep_n]

    docs_l: List[np.ndarray] = []
    feat_l: List[np.ndarray] = []
    w_l: List[np.ndarray] = []
    for fi, r in enumerate(sel):
        a, b = int(pb.starts[r]), int(pb.starts[r + 1])
        docs_l.append(pb.doc_ids[a:b].astype(np.int64))
        feat_l.append(np.full(b - a, fi, np.int32))
        w_l.append(dequant_impact_np(plane.q[a:b], plane.scale))
    docs = np.concatenate(docs_l)
    feat = np.concatenate(feat_l)
    w = np.concatenate(w_l).astype(np.float32)
    # doc-major: stable sort keeps each doc's features df-descending,
    # a deterministic but irrelevant inner order
    o = np.argsort(docs, kind="stable")
    docs, feat, w = docs[o], feat[o], w[o]
    dstarts = np.zeros(seg.ndocs + 1, np.int64)
    np.cumsum(np.bincount(docs, minlength=seg.ndocs), out=dstarts[1:])
    return dstarts, feat, w


def _ranges_gather(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    from .merge import _ranges_gather as rg
    return rg(starts, lens)


def _gap_cost(deg: np.ndarray, n: int) -> np.ndarray:
    """Weighted log-gap cost of one side: deg · log2((n+1)/(deg+1)) — the
    BP objective with impact mass standing in for posting counts."""
    return deg * np.log2((n + 1.0) / (deg + 1.0))


def _node_passes(docs: np.ndarray, dstarts: np.ndarray, feat: np.ndarray,
                 w: np.ndarray, nfeat: int, passes: int
                 ) -> Tuple[np.ndarray, int]:
    """Run the swap passes of one bisection node; returns the improved
    doc order (L half then R half) and the FIRST pass's swap count (the
    purity signal: a node whose first pass moves almost nothing is
    already one cluster and bisects no further)."""
    n = len(docs)
    half = n // 2
    L = docs[:half].copy()
    R = docs[half:].copy()
    dlens = np.diff(dstarts)
    first_k = 0
    for it in range(passes):
        idxL = _ranges_gather(dstarts[L], dlens[L])
        idxR = _ranges_gather(dstarts[R], dlens[R])
        fL, wL = feat[idxL], w[idxL]
        fR, wR = feat[idxR], w[idxR]
        degL = np.bincount(fL, weights=wL, minlength=nfeat)
        degR = np.bincount(fR, weights=wR, minlength=nfeat)
        base = _gap_cost(degL, len(L)) + _gap_cost(degR, len(R))
        # unit-move delta (clamped: weighted mass can sit below 1), the
        # standard BP approximation scaled per posting by its impact
        gainT_L = base - (_gap_cost(np.maximum(degL - 1.0, 0.0), len(L))
                          + _gap_cost(degR + 1.0, len(R)))
        gainT_R = base - (_gap_cost(np.maximum(degR - 1.0, 0.0), len(R))
                          + _gap_cost(degL + 1.0, len(L)))
        runL = np.repeat(np.arange(len(L)), dlens[L])
        runR = np.repeat(np.arange(len(R)), dlens[R])
        gL = np.bincount(runL, weights=wL * gainT_L[fL], minlength=len(L))
        gR = np.bincount(runR, weights=wR * gainT_R[fR], minlength=len(R))
        oL = np.argsort(-gL, kind="stable")
        oR = np.argsort(-gR, kind="stable")
        m = min(len(oL), len(oR))
        pair = gL[oL[:m]] + gR[oR[:m]]
        k = int((pair > _GAIN_TOL).sum())
        if it == 0:
            first_k = k
        if k == 0:
            break
        swapL = oL[:k]
        swapR = oR[:k]
        L[swapL], R[swapR] = R[swapR], L[swapL].copy()
    return np.concatenate([L, R]), first_k


def compute_permutation(seg: Segment, field: Optional[str] = None,
                        leaf: int = REORDER_LEAF,
                        passes: int = REORDER_PASSES
                        ) -> Optional[np.ndarray]:
    """-> new_order i64[ndocs] (new doc id -> old doc id), or None when
    the segment is ineligible (no v2 plane / empty signature band)."""
    if field is None:
        field = _pick_field(seg)
    if field is None:
        return None
    sig = _signature(seg, field)
    if sig is None:
        return None
    dstarts, feat, w = sig
    nfeat = int(feat.max()) + 1 if len(feat) else 0
    if nfeat == 0:
        return None
    # per-doc mean signature impact — the IMPACT-stratification key.
    # Bisection clusters docs by shared terms (presence); once a node is
    # one cluster the presence objective is flat and further splitting
    # is noise — sorting the converged node by this key instead lays its
    # docs out hot -> cold, so every term's postings inside the cluster
    # carry a monotone impact gradient and the tail BLOCKS (uniformly
    # low block_max) become prunable. This is the "impact-clustered"
    # half of the pass: BP alone concentrates terms into ranges but
    # leaves intra-cluster impacts i.i.d. — measured, that skips
    # nothing, because every block still contains one hot posting.
    cnt = np.diff(dstarts).astype(np.float64)
    dsum = np.zeros(seg.ndocs, np.float64)
    np.add.at(dsum, np.repeat(np.arange(seg.ndocs), np.diff(dstarts)), w)
    doc_key = dsum / np.maximum(cnt, 1.0)
    order = np.arange(seg.ndocs, dtype=np.int64)
    # explicit node stack (depth ~log2(ndocs/leaf)): each entry is a
    # half-open slice of `order` still to bisect
    stack: List[Tuple[int, int, int]] = [(0, seg.ndocs, 0)]
    leaf = max(int(leaf), 2)
    while stack:
        lo, hi, depth = stack.pop()
        n = hi - lo
        if n <= leaf or depth >= REORDER_MAX_DEPTH:
            continue
        node, first_k = _node_passes(order[lo:hi], dstarts, feat, w,
                                     nfeat, passes)
        if depth > 0 and first_k <= max(n // 100, 1):
            # converged (pure cluster): stratify by impact and stop —
            # stable sort on (-key, arrival) keeps determinism
            keys = doc_key[node]
            node = node[np.argsort(-keys, kind="stable")]
            order[lo:hi] = node
            continue
        order[lo:hi] = node
        mid = lo + n // 2
        stack.append((mid, hi, depth + 1))
        stack.append((lo, mid, depth + 1))
    return order


class _PermutedSeq:
    """Lazy permuted view over a list-like (bench segments carry lazy
    _ids/_source sequences a materializing list-comp would defeat)."""

    __slots__ = ("_base", "_order")

    def __init__(self, base, order: np.ndarray):
        self._base = base
        self._order = order

    def __len__(self):
        return len(self._order)

    def __getitem__(self, i):
        return self._base[int(self._order[i])]

    def __iter__(self):
        for i in range(len(self._order)):
            yield self[i]


def _permute_seq(base, order: np.ndarray):
    if base is None:
        return None
    if isinstance(base, list):
        return [base[int(i)] for i in order]
    return _PermutedSeq(base, order)


def _permute_postings(pb: PostingsBlock, old2new: np.ndarray
                      ) -> PostingsBlock:
    """Remap one CSR field and re-sort every row doc-ascending. Past the
    device threshold the (row, doc) two-key sort runs on the TPU
    (ops/device_merge.merge_sorted_runs — the merge pipeline's kernel);
    the host lexsort is the bit-identical fallback."""
    from ..ops import device_merge

    if pb.size == 0:
        return pb
    lens = np.diff(pb.starts)
    rows = np.repeat(np.arange(pb.nterms, dtype=np.int64), lens)
    nd = old2new[pb.doc_ids]
    if device_merge.use_device_merge(pb.size):
        _r, d32, t32, order, _counts = device_merge.merge_sorted_runs(
            rows, nd, pb.tfs, pb.nterms)
        new_docs = d32.astype(np.int32)
        new_tfs = t32.astype(np.float32)
        order = order.astype(np.int64)
    else:
        order = np.lexsort((nd, rows))
        new_docs = nd[order].astype(np.int32)
        new_tfs = pb.tfs[order].astype(np.float32)
    pos_starts = positions = None
    if pb.pos_starts is not None:
        plens = np.diff(pb.pos_starts)[order]
        idx = _ranges_gather(pb.pos_starts[:-1][order], plens)
        positions = pb.positions[idx]
        pos_starts = np.zeros(len(plens) + 1, np.int64)
        np.cumsum(plens, out=pos_starts[1:])
    out = PostingsBlock(pb.field, pb.vocab, pb.terms, pb.starts.copy(),
                        new_docs, new_tfs, pos_starts, positions)
    if pb.impact is not None:
        ip = pb.impact
        # the (tf, dl) multiset per term is permutation-invariant, so the
        # quantized values and the global scale carry over unchanged —
        # only the per-block maxima see the new layout
        q = ip.q[order]
        if len(ip.block_off):
            block_max = np.maximum.reduceat(q, ip.block_off)
        else:
            block_max = np.zeros(0, q.dtype)
        from .segment import ImpactPlane
        out.impact = ImpactPlane(
            q=q, scale=ip.scale, bits=ip.bits, k1=ip.k1, b=ip.b,
            avgdl=ip.avgdl, dl_max=ip.dl_max,
            block_starts=ip.block_starts.copy(),
            block_off=ip.block_off.copy(), block_max=block_max)
    return out


def apply_permutation(seg: Segment, new_order: np.ndarray) -> Segment:
    """Rebuild `seg` with doc ids permuted by `new_order` (new -> old).
    Every per-doc plane — postings, doc values, stored fields, _ids,
    seq_nos, live, nested children — threads through; postings rows stay
    doc-ascending; impact planes are permuted and re-sidecared."""
    ndocs = seg.ndocs
    new_order = np.asarray(new_order, np.int64)
    assert len(new_order) == ndocs
    old2new = np.empty(ndocs, np.int64)
    old2new[new_order] = np.arange(ndocs, dtype=np.int64)

    postings = {f: _permute_postings(pb, old2new)
                for f, pb in seg.postings.items()}
    numeric = {f: NumericColumn(f, col.kind, col.values[new_order],
                                col.present[new_order])
               for f, col in seg.numeric_cols.items()}
    keyword = {}
    for f, col in seg.keyword_cols.items():
        nd = old2new[col.doc_of_value]
        o = np.lexsort((col.ords, nd))
        docs = nd[o].astype(np.int32)
        ords = col.ords[o].astype(np.int32)
        starts = np.zeros(ndocs + 1, np.int64)
        np.cumsum(np.bincount(docs, minlength=ndocs), out=starts[1:])
        keyword[f] = KeywordColumn(f, col.vocab, starts, ords, docs,
                                   col.min_ord[new_order])
    geo = {}
    for f, col in seg.geo_cols.items():
        from .segment import GeoColumn
        geo[f] = GeoColumn(f, col.lat[new_order], col.lon[new_order],
                           col.present[new_order])
    vectors = {}
    for f, col in seg.vector_cols.items():
        from .segment import VectorColumn
        vectors[f] = VectorColumn(f, col.values[new_order],
                                  col.present[new_order], col.similarity,
                                  method=col.method)
    shapes = {}
    for f, col in seg.shape_cols.items():
        from .segment import ShapeColumn
        shapes[f] = ShapeColumn(
            f, [col.specs[int(i)] for i in new_order],
            col.minx[new_order], col.miny[new_order],
            col.maxx[new_order], col.maxy[new_order],
            col.present[new_order])
    doc_lens = {f: dl[new_order] for f, dl in seg.doc_lens.items()}
    nested = {}
    for path, blk in seg.nested.items():
        # children re-sort by NEW parent id so parent_of stays
        # nondecreasing (children_of binary-searches it); the child
        # segment recursively permutes by the same child order
        new_parent = old2new[blk.parent_of]
        corder = np.argsort(new_parent, kind="stable").astype(np.int64)
        child = apply_permutation(blk.child, corder)
        nested[path] = NestedBlock(child,
                                   new_parent[corder].astype(np.int32))

    stored = seg.stored_vals
    # ids/sources attach AFTER construction: Segment.__init__ builds
    # id2doc by iterating the full ids sequence, which would materialize
    # a lazy _PermutedSeq doc-by-doc (1M+ synthesized id strings on the
    # bench corpora this laziness exists for) only to be thrown away below
    out = Segment(seg.name, ndocs, postings, numeric, keyword, geo,
                  doc_lens,
                  {f: st for f, st in seg.text_stats.items()},
                  [], [],
                  seq_nos=seg.seq_nos[new_order],
                  vector_cols=vectors, nested=nested, shape_cols=shapes,
                  stored_vals=_permute_seq(stored, new_order),
                  codec_version=seg.codec_version)
    out.ids = _permute_seq(seg.ids, new_order)
    out.sources = _permute_seq(seg.sources, new_order)
    out.live = seg.live[new_order]
    if isinstance(out.ids, list):
        out.id2doc = {d: i for i, d in enumerate(out.ids)}
    else:
        out.id2doc = {}       # lazy-id corpora (bench) never realtime-get
    tv = getattr(seg, "term_vectors", None)
    if tv:
        out.term_vectors = {f: [col[int(i)] for i in new_order]
                            for f, col in tv.items()}
    derived = seg.__dict__.get("_derived_names")
    if derived:
        out.__dict__["_derived_names"] = set(derived)
    # pin the arrival-rank tie plane explicitly: Segment.tie_ranks infers
    # it from seq_no monotonicity, which degenerates when seq_nos carry
    # no order (direct-CSR corpora default them to zeros — bench
    # make_index) and would silently disable the whole tie-parity
    # machinery on the reordered arm. The source's arrival order is its
    # own tie plane when present, doc order otherwise.
    src_tr = seg.tie_ranks()
    if src_tr is None:
        src_tr = np.arange(seg.ndocs, dtype=np.int64)
    out.__dict__["_tie_rank"] = np.ascontiguousarray(src_tr[new_order])
    # the marker gates tie_ranks() (never-reordered segments must keep
    # their historical internal-id tie order) and the engine's lone-
    # segment forcemerge; maybe_reorder also sets it on no-op passes
    out.__dict__["_reordered"] = True
    return out


def maybe_reorder(seg: Segment) -> Segment:
    """The merge-time entry point: gate, compute, apply. Returns the
    input segment unchanged when the pass is skipped."""
    if not enabled():
        return seg
    if getattr(seg, "codec_version", CODEC_V1) < CODEC_V2:
        return seg
    if seg.ndocs < min_docs():
        return seg
    import time
    t0 = time.perf_counter()
    order = compute_permutation(seg)
    if order is None:
        # pass ran and found nothing to cluster (empty signature band):
        # mark it so engine.force_merge's lone-segment gate doesn't
        # re-run a full single-segment merge on every subsequent call.
        # Doc order was NOT permuted, so pin an absent tie plane too —
        # the marker alone would otherwise let tie_ranks() reconstruct a
        # bogus seq-rank plane on merge-concatenated (non-monotonic
        # seq_no) segments whose historical tie order is the internal id
        seg.__dict__["_reordered"] = True
        seg.__dict__.setdefault("_tie_rank", None)
        return seg
    out = apply_permutation(seg, order)
    out.__dict__["_reordered"] = True
    dt_ms = (time.perf_counter() - t0) * 1e3
    if METRICS.enabled:
        METRICS.counter("reorder.segments").inc()
        METRICS.histogram("reorder.wall_ms").record(dt_ms)
    return out
