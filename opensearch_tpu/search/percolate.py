"""Percolator: reverse search — stored queries matched against candidate
documents. Reference `modules/percolator` (PercolatorFieldMapper extracts
query terms at index time; PercolateQueryBuilder builds a MemoryIndex per
candidate doc and runs the pre-filtered stored queries against it).

TPU-native shape: the "MemoryIndex" is an ordinary in-memory `Segment` built
from the candidate doc(s); stored queries are pre-filtered by their extracted
terms (indexed as a hidden `<field>#terms` keyword column, NUL-joined
"field\\0term" strings) and then evaluated by a **host numpy evaluator** over
the logical plan — percolation runs thousands of tiny 1-doc matches, where a
per-query XLA compile would dwarf the work; the device path stays the
fallback for node kinds the host evaluator doesn't cover (scripts, joins,
knn)."""

from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional, Set, Tuple

import numpy as np

from ..index.mappings import Mappings
from ..index.segment import Segment, build_segment
from . import compiler as C, plan as PL
from . import query_dsl as dsl

# ---------------------------------------------------------------------------
# index-time term extraction (reference QueryAnalyzer)
# ---------------------------------------------------------------------------


def _extract(n) -> Optional[Set[Tuple[str, str]]]:
    """A set of (field, term) pairs such that a doc can only match `n` if it
    contains at least one of them — or None when no such guarantee exists
    (the stored query must then always be evaluated)."""
    if isinstance(n, PL.LTerms):
        if not n.terms:
            return None
        if n.msm >= len(n.terms):
            # conjunction: every term is individually necessary; one suffices
            return {(n.field, n.terms[0])}
        return {(n.field, t) for t in n.terms}
    if isinstance(n, PL.LPhrase):
        terms = n.terms[:-1] if n.prefix_last and len(n.terms) > 1 else n.terms
        if not terms or (n.prefix_last and len(n.terms) == 1):
            return None
        return {(n.field, terms[0])}
    if isinstance(n, PL.LBool):
        best: Optional[Set] = None
        for c in n.musts + n.filters:
            s = _extract(c)
            if s is not None and (best is None or len(s) < len(best)):
                best = s
        if best is not None:
            return best
        if n.shoulds and n.msm >= 1 and not n.musts and not n.filters:
            union: Set = set()
            for c in n.shoulds:
                s = _extract(c)
                if s is None:
                    return None
                union |= s
            return union
        return None
    if isinstance(n, PL.LConstScore):
        return _extract(n.child)
    if isinstance(n, PL.LBoosting):
        return _extract(n.positive)
    if isinstance(n, PL.LDisMax):
        union = set()
        for c in n.children:
            s = _extract(c)
            if s is None:
                return None
            union |= s
        return union
    if isinstance(n, PL.LFuncScore):
        return _extract(n.child)
    if isinstance(n, PL.LNested):
        return _extract(n.child)
    if isinstance(n, PL.LMatchNone):
        return set()  # never matches; empty necessary set keeps it skippable
    return None


def extract_index_terms(qdict: dict, mappings: Mappings) -> Tuple[List[str], bool]:
    """Parse+validate a stored percolator query and extract its pre-filter
    terms. Returns (["field\\0term", ...], always_run)."""
    q = dsl.parse_query(qdict)
    ctx = PL.ShardContext(mappings, [])
    lroot = PL.rewrite(q, ctx, scoring=False)
    s = _extract(lroot)
    if s is None:
        return [], True
    return sorted({f"{f}\x00{t}" for f, t in s}), False


# ---------------------------------------------------------------------------
# candidate "memory index"
# ---------------------------------------------------------------------------


def _clone_mappings(m: Mappings) -> Mappings:
    """Shallow clone so dynamic mapping of unseen candidate-doc fields never
    leaks into the real index mappings (reference maps unmapped percolated
    fields in a throwaway context the same way)."""
    m2 = copy.copy(m)
    m2.fields = dict(m.fields)
    m2.aliases = dict(m.aliases)
    m2.nested_paths = set(m.nested_paths)
    m2.dynamic_templates = list(m.dynamic_templates)
    return m2


def build_mini(mappings: Mappings, documents: List[dict]):
    """Candidate docs -> (mini Segment, stats context) — the MemoryIndex."""
    m2 = _clone_mappings(mappings)
    parsed = [m2.parse(str(i), doc) for i, doc in enumerate(documents)]
    seg = build_segment("_percolate", parsed, m2)
    ctx = PL.ShardContext(m2, [seg])
    return seg, ctx


def candidate_terms(seg: Segment) -> Set[str]:
    out: Set[str] = set()
    for f, pb in seg.postings.items():
        out.update(f"{f}\x00{t}" for t in pb.vocab)
    for blk in seg.nested.values():
        out |= candidate_terms(blk.child)
    return out


# ---------------------------------------------------------------------------
# host evaluator over the logical plan (matched masks only)
# ---------------------------------------------------------------------------


def host_eval(n, seg: Segment, ctx: PL.ShardContext) -> np.ndarray:
    """bool[ndocs] matched mask for one LNode over a host-resident segment.
    Mirrors emit()'s matched semantics; falls back to the jitted device path
    for node kinds it doesn't model."""
    live = seg.live[: seg.ndocs]

    if isinstance(n, PL.LMatchAll):
        return live.copy()
    if isinstance(n, PL.LMatchNone):
        return np.zeros(seg.ndocs, bool)
    if isinstance(n, PL.LTerms):
        pb = seg.postings.get(n.field)
        if pb is None:
            return np.zeros(seg.ndocs, bool)
        count = np.zeros(seg.ndocs, np.int32)
        for t in n.terms:
            r = pb.row(t)
            if r >= 0:
                a, b = pb.row_slice(r)
                count[pb.doc_ids[a:b]] += 1
        return (count >= max(n.msm, 1)) & live
    if isinstance(n, PL.LExpandTerms):
        rows = n.expander(seg)
        pb = seg.postings.get(n.field)
        mask = np.zeros(seg.ndocs, bool)
        if pb is not None:
            for r in np.asarray(rows).tolist():
                a, b = pb.row_slice(int(r))
                mask[pb.doc_ids[a:b]] = True
        return mask & live
    if isinstance(n, PL.LPhrase):
        from .executor import _host_phrase_freq
        mask = np.zeros(seg.ndocs, bool)
        for d in range(seg.ndocs):
            if live[d] and _host_phrase_freq(n, seg, d) > 0:
                mask[d] = True
        return mask
    if isinstance(n, PL.LRange):
        col = seg.numeric_cols.get(n.field)
        if col is None:
            return np.zeros(seg.ndocs, bool)
        v = col.values[: seg.ndocs]
        mask = col.present[: seg.ndocs].copy()
        if n.lo is not None:
            mask &= (v >= n.lo) if n.include_lo else (v > n.lo)
        if n.hi is not None:
            mask &= (v <= n.hi) if n.include_hi else (v < n.hi)
        return mask & live
    if isinstance(n, PL.LExists):
        f = n.field
        if f in seg.numeric_cols:
            present = seg.numeric_cols[f].present[: seg.ndocs]
        elif f in seg.keyword_cols:
            present = seg.keyword_cols[f].min_ord[: seg.ndocs] >= 0
        elif f in seg.geo_cols:
            present = seg.geo_cols[f].present[: seg.ndocs]
        elif f in seg.doc_lens:
            present = seg.doc_lens[f][: seg.ndocs] > 0
        else:
            return np.zeros(seg.ndocs, bool)
        return np.asarray(present, bool) & live
    if isinstance(n, PL.LIds):
        mask = np.zeros(seg.ndocs, bool)
        for i in n.ids:
            d = seg.id2doc.get(i)
            if d is not None:
                mask[d] = True
        return mask & live
    if isinstance(n, PL.LBool):
        mask = live.copy()
        for c in n.musts + n.filters:
            mask &= host_eval(c, seg, ctx)
        for c in n.must_nots:
            mask &= ~host_eval(c, seg, ctx)
        if n.shoulds:
            cnt = np.zeros(seg.ndocs, np.int32)
            for c in n.shoulds:
                cnt += host_eval(c, seg, ctx)
            mask &= cnt >= n.msm
        return mask
    if isinstance(n, PL.LConstScore):
        return host_eval(n.child, seg, ctx)
    if isinstance(n, PL.LBoosting):
        return host_eval(n.positive, seg, ctx)
    if isinstance(n, PL.LDisMax):
        mask = np.zeros(seg.ndocs, bool)
        for c in n.children:
            mask |= host_eval(c, seg, ctx)
        return mask
    if isinstance(n, PL.LFuncScore) and n.min_score is None:
        return host_eval(n.child, seg, ctx)
    if isinstance(n, PL.LNested):
        blk = seg.nested.get(n.path)
        if blk is None or blk.child.ndocs == 0:
            return np.zeros(seg.ndocs, bool)
        cm = host_eval(n.child, blk.child, n.child_ctx)
        mask = np.zeros(seg.ndocs, bool)
        np.logical_or.at(mask, blk.parent_of[cm], True)
        return mask & live
    if isinstance(n, PL.LGeoDist):
        col = seg.geo_cols.get(n.field)
        if col is None:
            return np.zeros(seg.ndocs, bool)
        r = 6371008.8
        p1 = np.deg2rad(col.lat[: seg.ndocs].astype(np.float64))
        p2 = np.deg2rad(n.lat)
        dphi = p2 - p1
        dlmb = np.deg2rad(n.lon - col.lon[: seg.ndocs].astype(np.float64))
        a = np.sin(dphi / 2) ** 2 + np.cos(p1) * np.cos(p2) * np.sin(dlmb / 2) ** 2
        d = 2 * r * np.arcsin(np.sqrt(np.clip(a, 0.0, 1.0)))
        return (d <= n.radius_m) & col.present[: seg.ndocs] & live
    if isinstance(n, PL.LGeoBox):
        col = seg.geo_cols.get(n.field)
        if col is None:
            return np.zeros(seg.ndocs, bool)
        lat, lon = col.lat[: seg.ndocs], col.lon[: seg.ndocs]
        return ((lat <= n.top) & (lat >= n.bottom) & (lon >= n.left)
                & (lon <= n.right) & col.present[: seg.ndocs] & live)

    # fallback: jitted device evaluation (scripts, knn, joins, min_score)
    params: Dict[str, Any] = {}
    spec = C.prepare(n, seg, ctx, params)
    docs = np.arange(seg.ndocs_pad, dtype=np.int32)
    _, matched = C.run_gather_scores(spec, seg.device_arrays(), params, docs)
    return np.asarray(matched)[: seg.ndocs] > 0


# ---------------------------------------------------------------------------
# percolate-time matching
# ---------------------------------------------------------------------------


def _stored_query(seg: Segment, doc: int, field: str) -> Optional[dsl.Query]:
    cache = getattr(seg, "_percolator_queries", None)
    if cache is None:
        cache = {}
        seg._percolator_queries = cache
    key = (field, doc)
    if key not in cache:
        node: Any = seg.sources[doc]
        for part in field.split("."):
            node = node.get(part) if isinstance(node, dict) else None
            if node is None:
                break
        try:
            cache[key] = dsl.parse_query(node) if isinstance(node, dict) else None
        except dsl.QueryParseError:
            cache[key] = None
    return cache[key]


def candidate_docs(seg: Segment, field: str, cand: Set[str]) -> np.ndarray:
    """Pre-filter: percolator docs whose extracted terms intersect the
    candidate doc's terms, plus always-run docs (reference: the extracted
    terms disjunction + the verified/unknown split)."""
    run = np.zeros(seg.ndocs, bool)
    kcol = seg.keyword_cols.get(f"{field}#terms")
    if kcol is not None and kcol.vocab:
        member = np.fromiter((v in cand for v in kcol.vocab), bool,
                             count=len(kcol.vocab))
        hit = member[kcol.ords]
        np.logical_or.at(run, kcol.doc_of_value[hit], True)
    fcol = seg.keyword_cols.get(f"{field}#flags")
    if fcol is not None:
        run |= fcol.min_ord[: seg.ndocs] >= 0
    return run & seg.live[: seg.ndocs]


def segment_mask(field: str, mini_seg: Segment, mini_ctx: PL.ShardContext,
                 seg: Segment) -> np.ndarray:
    """f32[ndocs_pad]: 1.0 for each stored query in `seg` that matches at
    least one candidate doc."""
    mask = np.zeros(seg.ndocs_pad, np.float32)
    cand = candidate_terms(mini_seg)
    for doc in np.nonzero(candidate_docs(seg, field, cand))[0]:
        q = _stored_query(seg, int(doc), field)
        if q is None:
            continue
        lq = PL.rewrite(q, mini_ctx, scoring=False)
        if host_eval(lq, mini_seg, mini_ctx).any():
            mask[doc] = 1.0
    return mask


def document_slots(field: str, mini_seg: Segment, mini_ctx: PL.ShardContext,
                   seg: Segment, doc: int) -> List[int]:
    """Which candidate documents one stored query matched (fetch-phase
    `_percolator_document_slot`)."""
    q = _stored_query(seg, doc, field)
    if q is None:
        return []
    lq = PL.rewrite(q, mini_ctx, scoring=False)
    return [int(i) for i in np.nonzero(host_eval(lq, mini_seg, mini_ctx))[0]]
