"""Logical plan: `ShardContext`, the `L*` node classes, `rewrite` (DSL tree ->
plan, once a query on the host) and the multi-term expanders.

Of the five modules `compiler.py` pictures it imports only `planes`
(`parse_interval_ms`); `ops.scoring` for the similarity ids and the idf.
"""

from __future__ import annotations

import fnmatch as _fnmatch
from bisect import bisect_left, bisect_right
from dataclasses import dataclass, field as dc_field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..index.date_formats import parse_date
from ..index.mappings import (FLOAT_TYPES, INT_TYPES, KEYWORD_TYPES,
                              RANGE_MEMBER, RANGE_TYPES, TEXT_TYPES,
                              Mappings, coerce_value, _parse_range_value)
from ..index.segment import Segment
from ..models.similarity import Similarity, resolve_similarity
from ..ops import scoring as ops
from ..script import painless_lite as pl
from . import query_dsl as dsl
from .planes import parse_interval_ms


# =====================================================================
# shard context (index-wide statistics)
# =====================================================================

class ShardContext:
    """Index-wide view used during rewrite (reference QueryShardContext)."""

    def __init__(self, mappings: Mappings, segments: List[Segment],
                 similarity=None, field_similarities: Optional[dict] = None,
                 device=None, cache_filters: bool = True):
        self.mappings = mappings
        self.segments = segments
        # where the searcher's segments are hosted (None: the process
        # default; a replica's own device): `compiler.prepare` asks a
        # segment's resident planes of that residency, not of a second one
        self.device = device
        # whether a `bool.filter` clause goes through the filter-mask cache
        # (`compiler._prepare_cached_filter`); a child space's does not
        self.cache_filters = cache_filters
        self.default_sim = resolve_similarity(similarity)
        self.field_sims = {f: resolve_similarity(s)
                           for f, s in (field_similarities or {}).items()}

    def sim_for(self, field: str) -> Similarity:
        return self.field_sims.get(field, self.default_sim)

    @property
    def num_docs(self) -> int:
        return sum(s.ndocs for s in self.segments)  # incl. deleted, like Lucene maxDoc

    def doc_freq(self, field: str, term: str) -> int:
        return sum(s.postings[field].doc_freq(term)
                   for s in self.segments if field in s.postings)

    def collection_tf(self, field: str, term: str) -> float:
        total = 0.0
        for s in self.segments:
            pb = s.postings.get(field)
            if pb is None:
                continue
            r = pb.row(term)
            if r >= 0:
                a, b = pb.row_slice(r)
                total += float(pb.tfs[a:b].sum())
        return total

    def field_stats(self, field: str) -> Tuple[int, int]:
        doc_count, sum_dl = 0, 0
        for s in self.segments:
            st = s.text_stats.get(field)
            if st:
                doc_count += st.doc_count
                sum_dl += st.sum_dl
        return doc_count, sum_dl

    def avgdl(self, field: str) -> float:
        dc, sdl = self.field_stats(field)
        return (sdl / dc) if dc > 0 else 1.0

    def total_tf(self, field: str) -> float:
        _, sdl = self.field_stats(field)
        return float(max(sdl, 1))


# =====================================================================
# logical plan nodes
# =====================================================================

_node_counter = [0]


def _nid() -> int:
    _node_counter[0] += 1
    return _node_counter[0]


@dataclass
class LNode:
    nid: int = dc_field(default_factory=_nid)
    name: Optional[str] = None  # _name


@dataclass
class LTerms(LNode):
    """One weighted term group over a field — the fused scoring leaf."""

    field: str = ""
    terms: List[str] = dc_field(default_factory=list)
    weights: Optional[np.ndarray] = None   # f32[T] idf*boost
    aux: Optional[np.ndarray] = None       # f32[T] (LM collection prob)
    msm: int = 1
    mode: str = "score"                    # score | filter
    sim: Optional[Similarity] = None
    has_norms: bool = True
    boost: float = 1.0                     # filter-mode constant score


@dataclass
class LExpandTerms(LNode):
    """Multi-term expansion (prefix/wildcard/fuzzy/regexp/keyword-range):
    rows resolved per segment via `expander(segment) -> np.ndarray[rows]`.
    Constant-score like Lucene's MultiTermQuery CONSTANT_SCORE rewrite."""

    field: str = ""
    expander: Optional[Callable[[Segment], np.ndarray]] = None
    boost: float = 1.0


@dataclass
class LPhrase(LNode):
    """Positional phrase/span-near: device pair-join over positional postings
    (ops/positions.py). `weight` is the summed idf*boost of the terms (Lucene
    PhraseWeight convention); the last term may expand by prefix
    (match_phrase_prefix)."""

    field: str = ""
    terms: List[str] = dc_field(default_factory=list)
    slop: int = 0
    weight: float = 0.0
    sim: Optional[Similarity] = None
    has_norms: bool = True
    prefix_last: bool = False
    max_expansions: int = 50
    ordered: bool = False              # span_near in_order / intervals ordered
    gap_cost: bool = False             # intervals max_gaps (span gaps, not moves)
    boost: float = 1.0


@dataclass
class LMatchAll(LNode):
    boost: float = 1.0


@dataclass
class LMatchNone(LNode):
    pass


@dataclass
class LRange(LNode):
    field: str = ""
    kind: str = "int"                      # int | float
    lo: Any = None                         # i64/f64 or None
    hi: Any = None
    include_lo: bool = True
    include_hi: bool = True
    boost: float = 1.0


@dataclass
class LExists(LNode):
    field: str = ""
    boost: float = 1.0


@dataclass
class LIds(LNode):
    ids: List[str] = dc_field(default_factory=list)
    boost: float = 1.0


@dataclass
class LBool(LNode):
    musts: List[LNode] = dc_field(default_factory=list)
    shoulds: List[LNode] = dc_field(default_factory=list)
    must_nots: List[LNode] = dc_field(default_factory=list)
    filters: List[LNode] = dc_field(default_factory=list)
    msm: int = 0
    boost: float = 1.0


@dataclass
class LConstScore(LNode):
    child: Optional[LNode] = None
    boost: float = 1.0


@dataclass
class LDisMax(LNode):
    children: List[LNode] = dc_field(default_factory=list)
    tie_breaker: float = 0.0
    boost: float = 1.0


@dataclass
class LBoosting(LNode):
    positive: Optional[LNode] = None
    negative: Optional[LNode] = None
    negative_boost: float = 0.5
    boost: float = 1.0


@dataclass
class LFuncScore(LNode):
    child: Optional[LNode] = None
    functions: List[dsl.ScoreFunction] = dc_field(default_factory=list)
    fn_filters: List[Optional[LNode]] = dc_field(default_factory=list)
    score_mode: str = "multiply"
    boost_mode: str = "multiply"
    min_score: Optional[float] = None
    boost: float = 1.0


@dataclass
class LNested(LNode):
    """Block-join to-parent query: the child subtree executes in the nested
    path's child doc space (its own CSR arrays), then scores reduce to the
    parent space via scatter-add/max over the child->parent map (reference
    ToParentBlockJoinQuery; design per SURVEY §2.2 nested = doc-block)."""

    path: str = ""
    child: Optional[LNode] = None
    child_ctx: Optional["ShardContext"] = None
    score_mode: str = "avg"
    boost: float = 1.0


@dataclass
class LHasChild(LNode):
    """Parents with matching children. Two device passes over the shard's
    join slot space (search/join.py): pass 1 scatters child-query scores into
    parent slots across ALL segments; pass 2 (emit) slices each segment's
    window out of the slot vectors. Reference modules/parent-join
    HasChildQueryBuilder + ToParentBlockJoin-style score modes."""

    join_field: str = ""
    child_rel: str = ""
    child: Optional[LNode] = None          # inner query AND join==child_rel
    parent_filter: Optional[LNode] = None  # join==parent_rel
    score_mode: str = "none"
    min_children: int = 1
    max_children: int = 2**31 - 1
    boost: float = 1.0
    join_index: Any = None
    pre: Any = None                        # lazily-computed slot vectors


@dataclass
class LHasParent(LNode):
    """Children whose parent matches (reference HasParentQueryBuilder):
    pass 1 places parent-query scores at the parents' own slots; pass 2
    gathers through each child's `parent_slot`."""

    join_field: str = ""
    parent_rel: str = ""
    child: Optional[LNode] = None          # inner query AND join==parent_rel
    child_filter: Optional[LNode] = None   # join in child relations
    use_score: bool = False
    boost: float = 1.0
    join_index: Any = None
    pre: Any = None


@dataclass
class LRankFeature(LNode):
    """rank_feature scoring: a single feature row of a feature-postings block
    (gather→fn→scatter) or a dense rank_feature numeric column."""

    field: str = ""
    feature: Optional[str] = None   # None = numeric rank_feature column
    fn: str = "saturation"
    p1: float = 1.0
    p2: float = 1.0
    positive: bool = True
    boost: float = 1.0


@dataclass
class LSparseDot(LNode):
    """Learned-sparse dot product: sum of query-token weight × stored feature
    weight over a rank_features/sparse_vector block."""

    field: str = ""
    tokens: List[str] = dc_field(default_factory=list)
    weights: Optional[np.ndarray] = None
    boost: float = 1.0


@dataclass
class LDistanceFeature(LNode):
    field: str = ""
    kind: str = "date"     # date | geo
    origin: Any = None     # i64 epoch-ms | (lat, lon)
    pivot: float = 0.0     # ms | meters
    boost: float = 1.0


@dataclass
class LPercolate(LNode):
    """Stored-query reverse match: per segment, a host-computed f32 mask of
    which percolator docs' queries match the candidate mini-segment
    (search/percolate.py); the device plan just consumes the mask."""

    field: str = ""
    mini_seg: Any = None
    mini_ctx: Any = None
    boost: float = 1.0


@dataclass
class LScriptFilter(LNode):
    """`script` query: filter where the traced expression is truthy. The AST
    (hashable tuples) lives in the jit-static spec; numeric script params are
    traced scalars, so param changes reuse the XLA program."""

    ast: tuple = ()
    params: dict = dc_field(default_factory=dict)
    boost: float = 1.0


@dataclass
class LScriptScore(LNode):
    """`script_score` query (reference ScriptScoreQueryBuilder): the script
    replaces the child's score; `_score` binds to the child's score vector."""

    child: Optional[LNode] = None
    ast: tuple = ()
    params: dict = dc_field(default_factory=dict)
    min_score: Optional[float] = None
    boost: float = 1.0


@dataclass
class LKnn(LNode):
    field: str = ""
    vector: Optional[np.ndarray] = None
    k: int = 10
    filter: Optional[LNode] = None
    similarity: str = "cosine"
    boost: float = 1.0
    # ANN: None = exact scan; int = IVF nprobe request (clamped to the
    # segment's actual nlist at prepare time)
    nprobe: Optional[int] = None
    exact: bool = False


@dataclass
class LSpanHost(LNode):
    """Span/interval algebra evaluated host-side (search/spans.py): prepare
    computes the per-segment sloppy-frequency vector; the device scores it
    like a phrase pseudo-term."""

    field: str = ""
    query: Any = None           # dsl span tree, or ("intervals", field, rule)
    weight: float = 0.0         # Σ idf(term)·boost, host-computed
    boost: float = 1.0
    has_norms: bool = True
    sim: Any = None


@dataclass
class LGeoDist(LNode):
    field: str = ""
    lat: float = 0.0
    lon: float = 0.0
    radius_m: float = 0.0
    boost: float = 1.0
    inclusive: bool = True


@dataclass
class LGeoBox(LNode):
    field: str = ""
    top: float = 0.0
    left: float = 0.0
    bottom: float = 0.0
    right: float = 0.0
    boost: float = 1.0


@dataclass
class LTermsSet(LNode):
    """terms_set: the child LTerms counts matching terms per doc; the
    per-DOC minimum comes from a numeric column or a host-evaluated
    script vector (reference TermsSetQueryBuilder / Lucene CoveringQuery)."""

    field: str = ""
    child: Optional[LNode] = None
    msm_field: Optional[str] = None
    script: Optional[Tuple[str, dict]] = None   # (source, params)
    num_terms: int = 0
    boost: float = 1.0


@dataclass
class LPinned(LNode):
    """pinned: listed ids rank first (descending by list order), organic
    results follow (reference PinnedQueryBuilder)."""

    ids: Tuple[str, ...] = ()
    organic: Optional[LNode] = None
    boost: float = 1.0


@dataclass
class LCombined(LNode):
    """combined_fields: true BM25F — per-term tf combined across weighted
    fields BEFORE saturation, idf from the union doc frequency, combined
    dl/avgdl (reference CombinedFieldsQueryBuilder over Lucene
    CombinedFieldQuery)."""

    fields: Tuple[Tuple[str, float], ...] = ()
    terms: Tuple[str, ...] = ()
    msm: int = 1
    boost: float = 1.0
    idf: Optional[np.ndarray] = None   # per-term union-df idf (rewrite-time)


@dataclass
class LGeoPolygon(LNode):
    """geo_polygon on geo_point columns: device ray-cast, vertex arrays are
    query params (static length per jit key)."""

    field: str = ""
    lats: Tuple[float, ...] = ()
    lons: Tuple[float, ...] = ()
    boost: float = 1.0


@dataclass
class LGeoShape(LNode):
    """geo_shape relation filter. The mask is computed EXACTLY on the host
    at prepare time (bbox-column prefilter -> search/geo.py refinement over
    survivors) and uploaded as a bool[ndocs_pad] plan param — see
    ShapeColumn for why that is the TPU-shaped split."""

    field: str = ""
    shape: Any = None             # parsed geo.Shape
    relation: str = "intersects"
    boost: float = 1.0


# =====================================================================
# rewrite: DSL tree -> logical plan (host, index-wide stats)
# =====================================================================

def rewrite(q: dsl.Query, ctx: ShardContext, scoring: bool = True) -> LNode:
    out = _rewrite(q, ctx, scoring)
    out.name = getattr(q, "name", None) or out.name
    return out


def weighted_terms(field: str, terms: List[str], boosts: List[float],
                   ctx: ShardContext, msm: int, mode: str, boost: float) -> LTerms:
    ft = ctx.mappings.resolve_field(field)
    sim = ctx.sim_for(field)
    has_norms = bool(ft is not None and ft.has_norms and sim.uses_norms)
    n = ctx.num_docs
    weights = np.zeros(len(terms), dtype=np.float32)
    aux = np.zeros(len(terms), dtype=np.float32)
    for i, t in enumerate(terms):
        df = ctx.doc_freq(field, t)
        weights[i] = sim.term_weight(boosts[i] * boost, n, max(df, 0)) if df > 0 else 0.0
        if sim.sim_id == ops.SIM_LM_DIRICHLET:
            aux[i] = sim.term_aux(ctx.collection_tf(field, t), ctx.total_tf(field))
    node = LTerms(field=field, terms=terms, weights=weights, aux=aux, msm=msm,
                  mode=mode, sim=sim, has_norms=has_norms, boost=boost)
    # raw (pre-idf) per-term boosts: the SPMD mesh path recomputes idf on
    # device from psum'd global stats (parallel/spmd.py DFS phase)
    node.raw_boosts = np.asarray([bi * boost for bi in boosts], np.float32)
    return node


def prefix_rows(pb, term: str, cap: Optional[int] = None) -> range:
    """Vocab row range whose terms start with `term`, optionally capped at
    `cap` expansions (reference MultiTermQuery maxExpansions)."""
    lo = bisect_left(pb.vocab, term)
    hi = bisect_left(pb.vocab, term + "￿")
    if cap is not None:
        hi = min(hi, lo + cap)
    return range(lo, hi)


def _range_field_node(ft, q: "dsl.RangeQuery") -> LNode:
    """Range query AGAINST a range field (reference RangeFieldMapper
    relation semantics): the query bounds normalize to a closed [a, b] in
    column space exactly like index-time values, then
    intersects: lo <= b AND hi >= a; within: lo >= a AND hi <= b;
    contains: lo <= a AND hi >= b. Constant score (like the reference)."""
    member = RANGE_MEMBER[ft.type]
    kind = "float" if member in ("float", "double") else "int"
    bounds = {k: v for k, v in (("gte", q.gte), ("gt", q.gt),
                                ("lte", q.lte), ("lt", q.lt))
              if v is not None}
    a, b = _parse_range_value(ft, bounds)
    lo_f, hi_f = f"{ft.name}#lo", f"{ft.name}#hi"
    rel = q.relation
    if rel == "within":
        parts = [LRange(field=lo_f, kind=kind, lo=a),
                 LRange(field=hi_f, kind=kind, hi=b)]
    elif rel == "contains":
        parts = [LRange(field=lo_f, kind=kind, hi=a),
                 LRange(field=hi_f, kind=kind, lo=b)]
    else:                           # intersects (default)
        parts = [LRange(field=lo_f, kind=kind, hi=b),
                 LRange(field=hi_f, kind=kind, lo=a)]
    return LConstScore(child=LBool(filters=parts), boost=q.boost)


@dataclass
class LSourcePhrase(LNode):
    """Phrase over a positions-less `match_only_text` field: candidates from
    the term postings conjunction, phrase verified by re-analyzing _source
    (reference MatchOnlyTextFieldMapper phrase queries via
    SourceConfirmedTextQuery). Documented deviation: hits score the constant
    phrase weight rather than a sloppy-freq BM25 (freqs are not indexed)."""

    field: str = ""
    terms: List[str] = dc_field(default_factory=list)
    slop: int = 0
    weight: float = 1.0


def _phrase_node(field: str, terms: List[str], slop: int, ctx: ShardContext,
                 boost: float, prefix_last: bool = False,
                 max_expansions: int = 50, ordered: bool = False,
                 gap_cost: bool = False) -> LPhrase:
    """Phrase weight = sum of per-term idf (Lucene PhraseWeight: the phrase
    scores as one pseudo-term whose idf is the terms' idf sum)."""
    ft = ctx.mappings.resolve_field(field)
    if ft is not None and ft.type == "match_only_text":
        n = ctx.num_docs
        sim = ctx.sim_for(field)
        w = sum(sim.term_weight(1.0, n, min(ctx.doc_freq(field, t), n))
                for t in terms if ctx.doc_freq(field, t) > 0)
        return LSourcePhrase(field=field, terms=terms, slop=slop,
                             weight=(w or 1.0) * boost)
    sim = ctx.sim_for(field)
    has_norms = bool(ft is not None and ft.has_norms and sim.uses_norms)
    n = ctx.num_docs
    w = 0.0
    last = len(terms) - 1
    for i, t in enumerate(terms):
        if prefix_last and i == last:
            # expansion union df (capped) stands in for the prefix "term"
            df = 0
            for s in ctx.segments:
                pb = s.postings.get(field)
                if pb is None:
                    continue
                for r in prefix_rows(pb, t, max_expansions):
                    df += int(pb.starts[r + 1] - pb.starts[r])
        else:
            df = ctx.doc_freq(field, t)
        if df > 0:
            # prefix-union df can exceed maxDoc; Lucene never sees df > N
            # (negative idf would break ranking invariants)
            w += sim.term_weight(1.0, n, min(df, n))
    return LPhrase(field=field, terms=terms, slop=slop, weight=w * boost,
                   sim=sim, has_norms=has_norms, prefix_last=prefix_last,
                   max_expansions=max_expansions, ordered=ordered,
                   gap_cost=gap_cost, boost=boost)


def analyze_query_text(field: str, text: Any, ctx: ShardContext,
                       analyzer_override: Optional[str] = None) -> List[str]:
    ft = ctx.mappings.resolve_field(field)
    if ft is None:
        return [str(text)]
    if analyzer_override:
        return ctx.mappings.analysis.get(analyzer_override).terms(str(text))
    return ctx.mappings.search_analyzer_for(ft).terms(str(text))


def index_term(field: str, value: Any, ctx: ShardContext) -> str:
    """Single exact term for term/terms queries: keyword normalizer applies,
    text fields match the raw token (reference TermQueryBuilder semantics).
    flat_object leaves match their "path=value" composite terms."""
    ft = ctx.mappings.resolve_field(field)
    if ft is not None and ft.flat_prefix:
        return f"{ft.flat_prefix}={value}"
    if ft is not None and ft.type in KEYWORD_TYPES:
        norm = ctx.mappings.index_analyzer(ft).terms(str(value))
        return norm[0] if norm else str(value)
    return str(value)


def _ip_cidr_node(field: str, mask: str, boost: float) -> LNode:
    """CIDR -> exact 64-bit ip range (reference IpFieldMapper prefix query)."""
    import ipaddress

    from ..index.mappings import _ip_to_int
    try:
        net = ipaddress.ip_network(mask, strict=False)
    except ValueError as e:
        raise dsl.QueryParseError(f"invalid IP mask [{mask}]: {e}")
    return LRange(field=field, kind="int",
                  lo=_ip_to_int(str(net.network_address)),
                  hi=_ip_to_int(str(net.broadcast_address)),
                  include_lo=True, include_hi=True, boost=boost)


def _numeric_eq_node(ft, field: str, value: Any, boost: float) -> LNode:
    cv = coerce_value(ft, value)
    kind = "float" if ft.type in FLOAT_TYPES else "int"
    return LRange(field=field, kind=kind, lo=cv, hi=cv,
                  include_lo=True, include_hi=True, boost=boost)


def _rewrite(q: dsl.Query, ctx: ShardContext, scoring: bool) -> LNode:  # noqa: C901
    m = ctx.mappings

    if isinstance(q, dsl.HybridQuery):
        # hybrid is a COORDINATOR construct (search/fusion.py): the
        # top-level interceptors (search_shards, distnode) consume it
        # before any per-shard plan exists. Reaching the rewriter means
        # it was nested inside another query — a structural 400.
        raise dsl.QueryParseError(
            "[hybrid] must be the top-level query — sub-queries fuse at "
            "the coordinator merge and cannot nest inside other queries")

    if isinstance(q, dsl.MatchAllQuery):
        return LMatchAll(boost=q.boost)
    if isinstance(q, dsl.MatchNoneQuery):
        return LMatchNone()

    if isinstance(q, dsl.TermQuery):
        ft = m.resolve_field(q.field)
        if ft is not None and ft.type in RANGE_TYPES:
            # containment: stored [lo, hi] covers the value (reference
            # RangeType.termQuery = intersects on a point)
            from ..index.mappings import (RANGE_MEMBER, _range_member_coerce)
            member = RANGE_MEMBER[ft.type]
            cv = _range_member_coerce(member, q.value, ft)
            kind = "float" if member in ("float", "double") else "int"
            return LConstScore(child=LBool(filters=[
                LRange(field=f"{ft.name}#lo", kind=kind, hi=cv),
                LRange(field=f"{ft.name}#hi", kind=kind, lo=cv)]),
                boost=q.boost)
        if (ft is not None and ft.type == "ip" and isinstance(q.value, str)
                and "/" in q.value):
            return _ip_cidr_node(ft.name, q.value, q.boost)
        if ft is not None and ft.type in (INT_TYPES | FLOAT_TYPES) and ft.type != "date":
            return _numeric_eq_node(ft, ft.name, q.value, q.boost)
        if ft is not None and ft.type == "date":
            return _numeric_eq_node(ft, ft.name, q.value, q.boost)
        field = ft.name if ft else q.field
        term = index_term(q.field, q.value, ctx)
        if q.case_insensitive:
            term = term.lower()
        mode = "score" if scoring else "filter"
        return weighted_terms(field, [term], [1.0], ctx, 1, mode, q.boost)

    if isinstance(q, dsl.TermsQuery):
        ft = m.resolve_field(q.field)
        if ft is not None and ft.type == "ip" and any(
                isinstance(v, str) and "/" in v for v in q.values):
            # CIDR members expand to ranges; exact ips stay term matches
            # (reference IpFieldMapper.termsQuery)
            children = [
                _ip_cidr_node(ft.name, v, 1.0)
                if isinstance(v, str) and "/" in v else
                weighted_terms(ft.name, [index_term(ft.name, v, ctx)],
                               [1.0], ctx, 1, "filter", 1.0)
                for v in q.values]
            return LBool(shoulds=children, msm=1, boost=q.boost)
        if ft is not None and ft.type in (INT_TYPES | FLOAT_TYPES):
            children = [_numeric_eq_node(ft, ft.name, v, 1.0) for v in q.values]
            return LBool(shoulds=children, msm=1, boost=q.boost)
        field = ft.name if ft else q.field
        terms = [index_term(q.field, v, ctx) for v in q.values]
        # terms query is constant-score (reference TermInSetQuery)
        return weighted_terms(field, terms, [1.0] * len(terms), ctx, 1, "filter", q.boost)

    if isinstance(q, dsl.MatchQuery):
        ft = m.resolve_field(q.field)
        if ft is not None and ft.type in (INT_TYPES | FLOAT_TYPES) and ft.type != "date":
            return _numeric_eq_node(ft, ft.name, q.query, q.boost)
        field = ft.name if ft else q.field
        terms = analyze_query_text(field, q.query, ctx, q.analyzer)
        if not terms:
            return LMatchNone()
        if q.fuzziness is not None:
            expanded: List[LNode] = []
            for t in terms:
                expanded.append(LExpandTerms(field=field,
                                             expander=fuzzy_expander(field, t, q.fuzziness, 0),
                                             boost=q.boost))
            msm = len(expanded) if q.operator == "and" else \
                dsl.parse_minimum_should_match(q.minimum_should_match, len(expanded)) or 1
            return LBool(shoulds=expanded, msm=msm, boost=1.0)
        msm = len(terms) if q.operator == "and" else \
            dsl.parse_minimum_should_match(q.minimum_should_match, len(terms)) or 1
        mode = "score" if scoring else "score"  # scores also drive msm counts
        return weighted_terms(field, terms, [1.0] * len(terms), ctx, msm, mode, q.boost)

    if isinstance(q, dsl.MatchBoolPrefixQuery):
        ft = m.resolve_field(q.field)
        field = ft.name if ft else q.field
        terms = analyze_query_text(field, q.query, ctx, q.analyzer)
        if not terms:
            return LMatchNone()
        children: List[LNode] = [
            weighted_terms(field, [t], [1.0], ctx, 1, "score", q.boost)
            for t in terms[:-1]]
        children.append(LExpandTerms(
            field=field,
            expander=prefix_expander(field, terms[-1], False, cap=50),
            boost=q.boost))
        msm = len(children) if q.operator == "and" else 1
        return LBool(shoulds=children, msm=msm, boost=1.0)

    if isinstance(q, dsl.TermsSetQuery):
        ft = m.resolve_field(q.field)
        field = ft.name if ft else q.field
        terms = [str(t) for t in q.terms]
        if not terms:
            return LMatchNone()
        child = weighted_terms(field, terms, [1.0] * len(terms), ctx, 0,
                               "score", q.boost)
        script = None
        if q.minimum_should_match_script is not None:
            src, prm = dsl.parse_script_spec(q.minimum_should_match_script)
            try:
                pl.parse(src)
            except pl.ScriptError as e:
                raise dsl.QueryParseError(f"[terms_set] bad script: {e}")
            script = (src, prm or {})
        return LTermsSet(field=field, child=child,
                         msm_field=q.minimum_should_match_field,
                         script=script, num_terms=len(terms), boost=q.boost)

    if isinstance(q, dsl.CombinedFieldsQuery):
        fspecs = []
        for f in q.fields:
            name, w = (f.rsplit("^", 1) if "^" in f else (f, "1"))
            ftc = m.resolve_field(name)
            try:
                wf = float(w)
            except ValueError:
                raise dsl.QueryParseError(
                    f"[combined_fields] bad field boost [{f}]")
            fspecs.append((ftc.name if ftc else name, wf))
        # analyze with the first field's analyzer (reference requires all
        # combined fields share one analyzer and errors otherwise)
        terms = analyze_query_text(fspecs[0][0], q.query, ctx, None)
        if not terms:
            return LMatchNone()
        msm = len(terms) if q.operator == "and" else \
            dsl.parse_minimum_should_match(q.minimum_should_match,
                                           len(terms)) or 1
        node = LCombined(fields=tuple(fspecs), terms=tuple(terms), msm=msm,
                         boost=q.boost)
        # union-df idf depends only on shard-wide stats: compute ONCE at
        # rewrite (like LTerms.weights), not per segment in prepare
        n = max(ctx.num_docs, 1)
        idf = np.zeros(len(terms), np.float32)
        for i, t in enumerate(terms):
            # segments have disjoint doc-id spaces: union WITHIN each
            # segment across fields, then sum the sizes
            df = 0
            for s2 in ctx.segments:
                seg_lists = []
                for fname, _w in node.fields:
                    pb = s2.postings.get(fname)
                    r = pb.row(t) if pb is not None else -1
                    if r >= 0:
                        a, b2 = pb.row_slice(r)
                        seg_lists.append(pb.doc_ids[a:b2])
                if len(seg_lists) == 1:
                    df += len(seg_lists[0])
                elif seg_lists:
                    df += len(np.unique(np.concatenate(seg_lists)))
            if df > 0:
                idf[i] = q.boost * float(
                    np.log(1.0 + (n - df + 0.5) / (df + 0.5)))
        node.idf = idf
        return node

    if isinstance(q, dsl.PinnedQuery):
        return LPinned(ids=tuple(q.ids),
                       organic=(rewrite(q.organic, ctx, scoring)
                                if q.organic else None), boost=q.boost)

    if isinstance(q, dsl.MultiMatchQuery):
        if q.type in ("phrase", "phrase_prefix"):
            children = [rewrite(dsl.MatchPhraseQuery(
                            field=f.split("^")[0], query=q.query,
                            prefix=q.type == "phrase_prefix",
                            boost=float(f.split("^")[1]) if "^" in f else 1.0),
                        ctx, scoring) for f in q.fields]
        else:
            children = [rewrite(dsl.MatchQuery(field=f.split("^")[0], query=q.query,
                                               operator=q.operator,
                                               minimum_should_match=q.minimum_should_match,
                                               boost=float(f.split("^")[1]) if "^" in f else 1.0),
                        ctx, scoring) for f in q.fields]
        if q.type in ("best_fields", "phrase", "phrase_prefix"):
            return LDisMax(children=children, tie_breaker=q.tie_breaker, boost=q.boost)
        return LBool(shoulds=children, msm=1, boost=q.boost)  # most_fields

    if isinstance(q, dsl.MatchPhraseQuery):
        ft = m.resolve_field(q.field)
        field = ft.name if ft else q.field
        terms = analyze_query_text(field, q.query, ctx, q.analyzer)
        if not terms:
            return LMatchNone()
        if len(terms) == 1 and not q.prefix:
            # Lucene rewrites a single-term phrase to a TermQuery
            return weighted_terms(field, terms, [1.0], ctx, 1, "score", q.boost)
        if len(terms) == 1 and q.prefix:
            return LExpandTerms(field=field,
                                expander=prefix_expander(field, terms[0], False,
                                                         cap=q.max_expansions),
                                boost=q.boost)
        return _phrase_node(field, terms, q.slop, ctx, q.boost,
                            prefix_last=q.prefix, max_expansions=q.max_expansions)

    if isinstance(q, dsl.SpanTermQuery):
        field = q.field
        term = index_term(field, q.value, ctx)
        return weighted_terms(field, [term], [1.0], ctx, 1, "score", q.boost)

    if isinstance(q, dsl.SpanNearQuery):
        if not all(isinstance(c, dsl.SpanTermQuery) for c in q.clauses) or \
                len({c.field for c in q.clauses}) > 1:
            # nested span algebra inside near -> host span engine
            return _span_host_node(q, None, ctx, q.boost)
        flat_terms: List[str] = []
        field = None
        for c in q.clauses:
            if field is None:
                field = c.field
            flat_terms.append(index_term(c.field, c.value, ctx))
        if not flat_terms or field is None:
            return LMatchNone()
        if len(flat_terms) == 1:
            return weighted_terms(field, flat_terms, [1.0], ctx, 1, "score", q.boost)
        # Lucene SpanNearQuery slop counts intervening unmatched positions
        # (gaps), not term movement
        return _phrase_node(field, flat_terms, q.slop, ctx, q.boost,
                            ordered=q.in_order, gap_cost=True)

    if isinstance(q, (dsl.SpanOrQuery, dsl.SpanNotQuery, dsl.SpanFirstQuery,
                      dsl.SpanContainingQuery, dsl.SpanWithinQuery,
                      dsl.SpanMultiQuery, dsl.FieldMaskingSpanQuery)):
        return _span_host_node(q, None, ctx, q.boost)

    if isinstance(q, dsl.IntervalsQuery) and q.rule is not None:
        ft = m.resolve_field(q.field)
        field = ft.name if ft else q.field
        r = q.rule
        if r.kind == "match" and r.filter_kind is None:
            # hot path: single match rule rides the device pair-join below
            q = dsl.IntervalsQuery(field=q.field, query=r.query,
                                   max_gaps=r.max_gaps, ordered=r.ordered,
                                   analyzer=r.analyzer, boost=q.boost)
        else:
            return _span_host_node(("intervals", field, r), field, ctx,
                                   q.boost)

    if isinstance(q, dsl.IntervalsQuery):
        ft = m.resolve_field(q.field)
        field = ft.name if ft else q.field
        terms = analyze_query_text(field, q.query, ctx, q.analyzer)
        if not terms:
            return LMatchNone()
        if len(terms) == 1:
            return weighted_terms(field, terms, [1.0], ctx, 1, "score", q.boost)
        # max_gaps=-1 means unbounded; bound by a large window (the device
        # join needs a finite slop). For ordered matches the median-centered
        # movement cost equals the total gap count, so max_gaps maps 1:1.
        slop = q.max_gaps if q.max_gaps >= 0 else 1 << 20
        return _phrase_node(field, terms, slop, ctx, q.boost, ordered=q.ordered,
                            gap_cost=True)

    if isinstance(q, dsl.BoolQuery):
        musts = [rewrite(c, ctx, scoring) for c in q.must]
        shoulds = [rewrite(c, ctx, scoring) for c in q.should]
        must_nots = [rewrite(c, ctx, False) for c in q.must_not]
        filters = [rewrite(c, ctx, False) for c in q.filter]
        n_should = len(shoulds)
        if q.minimum_should_match is not None:
            msm = dsl.parse_minimum_should_match(q.minimum_should_match, n_should)
        else:
            msm = 1 if (n_should and not musts and not filters) else 0
        return LBool(musts=musts, shoulds=shoulds, must_nots=must_nots,
                     filters=filters, msm=msm, boost=q.boost)

    if isinstance(q, dsl.RangeQuery):
        ft = m.resolve_field(q.field)
        if ft is None:
            return LMatchNone()
        if ft.type in RANGE_TYPES:
            return _range_field_node(ft, q)
        if ft.type in KEYWORD_TYPES and ft.type != "ip":
            return LExpandTerms(field=ft.name,
                                expander=_keyword_range_expander(ft.name, q),
                                boost=q.boost)
        kind = "float" if ft.type in FLOAT_TYPES else "int"
        lo = hi = None
        inc_lo = inc_hi = True
        if ft.type == "date":
            # the request's `format` replaces the mapping's for its bounds;
            # the parts a bound leaves out round up for lte / gt and down
            # for gte / lt (reference DateMathParser's roundUpProperty)
            fmt = q.date_format or ft.date_format

            def bound(v, round_up):
                # an unknown pattern and a text outside its format alike
                # are the request's fault: a 400 that names it
                try:
                    return parse_date(v, fmt, round_up)
                except ValueError as e:
                    raise dsl.QueryParseError(
                        f"[range] query on [{q.field}]: {e}")
        else:
            def bound(v, _round_up):
                return coerce_value(ft, v)
        if q.gte is not None:
            lo, inc_lo = bound(q.gte, False), True
        if q.gt is not None:
            lo, inc_lo = bound(q.gt, True), False
        if q.lte is not None:
            hi, inc_hi = bound(q.lte, True), True
        if q.lt is not None:
            hi, inc_hi = bound(q.lt, False), False
        return LRange(field=ft.name, kind=kind, lo=lo, hi=hi,
                      include_lo=inc_lo, include_hi=inc_hi, boost=q.boost)

    if isinstance(q, dsl.ExistsQuery):
        ft = m.resolve_field(q.field)
        if ft is not None and ft.type in RANGE_TYPES:
            return LExists(field=f"{ft.name}#lo", boost=q.boost)
        if ft is not None and ft.flat_prefix:
            # flat_object leaf exists = any "path=..." term under #paths
            return LExpandTerms(
                field=ft.name,
                expander=prefix_expander(ft.name, f"{ft.flat_prefix}=",
                                         False),
                boost=q.boost)
        return LExists(field=ft.name if ft else q.field, boost=q.boost)

    if isinstance(q, dsl.IdsQuery):
        return LIds(ids=list(q.values), boost=q.boost)

    if isinstance(q, dsl.ConstantScoreQuery):
        return LConstScore(child=rewrite(q.filter, ctx, False), boost=q.boost)

    if isinstance(q, dsl.BoostingQuery):
        return LBoosting(positive=rewrite(q.positive, ctx, scoring),
                         negative=rewrite(q.negative, ctx, False),
                         negative_boost=q.negative_boost, boost=q.boost)

    if isinstance(q, dsl.DisMaxQuery):
        return LDisMax(children=[rewrite(c, ctx, scoring) for c in q.queries],
                       tie_breaker=q.tie_breaker, boost=q.boost)

    if isinstance(q, dsl.PrefixQuery):
        return LExpandTerms(field=q.field, expander=prefix_expander(q.field, q.value,
                                                                    q.case_insensitive),
                            boost=q.boost)
    if isinstance(q, dsl.WildcardQuery):
        return LExpandTerms(field=q.field, expander=wildcard_expander(q.field, q.value,
                                                                      q.case_insensitive),
                            boost=q.boost)
    if isinstance(q, dsl.RegexpQuery):
        return LExpandTerms(field=q.field, expander=regexp_expander(q.field, q.value),
                            boost=q.boost)
    if isinstance(q, dsl.FuzzyQuery):
        return LExpandTerms(field=q.field,
                            expander=fuzzy_expander(q.field, q.value, q.fuzziness,
                                                    q.prefix_length),
                            boost=q.boost)

    if isinstance(q, (dsl.QueryStringQuery, dsl.SimpleQueryStringQuery)):
        return _rewrite_query_string(q, ctx, scoring)

    if isinstance(q, dsl.KnnQuery):
        ft = m.resolve_field(q.field)
        sim = ft.vector_similarity if ft is not None else "cosine"
        vec = np.asarray(q.vector, np.float32)
        if sim == "cosine":
            vec = vec / max(float(np.linalg.norm(vec)), 1e-12)
        return LKnn(field=q.field, vector=vec, k=q.k,
                    filter=rewrite(q.filter, ctx, False) if q.filter else None,
                    similarity=sim, boost=q.boost,
                    nprobe=q.nprobe, exact=q.exact)

    if isinstance(q, dsl.GeoDistanceQuery):
        return LGeoDist(field=q.field, lat=q.lat, lon=q.lon, radius_m=q.distance_m,
                        boost=q.boost, inclusive=q.inclusive)
    if isinstance(q, dsl.GeoBoundingBoxQuery):
        return LGeoBox(field=q.field, top=q.top, left=q.left, bottom=q.bottom,
                       right=q.right, boost=q.boost)

    if isinstance(q, dsl.GeoPolygonQuery):
        return LGeoPolygon(field=q.field, lats=tuple(q.lats),
                           lons=tuple(q.lons), boost=q.boost)

    if isinstance(q, dsl.GeoShapeQuery):
        from .geo import ShapeParseError, parse_shape
        ft = m.resolve_field(q.field)
        if ft is None:
            if q.ignore_unmapped:
                return LMatchNone()
            raise dsl.QueryParseError(
                f"[geo_shape] failed to find geo field [{q.field}]")
        if ft.type not in ("geo_shape", "geo_point"):
            raise dsl.QueryParseError(
                f"[geo_shape] field [{q.field}] is of type [{ft.type}], "
                f"not geo_shape/geo_point")
        try:
            shape = parse_shape(q.shape)
        except ShapeParseError as e:
            raise dsl.QueryParseError(f"[geo_shape] {e}")
        return LGeoShape(field=q.field, shape=shape, relation=q.relation,
                         boost=q.boost)

    if isinstance(q, dsl.ScriptQuery):
        try:
            ast = pl.validate_device_script(q.source)
        except pl.ScriptError as e:
            raise dsl.QueryParseError(f"[script] compile error: {e}")
        return LScriptFilter(ast=ast, params=q.params or {}, boost=q.boost)

    if isinstance(q, dsl.ScriptScoreQuery):
        try:
            ast = pl.validate_device_script(q.source)
        except pl.ScriptError as e:
            raise dsl.QueryParseError(f"[script_score] compile error: {e}")
        return LScriptScore(child=rewrite(q.query or dsl.MatchAllQuery(), ctx, scoring),
                            ast=ast, params=q.params or {},
                            min_score=q.min_score, boost=q.boost)

    if isinstance(q, dsl.FunctionScoreQuery):
        child = rewrite(q.query or dsl.MatchAllQuery(), ctx, scoring)
        fn_filters = [rewrite(f.filter, ctx, False) if f.filter else None
                      for f in q.functions]
        for f in q.functions:
            if f.kind == "script_score":
                try:
                    pl.validate_device_script(f.script or "")
                except pl.ScriptError as e:
                    raise dsl.QueryParseError(f"[script_score] compile error: {e}")
        return LFuncScore(child=child, functions=q.functions, fn_filters=fn_filters,
                          score_mode=q.score_mode, boost_mode=q.boost_mode,
                          min_score=q.min_score, boost=q.boost)

    if isinstance(q, dsl.MoreLikeThisQuery):
        return _rewrite_mlt(q, ctx, scoring)

    if isinstance(q, dsl.NestedQuery):
        if q.path not in m.nested_paths:
            if q.ignore_unmapped:
                return LMatchNone()
            raise dsl.QueryParseError(
                f"[nested] failed to find nested object under path [{q.path}]")
        # multi-level path queried from an outer level: blocks live on the
        # intermediate child segments, so route through the nested chain
        # (nested(a, nested(a.b, q)) — reference resolves the chain the same
        # way via parent filters)
        if not any(q.path in s.nested for s in ctx.segments):
            parts = q.path.split(".")
            for cut in range(len(parts) - 1, 0, -1):
                pfx = ".".join(parts[:cut])
                if pfx in m.nested_paths and any(pfx in s.nested
                                                 for s in ctx.segments):
                    inner_q = dsl.NestedQuery(path=q.path, query=q.query,
                                              score_mode=q.score_mode,
                                              ignore_unmapped=q.ignore_unmapped)
                    outer = dsl.NestedQuery(path=pfx, query=inner_q,
                                            score_mode=q.score_mode,
                                            boost=q.boost)
                    return _rewrite(outer, ctx, scoring)
        child_ctx = nested_context(ctx, q.path)
        inner = rewrite(q.query, child_ctx, scoring)
        return LNested(path=q.path, child=inner, child_ctx=child_ctx,
                       score_mode=q.score_mode, boost=q.boost)

    if isinstance(q, dsl.RankFeatureQuery):
        return _rewrite_rank_feature(q, ctx)

    if isinstance(q, dsl.NeuralSparseQuery):
        ft = m.resolve_field(q.field)
        if ft is None or ft.type not in ("rank_features", "sparse_vector"):
            raise dsl.QueryParseError(
                f"[neural_sparse] field [{q.field}] is not a rank_features/"
                f"sparse_vector field")
        toks = sorted(q.tokens)
        return LSparseDot(field=ft.name, tokens=toks,
                          weights=np.asarray([q.tokens[t] for t in toks],
                                             np.float32),
                          boost=q.boost)

    if isinstance(q, dsl.DistanceFeatureQuery):
        ft = m.resolve_field(q.field)
        if ft is None:
            raise dsl.QueryParseError(
                f"[distance_feature] unknown field [{q.field}]")
        if ft.type == "date":
            from ..index.mappings import _parse_date
            origin = _parse_date(q.origin, ft.date_format)
            pivot = float(parse_interval_ms(q.pivot))
            return LDistanceFeature(field=ft.name, kind="date", origin=origin,
                                    pivot=pivot, boost=q.boost)
        if ft.type in ("geo_point",):
            origin = dsl._parse_point(q.origin)
            pivot = dsl._parse_distance(q.pivot)
            return LDistanceFeature(field=ft.name, kind="geo", origin=origin,
                                    pivot=pivot, boost=q.boost)
        raise dsl.QueryParseError(
            f"[distance_feature] field [{q.field}] must be a date or "
            f"geo_point field")

    if isinstance(q, (dsl.HasChildQuery, dsl.HasParentQuery, dsl.ParentIdQuery)):
        return _rewrite_join(q, ctx, scoring)

    if isinstance(q, dsl.PercolateQuery):
        from .percolate import build_mini

        ft = m.resolve_field(q.field)
        if ft is None or ft.type != "percolator":
            raise dsl.QueryParseError(
                f"[percolate] field [{q.field}] is not a percolator field")
        if not q.documents:
            raise dsl.QueryParseError(
                "[percolate] document reference was not resolved "
                "(use the REST layer, or inline `document`)")
        try:
            mini_seg, mini_ctx = build_mini(m, q.documents)
        except ValueError as e:
            raise dsl.QueryParseError(f"[percolate] cannot parse document: {e}")
        return LPercolate(field=ft.name, mini_seg=mini_seg, mini_ctx=mini_ctx,
                          boost=q.boost)

    raise dsl.QueryParseError(f"cannot compile query {type(q).__name__}")


def _span_host_node(query, field: Optional[str], ctx: ShardContext,
                    boost: float) -> LNode:
    """Evaluate a span/interval algebra tree host-side over every segment
    (search/spans.py) and wrap the per-segment frequency vectors in an
    LSpanHost scored on device. Evaluation is eager at rewrite so the
    pseudo-term weight (Σ idf over involved terms) is identical across
    segments (global statistics, like the DFS phase)."""
    from . import spans as SP

    # structural validation first: shape/field errors must surface even on
    # an empty index (data-independent, like the reference's parse phase);
    # span evaluation itself is LAZY per segment (prepare) so a multi-shard
    # coordinator doesn't evaluate every shard's segments once per shard
    if isinstance(query, tuple):
        f = query[1]
    else:
        f = SP.span_query_field(query, ctx) or field
    if f is None:
        return LMatchNone()
    terms_seen = SP.collect_terms(query, ctx)
    sim = ctx.sim_for(f)
    n = ctx.num_docs
    weight = 0.0
    for t in dict.fromkeys(terms_seen):
        df = ctx.doc_freq(f, t)
        if df > 0:
            weight += sim.term_weight(1.0, n, df)
    ft = ctx.mappings.resolve_field(f)
    has_norms = bool(ft is not None and ft.has_norms and sim.uses_norms)
    node = LSpanHost(field=f, query=query, weight=weight * boost,
                     boost=boost, has_norms=has_norms, sim=sim)
    node._freqs = {}
    return node


def _rewrite_mlt(q: dsl.MoreLikeThisQuery, ctx: ShardContext,
                 scoring: bool) -> LNode:
    """more_like_this (reference `index/query/MoreLikeThisQueryBuilder.java`,
    Lucene MoreLikeThis): gather term frequencies from the liked texts/docs,
    rank candidate terms by tf·idf, keep the top `max_query_terms`, and
    search them as a weighted OR (device term-group). Liked docs are excluded
    via must_not ids unless `include`."""
    fields = list(q.fields)
    if not fields:
        fields = [name for name, ft in ctx.mappings.fields.items()
                  if ft.type == "text"]
        if not fields:
            return LMatchNone()
    stop = set(q.stop_words)

    def texts_of(like_item, liked_ids):
        if isinstance(like_item, str):
            return {f: [like_item] for f in fields}
        # {"_id": ...} / {"doc": {...}} document reference
        if isinstance(like_item, dict):
            if "doc" in like_item:
                src = like_item["doc"]
            else:
                did = like_item.get("_id")
                if did is None:
                    raise dsl.QueryParseError(
                        "[more_like_this] like item needs text, [_id] or [doc]")
                liked_ids.append(str(did))
                src = None
                for seg in ctx.segments:
                    d = seg.id2doc.get(str(did))
                    if d is not None and seg.live[d]:
                        src = seg.sources[d]
                        break
                if src is None:
                    return {}
            out = {}
            for f in fields:
                v = src.get(f)
                if isinstance(v, str):
                    out[f] = [v]
                elif isinstance(v, list):
                    out[f] = [str(x) for x in v]
            return out
        raise dsl.QueryParseError("[more_like_this] invalid like item")

    liked_ids: List[str] = []
    tf_counts: Dict[Tuple[str, str], int] = {}
    for item in q.like:
        for f, texts in texts_of(item, liked_ids).items():
            for text in texts:
                for t in analyze_query_text(f, text, ctx):
                    tf_counts[(f, t)] = tf_counts.get((f, t), 0) + 1
    skip: set = set()
    for item in q.unlike:
        for f, texts in texts_of(item, []).items():
            for text in texts:
                for t in analyze_query_text(f, text, ctx):
                    skip.add((f, t))

    n = max(ctx.num_docs, 1)
    scored = []
    for (f, t), tf in tf_counts.items():
        if (f, t) in skip or t in stop or tf < q.min_term_freq:
            continue
        if len(t) < q.min_word_length:
            continue
        if q.max_word_length and len(t) > q.max_word_length:
            continue
        df = ctx.doc_freq(f, t)
        if df < q.min_doc_freq or df > q.max_doc_freq or df <= 0:
            continue
        idf = ops.bm25_idf(n, df)
        scored.append((tf * idf, f, t))
    scored.sort(key=lambda x: (-x[0], x[1], x[2]))
    scored = scored[: q.max_query_terms]
    if not scored:
        return LMatchNone()
    best = scored[0][0]
    by_field: Dict[str, List[Tuple[str, float]]] = {}
    for s, f, t in scored:
        boost = (q.boost_terms * s / best) if q.boost_terms > 0 else 1.0
        by_field.setdefault(f, []).append((t, boost))
    msm_total = dsl.parse_minimum_should_match(q.minimum_should_match,
                                               len(scored))
    mode = "score" if scoring else "filter"
    if len(by_field) == 1:
        ((f, pairs),) = by_field.items()
        node = weighted_terms(f, [t for t, _ in pairs],
                              [b for _, b in pairs], ctx,
                              msm=max(msm_total, 1), mode=mode,
                              boost=q.boost)
    else:
        # multi-field: one single-term group per clause so msm counts terms
        # across fields exactly like the reference boolean query
        shoulds = [
            weighted_terms(f, [t], [b], ctx, msm=1, mode=mode, boost=1.0)
            for f, pairs in by_field.items() for t, b in pairs]
        node = LBool(shoulds=shoulds, msm=max(msm_total, 1), boost=q.boost)
    if liked_ids and not q.include:
        return LBool(musts=[node], must_nots=[LIds(ids=liked_ids)],
                     boost=1.0)
    return node


def _rewrite_rank_feature(q: dsl.RankFeatureQuery, ctx: ShardContext) -> LNode:
    m = ctx.mappings
    ft = m.resolve_field(q.field)
    if ft is not None and ft.type == "rank_feature":
        field, feature, positive = ft.name, None, ft.positive_score_impact
    else:
        # "features.pagerank": longest mapped prefix typed rank_features
        parts = q.field.split(".")
        field = feature = None
        for cut in range(len(parts) - 1, 0, -1):
            pft = m.resolve_field(".".join(parts[:cut]))
            if pft is not None and pft.type in ("rank_features", "sparse_vector"):
                field, feature = pft.name, ".".join(parts[cut:])
                positive = pft.positive_score_impact
                break
        if field is None:
            raise dsl.QueryParseError(
                f"[rank_feature] field [{q.field}] is not a rank_feature or "
                f"rank_features feature")

    fn, p1, p2 = q.function, 1.0, 1.0
    if not positive and fn in ("log", "linear"):
        raise dsl.QueryParseError(
            f"[rank_feature] [{fn}] is incompatible with "
            f"positive_score_impact=false fields")
    if fn == "saturation":
        p1 = q.pivot if q.pivot is not None else _default_pivot(ctx, field, feature)
    elif fn == "log":
        p1 = float(q.scaling_factor)
    elif fn == "sigmoid":
        p1, p2 = float(q.pivot), float(q.exponent)
    return LRankFeature(field=field, feature=feature, fn=fn, p1=float(p1),
                        p2=float(p2), positive=positive, boost=q.boost)


def _default_pivot(ctx: ShardContext, field: str, feature: Optional[str]) -> float:
    """Default saturation pivot ≈ mean feature value over the index
    (reference computes an approximate geometric mean from the index stats)."""
    total, count = 0.0, 0
    for s in ctx.segments:
        if feature is None:
            col = s.numeric_cols.get(field)
            if col is not None and col.present.any():
                total += float(col.values[col.present].sum())
                count += int(col.present.sum())
        else:
            pb = s.postings.get(field)
            if pb is not None:
                r = pb.row(feature)
                if r >= 0:
                    a, b = pb.row_slice(r)
                    total += float(pb.tfs[a:b].sum())
                    count += b - a
    return (total / count) if count else 1.0


def _rewrite_join(q, ctx: ShardContext, scoring: bool) -> LNode:
    from .join import get_join_index

    m = ctx.mappings
    jf = m.join_field
    kind = {dsl.HasChildQuery: "has_child", dsl.HasParentQuery: "has_parent",
            dsl.ParentIdQuery: "parent_id"}[type(q)]
    relations = m.fields[jf].relations if jf else {}
    child_rels_all = {c for cs in relations.values() for c in cs}

    def unmapped(msg: str) -> LNode:
        if q.ignore_unmapped:
            return LMatchNone()
        raise dsl.QueryParseError(f"[{kind}] {msg}")

    if jf is None:
        return unmapped("no [join] field is mapped on this index")

    if kind == "parent_id":
        if q.type not in child_rels_all:
            return unmapped(f"[{q.type}] is not a child relation")
        inner = LBool(filters=[
            weighted_terms(f"{jf}#parent", [q.id], [1.0], ctx, 1, "filter", 1.0),
            weighted_terms(jf, [q.type], [1.0], ctx, 1, "filter", 1.0)])
        return LConstScore(child=inner, boost=q.boost)

    ji = get_join_index(ctx.segments, jf)
    if kind == "has_child":
        parent_rel = next((p for p, cs in relations.items() if q.type in cs), None)
        if parent_rel is None:
            return unmapped(f"[{q.type}] is not a child relation of the join field")
        inner = rewrite(q.query or dsl.MatchAllQuery(), ctx, scoring)
        child = LBool(musts=[inner], filters=[
            weighted_terms(jf, [q.type], [1.0], ctx, 1, "filter", 1.0)])
        pf = weighted_terms(jf, [parent_rel], [1.0], ctx, 1, "filter", 1.0)
        return LHasChild(join_field=jf, child_rel=q.type, child=child,
                         parent_filter=pf, score_mode=q.score_mode,
                         min_children=q.min_children, max_children=q.max_children,
                         boost=q.boost, join_index=ji)

    # has_parent
    if q.parent_type not in relations:
        return unmapped(f"[{q.parent_type}] is not a parent relation")
    inner = rewrite(q.query or dsl.MatchAllQuery(), ctx, scoring)
    parent_plan = LBool(musts=[inner], filters=[
        weighted_terms(jf, [q.parent_type], [1.0], ctx, 1, "filter", 1.0)])
    cf = weighted_terms(jf, sorted(relations[q.parent_type]),
                        [1.0] * len(relations[q.parent_type]), ctx, 1,
                        "filter", 1.0)
    return LHasParent(join_field=jf, parent_rel=q.parent_type, child=parent_plan,
                      child_filter=cf, use_score=q.score, boost=q.boost,
                      join_index=ji)


def nested_context(ctx: ShardContext, path: str) -> ShardContext:
    """Child-space statistics context: BM25 idf/avgdl over the nested path's
    child docs (Lucene computes stats over child Lucene docs the same way)."""
    child_segs = [s.nested[path].child for s in ctx.segments if path in s.nested]
    return ShardContext(ctx.mappings, child_segs,
                        similarity=ctx.default_sim,
                        field_similarities=ctx.field_sims,
                        device=ctx.device, cache_filters=False)


def _rewrite_query_string(q, ctx: ShardContext, scoring: bool) -> LNode:
    """Full Lucene query_string / lenient simple_query_string grammars
    (search/querystring.py) -> DSL tree -> this rewriter. The string
    grammar therefore compiles to exactly the same device plans as native
    JSON DSL."""
    from . import querystring as qsmod
    default_fields = q.fields or ([q.default_field] if getattr(q, "default_field", None)
                                  else ["*"])
    if list(default_fields) == ["*"]:
        default_fields = [f for f, ft in ctx.mappings.fields.items()
                          if ft.type in TEXT_TYPES]
        if not default_fields:
            default_fields = list(ctx.mappings.fields)[:1] or ["_all"]
    if isinstance(q, dsl.SimpleQueryStringQuery):
        tree = qsmod.parse_simple_query_string(q.query, list(default_fields),
                                               q.default_operator)
    else:
        tree = qsmod.parse_query_string(
            q.query, list(default_fields), q.default_operator,
            phrase_slop=int(getattr(q, "phrase_slop", 0) or 0))
    tree.boost = tree.boost * q.boost
    return rewrite(tree, ctx, scoring)


# ---------------- multi-term expanders (host, per segment vocab) ----------------

def prefix_expander(field: str, prefix: str, ci: bool, cap: Optional[int] = None):
    def expand(seg: Segment) -> np.ndarray:
        pb = seg.postings.get(field)
        if pb is None:
            return np.empty(0, np.int32)
        if ci:
            rows = [i for i, t in enumerate(pb.vocab) if t.lower().startswith(prefix.lower())]
            rows = rows[:cap] if cap is not None else rows
            return np.asarray(rows, np.int32)
        r = prefix_rows(pb, prefix, cap)
        return np.arange(r.start, r.stop, dtype=np.int32)
    return expand


def wildcard_expander(field: str, pattern: str, ci: bool):
    def expand(seg: Segment) -> np.ndarray:
        pb = seg.postings.get(field)
        if pb is None:
            return np.empty(0, np.int32)
        pat = pattern.lower() if ci else pattern
        rows = [i for i, t in enumerate(pb.vocab)
                if _fnmatch.fnmatchcase(t.lower() if ci else t, pat)]
        return np.asarray(rows, np.int32)
    return expand


def regexp_expander(field: str, pattern: str):
    """Full Lucene regexp syntax (search/regexp.py DFA engine, incl. ~ & @
    <m-n>); the whole term dictionary is matched in one vectorized DFA run
    over a cached per-(segment, field) codepoint matrix."""
    from .regexp import RegexpError, compile_regexp, match_vocab
    try:
        compile_regexp(pattern)   # validate once -> 400, not per segment
    except RegexpError as e:
        raise dsl.QueryParseError(f"[regexp] {e}")

    def expand(seg: Segment) -> np.ndarray:
        pb = seg.postings.get(field)
        if pb is None:
            return np.empty(0, np.int32)
        hits = match_vocab(pattern, pb.vocab, cache_key=(seg.uid, field))
        return np.nonzero(hits)[0].astype(np.int32)
    return expand


def _edit_distance_le(a: str, b: str, k: int) -> bool:
    """Optimal-string-alignment distance <= k (transpositions count 1, like
    Lucene FuzzyQuery's default transpositions=true)."""
    if abs(len(a) - len(b)) > k:
        return False
    prev2: Optional[list] = None
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i] + [0] * len(b)
        lo = len(b) + 1
        for j, cb in enumerate(b, 1):
            cur[j] = min(prev[j] + 1, cur[j - 1] + 1, prev[j - 1] + (ca != cb))
            if (prev2 is not None and i > 1 and j > 1
                    and ca == b[j - 2] and a[i - 2] == cb):
                cur[j] = min(cur[j], prev2[j - 2] + 1)
            lo = min(lo, cur[j])
        if lo > k:
            return False
        prev2, prev = prev, cur
    return prev[-1] <= k


def _auto_fuzz(term: str, fuzziness) -> int:
    if fuzziness in ("AUTO", "auto", None):
        # reference Fuzziness.AUTO: 0 for <3 chars, 1 for 3-5, 2 for >5
        return 0 if len(term) < 3 else (1 if len(term) <= 5 else 2)
    return int(fuzziness)


def fuzzy_expander(field: str, term: str, fuzziness, prefix_length: int):
    k = None
    def expand(seg: Segment) -> np.ndarray:
        nonlocal k
        if k is None:
            k = _auto_fuzz(term, fuzziness)
        pb = seg.postings.get(field)
        if pb is None:
            return np.empty(0, np.int32)
        pre = term[:prefix_length]
        rows = [i for i, t in enumerate(pb.vocab)
                if t.startswith(pre) and _edit_distance_le(t, term, k)]
        return np.asarray(rows, np.int32)
    return expand


def _keyword_range_expander(field: str, q: dsl.RangeQuery):
    def expand(seg: Segment) -> np.ndarray:
        pb = seg.postings.get(field)
        if pb is None:
            return np.empty(0, np.int32)
        lo = 0
        hi = len(pb.vocab)
        if q.gte is not None:
            lo = bisect_left(pb.vocab, str(q.gte))
        if q.gt is not None:
            lo = bisect_right(pb.vocab, str(q.gt))
        if q.lte is not None:
            hi = bisect_right(pb.vocab, str(q.lte))
        if q.lt is not None:
            hi = bisect_left(pb.vocab, str(q.lt))
        return np.arange(lo, max(hi, lo), dtype=np.int32)
    return expand
