"""Query DSL parsing: JSON dicts -> QueryBuilder tree. Analog of reference
`index/query/*QueryBuilder.java` fromXContent parsers (same DSL surface).

The tree is *unrewritten*: analysis, multi-term expansion, and idf weighting
happen in `plan.rewrite` (the analog of QueryBuilder.rewrite +
Query.createWeight, which need index statistics).
"""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import Any, Dict, List, Optional, Tuple


class QueryParseError(ValueError):
    """Analog of reference ParsingException (HTTP 400)."""


@dataclass
class Query:
    boost: float = 1.0
    name: Optional[str] = None  # _name for matched_queries


@dataclass
class MatchAllQuery(Query):
    pass


@dataclass
class MatchNoneQuery(Query):
    pass


@dataclass
class TermQuery(Query):
    field: str = ""
    value: Any = None
    case_insensitive: bool = False


@dataclass
class TermsQuery(Query):
    field: str = ""
    values: List[Any] = dc_field(default_factory=list)


@dataclass
class MatchQuery(Query):
    field: str = ""
    query: Any = None
    operator: str = "or"
    minimum_should_match: Optional[str] = None
    analyzer: Optional[str] = None
    fuzziness: Optional[Any] = None


@dataclass
class MultiMatchQuery(Query):
    fields: List[str] = dc_field(default_factory=list)
    query: Any = None
    type: str = "best_fields"
    operator: str = "or"
    tie_breaker: float = 0.0
    minimum_should_match: Optional[str] = None


@dataclass
class MatchPhraseQuery(Query):
    field: str = ""
    query: Any = None
    slop: int = 0
    analyzer: Optional[str] = None
    prefix: bool = False               # match_phrase_prefix
    max_expansions: int = 50


@dataclass
class SpanTermQuery(Query):
    field: str = ""
    value: str = ""


@dataclass
class SpanNearQuery(Query):
    clauses: List[Query] = dc_field(default_factory=list)
    slop: int = 0
    in_order: bool = True


@dataclass
class SpanOrQuery(Query):
    clauses: List[Query] = dc_field(default_factory=list)


@dataclass
class SpanNotQuery(Query):
    include: Optional[Query] = None
    exclude: Optional[Query] = None
    pre: int = 0
    post: int = 0


@dataclass
class SpanFirstQuery(Query):
    match: Optional[Query] = None
    end: int = 0


@dataclass
class SpanContainingQuery(Query):
    big: Optional[Query] = None
    little: Optional[Query] = None


@dataclass
class SpanWithinQuery(Query):
    big: Optional[Query] = None
    little: Optional[Query] = None


@dataclass
class SpanMultiQuery(Query):
    match: Optional[Query] = None      # prefix/wildcard/fuzzy/regexp


@dataclass
class FieldMaskingSpanQuery(Query):
    query: Optional[Query] = None
    field: str = ""                    # the masked-as field


@dataclass
class IntervalRule:
    """One node of the intervals source tree (reference
    IntervalsSourceProvider: match/prefix/wildcard/fuzzy/all_of/any_of with
    an optional filter)."""

    kind: str                          # match|prefix|wildcard|fuzzy|all_of|any_of
    query: str = ""
    max_gaps: int = -1
    ordered: bool = False
    analyzer: Optional[str] = None
    rules: List["IntervalRule"] = dc_field(default_factory=list)
    fuzziness: Any = "AUTO"
    prefix_length: int = 0
    filter_kind: Optional[str] = None  # containing|contained_by|not_containing|
    #                                    not_contained_by|not_overlapping|before|after
    filter_rule: Optional["IntervalRule"] = None


@dataclass
class IntervalsQuery(Query):
    field: str = ""
    rule: Optional[IntervalRule] = None
    # back-compat accessors for the old single-match form
    query: str = ""
    max_gaps: int = -1
    ordered: bool = False
    analyzer: Optional[str] = None


@dataclass
class BoolQuery(Query):
    must: List[Query] = dc_field(default_factory=list)
    should: List[Query] = dc_field(default_factory=list)
    must_not: List[Query] = dc_field(default_factory=list)
    filter: List[Query] = dc_field(default_factory=list)
    minimum_should_match: Optional[str] = None


@dataclass
class RangeQuery(Query):
    field: str = ""
    gte: Any = None
    gt: Any = None
    lte: Any = None
    lt: Any = None
    date_format: Optional[str] = None
    relation: str = "intersects"   # range-field targets (RangeFieldMapper)


@dataclass
class ExistsQuery(Query):
    field: str = ""


@dataclass
class IdsQuery(Query):
    values: List[str] = dc_field(default_factory=list)


@dataclass
class ConstantScoreQuery(Query):
    filter: Optional[Query] = None


@dataclass
class BoostingQuery(Query):
    positive: Optional[Query] = None
    negative: Optional[Query] = None
    negative_boost: float = 0.5


@dataclass
class DisMaxQuery(Query):
    queries: List[Query] = dc_field(default_factory=list)
    tie_breaker: float = 0.0


@dataclass
class PrefixQuery(Query):
    field: str = ""
    value: str = ""
    case_insensitive: bool = False


@dataclass
class WildcardQuery(Query):
    field: str = ""
    value: str = ""
    case_insensitive: bool = False


@dataclass
class RegexpQuery(Query):
    field: str = ""
    value: str = ""


@dataclass
class FuzzyQuery(Query):
    field: str = ""
    value: str = ""
    fuzziness: Any = "AUTO"
    prefix_length: int = 0


@dataclass
class QueryStringQuery(Query):
    query: str = ""
    default_field: Optional[str] = None
    fields: List[str] = dc_field(default_factory=list)
    default_operator: str = "or"
    phrase_slop: int = 0


@dataclass
class SimpleQueryStringQuery(Query):
    query: str = ""
    fields: List[str] = dc_field(default_factory=list)
    default_operator: str = "or"


@dataclass
class GeoDistanceQuery(Query):
    field: str = ""
    lat: float = 0.0
    lon: float = 0.0
    distance_m: float = 0.0
    # internal: strict < for agg-refinement ring boundaries ("_inclusive")
    inclusive: bool = True


@dataclass
class GeoBoundingBoxQuery(Query):
    field: str = ""
    top: float = 0.0
    left: float = 0.0
    bottom: float = 0.0
    right: float = 0.0


@dataclass
class TermsSetQuery(Query):
    """terms_set: per-DOC minimum_should_match from a numeric field or a
    script (reference TermsSetQueryBuilder.java)."""

    field: str = ""
    terms: List[Any] = dc_field(default_factory=list)
    minimum_should_match_field: Optional[str] = None
    minimum_should_match_script: Optional[Any] = None


@dataclass
class MatchBoolPrefixQuery(Query):
    field: str = ""
    query: Any = None
    operator: str = "or"
    analyzer: Optional[str] = None


@dataclass
class CombinedFieldsQuery(Query):
    """combined_fields: BM25F over weighted fields — combined tf/dl on
    device, union df for the idf (reference CombinedFieldsQueryBuilder)."""

    query: Any = None
    fields: List[str] = dc_field(default_factory=list)
    operator: str = "or"
    minimum_should_match: Optional[str] = None


@dataclass
class PinnedQuery(Query):
    ids: List[str] = dc_field(default_factory=list)
    organic: Optional[Query] = None


@dataclass
class GeoPolygonQuery(Query):
    field: str = ""
    # vertex lists, parallel (lat[i], lon[i])
    lats: List[float] = dc_field(default_factory=list)
    lons: List[float] = dc_field(default_factory=list)


@dataclass
class GeoShapeQuery(Query):
    field: str = ""
    shape: Any = None              # GeoJSON dict or WKT string
    relation: str = "intersects"   # intersects | disjoint | within | contains
    ignore_unmapped: bool = False


@dataclass
class ScoreFunction:
    kind: str                      # weight | field_value_factor | random_score | script_score | decay
    weight: float = 1.0
    filter: Optional[Query] = None
    field: Optional[str] = None
    factor: float = 1.0
    modifier: str = "none"
    missing: Optional[float] = None
    seed: int = 0
    script: Optional[str] = None   # painless-lite source
    script_params: Optional[dict] = None
    # decay (gauss | exp | linear) — reference functionscore/
    # GaussDecayFunctionBuilder.java / ExponentialDecayFunctionBuilder.java /
    # LinearDecayFunctionBuilder.java
    decay_shape: Optional[str] = None   # gauss | exp | linear
    origin: Any = None
    scale: Any = None
    offset: Any = None
    decay: float = 0.5


@dataclass
class MoreLikeThisQuery(Query):
    """Reference `index/query/MoreLikeThisQueryBuilder.java` (Lucene
    MoreLikeThis): select interesting terms from liked texts/docs by tf·idf,
    search as a weighted OR."""

    fields: List[str] = dc_field(default_factory=list)
    like: List[Any] = dc_field(default_factory=list)      # str | {"_id": ...}
    unlike: List[Any] = dc_field(default_factory=list)
    max_query_terms: int = 25
    min_term_freq: int = 2
    min_doc_freq: int = 5
    max_doc_freq: int = 2**31 - 1
    min_word_length: int = 0
    max_word_length: int = 0          # 0 = unbounded
    stop_words: List[str] = dc_field(default_factory=list)
    minimum_should_match: Optional[str] = "30%"
    boost_terms: float = 0.0
    include: bool = False


@dataclass
class FunctionScoreQuery(Query):
    query: Optional[Query] = None
    functions: List[ScoreFunction] = dc_field(default_factory=list)
    score_mode: str = "multiply"   # multiply | sum | avg | max | min | first
    boost_mode: str = "multiply"   # multiply | sum | replace | avg | max | min
    max_boost: float = 3.4e38
    min_score: Optional[float] = None


@dataclass
class ScriptQuery(Query):
    """`script` query: filter docs where the expression is truthy."""

    source: str = ""
    params: Optional[dict] = None


@dataclass
class ScriptScoreQuery(Query):
    """`script_score` query: replace the child's score with the script's."""

    query: Optional[Query] = None
    source: str = ""
    params: Optional[dict] = None
    min_score: Optional[float] = None


@dataclass
class KnnQuery(Query):
    field: str = ""
    vector: List[float] = dc_field(default_factory=list)
    k: int = 10
    filter: Optional[Query] = None
    # ANN overrides (reference k-NN query `method_parameters`): nprobe
    # widens/narrows the IVF probe; exact=True forces the brute-force scan
    nprobe: Optional[int] = None
    exact: bool = False


@dataclass
class NestedQuery(Query):
    path: str = ""
    query: Optional[Query] = None
    score_mode: str = "avg"   # avg | sum | max | min | none
    ignore_unmapped: bool = False
    inner_hits: Optional[dict] = None


@dataclass
class HasChildQuery(Query):
    """Parents with matching children (reference modules/parent-join
    HasChildQueryBuilder)."""

    type: str = ""
    query: Optional[Query] = None
    score_mode: str = "none"  # none | min | max | sum | avg
    min_children: int = 1
    max_children: int = 2**31 - 1
    ignore_unmapped: bool = False
    inner_hits: Optional[dict] = None


@dataclass
class HasParentQuery(Query):
    """Children whose parent matches (reference HasParentQueryBuilder)."""

    parent_type: str = ""
    query: Optional[Query] = None
    score: bool = False
    ignore_unmapped: bool = False
    inner_hits: Optional[dict] = None


@dataclass
class RankFeatureQuery(Query):
    """Score docs by a rank_feature(s) value through one of four monotone
    functions (reference mapper-extras RankFeatureQueryBuilder)."""

    field: str = ""
    function: str = "saturation"   # saturation | log | sigmoid | linear
    pivot: Optional[float] = None  # saturation/sigmoid
    scaling_factor: Optional[float] = None  # log
    exponent: Optional[float] = None        # sigmoid


@dataclass
class DistanceFeatureQuery(Query):
    """Decaying proximity score on date/geo fields:
    boost * pivot / (pivot + distance) (reference DistanceFeatureQueryBuilder)."""

    field: str = ""
    origin: Any = None
    pivot: Any = None


@dataclass
class NeuralSparseQuery(Query):
    """Learned-sparse dot product over a rank_features/sparse_vector field
    (reference neural-search plugin neural_sparse, raw query_tokens mode —
    model inference happens outside the engine)."""

    field: str = ""
    tokens: Dict[str, float] = dc_field(default_factory=dict)


@dataclass
class HybridQuery(Query):
    """Top-level hybrid retrieval (reference neural-search plugin
    HybridQueryBuilder): N independent sub-queries — lexical,
    `neural_sparse`, `knn` — each executed as its own per-shard retrieval
    in its own score domain, fused at the coordinator merge
    (search/fusion.py) with RRF or normalized linear combination.
    Sub-queries stay RAW dicts: each one is re-parsed and served through
    the full serving ladder exactly as if it were the only query."""

    queries: List[dict] = dc_field(default_factory=list)
    # validated fusion parameters (method, rank_constant, weights,
    # normalization, window_size) — see fusion.FusionSpec
    fusion: Dict[str, Any] = dc_field(default_factory=dict)


@dataclass
class PercolateQuery(Query):
    """Match stored percolator queries against candidate document(s)
    (reference modules/percolator PercolateQueryBuilder)."""

    field: str = ""
    documents: List[dict] = dc_field(default_factory=list)
    # reference to an existing doc (resolved by the REST layer before parse)
    index: Optional[str] = None
    id: Optional[str] = None
    routing: Optional[str] = None


@dataclass
class ParentIdQuery(Query):
    """Children of one specific parent id (reference ParentIdQueryBuilder)."""

    type: str = ""
    id: str = ""
    ignore_unmapped: bool = False


def _one_entry(d: dict, what: str) -> Tuple[str, Any]:
    if not isinstance(d, dict) or len(d) != 1:
        raise QueryParseError(f"[{what}] malformed query, expected a single field object")
    return next(iter(d.items()))


def _common(q: Query, body: Any) -> None:
    if isinstance(body, dict):
        q.boost = float(body.get("boost", 1.0))
        q.name = body.get("_name")


def parse_query(dsl: Optional[dict]) -> Query:
    """DSL dict -> Query tree (reference: SearchModule registered parsers)."""
    if dsl is None:
        return MatchAllQuery()
    kind, body = _one_entry(dsl, "query")

    if kind == "match_all":
        q = MatchAllQuery(); _common(q, body); return q
    if kind == "match_none":
        q = MatchNoneQuery(); _common(q, body); return q

    if kind == "term":
        f, spec = _one_entry(body, "term")
        if isinstance(spec, dict):
            q = TermQuery(field=f, value=spec.get("value"),
                          case_insensitive=spec.get("case_insensitive", False))
            _common(q, spec)
        else:
            q = TermQuery(field=f, value=spec)
        return q

    if kind == "terms":
        opts = {k: v for k, v in body.items() if k in ("boost", "_name")}
        fields = [(k, v) for k, v in body.items() if k not in ("boost", "_name")]
        if len(fields) != 1:
            raise QueryParseError("[terms] query requires exactly one field")
        f, vals = fields[0]
        q = TermsQuery(field=f, values=list(vals))
        _common(q, opts)
        return q

    if kind == "match":
        f, spec = _one_entry(body, "match")
        if isinstance(spec, dict):
            q = MatchQuery(field=f, query=spec.get("query"),
                           operator=str(spec.get("operator", "or")).lower(),
                           minimum_should_match=spec.get("minimum_should_match"),
                           analyzer=spec.get("analyzer"),
                           fuzziness=spec.get("fuzziness"))
            _common(q, spec)
        else:
            q = MatchQuery(field=f, query=spec)
        return q

    if kind == "multi_match":
        q = MultiMatchQuery(fields=list(body.get("fields", [])), query=body.get("query"),
                            type=body.get("type", "best_fields"),
                            operator=str(body.get("operator", "or")).lower(),
                            tie_breaker=float(body.get("tie_breaker", 0.0)),
                            minimum_should_match=body.get("minimum_should_match"))
        _common(q, body)
        return q

    if kind in ("match_phrase", "match_phrase_prefix"):
        f, spec = _one_entry(body, kind)
        prefix = kind == "match_phrase_prefix"
        if isinstance(spec, dict):
            q = MatchPhraseQuery(field=f, query=spec.get("query"),
                                 slop=int(spec.get("slop", 0)), analyzer=spec.get("analyzer"),
                                 prefix=prefix,
                                 max_expansions=int(spec.get("max_expansions", 50)))
            _common(q, spec)
        else:
            q = MatchPhraseQuery(field=f, query=spec, prefix=prefix)
        return q

    if kind == "terms_set":
        f, spec = _one_entry(body, "terms_set")
        if not isinstance(spec, dict) or "terms" not in spec:
            raise QueryParseError("[terms_set] requires [terms]")
        msf = spec.get("minimum_should_match_field")
        mss = spec.get("minimum_should_match_script")
        if msf is None and mss is None:
            raise QueryParseError(
                "[terms_set] requires [minimum_should_match_field] or "
                "[minimum_should_match_script]")
        q = TermsSetQuery(field=f, terms=list(spec["terms"]),
                          minimum_should_match_field=msf,
                          minimum_should_match_script=mss)
        _common(q, spec)
        return q

    if kind == "match_bool_prefix":
        f, spec = _one_entry(body, "match_bool_prefix")
        if isinstance(spec, dict):
            q = MatchBoolPrefixQuery(field=f, query=spec.get("query"),
                                     operator=str(spec.get("operator",
                                                           "or")).lower(),
                                     analyzer=spec.get("analyzer"))
            _common(q, spec)
        else:
            q = MatchBoolPrefixQuery(field=f, query=spec)
        return q

    if kind == "combined_fields":
        q = CombinedFieldsQuery(query=body.get("query"),
                                fields=list(body.get("fields", [])),
                                operator=str(body.get("operator",
                                                      "or")).lower(),
                                minimum_should_match=body.get(
                                    "minimum_should_match"))
        if not q.fields:
            raise QueryParseError("[combined_fields] requires [fields]")
        _common(q, body)
        return q

    if kind == "wrapper":
        import base64
        import json as _json
        try:
            inner = _json.loads(base64.b64decode(body["query"]))
        except Exception as e:
            raise QueryParseError(f"[wrapper] cannot decode query: {e}")
        return parse_query(inner)

    if kind == "pinned":
        organic = body.get("organic")
        q = PinnedQuery(ids=[str(i) for i in body.get("ids", [])],
                        organic=parse_query(organic) if organic else None)
        _common(q, body)
        return q

    if kind == "span_term":
        f, spec = _one_entry(body, "span_term")
        if isinstance(spec, dict):
            q = SpanTermQuery(field=f, value=str(spec.get("value")))
            _common(q, spec)
        else:
            q = SpanTermQuery(field=f, value=str(spec))
        return q

    if kind == "span_near":
        q = SpanNearQuery(clauses=[parse_query(c) for c in body.get("clauses", [])],
                          slop=int(body.get("slop", 0)),
                          in_order=bool(body.get("in_order", True)))
        _common(q, body)
        return q

    if kind == "span_or":
        q = SpanOrQuery(clauses=[parse_query(c)
                                 for c in body.get("clauses", [])])
        _common(q, body)
        return q

    if kind == "span_not":
        dist = int(body.get("dist", 0))
        if "include" not in body or "exclude" not in body:
            raise QueryParseError("[span_not] requires [include] and [exclude]")
        q = SpanNotQuery(include=parse_query(body["include"]),
                         exclude=parse_query(body["exclude"]),
                         pre=int(body.get("pre", dist)),
                         post=int(body.get("post", dist)))
        _common(q, body)
        return q

    if kind == "span_first":
        if "end" not in body or "match" not in body:
            raise QueryParseError("[span_first] requires [match] and [end]")
        q = SpanFirstQuery(match=parse_query(body["match"]),
                           end=int(body["end"]))
        _common(q, body)
        return q

    if kind == "span_containing":
        if "big" not in body or "little" not in body:
            raise QueryParseError(
                "[span_containing] requires [big] and [little]")
        q = SpanContainingQuery(big=parse_query(body["big"]),
                                little=parse_query(body["little"]))
        _common(q, body)
        return q

    if kind == "span_within":
        if "big" not in body or "little" not in body:
            raise QueryParseError("[span_within] requires [big] and [little]")
        q = SpanWithinQuery(big=parse_query(body["big"]),
                            little=parse_query(body["little"]))
        _common(q, body)
        return q

    if kind == "span_multi":
        if "match" not in body:
            raise QueryParseError("[span_multi] requires [match]")
        q = SpanMultiQuery(match=parse_query(body["match"]))
        _common(q, body)
        return q

    if kind == "field_masking_span":
        if "query" not in body:
            raise QueryParseError("[field_masking_span] requires [query]")
        q = FieldMaskingSpanQuery(query=parse_query(body["query"]),
                                  field=body.get("field", ""))
        _common(q, body)
        return q

    if kind == "intervals":
        f, spec = _one_entry(body, "intervals")
        if not isinstance(spec, dict):
            raise QueryParseError("[intervals] needs a rule object")
        rule = parse_interval_rule(spec)
        q = IntervalsQuery(field=f, rule=rule)
        _common(q, spec)
        return q

    if kind == "bool":
        def many(key):
            v = body.get(key, [])
            v = v if isinstance(v, list) else [v]
            return [parse_query(x) for x in v]
        q = BoolQuery(must=many("must"), should=many("should"),
                      must_not=many("must_not"), filter=many("filter"),
                      minimum_should_match=body.get("minimum_should_match"))
        _common(q, body)
        return q

    if kind == "range":
        f, spec = _one_entry(body, "range")
        q = RangeQuery(field=f, gte=spec.get("gte", spec.get("from")),
                       gt=spec.get("gt"), lte=spec.get("lte", spec.get("to")),
                       lt=spec.get("lt"), date_format=spec.get("format"),
                       relation=str(spec.get("relation",
                                             "intersects")).lower())
        _common(q, spec)
        return q

    if kind == "exists":
        q = ExistsQuery(field=body["field"]); _common(q, body); return q

    if kind == "ids":
        q = IdsQuery(values=list(body.get("values", []))); _common(q, body); return q

    if kind == "constant_score":
        q = ConstantScoreQuery(filter=parse_query(body["filter"]))
        _common(q, body)
        return q

    if kind == "boosting":
        q = BoostingQuery(positive=parse_query(body["positive"]),
                          negative=parse_query(body["negative"]),
                          negative_boost=float(body.get("negative_boost", 0.5)))
        _common(q, body)
        return q

    if kind == "dis_max":
        q = DisMaxQuery(queries=[parse_query(x) for x in body.get("queries", [])],
                        tie_breaker=float(body.get("tie_breaker", 0.0)))
        _common(q, body)
        return q

    if kind in ("prefix", "wildcard", "regexp", "fuzzy"):
        f, spec = _one_entry(body, kind)
        if isinstance(spec, dict):
            value = spec.get("value", spec.get(kind))
            ci = spec.get("case_insensitive", False)
        else:
            value, ci, spec = spec, False, {}
        if kind == "prefix":
            q = PrefixQuery(field=f, value=str(value), case_insensitive=ci)
        elif kind == "wildcard":
            q = WildcardQuery(field=f, value=str(value), case_insensitive=ci)
        elif kind == "regexp":
            q = RegexpQuery(field=f, value=str(value))
        else:
            q = FuzzyQuery(field=f, value=str(value),
                           fuzziness=spec.get("fuzziness", "AUTO"),
                           prefix_length=int(spec.get("prefix_length", 0)))
        _common(q, spec)
        return q

    if kind == "query_string":
        q = QueryStringQuery(query=body["query"], default_field=body.get("default_field"),
                             fields=list(body.get("fields", [])),
                             default_operator=str(body.get("default_operator", "or")).lower(),
                             phrase_slop=int(body.get("phrase_slop", 0)))
        _common(q, body)
        return q

    if kind == "simple_query_string":
        q = SimpleQueryStringQuery(query=body["query"], fields=list(body.get("fields", [])),
                                   default_operator=str(body.get("default_operator", "or")).lower())
        _common(q, body)
        return q

    if kind == "geo_distance":
        dist = _parse_distance(body["distance"])
        fields = [(k, v) for k, v in body.items()
                  if k not in ("distance", "boost", "_name",
                               "validation_method", "_inclusive")]
        f, point = fields[0]
        lat, lon = _parse_point(point)
        q = GeoDistanceQuery(field=f, lat=lat, lon=lon, distance_m=dist,
                             inclusive=bool(body.get("_inclusive", True)))
        _common(q, body)
        return q

    if kind == "geo_bounding_box":
        fields = [(k, v) for k, v in body.items() if k not in ("boost", "_name", "validation_method")]
        f, box = fields[0]
        tl = box.get("top_left")
        br = box.get("bottom_right")
        if tl is not None:
            tlat, tlon = _parse_point(tl)
            blat, blon = _parse_point(br)
        else:
            tlat, tlon, blat, blon = box["top"], box["left"], box["bottom"], box["right"]
        q = GeoBoundingBoxQuery(field=f, top=tlat, left=tlon, bottom=blat, right=blon)
        _common(q, body)
        return q

    if kind == "geo_polygon":
        fields = [(k, v) for k, v in body.items()
                  if k not in ("boost", "_name", "validation_method")]
        if not fields or not isinstance(fields[0][1], dict):
            raise QueryParseError("[geo_polygon] requires a field with "
                                  "a [points] object")
        f, spec = fields[0]
        pts = [_parse_point(p) for p in spec.get("points", [])]
        if len(pts) < 3:
            raise QueryParseError(
                "[geo_polygon] requires at least 3 points")
        q = GeoPolygonQuery(field=f, lats=[p[0] for p in pts],
                            lons=[p[1] for p in pts])
        _common(q, body)
        return q

    if kind == "geo_shape":
        fields = [(k, v) for k, v in body.items()
                  if k not in ("boost", "_name", "ignore_unmapped")]
        if not fields:
            raise QueryParseError("[geo_shape] requires a field")
        f, spec = fields[0]
        shape = spec.get("shape", spec.get("indexed_shape"))
        if shape is None:
            raise QueryParseError(
                "[geo_shape] requires [shape] (or a resolved [indexed_shape])")
        rel = str(spec.get("relation", "intersects")).lower()
        if rel not in ("intersects", "disjoint", "within", "contains"):
            raise QueryParseError(f"[geo_shape] unknown relation [{rel}]")
        q = GeoShapeQuery(field=f, shape=shape, relation=rel,
                          ignore_unmapped=bool(body.get("ignore_unmapped",
                                                        False)))
        _common(q, body)
        return q

    if kind == "more_like_this":
        like = body.get("like", [])
        like = like if isinstance(like, list) else [like]
        unlike = body.get("unlike", [])
        unlike = unlike if isinstance(unlike, list) else [unlike]
        if not like:
            raise QueryParseError("[more_like_this] requires [like]")
        q = MoreLikeThisQuery(
            fields=list(body.get("fields", [])), like=like, unlike=unlike,
            max_query_terms=int(body.get("max_query_terms", 25)),
            min_term_freq=int(body.get("min_term_freq", 2)),
            min_doc_freq=int(body.get("min_doc_freq", 5)),
            max_doc_freq=int(body.get("max_doc_freq", 2**31 - 1)),
            min_word_length=int(body.get("min_word_length", 0)),
            max_word_length=int(body.get("max_word_length", 0)),
            stop_words=list(body.get("stop_words", [])),
            minimum_should_match=body.get("minimum_should_match", "30%"),
            boost_terms=float(body.get("boost_terms", 0.0)),
            include=bool(body.get("include", False)))
        _common(q, body)
        return q

    if kind == "function_score":
        inner = parse_query(body.get("query")) if body.get("query") else MatchAllQuery()
        functions = []
        raw_fns = body.get("functions", [])
        if not raw_fns:  # single-function shorthand
            raw_fns = [{k: v for k, v in body.items()
                        if k in ("weight", "field_value_factor", "random_score",
                                 "script_score", "gauss", "exp", "linear")}]
        for fn in raw_fns:
            filt = parse_query(fn["filter"]) if "filter" in fn else None
            shape = next((s for s in ("gauss", "exp", "linear") if s in fn), None)
            if shape is not None:
                spec = dict(fn[shape])
                spec.pop("multi_value_mode", None)
                if len(spec) != 1:
                    raise QueryParseError(
                        f"[{shape}] decay needs exactly one field")
                dfield, dspec = next(iter(spec.items()))
                if "scale" not in dspec:
                    raise QueryParseError(f"[{shape}] requires [scale]")
                functions.append(ScoreFunction(
                    "decay", fn.get("weight", 1.0), filt, dfield,
                    decay_shape=shape, origin=dspec.get("origin"),
                    scale=dspec["scale"], offset=dspec.get("offset", 0),
                    decay=float(dspec.get("decay", 0.5))))
            elif "field_value_factor" in fn:
                fv = fn["field_value_factor"]
                functions.append(ScoreFunction("field_value_factor", fn.get("weight", 1.0),
                                               filt, fv["field"], fv.get("factor", 1.0),
                                               fv.get("modifier", "none"), fv.get("missing")))
            elif "random_score" in fn:
                functions.append(ScoreFunction("random_score", fn.get("weight", 1.0), filt,
                                               seed=int(fn["random_score"].get("seed", 0))))
            elif "script_score" in fn:
                src, prm = parse_script_spec(fn["script_score"].get("script"))
                functions.append(ScoreFunction("script_score", fn.get("weight", 1.0),
                                               filt, script=src, script_params=prm))
            elif "weight" in fn:
                functions.append(ScoreFunction("weight", float(fn["weight"]), filt))
        q = FunctionScoreQuery(query=inner, functions=functions,
                               score_mode=body.get("score_mode", "multiply"),
                               boost_mode=body.get("boost_mode", "multiply"),
                               min_score=body.get("min_score"))
        _common(q, body)
        return q

    if kind == "script":
        src, prm = parse_script_spec(body.get("script"))
        q = ScriptQuery(source=src, params=prm)
        _common(q, body)
        return q

    if kind == "script_score":
        src, prm = parse_script_spec(body.get("script"))
        q = ScriptScoreQuery(query=parse_query(body.get("query")), source=src,
                             params=prm, min_score=body.get("min_score"))
        _common(q, body)
        return q

    if kind == "knn":
        # OpenSearch k-NN plugin form: {"knn": {"fieldname": {"vector": [...],
        # "k": 10, "filter": {...}}}}
        f, spec = _one_entry(body, "knn")
        mp = spec.get("method_parameters", {})
        nprobe = mp.get("nprobe", spec.get("nprobe"))
        q = KnnQuery(field=f, vector=list(spec["vector"]),
                     k=int(spec.get("k", 10)),
                     filter=parse_query(spec["filter"]) if spec.get("filter") else None,
                     nprobe=int(nprobe) if nprobe is not None else None,
                     exact=bool(spec.get("exact", False)))
        _common(q, spec)
        return q

    if kind == "nested":
        q = NestedQuery(path=body["path"], query=parse_query(body["query"]),
                        score_mode=body.get("score_mode", "avg"),
                        ignore_unmapped=bool(body.get("ignore_unmapped", False)),
                        inner_hits=body.get("inner_hits"))
        _common(q, body)
        return q

    if kind == "has_child":
        if body.get("score_mode", "none") not in ("none", "min", "max", "sum", "avg"):
            raise QueryParseError(
                f"[has_child] unknown score_mode [{body['score_mode']}]")
        q = HasChildQuery(type=body["type"], query=parse_query(body["query"]),
                          score_mode=body.get("score_mode", "none"),
                          min_children=int(body.get("min_children", 1)),
                          max_children=int(body.get("max_children", 2**31 - 1)),
                          ignore_unmapped=bool(body.get("ignore_unmapped", False)),
                          inner_hits=body.get("inner_hits"))
        _common(q, body)
        return q

    if kind == "has_parent":
        q = HasParentQuery(parent_type=body["parent_type"],
                           query=parse_query(body["query"]),
                           score=bool(body.get("score", False)),
                           ignore_unmapped=bool(body.get("ignore_unmapped", False)),
                           inner_hits=body.get("inner_hits"))
        _common(q, body)
        return q

    if kind == "parent_id":
        q = ParentIdQuery(type=body["type"], id=str(body["id"]),
                          ignore_unmapped=bool(body.get("ignore_unmapped", False)))
        _common(q, body)
        return q

    if kind == "rank_feature":
        fns = [k for k in ("saturation", "log", "sigmoid", "linear") if k in body]
        if len(fns) > 1:
            raise QueryParseError("[rank_feature] accepts at most one function")
        fn = fns[0] if fns else "saturation"
        spec = body.get(fn) or {}
        if fn == "log" and "scaling_factor" not in spec:
            raise QueryParseError("[rank_feature] [log] requires scaling_factor")
        if fn == "sigmoid" and ("pivot" not in spec or "exponent" not in spec):
            raise QueryParseError("[rank_feature] [sigmoid] requires pivot and exponent")
        q = RankFeatureQuery(field=body["field"], function=fn,
                             pivot=spec.get("pivot"),
                             scaling_factor=spec.get("scaling_factor"),
                             exponent=spec.get("exponent"))
        _common(q, body)
        return q

    if kind == "distance_feature":
        if body.get("origin") is None or body.get("pivot") is None:
            raise QueryParseError("[distance_feature] requires origin and pivot")
        q = DistanceFeatureQuery(field=body["field"], origin=body["origin"],
                                 pivot=body["pivot"])
        _common(q, body)
        return q

    if kind == "neural_sparse":
        f, spec = _one_entry(body, "neural_sparse")
        tokens = spec.get("query_tokens")
        if not isinstance(tokens, dict) or not tokens:
            raise QueryParseError(
                "[neural_sparse] requires query_tokens (raw token weights; "
                "model inference is out of engine scope)")
        q = NeuralSparseQuery(field=f,
                              tokens={str(t): float(w) for t, w in tokens.items()})
        _common(q, spec)
        return q

    if kind == "hybrid":
        subs = body.get("queries")
        if not isinstance(subs, list) or not subs:
            raise QueryParseError("[hybrid] requires a non-empty [queries] "
                                  "list")
        if len(subs) > MAX_HYBRID_SUB_QUERIES:
            raise QueryParseError(
                f"[hybrid] supports at most {MAX_HYBRID_SUB_QUERIES} "
                f"sub-queries, got {len(subs)}")
        for sub in subs:
            if not isinstance(sub, dict):
                raise QueryParseError("[hybrid] sub-queries must be query "
                                      "objects")
            inner = parse_query(sub)   # surface malformed subs as 400s now
            if isinstance(inner, HybridQuery):
                raise QueryParseError("[hybrid] queries cannot nest")
        q = HybridQuery(queries=[dict(s) for s in subs],
                        fusion=parse_fusion_spec(body.get("fusion"),
                                                 len(subs)))
        _common(q, body)
        return q

    if kind == "percolate":
        docs = body.get("documents")
        if docs is None and body.get("document") is not None:
            docs = [body["document"]]
        if docs is None and body.get("index") is None:
            raise QueryParseError(
                "[percolate] requires `document`, `documents`, or `index`+`id`")
        q = PercolateQuery(field=body["field"], documents=list(docs or []),
                           index=body.get("index"), id=body.get("id"),
                           routing=body.get("routing"))
        _common(q, body)
        return q

    raise QueryParseError(f"unknown query [{kind}]")


# reference neural-search HybridQueryBuilder caps sub-queries at 5
MAX_HYBRID_SUB_QUERIES = 5

_FUSION_METHODS = ("rrf", "linear")
_FUSION_NORMS = ("min_max", "l2")
# fused pages must be stable under pagination: the fused list is computed
# over fixed-depth per-sub-query rank windows, so `from`/`size` page INTO
# one deterministic list instead of re-fusing a different window per page
DEFAULT_FUSION_WINDOW = 100


def parse_fusion_spec(spec, n_sub: int) -> Dict[str, Any]:
    """Validate the [hybrid] fusion parameters -> canonical dict.

    - method: "rrf" (default) | "linear"
    - rank_constant: RRF k (default 60, >= 1)
    - weights: per-sub-query weights (default all 1.0, non-negative)
    - normalization: "min_max" (default) | "l2" — linear only; RRF fuses
      in the rank domain, which is score-domain-free by construction
    - window_size: per-sub-query rank-list depth the fusion sees
      (default 100); `from + size` beyond it is a 400, never a silent
      re-fusion at a different depth
    """
    spec = dict(spec or {})
    method = str(spec.get("method", "rrf")).lower()
    if method not in _FUSION_METHODS:
        raise QueryParseError(
            f"[hybrid] unknown fusion method [{method}] "
            f"(supported: {', '.join(_FUSION_METHODS)})")
    norm = str(spec.get("normalization", "min_max")).lower()
    if norm not in _FUSION_NORMS:
        # raw sub-query scores live in incomparable similarity domains
        # (BM25 vs cosine vs learned-sparse dot); a linear combination
        # without a normalizer is meaningless — refuse it (OSL604)
        raise QueryParseError(
            f"[hybrid] unknown normalization [{norm}] "
            f"(supported: {', '.join(_FUSION_NORMS)})")
    try:
        rank_constant = float(spec.get("rank_constant", 60))
        window = int(spec.get("window_size", DEFAULT_FUSION_WINDOW))
        weights = [float(w) for w in spec.get("weights",
                                              [1.0] * n_sub)]
    except (TypeError, ValueError) as e:
        raise QueryParseError(f"[hybrid] malformed fusion spec: {e}")
    if rank_constant < 1:
        raise QueryParseError("[hybrid] rank_constant must be >= 1")
    if window < 1:
        raise QueryParseError("[hybrid] window_size must be >= 1")
    if len(weights) != n_sub:
        raise QueryParseError(
            f"[hybrid] weights length [{len(weights)}] must match the "
            f"sub-query count [{n_sub}]")
    if any(w < 0 or w != w for w in weights):
        raise QueryParseError("[hybrid] weights must be finite and "
                              "non-negative")
    return {"method": method, "rank_constant": rank_constant,
            "weights": weights, "normalization": norm,
            "window_size": window}


def parse_script_spec(spec) -> Tuple[str, dict]:
    """{"source": ..., "params": ...} | "inline src" -> (source, params)
    (reference Script.parse; `lang` is accepted and ignored — painless-lite
    is the only engine)."""
    if spec is None:
        raise QueryParseError("missing required [script]")
    if isinstance(spec, str):
        return spec, {}
    if isinstance(spec, dict):
        src = spec.get("source", spec.get("inline"))
        if not isinstance(src, str):
            raise QueryParseError("script requires a [source] string")
        return src, dict(spec.get("params") or {})
    raise QueryParseError("malformed [script]")


def _parse_distance(d) -> float:
    """'5km', '100m', '2mi' -> meters (reference DistanceUnit). Longest
    suffix wins ('5nmi' is nautical miles, not '5n' miles)."""
    if isinstance(d, (int, float)):
        return float(d)
    s = str(d).strip().lower()
    units = [("nauticalmiles", 1852.0), ("kilometers", 1000.0),
             ("meters", 1.0), ("miles", 1609.344), ("nmi", 1852.0),
             ("km", 1000.0), ("mi", 1609.344), ("yd", 0.9144),
             ("ft", 0.3048), ("in", 0.0254), ("mm", 0.001), ("cm", 0.01),
             ("m", 1.0)]
    for suf, mult in units:
        if s.endswith(suf):
            return float(s[: -len(suf)]) * mult
    return float(s)


def _parse_point(p) -> Tuple[float, float]:
    if isinstance(p, dict):
        return float(p["lat"]), float(p["lon"])
    if isinstance(p, str):
        lat, lon = p.split(",")
        return float(lat), float(lon)
    return float(p[1]), float(p[0])  # GeoJSON [lon, lat]


_INTERVAL_FILTERS = ("containing", "contained_by", "not_containing",
                     "not_contained_by", "not_overlapping", "before", "after")


def parse_interval_rule(spec: dict) -> IntervalRule:
    """Parse one intervals source node (reference IntervalsSourceProvider)."""
    kinds = [k for k in spec if k in ("match", "prefix", "wildcard", "fuzzy",
                                      "all_of", "any_of")]
    if len(kinds) != 1:
        raise QueryParseError(
            "[intervals] rule must define exactly one of "
            "[match|prefix|wildcard|fuzzy|all_of|any_of]")
    kind = kinds[0]
    body = spec[kind]
    if not isinstance(body, dict):
        body = {"query": body}
    rule = IntervalRule(kind=kind)
    if kind in ("match", "prefix", "wildcard", "fuzzy"):
        rule.query = str(body.get("query", body.get(kind, body.get(
            "prefix" if kind == "prefix" else "pattern", ""))))
        rule.analyzer = body.get("analyzer")
        rule.max_gaps = int(body.get("max_gaps", -1))
        rule.ordered = bool(body.get("ordered", False))
        if kind == "fuzzy":
            rule.query = str(body.get("term", body.get("query", "")))
            rule.fuzziness = body.get("fuzziness", "AUTO")
            rule.prefix_length = int(body.get("prefix_length", 0))
    else:
        rule.max_gaps = int(body.get("max_gaps", -1))
        rule.ordered = bool(body.get("ordered", False))
        rule.rules = [parse_interval_rule(r) for r in body.get("intervals", [])]
        if not rule.rules:
            raise QueryParseError(f"[intervals] [{kind}] needs [intervals]")
    filt = body.get("filter")
    if filt:
        fk = [k for k in filt if k in _INTERVAL_FILTERS]
        if len(fk) != 1:
            raise QueryParseError(
                f"[intervals] filter must be one of {_INTERVAL_FILTERS}")
        rule.filter_kind = fk[0]
        rule.filter_rule = parse_interval_rule(filt[fk[0]])
    return rule


def parse_minimum_should_match(spec: Optional[str], n_optional: int) -> int:
    """'2', '-1', '75%', '-25%', and conditional '3<90%' / multi
    '2<-25% 9<-3' semantics (reference Queries.calculateMinShouldMatch)."""
    if spec is None or n_optional == 0:
        return 0 if spec is None else 0
    s = str(spec).strip()
    if "<" in s:
        # each "n<rule": when n_optional > n, apply rule; pick the clause
        # with the LARGEST matching n (Lucene applies them in order)
        result = n_optional  # fewer than every threshold -> all required
        best_n = -1
        for part in s.split():
            if "<" not in part:
                raise QueryParseError(f"invalid minimum_should_match [{spec}]")
            left, right = part.split("<", 1)
            try:
                thr = int(left)
            except ValueError:
                raise QueryParseError(f"invalid minimum_should_match [{spec}]")
            if n_optional > thr and thr > best_n:
                best_n = thr
                result = parse_minimum_should_match(right, n_optional)
        return result
    try:
        if s.endswith("%"):
            pct = float(s[:-1])
            if pct < 0:
                return max(n_optional - int(-pct / 100.0 * n_optional), 0)
            return int(pct / 100.0 * n_optional)
        v = int(s)
        if v < 0:
            return max(n_optional + v, 0)
        return min(v, n_optional)
    except ValueError:
        raise QueryParseError(f"invalid minimum_should_match [{spec}]")
