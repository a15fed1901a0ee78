"""Query compiler: DSL tree -> logical plan -> jitted device program.

The analog of the reference chain QueryBuilder.toQuery -> Query.rewrite ->
Weight/Scorer (`index/query/*`, Lucene createWeight), redesigned for XLA:

1. `rewrite(query, ctx)` runs once per query on the host: analysis,
   multi-term expansion, index-wide idf/avgdl statistics -> a LogicalNode
   tree whose *structure* is static and whose numeric inputs are arrays.
2. `prepare(node, segment)` binds the plan to one segment: term -> CSR row
   lookups, pow2 bucket selection (from host row pointers — no device sync),
   producing a `spec` (hashable static structure) + `params` (traced arrays).
3. `build_executor(spec)` constructs the traced function interpreting the
   spec; it is jitted once per spec and cached — segments with equal padded
   shapes and queries with equal structure all reuse the same XLA program.

Every node evaluates to a dense ScoredMask over ndocs_pad; scoring leaves are
gather->scatter passes (ops.scoring), predicates are vectorized column
compares, and combinators are elementwise VPU ops that XLA fuses.
"""

from __future__ import annotations

import fnmatch as _fnmatch
import re
import time as _time_mod
from bisect import bisect_left, bisect_right
from dataclasses import dataclass, field as dc_field
from functools import lru_cache, wraps
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..index.mappings import (FLOAT_TYPES, INT_TYPES, KEYWORD_TYPES,
                              RANGE_MEMBER, RANGE_TYPES, TEXT_TYPES,
                              Mappings, coerce_value, _parse_range_value)
from ..index.segment import (CODEC_V1, CODEC_V2, IMPACT_BLOCK, Segment,
                             next_pow2, split_i64)
from ..models.similarity import Similarity, resolve_similarity
from ..index.date_formats import parse_date
from ..ops import aggs as agg_ops
from ..ops import scoring as ops
from ..script import painless_lite as pl
from ..utils.metrics import METRICS, CounterGroup
from ..utils.trace import TRACER
from . import query_dsl as dsl
from .aggregations import AggNode

INT32_SENTINEL = np.int32(2**31 - 1)
HLL_LOG2M = 14

# ---------------------------------------------------------------------
# jit program-cache + compile-vs-execute attribution (utils/metrics.py)
# ---------------------------------------------------------------------
#
# Every jitted program builder in this module is lru_cache'd per
# canonical spec; the instrumented wrapper mirrors cache traffic into the
# registry and times the programs themselves, by the `device.dispatch`
# span round each call (attributes `program`, the family, and
# `first_call`). Attribution model: a
# program's FIRST python-side invocation runs trace + lower + XLA compile
# inline, so its wall lands in `search.jit.<family>.compile_ms`;
# steady-state calls land in `.execute_ms` (dispatch wall — XLA execution
# itself is async, so this is launch cost, not device busy time;
# RESCORE_STATS carries the synced device walls). Programs whose input
# shapes vary per segment can recompile on later calls — first-call
# attribution is the bounded, zero-sync approximation the reference's
# per-phase breakdowns also make.

_JIT_FAMILIES = ("executor", "mask", "gather", "agg", "rescore", "join")

# what a launch of `executor_program` is handed from the host (every numpy
# array or scalar among its params is one host->device copy a request),
# and the per-segment planes that stay on the device so that it is handed
# none of `ndocs_pad` elements: a date_histogram's bucket ids and a field
# sort's ranks (builds / hits of the per-segment caches, bytes built);
# `topk_keys_sorted`: the keys a launch's top-k hands to `lax.top_k`;
# `agg_bucket_launches`: the date-histogram bucket counts the launches
# carried, `agg_run_counted`: those of them whose plane is in row order and
# took `ops.aggs.run_counts` (the others take `ops.aggs.bucket_counts`)
EXECUTOR_STATS = CounterGroup(METRICS, "executor", {"params_h2d_bytes": 0,
                                                    "topk_keys_sorted": 0,
                                                    "agg_bucket_launches": 0,
                                                    "agg_run_counted": 0,
                                                    "launches": 0})
# what the aggregations of the launches cost, counted at each launch from
# the static spec (`_count_launch`): `scatter.updates` the rows handed to
# every scatter (a bucket count by `ops.aggs.bucket_counts`, and each
# scatter of a bucketed sub-metric: count, minimum, maximum and a limb a
# sum), which is what `ops.aggs.count_form` names "scatter" (and, for all
# of a sub-metric but its count, "product"); `blocked.rows` the rows a
# form that replaces a scatter reads, rows x passes over them
# (`ops.aggs.run_counts`; the dense form: one pass a bucket count, one
# for all of a sub-metric's accumulators; the product form: one pass a
# count); `bucketed_sub.launches` / `.buckets` the
# launches that carry a metric under a bucket aggregation, and their
# buckets; `auto_date.requests` the top-level auto_date_histograms a
# segment was asked, `auto_date.refine_launches` the launches taken first
# to learn their matched range (`auto_date_range`); `terms.ordinals` the
# vocabulary or combination slots the launches' `terms`, `multi_terms` and
# `composite` group-bys counted into, `composite.combinations` those of the
# composites alone (the combinations that occur in the segment, not the
# product of the sources' value spaces), and `terms.records` the bucket
# records the host then built from such counts (`executor` for a partial
# that is records, `aggregations.finalize` for one that stays arrays: the
# buckets a response returns, not the vocabulary); `terms.gathered_rows`
# the flat values to which a keyword group-by (`terms`, `significant_terms`,
# a multi-valued `composite` source, a keyword `cardinality` or
# `value_count`) gathered the match through `doc_of_value`, one element a
# value: those of the columns laid out by value
# (`ops.aggs.counts_by_value`), 0 for a column in which no document holds
# two values, which is counted by document
AGG_STATS = CounterGroup(METRICS, "aggs", {"scatter.updates": 0,
                                           "blocked.rows": 0,
                                           "bucketed_sub.launches": 0,
                                           "bucketed_sub.buckets": 0,
                                           "auto_date.requests": 0,
                                           "auto_date.refine_launches": 0,
                                           "terms.ordinals": 0,
                                           "terms.records": 0,
                                           "terms.gathered_rows": 0,
                                           "composite.combinations": 0})
# what the `knn` nodes of the launches cost, counted at each launch from
# the static spec (`_count_launch`): `queries` the nodes over a segment that
# holds the field, `ann_queries` / `exact_queries` those that probe the
# column's IVF lists / scan the whole matrix, `lists_probed` the lists a
# probe reads (`nprobe`), `candidate_slots` the slots it reads, scores and
# scatters back (`nprobe * cap`: a window of `cap` rows a list, whatever
# the list holds), `rows_by_id` the candidate rows a launch fetches one doc
# id at a time (none: a probe reads its lists where they lie, as dense
# windows of the list-ordered rows), and `query_vector_bytes` the padded
# query vector a node is handed
KNN_STATS = CounterGroup(METRICS, "knn", {"queries": 0, "ann_queries": 0,
                                          "exact_queries": 0,
                                          "lists_probed": 0,
                                          "candidate_slots": 0,
                                          "rows_by_id": 0,
                                          "query_vector_bytes": 0})
# the precision a `knn` node's scoring product names. Unnamed, a batch of
# queries a launch (the vmapped `msearch` twin) is ONE bfloat16 pass of the
# chip's matrix unit, scores 4e-4 relative off where the 100th neighbour
# stands closer than that to the 101st; one query a launch, and a CPU, are
# float32 either way: tests_tpu/test_knn_tpu.py moves this to see both
_KNN_SCORE_PRECISION = "highest"
BUCKET_PLANE_STATS = CounterGroup(METRICS, "aggs.bucket_plane",
                                  {"builds": 0, "hits": 0, "bytes": 0})
RANK_PLANE_STATS = CounterGroup(METRICS, "sort.rank_plane",
                                {"builds": 0, "hits": 0, "bytes": 0})


class _TimedProgram:
    __slots__ = ("_fn", "_family", "_shape", "_compiled", "__weakref__")

    def __init__(self, family: str, fn, shape: Optional[str] = None):
        self._fn = fn
        self._family = family
        self._shape = shape
        self._compiled = False

    def __call__(self, *a, **kw):
        # one `device.dispatch` span a launch, round the call alone:
        # flattening the argument tree, the host arrays' copy up and the
        # enqueue (on a first call also trace, lower and compile). Its
        # `start_ns` / `end_ns` are the launch's one clock: the
        # `perf_counter` pair below is the path of a disabled tracer
        first = not self._compiled
        if TRACER.enabled:
            with TRACER.span("device.dispatch", program=self._family,
                             first_call=first) as span:
                out = self._fn(*a, **kw)
            dt = (span.end_ns - span.start_ns) / 1e6
        elif METRICS.enabled:
            t0 = _time_mod.perf_counter()
            out = self._fn(*a, **kw)
            dt = (_time_mod.perf_counter() - t0) * 1e3
        else:
            out = self._fn(*a, **kw)
        self._compiled = True
        if not METRICS.enabled:
            return out
        base = f"search.jit.{self._family}"
        if first:
            # benign race: two threads can both attribute their first
            # call as a compile — the histogram stays honest enough and
            # a lock here would tax every launch
            METRICS.histogram(f"{base}.compile_ms").record(dt)
            if self._shape:
                METRICS.histogram(
                    f"{base}.shape.{self._shape}.compile_ms").record(dt)
        else:
            METRICS.counter(f"{base}.launches").inc()
            METRICS.histogram(f"{base}.execute_ms").record(dt)
            if self._shape:
                METRICS.histogram(
                    f"{base}.shape.{self._shape}.execute_ms").record(dt)
        return out


# every program-builder lru cache in the process, for
# `clear_program_caches` — the jitted wrappers these hold pin mmap'd
# JIT-code regions for as long as they live
_PROGRAM_CACHE_CLEARERS: List[Callable] = []


def clear_program_caches() -> None:
    """Drop every compiled-program cache in the process: the engine's
    lru program builders AND JAX's internal jit caches. Each XLA-CPU
    executable pins a triplet of mmap'd JIT-code regions; a process
    that compiles unboundedly many program shapes (full test suites,
    multi-corpus bench runs) accumulates tens of thousands of maps and
    can cross the kernel's `vm.max_map_count` ceiling — the same limit
    the reference engine's bootstrap check guards (Elasticsearch/
    OpenSearch demand vm.max_map_count >= 262144) — after which the
    next mmap inside a compile fails as a SIGSEGV. Everything
    recompiles on demand; counters and telemetry are untouched."""
    import gc

    import jax
    for clear in list(_PROGRAM_CACHE_CLEARERS):
        clear()
    jax.clear_caches()
    gc.collect()


def _instrumented_program_cache(family: str, maxsize: int,
                                shape_of: Optional[Callable] = None):
    """lru_cache a program builder with registry attribution: requests
    and misses count per family (hits = requests - misses), and the built
    program is wrapped in `_TimedProgram` for compile-vs-execute walls.
    `cache_info`/`cache_clear` keep functools semantics — tests ratchet
    on them."""

    def deco(build):
        @lru_cache(maxsize=maxsize)
        def cached(*key):
            from ..obs.hbm_ledger import LEDGER
            from ..utils.metrics import METRICS
            if METRICS.enabled:
                METRICS.counter(f"search.jit.{family}.cache_miss").inc()
            prog = _TimedProgram(family, build(*key),
                                 shape_of(*key) if shape_of else None)
            # per-shape compiled-program footprint tenant: ADVISORY
            # (bytes=0, uncharged) — XLA owns the executable's true HBM
            # cost and the ledger's device cross-check covers the
            # aggregate; the registration attributes program COUNT per
            # family and releases on lru eviction / cache_clear
            LEDGER.register("program", 0, owner=prog, charge=False,
                            label=f"jit[{family}]"
                                  f"{'.' + prog._shape if prog._shape else ''}")
            return prog

        @wraps(build)
        def wrapper(*key):
            # disabled-mode contract: no name formatting / registry lock
            # on the per-launch hot path when telemetry is off
            from ..utils.metrics import METRICS
            if METRICS.enabled:
                METRICS.counter(f"search.jit.{family}.requests").inc()
            return cached(*key)

        wrapper.cache_info = cached.cache_info
        wrapper.cache_clear = cached.cache_clear
        _PROGRAM_CACHE_CLEARERS.append(cached.cache_clear)
        return wrapper

    return deco


def jit_attribution() -> Dict[str, dict]:
    """Per-family program-cache and compile-vs-execute rollup (consumed
    by `_nodes/stats` and the enriched `profile` response)."""
    from ..utils.metrics import METRICS
    snap = METRICS.snapshot()
    cnt, hist = snap["counters"], snap["histograms"]
    out: Dict[str, dict] = {}
    for fam in _JIT_FAMILIES:
        base = f"search.jit.{fam}"
        requests = cnt.get(f"{base}.requests", 0)
        if not requests:
            continue
        misses = cnt.get(f"{base}.cache_miss", 0)
        comp = hist.get(f"{base}.compile_ms", {})
        ex = hist.get(f"{base}.execute_ms", {})
        out[fam] = {
            "cache": {"requests": requests, "hits": requests - misses,
                      "misses": misses},
            "compile": {"count": comp.get("count", 0),
                        "total_ms": comp.get("sum_ms", 0.0),
                        "p50_ms": comp.get("p50_ms")},
            "execute": {"count": ex.get("count", 0),
                        "total_ms": ex.get("sum_ms", 0.0),
                        "p50_ms": ex.get("p50_ms"),
                        "p99_ms": ex.get("p99_ms")},
        }
    return out

# reference PercentilesAggregationBuilder defaults — shared with the mesh
# service so host and mesh never drift
DEFAULT_PERCENTS = (1.0, 5.0, 25.0, 50.0, 75.0, 95.0, 99.0)
PCTL_BINS = 4096


# =====================================================================
# shard context (index-wide statistics)
# =====================================================================

class ShardContext:
    """Index-wide view used during rewrite (reference QueryShardContext)."""

    def __init__(self, mappings: Mappings, segments: List[Segment],
                 similarity=None, field_similarities: Optional[dict] = None):
        self.mappings = mappings
        self.segments = segments
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


def _weighted_terms(field: str, terms: List[str], boosts: List[float],
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


def _prefix_rows(pb, term: str, cap: Optional[int] = None) -> range:
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
                for r in _prefix_rows(pb, t, max_expansions):
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


def _analyze_query_text(field: str, text: Any, ctx: ShardContext,
                        analyzer_override: Optional[str] = None) -> List[str]:
    ft = ctx.mappings.resolve_field(field)
    if ft is None:
        return [str(text)]
    if analyzer_override:
        return ctx.mappings.analysis.get(analyzer_override).terms(str(text))
    return ctx.mappings.search_analyzer_for(ft).terms(str(text))


def _index_term(field: str, value: Any, ctx: ShardContext) -> str:
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
        term = _index_term(q.field, q.value, ctx)
        if q.case_insensitive:
            term = term.lower()
        mode = "score" if scoring else "filter"
        return _weighted_terms(field, [term], [1.0], ctx, 1, mode, q.boost)

    if isinstance(q, dsl.TermsQuery):
        ft = m.resolve_field(q.field)
        if ft is not None and ft.type == "ip" and any(
                isinstance(v, str) and "/" in v for v in q.values):
            # CIDR members expand to ranges; exact ips stay term matches
            # (reference IpFieldMapper.termsQuery)
            children = [
                _ip_cidr_node(ft.name, v, 1.0)
                if isinstance(v, str) and "/" in v else
                _weighted_terms(ft.name, [_index_term(ft.name, v, ctx)],
                                [1.0], ctx, 1, "filter", 1.0)
                for v in q.values]
            return LBool(shoulds=children, msm=1, boost=q.boost)
        if ft is not None and ft.type in (INT_TYPES | FLOAT_TYPES):
            children = [_numeric_eq_node(ft, ft.name, v, 1.0) for v in q.values]
            return LBool(shoulds=children, msm=1, boost=q.boost)
        field = ft.name if ft else q.field
        terms = [_index_term(q.field, v, ctx) for v in q.values]
        # terms query is constant-score (reference TermInSetQuery)
        return _weighted_terms(field, terms, [1.0] * len(terms), ctx, 1, "filter", q.boost)

    if isinstance(q, dsl.MatchQuery):
        ft = m.resolve_field(q.field)
        if ft is not None and ft.type in (INT_TYPES | FLOAT_TYPES) and ft.type != "date":
            return _numeric_eq_node(ft, ft.name, q.query, q.boost)
        field = ft.name if ft else q.field
        terms = _analyze_query_text(field, q.query, ctx, q.analyzer)
        if not terms:
            return LMatchNone()
        if q.fuzziness is not None:
            expanded: List[LNode] = []
            for t in terms:
                expanded.append(LExpandTerms(field=field,
                                             expander=_fuzzy_expander(field, t, q.fuzziness, 0),
                                             boost=q.boost))
            msm = len(expanded) if q.operator == "and" else \
                dsl.parse_minimum_should_match(q.minimum_should_match, len(expanded)) or 1
            return LBool(shoulds=expanded, msm=msm, boost=1.0)
        msm = len(terms) if q.operator == "and" else \
            dsl.parse_minimum_should_match(q.minimum_should_match, len(terms)) or 1
        mode = "score" if scoring else "score"  # scores also drive msm counts
        return _weighted_terms(field, terms, [1.0] * len(terms), ctx, msm, mode, q.boost)

    if isinstance(q, dsl.MatchBoolPrefixQuery):
        ft = m.resolve_field(q.field)
        field = ft.name if ft else q.field
        terms = _analyze_query_text(field, q.query, ctx, q.analyzer)
        if not terms:
            return LMatchNone()
        children: List[LNode] = [
            _weighted_terms(field, [t], [1.0], ctx, 1, "score", q.boost)
            for t in terms[:-1]]
        children.append(LExpandTerms(
            field=field,
            expander=_prefix_expander(field, terms[-1], False, cap=50),
            boost=q.boost))
        msm = len(children) if q.operator == "and" else 1
        return LBool(shoulds=children, msm=msm, boost=1.0)

    if isinstance(q, dsl.TermsSetQuery):
        ft = m.resolve_field(q.field)
        field = ft.name if ft else q.field
        terms = [str(t) for t in q.terms]
        if not terms:
            return LMatchNone()
        child = _weighted_terms(field, terms, [1.0] * len(terms), ctx, 0,
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
        terms = _analyze_query_text(fspecs[0][0], q.query, ctx, None)
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
        terms = _analyze_query_text(field, q.query, ctx, q.analyzer)
        if not terms:
            return LMatchNone()
        if len(terms) == 1 and not q.prefix:
            # Lucene rewrites a single-term phrase to a TermQuery
            return _weighted_terms(field, terms, [1.0], ctx, 1, "score", q.boost)
        if len(terms) == 1 and q.prefix:
            return LExpandTerms(field=field,
                                expander=_prefix_expander(field, terms[0], False,
                                                          cap=q.max_expansions),
                                boost=q.boost)
        return _phrase_node(field, terms, q.slop, ctx, q.boost,
                            prefix_last=q.prefix, max_expansions=q.max_expansions)

    if isinstance(q, dsl.SpanTermQuery):
        field = q.field
        term = _index_term(field, q.value, ctx)
        return _weighted_terms(field, [term], [1.0], ctx, 1, "score", q.boost)

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
            flat_terms.append(_index_term(c.field, c.value, ctx))
        if not flat_terms or field is None:
            return LMatchNone()
        if len(flat_terms) == 1:
            return _weighted_terms(field, flat_terms, [1.0], ctx, 1, "score", q.boost)
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
        terms = _analyze_query_text(field, q.query, ctx, q.analyzer)
        if not terms:
            return LMatchNone()
        if len(terms) == 1:
            return _weighted_terms(field, terms, [1.0], ctx, 1, "score", q.boost)
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
                expander=_prefix_expander(ft.name, f"{ft.flat_prefix}=",
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
        return LExpandTerms(field=q.field, expander=_prefix_expander(q.field, q.value,
                                                                     q.case_insensitive),
                            boost=q.boost)
    if isinstance(q, dsl.WildcardQuery):
        return LExpandTerms(field=q.field, expander=_wildcard_expander(q.field, q.value,
                                                                       q.case_insensitive),
                            boost=q.boost)
    if isinstance(q, dsl.RegexpQuery):
        return LExpandTerms(field=q.field, expander=_regexp_expander(q.field, q.value),
                            boost=q.boost)
    if isinstance(q, dsl.FuzzyQuery):
        return LExpandTerms(field=q.field,
                            expander=_fuzzy_expander(q.field, q.value, q.fuzziness,
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
                for t in _analyze_query_text(f, text, ctx):
                    tf_counts[(f, t)] = tf_counts.get((f, t), 0) + 1
    skip: set = set()
    for item in q.unlike:
        for f, texts in texts_of(item, []).items():
            for text in texts:
                for t in _analyze_query_text(f, text, ctx):
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
        node = _weighted_terms(f, [t for t, _ in pairs],
                               [b for _, b in pairs], ctx,
                               msm=max(msm_total, 1), mode=mode,
                               boost=q.boost)
    else:
        # multi-field: one single-term group per clause so msm counts terms
        # across fields exactly like the reference boolean query
        shoulds = [
            _weighted_terms(f, [t], [b], ctx, msm=1, mode=mode, boost=1.0)
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
            _weighted_terms(f"{jf}#parent", [q.id], [1.0], ctx, 1, "filter", 1.0),
            _weighted_terms(jf, [q.type], [1.0], ctx, 1, "filter", 1.0)])
        return LConstScore(child=inner, boost=q.boost)

    ji = get_join_index(ctx.segments, jf)
    if kind == "has_child":
        parent_rel = next((p for p, cs in relations.items() if q.type in cs), None)
        if parent_rel is None:
            return unmapped(f"[{q.type}] is not a child relation of the join field")
        inner = rewrite(q.query or dsl.MatchAllQuery(), ctx, scoring)
        child = LBool(musts=[inner], filters=[
            _weighted_terms(jf, [q.type], [1.0], ctx, 1, "filter", 1.0)])
        pf = _weighted_terms(jf, [parent_rel], [1.0], ctx, 1, "filter", 1.0)
        return LHasChild(join_field=jf, child_rel=q.type, child=child,
                         parent_filter=pf, score_mode=q.score_mode,
                         min_children=q.min_children, max_children=q.max_children,
                         boost=q.boost, join_index=ji)

    # has_parent
    if q.parent_type not in relations:
        return unmapped(f"[{q.parent_type}] is not a parent relation")
    inner = rewrite(q.query or dsl.MatchAllQuery(), ctx, scoring)
    parent_plan = LBool(musts=[inner], filters=[
        _weighted_terms(jf, [q.parent_type], [1.0], ctx, 1, "filter", 1.0)])
    cf = _weighted_terms(jf, sorted(relations[q.parent_type]),
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
                        field_similarities=ctx.field_sims)


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

def _prefix_expander(field: str, prefix: str, ci: bool, cap: Optional[int] = None):
    def expand(seg: Segment) -> np.ndarray:
        pb = seg.postings.get(field)
        if pb is None:
            return np.empty(0, np.int32)
        if ci:
            rows = [i for i, t in enumerate(pb.vocab) if t.lower().startswith(prefix.lower())]
            rows = rows[:cap] if cap is not None else rows
            return np.asarray(rows, np.int32)
        r = _prefix_rows(pb, prefix, cap)
        return np.arange(r.start, r.stop, dtype=np.int32)
    return expand


def _wildcard_expander(field: str, pattern: str, ci: bool):
    def expand(seg: Segment) -> np.ndarray:
        pb = seg.postings.get(field)
        if pb is None:
            return np.empty(0, np.int32)
        pat = pattern.lower() if ci else pattern
        rows = [i for i, t in enumerate(pb.vocab)
                if _fnmatch.fnmatchcase(t.lower() if ci else t, pat)]
        return np.asarray(rows, np.int32)
    return expand


def _regexp_expander(field: str, pattern: str):
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


def _fuzzy_expander(field: str, term: str, fuzziness, prefix_length: int):
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


# =====================================================================
# prepare: bind logical plan to one segment -> (spec, params)
# =====================================================================

F32_MIN = np.float32(-3.4e38)
F32_MAX_HOST = np.float32(3.4e38)


def _p(params: dict, key: str, value) -> str:
    params[key] = value
    return key


def _scalar_f32(params, key, v) -> str:
    return _p(params, key, np.float32(v))


def _scalar_i32(params, key, v) -> str:
    return _p(params, key, np.int32(v))


def _i64_bounds(params, nid: int, lo, hi) -> Tuple[str, str, str, str]:
    lo = -(2**63) if lo is None else int(lo)
    hi = 2**63 - 1 if hi is None else int(hi)
    lo_hi, lo_lo = split_i64(np.asarray([lo]))
    hi_hi, hi_lo = split_i64(np.asarray([hi]))
    return (_p(params, f"q{nid}_lohi", lo_hi[0]), _p(params, f"q{nid}_lolo", lo_lo[0]),
            _p(params, f"q{nid}_hihi", hi_hi[0]), _p(params, f"q{nid}_hilo", hi_lo[0]))


def _phrase_pairs(seg: Segment, pb, rows: Tuple[int, ...]):
    """Unshifted (doc, position) pairs for a term's postings (union over
    `rows` for prefix expansion), lex-sorted; cached per segment and shared
    across query positions (the caller subtracts the phrase offset when
    padding — a constant shift keeps lex order)."""
    cache = getattr(seg, "_phrase_pair_cache", None)
    if cache is None:
        cache = seg._phrase_pair_cache = {}
    key = (pb.field, rows)
    if key in cache:
        return cache[key]
    docs_parts, pos_parts = [], []
    for r in rows:
        a, b = pb.row_slice(r)
        counts = pb.pos_starts[a + 1: b + 1] - pb.pos_starts[a: b]
        docs_parts.append(np.repeat(pb.doc_ids[a:b], counts))
        pos_parts.append(pb.positions[pb.pos_starts[a]: pb.pos_starts[b]])
    d = np.concatenate(docs_parts) if docs_parts else np.empty(0, np.int32)
    p = np.concatenate(pos_parts) if pos_parts else np.empty(0, np.int32)
    if len(rows) > 1 and len(d):
        order = np.lexsort((p, d))
        d, p = d[order], p[order]
    res = (d.astype(np.int32), p.astype(np.int32))
    cache[key] = res
    return res


def _source_phrase_match(seg: Segment, doc: int, field: str,
                         terms: List[str], slop: int, analyzer) -> bool:
    """Re-analyze one doc's _source value(s) for `field` and test the
    phrase with the same median-offset total-movement slop cost the device
    path uses (ops/positions.py phrase_freqs)."""
    if analyzer is None:
        return False
    src = seg.sources[doc]
    node = src
    for part in field.split("."):
        if not isinstance(node, dict) or part not in node:
            return False
        node = node[part]
    values = node if isinstance(node, list) else [node]
    base = 0
    positions: Dict[str, List[int]] = {}
    for v in values:
        toks = analyzer.analyze(str(v))
        last = 0
        for t in toks:
            positions.setdefault(t.text, []).append(base + t.position)
            last = t.position
        base += last + 100          # value gap, matching index-time
    per_term = [positions.get(t) for t in terms]
    if any(p is None for p in per_term):
        return False
    for p0 in per_term[0]:
        deltas = [0.0]
        for i, plist in enumerate(per_term[1:], start=1):
            # nearest adjusted position to the anchor
            best = min((p - i - p0 for p in plist), key=abs)
            deltas.append(float(best))
        med = sorted(deltas)[len(deltas) // 2]
        cost = sum(abs(d - med) for d in deltas)
        if cost <= slop:
            return True
    return False


def _pad_to_sentinel(arr: np.ndarray, size: int) -> np.ndarray:
    out = np.full(size, INT32_SENTINEL, dtype=np.int32)
    out[: len(arr)] = arr
    return out


def _prepare_knn(node, seg: Segment, ctx, params: dict):
    """A `knn` node's params (the padded query vector, |q|^2, the boost),
    its filter's spec, and the static probe (`(nprobe, cap)`: the lists a
    probe reads and the rows of a list's window) where the column has an
    IVF index and the query did not force the exact scan."""
    nid = node.nid
    col_exists = node.field in seg.vector_cols
    if col_exists:
        dims = seg.vector_cols[node.field].values.shape[1]
        v = np.zeros(((dims + 127) // 128) * 128, np.float32)  # lane pad
        v[:dims] = node.vector[:dims]
        _p(params, f"q{nid}_vec", v)
        _scalar_f32(params, f"q{nid}_qsq", float(np.dot(node.vector, node.vector)))
    _scalar_f32(params, f"q{nid}_boost", node.boost)
    fspec = prepare(node.filter, seg, ctx, params) if node.filter else None
    # ANN route: mapping opted into IVF and the query didn't force
    # exact -> static nprobe (jit-key) clamped to this segment's nlist,
    # with the index's cap. Building here (host, once, cached on the
    # column) keeps emit pure.
    probe = None
    if col_exists and not node.exact:
        ivf = seg.vector_cols[node.field].ivf()
        if ivf is not None:
            probe = (int(min(node.nprobe or ivf.default_nprobe, ivf.nlist)),
                     ivf.cap)
    return ("knn", nid, node.field, col_exists, node.similarity, fspec,
            probe)


def prepare(node: LNode, seg: Segment, ctx: ShardContext, params: dict):  # noqa: C901
    """-> hashable spec tree; fills `params` with this segment's arrays."""
    nid = node.nid

    if isinstance(node, LTerms):
        pb = seg.postings.get(node.field)
        T_pad = next_pow2(len(node.terms), floor=1)
        rows = np.full(T_pad, -1, dtype=np.int32)
        total = 0
        if pb is not None:
            for i, t in enumerate(node.terms):
                r = pb.row(t)
                rows[i] = r
                if r >= 0:
                    a, b = pb.row_slice(r)
                    total += b - a
        bucket = ops.pick_bucket(total)
        # codec-version branch (consults Segment.codec_version, OSL507):
        # v2 fields carry no resident f32 tf plane. Filter-mode programs
        # run the tf-free gather (layout tag below); exact-scoring
        # programs still need tf/dl math, so prepare promotes the plane
        # back onto the device once per (segment, field) — the eager
        # impact hot path (search/impactpath.py) never does.
        v2 = (getattr(seg, "codec_version", CODEC_V1) >= CODEC_V2
              and pb is not None and pb.impact is not None)
        layout = "impact" if v2 else "tf"
        if v2 and node.mode != "filter":
            seg.ensure_device_tfs(node.field)
        w = np.zeros(T_pad, dtype=np.float32)
        w[: len(node.terms)] = node.weights
        a = np.zeros(T_pad, dtype=np.float32)
        a[: len(node.terms)] = node.aux
        _p(params, f"q{nid}_rows", rows)
        _p(params, f"q{nid}_w", w)
        _p(params, f"q{nid}_aux", a)
        _scalar_f32(params, f"q{nid}_msm", node.msm)
        _scalar_f32(params, f"q{nid}_avgdl", ctx.avgdl(node.field))
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        sim = node.sim
        b_eff = sim.b if node.has_norms else 0.0
        return ("terms", nid, node.field, T_pad, bucket, sim.sim_id,
                float(sim.k1), float(b_eff), node.mode, layout)

    if isinstance(node, LSourcePhrase):
        pb = seg.postings.get(node.field)
        if pb is None:
            return ("match_none", nid)
        rows = [pb.row(t) for t in node.terms]
        if any(r < 0 for r in rows):
            return ("match_none", nid)
        cand = None
        for r in rows:
            a, b = pb.row_slice(r)
            d = pb.doc_ids[a:b]
            cand = d if cand is None else np.intersect1d(
                cand, d, assume_unique=True)
            if len(cand) == 0:
                break
        ft = ctx.mappings.resolve_field(node.field)
        analyzer = ctx.mappings.index_analyzer(ft) if ft is not None else None
        docs = [int(d) for d in (cand if cand is not None else ())
                if _source_phrase_match(seg, int(d), node.field, node.terms,
                                        node.slop, analyzer)]
        pad = next_pow2(max(len(docs), 1), floor=8)
        arr = np.full(pad, INT32_SENTINEL, dtype=np.int32)
        arr[: len(docs)] = np.asarray(docs, np.int32)
        _p(params, f"q{nid}_docs", arr)
        _scalar_f32(params, f"q{nid}_boost", node.weight)
        return ("ids", nid, pad)

    if isinstance(node, LPhrase):
        pb = seg.postings.get(node.field)
        if pb is None or pb.pos_starts is None:
            return ("match_none", nid)
        m_terms = len(node.terms)
        last = m_terms - 1
        arrays = []
        term_rows = []
        for i, t in enumerate(node.terms):
            if node.prefix_last and i == last:
                rows = list(_prefix_rows(pb, t, node.max_expansions))
            else:
                r = pb.row(t)
                rows = [r] if r >= 0 else []
            if not rows:
                return ("match_none", nid)  # phrase needs every term
            arrays.append(_phrase_pairs(seg, pb, tuple(rows)))
            term_rows.append(tuple(rows))
        buckets = []
        # pair arrays are RAW and DEVICE-RESIDENT per (segment, term,
        # bucket): the query position rides as a scalar shift, so repeated
        # phrase queries never re-upload megabytes of positions (the
        # positional analog of the resident CSR postings)
        dev_cache = seg.__dict__.setdefault("_phrase_dev_cache", {})
        for i, (d, p) in enumerate(arrays):
            # coarse pow4 buckets: pair-array pads land on 1 of ~6 sizes so
            # phrase programs compile once per coarse shape, not per df
            bucket = next_pow2(max(len(d), 1), floor=64)
            if bucket.bit_length() % 2 == 0:   # odd exponent -> round up
                bucket <<= 1
            ck = (node.field, term_rows[i], bucket)
            dev = dev_cache.get(ck)
            if dev is None:
                import jax

                from ..obs.hbm_ledger import LEDGER
                d_dev = jax.device_put(_pad_to_sentinel(d, bucket))
                p_dev = jax.device_put(_pad_to_sentinel(p, bucket))
                alloc = LEDGER.register(
                    "phrase_pairs", int(d_dev.nbytes + p_dev.nbytes),
                    owner=seg, segment=seg,
                    label=f"phrase-pairs[{seg.name}][{node.field}]")
                dev = (d_dev, p_dev, alloc)
                while len(dev_cache) >= 1024:
                    evicted = dev_cache.pop(next(iter(dev_cache)))
                    LEDGER.release(evicted[2])
                dev_cache[ck] = dev
            _p(params, f"q{nid}_d{i}", dev[0])
            _p(params, f"q{nid}_p{i}", dev[1])
            _scalar_i32(params, f"q{nid}_shift{i}", i)
            buckets.append(bucket)
        sim = node.sim
        b_eff = sim.b if node.has_norms else 0.0
        _scalar_f32(params, f"q{nid}_w", node.weight)
        _scalar_f32(params, f"q{nid}_slop", node.slop)
        _scalar_f32(params, f"q{nid}_avgdl", ctx.avgdl(node.field))
        return ("phrase", nid, node.field, m_terms, tuple(buckets),
                float(sim.k1), float(b_eff), node.ordered, node.gap_cost)

    if isinstance(node, LExpandTerms):
        rows_np = node.expander(seg)
        pb = seg.postings.get(node.field)
        total = 0
        if pb is not None and len(rows_np):
            lens = pb.starts[rows_np + 1] - pb.starts[rows_np]
            total = int(lens.sum())
        T_pad = next_pow2(max(len(rows_np), 1), floor=1)
        rows = np.full(T_pad, -1, dtype=np.int32)
        rows[: len(rows_np)] = rows_np
        bucket = ops.pick_bucket(total)
        _p(params, f"q{nid}_rows", rows)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        layout = ("impact" if getattr(seg, "codec_version",
                                      CODEC_V1) >= CODEC_V2
                  and pb is not None and pb.impact is not None else "tf")
        return ("xterms", nid, node.field, T_pad, bucket, layout)

    if isinstance(node, LMatchAll):
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("match_all", nid)

    if isinstance(node, LMatchNone):
        return ("match_none", nid)

    if isinstance(node, LRange):
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        if node.kind == "int":
            _i64_bounds(params, nid, node.lo, node.hi)
        else:
            _scalar_f32(params, f"q{nid}_flo",
                        -np.inf if node.lo is None else node.lo)
            _scalar_f32(params, f"q{nid}_fhi",
                        np.inf if node.hi is None else node.hi)
        return ("range", nid, node.field, node.kind, node.include_lo, node.include_hi,
                node.field in seg.numeric_cols)

    if isinstance(node, LExists):
        src = ("numeric" if node.field in seg.numeric_cols else
               "keyword" if node.field in seg.keyword_cols else
               "geo" if node.field in seg.geo_cols else
               "dl" if node.field in seg.doc_lens else
               "none")
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("exists", nid, node.field, src)

    if isinstance(node, LIds):
        docs = [seg.id2doc[i] for i in node.ids if i in seg.id2doc]
        pad = next_pow2(max(len(docs), 1), floor=8)
        arr = np.full(pad, INT32_SENTINEL, dtype=np.int32)
        arr[: len(docs)] = docs
        _p(params, f"q{nid}_docs", arr)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("ids", nid, pad)

    if isinstance(node, LBool):
        _scalar_f32(params, f"q{nid}_msm", node.msm)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("bool", nid,
                tuple(prepare(c, seg, ctx, params) for c in node.musts),
                tuple(prepare(c, seg, ctx, params) for c in node.shoulds),
                tuple(prepare(c, seg, ctx, params) for c in node.must_nots),
                tuple(_prepare_cached_filter(c, seg, ctx, params)
                      for c in node.filters))

    if isinstance(node, LConstScore):
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("const", nid, prepare(node.child, seg, ctx, params))

    if isinstance(node, LDisMax):
        _scalar_f32(params, f"q{nid}_tie", node.tie_breaker)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("dismax", nid, tuple(prepare(c, seg, ctx, params) for c in node.children))

    if isinstance(node, LBoosting):
        _scalar_f32(params, f"q{nid}_nb", node.negative_boost)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("boosting", nid, prepare(node.positive, seg, ctx, params),
                prepare(node.negative, seg, ctx, params))

    if isinstance(node, LFuncScore):
        child_spec = prepare(node.child, seg, ctx, params)
        fn_specs = []
        for i, (fn, filt) in enumerate(zip(node.functions, node.fn_filters)):
            fspec = prepare(filt, seg, ctx, params) if filt is not None else None
            _scalar_f32(params, f"q{nid}_fn{i}_w", fn.weight)
            if fn.kind == "field_value_factor":
                _scalar_f32(params, f"q{nid}_fn{i}_factor", fn.factor)
                _scalar_f32(params, f"q{nid}_fn{i}_missing",
                            fn.missing if fn.missing is not None else 1.0)
                fn_specs.append(("fvf", i, fn.field, fn.modifier,
                                 fn.field in seg.numeric_cols, fspec))
            elif fn.kind == "random_score":
                _scalar_i32(params, f"q{nid}_fn{i}_seed", fn.seed)
                fn_specs.append(("random", i, fspec))
            elif fn.kind == "script_score":
                ast = pl.parse(fn.script or "")
                field_srcs, pkeys = _prepare_script(ast, fn.script_params or {},
                                                    seg, params, nid, f"fn{i}s")
                fn_specs.append(("fnscript", i, ast, field_srcs, pkeys, fspec))
            elif fn.kind == "decay":
                fn_specs.append(_prepare_decay(fn, i, nid, seg, ctx, params,
                                               fspec))
            else:
                fn_specs.append(("weight", i, fspec))
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        _scalar_f32(params, f"q{nid}_minscore",
                    node.min_score if node.min_score is not None else -3.4e38)
        return ("fnscore", nid, child_spec, tuple(fn_specs),
                node.score_mode, node.boost_mode)

    if isinstance(node, LNested):
        blk = seg.nested.get(node.path)
        if blk is None or blk.child.ndocs == 0:
            return ("match_none", nid)
        child_spec = prepare(node.child, blk.child, node.child_ctx, params)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("nested", nid, node.path, node.score_mode, child_spec)

    if isinstance(node, LHasChild):
        if node.pre is None:
            need = {"cnt"}
            if node.score_mode in ("sum", "avg"):
                need.add("sum")
            elif node.score_mode in ("max", "min"):
                need.add(node.score_mode)
            node.pre = _join_prepass(node.child, node.join_index, tuple(sorted(need)), ctx)
        for k, v in node.pre.items():
            params[f"q{nid}_{k}"] = v
        pf_spec = prepare(node.parent_filter, seg, ctx, params)
        _scalar_i32(params, f"q{nid}_base", node.join_index.seg_base(seg))
        # at least one matching child is always required (reference semantics)
        _scalar_f32(params, f"q{nid}_minc", max(node.min_children, 1))
        _scalar_f32(params, f"q{nid}_maxc", min(node.max_children, 2**31 - 1))
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("has_child", nid, node.score_mode, pf_spec)

    if isinstance(node, LHasParent):
        if node.pre is None:
            # parents occupy their own slot (base + doc): reuse the scatter
            # with identity slots — "cnt" is the match vector, "sum" the score
            node.pre = _join_prepass(node.child, node.join_index, ("cnt", "sum"),
                                     ctx, self_slots=True)
        params[f"q{nid}_match"] = node.pre["cnt"]
        params[f"q{nid}_score"] = node.pre["sum"]
        params[f"q{nid}_pslot"] = node.join_index.pslot(seg)
        cf_spec = prepare(node.child_filter, seg, ctx, params)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("has_parent", nid, node.use_score, cf_spec)

    if isinstance(node, LRankFeature):
        _scalar_f32(params, f"q{nid}_p1", node.p1)
        _scalar_f32(params, f"q{nid}_p2", node.p2)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        if node.feature is None:
            return ("rank_feature_col", nid, node.field, node.fn, node.positive,
                    node.field in seg.numeric_cols)
        pb = seg.postings.get(node.field)
        if pb is not None and pb.impact is not None:
            # feature-impact field: rank_feature's monotone functions
            # need the exact f32 weights (see LSparseDot above)
            seg.ensure_device_tfs(node.field)
        row = pb.row(node.feature) if pb is not None else -1
        df = pb.doc_freq(node.feature) if pb is not None else 0
        _p(params, f"q{nid}_rows", np.asarray([row], np.int32))
        return ("rank_feature_post", nid, node.field, ops.pick_bucket(df, 16),
                node.fn, node.positive, pb is not None)

    if isinstance(node, LSparseDot):
        pb = seg.postings.get(node.field)
        if pb is None:
            return ("match_none", nid)
        if pb.impact is not None:
            # feature-impact field (index_impacts): the v2 device layout
            # ships the quantized plane without the f32 weight plane; the
            # generic sparse_dot program (bool-embedded neural_sparse,
            # mesh-attached nodes, dense escalation of the sparse impact
            # ladder) still scores from exact weights — promote lazily
            seg.ensure_device_tfs(node.field)
        T_pad = next_pow2(len(node.tokens), floor=8)
        rows = np.full(T_pad, -1, np.int32)
        rows[: len(node.tokens)] = [pb.row(t) for t in node.tokens]
        _p(params, f"q{nid}_rows", rows)
        w = np.zeros(T_pad, np.float32)
        w[: len(node.tokens)] = node.weights
        _p(params, f"q{nid}_w", w)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        total = sum(pb.doc_freq(t) for t in node.tokens)
        return ("sparse_dot", nid, node.field, T_pad, ops.pick_bucket(total))

    if isinstance(node, LDistanceFeature):
        _scalar_f32(params, f"q{nid}_pivot", node.pivot)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        if node.kind == "date":
            hi, lo = split_i64(np.asarray([node.origin], np.int64))
            _scalar_i32(params, f"q{nid}_ohi", int(hi[0]))
            _scalar_i32(params, f"q{nid}_olo", int(lo[0]))
            return ("distfeat_date", nid, node.field,
                    node.field in seg.numeric_cols)
        _scalar_f32(params, f"q{nid}_lat", node.origin[0])
        _scalar_f32(params, f"q{nid}_lon", node.origin[1])
        return ("distfeat_geo", nid, node.field, node.field in seg.geo_cols)

    if isinstance(node, LPercolate):
        from .percolate import segment_mask

        _p(params, f"q{nid}_mask",
           segment_mask(node.field, node.mini_seg, node.mini_ctx, seg))
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("percolate", nid)

    if isinstance(node, LScriptFilter):
        field_srcs, pkeys = _prepare_script(node.ast, node.params, seg, params,
                                            nid, "s")
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("script", nid, node.ast, field_srcs, pkeys)

    if isinstance(node, LScriptScore):
        child_spec = prepare(node.child, seg, ctx, params)
        field_srcs, pkeys = _prepare_script(node.ast, node.params, seg, params,
                                            nid, "s")
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        _scalar_f32(params, f"q{nid}_minscore",
                    node.min_score if node.min_score is not None else F32_MIN)
        return ("scriptscore", nid, child_spec, node.ast, field_srcs, pkeys)

    if isinstance(node, LKnn):
        with TRACER.span("knn.prepare", field=node.field):
            return _prepare_knn(node, seg, ctx, params)

    if isinstance(node, LTermsSet):
        child_spec = prepare(node.child, seg, ctx, params)
        msm = np.full(seg.ndocs_pad, np.inf, np.float32)  # missing -> no hit
        if node.msm_field is not None:
            col = seg.numeric_cols.get(node.msm_field)
            if col is not None:
                msm[: seg.ndocs][col.present] = \
                    col.values[col.present].astype(np.float32)
        else:
            src, prm = node.script
            ast = pl.parse(src)
            variables = {"params": {**prm, "num_terms": node.num_terms}}
            flds = pl.referenced_doc_fields(ast)
            if not flds:
                # constant script ("params.num_terms - 1"): evaluate once
                msm[:] = float(pl.execute(ast, variables))
            else:
                for d in range(seg.ndocs):
                    dv = {f: pl.doc_view_for(seg, d, f) for f in flds}
                    msm[d] = float(pl.execute(ast, {**variables, "doc": dv}))
        _p(params, f"q{nid}_ts_msm", msm)
        return ("terms_set", nid, child_spec)

    if isinstance(node, LPinned):
        organic_spec = (prepare(node.organic, seg, ctx, params)
                        if node.organic is not None else None)
        docs = []
        ranks = []
        for rank, i in enumerate(node.ids):
            d = seg.id2doc.get(i)
            if d is not None:
                docs.append(d)
                ranks.append(rank)
        pad = next_pow2(max(len(docs), 1), floor=8)
        darr = np.full(pad, INT32_SENTINEL, np.int32)
        rarr = np.zeros(pad, np.float32)
        darr[: len(docs)] = docs
        rarr[: len(ranks)] = ranks
        _p(params, f"q{nid}_pin_docs", darr)
        _p(params, f"q{nid}_pin_ranks", rarr)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("pinned", nid, organic_spec, pad)

    if isinstance(node, LCombined):
        T = len(node.terms)
        T_pad = next_pow2(T, floor=1)
        sim = ctx.sim_for(node.fields[0][0])
        idf = np.zeros(T_pad, np.float32)
        idf[:T] = node.idf          # computed once at rewrite time
        fspecs = []
        avgdl_c = 0.0
        for fi, (fname, w) in enumerate(node.fields):
            pb = seg.postings.get(fname)
            if pb is not None and pb.impact is not None:
                # BM25F needs raw tf BEFORE saturation: promote the tf
                # plane on codec-v2 segments (once per segment/field)
                seg.ensure_device_tfs(fname)
            rows = np.full(T_pad, -1, np.int32)
            total = 0
            if pb is not None:
                for i, t in enumerate(node.terms):
                    r = pb.row(t)
                    rows[i] = r
                    if r >= 0:
                        a, b2 = pb.row_slice(r)
                        total += b2 - a
            _p(params, f"q{nid}_cf_rows{fi}", rows)
            _scalar_f32(params, f"q{nid}_cf_w{fi}", w)
            fspecs.append((fname, ops.pick_bucket(total), pb is not None))
            avgdl_c += w * ctx.avgdl(fname)
        _p(params, f"q{nid}_cf_idf", idf)
        _scalar_f32(params, f"q{nid}_cf_avgdl", max(avgdl_c, 1e-6))
        _scalar_f32(params, f"q{nid}_cf_msm", node.msm)
        k1 = getattr(sim, "k1", 1.2)
        b_p = getattr(sim, "b", 0.75)
        return ("combined", nid, tuple(fspecs), T_pad, float(k1), float(b_p))

    if isinstance(node, LGeoDist):
        _scalar_f32(params, f"q{nid}_lat", node.lat)
        _scalar_f32(params, f"q{nid}_lon", node.lon)
        _scalar_f32(params, f"q{nid}_rad", node.radius_m)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("geodist", nid, node.field, node.field in seg.geo_cols,
                node.inclusive)

    if isinstance(node, LGeoBox):
        for k, v in (("top", node.top), ("left", node.left),
                     ("bottom", node.bottom), ("right", node.right)):
            _scalar_f32(params, f"q{nid}_{k}", v)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("geobox", nid, node.field, node.field in seg.geo_cols)

    if isinstance(node, LGeoPolygon):
        # closed ring, padded to a pow2 vertex bucket with copies of the
        # FIRST vertex: position n closes the ring and every pad edge after
        # it is v0->v0, degenerate, contributing zero ray crossings
        nv = len(node.lats) + 1
        vpad = next_pow2(max(nv, 2), floor=8)
        lats = np.full(vpad, node.lats[0], np.float32)
        lons = np.full(vpad, node.lons[0], np.float32)
        lats[: len(node.lats)] = node.lats
        lons[: len(node.lons)] = node.lons
        _p(params, f"q{nid}_plat", lats)
        _p(params, f"q{nid}_plon", lons)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("geopoly", nid, node.field, node.field in seg.geo_cols, vpad)

    if isinstance(node, LGeoShape):
        from . import geo as G
        mask = np.zeros(seg.ndocs_pad, bool)
        col = seg.shape_cols.get(node.field)
        if col is not None:
            if node.relation == "disjoint":
                # disjoint = present & !intersects: bbox survivors need the
                # exact test; non-overlapping bboxes are disjoint for free
                cands = np.nonzero(col.bbox_candidates(node.shape.bbox))[0]
                mask[: seg.ndocs][col.present] = True
                for d in cands:
                    if G.intersects(col.shape(int(d)), node.shape):
                        mask[d] = False
            else:
                cands = np.nonzero(col.bbox_candidates(node.shape.bbox))[0]
                for d in cands:
                    if G.relation_matches(col.shape(int(d)), node.shape,
                                          node.relation):
                        mask[d] = True
        elif node.field in seg.geo_cols:
            # geo_point docs are point shapes: fully vectorized
            gc = seg.geo_cols[node.field]
            pts = np.stack([gc.lon.astype(np.float64),
                            gc.lat.astype(np.float64)], axis=1)
            if node.relation in ("intersects", "within"):
                m = G.points_in_shape(pts, node.shape) | \
                    G._points_on_edges(pts, node.shape)
                mask[: seg.ndocs] = m & gc.present
            elif node.relation == "disjoint":
                m = G.points_in_shape(pts, node.shape) | \
                    G._points_on_edges(pts, node.shape)
                mask[: seg.ndocs] = (~m) & gc.present
            else:  # contains: a point only contains a point query at the
                # same location
                if len(node.shape.points) == 1 and not node.shape.polys \
                        and not node.shape.lines:
                    qx, qy = node.shape.points[0]
                    mask[: seg.ndocs] = ((gc.lon == np.float32(qx))
                                         & (gc.lat == np.float32(qy))
                                         & gc.present)
        _p(params, f"q{nid}_shapemask", mask)
        _scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("geoshape", nid)

    if isinstance(node, LSpanHost):
        from . import spans as SP
        freq = node._freqs.get(seg.uid)
        if freq is None:
            if isinstance(node.query, tuple):
                s, _ts = SP.eval_interval_rule(node.query[2], node.query[1],
                                               seg, ctx)
            else:
                _f, s, _ts = SP.eval_span_query(node.query, seg, ctx)
            freq = SP.freq_vector(s, seg.ndocs_pad)
            node._freqs[seg.uid] = freq
        if not freq.any():
            return ("match_none", nid)
        _p(params, f"q{nid}_freq", freq)
        _scalar_f32(params, f"q{nid}_w", node.weight)
        _scalar_f32(params, f"q{nid}_avgdl", ctx.avgdl(node.field))
        sim = node.sim
        b_eff = sim.b if node.has_norms else 0.0
        return ("span_host", nid, node.field, float(sim.k1), float(b_eff))

    raise TypeError(f"cannot prepare node {type(node).__name__}")


def parse_distance_m(s) -> float:
    """'10km' / '500m' / plain number (meters) -> meters (reference
    `common/unit/DistanceUnit.java`); shares query_dsl's unit table."""
    try:
        return dsl._parse_distance(s)
    except (ValueError, TypeError):
        raise dsl.QueryParseError(f"invalid distance [{s}]")


def _parse_time_ms(s) -> float:
    """'10d' / '3h' / number (ms) -> milliseconds (decay scale/offset);
    extends parse_interval_ms with fractional amounts and weeks."""
    if isinstance(s, (int, float)):
        return float(s)
    mm = re.fullmatch(r"\s*([\d.]+)\s*(ms|s|m|h|d|w)\s*", str(s))
    if not mm:
        raise dsl.QueryParseError(f"invalid time value [{s}]")
    mult = {"ms": 1, "w": 7 * 86_400_000}.get(mm.group(2)) or \
        _FIXED_MS[mm.group(2)]
    return float(mm.group(1)) * mult


def _prepare_decay(fn, i: int, nid: int, seg: Segment, ctx: ShardContext,
                   params: dict, fspec):
    """Host-side resolution of a gauss/exp/linear decay function: parse
    origin/scale/offset per field family and bake the shape constant so the
    device evaluates one exp()/mul per doc (reference
    `functionscore/DecayFunctionBuilder.java`). Missing values decay to 1."""
    import math as _math
    import time as _time

    from ..index.mappings import _parse_date

    field = ctx.mappings.aliases.get(fn.field, fn.field)
    ft = ctx.mappings.resolve_field(field)
    ftype = ft.type if ft is not None else "float"
    shape = fn.decay_shape
    try:
        if field in seg.geo_cols or ftype == "geo_point":
            kind = "geo"
            if fn.origin is None:
                raise dsl.QueryParseError("[decay] geo requires [origin]")
            lat, lon = dsl._parse_point(fn.origin)
            scale = parse_distance_m(fn.scale)
            offset = parse_distance_m(fn.offset or 0)
            _scalar_f32(params, f"q{nid}_fn{i}_olat", lat)
            _scalar_f32(params, f"q{nid}_fn{i}_olon", lon)
        elif ftype == "date":
            kind = "num"
            origin = (float(_time.time() * 1000)
                      if fn.origin in (None, "now")
                      else float(_parse_date(fn.origin, ft.date_format
                                             if ft is not None else None)))
            scale = _parse_time_ms(fn.scale)
            offset = _parse_time_ms(fn.offset or 0)
            _scalar_f32(params, f"q{nid}_fn{i}_origin", origin)
        else:
            kind = "num"
            if fn.origin is None:
                raise dsl.QueryParseError("[decay] numeric requires [origin]")
            scale = float(fn.scale)
            offset = float(fn.offset or 0)
            _scalar_f32(params, f"q{nid}_fn{i}_origin", float(fn.origin))
    except (ValueError, TypeError, KeyError) as e:
        # malformed origin/scale/offset is a client error (HTTP 400)
        raise dsl.QueryParseError(f"[{shape}] decay on [{field}]: {e}")
    if scale <= 0:
        raise dsl.QueryParseError("[decay] scale must be > 0")
    decay = min(max(float(fn.decay), 1e-12), 1.0 - 1e-12)
    if shape == "gauss":
        a = _math.log(decay) / (scale * scale)     # factor = exp(a * d^2)
    elif shape == "exp":
        a = _math.log(decay) / scale               # factor = exp(a * d)
    else:                                          # linear
        a = scale / (1.0 - decay)                  # factor = max(0, (a-d)/a)
    _scalar_f32(params, f"q{nid}_fn{i}_a", a)
    _scalar_f32(params, f"q{nid}_fn{i}_offset", offset)
    col_map = seg.geo_cols if kind == "geo" else seg.numeric_cols
    return ("decay", i, shape, kind, field, field in col_map, fspec)


@_instrumented_program_cache("join", maxsize=64)
def _build_join_scatter(gsize: int, need: Tuple[str, ...]):
    """Pass-1 kernel: scatter one segment's matched scores into the shard's
    join slot space (padding/unresolved slots are -1 -> sentinel -> dropped)."""
    import jax

    def join_program(gslot, scores, matched):
        import jax.numpy as jnp

        ok = (gslot >= 0) & (matched > 0)
        idx = jnp.where(ok, gslot, INT32_SENTINEL)
        sc = jnp.where(ok, scores, 0.0)
        out = {}
        if "cnt" in need:
            out["cnt"] = jnp.zeros(gsize, jnp.float32).at[idx].add(
                ok.astype(jnp.float32), mode="drop")
        if "sum" in need:
            out["sum"] = jnp.zeros(gsize, jnp.float32).at[idx].add(sc, mode="drop")
        if "max" in need:
            out["max"] = jnp.full(gsize, -3.4e38, jnp.float32).at[idx].max(
                jnp.where(ok, scores, -3.4e38), mode="drop")
        if "min" in need:
            out["min"] = jnp.full(gsize, 3.4e38, jnp.float32).at[idx].min(
                jnp.where(ok, scores, 3.4e38), mode="drop")
        return out

    return jax.jit(join_program)


def _join_prepass(child: LNode, ji, need: Tuple[str, ...], ctx: ShardContext,
                  self_slots: bool = False) -> dict:
    """Run the inner plan densely over every segment of the join index and
    accumulate slot-space vectors on device (no host round trip — the result
    arrays feed pass 2 as traced params)."""
    import jax.numpy as jnp

    acc: Dict[str, Any] = {}
    for seg in ji.segments:
        if seg.live_count == 0:
            continue
        cparams: Dict[str, Any] = {}
        cspec = prepare(child, seg, ctx, cparams)
        docs = np.arange(seg.ndocs_pad, dtype=np.int32)
        scores, matched = run_gather_scores(cspec, seg.device_arrays(), cparams, docs)
        if self_slots:
            base = ji.seg_base(seg)
            gslot = np.arange(base, base + seg.ndocs_pad, dtype=np.int32)
            gslot[seg.ndocs:] = -1
        else:
            gslot = ji.pslot(seg)
        vecs = _build_join_scatter(ji.gsize, need)(gslot, scores, matched)
        for k, v in vecs.items():
            if k not in acc:
                acc[k] = v
            elif k == "max":
                acc[k] = jnp.maximum(acc[k], v)
            elif k == "min":
                acc[k] = jnp.minimum(acc[k], v)
            else:
                acc[k] = acc[k] + v
    if not acc:
        fill = {"cnt": 0.0, "sum": 0.0, "max": -3.4e38, "min": 3.4e38}
        acc = {k: jnp.full(ji.gsize, fill[k], jnp.float32) for k in need}
    return acc


def _prepare_script(ast: tuple, script_params: dict, seg: Segment, params: dict,
                    nid: int, tag: str):
    """Bind a device script to one segment: resolve doc['f'] columns and
    trace numeric params (date epochs ride the f32 column view — ms-epoch
    precision ~2min at f32, fine for scoring)."""
    fields = pl.referenced_doc_fields(ast)
    field_srcs = tuple((f, "numeric" if f in seg.numeric_cols else "none")
                       for f in fields)
    pkeys = []
    for k in sorted(script_params):
        v = script_params[k]
        if isinstance(v, bool):
            v = float(v)
        if not isinstance(v, (int, float)):
            raise dsl.QueryParseError(
                f"script param [{k}] must be numeric in score/filter scripts")
        _scalar_f32(params, f"q{nid}_{tag}p_{k}", v)
        pkeys.append(k)
    return field_srcs, tuple(pkeys)


def _script_env(jnp, field_srcs, pkeys, nid: int, tag: str, seg_arrays: dict,
                params: dict, score, ndocs_pad: int) -> pl.DeviceEnv:
    cols: Dict[str, Any] = {}
    present: Dict[str, Any] = {}
    for f, src in field_srcs:
        if src == "numeric":
            cols[f] = seg_arrays["numeric"][f]["f32"]
            present[f] = seg_arrays["numeric"][f]["present"]
    sparams = {k: params[f"q{nid}_{tag}p_{k}"] for k in pkeys}
    return pl.DeviceEnv(jnp, cols, present, score, sparams, ndocs_pad)


def describe_plan(node: Optional[LNode]) -> dict:
    """Logical-plan tree for the profile API (reference search/profile/
    ProfileResult): type + human description + children. Times live on the
    root only — the whole tree executes as ONE fused XLA program."""
    if node is None:
        return {"type": "MatchAll", "description": "*:*"}
    t = type(node).__name__.lstrip("L")
    desc = ""
    if isinstance(node, LTerms):
        desc = f"{node.field}:{list(node.terms)[:8]}"
    elif isinstance(node, LPhrase):
        desc = f"{node.field}:\"{' '.join(node.terms)}\""
    elif isinstance(node, (LRange,)):
        desc = f"{node.field}:[{node.lo} TO {node.hi}]"
    elif hasattr(node, "field") and getattr(node, "field", ""):
        desc = str(getattr(node, "field"))
    children = []
    for attr in ("musts", "shoulds", "must_nots", "filters", "children"):
        for c in getattr(node, attr, ()) or ():
            children.append(describe_plan(c))
    for attr in ("child", "positive", "negative", "filter", "organic"):
        c = getattr(node, attr, None)
        if isinstance(c, LNode):
            children.append(describe_plan(c))
    out = {"type": t, "description": desc, "time_in_nanos": 0,
           "fused": True}
    if children:
        out["children"] = children
    return out


def can_match(node: LNode, seg: Segment) -> bool:
    """Shard/segment pre-filter (reference CanMatchPreFilterSearchPhase):
    cheaply prove a segment has zero hits."""
    if isinstance(node, LTerms):
        pb = seg.postings.get(node.field)
        if pb is None:
            return False
        if node.msm >= len(node.terms):
            return all(pb.row(t) >= 0 for t in node.terms)
        return any(pb.row(t) >= 0 for t in node.terms)
    if isinstance(node, LPhrase):
        pb = seg.postings.get(node.field)
        if pb is None or pb.pos_starts is None:
            return False
        last = len(node.terms) - 1
        for i, t in enumerate(node.terms):
            if node.prefix_last and i == last:
                if not _prefix_rows(pb, t, node.max_expansions):
                    return False
            elif pb.row(t) < 0:
                return False
        return True
    if isinstance(node, LRange):
        col = seg.numeric_cols.get(node.field)
        if col is None:
            return False
        mn, mx = col.min_max
        if node.lo is not None and float(node.lo) > mx:
            return False
        if node.hi is not None and float(node.hi) < mn:
            return False
        return True
    if isinstance(node, LBool):
        for c in node.musts + node.filters:
            if not can_match(c, seg):
                return False
        if node.shoulds and not node.musts and not node.filters:
            return any(can_match(c, seg) for c in node.shoulds)
        return True
    if isinstance(node, LConstScore):
        return can_match(node.child, seg)
    if isinstance(node, LNested):
        blk = seg.nested.get(node.path)
        if blk is None or blk.child.ndocs == 0:
            return False
        return can_match(node.child, blk.child)
    if isinstance(node, LPercolate):
        return (f"{node.field}#terms" in seg.keyword_cols
                or f"{node.field}#flags" in seg.keyword_cols)
    if isinstance(node, LHasChild):
        # pass 2 only reads parent docs of this segment; the child pre-pass
        # spans all segments regardless
        return can_match(node.parent_filter, seg)
    if isinstance(node, LHasParent):
        return can_match(node.child_filter, seg)
    if isinstance(node, LMatchNone):
        return False
    if isinstance(node, LExists):
        f = node.field
        return (f in seg.postings or f in seg.numeric_cols
                or f in seg.keyword_cols or f in seg.geo_cols
                or f in seg.vector_cols or f in seg.shape_cols
                or f in seg.doc_lens)
    if isinstance(node, LIds):
        return any(i in seg.id2doc for i in node.ids)
    if isinstance(node, LKnn):
        return node.field in seg.vector_cols
    if isinstance(node, (LGeoDist, LGeoBox, LGeoPolygon)):
        return node.field in seg.geo_cols
    if isinstance(node, LGeoShape):
        return (node.field in seg.shape_cols or node.field in seg.geo_cols)
    if isinstance(node, LDisMax):
        return any(can_match(c, seg) for c in node.children)
    if isinstance(node, LBoosting):
        return node.positive is None or can_match(node.positive, seg)
    if isinstance(node, LFuncScore):
        return node.child is None or can_match(node.child, seg)
    if isinstance(node, LTermsSet):
        return node.child is None or can_match(node.child, seg)
    if isinstance(node, LCombined):
        return any(seg.postings.get(f) is not None
                   and seg.postings[f].row(t) >= 0
                   for f, _w in node.fields for t in node.terms)
    if isinstance(node, (LRankFeature, LSparseDot)):
        # feature CSRs live in seg.postings; rank_feature on a numeric
        # column falls back to numeric_cols
        return node.field in seg.postings or node.field in seg.numeric_cols
    return True


# =====================================================================
# emit: spec -> traced device computation (runs under jit trace)
# =====================================================================

def _emit_seg_helpers(seg_arrays: dict):
    import jax.numpy as jnp

    ndocs_pad = seg_arrays["live"].shape[0]
    live = seg_arrays["live"]
    zeros = jnp.zeros(ndocs_pad, jnp.float32)
    return jnp, ndocs_pad, live, zeros


def emit(spec, seg_arrays: dict, params: dict) -> ops.ScoredMask:  # noqa: C901
    import jax.numpy as jnp

    kind = spec[0]
    nid = spec[1]
    ndocs_pad = seg_arrays["live"].shape[0]
    live = seg_arrays["live"]
    zeros = jnp.zeros(ndocs_pad, jnp.float32)

    if kind == "terms":
        _, _, field, T_pad, bucket, sim_id, k1, b, mode, layout = spec
        post = seg_arrays["postings"].get(field)
        if post is None:
            return ops.ScoredMask(zeros, zeros)
        dl = seg_arrays["doc_lens"].get(field, zeros)
        if mode == "filter":
            # codec-v2 layout: no resident tf plane — the tf-free gather
            # moves half the bytes for identical mask semantics
            if layout == "impact":
                mask = ops.term_match_mask(post, live,
                                           params[f"q{nid}_rows"], bucket,
                                           ndocs_pad)
            else:
                mask = ops.term_filter_mask(post, live, params[f"q{nid}_rows"], bucket, ndocs_pad)
            boost = params[f"q{nid}_boost"]
            m = mask.astype(jnp.float32)
            return ops.ScoredMask(m * boost, m)
        sm = ops.score_term_group(post, dl, live, params[f"q{nid}_rows"],
                                  params[f"q{nid}_w"], params[f"q{nid}_aux"],
                                  bucket, ndocs_pad, sim_id, k1, b,
                                  params[f"q{nid}_avgdl"])
        msm = params[f"q{nid}_msm"]
        ok = sm.count >= msm
        return ops.ScoredMask(jnp.where(ok, sm.scores, 0.0),
                              jnp.where(ok, sm.count, 0.0))

    if kind == "phrase":
        from ..ops import positions as pos_ops

        _, _, field, m_terms, buckets, k1, b, ordered, gap_cost = spec
        dl = seg_arrays["doc_lens"].get(field, zeros)
        anchor_d = params[f"q{nid}_d0"]
        anchor_p = params[f"q{nid}_p0"]
        others = [(params[f"q{nid}_d{i}"], params[f"q{nid}_p{i}"])
                  for i in range(1, m_terms)]
        shifts = [params[f"q{nid}_shift{i}"] for i in range(1, m_terms)]
        freq = pos_ops.phrase_freqs(anchor_d, anchor_p, others,
                                    params[f"q{nid}_slop"], ndocs_pad,
                                    ordered=ordered, gap_cost=gap_cost,
                                    shifts=shifts)
        scores, matched = pos_ops.phrase_score(freq, dl, live, params[f"q{nid}_w"],
                                               k1, b, params[f"q{nid}_avgdl"])
        return ops.ScoredMask(scores, matched.astype(jnp.float32))

    if kind == "cached_mask":
        m = params[f"q{nid}_cached_mask"]
        return ops.ScoredMask(zeros, m.astype(jnp.float32))

    if kind == "span_host":
        from ..ops import positions as pos_ops

        _, _, field, k1, b = spec
        dl = seg_arrays["doc_lens"].get(field, zeros)
        freq = params[f"q{nid}_freq"]
        scores, matched = pos_ops.phrase_score(freq, dl, live,
                                               params[f"q{nid}_w"], k1, b,
                                               params[f"q{nid}_avgdl"])
        return ops.ScoredMask(scores, matched.astype(jnp.float32))

    if kind == "xterms":
        _, _, field, T_pad, bucket, layout = spec
        post = seg_arrays["postings"].get(field)
        if post is None:
            return ops.ScoredMask(zeros, zeros)
        if layout == "impact":
            mask = ops.term_match_mask(post, live, params[f"q{nid}_rows"],
                                       bucket, ndocs_pad)
        else:
            mask = ops.term_filter_mask(post, live, params[f"q{nid}_rows"], bucket, ndocs_pad)
        m = mask.astype(jnp.float32)
        return ops.ScoredMask(m * params[f"q{nid}_boost"], m)

    if kind == "match_all":
        m = (live > 0).astype(jnp.float32)
        return ops.ScoredMask(m * params[f"q{nid}_boost"], m)

    if kind == "match_none":
        return ops.ScoredMask(zeros, zeros)

    if kind == "range":
        _, _, field, ckind, inc_lo, inc_hi, col_exists = spec
        if not col_exists:
            return ops.ScoredMask(zeros, zeros)
        col = seg_arrays["numeric"][field]
        if ckind == "int":
            mask = ops.int64_range_mask(col, params[f"q{nid}_lohi"], params[f"q{nid}_lolo"],
                                        params[f"q{nid}_hihi"], params[f"q{nid}_hilo"],
                                        inc_lo, inc_hi)
        else:
            mask = ops.float_range_mask(col, params[f"q{nid}_flo"], params[f"q{nid}_fhi"],
                                        inc_lo, inc_hi)
        mask = mask & (live > 0)
        m = mask.astype(jnp.float32)
        return ops.ScoredMask(m * params[f"q{nid}_boost"], m)

    if kind == "exists":
        _, _, field, src = spec
        if src == "numeric":
            present = seg_arrays["numeric"][field]["present"]
        elif src == "keyword":
            present = seg_arrays["keyword"][field]["min_ord"] >= 0
        elif src == "geo":
            present = seg_arrays["geo"][field]["present"]
        elif src == "dl":
            present = seg_arrays["doc_lens"][field] > 0
        else:
            return ops.ScoredMask(zeros, zeros)
        mask = present & (live > 0)
        m = mask.astype(jnp.float32)
        return ops.ScoredMask(m * params[f"q{nid}_boost"], m)

    if kind == "ids":
        mask = ops.docs_mask(params[f"q{nid}_docs"], ndocs_pad) & (live > 0)
        m = mask.astype(jnp.float32)
        return ops.ScoredMask(m * params[f"q{nid}_boost"], m)

    if kind == "bool":
        _, _, musts, shoulds, must_nots, filters = spec
        m_sms = [emit(s, seg_arrays, params) for s in musts]
        s_sms = [emit(s, seg_arrays, params) for s in shoulds]
        n_sms = [emit(s, seg_arrays, params) for s in must_nots]
        f_sms = [emit(s, seg_arrays, params) for s in filters]
        scores = zeros
        for sm in m_sms + s_sms:
            scores = scores + sm.scores
        matched = live > 0
        for sm in m_sms:
            matched = matched & sm.matched
        for sm in f_sms:
            matched = matched & sm.matched
        for sm in n_sms:
            matched = matched & (~sm.matched)
        if s_sms:
            s_count = zeros
            for sm in s_sms:
                s_count = s_count + sm.matched.astype(jnp.float32)
            matched = matched & (s_count >= params[f"q{nid}_msm"])
        scores = jnp.where(matched, scores * params[f"q{nid}_boost"], 0.0)
        return ops.ScoredMask(scores, matched.astype(jnp.float32))

    if kind == "const":
        sm = emit(spec[2], seg_arrays, params)
        m = sm.matched.astype(jnp.float32)
        return ops.ScoredMask(m * params[f"q{nid}_boost"], m)

    if kind == "dismax":
        children = [emit(s, seg_arrays, params) for s in spec[2]]
        tie = params[f"q{nid}_tie"]
        best = zeros
        total = zeros
        matched = jnp.zeros_like(live, dtype=bool)
        for sm in children:
            best = jnp.maximum(best, sm.scores)
            total = total + sm.scores
            matched = matched | sm.matched
        scores = best + tie * (total - best)
        scores = jnp.where(matched, scores * params[f"q{nid}_boost"], 0.0)
        return ops.ScoredMask(scores, matched.astype(jnp.float32))

    if kind == "boosting":
        pos = emit(spec[2], seg_arrays, params)
        neg = emit(spec[3], seg_arrays, params)
        nb = params[f"q{nid}_nb"]
        scores = pos.scores * jnp.where(neg.matched, nb, 1.0) * params[f"q{nid}_boost"]
        return ops.ScoredMask(jnp.where(pos.matched, scores, 0.0), pos.count)

    if kind == "fnscore":
        _, _, child_spec, fn_specs, score_mode, boost_mode = spec
        child = emit(child_spec, seg_arrays, params)
        factors = []
        for fs in fn_specs:
            fkind = fs[0]
            i = fs[1]
            if fkind == "fvf":
                _, _, ffield, modifier, col_exists, fspec = fs
                if col_exists:
                    col = seg_arrays["numeric"][ffield]
                    v = jnp.where(col["present"],
                                  col["f32"] * params[f"q{nid}_fn{i}_factor"],
                                  params[f"q{nid}_fn{i}_missing"])
                else:
                    v = jnp.full(ndocs_pad, params[f"q{nid}_fn{i}_missing"])
                v = _apply_modifier(jnp, v, modifier)
            elif fkind == "random":
                _, _, fspec = fs
                seed = params[f"q{nid}_fn{i}_seed"]
                h = (jnp.arange(ndocs_pad, dtype=jnp.uint32) * jnp.uint32(2654435761)
                     ^ seed.astype(jnp.uint32))
                h = h ^ (h >> 16)
                h = h * jnp.uint32(0x45D9F3B)
                h = h ^ (h >> 16)
                v = h.astype(jnp.float32) / jnp.float32(2**32)
            elif fkind == "fnscript":
                _, _, s_ast, s_fields, s_pkeys, fspec = fs
                env = _script_env(jnp, s_fields, s_pkeys, nid, f"fn{i}s",
                                  seg_arrays, params, child.scores, ndocs_pad)
                v = pl.eval_device(s_ast, env)
            elif fkind == "decay":
                _, _, shape, dk, dfield, col_exists, fspec = fs
                a = params[f"q{nid}_fn{i}_a"]
                off = params[f"q{nid}_fn{i}_offset"]
                if not col_exists:
                    v = jnp.ones(ndocs_pad, jnp.float32)
                    present = jnp.zeros(ndocs_pad, bool)
                elif dk == "geo":
                    g = seg_arrays["geo"][dfield]
                    r = 6371008.8
                    p1 = jnp.deg2rad(params[f"q{nid}_fn{i}_olat"])
                    p2 = jnp.deg2rad(g["lat"])
                    dphi = p2 - p1
                    dlmb = jnp.deg2rad(g["lon"] - params[f"q{nid}_fn{i}_olon"])
                    h = (jnp.sin(dphi / 2) ** 2
                         + jnp.cos(p1) * jnp.cos(p2) * jnp.sin(dlmb / 2) ** 2)
                    d = 2 * r * jnp.arcsin(jnp.sqrt(jnp.clip(h, 0.0, 1.0)))
                    present = g["present"]
                else:
                    col = seg_arrays["numeric"][dfield]
                    d = jnp.abs(col["f32"] - params[f"q{nid}_fn{i}_origin"])
                    present = col["present"]
                if col_exists:
                    d = jnp.maximum(d - off, 0.0)
                    if shape == "gauss":
                        v = jnp.exp(a * d * d)
                    elif shape == "exp":
                        v = jnp.exp(a * d)
                    else:  # linear
                        v = jnp.maximum((a - d) / a, 0.0)
                    # docs without a value don't decay (factor 1)
                    v = jnp.where(present, v, 1.0)
            else:  # weight
                _, _, fspec = fs
                v = jnp.ones(ndocs_pad, jnp.float32)
            v = v * params[f"q{nid}_fn{i}_w"]
            if fspec is not None:
                fmask = emit(fspec, seg_arrays, params).matched
                neutral = _score_mode_neutral(score_mode)
                v = jnp.where(fmask, v, neutral)
            factors.append(v)
        if factors:
            fac = _combine_factors(jnp, factors, score_mode, ndocs_pad)
        else:
            fac = jnp.ones(ndocs_pad, jnp.float32)
        scores = _combine_boost(jnp, child.scores, fac, boost_mode)
        scores = scores * params[f"q{nid}_boost"]
        matched = child.matched & (scores >= params[f"q{nid}_minscore"])
        scores = jnp.where(matched, scores, 0.0)
        return ops.ScoredMask(scores, matched.astype(jnp.float32))

    if kind == "nested":
        _, _, path, score_mode, child_spec = spec
        carr = dict(seg_arrays["nested"][path])
        parent = carr["parent"]
        # child liveness inherits the parent's delete mask via a gather
        carr["live"] = carr["live"] * live[parent]
        sm = emit(child_spec, carr, params)
        cmatch = sm.matched
        cscore = jnp.where(cmatch, sm.scores, 0.0)
        cnt = zeros.at[parent].add(cmatch.astype(jnp.float32))
        pmatch = cnt > 0
        if score_mode == "none":
            pscores = pmatch.astype(jnp.float32)
        elif score_mode == "max":
            neg_inf = jnp.full(ndocs_pad, -jnp.inf, jnp.float32)
            mx = neg_inf.at[parent].max(jnp.where(cmatch, sm.scores, -jnp.inf))
            pscores = jnp.where(pmatch, mx, 0.0)
        elif score_mode == "min":
            pos_inf = jnp.full(ndocs_pad, jnp.inf, jnp.float32)
            mn = pos_inf.at[parent].min(jnp.where(cmatch, sm.scores, jnp.inf))
            pscores = jnp.where(pmatch, mn, 0.0)
        else:
            total = zeros.at[parent].add(cscore)
            pscores = total / jnp.maximum(cnt, 1.0) if score_mode == "avg" else total
        pmatch = pmatch & (live > 0)
        pscores = jnp.where(pmatch, pscores * params[f"q{nid}_boost"], 0.0)
        return ops.ScoredMask(pscores, pmatch.astype(jnp.float32))

    if kind == "has_child":
        from jax import lax

        _, _, score_mode, pf_spec = spec
        base = params[f"q{nid}_base"]
        cnt = lax.dynamic_slice(params[f"q{nid}_cnt"], (base,), (ndocs_pad,))
        pmask = emit(pf_spec, seg_arrays, params).matched
        ok = ((cnt >= params[f"q{nid}_minc"]) & (cnt <= params[f"q{nid}_maxc"])
              & (pmask > 0) & (live > 0))
        if score_mode == "none":
            sc = jnp.ones(ndocs_pad, jnp.float32)
        elif score_mode in ("sum", "avg"):
            sc = lax.dynamic_slice(params[f"q{nid}_sum"], (base,), (ndocs_pad,))
            if score_mode == "avg":
                sc = sc / jnp.maximum(cnt, 1.0)
        else:  # max | min
            sc = lax.dynamic_slice(params[f"q{nid}_{score_mode}"], (base,),
                                   (ndocs_pad,))
        sc = jnp.where(ok, sc * params[f"q{nid}_boost"], 0.0)
        return ops.ScoredMask(sc, ok.astype(jnp.float32))

    if kind == "has_parent":
        _, _, use_score, cf_spec = spec
        pslot = params[f"q{nid}_pslot"]
        gmatch = params[f"q{nid}_match"]
        gscore = params[f"q{nid}_score"]
        valid = pslot >= 0
        idx = jnp.clip(pslot, 0, gmatch.shape[0] - 1)
        cmask = emit(cf_spec, seg_arrays, params).matched
        ok = valid & (gmatch[idx] > 0) & (cmask > 0) & (live > 0)
        sc = gscore[idx] if use_score else jnp.ones(ndocs_pad, jnp.float32)
        sc = jnp.where(ok, sc * params[f"q{nid}_boost"], 0.0)
        return ops.ScoredMask(sc, ok.astype(jnp.float32))

    if kind == "rank_feature_post":
        _, _, field, bucket, fn, positive, pb_exists = spec
        post = seg_arrays["postings"].get(field)
        if not pb_exists or post is None:
            return ops.ScoredMask(zeros, zeros)
        p1, p2 = params[f"q{nid}_p1"], params[f"q{nid}_p2"]
        sm = ops.feature_score(
            post, live, params[f"q{nid}_rows"], bucket, ndocs_pad,
            lambda w, ti: ops.rank_feature_value(w, fn, p1, p2, positive))
        return ops.ScoredMask(sm.scores * params[f"q{nid}_boost"], sm.count)

    if kind == "rank_feature_col":
        _, _, field, fn, positive, col_exists = spec
        if not col_exists:
            return ops.ScoredMask(zeros, zeros)
        col = seg_arrays["numeric"][field]
        v = ops.rank_feature_value(col["f32"], fn, params[f"q{nid}_p1"],
                                   params[f"q{nid}_p2"], positive)
        mask = col["present"] & (live > 0)
        return ops.ScoredMask(jnp.where(mask, v * params[f"q{nid}_boost"], 0.0),
                              mask.astype(jnp.float32))

    if kind == "sparse_dot":
        _, _, field, T_pad, bucket = spec
        post = seg_arrays["postings"].get(field)
        if post is None:
            return ops.ScoredMask(zeros, zeros)
        qw = params[f"q{nid}_w"]
        sm = ops.feature_score(post, live, params[f"q{nid}_rows"], bucket,
                               ndocs_pad, lambda w, ti: qw[ti] * w)
        return ops.ScoredMask(sm.scores * params[f"q{nid}_boost"], sm.count)

    if kind == "distfeat_date":
        _, _, field, col_exists = spec
        if not col_exists:
            return ops.ScoredMask(zeros, zeros)
        col = seg_arrays["numeric"][field]
        dhi = (col["hi"] - params[f"q{nid}_ohi"]).astype(jnp.float32)
        dlo = col["lo"].astype(jnp.float32) - jnp.float32(params[f"q{nid}_olo"])
        dist = jnp.abs(dhi * 4294967296.0 + dlo)
        pivot = params[f"q{nid}_pivot"]
        mask = col["present"] & (live > 0)
        sc = params[f"q{nid}_boost"] * pivot / (pivot + dist)
        return ops.ScoredMask(jnp.where(mask, sc, 0.0), mask.astype(jnp.float32))

    if kind == "distfeat_geo":
        _, _, field, col_exists = spec
        if not col_exists:
            return ops.ScoredMask(zeros, zeros)
        geo = seg_arrays["geo"][field]
        r = 6371008.8
        p1r = jnp.deg2rad(geo["lat"])
        p2r = jnp.deg2rad(params[f"q{nid}_lat"])
        dphi = p2r - p1r
        dlmb = jnp.deg2rad(params[f"q{nid}_lon"] - geo["lon"])
        a = jnp.sin(dphi / 2) ** 2 + jnp.cos(p1r) * jnp.cos(p2r) * jnp.sin(dlmb / 2) ** 2
        dist = 2 * r * jnp.arcsin(jnp.sqrt(jnp.clip(a, 0.0, 1.0)))
        pivot = params[f"q{nid}_pivot"]
        mask = geo["present"] & (live > 0)
        sc = params[f"q{nid}_boost"] * pivot / (pivot + dist)
        return ops.ScoredMask(jnp.where(mask, sc, 0.0), mask.astype(jnp.float32))

    if kind == "percolate":
        mask = (params[f"q{nid}_mask"] > 0) & (live > 0)
        m = mask.astype(jnp.float32)
        return ops.ScoredMask(m * params[f"q{nid}_boost"], m)

    if kind == "script":
        _, _, ast, field_srcs, pkeys = spec
        env = _script_env(jnp, field_srcs, pkeys, nid, "s", seg_arrays, params,
                          None, ndocs_pad)
        vec = pl.eval_device(ast, env)
        mask = (vec != 0) & (live > 0)
        m = mask.astype(jnp.float32)
        return ops.ScoredMask(m * params[f"q{nid}_boost"], m)

    if kind == "scriptscore":
        _, _, child_spec, ast, field_srcs, pkeys = spec
        child = emit(child_spec, seg_arrays, params)
        env = _script_env(jnp, field_srcs, pkeys, nid, "s", seg_arrays, params,
                          child.scores, ndocs_pad)
        scores = pl.eval_device(ast, env) * params[f"q{nid}_boost"]
        matched = child.matched & (scores >= params[f"q{nid}_minscore"])
        return ops.ScoredMask(jnp.where(matched, scores, 0.0),
                              matched.astype(jnp.float32))

    if kind == "knn":
        import jax
        from jax import lax as _lax
        _, _, field, col_exists, simkind, fspec, probe = spec
        if not col_exists:
            return ops.ScoredMask(zeros, zeros)
        vc = seg_arrays["vector"][field]
        qvec = params[f"q{nid}_vec"]

        def _sim_score(raw, vecs_sq):
            if simkind == "cosine":
                return (1.0 + raw) / 2.0
            if simkind in ("dot_product", "innerproduct"):
                return jnp.where(raw > 0, raw + 1.0, 1.0 / (1.0 - raw))
            d2 = jnp.maximum(vecs_sq() + params[f"q{nid}_qsq"] - 2.0 * raw,
                             0.0)
            return 1.0 / (1.0 + d2)

        def _product(vecs):
            return jnp.dot(vecs, qvec, preferred_element_type=jnp.float32,
                           precision=_KNN_SCORE_PRECISION)

        if probe is not None and "ivf_centroids" in vc:
            # balanced-IVF probe (ops/ann.py): centroid matvec -> static
            # top-nprobe -> each probed list's window of `cap` rows, read
            # where it lies by the product -> scatter back into doc space.
            # Everything static-shape; candidate count = nprobe*cap
            # regardless of data.
            nprobe, cap = probe
            cents = vc["ivf_centroids"]
            rows, ids = vc["ivf_rows"], vc["ivf_ids"]
            with jax.named_scope("knn.centroids"):
                # default precision: this product only chooses lists
                cdot = jnp.dot(cents, qvec,
                               preferred_element_type=jnp.float32)
                if simkind in ("cosine", "dot_product", "innerproduct"):
                    caff = cdot
                else:  # l2: nearest centroid = max of 2c.q - ||c||^2
                    caff = 2.0 * cdot - jnp.sum(cents * cents, axis=1)
                caff = jnp.where(vc["ivf_cvalid"], caff, -jnp.inf)
                _, pids = _lax.top_k(caff, nprobe)

            def one_list(_, start):
                # the window runs past a short list's fill into the next
                # list's rows (the last list's into the zero tail): those
                # slots are masked below, by the fill
                with jax.named_scope("knn.gather"):
                    win = _lax.dynamic_slice(rows, (start, 0),
                                             (cap, rows.shape[1]))
                    cand = _lax.dynamic_slice(ids, (start,), (cap,))
                with jax.named_scope("knn.score"):
                    s = _sim_score(_product(win),
                                   lambda: jnp.sum(win * win, axis=1))
                return None, (s, cand)

            with jax.named_scope("knn.gather"):
                starts, fills = vc["ivf_offset"][pids], vc["ivf_fill"][pids]
            # two lists a step: what a step costs beside its product is
            # the loop's own time (PERF.md section 6, PR 42: the forms)
            _, (s, cand) = _lax.scan(one_list, None, starts, unroll=2)
            with jax.named_scope("knn.score"):
                valid = (jnp.arange(cap) < fills[:, None]).reshape(-1)
                cand = cand.reshape(-1)                   # i32[nprobe*cap]
                s = jnp.where(valid, s.reshape(-1), 0.0)
            with jax.named_scope("knn.scatter"):
                cidx = jnp.where(valid, cand, ndocs_pad)  # OOB -> dropped
                # each doc lives in exactly one list -> max==set, but max is
                # insensitive to the padding sentinel collisions
                score = zeros.at[cidx].max(s, mode="drop")
                cmask = zeros.at[cidx].max(valid.astype(jnp.float32),
                                           mode="drop")
            matched = (cmask > 0) & vc["present"] & (live > 0)
        else:
            # one MXU matvec per segment: exact brute-force kNN (the
            # reference k-NN plugin approximates with HNSW; at HBM bandwidth
            # the dense scan is the TPU-native answer for exact)
            with jax.named_scope("knn.scan"):
                mat = vc["mat"]
                score = _sim_score(_product(mat),
                                   lambda: jnp.sum(mat * mat, axis=1))
            matched = vc["present"] & (live > 0)
        if fspec is not None:
            matched = matched & emit(fspec, seg_arrays, params).matched
        score = jnp.where(matched, score * params[f"q{nid}_boost"], 0.0)
        return ops.ScoredMask(score, matched.astype(jnp.float32))

    if kind == "terms_set":
        _, _, child_spec = spec
        sm = emit(child_spec, seg_arrays, params)   # child msm=0: raw counts
        need = jnp.maximum(params[f"q{nid}_ts_msm"], 1.0)
        ok = (sm.count >= need) & (live > 0)
        return ops.ScoredMask(jnp.where(ok, sm.scores, 0.0),
                              ok.astype(jnp.float32))

    if kind == "pinned":
        _, _, organic_spec, _pad = spec
        org = (emit(organic_spec, seg_arrays, params) if organic_spec
               is not None else ops.ScoredMask(zeros, zeros))
        docs = params[f"q{nid}_pin_docs"]
        ranks = params[f"q{nid}_pin_ranks"]
        valid = (docs >= 0) & (docs < ndocs_pad)
        didx = jnp.where(valid, docs, ndocs_pad)
        # pinned scores sit far above any organic BM25 score, descending in
        # list order (reference PinnedQueryBuilder MAX_ORGANIC_SCORE). Base
        # chosen so a rank step of 1 survives f32 (ulp(1e6) = 0.0625; at
        # 1e9 it would be 64 and all pins would tie)
        pin_score = jnp.where(valid, 1e6 - ranks, 0.0)
        pins = zeros.at[didx].max(pin_score, mode="drop")
        pinned_mask = (pins > 0) & (live > 0)
        score = jnp.where(pinned_mask, pins,
                          org.scores * params[f"q{nid}_boost"])
        matched = pinned_mask | (org.matched > 0)
        return ops.ScoredMask(jnp.where(matched, score, 0.0),
                              matched.astype(jnp.float32))

    if kind == "combined":
        _, _, fspecs, T_pad, k1, b_p = spec
        tfc = jnp.zeros((T_pad, ndocs_pad), jnp.float32)
        dlc = zeros
        any_field = False
        for fi, (fname, bucket, has_post) in enumerate(fspecs):
            if not has_post:
                continue
            any_field = True
            post = seg_arrays["postings"][fname]
            w = params[f"q{nid}_cf_w{fi}"]
            tfc = tfc + w * ops.gather_tf_dense(post,
                                                params[f"q{nid}_cf_rows{fi}"],
                                                bucket, ndocs_pad, T_pad)
            dlc = dlc + w * seg_arrays["doc_lens"].get(fname, zeros)
        if not any_field:
            return ops.ScoredMask(zeros, zeros)
        norm = k1 * (1.0 - b_p + b_p * dlc / params[f"q{nid}_cf_avgdl"])
        # LUCENE-8563 form (no (k1+1) factor) — every other scoring path
        # here uses it, so combined_fields stays rank-commensurate in
        # mixed bool queries
        sat = tfc / (tfc + norm[None, :])
        idf = params[f"q{nid}_cf_idf"]
        scores = jnp.sum(jnp.where(tfc > 0, idf[:, None] * sat, 0.0), axis=0)
        counts = jnp.sum((tfc > 0).astype(jnp.float32), axis=0)
        ok = (counts >= params[f"q{nid}_cf_msm"]) & (live > 0)
        return ops.ScoredMask(jnp.where(ok, scores, 0.0),
                              ok.astype(jnp.float32))

    if kind == "geodist":
        _, _, field, col_exists, inclusive = spec
        if not col_exists:
            return ops.ScoredMask(zeros, zeros)
        geo = seg_arrays["geo"][field]
        mask = ops.geo_distance_mask(geo, params[f"q{nid}_lat"], params[f"q{nid}_lon"],
                                     params[f"q{nid}_rad"],
                                     inclusive=inclusive) & (live > 0)
        m = mask.astype(jnp.float32)
        return ops.ScoredMask(m * params[f"q{nid}_boost"], m)

    if kind == "geobox":
        _, _, field, col_exists = spec
        if not col_exists:
            return ops.ScoredMask(zeros, zeros)
        geo = seg_arrays["geo"][field]
        lat, lon = geo["lat"], geo["lon"]
        mask = ((lat <= params[f"q{nid}_top"]) & (lat >= params[f"q{nid}_bottom"]) &
                (lon >= params[f"q{nid}_left"]) & (lon <= params[f"q{nid}_right"]) &
                geo["present"] & (live > 0))
        m = mask.astype(jnp.float32)
        return ops.ScoredMask(m * params[f"q{nid}_boost"], m)

    if kind == "geopoly":
        _, _, field, col_exists, _vpad = spec
        if not col_exists:
            return ops.ScoredMask(zeros, zeros)
        mask = ops.point_in_polygon_mask(seg_arrays["geo"][field],
                                         params[f"q{nid}_plat"],
                                         params[f"q{nid}_plon"]) & (live > 0)
        m = mask.astype(jnp.float32)
        return ops.ScoredMask(m * params[f"q{nid}_boost"], m)

    if kind == "geoshape":
        mask = params[f"q{nid}_shapemask"] & (live > 0)
        m = mask.astype(jnp.float32)
        return ops.ScoredMask(m * params[f"q{nid}_boost"], m)

    raise ValueError(f"cannot emit spec kind [{kind}]")


def _apply_modifier(jnp, v, modifier: str):
    if modifier == "none":
        return v
    if modifier == "log":
        return jnp.log10(jnp.maximum(v, 1e-9))
    if modifier == "log1p":
        return jnp.log10(v + 1.0)
    if modifier == "log2p":
        return jnp.log10(v + 2.0)
    if modifier == "ln":
        return jnp.log(jnp.maximum(v, 1e-9))
    if modifier == "ln1p":
        return jnp.log1p(v)
    if modifier == "ln2p":
        return jnp.log(v + 2.0)
    if modifier == "square":
        return v * v
    if modifier == "sqrt":
        return jnp.sqrt(jnp.maximum(v, 0.0))
    if modifier == "reciprocal":
        return 1.0 / jnp.maximum(v, 1e-9)
    raise ValueError(f"unknown modifier [{modifier}]")


def _score_mode_neutral(mode: str) -> float:
    return 1.0 if mode == "multiply" else 0.0


def _combine_factors(jnp, factors, mode: str, ndocs_pad: int):
    if mode == "multiply":
        out = factors[0]
        for f in factors[1:]:
            out = out * f
        return out
    if mode in ("sum", "avg"):
        out = factors[0]
        for f in factors[1:]:
            out = out + f
        return out / len(factors) if mode == "avg" else out
    if mode == "max":
        out = factors[0]
        for f in factors[1:]:
            out = jnp.maximum(out, f)
        return out
    if mode == "min":
        out = factors[0]
        for f in factors[1:]:
            out = jnp.minimum(out, f)
        return out
    if mode == "first":
        return factors[0]
    raise ValueError(f"unknown score_mode [{mode}]")


def _combine_boost(jnp, score, factor, mode: str):
    if mode == "multiply":
        return score * factor
    if mode == "sum":
        return score + factor
    if mode == "replace":
        return factor
    if mode == "avg":
        return (score + factor) / 2.0
    if mode == "max":
        return jnp.maximum(score, factor)
    if mode == "min":
        return jnp.minimum(score, factor)
    raise ValueError(f"unknown boost_mode [{mode}]")


# =====================================================================
# sort
# =====================================================================

def _nested_sort_values(seg: Segment, field: str, path: str, mode: str):
    """Per-parent aggregate of a nested child numeric column (reference
    NestedSortBuilder): min/max/sum/avg over each parent's block children.
    Cached per (field, path, mode). -> (values f64[ndocs], present bool) or
    (None, None). The per-segment lock keeps concurrent first computations
    of one key from double-charging the breaker (only one cache write
    wins, but both finalizers would release)."""
    cache = seg.__dict__.setdefault("_nested_sort_cache", {})
    key = (field, path, mode)
    if key in cache:
        return cache[key]
    lock = seg.__dict__.setdefault("_nested_sort_lock",
                                   __import__("threading").Lock())
    with lock:
        if key in cache:
            return cache[key]
        return _nested_sort_values_build(seg, cache, key, field, path,
                                         mode)


def _nested_sort_values_build(seg: Segment, cache: dict, key, field: str,
                              path: str, mode: str):
    blk = seg.nested.get(path)
    col = blk.child.numeric_cols.get(field) if blk is not None else None
    if col is None:
        cache[key] = (None, None)
        return cache[key]
    n = seg.ndocs
    parent = blk.parent_of[: blk.child.ndocs]
    pres_child = col.present[: blk.child.ndocs] & blk.child.live[: blk.child.ndocs]
    vals_child = col.values[: blk.child.ndocs].astype(np.float64)
    out = np.full(n, np.inf if mode == "min" else
                  (-np.inf if mode == "max" else 0.0), np.float64)
    present = np.zeros(n, bool)
    p = parent[pres_child]
    v = vals_child[pres_child]
    if mode == "min":
        np.minimum.at(out, p, v)
    elif mode == "max":
        np.maximum.at(out, p, v)
    else:                              # sum / avg
        np.add.at(out, p, v)
    present[np.unique(p)] = True
    if mode == "avg":
        cnt = np.zeros(n, np.float64)
        np.add.at(cnt, p, 1.0)
        out = np.divide(out, np.maximum(cnt, 1.0))
    out = np.where(present, out, 0.0)
    # parent-docs-scale columns cached for the segment's lifetime:
    # register with the HBM ledger (same fielddata budget the fastpath
    # layouts charge, derived by the ledger), released when the
    # (immutable) segment is GC'd — the cache dict lives on it
    from ..obs.hbm_ledger import LEDGER
    LEDGER.register("nested_sort", out.nbytes + present.nbytes, owner=seg,
                    segment=seg,
                    label=f"nested-sort[{seg.name}][{path}.{field}]")
    cache[key] = (out, present)
    return cache[key]


def prepare_sort(sort_specs: List[dict], seg: Segment, params: dict):
    """Bind sort to a segment. Device ranks by the PRIMARY key exactly (rank
    ordinals for numerics — see NumericColumn.sort_ords); the executor
    re-orders the k-window on the host with the full key tuple."""
    if not sort_specs:
        return ("score",)
    primary = sort_specs[0]
    field = primary["field"]
    if field == "_score":
        return ("score",) if primary.get("order", "desc") == "desc" else ("score_asc",)
    if field == "_doc":
        return ("doc",)
    desc = primary.get("order", "asc") == "desc"
    missing = primary.get("missing", "_last")
    missing_last = missing == "_last"
    if field == "_geo_distance":
        # device primary key = f32 haversine meters (host re-orders the
        # window exactly); reference GeoDistanceSortBuilder
        gfield = primary["geo_field"]
        if gfield not in seg.geo_cols:
            return ("missing_field", desc, missing_last)
        lat, lon = primary["origin"]
        _p(params, "sort_geo_olat", np.float32(lat))
        _p(params, "sort_geo_olon", np.float32(lon))
        return ("geo_dist", gfield, desc, missing_last)
    nspec = primary.get("nested")
    if nspec and nspec.get("path"):
        vals, present = _nested_sort_values(seg, field, nspec["path"],
                                            primary.get("mode",
                                                        "max" if desc
                                                        else "min"))
        if vals is None:
            return ("missing_field", desc, missing_last)
        ords = np.full(seg.ndocs, -1, np.int32)
        if present.any():
            uniq = np.unique(vals[present])
            ords[present] = np.searchsorted(uniq, vals[present]).astype(np.int32)
        import jax.numpy as _jnp
        pad = np.full(seg.ndocs_pad, -1, dtype=np.int32)
        pad[: seg.ndocs] = ords
        params["sort_ords"] = _jnp.asarray(pad)
        return ("field_ord", desc, missing_last)
    if field in seg.numeric_cols:
        params["sort_ords"], = _segment_plane(
            seg, "_sort_dev_cache", (field,), "sort_rank_plane",
            RANK_PLANE_STATS,
            lambda: (seg.numeric_cols[field].sort_ords(),))
        return ("field_ord", desc, missing_last)
    if field in seg.keyword_cols:
        return ("kw_ord", field, desc, missing_last)
    return ("missing_field", desc, missing_last)


def emit_sort_key(sort_spec, seg_arrays: dict, params: dict, scores):
    import jax.numpy as jnp

    kind = sort_spec[0]
    ndocs_pad = seg_arrays["live"].shape[0]
    if kind == "score":
        return scores
    if kind == "score_asc":
        return -scores
    if kind == "doc":
        return -jnp.arange(ndocs_pad, dtype=jnp.float32)
    big = jnp.float32(2.0**30)
    if kind == "geo_dist":
        _, gfield, desc, missing_last = sort_spec
        g = seg_arrays["geo"][gfield]
        dist = ops.geo_distance_vec(g, params["sort_geo_olat"],
                                    params["sort_geo_olon"])
        key = dist if desc else -dist
        missing_key = -big if missing_last else big
        return jnp.where(g["present"], key, missing_key)
    if kind == "field_ord":
        _, desc, missing_last = sort_spec
        ords = params["sort_ords"].astype(jnp.float32)
        present = params["sort_ords"] >= 0
    elif kind == "kw_ord":
        _, field, desc, missing_last = sort_spec
        mo = seg_arrays["keyword"][field]["min_ord"]
        ords = mo.astype(jnp.float32)
        present = mo >= 0
    else:
        _, desc, missing_last = sort_spec
        ords = jnp.zeros(ndocs_pad, jnp.float32)
        present = jnp.zeros(ndocs_pad, bool)
    key = ords if desc else -ords
    missing_key = -big if missing_last else big
    return jnp.where(present, key, missing_key)


# =====================================================================
# aggregations: prepare + emit
# =====================================================================

def _segment_plane(seg: Segment, cache_name: str, key, kind: str, stats,
                   build: Callable[[], tuple]) -> tuple:
    """One i32[ndocs_pad] plane of per-document ids (-1 = none) kept on the
    device for the segment's lifetime, with whatever `build` returns after
    its host ids (a numpy array among it goes to the device and is charged
    with the plane): -> (device plane, *rest). Cached under
    `seg.<cache_name>[key]` (a tuple that starts with the field, or with the
    tuple of the fields of a plane over several) and attributed in the HBM
    ledger as `kind`;
    `derived._purge_query_caches` drops a rematerialized field's planes
    and the segment's GC the rest. `stats` counts builds, hits and bytes.
    The per-segment lock keeps two first requests from building (and
    charging) one plane twice."""
    cache = seg.__dict__.setdefault(cache_name, {})
    hit = cache.get(key)
    if hit is not None:
        stats.inc("hits")
        return hit
    lock = seg.__dict__.setdefault("_plane_build_lock",
                                   __import__("threading").Lock())
    with lock:
        hit = cache.get(key)
        if hit is not None:
            stats.inc("hits")
            return hit
        import jax.numpy as jnp

        from ..obs.hbm_ledger import LEDGER
        ids, *rest = build()
        pad = np.full(seg.ndocs_pad, -1, dtype=np.int32)
        pad[: len(ids)] = ids
        plane = jnp.asarray(pad)
        nbytes = pad.nbytes + sum(x.nbytes for x in rest
                                  if isinstance(x, np.ndarray))
        rest = [jnp.asarray(x) if isinstance(x, np.ndarray) else x
                for x in rest]
        alloc = LEDGER.register(kind, nbytes, owner=seg, segment=seg,
                                label=f"{kind}[{seg.name}][{key}]")
        seg.__dict__.setdefault("_plane_allocs", {})[cache_name, key] = alloc
        stats.inc("builds")
        stats.inc("bytes", nbytes)
        cache[key] = (plane, *rest)
        return cache[key]


def drop_segment_planes(seg: Segment, field: str) -> None:
    """Drop `field`'s rank, bucket and combination planes (a combination
    plane is every one of its fields') and release their ledger bytes (a
    rematerialized derived field: `derived._purge_query_caches`)."""
    from ..obs.hbm_ledger import LEDGER
    allocs = seg.__dict__.get("_plane_allocs", {})
    for cache_name in ("_sort_dev_cache", "_date_bucket_cache",
                       "_combo_plane_cache"):
        cache = seg.__dict__.get(cache_name, {})
        for key in [k for k in cache if field == k[0] or (
                isinstance(k[0], tuple) and field in k[0])]:
            del cache[key]
            LEDGER.release(allocs.pop((cache_name, key), None))
    # the host-side state that names the field: the mesh path's copies of
    # a `multi_terms` space, the multi-valued flag
    mesh = seg.__dict__.get("_multi_terms_cache", {})
    for fields in [k for k in mesh if field in k]:
        del mesh[fields]
    seg.__dict__.get("_kw_multi_cache", {}).pop(field, None)


def _date_bucket_plane(seg: Segment, field: str, interval_ms: int,
                       offset_ms: int, calendar: Optional[str]):
    """Exact date bucketing on host i64, once per (segment, field, interval,
    offset, calendar), then resident: -> (bucket ids i32[ndocs_pad] on the
    device, -1 = no value, min_bucket, nbuckets, starts). Calendar intervals
    follow real calendars (reference Rounding.Builder). `starts` is
    `_run_starts` of the ids, on the device too, where the segment's values
    are in row order (an append-only log), else None."""
    def build():
        ids, mn, nb = _date_bucket_ids(seg, field, interval_ms, offset_ms,
                                       calendar)
        return ids, mn, nb, _run_starts(ids, nb, seg.ndocs_pad)
    return _segment_plane(seg, "_date_bucket_cache",
                          (field, interval_ms, offset_ms, calendar),
                          "agg_bucket_plane", BUCKET_PLANE_STATS, build)


def _date_bucket_ids(seg: Segment, field: str, interval_ms: int,
                     offset_ms: int, calendar: Optional[str]):
    """(bucket ids i32[ndocs] from the least bucket, -1 = no value, the
    least bucket, the number of buckets) of a date column, on host i64."""
    col = seg.numeric_cols.get(field)
    if col is None or not col.present.any():
        return np.full(seg.ndocs, -1, np.int32), 0, 1
    vals = col.values.astype(np.int64)
    if calendar is None:
        b = np.floor_divide(vals - offset_ms, interval_ms)
    else:
        b = _calendar_bucket_ids(vals, calendar)
    bp = b[col.present]
    mn, mx = int(bp.min()), int(bp.max())
    ids = np.where(col.present, b - mn, -1).astype(np.int32)
    return ids, mn, int(mx - mn + 1)


def _run_starts(ids: np.ndarray, nbuckets: int,
                ndocs_pad: int) -> Optional[np.ndarray]:
    """i32[nbuckets + 1] for `ops.aggs.run_counts`: `starts[b]` is the first
    row whose id, or the id of the nearest row before it that has one, is
    at least b, so `starts[nbuckets]` = `len(ids)`. None where the ids of
    the rows that have a value (id >= 0; the others weigh nothing) are not
    non-decreasing in row order, or `run_blocks` has no cut for the sizes:
    such a plane is counted by scatter-add."""
    if agg_ops.run_blocks(ndocs_pad, nbuckets + 1) is None:
        return None
    # the running maximum forward-fills the rows without a value (-1), and
    # a row in order is one that is its own running maximum
    filled = np.maximum.accumulate(ids)
    if ((ids >= 0) & (ids < filled)).any():
        return None
    return np.searchsorted(filled, np.arange(nbuckets + 1),
                           side="left").astype(np.int32)


_DAY_MS = 86400000


def _calendar_bucket_ids(ms: np.ndarray, calendar: str) -> np.ndarray:
    """Calendar bucket ids of epoch-millisecond values (UTC), as whole
    columns: fixed-length units by floor division, months and years by
    numpy's proleptic Gregorian `datetime64`."""
    ms = np.asarray(ms, dtype=np.int64)
    if calendar in ("minute", "1m"):
        return ms // 60000
    if calendar in ("hour", "1h"):
        return ms // 3600000
    if calendar in ("day", "1d"):
        return ms // _DAY_MS
    if calendar in ("week", "1w"):
        return (ms // _DAY_MS + 3) // 7     # epoch day 0 = Thursday
    if calendar in ("year", "1y"):
        return ms.astype("datetime64[ms]").astype(
            "datetime64[Y]").astype(np.int64)
    months = ms.astype("datetime64[ms]").astype(
        "datetime64[M]").astype(np.int64)   # since 1970-01
    if calendar in ("month", "1M"):
        return months
    if calendar in ("quarter", "1q"):
        return months // 3
    raise ValueError(f"unknown calendar_interval [{calendar}]")


def calendar_bucket_start_ms(b: int, calendar: str) -> int:
    """Epoch ms (UTC) at which calendar bucket `b` starts: the inverse of
    `_calendar_bucket_ids`."""
    if calendar in ("minute", "1m"):
        return b * 60000
    if calendar in ("hour", "1h"):
        return b * 3600000
    if calendar in ("day", "1d"):
        return b * _DAY_MS
    if calendar in ("week", "1w"):
        return (b * 7 - 3) * _DAY_MS
    months = {"month": 1, "1M": 1, "quarter": 3, "1q": 3, "year": 12,
              "1y": 12}.get(calendar)
    if months is None:
        raise ValueError(f"unknown calendar_interval [{calendar}]")
    return int(np.datetime64(b * months, "M").astype(
        "datetime64[ms]").astype(np.int64))


_CAL_MS = {"month": None, "1M": None, "year": None, "1y": None, "quarter": None,
           "1q": None, "week": None, "1w": None}

_FIXED_MS = {"ms": 1, "s": 1000, "m": 60000, "h": 3600000, "d": 86400000}


def parse_interval_ms(s, allow_negative: bool = False) -> int:
    if isinstance(s, (int, float)):
        return int(s)
    # sign is legal only where the caller says so (date_histogram `offset`
    # accepts "+6h"/"-3h"; a negative fixed_interval must stay an error)
    sign_re = r"([+-]?)" if allow_negative else r"()"
    mm = re.fullmatch(sign_re + r"(\d+)(ms|s|m|h|d)", str(s))
    if not mm:
        raise ValueError(f"invalid fixed_interval [{s}]")
    v = int(mm.group(2)) * _FIXED_MS[mm.group(3)]
    return -v if mm.group(1) == "-" else v


def crc32_vocab_hashes(vocab, pad: int) -> np.ndarray:
    """crc32 of each vocab string, zero-padded to `pad` — the HLL value
    hashes; shared by the host segment path and the mesh service so the
    two register sets merge bit-identically."""
    import zlib
    out = np.zeros(pad, dtype=np.uint32)
    out[: len(vocab)] = np.fromiter(
        (zlib.crc32(v.encode()) for v in vocab), np.uint32,
        count=len(vocab))
    return out


def _kw_hash_cache(seg: Segment, field: str) -> np.ndarray:
    cache = getattr(seg, "_kw_hash_cache", None)
    if cache is None:
        cache = seg._kw_hash_cache = {}
    if field not in cache:
        col = seg.keyword_cols[field]
        cache[field] = crc32_vocab_hashes(
            col.vocab, next_pow2(max(len(col.vocab), 1)))
    return cache[field]


_B32 = "0123456789bcdefghjkmnpqrstuvwxyz"


def _geohash_strings(codes: np.ndarray, precision: int) -> List[str]:
    out = []
    for c in codes.tolist():
        s = []
        for i in range(precision):
            shift = 5 * (precision - 1 - i)
            s.append(_B32[(c >> shift) & 31])
        out.append("".join(s))
    return out


def _geo_grid_cache(seg: Segment, field: str, kind: str, precision: int):
    """(vocab cell keys, per-doc cell ordinal i32[ndocs_pad], -1 missing) —
    computed once per (segment, field, kind, precision) on the host; the
    device then bincounts ordinals exactly like the terms agg. (Reference
    GeoHashGridAggregator/GeoTileGridAggregator bucket by cell the same way,
    via doc-value cell ids.)"""
    cache = getattr(seg, "_geo_grid_cells", None)
    if cache is None:
        cache = seg._geo_grid_cells = {}
    key = (field, kind, precision)
    if key in cache:
        return cache[key]
    col = seg.geo_cols.get(field)
    ords = np.full(seg.ndocs_pad, -1, np.int32)
    vocab: List[str] = []
    if col is not None and col.present.any():
        lat = col.lat[: seg.ndocs].astype(np.float64)
        lon = col.lon[: seg.ndocs].astype(np.float64)
        if kind == "geotile_grid":
            z = precision
            n = 1 << z
            x = np.clip(np.floor((lon + 180.0) / 360.0 * n), 0, n - 1)
            latc = np.clip(lat, -85.05112878, 85.05112878)
            latr = np.deg2rad(latc)
            y = np.clip(np.floor(
                (1.0 - np.log(np.tan(latr) + 1.0 / np.cos(latr)) / np.pi)
                / 2.0 * n), 0, n - 1)
            codes = (x.astype(np.int64) * n + y.astype(np.int64))
            uniq, inv = np.unique(codes, return_inverse=True)
            vocab = [f"{z}/{int(c) // n}/{int(c) % n}" for c in uniq]
        else:  # geohash
            nbits = 5 * precision
            lonb = (nbits + 1) // 2
            latb = nbits // 2
            li = np.clip(np.floor((lon + 180.0) / 360.0 * (1 << lonb)),
                         0, (1 << lonb) - 1).astype(np.uint64)
            la = np.clip(np.floor((lat + 90.0) / 180.0 * (1 << latb)),
                         0, (1 << latb) - 1).astype(np.uint64)
            codes = np.zeros(len(lat), np.uint64)
            # interleave, lon first (standard geohash bit order)
            for b in range(nbits):
                if b % 2 == 0:
                    src, idx = li, lonb - 1 - b // 2
                else:
                    src, idx = la, latb - 1 - b // 2
                bit = (src >> np.uint64(idx)) & np.uint64(1)
                codes = (codes << np.uint64(1)) | bit
            uniq, inv = np.unique(codes, return_inverse=True)
            vocab = _geohash_strings(uniq, precision)
        o = np.where(col.present[: seg.ndocs], inv.astype(np.int32), -1)
        ords[: seg.ndocs] = o
    cache[key] = (vocab, ords)
    return cache[key]


# auto_date_histogram's roundings (reference AutoDateHistogramAggregation-
# Builder.buildRoundings, recalled): a unit and the multiples of it a bucket
# may span. (abbreviation, `_date_bucket_plane` calendar, inner intervals)
AUTO_ROUNDINGS = (
    ("s", None, (1, 5, 10, 30)),
    ("m", "minute", (1, 5, 10, 30)),
    ("h", "hour", (1, 3, 12)),
    ("d", "day", (1, 7)),
    ("M", "month", (1, 3)),
    ("y", "year", (1, 5, 10, 20, 50, 100)),
)


def auto_unit_ids(ms, unit: int) -> np.ndarray:
    """Bucket ids of epoch-millisecond values under rounding `unit` (UTC):
    whole seconds, minutes, hours and days since the epoch, calendar months
    since 1970-01, years since 1970."""
    cal = AUTO_ROUNDINGS[unit][1]
    ms = np.asarray(ms, dtype=np.int64)
    return ms // 1000 if cal is None else _calendar_bucket_ids(ms, cal)


def auto_unit_start_ms(bucket_id: int, unit: int) -> int:
    """Epoch ms at which bucket `bucket_id` of rounding `unit` starts."""
    cal = AUTO_ROUNDINGS[unit][1]
    return (int(bucket_id) * 1000 if cal is None
            else calendar_bucket_start_ms(int(bucket_id), cal))


def auto_unit_for(lo_ms: int, hi_ms: int, target: int) -> int:
    """The finest rounding under which the buckets from `lo_ms`'s to
    `hi_ms`'s, merged by the rounding's widest inner interval, number at
    most `target` (the coarsest where none does)."""
    for unit, (_abbr, _cal, inners) in enumerate(AUTO_ROUNDINGS):
        lo, hi = auto_unit_ids([lo_ms, hi_ms], unit)
        if -(-(int(hi) - int(lo) + 1) // inners[-1]) <= target:
            return unit
    return len(AUTO_ROUNDINGS) - 1


def auto_window(unit: int, target: int) -> int:
    """Buckets of rounding `unit` a launch counts: what `auto_unit_for`
    admits, as a power of two (a static size of the program)."""
    return next_pow2(target * AUTO_ROUNDINGS[unit][2][-1])


def auto_inner_for(nbuckets: int, unit: int, target: int) -> Optional[int]:
    """The least inner interval of rounding `unit` that merges `nbuckets`
    consecutive buckets into at most `target`; None where none does and a
    coarser rounding is left to try (the coarsest takes its widest)."""
    inners = AUTO_ROUNDINGS[unit][2]
    for inner in inners:
        if -(-nbuckets // inner) <= target:
            return inner
    return inners[-1] if unit + 1 == len(AUTO_ROUNDINGS) else None


def auto_bucket_end_ms(key_ms: int, interval: str) -> int:
    """Epoch ms at which the bucket that starts at `key_ms` ends, `interval`
    as the response names it (`7d`, `3M`)."""
    unit = next(u for u, r in enumerate(AUTO_ROUNDINGS)
                if r[0] == interval[-1])
    first = int(auto_unit_ids(key_ms, unit))
    return auto_unit_start_ms(first + int(interval[:-1]), unit)


# a combination space is enumerated through a table over the product of its
# sources' value spaces up to this many slots (a byte and an int32 each for
# the build's moment), beyond that by a sort of the rows' codes
_COMBO_TABLE_MAX = 1 << 26


class ComboSpace:
    """The combinations of source values that occur among a segment's
    documents, numbered in key order under each source's `order`: what a
    `multi_terms` or a `composite` over several sources counts into, one
    slot a combination that occurs (the product of the sources' value
    spaces, most of it empty, is laid out nowhere). `codes` i64[n]
    ascending: a combination's code is its sources' positions in mixed
    radix, first source first, a position being the value's ordinal under
    `asc` and `radix - 1 - ordinal` under `desc`. `sources` says how a
    source's ordinal decodes: ("terms", sorted values), ("hist", least
    bucket, interval) or ("date", least bucket, interval ms, calendar).
    A sequence of the key tuples besides (`len`, `[j]`, iteration), each
    decoded when asked for: a response names a page of them."""

    __slots__ = ("codes", "radix", "desc", "sources")

    def __init__(self, codes, radix, desc, sources):
        self.codes, self.radix = codes, tuple(radix)
        self.desc, self.sources = tuple(desc), tuple(sources)

    def __len__(self) -> int:
        return len(self.codes)

    def __iter__(self):
        return (self[j] for j in range(len(self.codes)))

    def __getitem__(self, j) -> tuple:
        rem, ords = int(self.codes[j]), []
        for n, desc in zip(reversed(self.radix), reversed(self.desc)):
            rem, t = divmod(rem, n)
            ords.append(n - 1 - t if desc else t)
        return tuple(self._value(src, o)
                     for src, o in zip(self.sources, reversed(ords)))

    @staticmethod
    def _value(src: tuple, o: int):
        if src[0] == "terms":
            return src[1][o]
        if src[0] == "hist":
            return (src[1] + o) * src[2]
        _, min_b, interval_ms, calendar = src
        if calendar:
            return calendar_bucket_start_ms(min_b + o, calendar)
        return int((min_b + o) * interval_ms)

    @staticmethod
    def _position(src: tuple, n: int, v) -> Tuple[int, bool]:
        """(how many of the source's `n` values lie under `v`, whether `v`
        is one of them)."""
        if src[0] == "terms":
            at = bisect_left(src[1], v)
            return at, at < n and src[1][at] == v
        if src[0] == "date" and src[3]:
            b = int(_calendar_bucket_ids(np.asarray([int(v)]), src[3])[0])
            held = calendar_bucket_start_ms(b, src[3]) == int(v)
            return min(max(b - src[1] + (not held), 0), n), \
                held and 0 <= b - src[1] < n
        q = float(v) / src[2] - src[1]
        near = int(np.floor(q + 0.5))    # the bucket a key would name
        if 0 <= near < n and abs(ComboSpace._value(src, near) - v) \
                <= 1e-9 * max(1.0, abs(float(v))):
            return near, True
        return min(max(int(np.ceil(q)), 0), n), False

    def first_after(self, after: tuple) -> int:
        """The number of the first combination whose key comes after the
        key tuple `after` in the sources' orders (`len(self)`: none)."""
        code, mult = 0, [1]
        for n in reversed(self.radix[1:]):
            mult.insert(0, mult[0] * n)
        for src, n, desc, m, v in zip(self.sources, self.radix, self.desc,
                                      mult, after):
            under, held = self._position(src, n, v)
            # the first position whose value is `v` or comes after it
            at = (n - 1 - under if held else n - under) if desc else under
            code += at * m
            if not held:
                return int(np.searchsorted(self.codes, code, side="left"))
        return int(np.searchsorted(self.codes, code, side="right"))


def _combo_ids(per_source: list, desc: tuple, ndocs: int):
    """(combination numbers i32[ndocs], -1 = a document that lacks a
    source; codes i64[n] ascending) from each source's (ordinals i32[ndocs]
    with -1 = none, number of values)."""
    valid = np.ones(ndocs, bool)
    code = np.zeros(ndocs, np.int64)
    product = 1
    for (ords, n), d in zip(per_source, desc):
        n = max(int(n), 1)
        valid &= ords >= 0
        code *= n
        code += np.maximum((n - 1 - ords) if d else ords, 0)
        product *= n
    if product >= 1 << 62:
        raise dsl.QueryParseError(
            f"the sources' value spaces multiply to {product}: too many")
    held = code[valid]
    if product <= _COMBO_TABLE_MAX:
        seen = np.zeros(product, bool)
        seen[held] = True
        codes = np.flatnonzero(seen)
        number = (np.cumsum(seen, dtype=np.int32) - 1)[held]
    else:
        codes, number = np.unique(held, return_inverse=True)
    ids = np.full(ndocs, -1, np.int32)
    ids[valid] = number
    return ids, codes.astype(np.int64)


def _multi_terms_sources(seg: Segment, ctx: ShardContext,
                         fields: Tuple[str, ...]):
    """[(ordinals i32[ndocs], number of values)] and the `ComboSpace`
    sources of a `multi_terms` source list: a keyword's least ordinal, a
    numeric column's rank among its distinct values; a field the segment
    lacks excludes every document."""
    per_source, sources = [], []
    for f in fields:
        f = ctx.mappings.aliases.get(f, f)
        kcol = seg.keyword_cols.get(f)
        ncol = seg.numeric_cols.get(f)
        if kcol is not None:
            ords, values = kcol.min_ord[: seg.ndocs], kcol.vocab
        elif ncol is not None:
            ords = ncol.sort_ords()[: seg.ndocs]
            values = np.unique(ncol.values[ncol.present]).tolist()
        else:
            ords, values = np.full(seg.ndocs, -1, np.int32), []
        per_source.append((ords, len(values)))
        sources.append(("terms", values))
    return per_source, sources


def _combo_space(per_source: list, sources: list, desc: tuple, ndocs: int):
    """(combination numbers i32[ndocs], `ComboSpace`) of per-source
    (ordinals, number of values) pairs and their decoders."""
    ids, codes = _combo_ids(per_source, desc, ndocs)
    return ids, ComboSpace(codes, [max(n, 1) for _o, n in per_source], desc,
                           sources)


def _combo_plane(seg: Segment, key: tuple, build: Callable[[], tuple]):
    """(combination numbers of the documents as a resident plane,
    `ComboSpace`) under `key` (the fields' tuple first): `build`
    (`_combo_space`) runs once a segment on the host, the plane then lives
    on the device for the segment's lifetime, in the HBM ledger with the
    bucket planes (`_segment_plane`)."""
    return _segment_plane(seg, "_combo_plane_cache", key,
                          "agg_bucket_plane", BUCKET_PLANE_STATS, build)


def _multi_terms_space(seg: Segment, ctx: ShardContext,
                       fields: Tuple[str, ...]):
    """`_combo_space` of a `multi_terms` source list; documents missing ANY
    source are excluded (-1), matching reference MultiTermsAggregator."""
    per_source, sources = _multi_terms_sources(seg, ctx, fields)
    return _combo_space(per_source, sources, (False,) * len(fields),
                        seg.ndocs)


def multi_terms_plane(seg: Segment, ctx: ShardContext,
                      fields: Tuple[str, ...]):
    """(plane, `ComboSpace`) of a `multi_terms` source list."""
    return _combo_plane(seg, (tuple(fields), "multi_terms"),
                        lambda: _multi_terms_space(seg, ctx, fields))


def _multi_terms_cache(seg: Segment, ctx: ShardContext, node, fields: Tuple[str, ...]):
    """(`ComboSpace` as the vocabulary of key tuples, combined doc-major
    ordinal i32[ndocs_pad] on the HOST) for the mesh path, which restacks
    the segments' ordinals into one index-wide space
    (`parallel/service.py`); the executor's launches read
    `multi_terms_plane`."""
    cache = getattr(seg, "_multi_terms_cache", None)
    if cache is None:
        cache = seg._multi_terms_cache = {}
    if fields not in cache:
        ids, space = _multi_terms_space(seg, ctx, fields)
        ords_out = np.full(next_pow2(seg.ndocs), -1, np.int32)
        ords_out[: seg.ndocs] = ids
        cache[fields] = (space, ords_out)
    return cache[fields]


def _col_sum(seg: Segment, field: str) -> Tuple[float, int]:
    """(Σ values, present count) of a numeric column, f64, cached per segment
    (segments are immutable apart from deletes, which don't need to perturb a
    scoring shift)."""
    cache = getattr(seg, "_col_sum_cache", None)
    if cache is None:
        cache = seg._col_sum_cache = {}
    if field not in cache:
        col = seg.numeric_cols.get(field)
        if col is None or not col.present.any():
            cache[field] = (0.0, 0)
        else:
            cache[field] = (float(col.values[col.present].astype(np.float64).sum()),
                            int(col.present.sum()))
    return cache[field]


def _kw_doc_counts(seg: Segment, field: str) -> Dict[str, int]:
    """Background per-value doc counts over the segment's live docs
    (significant_terms superset statistics); invalidated by deletes via
    `live_gen`."""
    cache = getattr(seg, "_kw_doc_count_cache", None)
    if cache is None or cache.get("__gen") != seg.live_gen:
        cache = seg._kw_doc_count_cache = {"__gen": seg.live_gen}
    if field in cache:
        return cache[field]
    col = seg.keyword_cols.get(field)
    out: Dict[str, int] = {}
    if col is not None and len(col.vocab):
        live_vals = seg.live[col.doc_of_value]
        counts = np.bincount(col.ords[live_vals], minlength=len(col.vocab))
        out = {col.vocab[i]: int(c) for i, c in enumerate(counts) if c > 0}
    cache[field] = out
    return out


def coerce_agg_ranges(kind: str, body: dict, field: str,
                      mappings) -> list:
    """Shared host/mesh range-agg bounds: date_range coerces from/to
    through the field type (date math/formats -> epoch ms) before the
    f32 bound construction. Single source of truth for both paths."""
    ranges = body.get("ranges", [])
    if kind != "date_range":
        return ranges
    ft = mappings.resolve_field(field)
    coerced = []
    for r in ranges:
        r2 = dict(r)
        for end in ("from", "to"):
            if r.get(end) is not None:
                r2[end] = coerce_value(ft, r[end])
        coerced.append(r2)
    return coerced


def filters_agg_items(body: dict) -> list:
    """Shared host/mesh normalization of a `filters` agg body to
    (key, clause) pairs (dict keys, or "0"/"1"/... for the anonymous list
    form). Single source of truth — mesh bucket keys must match the host
    coordinator merge exactly."""
    raw = body.get("filters", {})
    return (list(raw.items()) if isinstance(raw, dict)
            else [(str(i), f) for i, f in enumerate(raw)])


def grid_agg_precision(kind: str, body: dict) -> int:
    """Shared host/mesh geo-grid precision resolution (geohash default 5,
    geotile default 7). Single source of truth — the mesh keys its device
    program cache on this and must never drift from the cell binning."""
    return int(body.get("precision", 5 if kind == "geohash_grid" else 7))


def hist_agg_interval(kind: str, body: dict) -> Tuple[float, float]:
    """Shared host/mesh resolution of a histogram-family agg's (interval,
    offset) in value space (ms for dates; fixed_interval preferred).
    Single source of truth — the mesh service keys its device-program cache
    on this and must never drift from the binning itself."""
    if kind == "date_histogram":
        interval = float(parse_interval_ms(
            body.get("fixed_interval", body.get("interval", "1d"))))
        offset = (float(parse_interval_ms(body.get("offset", 0),
                                          allow_negative=True))
                  if body.get("offset") else 0.0)
    else:
        interval = float(body["interval"])
        offset = float(body.get("offset", 0.0))
    return interval, offset


def range_agg_spec(ranges: List[dict]) -> tuple:
    """Shared host/mesh construction of a plain `range` agg's f32 bounds,
    bucket keys, and from/to response meta (f32-roundtripped so host and
    mesh responses are bit-identical). Single source of truth: the mesh
    service (`parallel/service.py`) serves the same aggs and must never
    drift from this formatting."""
    nr = len(ranges)
    lows = np.full(nr, -np.inf, dtype=np.float32)
    highs = np.full(nr, np.inf, dtype=np.float32)
    keys, metas = [], []
    for i, r in enumerate(ranges):
        frm, to = r.get("from"), r.get("to")
        if frm is not None:
            lows[i] = float(frm)
        if to is not None:
            highs[i] = float(to)
        keys.append(r.get("key", f"{frm if frm is not None else '*'}-"
                                 f"{to if to is not None else '*'}"))
        meta = {}
        if frm is not None:
            meta["from"] = float(np.float32(frm))
        if to is not None:
            meta["to"] = float(np.float32(to))
        metas.append(meta)
    return lows, highs, keys, metas


def _bind_date_buckets(params: dict, prefix: str, seg: Segment, field: str,
                       interval_ms: int, offset_ms: int,
                       calendar: Optional[str]) -> Tuple[int, int, str]:
    """Hand a date histogram's resident planes to the launch: the bucket
    ids as `<prefix>_dbuckets` and, where the segment's values are in row
    order, the runs' boundaries as `<prefix>_dstarts`. -> (min_bucket,
    nbuckets, form): "runs" or "scatter", the static member of the spec
    that `_date_bucket_counts` builds the program from and `_count_launch`
    counts."""
    plane, min_b, nb, starts = _date_bucket_plane(
        seg, field, interval_ms, offset_ms, calendar)
    params[f"{prefix}_dbuckets"] = plane
    if starts is None:
        return min_b, nb, "scatter"
    params[f"{prefix}_dstarts"] = starts
    return min_b, nb, "runs"


def prepare_agg(node: AggNode, seg: Segment, ctx: ShardContext, params: dict,
                prefix: str, nest_stack: Tuple = (),
                auto_range: Optional[Tuple[int, int]] = None):  # noqa: C901
    """-> hashable agg spec; params filled per segment. `prefix` keys params.
    `nest_stack` is the nesting path down to `seg`: ((path, segment), ...)
    root-first, empty at root — reverse_nested climbs it. `auto_range` is
    the least and greatest value of a top-level `auto_date_histogram`'s
    field among this segment's matched documents (`auto_date_range`)."""
    kind = node.kind
    body = node.body

    if kind == "terms":
        field = _resolve_agg_field(node, ctx)
        if field not in seg.keyword_cols:
            return ("terms_missing", prefix)
        nvocab_pad = next_pow2(max(len(seg.keyword_cols[field].vocab), 1))
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("terms", prefix, field, nvocab_pad, subs)

    if kind == "histogram":
        field = _resolve_agg_field(node, ctx)
        interval = float(body["interval"])
        offset = float(body.get("offset", 0.0))
        col = seg.numeric_cols.get(field)
        if col is None or not col.present.any():
            return ("hist_missing", prefix, interval, offset)
        mn, mx = col.min_max
        min_b = int(np.floor((mn - offset) / interval))
        max_b = int(np.floor((mx - offset) / interval))
        nb = max_b - min_b + 1
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("hist", prefix, field, interval, offset, min_b, nb, subs)

    if kind == "date_histogram":
        field = _resolve_agg_field(node, ctx)
        calendar = body.get("calendar_interval")
        if calendar is not None:
            interval_ms = 0
        else:
            interval_ms = parse_interval_ms(body.get("fixed_interval",
                                                     body.get("interval", "1d")))
        offset_ms = (parse_interval_ms(body.get("offset", 0),
                                       allow_negative=True)
                     if body.get("offset") else 0)
        min_b, nb, form = _bind_date_buckets(
            params, prefix, seg, field, max(interval_ms, 1), offset_ms,
            calendar)
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("date_hist", prefix, field, interval_ms, offset_ms, calendar,
                min_b, nb, subs, form)

    if kind in ("range", "date_range"):
        field = _resolve_agg_field(node, ctx)
        ranges = coerce_agg_ranges(kind, node.body, field, ctx.mappings)
        lows, highs, keys, _metas = range_agg_spec(ranges)
        params[f"{prefix}_lows"] = lows
        params[f"{prefix}_highs"] = highs
        col_exists = field in seg.numeric_cols
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("range", prefix, field, tuple(keys), col_exists, subs,
                tuple((float(lows[i]), float(highs[i])) for i in range(len(ranges))))

    if kind == "geo_distance":
        # distance-ring buckets from an origin (reference bucket/range/
        # GeoDistanceAggregationBuilder): haversine vector on device, then
        # the same range-count pass as the numeric range agg
        field = _resolve_agg_field(node, ctx)
        if "origin" not in body:
            raise dsl.QueryParseError(
                "[geo_distance] aggregation requires [origin]")
        try:
            olat, olon = dsl._parse_point(body["origin"])
            unit_m = dsl._parse_distance(f"1{body.get('unit', 'm')}")
        except (ValueError, TypeError, KeyError) as e:
            raise dsl.QueryParseError(f"[geo_distance] {e}")
        ranges = body.get("ranges", [])
        lows = np.full(len(ranges), -np.inf, dtype=np.float32)
        highs = np.full(len(ranges), np.inf, dtype=np.float32)
        keys = []
        disp = []
        for i, r in enumerate(ranges):
            frm, to = r.get("from"), r.get("to")
            if frm is not None:
                lows[i] = float(frm) * unit_m
            if to is not None:
                highs[i] = float(to) * unit_m
            keys.append(r.get("key", f"{frm if frm is not None else '*'}-"
                                     f"{to if to is not None else '*'}"))
            disp.append((float(frm) if frm is not None else None,
                         float(to) if to is not None else None))
        params[f"{prefix}_lows"] = lows
        params[f"{prefix}_highs"] = highs
        _scalar_f32(params, f"{prefix}_olat", olat)
        _scalar_f32(params, f"{prefix}_olon", olon)
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("geo_range", prefix, field, tuple(keys),
                field in seg.geo_cols, subs,
                tuple((lo if lo is not None else float("-inf"),
                       hi if hi is not None else float("inf"))
                      for lo, hi in disp))

    if kind == "filter":
        lnode = rewrite(dsl.parse_query(body), ctx, scoring=False)
        fspec = prepare(lnode, seg, ctx, params)
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("filter", prefix, fspec, subs)

    if kind == "filters":
        items = filters_agg_items(body)
        fspecs = []
        for key, f in items:
            lnode = rewrite(dsl.parse_query(f), ctx, scoring=False)
            fspecs.append((key, prepare(lnode, seg, ctx, params)))
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("filters", prefix, tuple(fspecs), subs)

    if kind == "global":
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("global", prefix, subs)

    if kind == "missing":
        field = _resolve_agg_field(node, ctx)
        src = ("numeric" if field in seg.numeric_cols else
               "keyword" if field in seg.keyword_cols else "none")
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("missing", prefix, field, src, subs)

    if kind in ("min", "max", "sum", "avg", "stats", "extended_stats", "value_count"):
        field = _resolve_agg_field(node, ctx)
        if kind == "value_count" and field in seg.keyword_cols:
            return ("vc_keyword", prefix, field)
        col = seg.numeric_cols.get(field)
        if col is not None:
            # the power of two that brings the column under 1, for the
            # sums' fixed point (`ops.aggs.bucket_sums_exact`)
            _p(params, f"{prefix}_sinv",
               agg_ops.sum_scale_inv(max(abs(x) for x in col.min_max)))
        return ("stats", prefix, field, col is not None,
                kind == "extended_stats")

    if kind == "cardinality":
        field = _resolve_agg_field(node, ctx)
        if field in seg.keyword_cols:
            params[f"{prefix}_hashes"] = _kw_hash_cache(seg, field)
            nvocab_pad = next_pow2(max(len(seg.keyword_cols[field].vocab), 1))
            return ("card_kw", prefix, field, nvocab_pad)
        return ("card_num", prefix, field, field in seg.numeric_cols)

    if kind == "percentiles":
        field = _resolve_agg_field(node, ctx)
        col = seg.numeric_cols.get(field)
        percents = tuple(body.get("percents", DEFAULT_PERCENTS))
        return ("pctl", prefix, field, col is not None, percents)

    if kind == "percentile_ranks":
        field = _resolve_agg_field(node, ctx)
        col = seg.numeric_cols.get(field)
        values = tuple(float(v) for v in body.get("values", ()))
        return ("pctl_ranks", prefix, field, col is not None, values)

    if kind == "top_hits":
        return ("top_hits", prefix, int(body.get("size", 3)))

    if kind == "significant_terms":
        field = _resolve_agg_field(node, ctx)
        if field not in seg.keyword_cols:
            # still contributes its live docs to the background total —
            # supersetSize spans the whole shard (reference semantics)
            return ("sig_missing", prefix)
        nvocab_pad = next_pow2(max(len(seg.keyword_cols[field].vocab), 1))
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("sig_terms", prefix, field, nvocab_pad, subs)

    if kind == "sampler":
        shard_size = max(int(body.get("shard_size", 100)), 1)
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        # pass 2 of the shard-wide resample (executor._resample_samplers)
        # supplies a global score threshold instead of a per-segment top-k
        thr = getattr(node, "_global_thr", None)
        if thr is not None:
            _scalar_f32(params, f"{prefix}_thr", thr)
        return ("sampler", prefix, shard_size, thr is not None, subs)

    if kind == "diversified_sampler":
        shard_size = max(int(body.get("shard_size", 100)), 1)
        maxper = max(int(body.get("max_docs_per_value", 1)), 1)
        field = ctx.mappings.aliases.get(body.get("field", ""),
                                        body.get("field", ""))
        use_kw = field in seg.keyword_cols
        if not use_kw and field in seg.numeric_cols:
            ords = seg.numeric_cols[field].sort_ords()
            params[f"{prefix}_dords"] = np.pad(
                ords, (0, seg.ndocs_pad - len(ords)), constant_values=-1)
            n_ord_pad = next_pow2(seg.ndocs + 1)
        elif use_kw:
            n_ord_pad = next_pow2(len(seg.keyword_cols[field].vocab) + 1)
        else:
            params[f"{prefix}_dords"] = np.full(seg.ndocs_pad, -1, np.int32)
            n_ord_pad = 2
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("dsampler", prefix, shard_size, field, maxper, use_kw,
                n_ord_pad, subs)

    if kind in ("geohash_grid", "geotile_grid"):
        field = _resolve_agg_field(node, ctx)
        precision = grid_agg_precision(kind, body)
        vocab, ords = _geo_grid_cache(seg, field, kind, precision)
        params[f"{prefix}_gords"] = ords
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("geo_grid", prefix, kind, field, precision,
                next_pow2(max(len(vocab), 1)), subs)

    if kind == "nested":
        path = body.get("path")
        blk = seg.nested.get(path)
        if blk is None or blk.child.ndocs == 0:
            return ("terms_missing", prefix)
        new_stack = (nest_stack or ((None, seg),)) + ((path, blk.child),)
        subs = tuple(prepare_agg(s, blk.child, ctx, params, f"{prefix}_{i}",
                                 new_stack)
                     for i, s in enumerate(node.subs))
        return ("nested_agg", prefix, path, subs)

    if kind == "reverse_nested":
        if len(nest_stack) < 2:
            raise dsl.QueryParseError(
                "[reverse_nested] must be nested inside a [nested] aggregation")
        rpath = body.get("path")
        if rpath is None:
            ti = 0  # default: all the way back to the root document
        else:
            ti = next((i for i, (p, _) in enumerate(nest_stack) if p == rpath),
                      None)
            if ti is None:
                raise dsl.QueryParseError(
                    f"[reverse_nested] path [{rpath}] is not an enclosing "
                    f"nested level")
        up_k = len(nest_stack) - 1 - ti
        if up_k <= 0:
            raise dsl.QueryParseError(
                "[reverse_nested] path must point above the current level")
        target_seg = nest_stack[ti][1]
        subs = tuple(prepare_agg(s, target_seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack[: ti + 1] if ti > 0 else ())
                     for i, s in enumerate(node.subs))
        return ("reverse_nested", prefix, up_k, subs)

    if kind in ("children", "parent"):
        return _prepare_join_agg(node, seg, ctx, params, prefix)

    if kind == "composite":
        return _prepare_composite(node, seg, ctx, params, prefix, nest_stack)

    if kind == "weighted_avg":
        vspec = body.get("value", {})
        wspec = body.get("weight", {})
        vfield = ctx.mappings.aliases.get(vspec.get("field", ""),
                                          vspec.get("field", ""))
        wfield = ctx.mappings.aliases.get(wspec.get("field", ""),
                                          wspec.get("field", ""))
        _scalar_f32(params, f"{prefix}_vmiss", float(vspec.get("missing", 0.0)
                                                     or 0.0))
        _scalar_f32(params, f"{prefix}_wmiss", float(wspec.get("missing", 0.0)
                                                     or 0.0))
        return ("wavg", prefix, vfield, wfield,
                vfield in seg.numeric_cols, wfield in seg.numeric_cols,
                vspec.get("missing") is not None,
                wspec.get("missing") is not None)

    if kind == "median_absolute_deviation":
        field = _resolve_agg_field(node, ctx)
        return ("mad", prefix, field, field in seg.numeric_cols)

    if kind in ("geo_bounds", "geo_centroid"):
        field = _resolve_agg_field(node, ctx)
        return ("geo_stat", prefix, kind, field, field in seg.geo_cols)

    if kind == "ip_range":
        from ..index.mappings import _ip_to_int
        field = _resolve_agg_field(node, ctx)
        ranges = body.get("ranges", [])
        bounds = []
        keys = []
        for r in ranges:
            if "mask" in r:
                import ipaddress
                net = ipaddress.ip_network(r["mask"], strict=False)
                lo = _ip_to_int(str(net.network_address))
                hi = _ip_to_int(str(net.broadcast_address)) + 1
                keys.append(r.get("key", r["mask"]))
                bounds.append((lo, hi, str(net.network_address),
                               str(net.broadcast_address)))
            else:
                lo = _ip_to_int(r["from"]) if r.get("from") else None
                hi = _ip_to_int(r["to"]) if r.get("to") else None
                keys.append(r.get("key",
                                  f"{r.get('from', '*')}-{r.get('to', '*')}"))
                bounds.append((lo, hi, r.get("from"), r.get("to")))
        lo_hi = np.zeros(len(bounds), np.int32)
        lo_lo = np.zeros(len(bounds), np.int32)
        hi_hi = np.zeros(len(bounds), np.int32)
        hi_lo = np.zeros(len(bounds), np.int32)
        open_lo = np.zeros(len(bounds), bool)
        open_hi = np.zeros(len(bounds), bool)
        for i, (lo, hi, _f, _t) in enumerate(bounds):
            if lo is None:
                open_lo[i] = True
            else:
                h, l = split_i64(np.array([lo], np.int64))
                lo_hi[i], lo_lo[i] = h[0], l[0]
            if hi is None:
                open_hi[i] = True
            else:
                h, l = split_i64(np.array([hi], np.int64))
                hi_hi[i], hi_lo[i] = h[0], l[0]
        params[f"{prefix}_iplo"] = np.stack([lo_hi, lo_lo])
        params[f"{prefix}_iphi"] = np.stack([hi_hi, hi_lo])
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("ip_range", prefix, field, tuple(keys),
                tuple((b[2], b[3]) for b in bounds),
                tuple(bool(x) for x in open_lo), tuple(bool(x) for x in open_hi),
                field in seg.numeric_cols, subs)

    if kind == "rare_terms":
        field = _resolve_agg_field(node, ctx)
        if field not in seg.keyword_cols:
            return ("terms_missing", prefix)
        nvocab_pad = next_pow2(max(len(seg.keyword_cols[field].vocab), 1))
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("terms", prefix, field, nvocab_pad, subs)

    if kind == "multi_terms":
        sources = body.get("terms", [])
        if len(sources) < 2:
            raise dsl.QueryParseError(
                "[multi_terms] requires at least two [terms] sources")
        params[f"{prefix}_mords"], space = multi_terms_plane(
            seg, ctx, tuple(s["field"] for s in sources))
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("multi_terms", prefix, next_pow2(max(len(space), 1)),
                len(space), subs)

    if kind == "adjacency_matrix":
        raw = body.get("filters", {})
        sep = body.get("separator", "&")
        fspecs = []
        for key in sorted(raw):
            lnode = rewrite(dsl.parse_query(raw[key]), ctx, scoring=False)
            fspecs.append((key, prepare(lnode, seg, ctx, params)))
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("adjacency", prefix, tuple(fspecs), sep, subs)

    if kind == "auto_date_histogram":
        field = _resolve_agg_field(node, ctx)
        target = max(int(body.get("buckets", 10)), 1)
        col = seg.numeric_cols.get(field)
        if col is None or not col.present.any():
            return ("hist_missing", prefix, 0.0, 0.0)
        # the rounding follows the matched documents' least and greatest
        # value where the executor learned them for this node (a top-level
        # aggregation: `auto_range`), the column's span elsewhere (a
        # superset, so the window below still holds every matched bucket)
        lo_ms, hi_ms = auto_range or tuple(int(x) for x in col.min_max)
        unit = auto_unit_for(lo_ms, hi_ms, target)
        abbr, calendar, _inners = AUTO_ROUNDINGS[unit]
        min_b, nb, form = _bind_date_buckets(
            params, prefix, seg, field, 1000 if calendar is None else 1, 0,
            calendar)
        window = auto_window(unit, target)
        first = int(auto_unit_ids(lo_ms, unit))
        params[f"{prefix}_dfirst"] = np.int32(
            np.clip(first - min_b, -(1 << 30), 1 << 30))
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("auto_date_hist", prefix, field, unit, target, min_b, nb,
                window, subs, form)

    if kind == "scripted_metric":
        return ("scripted", prefix)

    if kind == "significant_text":
        # resolved host-side from the top sampled hits (executor)
        return ("sig_text", prefix)

    if kind == "matrix_stats":
        fields = tuple(body.get("fields", []))
        exists = tuple(f in seg.numeric_cols for f in fields)
        # index-wide per-field shift: device power sums run CENTERED about it
        # so f32 accumulation doesn't catastrophically cancel (the reference
        # keeps running central moments in double for the same reason)
        shift = getattr(node, "_ms_shift", None)
        if shift is None:
            shift = np.zeros(len(fields), np.float64)
            for i, f in enumerate(fields):
                sums = [_col_sum(s, f) for s in ctx.segments]
                tot = sum(t for t, _ in sums)
                cnt = sum(c for _, c in sums)
                shift[i] = tot / cnt if cnt else 0.0
            node._ms_shift = shift
        params[f"{prefix}_shift"] = shift.astype(np.float32)
        return ("matrix_stats", prefix, fields, exists)

    raise ValueError(f"cannot prepare aggregation [{kind}]")


def _prepare_join_agg(node: AggNode, seg: Segment, ctx: ShardContext,
                      params: dict, prefix: str):
    """children / parent aggregations (reference modules/parent-join
    ChildrenAggregator / ParentAggregator). The cross-segment join rides the
    same slot-space pre-pass as has_child/has_parent; the bucket context is
    the TOP-LEVEL query (`ctx._current_lroot`) — like the reference, these
    only make sense directly under the query context."""
    from .join import get_join_index

    kind = node.kind
    jf = ctx.mappings.join_field
    if jf is None:
        return ("terms_missing", prefix)
    relations = ctx.mappings.fields[jf].relations
    child_rel = node.body.get("type")
    parent_rel = next((p for p, cs in relations.items() if child_rel in cs), None)
    if parent_rel is None:
        raise dsl.QueryParseError(
            f"[{kind}] [{child_rel}] is not a child relation of the join field")
    ji = get_join_index(ctx.segments, jf)
    lroot = getattr(ctx, "_current_lroot", None) or LMatchAll()
    pre = getattr(node, "_agg_pre", None)
    if pre is None:
        # filter nodes are built ONCE per agg node so their nids (and thus
        # the jit spec) stay stable across segments
        node._rel_filters = {
            "child": _weighted_terms(jf, [child_rel], [1.0], ctx, 1, "filter", 1.0),
            "parent": _weighted_terms(jf, [parent_rel], [1.0], ctx, 1, "filter", 1.0)}
        if kind == "children":
            # global mask of context-matched PARENT docs at their own slots
            plan = LBool(musts=[lroot], filters=[node._rel_filters["parent"]])
            pre = _join_prepass(plan, ji, ("cnt",), ctx, self_slots=True)
        else:
            # global mask of parents having context-matched CHILD docs
            plan = LBool(musts=[lroot], filters=[node._rel_filters["child"]])
            pre = _join_prepass(plan, ji, ("cnt",), ctx, self_slots=False)
        node._agg_pre = pre
    params[f"{prefix}_gmatch"] = pre["cnt"]
    subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}")
                 for i, s in enumerate(node.subs))
    if kind == "children":
        params[f"{prefix}_pslot"] = ji.pslot(seg)
        cf = prepare(node._rel_filters["child"], seg, ctx, params)
        return ("children_agg", prefix, cf, subs)
    _scalar_i32(params, f"{prefix}_base", ji.seg_base(seg))
    pf = prepare(node._rel_filters["parent"], seg, ctx, params)
    return ("parent_agg", prefix, pf, subs)


def _composite_sources(node: AggNode, seg: Segment, ctx: ShardContext):
    """The sources of a composite over `seg`, resolved -> ([(source type,
    field, number of values, least bucket, interval, calendar, desc)] or
    None where the segment lacks a source's column (no bucket), the field
    of a single-source composite's multi-valued `terms` source or
    None)."""
    from .aggregations import composite_sources

    sources = composite_sources(node)
    infos = []
    for nm, stype, scfg, order in sources:
        field = scfg.get("field", "")
        ft = ctx.mappings.resolve_field(field)
        field = ft.name if ft else field
        desc = order == "desc"
        if stype == "terms":
            col = seg.keyword_cols.get(field)
            if col is None:
                return None, None
            if seg.kw_multi_valued(field):
                # a doc contributes one composite key per value (reference
                # behavior); supported for a single-source composite, where
                # it degenerates to an ordinal bincount
                if len(sources) > 1:
                    raise dsl.QueryParseError(
                        "[composite] a multi-valued terms source cannot be "
                        "combined with other sources")
                return None, field
            infos.append(("terms", field, len(col.vocab), 0, 0.0, "", desc))
        elif stype == "histogram":
            interval = float(scfg["interval"])
            col = seg.numeric_cols.get(field)
            if col is None or not col.present.any():
                return None, None
            mn, mx = col.min_max
            min_b = int(np.floor(mn / interval))
            nb = int(np.floor(mx / interval)) - min_b + 1
            infos.append(("hist", field, nb, min_b, interval, "", desc))
        elif stype == "date_histogram":
            calendar = scfg.get("calendar_interval")
            interval_ms = (0 if calendar else
                           parse_interval_ms(scfg.get("fixed_interval",
                                                      scfg.get("interval", "1d"))))
            col = seg.numeric_cols.get(field)
            if col is None or not col.present.any():
                return None, None
            infos.append(("date", field, 0, 0, float(max(interval_ms, 1)),
                          calendar or "", desc))
        else:
            raise dsl.QueryParseError(
                f"[composite] unsupported source type [{stype}]")
    return infos, None


def _composite_source_ordinals(seg: Segment, info: tuple):
    """(ordinals i32[ndocs] with -1 = no value, number of values,
    `ComboSpace` source) of one resolved composite source, on the host,
    as the device would reckon them (a histogram's bucket from the
    float32 the column holds there)."""
    stype, field, n, min_b, interval, cal, _desc = info
    if stype == "terms":
        col = seg.keyword_cols[field]
        return col.min_ord[: seg.ndocs], n, ("terms", col.vocab)
    if stype == "hist":
        col = seg.numeric_cols[field]
        o = np.floor(col.values.astype(np.float32)
                     / np.float32(interval)).astype(np.int64) - min_b
        o = np.where(col.present & (o >= 0) & (o < n), o, -1)
        return o.astype(np.int32), n, ("hist", min_b, interval)
    ids, min_b, nb = _date_bucket_ids(seg, field, int(interval), 0,
                                      cal or None)
    return ids, nb, ("date", min_b, interval, cal)


def composite_space(seg: Segment, infos: list):
    """(plane or None, `ComboSpace`) of a composite's resolved sources: one
    source counts into its own value space and needs no plane (its ordinal
    is on the device already), several count into the combinations that
    occur (`_combo_plane`)."""
    desc = tuple(i[6] for i in infos)
    if len(infos) > 1:
        key = (tuple(i[1] for i in infos), "composite",
               tuple((i[0], i[4], i[5], i[6]) for i in infos))

        def build():
            got = [_composite_source_ordinals(seg, i) for i in infos]
            return _combo_space([(o, n) for o, n, _s in got],
                                [s for _o, _n, s in got], desc, seg.ndocs)
        return _combo_plane(seg, key, build)
    stype, field, n, min_b, interval, cal, _desc = infos[0]
    if stype == "date":     # (the plane is cached: `_date_bucket_plane`)
        _plane, min_b, n, _starts = _date_bucket_plane(
            seg, field, int(interval), 0, cal or None)
        src = ("date", min_b, interval, cal)
    elif stype == "terms":
        src = ("terms", seg.keyword_cols[field].vocab)
    else:
        src = ("hist", min_b, interval)
    n = max(n, 1)
    return None, ComboSpace(np.arange(n, dtype=np.int64), [n], desc, [src])


def _prepare_composite(node: AggNode, seg: Segment, ctx: ShardContext,
                       params: dict, prefix: str, nest_stack):
    """Composite agg: each doc maps to the number of its sources'
    combination in key order (`ComboSpace`: the combinations that occur in
    the segment, so three keyword sources cost their joint cardinality and
    not their product); one device bincount yields every composite bucket
    of the segment, and the host makes records of one page of them
    (reference CompositeAggregator builds the same slot machinery per
    leaf)."""
    infos, multi = _composite_sources(node, seg, ctx)
    if multi is not None:
        col = seg.keyword_cols[multi]
        subs_mv = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                    nest_stack)
                        for i, s in enumerate(node.subs))
        return ("composite_mv", prefix, multi,
                next_pow2(max(len(col.vocab), 1)), subs_mv)
    if infos is None:
        return ("terms_missing", prefix)
    plane, space = composite_space(seg, infos)
    if plane is not None:
        params[f"{prefix}_cplane"] = plane
        single = None
    else:
        stype, field, _n, min_b, interval, cal, desc = infos[0]
        if stype == "date":
            params[f"{prefix}_s0"], min_b, _nb, _starts = \
                _date_bucket_plane(seg, field, int(interval), 0, cal or None)
        single = (stype, field, min_b, interval, desc)
    subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}", nest_stack)
                 for i, s in enumerate(node.subs))
    return ("composite", prefix, single, len(space), subs)


def _resolve_agg_field(node: AggNode, ctx: ShardContext) -> str:
    field = node.body.get("field", "")
    ft = ctx.mappings.resolve_field(field)
    return ft.name if ft else field


# a group-by over keyword ordinals or their combinations, whole (the match
# gathered by value, the ids, the count, a keyword cardinality's registers),
# names the stage `aggs.terms` in the device trace, around whatever form
# (`ops.aggs`' `aggs.dense` / `aggs.scatter`) the count then takes
TERMS_SCOPE = "aggs.terms"
_TERMS_STAGE_KINDS = frozenset({"terms", "sig_terms", "multi_terms",
                                "composite", "composite_mv", "card_kw"})


def emit_agg(spec, seg_arrays: dict, params: dict, match, scores=None):
    """-> nested dict of device arrays (this segment's partial)."""
    if spec[0] in _TERMS_STAGE_KINDS:
        import jax
        with jax.named_scope(TERMS_SCOPE):
            return _emit_agg(spec, seg_arrays, params, match, scores)
    return _emit_agg(spec, seg_arrays, params, match, scores)


def _emit_agg(spec, seg_arrays: dict, params: dict, match, scores=None):  # noqa: C901
    import jax
    import jax.numpy as jnp

    kind = spec[0]
    ndocs_pad = seg_arrays["live"].shape[0]

    if kind in ("terms_missing", "hist_missing"):
        return {}

    if kind == "sig_missing":
        return {"marker": jnp.float32(0)}

    if kind == "sig_terms":
        _, prefix, field, nvocab_pad, subs = spec
        kw = seg_arrays["keyword"][field]
        out = {"counts": agg_ops.terms_counts(kw, match, nvocab_pad),
               "fg_total": jnp.sum(match)}
        for i, sub in enumerate(subs):
            if sub and sub[0] == "stats":
                _, sprefix, sfield, col_exists, sumsq = sub
                if col_exists:
                    col = seg_arrays["numeric"][sfield]
                    out[f"sub{i}"] = agg_ops.terms_sub_metric(
                        kw, match, col["f32"], col["present"], nvocab_pad,
                        params[f"{sprefix}_sinv"], sumsq)
        return out

    if kind == "sampler":
        _, prefix, shard_size, use_thr, subs = spec
        out = {}
        if scores is None:
            sel = match
        elif use_thr:
            masked = jnp.where(match > 0, scores, -jnp.inf)
            sel = match * (masked >= params[f"{prefix}_thr"]).astype(jnp.float32)
        else:
            # best-scoring shard_size matching docs (reference
            # SamplerAggregator); score ties at the threshold may admit a few
            # extra docs. The per-segment top scores also go back to the host
            # so multi-segment shards can re-threshold shard-wide (pass 2).
            masked = jnp.where(match > 0, scores, -jnp.inf)
            k = min(shard_size, ndocs_pad)
            vals, _ = jax.lax.top_k(masked, k)
            thr = vals[k - 1]
            thr = jnp.where(jnp.isfinite(thr), thr, -jnp.inf)
            sel = match * (masked >= thr).astype(jnp.float32)
            out["topscores"] = vals
        out["doc_count"] = jnp.sum(sel)
        for i, sub in enumerate(subs):
            res = emit_agg(sub, seg_arrays, params, sel, scores)
            if res:
                out[f"sub{i}"] = res
        return out

    if kind == "geo_grid":
        _, prefix, gkind, field, precision, nb, subs = spec
        ords = params[f"{prefix}_gords"][:ndocs_pad]
        w = match * (ords >= 0).astype(jnp.float32)
        b = jnp.where(w > 0, ords, nb)
        out = {"counts": agg_ops.bucket_counts(b, w, nb)}
        for i, sub in enumerate(subs):
            out.update(_emit_bucketed_sub(jnp, sub, i, b, nb, seg_arrays,
                                          match, params))
        return out

    if kind == "nested_agg":
        _, prefix, path, subs = spec
        carr = dict(seg_arrays["nested"][path])
        parent = carr["parent"]
        live_p = seg_arrays["live"]
        carr["live"] = carr["live"] * live_p[parent]
        carr["__chain"] = ((seg_arrays, parent),) + seg_arrays.get("__chain", ())
        cmatch = match[parent] * jnp.where(carr["live"] > 0, 1.0, 0.0)
        out = {"doc_count": jnp.sum(cmatch)}
        for i, sub in enumerate(subs):
            res = emit_agg(sub, carr, params, cmatch, None)
            if res:
                out[f"sub{i}"] = res
        return out

    if kind == "reverse_nested":
        _, prefix, up_k, subs = spec
        chain = seg_arrays["__chain"]
        pmask, parent_arrays = match, seg_arrays
        for lvl in range(up_k):
            parent_arrays, parent_map = chain[lvl]
            npad_p = parent_arrays["live"].shape[0]
            pm = jnp.zeros(npad_p, jnp.float32).at[parent_map].add(pmask,
                                                                   mode="drop")
            pmask = ((pm > 0) & (parent_arrays["live"] > 0)).astype(jnp.float32)
        out = {"doc_count": jnp.sum(pmask)}
        for i, sub in enumerate(subs):
            res = emit_agg(sub, parent_arrays, params, pmask, None)
            if res:
                out[f"sub{i}"] = res
        return out

    if kind == "children_agg":
        _, prefix, cf, subs = spec
        g = params[f"{prefix}_gmatch"]
        pslot = params[f"{prefix}_pslot"]
        valid = pslot >= 0
        idx = jnp.clip(pslot, 0, g.shape[0] - 1)
        cfm = emit(cf, seg_arrays, params).matched
        cmask = (valid & (g[idx] > 0) & (cfm > 0)
                 & (seg_arrays["live"] > 0)).astype(jnp.float32)
        out = {"doc_count": jnp.sum(cmask)}
        for i, sub in enumerate(subs):
            res = emit_agg(sub, seg_arrays, params, cmask, None)
            if res:
                out[f"sub{i}"] = res
        return out

    if kind == "parent_agg":
        from jax import lax

        _, prefix, pf, subs = spec
        base = params[f"{prefix}_base"]
        cnt = lax.dynamic_slice(params[f"{prefix}_gmatch"], (base,), (ndocs_pad,))
        pfm = emit(pf, seg_arrays, params).matched
        pmask = ((cnt > 0) & (pfm > 0)
                 & (seg_arrays["live"] > 0)).astype(jnp.float32)
        out = {"doc_count": jnp.sum(pmask)}
        for i, sub in enumerate(subs):
            res = emit_agg(sub, seg_arrays, params, pmask, None)
            if res:
                out[f"sub{i}"] = res
        return out

    if kind == "composite_mv":
        _, prefix, field, nb, subs = spec
        kw = seg_arrays["keyword"][field]
        out = {"counts": agg_ops.terms_counts(kw, match, nb)}
        for i, sub in enumerate(subs):
            if sub and sub[0] == "stats":
                _, sprefix, sfield, col_exists, sumsq = sub
                if col_exists:
                    col = seg_arrays["numeric"][sfield]
                    out[f"sub{i}"] = agg_ops.terms_sub_metric(
                        kw, match, col["f32"], col["present"], nb,
                        params[f"{sprefix}_sinv"], sumsq)
        return out

    if kind == "composite":
        _, prefix, single, total, subs = spec
        valid = (match > 0) & (seg_arrays["live"] > 0)
        if single is None:      # several sources: the resident plane
            o = params[f"{prefix}_cplane"][:ndocs_pad]
        else:
            stype, field, min_b, interval, desc = single
            if stype == "terms":
                o = seg_arrays["keyword"][field]["min_ord"]
            elif stype == "hist":
                col = seg_arrays["numeric"][field]
                o = jnp.floor(col["f32"] / interval).astype(jnp.int32) - min_b
                o = jnp.where(col["present"] & (o >= 0) & (o < total), o, -1)
            else:  # date
                o = params[f"{prefix}_s0"][:ndocs_pad]
            if desc:            # slots in key order under the source's order
                o = jnp.where(o >= 0, total - 1 - o, -1)
        valid = valid & (o >= 0)
        w = valid.astype(jnp.float32)
        b = jnp.where(valid, o, total)
        out = {"counts": agg_ops.bucket_counts(b, w, total)}
        for i, sub in enumerate(subs):
            out.update(_emit_bucketed_sub(jnp, sub, i, b, total, seg_arrays,
                                          match * w, params))
        return out

    if kind == "matrix_stats":
        _, prefix, fields, exists = spec
        if not fields or not all(exists):
            return {"count": jnp.float32(0)}
        cols = [seg_arrays["numeric"][f] for f in fields]
        present_all = match > 0
        for c in cols:
            present_all = present_all & c["present"]
        w = present_all.astype(jnp.float32)
        X = jnp.stack([c["f32"] for c in cols])          # [k, ndocs]
        X = X - params[f"{prefix}_shift"][:, None]       # center (see prepare)
        Xw = X * w[None, :]
        out = {"count": jnp.sum(w),
               "s1": Xw.sum(axis=1),
               "s2": (Xw * X).sum(axis=1),
               "s3": (Xw * X * X).sum(axis=1),
               "s4": (Xw * X * X * X).sum(axis=1),
               # pairwise Σ w·x_i·x_j rides the MXU
               "xy": jnp.dot(Xw, X.T, preferred_element_type=jnp.float32),
               "shift": params[f"{prefix}_shift"]}
        return out

    if kind == "terms":
        _, prefix, field, nvocab_pad, subs = spec
        kw = seg_arrays["keyword"][field]
        out = {"counts": agg_ops.terms_counts(kw, match, nvocab_pad)}
        for i, sub in enumerate(subs):
            if sub and sub[0] == "stats":
                _, sprefix, sfield, col_exists, sumsq = sub
                if col_exists:
                    col = seg_arrays["numeric"][sfield]
                    out[f"sub{i}"] = agg_ops.terms_sub_metric(
                        kw, match, col["f32"], col["present"], nvocab_pad,
                        params[f"{sprefix}_sinv"], sumsq)
        return out

    if kind == "hist":
        _, prefix, field, interval, offset, min_b, nb, subs = spec
        col = seg_arrays["numeric"][field]
        w = match * jnp.where(col["present"], 1.0, 0.0)
        b = jnp.floor((col["f32"] - offset) / interval).astype(jnp.int32) - min_b
        b = jnp.where((b >= 0) & (b < nb) & (w > 0), b, nb)
        out = {"counts": agg_ops.bucket_counts(b, w, nb)}
        for i, sub in enumerate(subs):
            out.update(_emit_bucketed_sub(jnp, sub, i, b, nb, seg_arrays,
                                          match, params))
        return out

    if kind == "date_hist":
        (_, prefix, field, interval_ms, offset_ms, calendar, min_b, nb, subs,
         form) = spec
        counts, b = _date_bucket_counts(jnp, params, prefix, match, nb, form)
        out = {"counts": counts}
        for i, sub in enumerate(subs):
            out.update(_emit_bucketed_sub(jnp, sub, i, b, nb, seg_arrays,
                                          match, params))
        return out

    if kind == "range":
        _, prefix, field, keys, col_exists, subs, bounds = spec
        if not col_exists:
            return {}
        col = seg_arrays["numeric"][field]
        out = {"counts": agg_ops.range_counts(col["f32"], col["present"], match,
                                              params[f"{prefix}_lows"],
                                              params[f"{prefix}_highs"])}
        for ri in range(len(keys)):
            rmask = agg_ops.float_range_mask if False else None
            lo = params[f"{prefix}_lows"][ri]
            hi = params[f"{prefix}_highs"][ri]
            bucket_match = match * ((col["f32"] >= lo) & (col["f32"] < hi) &
                                    col["present"]).astype(jnp.float32)
            for i, sub in enumerate(subs):
                res = emit_agg(sub, seg_arrays, params, bucket_match, scores)
                if res:
                    out[f"r{ri}_sub{i}"] = res
        return out

    if kind == "geo_range":
        _, prefix, field, keys, col_exists, subs, _disp = spec
        if not col_exists:
            return {}
        geo = seg_arrays["geo"][field]
        dist = ops.geo_distance_vec(geo, params[f"{prefix}_olat"],
                                    params[f"{prefix}_olon"])
        out = {"counts": agg_ops.range_counts(dist, geo["present"], match,
                                              params[f"{prefix}_lows"],
                                              params[f"{prefix}_highs"])}
        for ri in range(len(keys)):
            lo = params[f"{prefix}_lows"][ri]
            hi = params[f"{prefix}_highs"][ri]
            bucket_match = match * ((dist >= lo) & (dist < hi) &
                                    geo["present"]).astype(jnp.float32)
            for i, sub in enumerate(subs):
                res = emit_agg(sub, seg_arrays, params, bucket_match, scores)
                if res:
                    out[f"r{ri}_sub{i}"] = res
        return out

    if kind == "filter":
        _, prefix, fspec, subs = spec
        fmask = emit(fspec, seg_arrays, params).matched
        bucket_match = match * fmask.astype(jnp.float32)
        out = {"count": jnp.sum(bucket_match)}
        for i, sub in enumerate(subs):
            res = emit_agg(sub, seg_arrays, params, bucket_match, scores)
            if res:
                out[f"sub{i}"] = res
        return out

    if kind == "filters":
        _, prefix, fspecs, subs = spec
        out = {}
        for ki, (key, fspec) in enumerate(fspecs):
            fmask = emit(fspec, seg_arrays, params).matched
            bucket_match = match * fmask.astype(jnp.float32)
            entry = {"count": jnp.sum(bucket_match)}
            for i, sub in enumerate(subs):
                res = emit_agg(sub, seg_arrays, params, bucket_match, scores)
                if res:
                    entry[f"sub{i}"] = res
            out[f"k{ki}"] = entry
        return out

    if kind == "global":
        _, prefix, subs = spec
        gmatch = seg_arrays["live"]
        out = {"count": jnp.sum(gmatch)}
        for i, sub in enumerate(subs):
            res = emit_agg(sub, seg_arrays, params, gmatch, scores)
            if res:
                out[f"sub{i}"] = res
        return out

    if kind == "missing":
        _, prefix, field, src, subs = spec
        if src == "numeric":
            present = seg_arrays["numeric"][field]["present"]
        elif src == "keyword":
            present = seg_arrays["keyword"][field]["min_ord"] >= 0
        else:
            present = jnp.zeros(ndocs_pad, bool)
        bucket_match = match * (~present).astype(jnp.float32)
        out = {"count": jnp.sum(bucket_match)}
        for i, sub in enumerate(subs):
            res = emit_agg(sub, seg_arrays, params, bucket_match, scores)
            if res:
                out[f"sub{i}"] = res
        return out

    if kind == "stats":
        _, prefix, field, col_exists, sumsq = spec
        if not col_exists:
            return {"empty": jnp.float32(0)}
        col = seg_arrays["numeric"][field]
        return agg_ops.stats_agg(col["f32"], col["present"], match,
                                 params[f"{prefix}_sinv"], sumsq)

    if kind == "vc_keyword":
        _, prefix, field = spec
        return {"count": agg_ops.value_count_keyword(seg_arrays["keyword"][field], match)}

    if kind == "card_kw":
        _, prefix, field, nvocab_pad = spec
        registers, distinct = agg_ops.cardinality_keyword_registers(
            seg_arrays["keyword"][field], match, nvocab_pad,
            params[f"{prefix}_hashes"], HLL_LOG2M)
        return {"registers": registers, "distinct": distinct}

    if kind == "card_num":
        _, prefix, field, col_exists = spec
        if not col_exists:
            return {"registers": jnp.zeros(1 << HLL_LOG2M, jnp.int32)}
        col = seg_arrays["numeric"][field]
        return {"registers": agg_ops.cardinality_numeric_registers(
            col["f32"], col["present"], match, HLL_LOG2M)}

    if kind in ("pctl", "pctl_ranks"):
        _, prefix, field, col_exists, _pv = spec
        if not col_exists:
            return {"hist": jnp.zeros(agg_ops.DD_NBINS, jnp.float32)}
        col = seg_arrays["numeric"][field]
        return {"hist": agg_ops.ddsketch_hist(col["f32"], col["present"], match)}

    if kind == "top_hits":
        _, prefix, size = spec
        return {"top_hits_marker": jnp.float32(size)}  # resolved host-side

    if kind == "dsampler":
        _, prefix, shard_size, dfield, maxper, use_kw, n_ord_pad, subs = spec
        # pass 1: the plain sampler's best-scoring shard_size matched docs
        if scores is None:
            sel = match
        else:
            masked = jnp.where(match > 0, scores, -jnp.inf)
            k = min(shard_size, ndocs_pad)
            vals, _ = jax.lax.top_k(masked, k)
            thr = vals[k - 1]
            thr = jnp.where(jnp.isfinite(thr), thr, -jnp.inf)
            sel = match * (masked >= thr).astype(jnp.float32)
        # pass 2: de-bias — keep at most max_docs_per_value docs per key
        # (reference DiversifiedAggregator): `maxper` rounds of per-key
        # argmax selection, ties to the lowest doc id (collapse machinery)
        if use_kw:
            ords = seg_arrays["keyword"][dfield]["min_ord"]
        else:
            ords = params[f"{prefix}_dords"][:ndocs_pad]
        g = jnp.where(ords >= 0, ords, n_ord_pad - 1).astype(jnp.int32)
        g = jnp.clip(g, 0, n_ord_pad - 1)
        sc = scores if scores is not None else jnp.zeros(ndocs_pad, jnp.float32)
        # docs without a key are each their own group (reference: only keyed
        # docs dedup); they bypass the rounds and stay selected
        keyed = ords >= 0
        remaining = jnp.where((sel > 0) & keyed, sc, -jnp.inf)
        doc_iota = jnp.arange(ndocs_pad, dtype=jnp.int32)
        chosen = sel * (~keyed).astype(jnp.float32)
        for _round in range(maxper):
            gbest = jnp.full(n_ord_pad, -jnp.inf, jnp.float32).at[g].max(remaining)
            cand = jnp.where(jnp.isfinite(remaining)
                             & (remaining == gbest[g]),
                             doc_iota, jnp.int32(2**31 - 1))
            gdoc = jnp.full(n_ord_pad, 2**31 - 1, jnp.int32).at[g].min(cand)
            pick = (doc_iota == gdoc[g]) & jnp.isfinite(remaining)
            chosen = chosen + pick.astype(jnp.float32)
            remaining = jnp.where(pick, -jnp.inf, remaining)
        out = {"doc_count": jnp.sum(chosen)}
        for i, sub in enumerate(subs):
            res = emit_agg(sub, seg_arrays, params, chosen, scores)
            if res:
                out[f"sub{i}"] = res
        return out

    if kind == "wavg":
        _, prefix, vf, wf, v_ok, w_ok, has_vm, has_wm = spec
        if (not v_ok and not has_vm) or (not w_ok and not has_wm):
            return {"vwsum": jnp.float32(0), "wsum": jnp.float32(0),
                    "count": jnp.float32(0)}
        if v_ok:
            vcol = seg_arrays["numeric"][vf]
            v, vp = vcol["f32"], vcol["present"]
        else:  # absent column + configured missing default: all docs default
            v = jnp.zeros(ndocs_pad, jnp.float32)
            vp = jnp.zeros(ndocs_pad, bool)
        if w_ok:
            wcol = seg_arrays["numeric"][wf]
            w, wp = wcol["f32"], wcol["present"]
        else:
            w = jnp.zeros(ndocs_pad, jnp.float32)
            wp = jnp.zeros(ndocs_pad, bool)
        vw, ws, cnt = agg_ops.weighted_avg_agg(
            v, vp, w, wp, match,
            params[f"{prefix}_vmiss"], params[f"{prefix}_wmiss"],
            has_vm, has_wm)
        return {"vwsum": vw, "wsum": ws, "count": cnt}

    if kind == "mad":
        _, prefix, field, col_exists = spec
        if not col_exists:
            return {"hist": jnp.zeros(agg_ops.DD_NBINS, jnp.float32)}
        col = seg_arrays["numeric"][field]
        return {"hist": agg_ops.ddsketch_hist(col["f32"], col["present"], match)}

    if kind == "geo_stat":
        _, prefix, gkind, field, col_exists = spec
        if not col_exists:
            return {"count": jnp.float32(0)}
        g = seg_arrays["geo"][field]
        if gkind == "geo_bounds":
            top, bottom, left, right, count = agg_ops.geo_bounds_agg(
                g["lat"], g["lon"], g["present"], match)
            return {"top": top, "bottom": bottom, "left": left,
                    "right": right, "count": count}
        slat, slon, count = agg_ops.geo_centroid_agg(
            g["lat"], g["lon"], g["present"], match)
        return {"slat": slat, "slon": slon, "count": count}

    if kind == "ip_range":
        _, prefix, field, keys, bounds, open_lo, open_hi, col_exists, subs = spec
        nr = len(keys)
        if not col_exists:
            out = {"counts": jnp.zeros(nr, jnp.float32)}
            return out
        col = seg_arrays["numeric"][field]
        iplo = params[f"{prefix}_iplo"]
        iphi = params[f"{prefix}_iphi"]
        out = {}
        counts = []
        for ri in range(nr):
            m = col["present"]
            if not open_lo[ri]:
                ge = ops.int64_range_mask(col, iplo[0, ri], iplo[1, ri],
                                          jnp.int32(2**31 - 1),
                                          jnp.int32(2**31 - 1), True, True)
                m = m & ge
            if not open_hi[ri]:
                lt = ops.int64_range_mask(col, jnp.int32(-2**31),
                                          jnp.int32(-2**31),
                                          iphi[0, ri], iphi[1, ri],
                                          True, False)
                m = m & lt
            sel = match * m.astype(jnp.float32)
            counts.append(jnp.sum(sel))
            for i, sub in enumerate(subs):
                res = emit_agg(sub, seg_arrays, params, sel, scores)
                if res:
                    out[f"r{ri}_sub{i}"] = res
        out["counts"] = jnp.stack(counts)
        return out

    if kind == "multi_terms":
        _, prefix, nord_pad, nvocab, subs = spec
        ords = params[f"{prefix}_mords"][:ndocs_pad]
        out = {"counts": agg_ops.ord_counts(ords, match, nord_pad)}
        b = jnp.where(ords >= 0, ords, nord_pad)
        for i, sub in enumerate(subs):
            out.update(_emit_bucketed_sub(jnp, sub, i, b, nord_pad,
                                          seg_arrays, match, params))
        return out

    if kind == "adjacency":
        _, prefix, fspecs, sep, subs = spec
        masks = []
        out = {}
        for key, fs in fspecs:
            masks.append((key, emit(fs, seg_arrays, params).matched))
        idx = 0
        for ai, (ka, ma) in enumerate(masks):
            sel = match * ma.astype(jnp.float32)
            out[f"c{idx}"] = jnp.sum(sel)
            for i, sub in enumerate(subs):
                res = emit_agg(sub, seg_arrays, params, sel, scores)
                if res:
                    out[f"c{idx}_sub{i}"] = res
            idx += 1
        for ai, (ka, ma) in enumerate(masks):
            for bi in range(ai + 1, len(masks)):
                kb, mb = masks[bi]
                sel = match * (ma & mb).astype(jnp.float32)
                out[f"c{idx}"] = jnp.sum(sel)
                for i, sub in enumerate(subs):
                    res = emit_agg(sub, seg_arrays, params, sel, scores)
                    if res:
                        out[f"c{idx}_sub{i}"] = res
                idx += 1
        return out

    if kind == "auto_date_hist":
        (_, prefix, field, unit, target, min_b, nb, window, subs,
         form) = spec
        first = params[f"{prefix}_dfirst"]
        counts, b = _date_bucket_counts(jnp, params, prefix, match, nb, form,
                                        first, window)
        out = {"counts": counts, "first": first}
        for i, sub in enumerate(subs):
            out.update(_emit_bucketed_sub(jnp, sub, i, b, window, seg_arrays,
                                          match, params))
        return out

    if kind in ("scripted", "sig_text"):
        # host-resolved: the partial needs the dense match mask
        return {"match_mask": match, "score_vec": (scores if scores is not None
                                                   else jnp.zeros_like(match))}

    raise ValueError(f"cannot emit aggregation spec [{kind}]")


def _date_bucket_counts(jnp, params: dict, prefix: str, match, nb: int,
                        form: str, first=None, window: Optional[int] = None):
    """A date histogram's counts over its resident bucket plane: ->
    (counts i32[nb], per-row bucket ids with `nb` where the row does not
    count, for the sub-aggregations). A row counts where it matches and
    has a value; `form` "runs" reads the counts at the runs' boundaries
    (`ops.aggs.run_counts`), "scatter" takes `ops.aggs.bucket_counts`,
    whose bucket count chooses between its dense form and a scatter-add.
    With `first` (a traced scalar) and `window` the counts are those of
    the plane's buckets [first, first + window) alone, i32[window]
    (`auto_date_histogram`: the plane spans the column, the response a few
    buckets of it)."""
    ids = params[f"{prefix}_dbuckets"][:match.shape[0]]
    held = (match > 0) & (ids >= 0)
    starts = params.get(f"{prefix}_dstarts")
    if window is not None:
        ids = ids - first
        held = held & (ids >= 0) & (ids < window)
        if form == "runs":
            at = first + jnp.arange(window + 1, dtype=jnp.int32)
            starts = starts[jnp.clip(at, 0, nb)]
        nb = window
    b = jnp.where(held, ids, nb)
    if form == "runs":
        return agg_ops.run_counts(held.astype(jnp.int32), starts), b
    return agg_ops.bucket_counts(b, held, nb), b


def _emit_bucketed_sub(jnp, sub, i: int, bucket_ids, nb: int, seg_arrays, match,
                       params: dict):
    """Metric sub-agg under an ordinal bucket agg: per-bucket accumulators
    (`ops.aggs.bucketed_sub_metric`: int32 counts, sums in limbs)."""
    if not sub or sub[0] != "stats":
        return {}
    _, sprefix, sfield, col_exists, sumsq = sub
    if not col_exists:
        return {}
    col = seg_arrays["numeric"][sfield]
    w = match * jnp.where(col["present"], 1.0, 0.0)
    return {f"sub{i}": agg_ops.bucketed_sub_metric(
        bucket_ids, col["f32"], w, nb, params[f"{sprefix}_sinv"], sumsq)}


# =====================================================================
# executor: jitted per-spec program
# =====================================================================

# filter-context mask cache (reference IndicesQueryCache: bitsets cached per
# (segment, filter)): dense bool masks keyed by (segment uid, live_gen,
# filter spec, param digest), device-resident, LRU-evicted
_FILTER_MASK_CACHE: "OrderedDict[tuple, Any]" = __import__(
    "collections").OrderedDict()
_FILTER_MASK_MAX_BYTES = 256 << 20   # byte-bounded like IndicesQueryCache
_FILTER_MASK_BYTES = [0]
_FILTER_HASH_BYTE_CAP = 1 << 20   # don't hash megabyte param sets
# msearch's per-body fallback searches on a thread pool; LRU mutation and
# the byte counter must not interleave (RLock: build path can re-enter via
# nested cached filters)
_FILTER_MASK_LOCK = __import__("threading").RLock()


def filter_mask_cache_stats() -> dict:
    return {"entries": len(_FILTER_MASK_CACHE),
            "bytes": _FILTER_MASK_BYTES[0]}


def _purge_masks_for_uid(uid: int) -> None:
    """Weakref finalizer: a dropped segment's masks can never hit again."""
    with _FILTER_MASK_LOCK:
        for k in [k for k in _FILTER_MASK_CACHE if k[0] == uid]:
            _FILTER_MASK_BYTES[0] -= _FILTER_MASK_CACHE[k].nbytes
            del _FILTER_MASK_CACHE[k]


@_instrumented_program_cache("mask", maxsize=256)
def _build_mask_executor(spec):
    import jax

    def mask_program(seg_arrays, params):
        with jax.named_scope("executor.match"):
            return emit(spec, seg_arrays, params).matched

    return jax.jit(mask_program)


# =====================================================================
# device phase-2 rescore programs (search/fastpath.py escalation rung)
# =====================================================================
#
# The candidate-union rescore launches with a dynamic candidate count per
# query (anything from a few head hits to the full T*4*L_HEAD tier-2
# union). Shapes are canonicalized HERE — pow2 candidate bucket with a
# floor, pow2 query batch in the caller — so the jit cache sees a bounded
# spec space (~10 C buckets x 4 T buckets per similarity) instead of one
# program per candidate count: the same recompile-storm discipline as the
# scoring executors above.

RESCORE_C_MIN = 1 << 8          # pad floor: tiny unions share one program
RESCORE_C_MAX = 1 << 17         # == MAX_T * 4 * L_HEAD (deepest tier-2
                                # union); beyond -> caller's host fallback


def rescore_cand_bucket(n: int) -> Optional[int]:
    """Candidate-axis pow2 bucket for a union of `n` ids; None when the
    union exceeds every compiled variant (host pass instead)."""
    if n <= 0 or n > RESCORE_C_MAX:
        return None
    return min(max(next_pow2(n), RESCORE_C_MIN), RESCORE_C_MAX)


@_instrumented_program_cache(
    "rescore", maxsize=64,
    shape_of=lambda T, C, k1, b: f"T{T}xC{C}")
def build_rescore_program(T: int, C: int, k1: float, b: float):
    """Cached callable for one (term-slot, candidate-bucket, similarity)
    shape of ops/rescore.exact_rescore_batch."""
    from ..ops.rescore import exact_rescore_batch

    def run(d_docs, d_tfdl, starts, lens, weights, avgdl, cand, rounds):
        return exact_rescore_batch(d_docs, d_tfdl, starts, lens, weights,
                                   avgdl, cand, rounds, T=T, C=C, k1=k1, b=b)

    return run


# ---------------------------------------------------------------------
# codec-v2 impact program (search/impactpath.py first pass)
# ---------------------------------------------------------------------
#
# Program variants are KEYED BY CODEC layout: (impact bit width, block
# slot bucket, candidate window); the gather is IMPACT_BLOCK slots a
# block slot, so the block bucket fixes its width. The program is the
# whole eager hot loop — integer impact gather over the host-pruned
# block windows, one dequant multiply, scatter-add, masked top-C — with
# no tf/doclen math anywhere in the trace.


@_instrumented_program_cache(
    "impact", maxsize=128,
    shape_of=lambda B, C, bits: f"B{B}xC{C}u{bits}")
def build_impact_program(B: int, C: int, bits: int):
    import jax

    def impact_program(d_docs, d_impacts, live, bstart, blen, bweight, msm):
        import jax.numpy as jnp
        ndocs_pad = live.shape[0]
        sm = ops.impact_score_blocks(d_docs, d_impacts, live, bstart,
                                     blen, bweight, IMPACT_BLOCK, ndocs_pad)
        with jax.named_scope("impact.topk"):
            ok = (sm.count >= msm) & (live > 0)
            masked = jnp.where(ok, sm.scores, ops.NEG_INF)
            total = jnp.sum(ok.astype(jnp.int32))
            kk = min(C, ndocs_pad)
            vals, idx = jax.lax.top_k(masked, kk)
        return vals, idx, total

    return jax.jit(impact_program)


# spec kinds whose second element is a node id (everything `prepare`
# returns with a nid head). Only these are renumbered — other (str, int)
# tuples (e.g. function-score sub-specs ("fvf", i, ...)) keep their ints.
_NID_KINDS = frozenset({
    "terms", "xterms", "phrase", "match_all", "match_none", "range",
    "exists", "ids", "bool", "const", "dismax", "boosting", "fnscore",
    "nested", "has_child", "has_parent", "rank_feature_col",
    "rank_feature_post", "sparse_dot", "distfeat_date", "distfeat_geo",
    "percolate", "script", "scriptscore", "knn", "span_host", "geodist",
    "geobox", "terms_set", "pinned", "combined", "geopoly", "geoshape",
    "cached_mask",
})


def _canon_spec(spec, mapping: Dict[int, int]):
    """Renumber node ids by first appearance so structurally identical
    specs hash equal across queries (nids are a global counter)."""
    if (isinstance(spec, tuple) and len(spec) >= 2
            and isinstance(spec[0], str) and isinstance(spec[1], int)
            and spec[0] in _NID_KINDS):
        cid = mapping.setdefault(spec[1], len(mapping))
        return (spec[0], cid) + tuple(_canon_spec(x, mapping)
                                      for x in spec[2:])
    if isinstance(spec, tuple):
        return tuple(_canon_spec(x, mapping) for x in spec)
    return spec


def _canon_param_key(key: str, mapping: Dict[int, int]) -> str:
    if key.startswith("q"):
        head, _, rest = key.partition("_")
        try:
            nid = int(head[1:])
        except ValueError:
            return key
        if nid in mapping:
            return f"q{mapping[nid]}_{rest}"
    return key


def filter_mask_for(node: LNode, seg: Segment, ctx: ShardContext):
    """Dense bool match mask for a filter-context clause, through the mask
    cache. Returns (mask np.bool_[ndocs_pad], cache_key, spec, local_params);
    mask/key are None when the clause's params are too big to hash cheaply
    (caller falls back to inlining spec+params into its own program)."""
    local: Dict[str, Any] = {}
    spec = prepare(node, seg, ctx, local)
    key, mapping = _filter_cache_key(spec, local, seg)
    if key is None:
        return None, None, spec, local
    mask = _mask_for_key(key, spec, local, mapping, seg,
                         needs=node_needs(node))
    return mask, key, spec, local


def node_needs(node: LNode) -> Optional[Dict[str, set]]:
    """Per-group field sets a filter node's program reads — the mask
    executor then ships ONLY those columns to device (Segment.pruned_arrays)
    instead of the whole segment. None = unknown node kind, use the full
    arrays."""
    needs: Dict[str, set] = {"postings": set(), "numeric": set(),
                             "keyword": set(), "geo": set(),
                             "doc_lens": set()}

    def walk(n) -> bool:
        if n is None:
            return True
        if isinstance(n, (LMatchAll, LMatchNone, LIds)):
            return True
        if isinstance(n, (LTerms, LExpandTerms)):
            needs["postings"].add(n.field)
            needs["doc_lens"].add(n.field)
            return True
        if isinstance(n, LRange):
            needs["numeric"].add(n.field)
            return True
        if isinstance(n, LExists):
            for g in ("postings", "numeric", "keyword", "geo"):
                needs[g].add(n.field)
            return True
        if isinstance(n, (LGeoDist, LGeoBox)):
            needs["geo"].add(n.field)
            return True
        if isinstance(n, LConstScore):
            return walk(n.child)
        if isinstance(n, LBool):
            return all(walk(c) for c in
                       n.musts + n.shoulds + n.must_nots + n.filters)
        return False     # unknown kind: caller ships the full arrays

    return needs if walk(node) else None


def _filter_cache_key(spec, local: dict, seg: Segment):
    """-> ((uid, live_gen, digest), nid-mapping) or (None, mapping)."""
    import hashlib

    # hash the nid-canonicalized spec + this segment's param payload
    mapping: Dict[int, int] = {}
    h = hashlib.blake2b(repr(_canon_spec(spec, mapping)).encode(),
                        digest_size=16)
    total = 0
    for k0 in sorted(local, key=lambda k: _canon_param_key(k, mapping)):
        v = local[k0]
        arr = np.asarray(v)
        total += arr.nbytes
        if total > _FILTER_HASH_BYTE_CAP:
            return None, mapping   # too big to hash cheaply: no caching
        h.update(_canon_param_key(k0, mapping).encode())
        h.update(arr.tobytes())
    return (seg.uid, seg.live_gen, h.hexdigest()), mapping


def _prepare_cached_filter(node: LNode, seg: Segment, ctx: ShardContext,
                           params: dict):
    """Prepare a filter-context clause through the mask cache: repeated
    filters (the classic "status:published + range" guardrails) reuse one
    device-resident bool mask instead of re-running their program."""
    mask, key, spec, local = filter_mask_for(node, seg, ctx)
    if mask is None:
        params.update(local)
        return spec
    nid = node.nid
    params[f"q{nid}_cached_mask"] = mask
    return ("cached_mask", nid)


def _mask_for_key(key, spec, local: dict, mapping: Dict[int, int],
                  seg: Segment, needs: Optional[Dict[str, set]] = None
                  ) -> np.ndarray:
    with _FILTER_MASK_LOCK:
        mask = _FILTER_MASK_CACHE.get(key)
        if mask is not None:
            _FILTER_MASK_CACHE.move_to_end(key)
            return mask
    if mask is None:
        # use whichever device already hosts this segment (replica copies
        # must not trigger a default-device re-host just for the cache)
        dev_key = None
        dc = seg._device_cache   # snapshot: pressure eviction swaps the dict
        if dc and None not in dc:
            dev_key = next(iter(dc))
        # jit against the CANONICAL spec/params so structurally identical
        # filters share one compiled program across requests
        canon = _canon_spec(spec, dict(mapping))
        canon_local = {_canon_param_key(k, mapping): v
                       for k, v in local.items()}
        exe = _build_mask_executor(canon)
        arrays = (seg.pruned_arrays(dev_key, needs) if needs is not None
                  else seg.device_arrays(dev_key))
        import jax
        launched = exe(arrays, canon_local)
        # host-resident bools: safe to feed executors on ANY device
        with TRACER.span("device.wait", program="mask"):
            mask = jax.device_get(launched)
        with _FILTER_MASK_LOCK:
            # two threads can race the same miss: keep the winner's entry so
            # the byte counter never double-counts one key
            prev = _FILTER_MASK_CACHE.get(key)
            if prev is not None:
                _FILTER_MASK_CACHE.move_to_end(key)
                return prev
            _FILTER_MASK_CACHE[key] = mask
            _FILTER_MASK_BYTES[0] += mask.nbytes
            if not hasattr(seg, "_mask_fin"):
                import weakref
                seg._mask_fin = weakref.finalize(seg, _purge_masks_for_uid,
                                                 seg.uid)
            while _FILTER_MASK_BYTES[0] > _FILTER_MASK_MAX_BYTES:
                _k, _v = _FILTER_MASK_CACHE.popitem(last=False)
                _FILTER_MASK_BYTES[0] -= _v.nbytes
    return mask


def prepare_collapse(collapse: Optional[dict], seg: Segment, ctx: ShardContext,
                     params: dict):
    """-> hashable collapse spec for _build_executor, or None. Keyword fields
    collapse on the device-resident min-ord column; numeric fields on the
    host-built per-segment value-rank ords (exact for 64-bit values)."""
    if not collapse:
        return None
    field = ctx.mappings.aliases.get(collapse["field"], collapse["field"])
    if field in seg.keyword_cols:
        n_ord_pad = next_pow2(len(seg.keyword_cols[field].vocab) + 1)
        return ("collapse", field, n_ord_pad, True)
    if field in seg.numeric_cols:
        col = seg.numeric_cols[field]
        ords = col.sort_ords()
        _p(params, "collapse_ords",
           np.pad(ords, (0, seg.ndocs_pad - len(ords)), constant_values=-1))
        n_ord_pad = next_pow2(seg.ndocs + 1)
        return ("collapse", field, n_ord_pad, False)
    # unmapped in this segment: every doc falls into the null group
    _p(params, "collapse_ords", np.full(seg.ndocs_pad, -1, np.int32))
    return ("collapse", field, 2, False)


@_instrumented_program_cache("executor", maxsize=512)
def _build_executor(full_spec):
    import jax

    return jax.jit(_executor_run_fn(full_spec))


def _executor_run_fn(full_spec):
    """The raw (unjitted) per-segment executor body, jitted by
    `_build_executor` — the ONE program both the direct path and the
    coalesced knn batch (`launch_segment_batch`) invoke, which is what
    makes a batched page byte-identical to its direct sibling."""
    import jax

    (query_spec, sort_spec, agg_specs, k_pad, named_specs, has_after,
     collapse_spec) = full_spec

    def executor_program(seg_arrays, params):
        import jax.numpy as jnp

        # the stages carry `jax.named_scope`s (metadata of the ops, read
        # from a trace by `benchmark/launch_reduce.py`): `executor.match`,
        # `.sort_key`, `.topk`, `.total`, `.aggs` (the forms of
        # `ops/aggs.py` name themselves inside it), `.named`
        with jax.named_scope("executor.match"):
            sm = emit(query_spec, seg_arrays, params)
        live = seg_arrays["live"]
        with jax.named_scope("executor.sort_key"):
            key = emit_sort_key(sort_spec, seg_arrays, params, sm.scores)
            matched = sm.matched
            if has_after:
                # search_after: strictly below the cursor in ranking order
                matched = matched & (key < params["after_key"])
            sm = ops.ScoredMask(sm.scores, matched.astype(jnp.float32))
        with jax.named_scope("executor.topk"):
            if collapse_spec is not None:
                _, cfield, n_ord_pad, use_kw = collapse_spec
                if use_kw:
                    ords = seg_arrays["keyword"][cfield]["min_ord"]
                else:
                    ords = params["collapse_ords"]
                vals, idx = ops.collapse_topk(key, sm.matched, live, ords,
                                              n_ord_pad, k_pad)
            else:
                vals, idx = ops.topk_docs(key, sm.matched, live, k_pad)
            topk_scores = sm.scores[idx]
        with jax.named_scope("executor.total"):
            total = ops.total_hits(sm.matched, live)
            max_score = jnp.max(jnp.where(sm.matched & (live > 0),
                                          sm.scores, -jnp.inf))
        out = {
            "topk_key": vals,
            "topk_idx": idx,
            "topk_scores": topk_scores,
            "total": total,
            "max_score": max_score,
        }
        aggs = {}
        with jax.named_scope("executor.aggs"):
            match_f = (sm.matched.astype(jnp.float32)
                       * jnp.where(live > 0, 1.0, 0.0))
            for name, aspec in agg_specs:
                res = emit_agg(aspec, seg_arrays, params, match_f, sm.scores)
                if res:  # oslint: disable=OSL201 -- host dict truthiness, trace-static
                    aggs[name] = res
        if aggs:  # oslint: disable=OSL201 -- host dict truthiness, trace-static
            out["aggs"] = aggs
        named = {}
        with jax.named_scope("executor.named"):
            for nm, nspec in named_specs:
                nsm = emit(nspec, seg_arrays, params)
                named[nm] = nsm.matched[idx]
        if named:  # oslint: disable=OSL201 -- host dict truthiness, trace-static
            out["named"] = named
        return out

    return executor_program


def launch_segment_batch(prepared: list, seg_arrays: dict):
    """LAUNCH a coalesced batch of per-query executor programs over one
    segment: every query's invocation of THE direct-path program
    (`_build_executor`, shared jit cache — structurally identical
    queries compile once) enqueues here UNFETCHED; the returned closure
    performs one deferred `device_get` sweep for the whole batch
    (oslint OSL504). `prepared` is a list of `(full_spec, params)`
    already canonicalized via `canon_query`.

    Deliberately NOT a vmapped mega-program: vmap's batched dot_general
    lands ~1 ULP away from the scalar program's contraction on real
    backends, and a scheduler-coalesced page must stay BYTE-identical
    to its scheduler-off sibling (the f32 single-domain serving
    contract, docs/FASTPATH.md) — the batching win here is cross-request
    coalescing + async launch pipelining, with the score domain pinned
    by construction."""
    import jax

    pending = []
    for full_spec, cparams in prepared:
        exe = _build_executor(full_spec)
        _count_launch(full_spec, seg_arrays, cparams)
        pending.append(exe(seg_arrays, cparams))   # invocation, no sync

    def _fetch():
        with TRACER.span("device.wait", program="executor"):
            return jax.device_get(pending)

    return _fetch


def _count_launch(full_spec, seg_arrays: dict, cparams: dict) -> None:
    """One launch of `executor_program`, counted. `executor.params_h2d_bytes`:
    the bytes of every host numpy array or scalar it is handed (each is
    copied to the device by the call; planes that live there are not
    counted). `executor.topk_keys_sorted`: the keys its `ops.topk_docs`
    hands to `lax.top_k` (a collapse launch takes `collapse_topk`: none).
    `executor.agg_bucket_launches` / `agg_run_counted`: its date-histogram
    bucket counts, and those whose spec says "runs"."""
    EXECUTOR_STATS.inc("params_h2d_bytes", sum(
        v.nbytes for v in cparams.values()
        if isinstance(v, (np.ndarray, np.generic))))
    _query, _sort, aggs, k_pad, _named, _after, collapse_spec = full_spec
    if collapse_spec is None:
        EXECUTOR_STATS.inc("topk_keys_sorted", ops.topk_keys_sorted(
            seg_arrays["live"].shape[0], k_pad))
    EXECUTOR_STATS.inc("launches")
    for node in _knn_nodes(_query):
        _count_knn(node, seg_arrays, cparams)
    forms = list(_date_count_forms(aggs))
    if forms:
        EXECUTOR_STATS.inc("agg_bucket_launches", len(forms))
        EXECUTOR_STATS.inc("agg_run_counted", forms.count("runs"))
    if aggs:
        cost = {"scatter": 0, "blocked": 0, "sub_buckets": 0,
                "ordinals": 0, "combinations": 0, "gathered": 0}
        for _name, aspec in aggs:
            _agg_cost(aspec, seg_arrays, cost)
        if cost["ordinals"]:
            AGG_STATS.inc("terms.ordinals", cost["ordinals"])
        if cost["combinations"]:
            AGG_STATS.inc("composite.combinations", cost["combinations"])
        if cost["gathered"]:
            AGG_STATS.inc("terms.gathered_rows", cost["gathered"])
        if cost["scatter"]:
            AGG_STATS.inc("scatter.updates", cost["scatter"])
        if cost["blocked"]:
            AGG_STATS.inc("blocked.rows", cost["blocked"])
        if cost["sub_buckets"]:
            AGG_STATS.inc("bucketed_sub.launches")
            AGG_STATS.inc("bucketed_sub.buckets", cost["sub_buckets"])


def _knn_nodes(spec):
    """The `knn` nodes of a query spec, its filters' included."""
    if isinstance(spec, (tuple, list)):
        if spec and spec[0] == "knn":
            yield spec
        for part in spec:
            yield from _knn_nodes(part)


def _count_knn(node, seg_arrays: dict, cparams: dict) -> None:
    """One `knn` node of a launch into `KNN_STATS`, by the predicate
    `emit` itself routes by."""
    _, nid, field, col_exists, _sim, _fspec, probe = node
    if not col_exists:
        return
    vc = seg_arrays["vector"][field]
    KNN_STATS.inc("queries")
    KNN_STATS.inc("query_vector_bytes", cparams[f"q{nid}_vec"].nbytes)
    if probe is not None and "ivf_centroids" in vc:
        nprobe, cap = probe
        KNN_STATS.inc("ann_queries")
        KNN_STATS.inc("lists_probed", nprobe)
        KNN_STATS.inc("candidate_slots", nprobe * cap)
    else:
        KNN_STATS.inc("exact_queries")


# where the sub-aggregation specs sit in the containers that hand their
# children this segment's own rows (the nested and join kinds hand them
# another segment's: not walked)
_AGG_CONTAINER_SUBS = {"filter": 3, "filters": 3, "global": 2, "missing": 4,
                       "range": 5, "geo_range": 5, "sampler": 4,
                       "adjacency": 4}


def _agg_cost(spec, seg_arrays: dict, cost: dict) -> None:
    """What `emit_agg` builds for `spec`, reckoned from the spec alone (the
    walk mirrors it): rows handed to scatters, rows read by `run_counts`
    and by the dense and product forms (`ops.aggs.count_form`, the
    predicate the emit chooses by), buckets that carry a metric
    sub-aggregation, and
    where `cost` has the keys the slots a terms-like group-by counts into
    (`ordinals`; `combinations` those of a composite) and the flat values
    a keyword group-by gathers the match to (`gathered`: its rows where
    the column is laid out by value, `ops.aggs.counts_by_value`). A keyword
    `cardinality` is the `terms_counts` under its registers. Kinds that
    reduce nothing per row of the segment add nothing."""
    if not isinstance(spec, tuple) or not spec:
        return
    kind = spec[0]
    n = seg_arrays["live"].shape[0]
    rows = nb = None                    # of this node's own bucket count
    slots = 0                           # of a terms-like group-by
    if kind == "hist":
        rows, nb, subs = n, spec[6], spec[7]
    elif kind == "date_hist":
        rows, nb, subs = n, spec[7], spec[8]
    elif kind == "auto_date_hist":
        rows, nb, subs = n, spec[7], spec[8]
    elif kind in ("terms", "sig_terms", "composite_mv", "card_kw",
                  "vc_keyword"):
        kw = seg_arrays["keyword"][spec[2]]
        rows = agg_ops.group_by_rows(kw)
        if "gathered" in cost and agg_ops.counts_by_value(kw):
            cost["gathered"] += rows
        if kind == "vc_keyword":        # one sum: no bucket count
            return
        nb = spec[3]
        if kind == "card_kw":   # `terms_counts` under the registers
            subs = ()
        else:
            subs, slots = spec[4], nb
    elif kind == "geo_grid":
        rows, nb, subs = n, spec[5], spec[6]
    elif kind == "composite":
        rows, nb, subs = n, spec[3], spec[4]
        slots = nb
        if "combinations" in cost:
            cost["combinations"] += nb
    elif kind == "multi_terms":
        rows, nb, subs = n, spec[2], spec[4]
        slots = nb
    if slots and "ordinals" in cost:    # (a caller that wants them asks)
        cost["ordinals"] += slots
    if rows is None:
        at = _AGG_CONTAINER_SUBS.get(kind)
        for sub in (spec[at] if at is not None else ()):
            _agg_cost(sub, seg_arrays, cost)
        return
    form = agg_ops.count_form(nb)
    if spec[-1] == "runs" or form != "scatter":
        cost["blocked"] += rows
    else:
        cost["scatter"] += rows
    for sub in subs:
        if sub and sub[0] == "stats" and sub[3]:
            if form != "scatter":   # all of it dense, or its count a product
                cost["blocked"] += rows
            if form != "dense":
                cost["scatter"] += rows * agg_ops.sub_metric_scatters(
                    rows, nb, sub[4])
            cost["sub_buckets"] += nb


def _date_count_forms(spec):
    """The `form` of every `date_hist` / `auto_date_hist` spec in a tree of
    aggregation specs (a pair that only carries such a name, an aggregation
    a user called so, ends in no form)."""
    if isinstance(spec, tuple):
        if (spec and spec[0] in ("date_hist", "auto_date_hist")
                and spec[-1] in ("runs", "scatter")):
            yield spec[-1]
        for x in spec:
            yield from _date_count_forms(x)


def canon_query(query_spec, sort_spec, k_pad: int, params: dict):
    """Canonicalize one prepared (query, sort, k_pad) triple + params the
    way `run_segment` does — the grouping key for batched launches."""
    mapping: Dict[int, int] = {}
    full = _canon_spec((query_spec, sort_spec, (), k_pad, (), False,
                        None), mapping)
    return full, {_canon_param_key(k, mapping): v
                  for k, v in params.items()}


def run_segment(query_spec, sort_spec, agg_specs, named_specs, k_pad: int,
                seg_arrays: dict, params: dict, has_after: bool = False,
                collapse_spec=None) -> dict:
    # canonicalize node ids (nids come from a global counter) so
    # structurally identical queries hit the same compiled executor instead
    # of recompiling per request — the XLA analog of Lucene's per-shape
    # query plan reuse
    mapping: Dict[int, int] = {}
    full = _canon_spec((query_spec, sort_spec, tuple(agg_specs), k_pad,
                        tuple(named_specs), has_after, collapse_spec),
                       mapping)
    cparams = {_canon_param_key(k, mapping): v for k, v in params.items()}
    exe = _build_executor(full)
    _count_launch(full, seg_arrays, cparams)
    return exe(seg_arrays, cparams)


@_instrumented_program_cache("gather", maxsize=256)
def _build_gather_executor(query_spec):
    """Scores of a query at an explicit doc list (rescore second pass,
    reference `search/rescore/QueryRescorer.java`)."""
    import jax

    def gather_program(seg_arrays, params):
        sm = emit(query_spec, seg_arrays, params)
        docs = params["gather_docs"]
        return sm.scores[docs], sm.matched[docs]

    return jax.jit(gather_program)


def run_gather_scores(query_spec, seg_arrays: dict, params: dict, docs: np.ndarray):
    mapping: Dict[int, int] = {}
    canon = _canon_spec(query_spec, mapping)
    exe = _build_gather_executor(canon)
    params = {_canon_param_key(k, mapping): v for k, v in params.items()}
    params["gather_docs"] = docs
    return exe(seg_arrays, params)


@_instrumented_program_cache("agg", maxsize=128)
def _build_agg_executor(key):
    """Aggs-only program (no top-k): the shard-wide sampler re-threshold
    pass re-runs just the agg tree with a global threshold param."""
    import jax

    query_spec, agg_spec = key

    def agg_program(seg_arrays, params):
        import jax.numpy as jnp

        with jax.named_scope("executor.match"):
            sm = emit(query_spec, seg_arrays, params)
        live = seg_arrays["live"]
        with jax.named_scope("executor.aggs"):
            match_f = (sm.matched.astype(jnp.float32)
                       * jnp.where(live > 0, 1.0, 0.0))
            return emit_agg(agg_spec, seg_arrays, params, match_f,
                            sm.scores)

    return jax.jit(agg_program)


def run_agg_only(query_spec, agg_spec, seg_arrays: dict, params: dict):
    mapping: Dict[int, int] = {}
    canon = _canon_spec((query_spec, agg_spec), mapping)
    cparams = {_canon_param_key(k, mapping): v for k, v in params.items()}
    return _build_agg_executor(canon)(seg_arrays, cparams)


@_instrumented_program_cache("agg", maxsize=128)
def _build_auto_range_executor(key):
    """The launch an `auto_date_histogram` takes first: the least and the
    greatest value of each of `fields` among the matched live documents,
    exact in the (hi, lo) words of the int64 planes, with their count."""
    import jax

    query_spec, fields = key
    big = np.int32((1 << 31) - 1)

    def auto_range_program(seg_arrays, params):
        import jax.numpy as jnp

        with jax.named_scope("executor.match"):
            sm = emit(query_spec, seg_arrays, params)
        out = {}
        with jax.named_scope("aggs.auto_range"):
            ok0 = (sm.matched > 0) & (seg_arrays["live"] > 0)
            for f in fields:
                col = seg_arrays["numeric"][f]
                ok, hi, lo = ok0 & col["present"], col["hi"], col["lo"]
                min_hi = jnp.min(jnp.where(ok, hi, big))
                max_hi = jnp.max(jnp.where(ok, hi, -big - 1))
                out[f] = (
                    min_hi,
                    jnp.min(jnp.where(ok & (hi == min_hi), lo, big)),
                    max_hi,
                    jnp.max(jnp.where(ok & (hi == max_hi), lo, -big - 1)),
                    jnp.sum(ok.astype(jnp.int32)))
        return out

    return jax.jit(auto_range_program)


def auto_date_range(query_spec, fields: Tuple[str, ...], seg_arrays: dict,
                    params: dict) -> Dict[str, Optional[Tuple[int, int]]]:
    """field -> (least, greatest) epoch ms among the documents `query_spec`
    matches in this segment, None where none has a value: one launch and
    one read, counted as `executor.launches` and
    `aggs.auto_date.refine_launches`."""
    import jax

    mapping: Dict[int, int] = {}
    canon = _canon_spec((query_spec, tuple(fields)), mapping)
    cparams = {_canon_param_key(k, mapping): v for k, v in params.items()}
    EXECUTOR_STATS.inc("launches")
    AGG_STATS.inc("auto_date.refine_launches")
    out = _build_auto_range_executor(canon)(seg_arrays, cparams)
    with TRACER.span("device.wait", program="agg", outputs="auto_range"):
        got = jax.device_get(out)

    def i64(hi, lo):
        return (int(hi) << 32) + int(lo) + (1 << 31)
    return {f: (i64(v[0], v[1]), i64(v[2], v[3])) if int(v[4]) else None
            for f, v in got.items()}
