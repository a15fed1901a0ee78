r"""Query compiler: logical plan -> jitted device program.

The analog of the reference chain QueryBuilder.toQuery -> Query.rewrite ->
Weight/Scorer (`index/query/*`, Lucene createWeight), redesigned for XLA, in
five modules whose imports point one way (left imports right):

    programs.py  ->  agg_compiler.py  ->  compiler.py  ->  plan.py  ->  planes.py
                            \_______________________________/^           ^
                             (agg_compiler and compiler both read planes)

- `plan.rewrite(query, ctx)` runs once per query on the host: analysis,
  multi-term expansion, index-wide idf/avgdl statistics -> a LogicalNode
  tree whose *structure* is static and whose numeric inputs are arrays.
- `prepare(node, segment)` (here; `agg_compiler.prepare_agg` for an
  aggregation tree) binds the plan to one segment: term -> CSR row lookups,
  pow2 bucket selection (from host row pointers — no device sync),
  producing a `spec` (hashable static structure) + `params` (traced
  arrays), over the per-segment planes `planes.py` keeps resident.
- `emit(spec, ...)` / `agg_compiler.emit_agg` interpret the spec inside a
  trace; `programs.py` assembles query, sort and aggregations into one
  program a request, jitted once per canonical spec and cached here
  (`instrumented_program_cache`) — segments with equal padded shapes and
  queries with equal structure all reuse the same XLA program.

This module keeps what is mutually recursive by design: a bound filter
(`_prepare_cached_filter`) and a join (`join_prepass`) launch a program at
prepare time, so query compile, program cache and filter-mask cache share it.

Every node evaluates to a dense ScoredMask over ndocs_pad; scoring leaves are
gather->scatter passes (ops.scoring), predicates are vectorized column
compares, and combinators are elementwise VPU ops that XLA fuses.
"""

from __future__ import annotations

import re
import time as _time_mod
from functools import lru_cache, wraps
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..index.segment import (CODEC_V1, CODEC_V2, IMPACT_BLOCK, Segment,
                             next_pow2, split_i64)
from ..obs.query_cost import nested_join_scatters
from ..ops import scoring as ops
from ..script import painless_lite as pl
from ..utils.metrics import METRICS, CounterGroup
from ..utils.trace import TRACER
from . import query_dsl as dsl
# AGG_STATS and BUCKET_PLANE_STATS are not used here: the benchmark
# (benchmark/deployments/*.py) resolves its five counter groups by `getattr`
# on this module and exits where one is missing (ROADMAP B1)
from .aggregations import AGG_STATS  # noqa: F401
from .plan import (LBool, LBoosting, LCombined, LConstScore, LDisMax,
                   LDistanceFeature, LExists, LExpandTerms, LFuncScore,
                   LGeoBox, LGeoDist, LGeoPolygon, LGeoShape, LHasChild,
                   LHasParent, LIds, LKnn, LMatchAll, LMatchNone, LNested,
                   LNode, LPercolate, LPhrase, LPinned, LRange, LRankFeature,
                   LScriptFilter, LScriptScore, LSourcePhrase, LSpanHost,
                   LSparseDot, LTerms, LTermsSet, ShardContext, prefix_rows)
from .planes import (BUCKET_PLANE_STATS, FIXED_MS, NESTED_STATS,  # noqa: F401
                     RANK_PLANE_STATS, nested_sort_plane, segment_plane)

INT32_SENTINEL = np.int32(2**31 - 1)

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

# the filter-mask cache's lock (the cache itself is below, with
# `mask_program`), declared up here so that no edit of `prepare` or `emit`
# moves its `declared` line in `lock_order.json`. msearch's per-body
# fallback searches on a thread pool; LRU mutation and the byte counter
# must not interleave (RLock: build path can re-enter via nested cached
# filters)
_FILTER_MASK_LOCK = __import__("threading").RLock()

# what a launch of `executor_program` is handed from the host (every numpy
# array or scalar among its params is one host->device copy a request; the
# per-segment planes that stay on the device so that it is handed none of
# `ndocs_pad` elements are counted where they are kept, `planes.py`);
# `topk_keys_sorted`: the keys a launch's top-k hands to `lax.top_k`;
# `agg_bucket_launches`: the date-histogram bucket counts the launches
# carried, `agg_run_counted`: those of them whose plane is in row order and
# took `ops.aggs.run_counts` (the others take `ops.aggs.bucket_counts`)
EXECUTOR_STATS = CounterGroup(METRICS, "executor", {"params_h2d_bytes": 0,
                                                    "topk_keys_sorted": 0,
                                                    "agg_bucket_launches": 0,
                                                    "agg_run_counted": 0,
                                                    "launches": 0})
# what the `knn` nodes of the launches cost, counted at each launch from
# the static spec (`programs.count_knn`): `queries` the nodes over a segment
# that holds the field, `ann_queries` / `exact_queries` those that probe the
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
# what the `phrase` nodes of the launches cost, counted at each launch from
# the static spec and the request's window lengths (`programs.count_phrase`):
# `queries` the nodes over a segment that holds positions, `anchor_slots`
# the padded slots of their anchor windows and `anchor_positions` the
# positions those hold, `probe_elems` the indices the join gathers one at
# a time (`ops.positions.probe_elems`: a row of the search is one index),
# `probe_rows` those of them that fetch a row of the planes or of their
# fences (`ops.positions.probe_rows`), `window_positions`
# the positions of all the phrases' terms, `programs` the distinct phrase
# shapes launched so far, `host_pair_builds` the (doc, position) arrays
# merged on the host (a `match_phrase_prefix` whose last term expands to
# several rows: `_union_pairs`; every other term is a window of the
# segment's resident planes and builds nothing)
PHRASE_STATS = CounterGroup(METRICS, "phrase", {"queries": 0,
                                                "anchor_slots": 0,
                                                "anchor_positions": 0,
                                                "probe_elems": 0,
                                                "probe_rows": 0,
                                                "window_positions": 0,
                                                "host_pair_builds": 0,
                                                "programs": 0})
# the precision a `knn` node's scoring product names. Unnamed, a batch of
# queries a launch (the vmapped `msearch` twin) is ONE bfloat16 pass of the
# chip's matrix unit, scores 4e-4 relative off where the 100th neighbour
# stands closer than that to the 101st; one query a launch, and a CPU, are
# float32 either way: tests_tpu/test_knn_tpu.py moves this to see both
_KNN_SCORE_PRECISION = "highest"


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


def instrumented_program_cache(family: str, maxsize: int,
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


# =====================================================================
# prepare: bind logical plan to one segment -> (spec, params)
# =====================================================================

F32_MIN = np.float32(-3.4e38)
F32_MAX_HOST = np.float32(3.4e38)


def put_param(params: dict, key: str, value) -> str:
    params[key] = value
    return key


def scalar_f32(params, key, v) -> str:
    return put_param(params, key, np.float32(v))


def scalar_i32(params, key, v) -> str:
    return put_param(params, key, np.int32(v))


def _i64_bounds(params, nid: int, lo, hi) -> Tuple[str, str, str, str]:
    lo = -(2**63) if lo is None else int(lo)
    hi = 2**63 - 1 if hi is None else int(hi)
    lo_hi, lo_lo = split_i64(np.asarray([lo]))
    hi_hi, hi_lo = split_i64(np.asarray([hi]))
    return (put_param(params, f"q{nid}_lohi", lo_hi[0]), put_param(params, f"q{nid}_lolo", lo_lo[0]),
            put_param(params, f"q{nid}_hihi", hi_hi[0]), put_param(params, f"q{nid}_hilo", hi_lo[0]))


UNION_PAIRS_MAX = 64        # a segment's cached prefix unions


def _union_pairs(seg: Segment, pb, rows: Tuple[int, ...]):
    """(doc plane, position plane, positions) of the union of several
    terms' positions (a `match_phrase_prefix`'s expanded last term: the one
    phrase term that is no window of the segment's resident planes),
    lex-sorted by (doc, position), sentinel-padded to the anchor bucket of
    its length and put on the device; the segment keeps the last
    `UNION_PAIRS_MAX`, charged to the HBM ledger as `phrase_pairs` and
    released when evicted or when the segment's device arrays are dropped
    (`Segment.drop_device`)."""
    import jax

    from ..obs.hbm_ledger import LEDGER
    from ..ops.positions import anchor_bucket
    cache = seg.__dict__.setdefault("_phrase_unions", {})
    key = (pb.field, rows)
    held = cache.get(key)
    if held is not None:
        return held[:3]
    PHRASE_STATS.inc("host_pair_builds")
    slices = [pb.row_slice(r) for r in rows]
    d = np.concatenate([np.repeat(pb.doc_ids[a:b],
                                  np.diff(pb.pos_starts[a: b + 1]))
                        for a, b in slices])
    p = np.concatenate([pb.positions[pb.pos_starts[a]: pb.pos_starts[b]]
                        for a, b in slices])
    order = np.lexsort((p, d))
    bucket = anchor_bucket(len(d))
    d_dev = jax.device_put(_pad_to_sentinel(d[order].astype(np.int32), bucket))
    p_dev = jax.device_put(_pad_to_sentinel(p[order].astype(np.int32), bucket))
    alloc = LEDGER.register(
        "phrase_pairs", int(d_dev.nbytes + p_dev.nbytes), owner=seg,
        segment=seg, label=f"phrase-pairs[{seg.name}][{pb.field}]")
    while len(cache) >= UNION_PAIRS_MAX:
        LEDGER.release(cache.pop(next(iter(cache)))[3])
    cache[key] = (d_dev, p_dev, len(d), alloc)
    return d_dev, p_dev, len(d)


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
        put_param(params, f"q{nid}_vec", v)
        scalar_f32(params, f"q{nid}_qsq", float(np.dot(node.vector, node.vector)))
    scalar_f32(params, f"q{nid}_boost", node.boost)
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
        put_param(params, f"q{nid}_rows", rows)
        put_param(params, f"q{nid}_w", w)
        put_param(params, f"q{nid}_aux", a)
        scalar_f32(params, f"q{nid}_msm", node.msm)
        scalar_f32(params, f"q{nid}_avgdl", ctx.avgdl(node.field))
        scalar_f32(params, f"q{nid}_boost", node.boost)
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
        put_param(params, f"q{nid}_docs", arr)
        scalar_f32(params, f"q{nid}_boost", node.weight)
        return ("ids", nid, pad)

    if isinstance(node, LPhrase):
        pb = seg.postings.get(node.field)
        if pb is None or pb.pos_starts is None:
            return ("match_none", nid)
        from ..ops import positions as pos_ops
        planes = seg.device_positions(node.field, ctx.device)
        if planes is None:
            return ("match_none", nid)
        m_terms = len(node.terms)
        last = m_terms - 1
        # a term is a window of the field's resident planes: (positions,
        # first slot, None); a prefix that expands to several rows is the
        # union's own arrays: (positions, 0, (doc, pos))
        wins = []
        for i, t in enumerate(node.terms):
            if node.prefix_last and i == last:
                rows = list(prefix_rows(pb, t, node.max_expansions))
            else:
                r = pb.row(t)
                rows = [r] if r >= 0 else []
            if not rows:
                return ("match_none", nid)  # phrase needs every term
            if len(rows) == 1:
                a, b = pb.row_slice(rows[0])
                lo = int(pb.pos_starts[a])
                wins.append((int(pb.pos_starts[b]) - lo, lo, None))
            else:
                d_dev, p_dev, n = _union_pairs(seg, pb, tuple(rows))
                wins.append((n, 0, (d_dev, p_dev)))
        # an exact phrase anchors on its term of fewest positions (Lucene
        # leads a phrase by its cheapest term): every occurrence has one
        # position of every slot, so the count is the same and the shifts
        # follow. A sloppy or span form keeps its first term
        exact = node.slop == 0 and not node.ordered and not node.gap_cost
        anchor = min(range(m_terms), key=lambda i: (wins[i][0], i)) \
            if exact else 0
        order = [anchor] + [i for i in range(m_terms) if i != anchor]
        shape = pos_ops.phrase_shape([wins[i][0] for i in order])
        own = []        # a slot's own arrays' length; 0: the planes
        for slot, i in enumerate(order):
            arrays = wins[i][2]
            own.append(0 if arrays is None else int(arrays[0].shape[0]))
            if arrays is not None:
                put_param(params, f"q{nid}_d{slot}", arrays[0])
                put_param(params, f"q{nid}_p{slot}", arrays[1])
        if not all(own):    # the planes, and the fence levels searched
            for key in pos_ops.plane_keys(shape[1]):
                put_param(params, f"q{nid}_pos_{key}", planes[key])
        put_param(params, f"q{nid}_len",
                  np.asarray([wins[i][0] for i in order], np.int32))
        put_param(params, f"q{nid}_off",
                  np.asarray([wins[i][1] for i in order], np.int32))
        put_param(params, f"q{nid}_shift",
                  np.asarray([i - anchor for i in order], np.int32))
        sim = node.sim
        b_eff = sim.b if node.has_norms else 0.0
        scalar_f32(params, f"q{nid}_w", node.weight)
        scalar_f32(params, f"q{nid}_slop", node.slop)
        scalar_f32(params, f"q{nid}_avgdl", ctx.avgdl(node.field))
        return ("phrase", nid, node.field, m_terms, shape,
                float(sim.k1), float(b_eff), node.ordered, node.gap_cost,
                tuple(own))

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
        put_param(params, f"q{nid}_rows", rows)
        scalar_f32(params, f"q{nid}_boost", node.boost)
        layout = ("impact" if getattr(seg, "codec_version",
                                      CODEC_V1) >= CODEC_V2
                  and pb is not None and pb.impact is not None else "tf")
        return ("xterms", nid, node.field, T_pad, bucket, layout)

    if isinstance(node, LMatchAll):
        scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("match_all", nid)

    if isinstance(node, LMatchNone):
        return ("match_none", nid)

    if isinstance(node, LRange):
        scalar_f32(params, f"q{nid}_boost", node.boost)
        if node.kind == "int":
            _i64_bounds(params, nid, node.lo, node.hi)
        else:
            scalar_f32(params, f"q{nid}_flo",
                       -np.inf if node.lo is None else node.lo)
            scalar_f32(params, f"q{nid}_fhi",
                       np.inf if node.hi is None else node.hi)
        return ("range", nid, node.field, node.kind, node.include_lo, node.include_hi,
                node.field in seg.numeric_cols)

    if isinstance(node, LExists):
        src = ("numeric" if node.field in seg.numeric_cols else
               "keyword" if node.field in seg.keyword_cols else
               "geo" if node.field in seg.geo_cols else
               "dl" if node.field in seg.doc_lens else
               "none")
        scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("exists", nid, node.field, src)

    if isinstance(node, LIds):
        docs = [seg.id2doc[i] for i in node.ids if i in seg.id2doc]
        pad = next_pow2(max(len(docs), 1), floor=8)
        arr = np.full(pad, INT32_SENTINEL, dtype=np.int32)
        arr[: len(docs)] = docs
        put_param(params, f"q{nid}_docs", arr)
        scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("ids", nid, pad)

    if isinstance(node, LBool):
        scalar_f32(params, f"q{nid}_msm", node.msm)
        scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("bool", nid,
                tuple(prepare(c, seg, ctx, params) for c in node.musts),
                tuple(prepare(c, seg, ctx, params) for c in node.shoulds),
                tuple(prepare(c, seg, ctx, params) for c in node.must_nots),
                tuple(_prepare_cached_filter(c, seg, ctx, params)
                      for c in node.filters))

    if isinstance(node, LConstScore):
        scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("const", nid, prepare(node.child, seg, ctx, params))

    if isinstance(node, LDisMax):
        scalar_f32(params, f"q{nid}_tie", node.tie_breaker)
        scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("dismax", nid, tuple(prepare(c, seg, ctx, params) for c in node.children))

    if isinstance(node, LBoosting):
        scalar_f32(params, f"q{nid}_nb", node.negative_boost)
        scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("boosting", nid, prepare(node.positive, seg, ctx, params),
                prepare(node.negative, seg, ctx, params))

    if isinstance(node, LFuncScore):
        child_spec = prepare(node.child, seg, ctx, params)
        fn_specs = []
        for i, (fn, filt) in enumerate(zip(node.functions, node.fn_filters)):
            fspec = prepare(filt, seg, ctx, params) if filt is not None else None
            scalar_f32(params, f"q{nid}_fn{i}_w", fn.weight)
            if fn.kind == "field_value_factor":
                scalar_f32(params, f"q{nid}_fn{i}_factor", fn.factor)
                scalar_f32(params, f"q{nid}_fn{i}_missing",
                           fn.missing if fn.missing is not None else 1.0)
                fn_specs.append(("fvf", i, fn.field, fn.modifier,
                                 fn.field in seg.numeric_cols, fspec))
            elif fn.kind == "random_score":
                scalar_i32(params, f"q{nid}_fn{i}_seed", fn.seed)
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
        scalar_f32(params, f"q{nid}_boost", node.boost)
        scalar_f32(params, f"q{nid}_minscore",
                   node.min_score if node.min_score is not None else -3.4e38)
        return ("fnscore", nid, child_spec, tuple(fn_specs),
                node.score_mode, node.boost_mode)

    if isinstance(node, LNested):
        blk = seg.nested.get(node.path)
        if blk is None or blk.child.ndocs == 0:
            return ("match_none", nid)
        child_spec = prepare(node.child, blk.child, node.child_ctx, params)
        scalar_f32(params, f"q{nid}_boost", node.boost)
        cpad = blk.child.ndocs_pad
        NESTED_STATS.inc("queries")
        NESTED_STATS.inc("child_rows", cpad)
        NESTED_STATS.inc("child_rows_real", blk.child.ndocs)
        NESTED_STATS.inc("parents", seg.ndocs_pad)
        NESTED_STATS.inc("join_updates",
                         cpad * nested_join_scatters(node.score_mode))
        # the padded child rows are static: what `obs/query_cost.py`
        # prices the launched clause by
        return ("nested", nid, node.path, node.score_mode, child_spec, cpad)

    if isinstance(node, LHasChild):
        if node.pre is None:
            need = {"cnt"}
            if node.score_mode in ("sum", "avg"):
                need.add("sum")
            elif node.score_mode in ("max", "min"):
                need.add(node.score_mode)
            node.pre = join_prepass(node.child, node.join_index, tuple(sorted(need)), ctx)
        for k, v in node.pre.items():
            params[f"q{nid}_{k}"] = v
        pf_spec = prepare(node.parent_filter, seg, ctx, params)
        scalar_i32(params, f"q{nid}_base", node.join_index.seg_base(seg))
        # at least one matching child is always required (reference semantics)
        scalar_f32(params, f"q{nid}_minc", max(node.min_children, 1))
        scalar_f32(params, f"q{nid}_maxc", min(node.max_children, 2**31 - 1))
        scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("has_child", nid, node.score_mode, pf_spec)

    if isinstance(node, LHasParent):
        if node.pre is None:
            # parents occupy their own slot (base + doc): reuse the scatter
            # with identity slots — "cnt" is the match vector, "sum" the score
            node.pre = join_prepass(node.child, node.join_index, ("cnt", "sum"),
                                    ctx, self_slots=True)
        params[f"q{nid}_match"] = node.pre["cnt"]
        params[f"q{nid}_score"] = node.pre["sum"]
        params[f"q{nid}_pslot"] = node.join_index.pslot(seg)
        cf_spec = prepare(node.child_filter, seg, ctx, params)
        scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("has_parent", nid, node.use_score, cf_spec)

    if isinstance(node, LRankFeature):
        scalar_f32(params, f"q{nid}_p1", node.p1)
        scalar_f32(params, f"q{nid}_p2", node.p2)
        scalar_f32(params, f"q{nid}_boost", node.boost)
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
        put_param(params, f"q{nid}_rows", np.asarray([row], np.int32))
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
        put_param(params, f"q{nid}_rows", rows)
        w = np.zeros(T_pad, np.float32)
        w[: len(node.tokens)] = node.weights
        put_param(params, f"q{nid}_w", w)
        scalar_f32(params, f"q{nid}_boost", node.boost)
        total = sum(pb.doc_freq(t) for t in node.tokens)
        return ("sparse_dot", nid, node.field, T_pad, ops.pick_bucket(total))

    if isinstance(node, LDistanceFeature):
        scalar_f32(params, f"q{nid}_pivot", node.pivot)
        scalar_f32(params, f"q{nid}_boost", node.boost)
        if node.kind == "date":
            hi, lo = split_i64(np.asarray([node.origin], np.int64))
            scalar_i32(params, f"q{nid}_ohi", int(hi[0]))
            scalar_i32(params, f"q{nid}_olo", int(lo[0]))
            return ("distfeat_date", nid, node.field,
                    node.field in seg.numeric_cols)
        scalar_f32(params, f"q{nid}_lat", node.origin[0])
        scalar_f32(params, f"q{nid}_lon", node.origin[1])
        return ("distfeat_geo", nid, node.field, node.field in seg.geo_cols)

    if isinstance(node, LPercolate):
        from .percolate import segment_mask

        put_param(params, f"q{nid}_mask",
                  segment_mask(node.field, node.mini_seg, node.mini_ctx, seg))
        scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("percolate", nid)

    if isinstance(node, LScriptFilter):
        field_srcs, pkeys = _prepare_script(node.ast, node.params, seg, params,
                                            nid, "s")
        scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("script", nid, node.ast, field_srcs, pkeys)

    if isinstance(node, LScriptScore):
        child_spec = prepare(node.child, seg, ctx, params)
        field_srcs, pkeys = _prepare_script(node.ast, node.params, seg, params,
                                            nid, "s")
        scalar_f32(params, f"q{nid}_boost", node.boost)
        scalar_f32(params, f"q{nid}_minscore",
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
        put_param(params, f"q{nid}_ts_msm", msm)
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
        put_param(params, f"q{nid}_pin_docs", darr)
        put_param(params, f"q{nid}_pin_ranks", rarr)
        scalar_f32(params, f"q{nid}_boost", node.boost)
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
            put_param(params, f"q{nid}_cf_rows{fi}", rows)
            scalar_f32(params, f"q{nid}_cf_w{fi}", w)
            fspecs.append((fname, ops.pick_bucket(total), pb is not None))
            avgdl_c += w * ctx.avgdl(fname)
        put_param(params, f"q{nid}_cf_idf", idf)
        scalar_f32(params, f"q{nid}_cf_avgdl", max(avgdl_c, 1e-6))
        scalar_f32(params, f"q{nid}_cf_msm", node.msm)
        k1 = getattr(sim, "k1", 1.2)
        b_p = getattr(sim, "b", 0.75)
        return ("combined", nid, tuple(fspecs), T_pad, float(k1), float(b_p))

    if isinstance(node, LGeoDist):
        scalar_f32(params, f"q{nid}_lat", node.lat)
        scalar_f32(params, f"q{nid}_lon", node.lon)
        scalar_f32(params, f"q{nid}_rad", node.radius_m)
        scalar_f32(params, f"q{nid}_boost", node.boost)
        return ("geodist", nid, node.field, node.field in seg.geo_cols,
                node.inclusive)

    if isinstance(node, LGeoBox):
        for k, v in (("top", node.top), ("left", node.left),
                     ("bottom", node.bottom), ("right", node.right)):
            scalar_f32(params, f"q{nid}_{k}", v)
        scalar_f32(params, f"q{nid}_boost", node.boost)
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
        put_param(params, f"q{nid}_plat", lats)
        put_param(params, f"q{nid}_plon", lons)
        scalar_f32(params, f"q{nid}_boost", node.boost)
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
        put_param(params, f"q{nid}_shapemask", mask)
        scalar_f32(params, f"q{nid}_boost", node.boost)
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
        put_param(params, f"q{nid}_freq", freq)
        scalar_f32(params, f"q{nid}_w", node.weight)
        scalar_f32(params, f"q{nid}_avgdl", ctx.avgdl(node.field))
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
        FIXED_MS[mm.group(2)]
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
            scalar_f32(params, f"q{nid}_fn{i}_olat", lat)
            scalar_f32(params, f"q{nid}_fn{i}_olon", lon)
        elif ftype == "date":
            kind = "num"
            origin = (float(_time.time() * 1000)
                      if fn.origin in (None, "now")
                      else float(_parse_date(fn.origin, ft.date_format
                                             if ft is not None else None)))
            scale = _parse_time_ms(fn.scale)
            offset = _parse_time_ms(fn.offset or 0)
            scalar_f32(params, f"q{nid}_fn{i}_origin", origin)
        else:
            kind = "num"
            if fn.origin is None:
                raise dsl.QueryParseError("[decay] numeric requires [origin]")
            scale = float(fn.scale)
            offset = float(fn.offset or 0)
            scalar_f32(params, f"q{nid}_fn{i}_origin", float(fn.origin))
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
    scalar_f32(params, f"q{nid}_fn{i}_a", a)
    scalar_f32(params, f"q{nid}_fn{i}_offset", offset)
    col_map = seg.geo_cols if kind == "geo" else seg.numeric_cols
    return ("decay", i, shape, kind, field, field in col_map, fspec)


@instrumented_program_cache("join", maxsize=64)
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


def join_prepass(child: LNode, ji, need: Tuple[str, ...], ctx: ShardContext,
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
        scalar_f32(params, f"q{nid}_{tag}p_{k}", v)
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
                if not prefix_rows(pb, t, node.max_expansions):
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


# the request-level key of a launch's row span in `params` (not numbered by
# node: `canon_param_key` leaves it, and the program's key, alone)
ROW_SPAN = "row_span"


def row_span(node: LNode, seg: Segment) -> Optional[Tuple[int, int]]:
    """The rows [lo, hi) of `seg` outside which `node` matches nothing, from
    the host's columns alone, or None where nothing narrows them (the whole
    segment): a `range` over a column whose values are in row order
    (`NumericColumn.in_row_order`) is two binary searches, a `bool` the
    intersection of what it requires (`should` and `must_not` narrow
    nothing), anything else None. It holds every row the device's mask
    accepts: the search reads the representation the mask compares (the
    int64 values of kind `int`; of kind `float` the float64 values against
    the float32 neighbours of the rounded bounds, which rounding, being
    monotone, cannot pass), and the mask still decides every row inside it.
    Which of the two it is follows from the query's structure and the
    column's order, not from a bound's value. `can_match` is the case that
    the span is empty, and keeps its own contract."""
    if isinstance(node, LRange):
        col = seg.numeric_cols.get(node.field)
        filled = None if col is None else col.in_row_order
        if filled is None:
            return None
        lo, hi = node.lo, node.hi
        left, right = node.include_lo, node.include_hi
        if node.kind == "int":
            lo, hi = (None if b is None else int(b) for b in (lo, hi))
        else:       # one float32 outward, and inclusive: a superset
            lo = None if lo is None else np.nextafter(np.float32(lo),
                                                      np.float32(-np.inf))
            hi = None if hi is None else np.nextafter(np.float32(hi),
                                                      np.float32(np.inf))
            left = right = True
        first = 0 if lo is None else int(np.searchsorted(
            filled, lo, side="left" if left else "right"))
        end = len(filled) if hi is None else int(np.searchsorted(
            filled, hi, side="right" if right else "left"))
        return first, max(end, first)
    if isinstance(node, LBool):
        spans = [s for s in (row_span(c, seg)
                             for c in node.musts + node.filters) if s]
        if not spans:
            return None
        first = max(lo for lo, _hi in spans)
        return first, max(min(hi for _lo, hi in spans), first)
    return None


def bind_row_span(node: LNode, seg: Segment, params: dict) -> None:
    """`row_span` of a launch's query into `params`, as int32 [lo, hi],
    that the program's aggregations bound their block loops by
    (`programs.launch_span`): a window of another length is the same
    program. Nothing where nothing narrows the rows: such a launch runs the
    loops at their static length and carries no argument for it. One array
    and not two scalars: every host value a launch carries is a copy of
    its own to the device, 0.18 ms of the dispatch each on a v5e's host
    (PERF.md, PR 49)."""
    span = row_span(node, seg)
    if span is not None:
        put_param(params, ROW_SPAN, np.asarray(span, np.int32))


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

        (_, _, field, m_terms, (bucket, levels), k1, b, ordered, gap_cost,
         own) = spec
        dl = seg_arrays["doc_lens"].get(field, zeros)
        off, length = params[f"q{nid}_off"], params[f"q{nid}_len"]
        shift = params[f"q{nid}_shift"]

        def window(slot):
            if own[slot]:
                return pos_ops.whole(params[f"q{nid}_d{slot}"],
                                     params[f"q{nid}_p{slot}"],
                                     length[slot], levels)
            return pos_ops.resident(
                {key: params[f"q{nid}_pos_{key}"]
                 for key in pos_ops.plane_keys(levels)},
                off[slot], length[slot], levels)
        anchor_d, anchor_p = pos_ops.anchor_window(window(0), bucket)
        freq = pos_ops.phrase_freqs(
            anchor_d, anchor_p, [window(i) for i in range(1, m_terms)],
            params[f"q{nid}_slop"], ndocs_pad, ordered=ordered,
            gap_cost=gap_cost, shifts=[shift[i] for i in range(1, m_terms)])
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
        import jax

        _, _, path, score_mode, child_spec, _cpad = spec
        carr = seg_arrays["nested"][path]
        parent = carr["parent"]
        # the child clause over the child space, under the children's own
        # liveness: a deleted parent's children may match, and what they
        # add lands on their parent alone, which `live` masks below. (The
        # parents' mask gathered to every child, `live[parent]`, said the
        # same at the price of a gather the size of the child space.)
        with jax.named_scope("executor.nested_child"):
            sm = emit(child_spec, carr, params)
            cmatch = sm.matched
        # child -> parent: a scatter update a child slot, padding included
        # (padded children match nothing). `parent` is nondecreasing and
        # the scatters say so: undeclared, the compiler sorts the 2^25
        # indices of the `nested` cell first, a request and a compile
        with jax.named_scope("executor.nested_join"):
            cnt = zeros.at[parent].add(cmatch.astype(jnp.float32),
                                       indices_are_sorted=True)
            pmatch = cnt > 0
            if score_mode == "none":
                pscores = pmatch.astype(jnp.float32)
            elif score_mode == "max":
                neg_inf = jnp.full(ndocs_pad, -jnp.inf, jnp.float32)
                mx = neg_inf.at[parent].max(
                    jnp.where(cmatch, sm.scores, -jnp.inf),
                    indices_are_sorted=True)
                pscores = jnp.where(pmatch, mx, 0.0)
            elif score_mode == "min":
                pos_inf = jnp.full(ndocs_pad, jnp.inf, jnp.float32)
                mn = pos_inf.at[parent].min(
                    jnp.where(cmatch, sm.scores, jnp.inf),
                    indices_are_sorted=True)
                pscores = jnp.where(pmatch, mn, 0.0)
            else:
                total = zeros.at[parent].add(
                    jnp.where(cmatch, sm.scores, 0.0),
                    indices_are_sorted=True)
                pscores = total / jnp.maximum(cnt, 1.0) \
                    if score_mode == "avg" else total
            pmatch = pmatch & (live > 0)
            pscores = jnp.where(pmatch, pscores * params[f"q{nid}_boost"],
                                0.0)
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
        put_param(params, "sort_geo_olat", np.float32(lat))
        put_param(params, "sort_geo_olon", np.float32(lon))
        return ("geo_dist", gfield, desc, missing_last)
    nspec = primary.get("nested")
    if nspec and nspec.get("path"):
        # the key is a resident plane of the segment, like a numeric
        # field's ranks below: the request carries none of it
        plane = nested_sort_plane(seg, field, nspec["path"],
                                  primary.get("mode",
                                              "max" if desc else "min"))
        if plane is None:
            return ("missing_field", desc, missing_last)
        params["sort_ords"] = plane
        return ("field_ord", desc, missing_last)
    if field in seg.numeric_cols:
        params["sort_ords"], = segment_plane(
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
# the programs query compile itself launches or hands out: filter mask,
# rescore, impact, gather (the whole-request programs are programs.py's)
# =====================================================================

# filter-context mask cache (reference IndicesQueryCache: bitsets cached per
# (segment, filter)): dense bool masks keyed by (segment uid, live_gen,
# filter spec, param digest), device-resident, LRU-evicted
_FILTER_MASK_CACHE: "OrderedDict[tuple, Any]" = __import__(
    "collections").OrderedDict()
_FILTER_MASK_MAX_BYTES = 256 << 20   # byte-bounded like IndicesQueryCache
_FILTER_MASK_BYTES = [0]
_FILTER_HASH_BYTE_CAP = 1 << 20   # don't hash megabyte param sets


def filter_mask_cache_stats() -> dict:
    return {"entries": len(_FILTER_MASK_CACHE),
            "bytes": _FILTER_MASK_BYTES[0]}


def purge_masks_for_uid(uid: int) -> None:
    """Weakref finalizer: a dropped segment's masks can never hit again."""
    with _FILTER_MASK_LOCK:
        for k in [k for k in _FILTER_MASK_CACHE if k[0] == uid]:
            _FILTER_MASK_BYTES[0] -= _FILTER_MASK_CACHE[k].nbytes
            del _FILTER_MASK_CACHE[k]


@instrumented_program_cache("mask", maxsize=256)
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


@instrumented_program_cache(
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


@instrumented_program_cache(
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


def canon_spec(spec, mapping: Dict[int, int]):
    """Renumber node ids by first appearance so structurally identical
    specs hash equal across queries (nids are a global counter)."""
    if (isinstance(spec, tuple) and len(spec) >= 2
            and isinstance(spec[0], str) and isinstance(spec[1], int)
            and spec[0] in _NID_KINDS):
        cid = mapping.setdefault(spec[1], len(mapping))
        return (spec[0], cid) + tuple(canon_spec(x, mapping)
                                      for x in spec[2:])
    if isinstance(spec, tuple):
        return tuple(canon_spec(x, mapping) for x in spec)
    return spec


def canon_param_key(key: str, mapping: Dict[int, int]) -> str:
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
    key, mapping = filter_cache_key(spec, local, seg)
    if key is None:
        return None, None, spec, local
    mask = mask_for_key(key, spec, local, mapping, seg,
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


def filter_cache_key(spec, local: dict, seg: Segment):
    """-> ((uid, live_gen, digest), nid-mapping) or (None, mapping)."""
    import hashlib

    # hash the nid-canonicalized spec + this segment's param payload
    mapping: Dict[int, int] = {}
    h = hashlib.blake2b(repr(canon_spec(spec, mapping)).encode(),
                        digest_size=16)
    total = 0
    for k0 in sorted(local, key=lambda k: canon_param_key(k, mapping)):
        v = local[k0]
        arr = np.asarray(v)
        total += arr.nbytes
        if total > _FILTER_HASH_BYTE_CAP:
            return None, mapping   # too big to hash cheaply: no caching
        h.update(canon_param_key(k0, mapping).encode())
        h.update(arr.tobytes())
    return (seg.uid, seg.live_gen, h.hexdigest()), mapping


def _prepare_cached_filter(node: LNode, seg: Segment, ctx: ShardContext,
                           params: dict):
    """Prepare a filter-context clause through the mask cache: repeated
    filters (the classic "status:published + range" guardrails) reuse one
    device-resident bool mask instead of re-running their program. A
    clause of a child space (`plan.nested_context`) is inlined: its mask is
    an operand of the join in the same program, and the cache would read
    it to the host and hand it back, a byte a child slot each way."""
    if not ctx.cache_filters:
        return prepare(node, seg, ctx, params)
    mask, key, spec, local = filter_mask_for(node, seg, ctx)
    if mask is None:
        params.update(local)
        return spec
    nid = node.nid
    params[f"q{nid}_cached_mask"] = mask
    return ("cached_mask", nid)


def mask_for_key(key, spec, local: dict, mapping: Dict[int, int],
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
        canon = canon_spec(spec, dict(mapping))
        canon_local = {canon_param_key(k, mapping): v
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
                seg._mask_fin = weakref.finalize(seg, purge_masks_for_uid,
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
        put_param(params, "collapse_ords",
                  np.pad(ords, (0, seg.ndocs_pad - len(ords)), constant_values=-1))
        n_ord_pad = next_pow2(seg.ndocs + 1)
        return ("collapse", field, n_ord_pad, False)
    # unmapped in this segment: every doc falls into the null group
    put_param(params, "collapse_ords", np.full(seg.ndocs_pad, -1, np.int32))
    return ("collapse", field, 2, False)


@instrumented_program_cache("gather", maxsize=256)
def _build_gather_executor(query_spec, scope: Optional[str] = None):
    """Scores of a query at an explicit doc list (rescore second pass,
    reference `search/rescore/QueryRescorer.java`; the inner hits of a
    page's blocks, under the stage `scope`)."""
    import contextlib

    import jax

    def gather_program(seg_arrays, params):
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            sm = emit(query_spec, seg_arrays, params)
            docs = params["gather_docs"]
            return sm.scores[docs], sm.matched[docs]

    return jax.jit(gather_program)


def run_gather_scores(query_spec, seg_arrays: dict, params: dict,
                      docs: np.ndarray, scope: Optional[str] = None):
    mapping: Dict[int, int] = {}
    canon = canon_spec(query_spec, mapping)
    exe = _build_gather_executor(canon, scope)
    params = {canon_param_key(k, mapping): v for k, v in params.items()}
    params["gather_docs"] = docs
    return exe(seg_arrays, params)
