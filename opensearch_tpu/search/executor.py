"""Per-shard search execution + coordinator reduce. Analog of reference
`search/SearchService.java` (executeQueryPhase/executeFetchPhase),
`search/query/QueryPhase.java`, `search/fetch/FetchPhase.java`, and the
coordinator-side `action/search/SearchPhaseController.java`.

Query-then-fetch: the QUERY phase runs the jitted device program per segment
(scoring + top-k + aggs in one XLA program), returns light-weight candidate
descriptors; the coordinator merges candidates across shards; the FETCH phase
materializes `_source`, highlights, docvalue_fields for the winning docs only.
"""

from __future__ import annotations

import fnmatch
import time
from dataclasses import dataclass, field as dc_field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..index.engine import Engine
from ..index.segment import CODEC_V1, CODEC_V2, Segment, next_pow2
from ..obs import flight_recorder as _flight
from ..obs import query_cost as _qcost
from ..ops import aggs as agg_ops
from ..script.painless_lite import ScriptError as _ScriptError
from ..utils import deadline as _dl
from ..utils.trace import TRACER
from . import agg_compiler as AC
from . import compiler as C
from . import plan as PL
from . import planes as PN
from . import programs as PG
from . import fastpath
from . import impactpath
from . import query_dsl as dsl
from .aggregations import (AGG_STATS, AggNode, OrdinalBuckets,
                           _apply_bucket_pipelines, apply_pipelines_tree,
                           composite_sources, finalize, merge_partials,
                           parse_aggs)
from .highlight import (collect_query_terms, highlight_field,
                        highlight_fvh, highlight_unified)

INT32_SENTINEL = np.int32(2**31 - 1)


@dataclass
class Candidate:
    """One query-phase hit descriptor (analog of Lucene ScoreDoc + shard ref)."""

    shard: int
    seg_ord: int
    local_doc: int
    score: Optional[float]
    sort_values: Tuple            # host-comparable, already direction-adjusted
    raw_sort_values: Tuple        # user-facing sort array
    collapse_key: Any = None      # field-collapse group value (None = null group)


def _tie_collect_order(keys: np.ndarray, idx: np.ndarray,
                       valid: np.ndarray, seg) -> np.ndarray:
    """Candidate append order for one top-k window: device order
    normally (the stable shard sort then breaks full-tuple ties by
    append order == device doc-id order), but on a BP-reordered segment
    (index/reorder.py) key ties re-break by ARRIVAL rank first, so the
    served page does not depend on the permuted internal ids — the
    reorder parity contract. `tie_ranks()` is None everywhere else and
    this is a plain nonzero."""
    jj = np.nonzero(valid)[0]
    f = getattr(seg, "tie_ranks", None)
    tr = f() if f is not None else None
    if tr is None or len(jj) == 0:
        return jj
    d = np.clip(idx[jj].astype(np.int64), 0, len(tr) - 1)
    return jj[np.lexsort((tr[d], -keys[jj].astype(np.float64)))]


@dataclass
class ShardQueryResult:
    shard: int
    candidates: List[Candidate] = dc_field(default_factory=list)
    total: int = 0
    total_rel: str = "eq"   # "gte" when a pruned segment undercounted
    max_score: float = float("-inf")
    agg_partials: Dict[str, dict] = dc_field(default_factory=dict)
    segments: List[Segment] = dc_field(default_factory=list)
    named_by_doc: Dict[Tuple[int, int], List[str]] = dc_field(default_factory=dict)
    took_ms: float = 0.0
    # partial-results contract (docs/RESILIENCE.md): the deadline budget
    # ran out between segments / the terminate_after doc budget was hit —
    # both cross the distnode wire inside the pickled result
    timed_out: bool = False
    terminated_early: bool = False


def _suppress_score(body: dict) -> bool:
    """Reference `track_scores` semantics under a field sort: an
    explicit `track_scores: false` nulls per-hit `_score`. Absent
    track_scores keeps this engine's historical behavior — scores are
    free on device (documented divergence, docs/RESILIENCE.md)."""
    if body.get("track_scores") is not False or not body.get("sort"):
        return False
    specs = _norm_sort_specs(body)
    return bool(specs) and specs[0]["field"] != "_score"


_GEO_SORT_OPTS = {"order", "unit", "mode", "distance_type",
                  "ignore_unmapped", "nested"}
_DIST_UNITS = {"m": 1.0, "meters": 1.0, "km": 1000.0, "kilometers": 1000.0,
               "mi": 1609.344, "miles": 1609.344, "yd": 0.9144,
               "ft": 0.3048, "in": 0.0254, "cm": 0.01, "mm": 0.001,
               "nmi": 1852.0, "nauticalmiles": 1852.0}


def _norm_sort_specs(body: dict) -> List[dict]:
    out = []
    for s in body.get("sort", []):
        if isinstance(s, str):
            out.append({"field": s, "order": "desc" if s == "_score" else "asc"})
        else:
            ((f, spec),) = s.items()
            if f == "_geo_distance":
                # {"_geo_distance": {"location": <origin>, "order": ...,
                #  "unit": "km"}} (reference GeoDistanceSortBuilder)
                from ..index.mappings import _parse_geo
                opts = {k: v for k, v in spec.items() if k in _GEO_SORT_OPTS}
                geo_fields = [k for k in spec if k not in _GEO_SORT_OPTS]
                if len(geo_fields) != 1:
                    raise dsl.QueryParseError(
                        "[_geo_distance] sort needs exactly one geo field")
                lat, lon = _parse_geo(spec[geo_fields[0]])
                out.append({"field": "_geo_distance",
                            "geo_field": geo_fields[0],
                            "origin": (lat, lon),
                            "order": opts.get("order", "asc"),
                            "unit": opts.get("unit", "m")})
            elif isinstance(spec, str):
                out.append({"field": f, "order": spec})
            else:
                out.append({"field": f, **spec})
    return out


_LNODE_CHILD_ATTRS = ("musts", "shoulds", "must_nots", "filters",
                      "children", "child", "positive", "negative")


def _cost_predicted(lroot, seg, window: int) -> None:
    """Plan-time device-cost prediction from CSR block stats alone: each
    scoring term row the query touches contributes its TRUE posting count
    (8 bytes per slot on codec v1; 4 + impact width on codec-v2 eager
    fields — the cost model in docs/OBSERVABILITY.md). Noted per planned
    segment BEFORE any launched program shape exists; the launch sites
    note the padded shapes they actually move, and the profile `cost`
    block reconciles the two."""
    qc = _qcost.current()
    if qc is None:
        return
    npost = 0
    nbytes = 0
    stack = [(lroot, seg)]
    while stack:
        node, at = stack.pop()
        if node is None:
            continue
        if isinstance(node, PL.LNested):
            # the block join reads every child row's parent, whatever
            # matches; the child clause is the child space's
            blk = at.nested.get(node.path)
            if blk is not None:
                nbytes += blk.child.ndocs * _qcost.NESTED_CHILD_BYTES
                npost += blk.child.ndocs * _qcost.nested_join_scatters(
                    node.score_mode)
                stack.append((node.child, blk.child))
            continue
        terms = None
        if isinstance(node, (PL.LTerms, PL.LPhrase, PL.LSourcePhrase)):
            terms = node.terms
        elif isinstance(node, PL.LSparseDot):
            terms = node.tokens
        if terms:
            pb = at.postings.get(node.field)
            if pb is not None:
                df = sum(pb.doc_freq(t) for t in terms)
                npost += df
                v2 = (getattr(at, "codec_version", CODEC_V1)
                      >= CODEC_V2 and pb.impact is not None)
                if v2 and ((isinstance(node, PL.LTerms)
                            and node.mode == "score")
                           or (isinstance(node, PL.LSparseDot)
                               and pb.impact.kind == "feature")):
                    # codec v2: the eager plane replaces the f32 tf slot
                    # with a u8/u16 impact — predict the SMALLER volume
                    # (the claim the actual-launch stamps reconcile);
                    # learned-sparse feature planes price identically
                    nbytes += df * (4 + pb.impact.bits // 8)
                else:
                    nbytes += df * _qcost.POSTING_SLOT_BYTES
        for attr in _LNODE_CHILD_ATTRS:
            v = getattr(node, attr, None)
            if isinstance(v, (list, tuple)):
                stack.extend((c, at) for c in v)
            elif v is not None and not isinstance(v, (str, int, float,
                                                      bool)):
                stack.append((v, at))
    qc.note_predicted(nbytes, npost, window, segment=seg)


def compose_knn_query(body: dict) -> Optional[dsl.Query]:
    """The body's effective query tree, folding the ES-style top-level
    `knn` section ({"field", "query_vector", "k", "filter"}) into the DSL
    tree: knn alone, or bool-should'ed with the query (reference
    SearchSourceBuilder knn handling). Shared by the per-shard query
    phase and the batched-launch classifier so the two can never
    disagree on what a body means."""
    query = dsl.parse_query(body.get("query")) if (body.get("query")
                                                   or "knn" not in body) \
        else None
    knn_spec = body.get("knn")
    if knn_spec is not None:
        _np = knn_spec.get("method_parameters", {}).get(
            "nprobe", knn_spec.get("nprobe"))
        kq = dsl.KnnQuery(field=knn_spec["field"],
                          vector=list(knn_spec.get("query_vector",
                                                   knn_spec.get("vector",
                                                                []))),
                          k=int(knn_spec.get("k", 10)),
                          filter=(dsl.parse_query(knn_spec["filter"])
                                  if knn_spec.get("filter") else None),
                          boost=float(knn_spec.get("boost", 1.0)),
                          nprobe=int(_np) if _np is not None else None,
                          exact=bool(knn_spec.get("exact", False)))
        query = dsl.BoolQuery(should=[query, kq],
                              minimum_should_match="1") \
            if query is not None else kq
    return query


class ShardSearcher:
    """Executes searches over one shard's engine (one set of segments)."""

    def __init__(self, engine: Engine, shard_id: int = 0,
                 similarity=None, field_similarities=None,
                 index_key: Optional[str] = None, device=None):
        self.engine = engine
        self.shard_id = shard_id
        self.similarity = similarity
        self.field_similarities = field_similarities
        # shards sharing an index_key share collection statistics (DFS);
        # standalone searchers all fall into one default group
        self.index_key = index_key
        # replica read path (cluster/replication.py): segments come from the
        # replica's synced checkpoint, arrays hosted on its device
        self.device = device
        self.replica = None

    def context(self) -> PL.ShardContext:
        return PL.ShardContext(self.engine.mappings, self.engine.segments,
                               self.similarity, self.field_similarities,
                               device=self.device)

    # ---------------- QUERY phase ----------------

    def query_phase(self, body: dict, segments: Optional[List[Segment]] = None,
                    shard_ord: Optional[int] = None,
                    stats_ctx: Optional[PL.ShardContext] = None,
                    task=None) -> ShardQueryResult:
        """`shard_ord` overrides the candidate shard tag so a coordinator can
        search shards of several indices in one pass without id collisions.
        `stats_ctx` carries index-wide collection statistics (the coordinator
        DFS phase, reference DFS_QUERY_THEN_FETCH) so idf/avgdl — and thus
        scores — are identical across shards."""
        t0 = time.monotonic()
        if shard_ord is None:
            shard_ord = self.shard_id
        if segments is None:
            segments = (list(self.replica.segments) if self.replica is not None
                        else list(self.engine.segments))
        with TRACER.span("search.plan"):
            ctx = stats_ctx or PL.ShardContext(
                self.engine.mappings, segments, self.similarity,
                self.field_similarities, device=self.device)
            # derived (runtime) fields: mapping-level + search-body defs
            # materialize into per-segment columns before rewrite sees them
            ddefs = dict(getattr(ctx.mappings, "derived", {}) or {})
            if body.get("derived"):
                from . import derived as derived_mod
                try:
                    req_defs = derived_mod.parse_defs(body["derived"])
                    derived_mod.check_conflicts(ctx.mappings, req_defs)
                    ddefs.update(req_defs)
                except ValueError as e:
                    raise dsl.QueryParseError(str(e))
                import copy as _copy
                ctx = _copy.copy(ctx)
                ctx.mappings = derived_mod.MappingsOverlay(ctx.mappings, ddefs)
            if ddefs:
                from . import derived as derived_mod
                names = derived_mod.referenced(ddefs, body)
                if names:
                    from ..script.painless_lite import ScriptError
                    try:
                        for seg in segments:
                            derived_mod.ensure(seg, ctx.mappings, ddefs, names)
                    except (ScriptError, ValueError) as e:
                        raise dsl.QueryParseError(f"derived field: {e}")
            query = compose_knn_query(body)
            lroot = PL.rewrite(query, ctx, scoring=True)
            ctx._current_lroot = lroot  # children/parent aggs join against it

            size = int(body.get("size", 10))
            frm = int(body.get("from", 0))
            sort_specs = _norm_sort_specs(body)
            is_field_sort = bool(sort_specs) and sort_specs[0]["field"] not in ("_score",)
            # oversample: host tie-refinement + multi-key sorting need slack
            window = frm + size
            oversample = 2 if (is_field_sort or len(sort_specs) > 1) else 1
            agg_nodes = parse_aggs(body.get("aggs", body.get("aggregations")))
            named_nodes = _collect_named(lroot)
            rescores = body.get("rescore")
            if rescores is not None and not isinstance(rescores, list):
                rescores = [rescores]
            min_score = body.get("min_score")
            search_after = body.get("search_after")
            collapse = body.get("collapse")
            if collapse:
                if not isinstance(collapse, dict) or not collapse.get("field"):
                    raise dsl.QueryParseError("[collapse] requires [field]")
                if sort_specs and sort_specs[0]["field"] == "_script":
                    raise dsl.QueryParseError(
                        "cannot use [collapse] with a primary _script sort")

            # per-shard doc budget (reference terminate_after) + the ambient
            # request deadline (utils/deadline.py): both are enforced at
            # segment granularity — one segment is one device program, the
            # natural cancellation point — and both mark the result partial
            # (`terminated_early` / `timed_out`) with honest `gte` totals
            ta = int(body.get("terminate_after") or 0)
            deadline = _dl.current()

            result = ShardQueryResult(shard=shard_ord, segments=segments)
            ran_segs: List[Segment] = []

            # Pallas fast path: plain BM25 term-group top-k AND bool/filtered
            # shapes go through the fused kernels (search/fastpath.py); anything
            # they can't serve falls back to the general XLA plan per segment
            fast_spec = (fastpath.make_spec(lroot, sort_specs, agg_nodes,
                                            named_nodes, search_after, window,
                                            body)
                         if fastpath.enabled() and self.device is None else None)
            # codec-v2 eager-impact path (search/impactpath.py): the same pure
            # BM25 top-k shape class served from the quantized impact plane
            # with host block-max pruning — XLA, so it engages on every
            # backend. Segments decline per-segment (v1 codec, no plane), and
            # a failed serve certificate falls through to the exact program.
            imp_spec = (impactpath.make_spec(lroot, sort_specs, agg_nodes,
                                             named_nodes, search_after, window,
                                             body)
                        if self.device is None else None)

        # concurrent segment search, TPU-style: a many-segment shard runs
        # as ONE kernel launch over the concatenated shard view instead of
        # the serial per-segment loop (reference
        # ConcurrentQueryPhaseSearcher parallelizes with threads; a TPU
        # wants one bigger launch) — pure term-group specs only
        if fast_spec is not None and len(segments) > 1 and not rescores \
                and not ta:
            # (terminate_after needs the per-segment loop: the concat
            # shard-view launch scans every segment in one program)
            sv = fastpath.shard_search(self, ctx, fast_spec, window)
            if sv is not None:
                view, fout = sv
                if _qcost.current() is not None:
                    # the per-segment loop below won't run — predict per
                    # view segment here (the view concatenates them)
                    for vseg in view.segments:
                        _cost_predicted(lroot, vseg, window)
                self._collect_view_topk(result, view, fout, shard_ord,
                                        sort_specs, min_score, ctx)
                result.candidates.sort(key=lambda c: c.sort_values)
                result.candidates = result.candidates[: window * oversample]
                result.took_ms = (time.monotonic() - t0) * 1000.0
                if task is not None:
                    task.track(device_seconds=result.took_ms / 1000.0)
                return result

        import jax
        seg_t0 = time.monotonic()
        for seg_ord, seg in enumerate(segments):
            if ta and result.total >= ta:
                result.terminated_early = True
                if any(s.live_count for s in segments[seg_ord:]):
                    result.total_rel = "gte"
                break
            if deadline is not None and deadline.exhausted():
                result.timed_out = True
                if any(s.live_count for s in segments[seg_ord:]):
                    result.total_rel = "gte"
                break
            if task is not None:
                # cooperative cancellation between segment programs
                # (reference CancellableTask checks between leaves) +
                # device-time accounting for backpressure victim selection
                task.track(device_seconds=time.monotonic() - seg_t0)
                seg_t0 = time.monotonic()
                task.ensure_not_cancelled()
            if seg.live_count == 0:
                continue
            if not _aggs_need_all_segments(agg_nodes) and not C.can_match(lroot, seg):
                # segment provably has no hits (can_match pre-filter); only
                # global/filter-family aggs see docs the query doesn't match,
                # so ordinary agg trees still allow the skip
                continue
            _cost_predicted(lroot, seg, window)
            if fast_spec is not None:
                fout = fastpath.segment_search(seg, ctx, fast_spec, window)
                if fout is not None:
                    ran_segs.append(seg)
                    self._collect_topk(result, fout, seg, seg_ord, shard_ord,
                                       sort_specs, rescores, min_score,
                                       is_field_sort, ctx)
                    continue
            if imp_spec is not None:
                with TRACER.span("impactpath.serve"):
                    iout = impactpath.segment_search(seg, ctx, imp_spec,
                                                     window)
                if iout is not None:
                    ran_segs.append(seg)
                    self._collect_topk(result, iout, seg, seg_ord,
                                       shard_ord, sort_specs, rescores,
                                       min_score, is_field_sort, ctx)
                    continue
            with TRACER.span("search.prepare"):
                tief = getattr(seg, "tie_ranks", None)
                tie_aware = tief is not None and tief() is not None
                if sort_specs and sort_specs[0]["field"] == "_script":
                    # script order is host-computed: collect the full segment
                    # window so the host re-sort sees every matching doc
                    k_pad = seg.ndocs_pad
                else:
                    k_pad = min(next_pow2(max(window * oversample, 16)), seg.ndocs_pad)
                    if tie_aware:
                        # BP-reordered segment: seed the window deep enough
                        # that a saturated all-distinct extraction already
                        # holds >= window*oversample strictly-better lanes
                        # above its deepest key — otherwise the widen loop
                        # below pays a second launch with zero ties present
                        k_pad = min(next_pow2(max(window * oversample * 2, 32)),
                                    seg.ndocs_pad)
                params: Dict[str, Any] = {}
                qspec = C.prepare(lroot, seg, ctx, params)
                qc = _qcost.current()
                if qc is not None:
                    # actual launched-shape cost of the XLA path: the program
                    # gathers the spec's pow2 buckets (ops.gather_postings)
                    # and extracts a k_pad top-k window
                    gb, slots = _qcost.spec_gather_shape(qspec)
                    qc.note_actual(gb, slots, k_pad, path="xla", segment=seg)
                sspec = C.prepare_sort(sort_specs, seg, params)
                agg_specs = []
                if agg_nodes:
                    C.bind_row_span(lroot, seg, params)
                    auto_ranges = _auto_date_ranges(
                        agg_nodes, qspec, seg, ctx, params, self.device)
                    with TRACER.span("search.aggs.prepare"):
                        for i, an in enumerate(agg_nodes):
                            if an.kind == "top_hits":
                                continue  # from this segment's top-k below
                            agg_specs.append((an.name, AC.prepare_agg(
                                an, seg, ctx, params, f"a{i}",
                                auto_range=auto_ranges.get(an.name))))
                named_specs = []
                for nm, nnode in named_nodes:
                    nparams: Dict[str, Any] = {}
                    nspec = C.prepare(nnode, seg, ctx, params)
                    named_specs.append((nm, nspec))
                has_after = search_after is not None
                if has_after:
                    if sort_specs and sort_specs[0]["field"] == "_script":
                        raise dsl.QueryParseError(
                            "search_after is not supported with a primary _script sort")
                    params["after_key"] = np.float32(
                        _after_key_value(search_after, sort_specs, seg))
                cspec = C.prepare_collapse(collapse, seg, ctx, params)
            while True:
                try:
                    out = PG.run_segment(qspec, sspec, agg_specs,
                                         named_specs, k_pad,
                                         seg.device_arrays(self.device),
                                         params, has_after,
                                         collapse_spec=cspec)
                except _ScriptError as e:
                    # device-script trace failures are user errors (HTTP 400)
                    raise dsl.QueryParseError(f"script compile error: {e}")
                with TRACER.span("device.wait", program="executor"):
                    # one sweep for the window and the scalars: a read
                    # of its own is a device->host hop of its own, about
                    # a millisecond each with the device idle
                    keys, idx, scores, total, ms, named_np = jax.device_get(
                        (out["topk_key"], out["topk_idx"],
                         out["topk_scores"], out["total"],
                         out["max_score"], out.get("named", {})))
                valid = keys > -np.inf
                if not tie_aware or sort_specs:
                    # widen only for score sorts: a field sort's primary
                    # key can tie across most of the segment (enum-like
                    # fields), where widening would walk k_pad all the
                    # way to ndocs_pad per query — those ties break by
                    # the host's full sort tuple downstream, the same
                    # oversample approximation unreordered segments use
                    break
                # BP-reordered segment (index/reorder.py): device top-k
                # breaks key ties by PERMUTED internal id, so a tie class
                # cut at the extraction edge may have dropped its
                # arrival-earliest members — _tie_collect_order can only
                # re-sort lanes that were extracted. A cut class always
                # contains the deepest extracted key; it is provably
                # complete when extraction didn't saturate. Widen until
                # the page-relevant classes are whole, then drop the
                # (possibly cut) deepest class — safe once enough
                # strictly-better candidates cover this segment's
                # contribution cap (window * oversample).
                nvalid = int(valid.sum())
                if nvalid < k_pad or k_pad >= seg.ndocs_pad:
                    break
                kmin = keys[valid].min()
                if int((keys > kmin).sum()) >= window * oversample:
                    valid &= keys > kmin
                    break
                k_pad = min(next_pow2(k_pad * 2), seg.ndocs_pad)

            ran_segs.append(seg)
            with TRACER.span("search.collect"):
                total, ms = int(total), float(ms)
                result.total += total
                if ms > result.max_score:
                    result.max_score = ms

                if agg_specs:
                    aggs_out = _fetch_agg_outputs(out.get("aggs", {}))
                    with TRACER.span("search.aggs.partial"):
                        for name, aspec in agg_specs:
                            node = next(a for a in agg_nodes
                                        if a.name == name)
                            partial = _device_agg_to_partial(
                                node, aspec, aggs_out.get(name), seg, ctx)
                            result.agg_partials.setdefault(
                                name, []).append(partial)

                # rescore second pass over this segment's window
                if rescores:
                    scores = self._apply_rescores(rescores, ctx, seg, idx, valid, scores)

                for j in _tie_collect_order(keys, idx, valid, seg):
                    d = int(idx[j])
                    if d >= seg.ndocs:
                        continue
                    sc = float(scores[j])
                    if min_score is not None and not is_field_sort and sc < min_score:
                        continue
                    sort_vals, raw_vals = _host_sort_values(sort_specs, seg, d, sc)
                    cand = Candidate(shard_ord, seg_ord, d, sc, sort_vals, raw_vals)
                    if collapse:
                        cand.collapse_key = _collapse_key_value(
                            seg, ctx.mappings.aliases.get(collapse["field"],
                                                          collapse["field"]), d)
                    result.candidates.append(cand)
                    names = [nm for nm, arr in named_np.items() if arr[j]]
                    if names:
                        result.named_by_doc[(seg_ord, d)] = names

        if ta and result.total >= ta:
            # the budget was crossed (possibly exactly on the final
            # segment): the reference flags terminated_early whenever the
            # collector hit its limit, whether or not docs remained
            result.terminated_early = True

        self._resample_samplers(agg_nodes, result, ran_segs, ctx, lroot)

        # top_hits root aggs from candidates
        for i, an in enumerate(agg_nodes):
            if an.kind == "top_hits":
                top = sorted(result.candidates, key=lambda c: -(c.score or 0.0))
                size_th = int(an.body.get("size", 3))
                hits = [self._fetch_one(result.segments[c.seg_ord], c, an.body)
                        for c in top[:size_th]]
                result.agg_partials[an.name] = [{"hits": hits, "total": result.total,
                                                 "size": size_th}]

        # keep only the best window per shard
        result.candidates.sort(key=lambda c: c.sort_values)
        result.candidates = result.candidates[: window * oversample]
        result.took_ms = (time.monotonic() - t0) * 1000.0
        return result

    @TRACER.spanned("search.collect")
    def _collect_view_topk(self, result: ShardQueryResult, view, out: dict,
                           shard_ord: int, sort_specs, min_score,
                           ctx) -> None:
        """Fold the shard-view launch's top-k (view-space doc ids) into the
        shard result, translating to (segment, local doc)."""
        keys = np.asarray(out["topk_key"])
        idx = np.asarray(out["topk_idx"])
        scores = np.asarray(out["topk_scores"])
        valid = keys > -np.inf
        result.total += int(out["total"])
        if out.get("total_rel") == "gte":
            result.total_rel = "gte"
        ms = float(out["max_score"])
        if ms > result.max_score:
            result.max_score = ms
        for j in _tie_collect_order(keys, idx, valid, view):
            d = int(idx[j])
            if d < 0 or d >= view.ndocs:
                continue
            sc = float(scores[j])
            if min_score is not None and sc < min_score:
                continue
            seg_ord, seg, local = view.locate(d)
            sort_vals, raw_vals = _host_sort_values(sort_specs, seg, local,
                                                    sc)
            result.candidates.append(
                Candidate(shard_ord, seg_ord, local, sc, sort_vals,
                          raw_vals))

    @TRACER.spanned("search.collect")
    def _collect_topk(self, result: ShardQueryResult, out: dict, seg: Segment,
                      seg_ord: int, shard_ord: int, sort_specs, rescores,
                      min_score, is_field_sort: bool, ctx) -> None:
        """Fold one segment's top-k output (fast path) into the shard result —
        the same bookkeeping the general path does inline."""
        keys = np.asarray(out["topk_key"])
        idx = np.asarray(out["topk_idx"])
        scores = np.asarray(out["topk_scores"])
        valid = keys > -np.inf
        result.total += int(out["total"])
        if out.get("total_rel") == "gte":
            result.total_rel = "gte"
        ms = float(out["max_score"])
        if ms > result.max_score:
            result.max_score = ms
        if rescores:
            scores = self._apply_rescores(rescores, ctx, seg, idx, valid, scores)
        for j in _tie_collect_order(keys, idx, valid, seg):
            d = int(idx[j])
            if d < 0 or d >= seg.ndocs:
                continue
            sc = float(scores[j])
            if min_score is not None and not is_field_sort and sc < min_score:
                continue
            sort_vals, raw_vals = _host_sort_values(sort_specs, seg, d, sc)
            result.candidates.append(
                Candidate(shard_ord, seg_ord, d, sc, sort_vals, raw_vals))

    def _resample_samplers(self, agg_nodes, result: ShardQueryResult,
                           ran_segs: List[Segment], ctx, lroot) -> None:
        """Shard-wide sampler pass 2: pass 1 thresholds per segment, so a
        multi-segment shard would sample up to segments×shard_size docs.
        Merge the per-segment top scores, derive ONE shard-wide threshold,
        and re-run just the agg tree with it (reference SamplerAggregator
        samples per shard). Top-level sampler nodes only — a sampler nested
        under another bucket agg keeps per-segment semantics."""
        for an in agg_nodes:
            if an.kind != "sampler":
                continue
            partials = [p for p in result.agg_partials.get(an.name, []) if p]
            tops = [p.pop("topscores") for p in partials if "topscores" in p]
            if len(partials) <= 1 or not tops:
                continue
            shard_size = max(int(an.body.get("shard_size", 100)), 1)
            allscores = np.concatenate(tops)
            allscores = allscores[np.isfinite(allscores)]
            if len(allscores) <= shard_size:
                continue  # fewer matches than shard_size: pass 1 was exact
            thr = float(np.sort(allscores)[-shard_size])
            an._global_thr = thr
            try:
                new_parts = []
                for seg in ran_segs:
                    params: Dict[str, Any] = {}
                    qspec = C.prepare(lroot, seg, ctx, params)
                    C.bind_row_span(lroot, seg, params)
                    aspec = AC.prepare_agg(an, seg, ctx, params, "rs")
                    out = _fetch_agg_outputs(PG.run_agg_only(
                        qspec, aspec, seg.device_arrays(self.device), params))
                    new_parts.append(_device_agg_to_partial(an, aspec, out, seg, ctx))
                result.agg_partials[an.name] = new_parts
            finally:
                an._global_thr = None

    def _apply_rescores(self, rescores: List[dict], ctx, seg, idx, valid, scores):
        for rs in rescores:
            spec = rs.get("query", rs)
            window = int(rs.get("window_size", 10))
            rq = dsl.parse_query(spec.get("rescore_query"))
            qw = float(spec.get("query_weight", 1.0))
            rw = float(spec.get("rescore_query_weight", 1.0))
            mode = spec.get("score_mode", "total")
            lr = PL.rewrite(rq, ctx, scoring=True)
            params: Dict[str, Any] = {}
            rspec = C.prepare(lr, seg, ctx, params)
            docs = np.where(valid, idx, INT32_SENTINEL % seg.ndocs_pad).astype(np.int32)
            rscores, rmatched = C.run_gather_scores(rspec, seg.device_arrays(self.device), params,
                                                    np.minimum(docs, seg.ndocs_pad - 1))
            rscores = np.asarray(rscores)
            rmatched = np.asarray(rmatched)
            in_window = np.arange(len(scores)) < window
            combined = np.where(rmatched, _combine_rescore(mode, qw * scores, rw * rscores),
                                qw * scores)
            scores = np.where(valid & in_window, combined, scores)
        return scores

    # ---------------- FETCH phase ----------------

    def fetch_phase(self, result: ShardQueryResult, selected: List[Candidate],
                    body: dict, stats_ctx: Optional[PL.ShardContext] = None) -> List[dict]:
        # explain must recompute with the SAME collection-wide statistics the
        # query phase scored with, or _explanation diverges from _score
        ctx = stats_ctx or PL.ShardContext(
            self.engine.mappings, result.segments, self.similarity,
            self.field_similarities, device=self.device)
        qtree = dsl.parse_query(body.get("query"))
        lroot = PL.rewrite(qtree, ctx, scoring=True)
        hl_terms = collect_query_terms(lroot) if body.get("highlight") else {}
        nested_ihs = _nested_queries_with_inner_hits(qtree)
        join_ihs = _join_queries_with_inner_hits(qtree)
        perc_multi = [pq for pq in _walk_query_nodes(qtree, dsl.PercolateQuery)
                      if len(pq.documents) > 1]
        ih_cache: Dict[Tuple[int, int], Any] = {}
        # the matching children of the page's parents: one launch a nested
        # clause and segment, over the page's blocks
        inner = {}
        if nested_ihs:
            with TRACER.span("fetch.inner_hits", clauses=len(nested_ihs),
                             hits=len(selected)):
                for seg_ord in sorted({c.seg_ord for c in selected}):
                    docs = [c.local_doc for c in selected
                            if c.seg_ord == seg_ord]
                    for nq in nested_ihs:
                        inner[id(nq), seg_ord] = self._nested_inner_hits(
                            nq, result.segments[seg_ord], docs, ctx)
        suppress = _suppress_score(body) if body.get("sort") else False
        hits = []
        for c in selected:
            seg = result.segments[c.seg_ord]
            hit = self._fetch_one(seg, c, body, hl_terms,
                                  suppress_score=suppress)
            names = result.named_by_doc.get((c.seg_ord, c.local_doc))
            if names:
                hit["matched_queries"] = names
            if body.get("explain") and body.get("explain") != "device_plan":
                hit["_explanation"] = explain_doc(lroot, seg, c.local_doc, ctx)
            for nq in nested_ihs:
                self._add_inner_hits(hit, nq, seg, c, inner[id(nq), c.seg_ord])
            for jq in join_ihs:
                self._add_join_inner_hits(hit, jq, seg, c, ctx, ih_cache)
            for pq in perc_multi:
                self._add_percolate_slots(hit, pq, seg, c, ih_cache)
            hits.append(hit)
        return hits

    def _add_percolate_slots(self, hit: dict, pq, seg: Segment, c: Candidate,
                             ih_cache: dict) -> None:
        """`_percolator_document_slot` for multi-document percolation
        (reference PercolatorMatchedSlotSubFetchPhase)."""
        from . import percolate as P

        key = ("perc", id(pq))
        if key not in ih_cache:
            ih_cache[key] = P.build_mini(self.engine.mappings, pq.documents)
        mini_seg, mini_ctx = ih_cache[key]
        field = self.engine.mappings.resolve_field(pq.field)
        slots = P.document_slots(field.name if field else pq.field, mini_seg,
                                 mini_ctx, seg, c.local_doc)
        # multiple percolate clauses disambiguate by _name, like the reference
        key = (f"_percolator_document_slot_{pq.name}" if pq.name
               else "_percolator_document_slot")
        hit.setdefault("fields", {})[key] = slots

    def _join_child_scores(self, jq_key, lnode, cseg, ctx, ih_cache):
        """Dense matched scores of a join inner query over one segment
        (cached per (query, segment) across the fetch loop)."""
        key = (jq_key, id(cseg))
        if key not in ih_cache:
            cparams: Dict[str, Any] = {}
            cspec = C.prepare(lnode, cseg, ctx, cparams)
            docs = np.arange(cseg.ndocs_pad, dtype=np.int32)
            sc, cm = C.run_gather_scores(cspec, cseg.device_arrays(self.device), cparams, docs)
            ih_cache[key] = (np.asarray(sc), np.asarray(cm))
        return ih_cache[key]

    def _add_join_inner_hits(self, hit: dict, jq, seg: Segment, c: Candidate,
                             ctx, ih_cache: dict) -> None:
        """inner_hits for has_child (matching children under each parent hit)
        and has_parent (the matched parent of each child hit) — reference
        modules/parent-join InnerHitContextBuilder."""
        from .join import get_join_index

        jf = self.engine.mappings.join_field
        if jf is None:
            return
        ji = get_join_index(ctx.segments, jf)
        ih = jq.inner_hits or {}
        if isinstance(jq, dsl.HasChildQuery):
            name = ih.get("name", jq.type)
            inner_q = dsl.BoolQuery(must=[jq.query or dsl.MatchAllQuery()],
                                    filter=[dsl.TermQuery(field=jf, value=jq.type)])
            lkey = ("jihc", id(jq))
            if lkey not in ih_cache:
                ih_cache[lkey] = PL.rewrite(inner_q, ctx, scoring=True)
            lnode = ih_cache[lkey]
            kids = []
            for cseg, cd in ji.children_of(ji.seg_base(seg) + c.local_doc):
                sc, cm = self._join_child_scores(id(jq), lnode, cseg, ctx, ih_cache)
                if cm[cd] and cseg.live[cd]:
                    kids.append((float(sc[cd]), cseg, cd))
            kids.sort(key=lambda t: -t[0])
            frm, size = int(ih.get("from", 0)), int(ih.get("size", 3))
            child_hits = []
            for sc_v, cseg, cd in kids[frm: frm + size]:
                ch = {"_index": hit.get("_index", ""), "_id": cseg.ids[cd],
                      "_score": sc_v, "_routing": seg.ids[c.local_doc]}
                if ih.get("_source", True) is not False:
                    ch["_source"] = cseg.sources[cd]
                child_hits.append(ch)
            hit.setdefault("inner_hits", {})[name] = {
                "hits": {"total": {"value": len(kids), "relation": "eq"},
                         "max_score": kids[0][0] if kids else None,
                         "hits": child_hits}}
            return
        # has_parent: the one matched parent of this child hit
        name = ih.get("name", jq.parent_type)
        slot = int(ji.pslot(seg)[c.local_doc])
        loc = ji.slot_to_doc(slot) if slot >= 0 else None
        parent_hits = []
        if loc is not None:
            pseg, pd = loc
            inner_q = dsl.BoolQuery(must=[jq.query or dsl.MatchAllQuery()],
                                    filter=[dsl.TermQuery(field=jf,
                                                          value=jq.parent_type)])
            lkey = ("jihp", id(jq))
            if lkey not in ih_cache:
                ih_cache[lkey] = PL.rewrite(inner_q, ctx, scoring=True)
            sc, cm = self._join_child_scores(id(jq), ih_cache[lkey], pseg, ctx,
                                             ih_cache)
            if cm[pd] and pseg.live[pd]:
                ph = {"_index": hit.get("_index", ""), "_id": pseg.ids[pd],
                      "_score": float(sc[pd])}
                if ih.get("_source", True) is not False:
                    ph["_source"] = pseg.sources[pd]
                parent_hits.append(ph)
        hit.setdefault("inner_hits", {})[name] = {
            "hits": {"total": {"value": len(parent_hits), "relation": "eq"},
                     "max_score": parent_hits[0]["_score"] if parent_hits else None,
                     "hits": parent_hits}}

    def _nested_inner_hits(self, nq: dsl.NestedQuery, seg: Segment,
                           docs: List[int], ctx) -> Optional[dict]:
        """The matching children of the parents `docs` of one segment for
        one nested query (reference InnerHitsContext / InnerHitsPhase):
        parent -> (first child row, [(score, child row)] best first, the
        earlier child first among equals). One launch: the child clause
        over the child space, gathered at the rows of the parents' blocks
        (`children_of`: a few rows a parent), and only those read back;
        None where the segment has no such block."""
        import jax

        blk = seg.nested.get(nq.path)
        if blk is None or blk.child.ndocs == 0:
            return None
        windows = [blk.children_of(d) for d in docs]
        nrows = sum(b - a for a, b in windows)
        found = {d: (a, []) for d, (a, _b) in zip(docs, windows)}
        if not nrows:
            return found
        rows = np.zeros(next_pow2(nrows, floor=64), np.int32)
        rows[:nrows] = np.concatenate(
            [np.arange(a, b, dtype=np.int32) for a, b in windows])
        child_ctx = PL.nested_context(ctx, nq.path)
        inner_l = PL.rewrite(nq.query, child_ctx, scoring=True)
        cparams: Dict[str, Any] = {}
        cspec = C.prepare(inner_l, blk.child, child_ctx, cparams)
        launched = C.run_gather_scores(
            cspec, blk.child.device_arrays(self.device), cparams, rows,
            scope="executor.nested_inner")
        with TRACER.span("device.wait", program="gather"):
            scores, matched = jax.device_get(launched)
        PN.NESTED_STATS.inc("inner_hits_requests")
        PN.NESTED_STATS.inc("inner_hits_child_rows", len(rows))
        PN.NESTED_STATS.inc("inner_hits_readback_bytes",
                            scores.nbytes + matched.nbytes)
        at = 0
        for d, (a, b) in zip(docs, windows):
            sc, ok = scores[at: at + b - a], matched[at: at + b - a] > 0
            at += b - a
            kept = np.flatnonzero(ok)
            kept = kept[np.argsort(-sc[kept], kind="stable")]
            found[d] = (a, [(float(sc[i]), a + int(i)) for i in kept])
        return found

    def _add_inner_hits(self, hit: dict, nq: dsl.NestedQuery, seg: Segment,
                        c: Candidate, found: Optional[dict]) -> None:
        """One hit's `inner_hits` entry from `_nested_inner_hits`."""
        if found is None:
            return
        blk = seg.nested[nq.path]
        ih = nq.inner_hits or {}
        name = ih.get("name", nq.path)
        a, kids = found[c.local_doc]
        frm = int(ih.get("from", 0))
        size = int(ih.get("size", 3))
        child_hits = []
        for sc, i in kids[frm: frm + size]:
            ch = {"_index": hit.get("_index", ""), "_id": hit["_id"],
                  "_nested": {"field": nq.path, "offset": i - a},
                  "_score": sc}
            if ih.get("_source", True) is not False:
                ch["_source"] = blk.child.sources[i]
            child_hits.append(ch)
        hit.setdefault("inner_hits", {})[name] = {
            "hits": {"total": {"value": len(kids), "relation": "eq"},
                     "max_score": kids[0][0] if kids else None,
                     "hits": child_hits}}

    def _fetch_one(self, seg: Segment, c: Candidate, body: dict,
                   hl_terms: Optional[dict] = None,
                   suppress_score: Optional[bool] = None) -> dict:
        # per-searcher index label (multi-index and cross-cluster searches
        # need the concrete "alias:index" name, not the joined expression)
        hit = {"_index": self.index_key or body.get("_index_name", ""),
               "_id": seg.ids[c.local_doc],
               "_score": c.score}
        if body.get("sort"):
            hit["sort"] = list(c.raw_sort_values)
            if suppress_score is None:
                suppress_score = _suppress_score(body)
            if suppress_score:
                hit["_score"] = None
        stored_opt = body.get("stored_fields")
        # reference semantics: asking for stored_fields suppresses _source
        # unless the request opts back in explicitly
        src_opt = body.get("_source",
                           True if stored_opt is None else False)
        if src_opt is not False:
            src = seg.sources[c.local_doc]
            hit["_source"] = _filter_source(src, src_opt)
        if stored_opt and stored_opt != "_none_":
            stored = (seg.stored_vals[c.local_doc]
                      if getattr(seg, "stored_vals", None) else None) or {}
            flds = hit.setdefault("fields", {})
            for f in (stored_opt if isinstance(stored_opt, list)
                      else [stored_opt]):
                if f in stored:
                    flds[f] = list(stored[f])
        if body.get("docvalue_fields"):
            # merge: stored_fields may already have populated hit["fields"]
            hit.setdefault("fields", {}).update(
                _docvalue_fields(seg, c.local_doc, body["docvalue_fields"]))
        if body.get("fields"):
            flds = hit.setdefault("fields", {})
            for f in body["fields"]:
                fname = f if isinstance(f, str) else f.get("field")
                vals = _extract_source_values(seg.sources[c.local_doc], fname)
                if vals:
                    flds[fname] = vals
        if body.get("script_fields"):
            from ..script import ScriptError, run_field_script
            from .query_dsl import parse_script_spec
            flds = hit.setdefault("fields", {})
            for fname, fspec in body["script_fields"].items():
                src_str, prm = parse_script_spec(fspec.get("script"))
                try:
                    v = run_field_script(src_str, prm, seg, c.local_doc,
                                         score=c.score)
                except ScriptError as e:
                    raise dsl.QueryParseError(f"[script_fields.{fname}]: {e}")
                flds[fname] = v if isinstance(v, list) else [v]
        if body.get("highlight") and hl_terms is not None:
            hl = {}
            hl_body = body["highlight"]
            for fname, fopts in hl_body.get("fields", {}).items():
                ft = self.engine.mappings.resolve_field(fname)
                if ft is None:
                    continue
                terms = hl_terms.get(fname, set())
                vals = _extract_source_values(seg.sources[c.local_doc], fname)
                frags = []
                analyzer = self.engine.mappings.index_analyzer(ft)
                hl_type = fopts.get("type", hl_body.get("type", "plain"))
                hl_kw = dict(
                    pre_tag=(hl_body.get("pre_tags") or ["<em>"])[0],
                    post_tag=(hl_body.get("post_tags") or ["</em>"])[0],
                    fragment_size=int(fopts.get(
                        "fragment_size", hl_body.get("fragment_size", 100))),
                    number_of_fragments=int(fopts.get(
                        "number_of_fragments",
                        hl_body.get("number_of_fragments", 5))))
                tv = (getattr(seg, "term_vectors", None) or {}).get(fname)
                entries = tv[c.local_doc] if tv else None
                if hl_type == "fvh" and entries:
                    # real FVH: persisted term-vector offsets, no
                    # re-analysis; entries are per value, offsets relative
                    # to that value (term_vector=with_positions_offsets)
                    for v, ventry in zip(vals, entries):
                        if ventry:
                            frags.extend(highlight_fvh(
                                str(v), terms, ventry, **hl_kw))
                else:
                    # fvh without stored vectors degrades to unified
                    # (offsets re-derived by re-analysis)
                    hl_fn = (highlight_unified
                             if hl_type in ("unified", "fvh")
                             else highlight_field)
                    for v in vals:
                        frags.extend(hl_fn(str(v), terms, analyzer, **hl_kw))
                if frags:
                    hl[fname] = frags
            if hl:
                hit["highlight"] = hl
        return hit


# =====================================================================
# coordinator reduce (SearchPhaseController analog)
# =====================================================================

def reduce_shard_results(shard_results: List[ShardQueryResult], body: dict,
                         agg_nodes: Optional[List[AggNode]] = None,
                         defer_pipelines: bool = False) -> dict:
    size = int(body.get("size", 10))
    frm = int(body.get("from", 0))
    all_cands: List[Candidate] = []
    total = 0
    total_rel = "eq"
    max_score = float("-inf")
    for r in shard_results:
        all_cands.extend(r.candidates)
        total += r.total
        if r.total_rel == "gte":
            total_rel = "gte"
        max_score = max(max_score, r.max_score)
    all_cands.sort(key=lambda c: c.sort_values)
    if body.get("collapse"):
        # keep only the best hit per group across shards (reference
        # SearchPhaseController + CollapseBuilder coordinator merge)
        seen = set()
        deduped = []
        for c in all_cands:
            gk = ("null",) if c.collapse_key is None else ("v", c.collapse_key)
            if gk in seen:
                continue
            seen.add(gk)
            deduped.append(c)
        all_cands = deduped
    selected = all_cands[frm: frm + size]

    if agg_nodes is None:
        agg_nodes = parse_aggs(body.get("aggs", body.get("aggregations")))
    aggs_out = {}
    for node in agg_nodes:
        partials = []
        for r in shard_results:
            partials.extend(r.agg_partials.get(node.name, []))
        merged = merge_partials(node, partials) if partials else {}
        aggs_out[node.name] = finalize(node, merged,
                                       pipelines=not defer_pipelines)

    return {"selected": selected, "total": total, "total_rel": total_rel,
            "max_score": None if max_score == float("-inf") else max_score,
            "aggs": aggs_out}


def search_shards(searchers: List[ShardSearcher], body: dict,
                  index_name: str = "", task=None, phase_hook=None,
                  phase_ctx: Optional[dict] = None) -> dict:
    """Full query-then-fetch across shards -> OpenSearch-shaped response.

    `phase_hook(shard_results, body, ctx)` is the search-pipeline
    phase-results slot (reference SearchPhaseResultsProcessor.java): it runs
    after the per-shard device query phase, before the coordinator reduce.
    """
    from . import fusion
    if fusion.is_hybrid_body(body):
        # hybrid retrieval (search/fusion.py): each sub-query runs as an
        # independent retrieval through THIS same entry (its own serving
        # ladder, its own cost accumulator feeding the shared insights
        # observation); the fused page is a pure function of the ranked
        # sub-pages
        hq = fusion.parse_hybrid(body)
        return fusion.run_hybrid(
            body,
            lambda sub: search_shards(searchers, sub, index_name,
                                      task=task),
            q=hq)
    t0 = time.monotonic()
    body = dict(body)
    body["_index_name"] = index_name
    stats = _global_stats_contexts(searchers)
    from ..utils.metrics import METRICS
    if body.get("profile"):
        # jit-attribution baseline: the profile response reports the
        # DELTA this request caused (compiles triggered, cache traffic)
        body["_jit_before"] = C.jit_attribution()
    # per-query device cost accounting (obs/query_cost.py): one
    # accumulator spans the whole shard loop + fastpath ladder; plan-time
    # predictions and launched-shape actuals reconcile in the profile
    # `cost` block and the cost.* histograms at finish
    qc_token = None
    qc_acc = None
    if _qcost.enabled() and _qcost.current() is None:
        qc_acc, qc_token = _qcost.start(
            detail=body.get("explain") == "device_plan")
    # request deadline: REST/distnode installs the ambient budget at
    # accept time (queue wait counts); direct engine callers get one
    # derived from the body's `timeout` here
    dl_token = None
    if _dl.current() is None:
        try:
            _deadline = _dl.Deadline.from_body(body)
        except ValueError as e:
            raise dsl.QueryParseError(str(e))
        if _deadline is not None:
            dl_token = _dl.set_current(_deadline)
    try:
        results = []
        for i, s in enumerate(searchers):
            with TRACER.span("query_phase", shard=i), \
                    METRICS.timer("search.query_phase"):
                results.append(s.query_phase(body, shard_ord=i,
                                             stats_ctx=stats[i], task=task))
        if phase_hook is not None:
            phase_hook(results, body,
                       phase_ctx if phase_ctx is not None else {})
        agg_nodes = parse_aggs(body.get("aggs", body.get("aggregations")))
        # pipelines whose buckets_path targets a refinement-resolved
        # sub-agg are deferred until after _refine_complex_subs; the rest
        # run in finalize so bucket_selector/bucket_sort still prune
        # BEFORE per-bucket refinement
        for an in agg_nodes:
            _mark_deferred_pipelines(an)
        return _finish_search(searchers, results, body, stats, index_name,
                              t0, agg_nodes)
    finally:
        if dl_token is not None:
            _dl.reset_current(dl_token)
        if qc_token is not None:
            if qc_acc is not None and qc_acc.actual_bytes:
                # feed the measured bytes-moved into the request's
                # query-insights observation (obs/insights.py) — the
                # per-SHAPE bytes attribution `top_queries?by=bytes`
                # ranks on. Same-thread contextvar, so coalesced
                # scheduler batches (other threads) stay unattributed
                # exactly like query_cost itself documents.
                from ..obs import insights as _ins
                _ins.note_bytes(qc_acc.actual_bytes)
            _qcost.finish(qc_token)


def msearch_batched(searchers: List[ShardSearcher],
                    bodies: List[dict], index_name: str = ""
                    ) -> Optional[List[dict]]:
    """Synchronous batched msearch on the Pallas fast path: launch +
    fetch back-to-back (see `launch_msearch_batched` for the split)."""
    handle = launch_msearch_batched(searchers, bodies, index_name)
    if handle is None:
        return None
    return handle.fetch()


def launch_msearch_batched(searchers: List[ShardSearcher],
                           bodies: List[dict], index_name: str = ""):
    """Batched msearch on the Pallas fast path: eligible bodies' queries
    over each segment run as ONE kernel launch per shape group (grid over
    queries) — server-side query batching, the production shape of a TPU
    search tier (reference analog: `action/search/TransportMultiSearchAction`
    just loops; we fuse).

    LAUNCH stage: parsing, spec building, and EVERY shard/segment's
    frontier kernel enqueue run here, unfetched — all segments' launches
    pipeline on the device before the first sync. The returned handle's
    `fetch()` syncs each segment batch, collects top-ks, and finishes the
    responses: a per-body list whose entries are response dicts for
    bodies the fast path served and None for the rest (the caller runs
    those through the regular per-body search). Returns None wholesale
    when the fast path is off."""
    from .launch import LaunchHandle

    if not searchers:
        return None
    fp_on = fastpath.enabled()
    if not fp_on and not any(_maybe_knn_body(b) for b in bodies):
        # the Pallas kernels are TPU-only, but the batched pure-knn
        # route is plain XLA (vmapped executor twin) and engages on
        # every backend — only bail wholesale when NEITHER route can
        # serve anything
        return None
    stats = _global_stats_contexts(searchers)
    nb = len(bodies)
    parsed: List[Optional[tuple]] = []
    with TRACER.span("search.plan", bodies=nb):
        for body in bodies:
            body = dict(body)
            body["_index_name"] = index_name
            if (body.get("aggs") or body.get("aggregations") or body.get("rescore")
                    or body.get("search_after") is not None or body.get("min_score")
                    is not None or body.get("profile")
                    or body.get("explain") == "device_plan"):
                parsed.append(None)
                continue
            try:
                query = compose_knn_query(body)
            except (dsl.QueryParseError, KeyError, TypeError, ValueError):
                parsed.append(None)     # slow path surfaces the error per body
                continue
            parsed.append((body, query, _norm_sort_specs(body),
                           int(body.get("from", 0)) + int(body.get("size", 10))))

    t0 = time.monotonic()
    ok = [p is not None for p in parsed]
    results = [[ShardQueryResult(shard=i, segments=list(s.engine.segments))
                for i, s in enumerate(searchers)] for _ in range(nb)]
    # (shard idx, searcher, ctx, seg, seg_ord, launch-time live set,
    #  fspecs, handle-or-None); a body invalidated by an EARLIER segment's
    # fetch may still ride a later launch — per-query results are
    # batch-composition invariant, so its entries are simply discarded
    launches: List[tuple] = []
    knn_launches: List[tuple] = []
    for i, s in enumerate(searchers):
        if not any(ok):
            break
        ctx = stats[i]
        segments = list(s.engine.segments)
        fspecs: List[Optional[Any]] = [None] * nb
        kroots: List[Optional[Any]] = [None] * nb
        with TRACER.span("search.plan", shard=i):
            for bi, p in enumerate(parsed):
                if not ok[bi]:
                    continue
                body, query, sort_specs, window = p
                try:
                    lroot = PL.rewrite(query, ctx, scoring=True)
                except dsl.QueryParseError:
                    ok[bi] = False
                    continue
                if _collect_named(lroot):
                    ok[bi] = False
                    continue
                fspecs[bi] = (fastpath.make_spec(lroot, sort_specs, [], [],
                                                 None, window, body)
                              if fp_on else None)
                if fspecs[bi] is None:
                    # pure-knn route: a lone LKnn root (query.knn, or the
                    # ES-style top-level knn section with no query) batches
                    # through the vmapped twin of the SAME general program
                    # the direct path runs — first-class vector serving
                    # (ISSUE 15), byte-identical per query by construction
                    if isinstance(lroot, PL.LKnn) \
                            and _knn_batch_body_ok(sort_specs, body, window):
                        kroots[bi] = lroot
                    else:
                        if isinstance(lroot, PL.LKnn):
                            from ..search import fusion as _fusion
                            _fusion.STATS.inc("knn_batch_declined")
                        ok[bi] = False
        live_bis = [bi for bi in range(nb)
                    if ok[bi] and fspecs[bi] is not None]
        knn_bis = [bi for bi in range(nb) if ok[bi] and kroots[bi] is not None]
        if not live_bis and not knn_bis:
            continue
        for seg_ord, seg in enumerate(segments):
            if seg.live_count == 0:
                continue
            if live_bis:
                handle = fastpath.launch_batch(
                    seg, ctx, [fspecs[bi] for bi in live_bis],
                    max((parsed[bi][3] for bi in live_bis), default=10),
                    count_stats=False)
                if handle is None:
                    # wholesale decline, known AT LAUNCH (segment can't
                    # take the fast path at all): fail these bodies now
                    # so later shards don't enqueue kernels for work that
                    # would only be discarded at fetch (same outcome as
                    # the synchronous path's `outs is None` break, same
                    # launch count too)
                    for bi in live_bis:
                        ok[bi] = False
                    live_bis = []
                else:
                    launches.append((i, s, ctx, seg, seg_ord,
                                     list(live_bis), fspecs, handle))
            if knn_bis:
                got = _launch_knn_segment(s, ctx, seg, seg_ord, i,
                                          [(bi, kroots[bi],
                                            parsed[bi][2], parsed[bi][3])
                                           for bi in knn_bis])
                if got is None:
                    # tie-aware segment (BP reorder widen loop) or
                    # can-prepare failure: parity demands the direct
                    # path's per-segment machinery — decline these
                    # bodies wholesale
                    from ..search import fusion as _fusion
                    _fusion.STATS.inc("knn_batch_declined", len(knn_bis))
                    for bi in knn_bis:
                        ok[bi] = False
                    knn_bis = []
                else:
                    knn_launches.extend(got)

    def _finish():
        served_batches: List[tuple] = []
        for (i, s, ctx, seg, seg_ord, bis, fetch_fn) in knn_launches:
            live = [bi for bi in bis if ok[bi]]
            if not live:
                continue
            outs = fetch_fn()
            by_bi = dict(zip(bis, outs))
            for bi in live:
                _b, _q, k_sort_specs, _w = parsed[bi]
                s._collect_topk(results[bi][i], by_bi[bi], seg, seg_ord,
                                i, k_sort_specs, None, None, False, ctx)
        for (i, s, ctx, seg, seg_ord, seg_live, fspecs,
             handle) in launches:
            live = [bi for bi in seg_live if ok[bi]]
            if not live:
                continue
            # stats counted only for bodies served on every shard/segment
            # — a later fallback discards that body's results, re-runs slow
            outs = handle.fetch()
            by_bi = dict(zip(seg_live, outs))
            for bi in live:
                o = by_bi[bi]
                if o is not None:
                    served_batches.append((bi, fspecs[bi], o))
            for bi in live:
                fout = by_bi[bi]
                if fout is None:
                    ok[bi] = False
                    continue
                body, _, sort_specs, window = parsed[bi]
                s._collect_topk(results[bi][i], fout, seg, seg_ord, i,
                                sort_specs, None, None, False, ctx)
        for i in range(len(searchers)):
            for bi in range(nb):
                if not ok[bi]:
                    continue
                body, _, sort_specs, window = parsed[bi]
                r = results[bi][i]
                r.candidates.sort(key=lambda c: c.sort_values)
                r.candidates = r.candidates[:window]
                r.took_ms = (time.monotonic() - t0) * 1000.0
        if not any(ok):
            return [None] * nb
        for bi, fs, o in served_batches:
            if ok[bi]:
                fastpath.count_served([fs], [o])
        return [_finish_search(searchers, results[bi], parsed[bi][0],
                               stats, index_name, t0, [])
                if ok[bi] else None for bi in range(nb)]

    info = None
    if _flight.RECORDER.enabled:
        # launch forensics for the scheduler's per-request journal
        # (mirrors MeshSearchService.launch_msearch's handle.info)
        info = {"path": "kernel", "bodies": int(sum(ok)),
                "kernel_launches": len(launches),
                "knn_batch_launches": len(knn_launches)}
    return LaunchHandle(_finish, kind="fastpath", info=info)


def _maybe_knn_body(body) -> bool:
    """Cheap screen: could this body take the batched pure-knn route?"""
    if not isinstance(body, dict):
        return False
    if isinstance(body.get("knn"), dict):
        return True
    q = body.get("query")
    return isinstance(q, dict) and "knn" in q


def _knn_batch_body_ok(sort_specs, body: dict, window: int) -> bool:
    """Body checks for the batched pure-knn route — the shape class the
    direct general path serves with oversample 1 and no per-segment
    budget stops (terminate_after / a live timeout need the
    deadline-aware host loop; a non-score sort needs host re-sorting)."""
    if window < 1 or window > 1024:
        return False
    if sort_specs and not (len(sort_specs) == 1
                           and sort_specs[0]["field"] == "_score"
                           and sort_specs[0].get("order", "desc")
                           == "desc"):
        return False
    if body.get("collapse") or body.get("suggest") \
            or body.get("terminate_after"):
        return False
    if body.get("timeout") is not None:
        from ..utils.deadline import parse_timeout_s
        try:
            if parse_timeout_s(body["timeout"]) is not None:
                return False
        except ValueError:
            return False
    return True


def _launch_knn_segment(s: ShardSearcher, ctx, seg: Segment, seg_ord: int,
                        shard_i: int, items: List[tuple]
                        ) -> Optional[List[tuple]]:
    """LAUNCH the coalesced pure-knn batch for one segment: prepare
    each body exactly like the direct general path (same k_pad, same
    spec/params via canon_query — structurally identical bodies share
    one compiled program), enqueue every per-query invocation of the
    DIRECT-path executor unfetched, and defer the device sync to one
    fetch sweep (programs.launch_segment_batch — deliberately not a
    vmapped mega-program; see its docstring for the byte-parity
    rationale). Returns [(shard_i, s, ctx, seg, seg_ord, [bi...],
    fetch_fn)] or None to decline the whole segment (BP-reordered
    tie-aware segments need the direct path's widen loop)."""
    from ..search import fusion as _fusion

    tief = getattr(seg, "tie_ranks", None)
    if tief is not None and tief() is not None:
        return None
    prepared: List[tuple] = []
    bis: List[int] = []
    for bi, lroot, sort_specs, window in items:
        k_pad = min(next_pow2(max(window, 16)), seg.ndocs_pad)
        params: Dict[str, Any] = {}
        try:
            qspec = C.prepare(lroot, seg, ctx, params)
            sspec = C.prepare_sort(sort_specs, seg, params)
        except dsl.QueryParseError:
            return None
        full, cparams = PG.canon_query(qspec, sspec, k_pad, params)
        prepared.append((full, cparams))
        bis.append(bi)
    fetch_fn = PG.launch_segment_batch(prepared, seg.device_arrays(s.device))
    _fusion.STATS.inc("knn_batch_launches")
    _fusion.STATS.inc("knn_batched", len(prepared))
    return [(shard_i, s, ctx, seg, seg_ord, bis, fetch_fn)]


def _finish_search(searchers: List[ShardSearcher],
                   results: List[ShardQueryResult], body: dict, stats,
                   index_name: str, t0: float,
                   agg_nodes: List[AggNode]) -> dict:
    """Coordinator reduce + fetch + response assembly (the tail of
    query-then-fetch, shared by search and batched msearch)."""
    from ..utils.metrics import METRICS
    with TRACER.span("reduce"), METRICS.timer("search.reduce"):
        reduced = reduce_shard_results(results, body, agg_nodes=agg_nodes,
                                       defer_pipelines=bool(agg_nodes))
    by_shard: Dict[int, List[Candidate]] = {}
    for c in reduced["selected"]:
        by_shard.setdefault(c.shard, []).append(c)
    hits_by_key: Dict[Tuple, dict] = {}
    with TRACER.span("fetch_phase", hits=len(reduced["selected"])), \
            METRICS.timer("search.fetch_phase"):
        for i, r in enumerate(results):
            sel = by_shard.get(r.shard, [])
            if not sel:
                continue
            fetched = searchers[i].fetch_phase(r, sel, body,
                                               stats_ctx=stats[i])
            for c, h in zip(sel, fetched):
                hits_by_key[(c.shard, c.seg_ord, c.local_doc)] = h
    with TRACER.span("search.respond"):
        hits = [hits_by_key[(c.shard, c.seg_ord, c.local_doc)] for c in reduced["selected"]
                if (c.shard, c.seg_ord, c.local_doc) in hits_by_key]

        collapse = body.get("collapse")
        if collapse:
            _apply_collapse_inner_hits(searchers, body, index_name, collapse,
                                       reduced["selected"], hits_by_key)

        if reduced["aggs"]:
            # bucket refinement: ordinal bucket aggs execute complex sub-trees
            # (terms>terms, bucket top_hits, cardinality-under-terms, ...) as one
            # recursive sub-search per top bucket — the device pass only fuses
            # the stats-family metrics into the ordinal bincount
            for an in agg_nodes:
                _refine_complex_subs(searchers, body, index_name, an,
                                     reduced["aggs"].get(an.name),
                                     body.get("query"), [])
            for an in agg_nodes:
                _apply_deferred_tree(an, reduced["aggs"].get(an.name))

        track = body.get("track_total_hits", True)
        relation = reduced.get("total_rel", "eq")
        total = reduced["total"]
        if track is not True and track is not False:
            track_n = int(track)
            if total > track_n:
                total, relation = track_n, "gte"
        took_ms = (time.monotonic() - t0) * 1000.0
        METRICS.histogram("search.total").record(took_ms)
        timed_out = any(r.timed_out for r in results)
        terminated_early = any(r.terminated_early for r in results)
        if body.get("allow_partial_search_results", True) is False \
                and timed_out:
            # reference parity: partial pages refused -> whole-request error
            # (the REST facade maps this to a 503
            # search_phase_execution_exception)
            raise _dl.PartialResultsUnacceptable(
                "request timed out with allow_partial_search_results=false")
        # track_scores (reference): a field-sorted request normally reports
        # max_score null; track_scores=true opts the rollup back in (the
        # engine computes scores regardless — they are free on device)
        show_max = not body.get("sort") or bool(body.get("track_scores"))
        resp = {
            "took": int(took_ms),
            "timed_out": timed_out,
            "_shards": {"total": len(searchers), "successful": len(searchers),
                        "skipped": 0, "failed": 0},
            "hits": {"total": {"value": total, "relation": relation},
                     "max_score": reduced["max_score"] if show_max else None,
                     "hits": hits},
        }
        if terminated_early:
            resp["terminated_early"] = True
        if reduced["aggs"]:
            resp["aggregations"] = reduced["aggs"]
        if body.get("suggest"):
            from .suggest import run_suggest
            segs = [g for s in searchers for g in s.engine.segments
                    if g.live_count > 0]
            mappings = searchers[0].engine.mappings if searchers else None
            resp["suggest"] = run_suggest(body["suggest"], segs, mappings)
        if body.get("profile"):
            # per-plan-node breakdown (reference search/profile/): the plan tree
            # with type/description per node. One honesty note a TPU engine owes
            # its users: XLA fuses the whole plan into one program, so per-node
            # device times are not separable — node entries carry the tree and
            # the root carries the measured phase time (children fused=true).
            try:
                plan_tree = C.describe_plan(
                    PL.rewrite(dsl.parse_query(body.get("query")),
                               stats[0], scoring=True)) if stats else None
            except Exception:
                plan_tree = None
            # device attribution: what this request cost the jit layer (cache
            # traffic + compiles triggered, the DELTA vs the pre-request
            # baseline search_shards stashed) and which phase-2 rescore path
            # is active — the per-plan-node "why was this slow" the reference
            # gets from search/profile/
            from .fastpath import rescore_mode
            device_attr = {"rescore_path": rescore_mode(),
                           "jit": _jit_delta(body.pop("_jit_before", None),
                                             C.jit_attribution())}
            shards_profile = []
            for r in results:
                entry: dict = {"id": f"[shard][{r.shard}]",
                               "query_ms": r.took_ms,
                               "device": device_attr,
                               "searches": [{"query": [], "rewrite_time": 0,
                                             "collector": [{
                                                 "name": "SimpleTopKCollector",
                                                 "reason": "search_top_hits",
                                                 "time_in_nanos": int(
                                                     r.took_ms * 1e6)}]}]}
                if plan_tree is not None:
                    root = dict(plan_tree)
                    root["time_in_nanos"] = int(r.took_ms * 1e6)
                    root["device"] = device_attr
                    entry["searches"][0]["query"] = [root]
                shards_profile.append(entry)
            resp["profile"] = {"shards": shards_profile}
            qc = _qcost.current()
            if qc is not None:
                # per-query device cost: plan-time prediction (CSR stats)
                # reconciled against the launched program shapes — the byte
                # domain the north star's ≥20× claim is argued in
                resp["profile"]["cost"] = qc.snapshot()
        if body.get("explain") == "device_plan":
            # device-plan search view: the cost rollup + per-segment
            # predicted/actual entries, without per-hit _explanation trees
            qc = _qcost.current()
            if qc is not None:
                resp["device_plan"] = {"cost": qc.snapshot(),
                                       "segments": list(qc.segments)}
        return resp


# =====================================================================
# helpers
# =====================================================================

def _jit_delta(before, after):
    """Recursive numeric diff of two `compiler.jit_attribution()`
    snapshots: count/total fields become this-request deltas, percentile
    fields (registry-lifetime, not diffable) pass through from `after`."""
    if not isinstance(before, dict) or not isinstance(after, dict):
        return after
    out = {}
    for k, v in after.items():
        if isinstance(v, dict):
            out[k] = _jit_delta(before.get(k), v)
        elif isinstance(v, (int, float)) and not k.startswith("p") \
                and isinstance(before.get(k), (int, float)):
            d = v - before[k]
            out[k] = round(d, 3) if isinstance(d, float) else d
        else:
            out[k] = v
    return out


_STATS_FAMILY = {"min", "max", "sum", "avg", "stats", "extended_stats",
                 "value_count"}
_ORDINAL_KINDS = {"terms", "significant_terms", "histogram", "date_histogram",
                  "geohash_grid", "geotile_grid", "composite", "rare_terms",
                  "multi_terms", "auto_date_histogram", "significant_text"}
_WALK_CONTAINERS = {"filter", "filters", "range", "date_range", "global",
                    "missing"}


def _apply_collapse_inner_hits(searchers, body, index_name, collapse,
                               selected, hits_by_key) -> None:
    """Stamp the collapse field value into each hit and resolve inner_hits
    groups via per-group sub-searches (reference ExpandSearchPhase)."""
    field = collapse["field"]
    ih_specs = collapse.get("inner_hits") or []
    if isinstance(ih_specs, dict):
        ih_specs = [ih_specs]
    for c in selected:
        h = hits_by_key.get((c.shard, c.seg_ord, c.local_doc))
        if h is None:
            continue
        h.setdefault("fields", {})[field] = [c.collapse_key]
        for ih in ih_specs:
            name = ih.get("name", field)
            if c.collapse_key is None:
                gfilter = {"bool": {"must_not": [{"exists": {"field": field}}]}}
            else:
                gfilter = {"term": {field: c.collapse_key}}
            sub = {
                "query": {"bool": {
                    "must": [body.get("query") or {"match_all": {}}],
                    "filter": [gfilter]}},
                "size": int(ih.get("size", 3)),
                "from": int(ih.get("from", 0)),
            }
            if ih.get("sort"):
                sub["sort"] = ih["sort"]
            sub_resp = search_shards(searchers, sub, index_name=index_name)
            h.setdefault("inner_hits", {})[name] = {"hits": sub_resp["hits"]}


def _pipeline_input_names(p: AggNode) -> set:
    """First path components of every buckets_path (and bucket_sort sort
    fields) a pipeline node reads."""
    raw = p.body.get("buckets_path", "_count")
    paths = list(raw.values()) if isinstance(raw, dict) else [raw]
    if p.kind == "bucket_sort":
        for s in p.body.get("sort", []):
            if isinstance(s, dict):
                paths.extend(s.keys())
            elif isinstance(s, str):
                paths.append(s)
    return {str(pth).replace(">", ".").split(".")[0] for pth in paths if pth}


def _mark_deferred_pipelines(node: AggNode) -> None:
    """Flag pipelines whose inputs come from refinement-resolved sub-aggs
    (complex subs of ordinal buckets) — transitively through pipelines that
    read other deferred pipelines' outputs."""
    deferred_names = ({s.name for s in node.subs if s.kind not in _STATS_FAMILY}
                      if node.kind in _ORDINAL_KINDS else set())
    for p in node.pipelines:
        p.deferred = False
    changed = True
    while changed:
        changed = False
        for p in node.pipelines:
            if not p.deferred and (_pipeline_input_names(p) & deferred_names):
                p.deferred = True
                deferred_names.add(p.name)
                changed = True
    for s in node.subs:
        _mark_deferred_pipelines(s)


def _apply_deferred_tree(node: AggNode, result) -> None:
    """Apply deferred pipelines after refinement, mirroring the
    _refine_complex_subs walk: complex subs of reached ordinal nodes were
    REPLACED by fully-pipelined refinement sub-search results — don't descend
    into them (double application); subtrees the walk never reached get the
    plain post-order pass."""
    if not isinstance(result, dict):
        return
    if node.kind in _ORDINAL_KINDS:
        _apply_bucket_pipelines(node, result, "deferred")
        return
    if node.kind in _WALK_CONTAINERS:
        buckets = result.get("buckets")
        if isinstance(buckets, list):
            for b in buckets:
                for s in node.subs:
                    _apply_deferred_tree(s, b.get(s.name))
        elif isinstance(buckets, dict):
            for bd in buckets.values():
                for s in node.subs:
                    _apply_deferred_tree(s, bd.get(s.name))
        else:
            for s in node.subs:
                _apply_deferred_tree(s, result.get(s.name))
        _apply_bucket_pipelines(node, result, "deferred")
        return
    apply_pipelines_tree(node, result)


def _agg_to_dsl(node: AggNode) -> dict:
    spec: dict = {node.kind: node.body}
    subs = {s.name: _agg_to_dsl(s) for s in node.subs}
    subs.update({p.name: _agg_to_dsl(p) for p in node.pipelines})
    if subs:
        spec["aggs"] = subs
    return spec


def _next_calendar_ms(ms: int, cal: str) -> int:
    import datetime as dt

    d = dt.datetime.fromtimestamp(ms / 1000.0, dt.timezone.utc)
    if cal in ("month", "1M"):
        y, m = (d.year + 1, 1) if d.month == 12 else (d.year, d.month + 1)
        return int(dt.datetime(y, m, 1, tzinfo=dt.timezone.utc).timestamp() * 1000)
    if cal in ("year", "1y"):
        return int(dt.datetime(d.year + 1, 1, 1,
                               tzinfo=dt.timezone.utc).timestamp() * 1000)
    if cal in ("quarter", "1q"):
        m = ((d.month - 1) // 3) * 3 + 4
        y = d.year + (1 if m > 12 else 0)
        m = 1 if m > 12 else m
        return int(dt.datetime(y, m, 1, tzinfo=dt.timezone.utc).timestamp() * 1000)
    step = {"week": 7 * 86400000, "1w": 7 * 86400000, "day": 86400000,
            "1d": 86400000, "hour": 3600000, "1h": 3600000,
            "minute": 60000, "1m": 60000}[cal]
    return ms + step


def _geohash_bbox(cell: str):
    lat_lo, lat_hi = -90.0, 90.0
    lon_lo, lon_hi = -180.0, 180.0
    is_lon = True
    for ch in cell:
        bits = "0123456789bcdefghjkmnpqrstuvwxyz".index(ch)
        for b in (16, 8, 4, 2, 1):
            if is_lon:
                mid = (lon_lo + lon_hi) / 2
                if bits & b:
                    lon_lo = mid
                else:
                    lon_hi = mid
            else:
                mid = (lat_lo + lat_hi) / 2
                if bits & b:
                    lat_lo = mid
                else:
                    lat_hi = mid
            is_lon = not is_lon
    return lat_lo, lat_hi, lon_lo, lon_hi


def _geotile_bbox(cell: str):
    import math as _m

    z, x, y = (int(p) for p in cell.split("/"))
    n = 1 << z
    lon_lo = x / n * 360.0 - 180.0
    lon_hi = (x + 1) / n * 360.0 - 180.0

    def lat_of(yy):
        return _m.degrees(_m.atan(_m.sinh(_m.pi * (1 - 2 * yy / n))))

    return lat_of(y + 1), lat_of(y), lon_lo, lon_hi


def _bucket_filter(node: AggNode, bucket: dict) -> Optional[dict]:
    """DSL filter selecting exactly the docs of one finalized bucket."""
    body = node.body
    field = body.get("field")
    kind = node.kind
    if kind in ("terms", "significant_terms", "rare_terms",
                "significant_text"):
        # significant_text keys are analyzed tokens of a text field: a term
        # query on the same field matches exactly the docs carrying the token
        return {"term": {field: bucket["key"]}}
    if kind == "multi_terms":
        flt = [{"term": {src["field"]: v}}
               for src, v in zip(body.get("terms", []), bucket["key"])]
        return {"bool": {"filter": flt}}
    if kind == "auto_date_histogram":
        key = int(bucket["key"])
        # the chosen interval is in the finalized result, threaded onto the
        # bucket by _refine via the parent result's "interval"
        return {"range": {field: {"gte": key, "lt": PN.auto_bucket_end_ms(
            key, bucket.get("_interval", "1s"))}}}
    if kind == "histogram":
        interval = float(body["interval"])
        return {"range": {field: {"gte": bucket["key"],
                                  "lt": bucket["key"] + interval}}}
    if kind == "date_histogram":
        key = int(bucket["key"])
        cal = body.get("calendar_interval")
        if cal:
            end = _next_calendar_ms(key, cal)
        else:
            end = key + PN.parse_interval_ms(body.get("fixed_interval",
                                                      body.get("interval", "1d")))
        return {"range": {field: {"gte": key, "lt": end}}}
    if kind in ("geohash_grid", "geotile_grid"):
        lat_lo, lat_hi, lon_lo, lon_hi = (
            _geohash_bbox(bucket["key"]) if kind == "geohash_grid"
            else _geotile_bbox(bucket["key"]))
        return {"geo_bounding_box": {field: {
            "top": lat_hi, "left": lon_lo, "bottom": lat_lo, "right": lon_hi}}}
    if kind == "composite":
        from .aggregations import composite_sources

        flt = []
        for nm, stype, scfg, _ in composite_sources(node):
            v = bucket["key"][nm]
            f = scfg.get("field")
            if stype == "terms":
                flt.append({"term": {f: v}})
            elif stype == "histogram":
                flt.append({"range": {f: {"gte": v,
                                          "lt": v + float(scfg["interval"])}}})
            else:
                cal = scfg.get("calendar_interval")
                end = (_next_calendar_ms(int(v), cal) if cal else
                       int(v) + PN.parse_interval_ms(scfg.get(
                           "fixed_interval", scfg.get("interval", "1d"))))
                flt.append({"range": {f: {"gte": int(v), "lt": end}}})
        return {"bool": {"filter": flt}} if len(flt) != 1 else flt[0]
    return None


def _refine_complex_subs(searchers: List[ShardSearcher], body: dict,
                         index_name: str, node: AggNode, result: Optional[dict],
                         query: Optional[dict], filters: List[dict]) -> None:
    """Recursive bucket refinement (see search_shards). Descends through
    filter-expressible containers accumulating context filters; for each
    ordinal bucket with complex subs, runs one size-0 sub-search whose own
    aggs recurse naturally. Doc-space-changing aggs (nested, children,
    sampler) stop the walk — their device recursion covers the stats family."""
    if result is None:
        return
    kind = node.kind
    if kind in _ORDINAL_KINDS:
        complex_subs = [s for s in node.subs if s.kind not in _STATS_FAMILY]
        buckets = result.get("buckets")
        if not isinstance(buckets, list) or not complex_subs:
            return
        if kind == "auto_date_histogram":
            # thread the coordinator-chosen interval to the bucket filters
            for b in buckets:
                b["_interval"] = result.get("interval", "1s")
        for b in buckets:
            bf = _bucket_filter(node, b)
            if bf is None:
                continue
            sub_body = {"size": 0, "_index_name": index_name,
                        "query": {"bool": {"must": ([query] if query else []),
                                           "filter": filters + [bf]}},
                        "aggs": {s.name: _agg_to_dsl(s) for s in complex_subs}}
            resp = search_shards(searchers, sub_body, index_name)
            for s in complex_subs:
                b[s.name] = resp["aggregations"][s.name]
        for b in buckets:
            b.pop("_interval", None)
        return
    if kind == "filter":
        for s in node.subs:
            _refine_complex_subs(searchers, body, index_name, s,
                                 result.get(s.name), query,
                                 filters + [node.body])
        return
    if kind == "filters":
        fmap = dict(AC.filters_agg_items(node.body))
        for key, bucket in (result.get("buckets") or {}).items():
            bf = fmap.get(key)
            if bf is None:
                continue
            for s in node.subs:
                _refine_complex_subs(searchers, body, index_name, s,
                                     bucket.get(s.name), query, filters + [bf])
        return
    if kind in ("range", "date_range"):
        field = node.body.get("field")
        for bucket in (result.get("buckets") or []):
            rng = {}
            if bucket.get("from") is not None:
                rng["gte"] = bucket["from"]
            if bucket.get("to") is not None:
                rng["lt"] = bucket["to"]
            for s in node.subs:
                _refine_complex_subs(searchers, body, index_name, s,
                                     bucket.get(s.name), query,
                                     filters + [{"range": {field: rng}}])
        return
    if kind == "geo_distance":
        field = node.body.get("field")
        origin = node.body.get("origin")
        unit = node.body.get("unit", "m")
        for bucket in (result.get("buckets") or []):
            # match the device bucket semantics [from, to): strict < on
            # the upper edge, NOT(dist < from) = dist >= from on the lower
            flt: List[dict] = []
            if bucket.get("to") is not None:
                flt.append({"geo_distance": {
                    "distance": f"{bucket['to']}{unit}", field: origin,
                    "_inclusive": False}})
            if bucket.get("from") is not None:
                flt.append({"bool": {"must_not": [{"geo_distance": {
                    "distance": f"{bucket['from']}{unit}",
                    field: origin, "_inclusive": False}}]}})
            for s in node.subs:
                _refine_complex_subs(searchers, body, index_name, s,
                                     bucket.get(s.name), query,
                                     filters + flt)
        return
    if kind == "global":
        for s in node.subs:
            _refine_complex_subs(searchers, body, index_name, s,
                                 result.get(s.name), None, [])
        return
    if kind == "missing":
        mf = {"bool": {"must_not": [{"exists": {"field": node.body.get("field")}}]}}
        for s in node.subs:
            _refine_complex_subs(searchers, body, index_name, s,
                                 result.get(s.name), query, filters + [mf])
        return


def _global_stats_contexts(searchers: List[ShardSearcher]) -> List[Any]:
    """DFS phase: collection statistics span ALL segments of the searcher's
    index_key group, so idf/avgdl are collection-wide — but each searcher
    keeps its OWN mappings/similarity for rewrite (heterogeneous standalone
    searchers must not resolve fields against another index's mappings).
    Returns one stats context per searcher, aligned by position."""
    group_segs: Dict[Any, List] = {}
    for s in searchers:
        group_segs.setdefault(s.index_key, []).extend(
            getattr(s, "_snapshot_segments", None) or s.engine.segments)
    return [PL.ShardContext(s.engine.mappings, group_segs[s.index_key],
                            s.similarity, s.field_similarities,
                            device=s.device)
            for s in searchers]


def _combine_rescore(mode: str, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    if mode == "total":
        return a + b
    if mode == "multiply":
        return a * b
    if mode == "avg":
        return (a + b) / 2
    if mode == "max":
        return np.maximum(a, b)
    if mode == "min":
        return np.minimum(a, b)
    raise ValueError(f"unknown rescore score_mode [{mode}]")


def _aggs_need_all_segments(agg_nodes) -> bool:
    """True if any agg in the tree observes docs outside the query match set
    (reference: global/filter/filters/missing aggregators; significant_terms
    needs every segment's background counts)."""
    for n in agg_nodes:
        if n.kind in ("global", "filter", "filters", "missing",
                      "significant_terms", "children", "parent"):
            return True
        if _aggs_need_all_segments(n.subs):
            return True
    return False


def _nested_queries_with_inner_hits(q) -> List[dsl.NestedQuery]:
    return [n for n in _walk_query_nodes(q, dsl.NestedQuery)
            if n.inner_hits is not None]


def _walk_query_nodes(q, types) -> List:
    out: List = []

    def walk(node):
        if not hasattr(node, "__dataclass_fields__"):
            return
        if isinstance(node, types):
            out.append(node)
        for fname in node.__dataclass_fields__:
            v = getattr(node, fname)
            if isinstance(v, dsl.Query):
                walk(v)
            elif isinstance(v, list):
                for x in v:
                    if isinstance(x, dsl.Query):
                        walk(x)
    walk(q)
    return out


def _join_queries_with_inner_hits(q) -> List:
    return [n for n in _walk_query_nodes(q, (dsl.HasChildQuery, dsl.HasParentQuery))
            if n.inner_hits is not None]


def _collect_named(lroot) -> List[Tuple[str, Any]]:
    out = []

    def walk(n):
        if n is None:
            return
        if getattr(n, "name", None):
            out.append((n.name, n))
        for attr in ("musts", "shoulds", "must_nots", "filters", "children"):
            for c in getattr(n, attr, []) or []:
                walk(c)
        for attr in ("child", "positive", "negative"):
            walk(getattr(n, attr, None))

    walk(lroot)
    return out


def _collapse_key_value(seg: Segment, field: str, doc: int):
    """Host group-key for one doc (keyword string or numeric value)."""
    kcol = seg.keyword_cols.get(field)
    if kcol is not None:
        o = int(kcol.min_ord[doc])
        return kcol.vocab[o] if o >= 0 else None
    ncol = seg.numeric_cols.get(field)
    if ncol is not None and ncol.present[doc]:
        return _render_numeric(ncol, doc)
    return None


def _host_sort_values(sort_specs: List[dict], seg: Segment, doc: int,
                      score: float) -> Tuple[Tuple, Tuple]:
    """(comparison tuple asc-ordered, raw user-facing values)."""
    if not sort_specs:
        # score ties break by (shard, segment, local doc) via the STABLE
        # final sort over shard-concatenated candidates — the reference's
        # own merge comparator (score, shard index, doc), and exactly the
        # order every device selection (kernel top-k, mesh program) uses.
        # An _id tie-break here would diverge from both.
        return ((-score,), (score,))
    comp = []
    raw = []
    for spec in sort_specs:
        f = spec["field"]
        desc = spec.get("order", "desc" if f == "_score" else "asc") == "desc"
        missing_last = spec.get("missing", "_last") == "_last"
        if f == "_score":
            v: Any = score
            comp.append(-v if desc else v)
            raw.append(v)
            continue
        if f == "_doc":
            comp.append(doc)
            raw.append(doc)
            continue
        if f == "_geo_distance":
            import math
            col = seg.geo_cols.get(spec["geo_field"])
            if col is not None and col.present[doc]:
                olat, olon = spec["origin"]
                p1 = math.radians(float(col.lat[doc]))
                p2 = math.radians(olat)
                dl = math.radians(olon - float(col.lon[doc]))
                a = (math.sin((p2 - p1) / 2) ** 2
                     + math.cos(p1) * math.cos(p2) * math.sin(dl / 2) ** 2)
                dist_m = 2 * 6371008.8 * math.asin(math.sqrt(min(a, 1.0)))
                v = dist_m / _DIST_UNITS.get(spec.get("unit", "m"), 1.0)
                comp.append((0, -v if desc else v))
                raw.append(v)
            else:
                comp.append((1 if missing_last else -1, 0.0))
                raw.append(None)
            continue
        nspec = spec.get("nested")
        if nspec and nspec.get("path"):
            v = PN.nested_sort_value(
                seg, f, nspec["path"],
                spec.get("mode", "max" if desc else "min"), doc)
            if v is not None:
                comp.append((0, -v if desc else v))
                raw.append(v)
            else:
                comp.append((1 if missing_last else -1, 0.0))
                raw.append(None)
            continue
        if f == "_script":
            from ..script import run_field_script
            from .query_dsl import parse_script_spec
            src_str, prm = parse_script_spec(spec.get("script"))
            try:
                v = run_field_script(src_str, prm, seg, doc, score=score)
            except _ScriptError as e:
                raise dsl.QueryParseError(f"[_script sort]: {e}")
            if spec.get("type") == "string":
                comp.append((0, _StrKey(str(v), desc)))
            else:
                v = float(v)
                comp.append((0, -v if desc else v))
            raw.append(v)
            continue
        col = seg.numeric_cols.get(f)
        if col is not None and col.present[doc]:
            v = _render_numeric(col, doc)
            comp.append((0 if not missing_last else 0, -v if desc else v))
            raw.append(v)
            continue
        kcol = seg.keyword_cols.get(f)
        if kcol is not None and kcol.min_ord[doc] >= 0:
            sv = kcol.vocab[kcol.min_ord[doc]]
            comp.append((0, _StrKey(sv, desc)))
            raw.append(sv)
            continue
        comp.append((1 if missing_last else -1, 0))
        raw.append(None)
    comp.append(seg.ids[doc])  # stable tiebreak
    return (tuple(comp), tuple(raw))


class _StrKey:
    """String sort key supporting descending order in tuple comparisons."""

    __slots__ = ("s", "desc")

    def __init__(self, s: str, desc: bool):
        self.s = s
        self.desc = desc

    def __lt__(self, other):
        return (self.s > other.s) if self.desc else (self.s < other.s)

    def __eq__(self, other):
        return self.s == other.s


def _after_key_value(search_after: List, sort_specs: List[dict], seg: Segment) -> float:
    """Device-comparable primary-key cursor for search_after."""
    if not sort_specs or sort_specs[0]["field"] == "_score":
        return float(search_after[0])
    f = sort_specs[0]["field"]
    desc = sort_specs[0].get("order", "asc") == "desc"
    v = search_after[0]
    col = seg.numeric_cols.get(f)
    if col is not None:
        ords = col.sort_ords()
        pos = np.searchsorted(np.unique(col.values[col.present]), v)
        key = float(pos)
        return key if desc else -key
    kcol = seg.keyword_cols.get(f)
    if kcol is not None:
        from bisect import bisect_left
        pos = bisect_left(kcol.vocab, str(v))
        return float(pos) if desc else -float(pos)
    return float("inf")


def _filter_source(src: dict, opt) -> dict:
    if opt is True:
        return src
    if isinstance(opt, str):
        opt = {"includes": [opt]}
    if isinstance(opt, list):
        opt = {"includes": opt}
    includes = opt.get("includes", [])
    excludes = opt.get("excludes", [])

    def flatten(d, prefix=""):
        for k, v in d.items():
            path = f"{prefix}{k}"
            if isinstance(v, dict):
                yield from flatten(v, f"{path}.")
            else:
                yield path, v

    def keep(path):
        if includes and not any(fnmatch.fnmatch(path, p) or path.startswith(p + ".")
                                for p in includes):
            return False
        if any(fnmatch.fnmatch(path, p) for p in excludes):
            return False
        return True

    out: dict = {}
    for path, v in flatten(src):
        if keep(path):
            node = out
            parts = path.split(".")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = v
    return out


def _render_numeric(col, doc: int):
    """Column value -> JSON value; unsigned_long unbiases its i64 storage
    (index/mappings.py U64_BIAS)."""
    v = col.values[doc]
    if col.kind == "float":
        return float(v)
    if col.kind == "uint":
        return int(v) + (1 << 63)
    return int(v)


def _docvalue_fields(seg: Segment, doc: int, specs: List) -> dict:
    out = {}
    for spec in specs:
        f = spec if isinstance(spec, str) else spec.get("field")
        if f == "_id":
            # OpenSearch Benchmark's vector search reads a hit's id here,
            # with `stored_fields: _none_`
            out[f] = [seg.ids[doc]]
            continue
        col = seg.numeric_cols.get(f)
        if col is not None and col.present[doc]:
            out[f] = [_render_numeric(col, doc)]
            continue
        kcol = seg.keyword_cols.get(f)
        if kcol is not None:
            a, b = int(kcol.starts[doc]), int(kcol.starts[doc + 1])
            if b > a:
                out[f] = [kcol.vocab[o] for o in kcol.ords[a:b]]
    return out


def _extract_source_values(src: dict, path: str) -> List:
    node: Any = src
    for part in path.split("."):
        if isinstance(node, dict):
            node = node.get(part)
        elif isinstance(node, list):
            node = [n.get(part) for n in node if isinstance(n, dict)]
        else:
            return []
        if node is None:
            return []
    return node if isinstance(node, list) else [node]


def _ordinal_buckets(node: AggNode, device_out: dict, vocab,
                     ordinals=None) -> dict:
    """Ordinal-bucket partial extraction as records (significant_terms,
    rare_terms, geo grids, a composite's page): the non-empty ordinals, or
    those of them that `ordinals` names, keyed by vocab + per-bucket stats
    tuples. One Python record a bucket (`aggs.terms.records`): a kind
    whose response returns a few of many buckets keeps arrays
    (`_ordinal_arrays`)."""
    counts = np.asarray(device_out["counts"])
    subs = _sub_metric_columns(node, device_out)
    if ordinals is None:
        ordinals = np.nonzero(counts[: len(vocab)] > 0)[0]
    AGG_STATS.inc("terms.records", len(ordinals))
    buckets: dict = {}
    for o in ordinals:
        rec: dict = {"doc_count": int(round(float(counts[o])))}
        sub_partials = _bucket_subs(subs, int(o))
        if sub_partials:
            rec["subs"] = sub_partials
        buckets[vocab[o]] = rec
    return buckets


def _ordinal_arrays(node: AggNode, device_out: dict, keys) -> OrdinalBuckets:
    """A `terms` / `multi_terms` partial: the counts stay an array by
    ordinal beside the sub-metrics' columns, `keys` names an ordinal when
    `aggregations.finalize` asks (no record is built here)."""
    n = len(keys)
    return OrdinalBuckets(
        keys, np.asarray(device_out["counts"])[:n],
        {name: {k: np.asarray(v)[:n] for k, v in cols.items()}
         for name, cols in _sub_metric_columns(node, device_out).items()})


def _composite_page(node: AggNode, counts: np.ndarray, space) -> np.ndarray:
    """The slots of a composite partial that can reach the response: the
    first `size` non-empty combinations after the request's `after` key
    in key order (`space` numbers them so). A merged page's buckets each
    lie among every segment's first `size`, so the rest never become
    records; an `after` the sources cannot place keeps them all."""
    size = int(node.body.get("size", 10))
    after = node.body.get("after")
    start = 0
    if after is not None:
        try:
            start = space.first_after(tuple(
                after[nm] for nm, _t, _c, _o in composite_sources(node)))
        except (TypeError, ValueError, KeyError):
            size = len(counts)
    return start + np.flatnonzero(counts[start:] > 0)[:size]


def _auto_date_ranges(agg_nodes, qspec, seg: Segment, ctx, params: dict,
                      device) -> dict:
    """aggregation name -> (least, greatest) epoch ms of its field among
    the documents the query matches in `seg`, for every top-level
    `auto_date_histogram` (its rounding follows the matched range, which
    only the device knows): one launch before the request's own, in a
    `search.aggs.refine` span. Empty where the request has none."""
    fields = {}
    for an in agg_nodes:
        if an.kind == "auto_date_histogram":
            f = AC.resolve_agg_field(an, ctx)
            col = seg.numeric_cols.get(f)
            if col is not None and col.kind == "int":
                fields[an.name] = f
    if not fields:
        return {}
    AGG_STATS.inc("auto_date.requests", len(fields))
    with TRACER.span("search.aggs.refine"):
        got = PG.auto_date_range(qspec, tuple(sorted(set(fields.values()))),
                                 seg.device_arrays(device), params)
    return {name: got[f] for name, f in fields.items() if got[f]}


def _fetch_agg_outputs(device_out):
    """An agg program's output tree read to the host in one sweep, inside
    a `device.wait` span: `_device_agg_to_partial` then walks numpy arrays
    and its time is the host's alone."""
    if not device_out:
        return device_out
    import jax
    with TRACER.span("device.wait", program="executor", outputs="aggs"):
        return jax.device_get(device_out)


def _device_agg_to_partial(node: AggNode, aspec, device_out: Optional[dict],
                           seg: Segment, ctx,
                           seg_stack: Tuple[Segment, ...] = ()) -> Optional[dict]:
    """Device arrays -> host partial in the shapes `aggregations.merge_partials`
    expects."""
    if device_out is None:
        return None
    kind = aspec[0]

    if kind in ("terms_missing", "hist_missing"):
        return None

    if kind == "terms":
        _, prefix, f, nvocab_pad, subs = aspec
        vocab = seg.keyword_cols[f].vocab
        if node.kind == "terms":
            return {"buckets": _ordinal_arrays(node, device_out, vocab)}
        return {"buckets": _ordinal_buckets(node, device_out, vocab)}

    if kind == "hist":
        _, prefix, f, interval, offset, min_b, nb, subs = aspec
        return _hist_partial(node, device_out, min_b, interval, offset)

    if kind == "date_hist":
        (_, prefix, f, interval_ms, offset_ms, calendar, min_b, nb, subs,
         _form) = aspec
        if calendar is not None:
            # convert calendar bucket ids to epoch-ms keys host-side
            counts = np.asarray(device_out["counts"])
            sub_cols = _sub_metric_columns(node, device_out)
            buckets = {}
            for j in np.nonzero(counts > 0)[0]:
                epoch = PN.calendar_bucket_start_ms(min_b + int(j), calendar)
                rec = {"doc_count": int(round(float(counts[j])))}
                rec["subs"] = _bucket_subs(sub_cols, int(j))
                buckets[epoch] = rec
            return {"buckets": buckets, "interval": 1, "offset": 0.0,
                    "calendar": calendar}
        return _hist_partial(node, device_out, min_b, float(interval_ms),
                             float(offset_ms))

    if kind in ("range", "geo_range"):
        _, prefix, f, keys, col_exists, subs, bounds = aspec[:7]
        counts = np.asarray(device_out["counts"])
        buckets = {}
        for ri, key in enumerate(keys):
            rec = {"doc_count": int(round(float(counts[ri])))}
            lo, hi = bounds[ri]
            meta = {}
            if np.isfinite(lo):
                meta["from"] = lo
            if np.isfinite(hi):
                meta["to"] = hi
            rec["meta"] = meta
            sub_partials = {}
            for i, sub_node in enumerate(node.subs):
                r = device_out.get(f"r{ri}_sub{i}")
                if r is not None:
                    sub_partials[sub_node.name] = _device_agg_to_partial(
                        sub_node, _find_sub_spec(aspec, i), r, seg, ctx,
                        seg_stack)
            rec["subs"] = sub_partials
            buckets[key] = rec
        return {"buckets": buckets}

    if kind in ("filter", "global", "missing"):
        subs_field = {"filter": 3, "global": 2, "missing": 4}[kind]
        sub_specs = aspec[subs_field]
        rec = {"doc_count": int(round(float(np.asarray(device_out["count"])))),
               "subs": {}}
        for i, sub_node in enumerate(node.subs):
            r = device_out.get(f"sub{i}")
            if r is not None:
                rec["subs"][sub_node.name] = _device_agg_to_partial(
                    sub_node, sub_specs[i], r, seg, ctx, seg_stack)
        return rec

    if kind == "filters":
        _, prefix, fspecs, sub_specs = aspec
        buckets = {}
        for ki, (key, _) in enumerate(fspecs):
            ent = device_out.get(f"k{ki}", {})
            rec = {"doc_count": int(round(float(np.asarray(ent.get("count", 0.0))))),
                   "subs": {}}
            for i, sub_node in enumerate(node.subs):
                r = ent.get(f"sub{i}")
                if r is not None:
                    rec["subs"][sub_node.name] = _device_agg_to_partial(
                        sub_node, sub_specs[i], r, seg, ctx)
            buckets[key] = rec
        return {"buckets": buckets}

    if kind == "sig_missing":
        return {"buckets": {}, "fg_total": 0, "bg": {},
                "bg_total": seg.live_count}

    if kind == "sig_terms":
        _, prefix, f, nvocab_pad, subs = aspec
        return {"buckets": _ordinal_buckets(node, device_out,
                                            seg.keyword_cols[f].vocab),
                "fg_total": int(round(float(np.asarray(device_out["fg_total"])))),
                "bg": PN.kw_doc_counts(seg, f),
                "bg_total": seg.live_count}

    if kind in ("sampler", "dsampler"):
        sub_specs = aspec[-1]
        rec = {"doc_count": int(round(float(np.asarray(device_out["doc_count"])))),
               "subs": {}}
        if "topscores" in device_out:
            rec["topscores"] = np.asarray(device_out["topscores"])
        for i, sub_node in enumerate(node.subs):
            r = device_out.get(f"sub{i}")
            if r is not None:
                rec["subs"][sub_node.name] = _device_agg_to_partial(
                    sub_node, sub_specs[i], r, seg, ctx, seg_stack)
        return rec

    if kind == "geo_grid":
        _, prefix, gkind, f, precision, nb, subs = aspec
        vocab, _ords = PN.geo_grid_cache(seg, f, gkind, precision)
        return {"buckets": _ordinal_buckets(node, device_out, vocab)}

    if kind == "matrix_stats":
        _, prefix, fields, exists = aspec
        n = float(np.asarray(device_out["count"]))
        k = len(fields)
        if not fields or "s1" not in device_out:
            return {"count": 0, "fields": list(fields), "shift": np.zeros(k),
                    "s1": np.zeros(k), "s2": np.zeros(k), "s3": np.zeros(k),
                    "s4": np.zeros(k), "xy": np.zeros((k, k))}
        return {"count": n, "fields": list(fields),
                "shift": np.asarray(device_out["shift"], np.float64),
                "s1": np.asarray(device_out["s1"], np.float64),
                "s2": np.asarray(device_out["s2"], np.float64),
                "s3": np.asarray(device_out["s3"], np.float64),
                "s4": np.asarray(device_out["s4"], np.float64),
                "xy": np.asarray(device_out["xy"], np.float64)}

    if kind in ("nested_agg", "reverse_nested", "children_agg", "parent_agg"):
        sub_specs = aspec[3]
        sub_seg, sub_stack = seg, seg_stack
        if kind == "nested_agg":
            blk = seg.nested.get(aspec[2])
            sub_seg = blk.child if blk else seg
            sub_stack = seg_stack + (seg,)
        elif kind == "reverse_nested":
            up_k = aspec[2]
            full = seg_stack + (seg,)
            sub_seg = full[-(up_k + 1)]
            sub_stack = full[: -(up_k + 1)]
        rec = {"doc_count": int(round(float(np.asarray(device_out["doc_count"])))),
               "subs": {}}
        for i, sub_node in enumerate(node.subs):
            r = device_out.get(f"sub{i}")
            if r is not None:
                rec["subs"][sub_node.name] = _device_agg_to_partial(
                    sub_node, sub_specs[i], r, sub_seg, ctx, sub_stack)
        return rec

    if kind == "composite_mv":
        _, prefix, f, nb, subs = aspec
        flat = _ordinal_buckets(node, device_out, seg.keyword_cols[f].vocab)
        return {"buckets": {(k,): v for k, v in flat.items()}}

    if kind == "composite":
        _, prefix, single, total, subs = aspec
        _plane, space = AC.composite_space(
            seg, AC.bind_composite_sources(node, seg, ctx)[0])
        counts = np.asarray(device_out["counts"])[:total]
        return {"buckets": _ordinal_buckets(
            node, device_out, space, _composite_page(node, counts, space))}

    if kind == "stats":
        if "empty" in device_out:
            return {"count": 0, "sum": 0.0, "min": float("inf"),
                    "max": float("-inf"), "sumsq": 0.0}
        cols = _sub_metric_arrays(device_out)
        return {k: float(np.asarray(v).reshape(-1)[0])
                for k, v in cols.items()}

    if kind == "vc_keyword":
        return {"count": float(np.asarray(device_out["count"])), "sum": 0.0,
                "min": 0.0, "max": 0.0, "sumsq": 0.0}

    if kind in ("card_kw", "card_num"):
        out = {"registers": np.asarray(device_out["registers"])}
        if "distinct" in device_out:    # a keyword's matched ordinals
            out["distinct"] = int(np.asarray(device_out["distinct"]))
        return out

    if kind == "pctl":
        _, prefix, f, col_exists, percents = aspec
        return {"hist": np.asarray(device_out["hist"]), "percents": list(percents)}

    if kind == "pctl_ranks":
        _, prefix, f, col_exists, values = aspec
        return {"hist": np.asarray(device_out["hist"]), "values": list(values)}

    if kind == "wavg":
        return {"vwsum": float(np.asarray(device_out["vwsum"])),
                "wsum": float(np.asarray(device_out["wsum"])),
                "count": float(np.asarray(device_out["count"]))}

    if kind == "mad":
        return {"hist": np.asarray(device_out["hist"])}

    if kind == "geo_stat":
        out = {k: float(np.asarray(v)) for k, v in device_out.items()}
        return out

    if kind == "ip_range":
        _, prefix, f, keys, bounds, open_lo, open_hi, col_exists, sub_specs = aspec
        counts = np.asarray(device_out.get("counts", np.zeros(len(keys))))
        buckets = {}
        for ri, key in enumerate(keys):
            rec = {"doc_count": int(round(float(counts[ri]))), "subs": {}}
            meta = {}
            frm, to = bounds[ri]
            if frm is not None:
                meta["from"] = frm
            if to is not None:
                meta["to"] = to
            rec["meta"] = meta
            for i, sub_node in enumerate(node.subs):
                r = device_out.get(f"r{ri}_sub{i}")
                if r is not None:
                    rec["subs"][sub_node.name] = _device_agg_to_partial(
                        sub_node, sub_specs[i], r, seg, ctx, seg_stack)
            buckets[key] = rec
        return {"buckets": buckets}

    if kind == "multi_terms":
        _, prefix, nord_pad, nvocab, sub_specs = aspec
        fields = tuple(s["field"] for s in node.body.get("terms", []))
        _plane, space = PN.multi_terms_plane(seg, ctx, fields)
        return {"buckets": _ordinal_arrays(node, device_out, space)}

    if kind == "adjacency":
        _, prefix, fspecs, sep, sub_specs = aspec
        names = [key for key, _ in fspecs]
        labels = list(names)
        for ai in range(len(names)):
            for bi in range(ai + 1, len(names)):
                labels.append(f"{names[ai]}{sep}{names[bi]}")
        buckets = {}
        for ci, label in enumerate(labels):
            cnt = int(round(float(np.asarray(device_out[f"c{ci}"]))))
            rec = {"doc_count": cnt, "subs": {}}
            for i, sub_node in enumerate(node.subs):
                r = device_out.get(f"c{ci}_sub{i}")
                if r is not None:
                    rec["subs"][sub_node.name] = _device_agg_to_partial(
                        sub_node, sub_specs[i], r, seg, ctx, seg_stack)
            buckets[label] = rec
        return {"buckets": buckets}

    if kind == "auto_date_hist":
        (_, prefix, f, unit, target, min_b, nb, window, sub_specs,
         _form) = aspec
        first = min_b + int(np.asarray(device_out["first"]))
        part = _hist_partial(node, device_out, first, 1.0, 0.0)
        # keyed by the unit bucket's start in epoch ms (the merge coarsens
        # across shards' units)
        return {"buckets": {PN.auto_unit_start_ms(b, unit): rec
                            for b, rec in part["buckets"].items()},
                "unit": unit}

    if kind == "scripted":
        return _scripted_metric_partial(node, device_out, seg)

    if kind == "sig_text":
        return _significant_text_partial(node, device_out, seg, ctx)

    raise ValueError(f"cannot build partial for agg spec [{kind}]")


def _scripted_metric_partial(node: AggNode, device_out: dict, seg: Segment) -> dict:
    """Host map/combine passes of scripted_metric (reference
    ScriptedMetricAggregator): painless-lite over each matched doc."""
    from ..script.painless_lite import execute
    from ..script.painless_lite import doc_view_for

    body = node.body
    sparams = body.get("params", {})
    state: Dict[str, Any] = {}
    if body.get("init_script"):
        src, prm = _script_spec(body["init_script"], sparams)
        execute(src, {"state": state, "params": prm})
    map_src, map_prm = _script_spec(body.get("map_script", ""), sparams)
    mask = np.asarray(device_out["match_mask"])[: seg.ndocs] > 0

    class _Doc(dict):
        def __init__(self, d):
            self._d = d
            super().__init__()

        def __getitem__(self, f):
            return doc_view_for(seg, self._d, f)

        def get(self, f, default=None):
            return doc_view_for(seg, self._d, f)

        def containsKey(self, f):  # noqa: N802 (painless API)
            return not doc_view_for(seg, self._d, f).empty

    for d in np.nonzero(mask)[0]:
        execute(map_src, {"state": state, "params": map_prm,
                          "doc": _Doc(int(d))})
    if body.get("combine_script"):
        src, prm = _script_spec(body["combine_script"], sparams)
        combined = execute(src, {"state": state, "params": prm})
    else:
        combined = state
    return {"states": [combined]}


def _script_spec(spec, defaults: dict):
    if isinstance(spec, str):
        return spec, dict(defaults)
    prm = dict(defaults)
    prm.update(spec.get("params", {}))
    return spec.get("source", ""), prm


def _significant_text_partial(node: AggNode, device_out: dict, seg: Segment,
                              ctx) -> dict:
    """significant_text (reference SignificantTextAggregator): sample the
    best-scoring matched docs, re-analyze the text field from _source, and
    score candidate terms against the index background (postings df)."""
    body = node.body
    field = body.get("field", "")
    shard_size = int(body.get("shard_size", 200))
    mask = np.asarray(device_out["match_mask"])[: seg.ndocs] > 0
    scores = np.asarray(device_out["score_vec"])[: seg.ndocs]
    docs = np.nonzero(mask)[0]
    if len(docs) > shard_size:
        order = np.argsort(-scores[docs], kind="stable")
        docs = docs[order[:shard_size]]
    from .plan import analyze_query_text
    fg: Dict[str, int] = {}
    for d in docs:
        src = seg.sources[int(d)]
        v = src.get(field) if isinstance(src, dict) else None
        if v is None:
            continue
        texts = v if isinstance(v, list) else [v]
        seen = set()
        for t in texts:
            for tok in analyze_query_text(field, str(t), ctx):
                seen.add(tok)
        for tok in seen:
            fg[tok] = fg.get(tok, 0) + 1
    pb = seg.postings.get(field)
    bg = {}
    for tok in fg:
        bg[tok] = pb.doc_freq(tok) if pb is not None else 0
    buckets = {tok: {"doc_count": c, "subs": {}} for tok, c in fg.items()}
    return {"buckets": buckets, "bg": bg, "fg_total": int(len(docs)),
            "bg_total": int(seg.live_count)}


def _find_sub_spec(aspec, i):
    for item in aspec:
        if isinstance(item, tuple) and len(item) > i and isinstance(item[i], tuple):
            return item[i]
    return None


def _sub_metric_arrays(out: dict) -> dict:
    """A metric's device output (`ops.aggs.bucketed_sub_metric` /
    `stats_agg`, read to the host) -> {"count", "sum", "min", "max",
    "sumsq"} as numpy: the limb sums finished in float64."""
    inv = float(np.asarray(out["scale"]))
    cols = {"count": np.asarray(out["count"]),
            "sum": agg_ops.limb_sums_to_f64(out["sum"], inv),
            "min": np.asarray(out["min"]), "max": np.asarray(out["max"])}
    cols["sumsq"] = (agg_ops.limb_sums_to_f64(out["sumsq"], inv * inv)
                     if "sumsq" in out else np.zeros_like(cols["sum"]))
    return cols


def _sub_metric_columns(node: AggNode, device_out: dict) -> dict:
    """sub-aggregation name -> `_sub_metric_arrays` of a bucket
    aggregation's `sub{i}` outputs (once a partial, not once a bucket)."""
    return {sub_node.name: _sub_metric_arrays(device_out[f"sub{i}"])
            for i, sub_node in enumerate(node.subs)
            if device_out.get(f"sub{i}") is not None}


def _bucket_subs(sub_cols: dict, j: int) -> dict:
    return {name: {k: float(v[j]) for k, v in cols.items()}
            for name, cols in sub_cols.items()}


def _hist_partial(node: AggNode, device_out: dict, min_b: int, interval: float,
                  offset: float) -> dict:
    counts = np.asarray(device_out["counts"])
    sub_cols = _sub_metric_columns(node, device_out)
    buckets = {}
    for j in np.nonzero(counts > 0)[0]:
        rec = {"doc_count": int(round(float(counts[j])))}
        rec["subs"] = _bucket_subs(sub_cols, int(j))
        buckets[min_b + int(j)] = rec
    return {"buckets": buckets, "interval": interval, "offset": offset}


# =====================================================================
# explain (host recompute, reference TransportExplainAction)
# =====================================================================

def _host_phrase_freq(node, seg: Segment, doc: int) -> float:
    """Host mirror of ops.positions.phrase_freqs for one doc (explain)."""
    from .plan import prefix_rows

    pb = seg.postings.get(node.field)
    if pb is None or pb.pos_starts is None:
        return 0.0
    pos_lists: List[np.ndarray] = []
    last = len(node.terms) - 1
    for i, t in enumerate(node.terms):
        if node.prefix_last and i == last:
            rows = list(prefix_rows(pb, t, node.max_expansions))
        else:
            r = pb.row(t)
            rows = [r] if r >= 0 else []
        plist: List[int] = []
        for r in rows:
            a, b = pb.row_slice(r)
            k = a + int(np.searchsorted(pb.doc_ids[a:b], doc))
            if k < b and pb.doc_ids[k] == doc:
                plist.extend((pb.positions[pb.pos_starts[k]: pb.pos_starts[k + 1]]
                              - i).tolist())
        if not plist:
            return 0.0
        pos_lists.append(np.asarray(sorted(plist)))
    freq = 0.0
    for base in pos_lists[0]:
        ok = True
        if node.ordered:
            # greedy sequential join, mirroring the device ordered path
            prev = 0.0
            for arr in pos_lists[1:]:
                j = int(np.searchsorted(arr, base + prev))
                if j >= len(arr):
                    ok = False
                    break
                prev = float(arr[j]) - float(base)
            cost = prev if ok else 0.0
        else:
            deltas = [0.0]
            for arr in pos_lists[1:]:
                j = int(np.searchsorted(arr, base))
                # tie prefers the right neighbor, like the device kernel
                cands = [int(arr[jj]) - int(base)
                         for jj in (j, j - 1) if 0 <= jj < len(arr)]
                if not cands:
                    ok = False
                    break
                deltas.append(float(min(cands, key=abs)))
            if ok:
                if node.gap_cost:
                    abs_off = [d + i for i, d in enumerate(deltas)]
                    cost = max(abs_off) - min(abs_off) + 1 - len(deltas)
                else:
                    med = sorted(deltas)[len(deltas) // 2]  # optimal offset
                    cost = sum(abs(d - med) for d in deltas)
        if ok and cost <= node.slop:
            freq += 1.0 / (1.0 + cost)
    return freq

def explain_doc(lroot, seg: Segment, doc: int, ctx) -> dict:
    from .plan import LBool, LConstScore, LDisMax, LPhrase, LTerms
    from ..ops.scoring import SIM_BM25

    def walk(n) -> Tuple[float, dict]:
        if isinstance(n, PL.LSpanHost):
            freq = float(n._freqs.get(seg.uid, np.zeros(1))[doc]
                         if doc < len(n._freqs.get(seg.uid, [])) else 0.0)
            dl = float(seg.doc_lens.get(n.field, np.zeros(seg.ndocs))[doc]) \
                if n.field in seg.doc_lens else 0.0
            avgdl = max(ctx.avgdl(n.field), 1e-9)
            b_eff = n.sim.b if n.has_norms else 0.0
            kk = n.sim.k1 * (1 - b_eff + b_eff * dl / avgdl)
            total = n.weight * freq / (freq + kk) if freq > 0 else 0.0
            return total, {"value": total,
                           "description": f"span/intervals on [{n.field}]: "
                                          f"sloppyFreq {freq:.3f}",
                           "details": []}
        if isinstance(n, LPhrase):
            freq = _host_phrase_freq(n, seg, doc)
            dl = float(seg.doc_lens.get(n.field, np.zeros(seg.ndocs))[doc]) \
                if n.field in seg.doc_lens else 0.0
            avgdl = max(ctx.avgdl(n.field), 1e-9)
            b_eff = n.sim.b if n.has_norms else 0.0
            kk = n.sim.k1 * (1 - b_eff + b_eff * dl / avgdl)
            total = n.weight * freq / (freq + kk) if freq > 0 else 0.0
            desc = (f'phrase "{" ".join(n.terms)}" on [{n.field}]: idf-sum*boost '
                    f'{n.weight:.4f} * sloppyFreq {freq:.3f}/(freq+{kk:.3f})')
            return total, {"value": total, "description": desc, "details": []}
        if isinstance(n, LTerms):
            details = []
            total = 0.0
            dl = float(seg.doc_lens.get(n.field, np.zeros(seg.ndocs))[doc]) \
                if n.field in seg.doc_lens else 0.0
            avgdl = ctx.avgdl(n.field)
            pb = seg.postings.get(n.field)
            for i, t in enumerate(n.terms):
                if pb is None:
                    continue
                r = pb.row(t)
                if r < 0:
                    continue
                a, b = pb.row_slice(r)
                k = a + int(np.searchsorted(pb.doc_ids[a:b], doc))
                if k >= b or pb.doc_ids[k] != doc:
                    continue
                tf = float(pb.tfs[k])
                w = float(n.weights[i])
                sim = n.sim
                if sim.sim_id == SIM_BM25:
                    b_eff = sim.b if n.has_norms else 0.0
                    kk = sim.k1 * (1 - b_eff + b_eff * dl / max(avgdl, 1e-9))
                    contrib = w * tf / (tf + kk)
                    desc = (f"weight({n.field}:{t}) = idf*boost {w:.4f} * "
                            f"tf {tf:.0f}/(tf+{kk:.3f})")
                else:
                    contrib = w
                    desc = f"weight({n.field}:{t})"
                total += contrib
                details.append({"value": contrib, "description": desc, "details": []})
            return total, {"value": total,
                           "description": f"sum of term scores on [{n.field}]",
                           "details": details}
        if isinstance(n, LBool):
            total = 0.0
            details = []
            for c in n.musts + n.shoulds:
                v, d = walk(c)
                total += v
                details.append(d)
            total *= n.boost
            return total, {"value": total, "description": "sum of:", "details": details}
        if isinstance(n, LConstScore):
            return n.boost, {"value": n.boost, "description": "ConstantScore",
                             "details": []}
        if isinstance(n, LDisMax):
            vals = [walk(c) for c in n.children]
            best = max((v for v, _ in vals), default=0.0)
            total = best + n.tie_breaker * (sum(v for v, _ in vals) - best)
            return total, {"value": total, "description": "max plus tie_breaker of:",
                           "details": [d for _, d in vals]}
        from .plan import LExists, LMatchAll, LRange
        if isinstance(n, LRange):
            col = seg.numeric_cols.get(n.field)
            ok = col is not None and bool(col.present[doc])
            if ok:
                v = float(col.values[doc])
                if n.lo is not None:
                    ok = v >= float(n.lo) if n.include_lo else v > float(n.lo)
                if ok and n.hi is not None:
                    ok = v <= float(n.hi) if n.include_hi else v < float(n.hi)
            val = n.boost if ok else 0.0
            return val, {"value": val,
                         "description": f"range filter on [{n.field}]", "details": []}
        if isinstance(n, LMatchAll):
            return n.boost, {"value": n.boost, "description": "*:*", "details": []}
        if isinstance(n, LExists):
            ok = ((n.field in seg.numeric_cols and bool(seg.numeric_cols[n.field].present[doc]))
                  or (n.field in seg.keyword_cols and int(seg.keyword_cols[n.field].min_ord[doc]) >= 0)
                  or (n.field in seg.doc_lens and int(seg.doc_lens[n.field][doc]) > 0))
            val = n.boost if ok else 0.0
            return val, {"value": val,
                         "description": f"exists [{n.field}]", "details": []}
        from .plan import LNested
        if isinstance(n, LNested):
            blk = seg.nested.get(n.path)
            if blk is None or blk.child.ndocs == 0:
                return 0.0, {"value": 0.0, "description": "no nested docs",
                             "details": []}
            # match/score truth comes from the same device program the query
            # ran (host explains can't see filter-context matches); the host
            # child explains are attached as details only
            from . import compiler as _C
            cparams: Dict[str, Any] = {}
            cspec = _C.prepare(n.child, blk.child, n.child_ctx, cparams)
            a, b = blk.children_of(doc)
            docs = np.arange(blk.child.ndocs_pad, dtype=np.int32)
            csc, cm = _C.run_gather_scores(cspec, blk.child.device_arrays(),
                                           cparams, docs)
            csc, cm = np.asarray(csc), np.asarray(cm)
            vals = [float(csc[i]) for i in range(a, b) if cm[i]]
            if not vals:
                return 0.0, {"value": 0.0,
                             "description": f"no matching children in [{n.path}]",
                             "details": []}
            mode = n.score_mode
            total = (sum(vals) / len(vals) if mode == "avg" else
                     max(vals) if mode == "max" else
                     min(vals) if mode == "min" else
                     1.0 if mode == "none" else sum(vals))
            total *= n.boost
            details = [explain_doc(n.child, blk.child, cd, n.child_ctx)
                       for cd in range(a, b) if cm[cd]]
            return total, {"value": total,
                           "description": f"nested [{n.path}] {mode} of children:",
                           "details": details}
        from .plan import LHasChild, LHasParent
        if isinstance(n, LHasChild):
            from . import compiler as _C
            ji = n.join_index
            cache: Dict[int, Any] = {}
            vals = []
            for cseg, cd in ji.children_of(ji.seg_base(seg) + doc):
                if id(cseg) not in cache:
                    cparams: Dict[str, Any] = {}
                    cspec = _C.prepare(n.child, cseg, ctx, cparams)
                    darr = np.arange(cseg.ndocs_pad, dtype=np.int32)
                    csc, cm = _C.run_gather_scores(cspec, cseg.device_arrays(),
                                                   cparams, darr)
                    cache[id(cseg)] = (np.asarray(csc), np.asarray(cm))
                csc, cm = cache[id(cseg)]
                if cm[cd] and cseg.live[cd]:
                    vals.append(float(csc[cd]))
            ok = max(n.min_children, 1) <= len(vals) <= n.max_children
            mode = n.score_mode
            total = 0.0
            if ok:
                total = (1.0 if mode == "none" else
                         sum(vals) / len(vals) if mode == "avg" else
                         max(vals) if mode == "max" else
                         min(vals) if mode == "min" else sum(vals)) * n.boost
            return total, {"value": total,
                           "description": (f"has_child [{n.child_rel}] {mode} of "
                                           f"{len(vals)} matching children"),
                           "details": []}
        if isinstance(n, LHasParent):
            from . import compiler as _C
            ji = n.join_index
            slot = int(ji.pslot(seg)[doc])
            loc = ji.slot_to_doc(slot) if slot >= 0 else None
            total = 0.0
            if loc is not None:
                pseg, pd = loc
                cparams = {}
                cspec = _C.prepare(n.child, pseg, ctx, cparams)
                darr = np.arange(pseg.ndocs_pad, dtype=np.int32)
                psc, pm = _C.run_gather_scores(cspec, pseg.device_arrays(),
                                               cparams, darr)
                if np.asarray(pm)[pd] and pseg.live[pd]:
                    total = (float(np.asarray(psc)[pd]) if n.use_score else 1.0) * n.boost
            return total, {"value": total,
                           "description": f"has_parent [{n.parent_rel}]",
                           "details": []}
        return 0.0, {"value": 0.0, "description": type(n).__name__, "details": []}

    _, expl = walk(lroot)
    return expl
