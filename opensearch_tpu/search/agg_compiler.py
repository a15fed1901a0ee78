"""Aggregation compile: `prepare_agg` binds an `AggNode` tree to one segment
(spec + params, the planes it reads), `emit_agg` traces it over a match.

Imports `compiler` (`prepare` / `emit` of a filter's query), `plan`,
`planes`, `aggregations` and `ops/`; never `programs`.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ..index.mappings import coerce_value
from ..index.segment import Segment, next_pow2, split_i64
from ..ops import aggs as agg_ops
from ..ops import scoring as ops
from . import query_dsl as dsl
from .aggregations import AggNode
from .compiler import (emit, join_prepass, prepare, put_param, scalar_f32,
                       scalar_i32)
from .plan import LBool, LMatchAll, ShardContext, rewrite, weighted_terms
from .planes import (AUTO_ROUNDINGS, ComboSpace, auto_unit_for, auto_unit_ids,
                     auto_window, col_sum, combo_plane, combo_space,
                     date_bucket_ids, date_bucket_plane, geo_grid_cache,
                     kw_hash_cache, multi_terms_plane, parse_interval_ms)

HLL_LOG2M = 14

# reference PercentilesAggregationBuilder defaults — shared with the mesh
# service so host and mesh never drift
DEFAULT_PERCENTS = (1.0, 5.0, 25.0, 50.0, 75.0, 95.0, 99.0)
PCTL_BINS = 4096


# =====================================================================
# aggregations: prepare + emit
# =====================================================================

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
    plane, min_b, nb, starts = date_bucket_plane(
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
        field = resolve_agg_field(node, ctx)
        if field not in seg.keyword_cols:
            return ("terms_missing", prefix)
        nvocab_pad = next_pow2(max(len(seg.keyword_cols[field].vocab), 1))
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("terms", prefix, field, nvocab_pad, subs)

    if kind == "histogram":
        field = resolve_agg_field(node, ctx)
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
        field = resolve_agg_field(node, ctx)
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
        field = resolve_agg_field(node, ctx)
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
        field = resolve_agg_field(node, ctx)
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
        scalar_f32(params, f"{prefix}_olat", olat)
        scalar_f32(params, f"{prefix}_olon", olon)
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
        field = resolve_agg_field(node, ctx)
        src = ("numeric" if field in seg.numeric_cols else
               "keyword" if field in seg.keyword_cols else "none")
        subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}",
                                 nest_stack)
                     for i, s in enumerate(node.subs))
        return ("missing", prefix, field, src, subs)

    if kind in ("min", "max", "sum", "avg", "stats", "extended_stats", "value_count"):
        field = resolve_agg_field(node, ctx)
        if kind == "value_count" and field in seg.keyword_cols:
            return ("vc_keyword", prefix, field)
        col = seg.numeric_cols.get(field)
        if col is not None:
            # the power of two that brings the column under 1, for the
            # sums' fixed point (`ops.aggs.bucket_sums_exact`)
            put_param(params, f"{prefix}_sinv",
                      agg_ops.sum_scale_inv(max(abs(x) for x in col.min_max)))
        return ("stats", prefix, field, col is not None,
                kind == "extended_stats")

    if kind == "cardinality":
        field = resolve_agg_field(node, ctx)
        if field in seg.keyword_cols:
            params[f"{prefix}_hashes"] = kw_hash_cache(seg, field)
            nvocab_pad = next_pow2(max(len(seg.keyword_cols[field].vocab), 1))
            return ("card_kw", prefix, field, nvocab_pad)
        return ("card_num", prefix, field, field in seg.numeric_cols)

    if kind == "percentiles":
        field = resolve_agg_field(node, ctx)
        col = seg.numeric_cols.get(field)
        percents = tuple(body.get("percents", DEFAULT_PERCENTS))
        return ("pctl", prefix, field, col is not None, percents)

    if kind == "percentile_ranks":
        field = resolve_agg_field(node, ctx)
        col = seg.numeric_cols.get(field)
        values = tuple(float(v) for v in body.get("values", ()))
        return ("pctl_ranks", prefix, field, col is not None, values)

    if kind == "top_hits":
        return ("top_hits", prefix, int(body.get("size", 3)))

    if kind == "significant_terms":
        field = resolve_agg_field(node, ctx)
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
            scalar_f32(params, f"{prefix}_thr", thr)
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
        field = resolve_agg_field(node, ctx)
        precision = grid_agg_precision(kind, body)
        vocab, ords = geo_grid_cache(seg, field, kind, precision)
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
        scalar_f32(params, f"{prefix}_vmiss", float(vspec.get("missing", 0.0)
                                                    or 0.0))
        scalar_f32(params, f"{prefix}_wmiss", float(wspec.get("missing", 0.0)
                                                    or 0.0))
        return ("wavg", prefix, vfield, wfield,
                vfield in seg.numeric_cols, wfield in seg.numeric_cols,
                vspec.get("missing") is not None,
                wspec.get("missing") is not None)

    if kind == "median_absolute_deviation":
        field = resolve_agg_field(node, ctx)
        return ("mad", prefix, field, field in seg.numeric_cols)

    if kind in ("geo_bounds", "geo_centroid"):
        field = resolve_agg_field(node, ctx)
        return ("geo_stat", prefix, kind, field, field in seg.geo_cols)

    if kind == "ip_range":
        from ..index.mappings import _ip_to_int
        field = resolve_agg_field(node, ctx)
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
        field = resolve_agg_field(node, ctx)
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
        field = resolve_agg_field(node, ctx)
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
                sums = [col_sum(s, f) for s in ctx.segments]
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
            "child": weighted_terms(jf, [child_rel], [1.0], ctx, 1, "filter", 1.0),
            "parent": weighted_terms(jf, [parent_rel], [1.0], ctx, 1, "filter", 1.0)}
        if kind == "children":
            # global mask of context-matched PARENT docs at their own slots
            plan = LBool(musts=[lroot], filters=[node._rel_filters["parent"]])
            pre = join_prepass(plan, ji, ("cnt",), ctx, self_slots=True)
        else:
            # global mask of parents having context-matched CHILD docs
            plan = LBool(musts=[lroot], filters=[node._rel_filters["child"]])
            pre = join_prepass(plan, ji, ("cnt",), ctx, self_slots=False)
        node._agg_pre = pre
    params[f"{prefix}_gmatch"] = pre["cnt"]
    subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}")
                 for i, s in enumerate(node.subs))
    if kind == "children":
        params[f"{prefix}_pslot"] = ji.pslot(seg)
        cf = prepare(node._rel_filters["child"], seg, ctx, params)
        return ("children_agg", prefix, cf, subs)
    scalar_i32(params, f"{prefix}_base", ji.seg_base(seg))
    pf = prepare(node._rel_filters["parent"], seg, ctx, params)
    return ("parent_agg", prefix, pf, subs)


def bind_composite_sources(node: AggNode, seg: Segment, ctx: ShardContext):
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
    ids, min_b, nb = date_bucket_ids(seg, field, int(interval), 0,
                                     cal or None)
    return ids, nb, ("date", min_b, interval, cal)


def composite_space(seg: Segment, infos: list):
    """(plane or None, `ComboSpace`) of a composite's resolved sources: one
    source counts into its own value space and needs no plane (its ordinal
    is on the device already), several count into the combinations that
    occur (`combo_plane`)."""
    desc = tuple(i[6] for i in infos)
    if len(infos) > 1:
        key = (tuple(i[1] for i in infos), "composite",
               tuple((i[0], i[4], i[5], i[6]) for i in infos))

        def build():
            got = [_composite_source_ordinals(seg, i) for i in infos]
            return combo_space([(o, n) for o, n, _s in got],
                               [s for _o, _n, s in got], desc, seg.ndocs)
        return combo_plane(seg, key, build)
    stype, field, n, min_b, interval, cal, _desc = infos[0]
    if stype == "date":     # (the plane is cached: `date_bucket_plane`)
        _plane, min_b, n, _starts = date_bucket_plane(
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
    infos, multi = bind_composite_sources(node, seg, ctx)
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
                date_bucket_plane(seg, field, int(interval), 0, cal or None)
        single = (stype, field, min_b, interval, desc)
    subs = tuple(prepare_agg(s, seg, ctx, params, f"{prefix}_{i}", nest_stack)
                 for i, s in enumerate(node.subs))
    return ("composite", prefix, single, len(space), subs)


def resolve_agg_field(node: AggNode, ctx: ShardContext) -> str:
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


def emit_agg(spec, seg_arrays: dict, params: dict, match, scores=None,
             span=None):
    """-> nested dict of device arrays (this segment's partial). `span` is
    the launch's row span (`compiler.row_span`: two traced int32 scalars,
    rows of `seg_arrays` outside which `match` is 0; None the whole
    segment), handed to the group-bys that reduce `match` over this
    segment's rows (`ops.aggs.bucket_counts`) and through the containers
    that only narrow it; it is whole again where the match or the rows are
    not the query's (`global`, the nested and join kinds, a keyword column
    laid out by value) and under the two containers `programs.agg_cost`
    does not walk (`diversified_sampler`, `ip_range`)."""
    if spec[0] in _TERMS_STAGE_KINDS:
        import jax
        with jax.named_scope(TERMS_SCOPE):
            return _emit_agg(spec, seg_arrays, params, match, scores, span)
    return _emit_agg(spec, seg_arrays, params, match, scores, span)


def _emit_agg(spec, seg_arrays: dict, params: dict, match, scores, span):  # noqa: C901
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
        out = {"counts": agg_ops.terms_counts(kw, match, nvocab_pad, span),
               "fg_total": jnp.sum(match)}
        for i, sub in enumerate(subs):
            if sub and sub[0] == "stats":
                _, sprefix, sfield, col_exists, sumsq = sub
                if col_exists:
                    col = seg_arrays["numeric"][sfield]
                    out[f"sub{i}"] = agg_ops.terms_sub_metric(
                        kw, match, col["f32"], col["present"], nvocab_pad,
                        params[f"{sprefix}_sinv"], sumsq, span)
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
            res = emit_agg(sub, seg_arrays, params, sel, scores, span)
            if res:
                out[f"sub{i}"] = res
        return out

    if kind == "geo_grid":
        _, prefix, gkind, field, precision, nb, subs = spec
        ords = params[f"{prefix}_gords"][:ndocs_pad]
        w = match * (ords >= 0).astype(jnp.float32)
        b = jnp.where(w > 0, ords, nb)
        out = {"counts": agg_ops.bucket_counts(b, w, nb, span)}
        for i, sub in enumerate(subs):
            out.update(_emit_bucketed_sub(jnp, sub, i, b, nb, seg_arrays,
                                          match, params, span))
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
        out = {"counts": agg_ops.terms_counts(kw, match, nb, span)}
        for i, sub in enumerate(subs):
            if sub and sub[0] == "stats":
                _, sprefix, sfield, col_exists, sumsq = sub
                if col_exists:
                    col = seg_arrays["numeric"][sfield]
                    out[f"sub{i}"] = agg_ops.terms_sub_metric(
                        kw, match, col["f32"], col["present"], nb,
                        params[f"{sprefix}_sinv"], sumsq, span)
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
        out = {"counts": agg_ops.bucket_counts(b, w, total, span)}
        for i, sub in enumerate(subs):
            out.update(_emit_bucketed_sub(jnp, sub, i, b, total, seg_arrays,
                                          match * w, params, span))
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
        out = {"counts": agg_ops.terms_counts(kw, match, nvocab_pad, span)}
        for i, sub in enumerate(subs):
            if sub and sub[0] == "stats":
                _, sprefix, sfield, col_exists, sumsq = sub
                if col_exists:
                    col = seg_arrays["numeric"][sfield]
                    out[f"sub{i}"] = agg_ops.terms_sub_metric(
                        kw, match, col["f32"], col["present"], nvocab_pad,
                        params[f"{sprefix}_sinv"], sumsq, span)
        return out

    if kind == "hist":
        _, prefix, field, interval, offset, min_b, nb, subs = spec
        col = seg_arrays["numeric"][field]
        w = match * jnp.where(col["present"], 1.0, 0.0)
        b = jnp.floor((col["f32"] - offset) / interval).astype(jnp.int32) - min_b
        b = jnp.where((b >= 0) & (b < nb) & (w > 0), b, nb)
        out = {"counts": agg_ops.bucket_counts(b, w, nb, span)}
        for i, sub in enumerate(subs):
            out.update(_emit_bucketed_sub(jnp, sub, i, b, nb, seg_arrays,
                                          match, params, span))
        return out

    if kind == "date_hist":
        (_, prefix, field, interval_ms, offset_ms, calendar, min_b, nb, subs,
         form) = spec
        counts, b = _date_bucket_counts(jnp, params, prefix, match, nb, form,
                                        span=span)
        out = {"counts": counts}
        for i, sub in enumerate(subs):
            out.update(_emit_bucketed_sub(jnp, sub, i, b, nb, seg_arrays,
                                          match, params, span))
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
                res = emit_agg(sub, seg_arrays, params, bucket_match, scores,
                               span)
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
                res = emit_agg(sub, seg_arrays, params, bucket_match, scores,
                               span)
                if res:
                    out[f"r{ri}_sub{i}"] = res
        return out

    if kind == "filter":
        _, prefix, fspec, subs = spec
        fmask = emit(fspec, seg_arrays, params).matched
        bucket_match = match * fmask.astype(jnp.float32)
        out = {"count": jnp.sum(bucket_match)}
        for i, sub in enumerate(subs):
            res = emit_agg(sub, seg_arrays, params, bucket_match, scores,
                           span)
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
                res = emit_agg(sub, seg_arrays, params, bucket_match, scores,
                               span)
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
            res = emit_agg(sub, seg_arrays, params, bucket_match, scores,
                           span)
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
            params[f"{prefix}_hashes"], HLL_LOG2M, span)
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
        out = {"counts": agg_ops.ord_counts(ords, match, nord_pad, span)}
        b = jnp.where(ords >= 0, ords, nord_pad)
        for i, sub in enumerate(subs):
            out.update(_emit_bucketed_sub(jnp, sub, i, b, nord_pad,
                                          seg_arrays, match, params, span))
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
                res = emit_agg(sub, seg_arrays, params, sel, scores, span)
                if res:
                    out[f"c{idx}_sub{i}"] = res
            idx += 1
        for ai, (ka, ma) in enumerate(masks):
            for bi in range(ai + 1, len(masks)):
                kb, mb = masks[bi]
                sel = match * (ma & mb).astype(jnp.float32)
                out[f"c{idx}"] = jnp.sum(sel)
                for i, sub in enumerate(subs):
                    res = emit_agg(sub, seg_arrays, params, sel, scores,
                                   span)
                    if res:
                        out[f"c{idx}_sub{i}"] = res
                idx += 1
        return out

    if kind == "auto_date_hist":
        (_, prefix, field, unit, target, min_b, nb, window, subs,
         form) = spec
        first = params[f"{prefix}_dfirst"]
        counts, b = _date_bucket_counts(jnp, params, prefix, match, nb, form,
                                        first, window, span)
        out = {"counts": counts, "first": first}
        for i, sub in enumerate(subs):
            out.update(_emit_bucketed_sub(jnp, sub, i, b, window, seg_arrays,
                                          match, params, span))
        return out

    if kind in ("scripted", "sig_text"):
        # host-resolved: the partial needs the dense match mask
        return {"match_mask": match, "score_vec": (scores if scores is not None
                                                   else jnp.zeros_like(match))}

    raise ValueError(f"cannot emit aggregation spec [{kind}]")


def _date_bucket_counts(jnp, params: dict, prefix: str, match, nb: int,
                        form: str, first=None, window: Optional[int] = None,
                        span=None):
    """A date histogram's counts over its resident bucket plane: ->
    (counts i32[nb], per-row bucket ids with `nb` where the row does not
    count, for the sub-aggregations). A row counts where it matches and
    has a value; `form` "runs" reads the counts at the runs' boundaries
    (`ops.aggs.run_counts`), "scatter" takes `ops.aggs.bucket_counts`,
    whose bucket count chooses between its dense form and a scatter-add
    and whose block loop `span` bounds (`emit_agg`).
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
    return agg_ops.bucket_counts(b, held, nb, span), b


def _emit_bucketed_sub(jnp, sub, i: int, bucket_ids, nb: int, seg_arrays, match,
                       params: dict, span=None):
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
        bucket_ids, col["f32"], w, nb, params[f"{sprefix}_sinv"], sumsq,
        span)}
