"""Aggregations: DSL parsing + cross-segment/shard merge + response shaping.
Analog of reference `search/aggregations/` (AggregatorFactories parse tree,
InternalAggregation#reduce, and the response XContent shapes).

Device emission lives in `agg_compiler.py` (same jitted program as scoring);
this module is host-only: it defines the agg tree, merges per-segment
partials (the analog of InternalAggregation.reduce), and renders the
OpenSearch-shaped response JSON.

Design notes vs the reference:
- terms aggs are exact per shard (full ordinal bincount on device — no
  shard_size truncation error; doc_count_error_upper_bound is honestly 0,
  sum_other_doc_count the exact rest). A `terms` / `multi_terms` partial
  carries its counts as ARRAYS by ordinal (`OrdinalBuckets`: the segment's
  vocabulary, or the combinations that occur in it, with the sub-metrics'
  columns beside them); segments merge by vocabulary, array to array, and
  a bucket becomes a Python record only when `finalize` knows the `size`
  buckets the response returns. A `composite` partial is records already,
  but of one page: the first `size` non-empty combinations after `after`
  in key order, which is all a merged page can draw from a segment.
- cardinality is device-side HyperLogLog (log2m=14) over value hashes —
  mergeable across segments and shards like the reference's HLL++. A
  keyword cardinality that ONE segment answers is its exact count of
  matched ordinals (`distinct`); merged with anything it is the sketch's
  estimate (standard error 1.04 / sqrt(2^14) = 0.81%).
- percentiles use a mergeable 4096-bin histogram sketch between index-wide
  column bounds instead of TDigest.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field as dc_field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..utils.metrics import METRICS, CounterGroup
from .planes import (AUTO_ROUNDINGS, auto_inner_for, auto_unit_ids,
                     auto_unit_start_ms, calendar_bucket_ids,
                     calendar_bucket_start_ms)

# what the aggregations of the launches cost, counted at each launch from
# the static spec (`programs._count_launch`): `scatter.updates` the rows
# handed to every scatter (a bucket count by `ops.aggs.bucket_counts`, each
# scatter of a bucketed sub-metric: count, minimum, maximum and a limb a
# sum), which is what `ops.aggs.count_form` names "scatter" (and, for all
# of a sub-metric but its count, "product"); `blocked.rows` the rows a
# form that replaces a scatter reads, rows x passes over them
# (`ops.aggs.run_counts`; the dense form: one pass a bucket count, one
# for all of a sub-metric's accumulators; the product form: one pass a
# count; of the dense and the product form the rows of the blocks the loop
# visits under the launch's row span, `ops.aggs.span_rows`); `span.rows` the
# rows of that span (`compiler.row_span`: what a `range` over a column in
# row order leaves of the segment) and `span.segment_rows` the segment's, of
# every launch that carries aggregations; `bucketed_sub.launches` /
# `.buckets` the
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
                                           "span.rows": 0,
                                           "span.segment_rows": 0,
                                           "bucketed_sub.launches": 0,
                                           "bucketed_sub.buckets": 0,
                                           "auto_date.requests": 0,
                                           "auto_date.refine_launches": 0,
                                           "terms.ordinals": 0,
                                           "terms.records": 0,
                                           "terms.gathered_rows": 0,
                                           "composite.combinations": 0})

BUCKET_KINDS = {"terms", "histogram", "date_histogram", "range", "date_range",
                "geo_distance",
                "filter", "filters", "global", "missing", "significant_terms",
                "sampler", "geohash_grid", "geotile_grid", "nested",
                "reverse_nested", "children", "parent", "composite",
                "ip_range", "rare_terms", "multi_terms", "adjacency_matrix",
                "auto_date_histogram", "significant_text",
                "diversified_sampler"}
METRIC_KINDS = {"min", "max", "sum", "avg", "stats", "extended_stats",
                "value_count", "cardinality", "percentiles",
                "percentile_ranks", "top_hits",
                "matrix_stats", "weighted_avg", "median_absolute_deviation",
                "geo_bounds", "geo_centroid", "scripted_metric"}
PIPELINE_KINDS = {"avg_bucket", "sum_bucket", "min_bucket", "max_bucket",
                  "stats_bucket", "cumulative_sum", "derivative", "bucket_script",
                  "bucket_selector", "moving_avg", "moving_fn", "serial_diff",
                  "percentiles_bucket", "bucket_sort"}


@dataclass
class AggNode:
    name: str
    kind: str
    body: dict
    subs: List["AggNode"] = dc_field(default_factory=list)
    pipelines: List["AggNode"] = dc_field(default_factory=list)
    # pipeline nodes whose buckets_path targets a refinement-resolved sub-agg
    # are deferred: the coordinator applies them AFTER bucket refinement
    # (executor._mark_deferred_pipelines / _apply_deferred_tree)
    deferred: bool = False


_STAT_ADD = ("count", "sum", "sumsq")


class OrdinalBuckets:
    """A `terms` or `multi_terms` partial's buckets as arrays: `counts`
    int[n] by ordinal, `keys` the sequence that names an ordinal's key
    (a segment's sorted vocabulary, a `planes.ComboSpace`: ordinal order
    is key order), `subs` {sub-aggregation name: {"count", "sum", "min",
    "max", "sumsq": float[n]}} for the metric sub-aggregations the launch
    carried. `items()` spells the non-empty buckets as the records the
    other bucket kinds' partials hold, for a consumer that wants them all
    (counted: `aggs.terms.records`)."""

    __slots__ = ("keys", "counts", "subs")

    def __init__(self, keys, counts: np.ndarray, subs: Optional[dict] = None):
        self.keys, self.counts, self.subs = keys, counts, subs or {}

    def sub_partials(self, j: int) -> dict:
        return {name: {k: float(v[j]) for k, v in cols.items()}
                for name, cols in self.subs.items()}

    def items(self):
        held = np.flatnonzero(self.counts > 0)
        AGG_STATS.inc("terms.records", len(held))
        out = []
        for j in held.tolist():
            rec = {"doc_count": int(self.counts[j])}
            subs = self.sub_partials(j)
            if subs:
                rec["subs"] = subs
            out.append((self.keys[j], rec))
        return out

    @staticmethod
    def merged(parts: List["OrdinalBuckets"]) -> "OrdinalBuckets":
        """Segments merged by vocabulary: the union of the keys that hold
        a document somewhere, in key order, their counts and sub-metric
        columns added (minimum and maximum taken) array to array."""
        if len(parts) == 1:
            return parts[0]
        slot: Dict[Any, int] = {}
        at = []
        for p in parts:
            held = np.flatnonzero(p.counts > 0)
            at.append((held, np.fromiter(
                (slot.setdefault(p.keys[j], len(slot))
                 for j in held.tolist()), np.int64, len(held))))
        keys = sorted(slot)
        rank = np.empty(len(keys), np.int64)
        rank[[slot[k] for k in keys]] = np.arange(len(keys))
        counts = np.zeros(len(keys), np.int64)
        names = sorted({n for p in parts for n in p.subs})
        subs = {n: {"count": np.zeros(len(keys)), "sum": np.zeros(len(keys)),
                    "sumsq": np.zeros(len(keys)),
                    "min": np.full(len(keys), np.inf),
                    "max": np.full(len(keys), -np.inf)} for n in names}
        for p, (held, to) in zip(parts, at):
            to = rank[to]           # a key comes once a part: plain stores
            counts[to] += np.asarray(p.counts)[held].astype(np.int64)
            for n, cols in p.subs.items():
                has = np.asarray(cols["count"])[held] > 0
                for k in _STAT_ADD:
                    subs[n][k][to] += np.asarray(cols[k])[held]
                t = to[has]
                subs[n]["min"][t] = np.minimum(
                    subs[n]["min"][t], np.asarray(cols["min"])[held][has])
                subs[n]["max"][t] = np.maximum(
                    subs[n]["max"][t], np.asarray(cols["max"])[held][has])
        return OrdinalBuckets(keys, counts, subs)


def parse_aggs(aggs: Optional[dict]) -> List[AggNode]:
    out: List[AggNode] = []
    if not aggs:
        return out
    for name, spec in aggs.items():
        sub_specs = spec.get("aggs", spec.get("aggregations"))
        kinds = [k for k in spec if k not in ("aggs", "aggregations", "meta")]
        if len(kinds) != 1:
            raise ValueError(f"aggregation [{name}] must define exactly one type")
        kind = kinds[0]
        if kind not in BUCKET_KINDS | METRIC_KINDS | PIPELINE_KINDS:
            raise ValueError(f"unknown aggregation type [{kind}]")
        node = AggNode(name, kind, spec[kind])
        children = parse_aggs(sub_specs)
        node.subs = [c for c in children if c.kind not in PIPELINE_KINDS]
        node.pipelines = [c for c in children if c.kind in PIPELINE_KINDS]
        if kind in METRIC_KINDS and node.subs:
            raise ValueError(f"metric aggregation [{name}] cannot have sub-aggregations")
        out.append(node)
    return out


# ---------------- merge (reduce) ----------------

def merge_partials(node: AggNode, partials: List[dict]) -> dict:
    """Merge per-segment/per-shard partials for one agg node (reference:
    InternalAggregation#reduce). Each partial is a host dict produced by the
    compiled program's device run + segment context."""
    parts = [p for p in partials if p is not None]
    if not parts:
        return {}
    kind = node.kind
    if kind in ("terms", "geohash_grid", "geotile_grid", "rare_terms",
                "multi_terms"):
        if all(isinstance(p["buckets"], OrdinalBuckets) for p in parts):
            return {"buckets": OrdinalBuckets.merged(
                [p["buckets"] for p in parts])}
        return {"buckets": _acc_buckets(node, parts)}
    if kind in ("histogram", "date_histogram"):
        acc = {}
        for p in parts:
            for b, rec in p["buckets"].items():
                slot = acc.setdefault(b, {"doc_count": 0, "subs": []})
                slot["doc_count"] += rec["doc_count"]
                slot["subs"].append(rec.get("subs"))
        for b, slot in acc.items():
            slot["subs"] = _merge_sub_metrics(node.subs, slot["subs"])
        return {"buckets": acc, "interval": parts[0]["interval"],
                "offset": parts[0].get("offset", 0.0), "keyed_fmt": parts[0].get("keyed_fmt"),
                "calendar": parts[0].get("calendar")}
    if kind in ("range", "date_range", "geo_distance", "filters", "ip_range",
                "adjacency_matrix"):
        acc = {}
        for p in parts:
            for key, rec in p["buckets"].items():
                slot = acc.setdefault(key, {"doc_count": 0, "subs": [], "meta": rec.get("meta")})
                slot["doc_count"] += rec["doc_count"]
                slot["subs"].append(rec.get("subs"))
        for key, slot in acc.items():
            slot["subs"] = _merge_subtrees(node.subs, slot["subs"])
        return {"buckets": acc}
    if kind in ("filter", "global", "missing", "sampler", "nested",
                "reverse_nested", "children", "parent",
                "diversified_sampler"):
        total = sum(p["doc_count"] for p in parts)
        subs = _merge_subtrees(node.subs, [p.get("subs") for p in parts])
        return {"doc_count": total, "subs": subs}
    if kind in ("significant_terms", "significant_text"):
        bg: Dict[Any, int] = {}
        for p in parts:
            for key, c in p["bg"].items():
                bg[key] = bg.get(key, 0) + c
        return {"buckets": _acc_buckets(node, parts), "bg": bg,
                "fg_total": sum(p["fg_total"] for p in parts),
                "bg_total": sum(p["bg_total"] for p in parts)}
    if kind == "weighted_avg":
        return {"vwsum": sum(p["vwsum"] for p in parts),
                "wsum": sum(p["wsum"] for p in parts),
                "count": sum(p["count"] for p in parts)}
    if kind == "median_absolute_deviation":
        hist = parts[0]["hist"].copy()
        for p in parts[1:]:
            hist += p["hist"]
        return {"hist": hist}
    if kind == "geo_bounds":
        live = [p for p in parts if p["count"] > 0]
        if not live:
            return {"count": 0}
        return {"count": sum(p["count"] for p in live),
                "top": max(p["top"] for p in live),
                "bottom": min(p["bottom"] for p in live),
                "left": min(p["left"] for p in live),
                "right": max(p["right"] for p in live)}
    if kind == "geo_centroid":
        return {"count": sum(p["count"] for p in parts),
                "slat": sum(p.get("slat", 0.0) for p in parts),
                "slon": sum(p.get("slon", 0.0) for p in parts)}
    if kind == "scripted_metric":
        return {"states": [s for p in parts for s in p["states"]]}
    if kind == "auto_date_histogram":
        # shards (and segments) may have rounded at different units: bring
        # every bucket to the coarsest before accumulating (reference
        # InternalAutoDateHistogram#reduce)
        unit = max(p["unit"] for p in parts)
        return {"buckets": _auto_accumulate(
            node, [(p["buckets"], p["unit"]) for p in parts], unit),
            "unit": unit}
    if kind == "composite":
        return {"buckets": _acc_buckets(node, parts)}
    if kind == "matrix_stats":
        count = sum(p["count"] for p in parts)
        # the shift is index-wide and identical for every non-empty partial;
        # empty (missing-field) partials carry zeros and must not win
        shift = next((p["shift"] for p in parts
                      if p["count"] > 0 and p.get("shift") is not None), None)
        out = {"count": count, "fields": parts[0]["fields"], "shift": shift}
        for key in ("s1", "s2", "s3", "s4"):
            out[key] = np.sum([p[key] for p in parts], axis=0)
        out["xy"] = np.sum([p["xy"] for p in parts], axis=0)
        return out
    if kind in ("min", "max", "sum", "avg", "stats", "extended_stats", "value_count"):
        return _merge_stats(parts)
    if kind == "cardinality":
        if len(parts) == 1:     # one segment's `distinct`, where it has one
            return parts[0]
        regs = parts[0]["registers"]
        for p in parts[1:]:
            regs = np.maximum(regs, p["registers"])
        return {"registers": regs}
    if kind in ("percentiles", "percentile_ranks"):
        # DDSketch bins are global constants, so histogram addition IS the
        # cross-segment/shard reduce; ranks carries the queried values
        # where percentiles carries the queried percents
        hist = parts[0]["hist"].copy()
        for p in parts[1:]:
            hist += p["hist"]
        key = "percents" if kind == "percentiles" else "values"
        return {"hist": hist, key: parts[0][key]}
    if kind == "top_hits":
        rows = [r for p in parts for r in p["hits"]]
        rows.sort(key=lambda r: -r["_score"] if r["_score"] is not None else 0)
        return {"hits": rows[: parts[0]["size"]], "total": sum(p["total"] for p in parts)}
    raise ValueError(f"cannot merge aggregation kind [{kind}]")


# empty buckets a histogram's response is filled with at most (the
# reference's `search.max_buckets` default is 65,535)
_MAX_FILLED_BUCKETS = 65_535


def _with_empty_buckets(held: dict, calendar: Optional[str]) -> dict:
    """A histogram's merged buckets with the empty ones between the least
    and the greatest key: keys are bucket numbers, or, under a calendar
    interval, the buckets' starts in epoch ms. Left as it is where that
    would pass `_MAX_FILLED_BUCKETS`."""
    empty = {"doc_count": 0, "subs": {}}
    if calendar is None:
        if max(held) - min(held) >= _MAX_FILLED_BUCKETS:
            return held
        return {b: held.get(b, empty)
                for b in range(min(held), max(held) + 1)}
    lo, hi = (int(x) for x in calendar_bucket_ids(
        [min(held), max(held)], calendar))
    if hi - lo >= _MAX_FILLED_BUCKETS:
        return held
    out = dict(held)
    for b in range(lo, hi + 1):
        out.setdefault(calendar_bucket_start_ms(b, calendar), empty)
    return out


def _auto_accumulate(node: AggNode, parts: List[Tuple[dict, int]],
                     unit: int) -> Dict[Any, dict]:
    """auto_date_histogram buckets (keyed by their start in epoch ms under
    each part's own rounding) brought to rounding `unit` and accumulated:
    `parts` is [(buckets, their unit)]."""
    acc: Dict[Any, dict] = {}
    for buckets, from_unit in parts:
        for key, rec in buckets.items():
            if from_unit != unit:
                key = auto_unit_start_ms(
                    int(auto_unit_ids(key, unit)), unit)
            slot = acc.setdefault(key, {"doc_count": 0, "subs": []})
            slot["doc_count"] += rec["doc_count"]
            slot["subs"].append(rec.get("subs"))
    for slot in acc.values():
        slot["subs"] = _merge_sub_metrics(node.subs, slot["subs"])
    return acc


def _acc_buckets(node: AggNode, parts: List[dict]) -> Dict[Any, dict]:
    """Accumulate keyed buckets + their sub-metric partials across segments
    (shared by terms / significant_terms / geo grids)."""
    acc: Dict[Any, dict] = {}
    for p in parts:
        for key, rec in p["buckets"].items():
            slot = acc.setdefault(key, {"doc_count": 0, "subs": []})
            slot["doc_count"] += rec["doc_count"]
            slot["subs"].append(rec.get("subs"))
    for key, slot in acc.items():
        slot["subs"] = _merge_sub_metrics(node.subs, slot["subs"])
    return acc


def _finalize_ordinal(node: AggNode, held: OrdinalBuckets,
                      pipelines: bool) -> dict:
    """`terms` / `multi_terms` from counts by ordinal: the `size` buckets
    the response returns are chosen over the arrays (ordinal order is key
    order, so a tie in the count breaks by key as the reference's does),
    and only they become records; `sum_other_doc_count` is the exact
    rest."""
    terms = node.kind == "terms"
    size = int(node.body.get("size", 10))
    okey, odir = "_count", "desc"
    if terms:
        order = node.body.get("order", {"_count": "desc"})
        if isinstance(order, dict):
            (okey, odir), = order.items()
    counts = np.asarray(held.counts).astype(np.int64)
    least = max(int(node.body.get("min_doc_count", 1)), 1) if terms else 1
    kept = np.flatnonzero(counts >= least)
    if okey == "_key":
        kept = kept[::-1] if odir == "desc" else kept
    else:
        c = counts[kept]
        kept = kept[np.lexsort((kept, -c if odir == "desc" else c))]
    page = kept[:size].tolist()
    AGG_STATS.inc("terms.records", len(page))
    buckets = []
    for j in page:
        key = held.keys[j]
        b = ({"key": key} if terms else
             {"key": list(key), "key_as_string": "|".join(str(x)
                                                          for x in key)})
        b["doc_count"] = int(counts[j])
        subs = held.sub_partials(j)
        for sub in node.subs:
            part = subs.get(sub.name)
            b[sub.name] = finalize(
                sub, merge_partials(sub, [part]) if part else {}, pipelines)
        buckets.append(b)
    rest = int(counts[kept].sum() - sum(b["doc_count"] for b in buckets))
    result = ({"doc_count_error_upper_bound": 0, "sum_other_doc_count": rest,
               "buckets": buckets} if terms else
              {"buckets": buckets, "sum_other_doc_count": rest})
    _apply_bucket_pipelines(node, result, "all" if pipelines else "early")
    return result


def _merge_stats(parts: List[dict]) -> dict:
    count = sum(p["count"] for p in parts)
    s = sum(p["sum"] for p in parts)
    ssq = sum(p.get("sumsq", 0.0) for p in parts)
    mn = min((p["min"] for p in parts if p["count"] > 0), default=float("inf"))
    mx = max((p["max"] for p in parts if p["count"] > 0), default=float("-inf"))
    return {"count": count, "sum": s, "min": mn, "max": mx, "sumsq": ssq}


def _merge_sub_metrics(subs: List[AggNode], partial_lists: List[Optional[dict]]) -> dict:
    out = {}
    for sub in subs:
        parts = [pl.get(sub.name) for pl in partial_lists if pl]
        out[sub.name] = merge_partials(sub, parts)
    return out


def _merge_subtrees(subs: List[AggNode], partial_lists: List[Optional[dict]]) -> dict:
    return _merge_sub_metrics(subs, partial_lists)


# ---------------- finalize (response shaping) ----------------

def finalize(node: AggNode, merged: dict, pipelines: bool = True) -> dict:
    """`pipelines=True` applies every pipeline agg; `pipelines=False` applies
    only non-deferred ones — the coordinator applies deferred pipelines after
    bucket refinement (executor._apply_deferred_tree), so a buckets_path
    targeting a refined sub-agg sees post-refinement values."""
    kind = node.kind
    if not merged:
        return _empty_result(node)
    if kind in ("terms", "multi_terms") and isinstance(
            merged["buckets"], OrdinalBuckets):
        return _finalize_ordinal(node, merged["buckets"], pipelines)
    if kind == "terms":
        size = int(node.body.get("size", 10))
        order = node.body.get("order", {"_count": "desc"})
        (okey, odir), = order.items() if isinstance(order, dict) else [("_count", "desc")]
        items = [(k, v) for k, v in merged["buckets"].items() if v["doc_count"] > 0]
        min_doc_count = int(node.body.get("min_doc_count", 1))
        items = [(k, v) for k, v in items if v["doc_count"] >= min_doc_count]
        if okey == "_key":
            items.sort(key=lambda kv: kv[0], reverse=(odir == "desc"))
        else:
            items.sort(key=lambda kv: (-kv[1]["doc_count"], kv[0])
                       if odir == "desc" else (kv[1]["doc_count"], kv[0]))
        total_count = sum(v["doc_count"] for _, v in items)
        buckets = []
        for k, v in items[:size]:
            b = {"key": k, "doc_count": int(v["doc_count"])}
            for sub in node.subs:
                b[sub.name] = finalize(sub, v["subs"].get(sub.name, {}), pipelines)
            _apply_pipelines(node, buckets_ref=None)
            buckets.append(b)
        shown = sum(b["doc_count"] for b in buckets)
        result = {"doc_count_error_upper_bound": 0,
                  "sum_other_doc_count": int(total_count - shown),
                  "buckets": buckets}
        _apply_bucket_pipelines(node, result, "all" if pipelines else "early")
        return result
    if kind in ("histogram", "date_histogram"):
        buckets = []
        held = merged["buckets"]
        min_doc_count = int(node.body.get("min_doc_count", 0))
        if min_doc_count == 0 and held:
            # `min_doc_count` 0 (the default): the empty buckets between
            # the least and the greatest key are part of the answer
            # (reference InternalHistogram#addEmptyBuckets)
            held = _with_empty_buckets(held, merged.get("calendar"))
        for b in sorted(held):
            rec = held[b]
            if rec["doc_count"] <= 0 and min_doc_count > 0:
                continue
            key = b * merged["interval"] + merged.get("offset", 0.0)
            entry = {"key": key, "doc_count": int(rec["doc_count"])}
            if kind == "date_histogram":
                entry["key"] = int(key)
                entry["key_as_string"] = _format_epoch_ms(int(key))
            for sub in node.subs:
                entry[sub.name] = finalize(sub, rec["subs"].get(sub.name, {}), pipelines)
            buckets.append(entry)
        result = {"buckets": buckets}
        _apply_bucket_pipelines(node, result, "all" if pipelines else "early")
        return result
    if kind in ("range", "date_range", "geo_distance"):
        buckets = []
        for key in merged["buckets"]:
            rec = merged["buckets"][key]
            entry = {"key": key, "doc_count": int(rec["doc_count"])}
            if rec.get("meta"):
                entry.update(rec["meta"])
            for sub in node.subs:
                entry[sub.name] = finalize(sub, rec["subs"].get(sub.name, {}), pipelines)
            buckets.append(entry)
        result = {"buckets": buckets}
        _apply_bucket_pipelines(node, result, "all" if pipelines else "early")
        return result
    if kind == "filters":
        buckets = {}
        for key in merged["buckets"]:
            rec = merged["buckets"][key]
            entry = {"doc_count": int(rec["doc_count"])}
            for sub in node.subs:
                entry[sub.name] = finalize(sub, rec["subs"].get(sub.name, {}), pipelines)
            buckets[key] = entry
        return {"buckets": buckets}
    if kind in ("filter", "global", "missing", "sampler", "nested",
                "reverse_nested", "children", "parent",
                "diversified_sampler"):
        out = {"doc_count": int(merged["doc_count"])}
        for sub in node.subs:
            out[sub.name] = finalize(sub, merged["subs"].get(sub.name, {}), pipelines)
        return out
    if kind == "significant_terms":
        return _finalize_significant(node, merged, pipelines)
    if kind in ("geohash_grid", "geotile_grid"):
        size = int(node.body.get("size", 10000))
        items = sorted(((k, v) for k, v in merged["buckets"].items()
                        if v["doc_count"] > 0),
                       key=lambda kv: (-kv[1]["doc_count"], kv[0]))
        buckets = []
        for k, v in items[:size]:
            b = {"key": k, "doc_count": int(v["doc_count"])}
            for sub in node.subs:
                b[sub.name] = finalize(sub, v["subs"].get(sub.name, {}), pipelines)
            buckets.append(b)
        result = {"buckets": buckets}
        _apply_bucket_pipelines(node, result, "all" if pipelines else "early")
        return result
    if kind == "matrix_stats":
        return _finalize_matrix_stats(merged)
    if kind == "composite":
        return _finalize_composite(node, merged, pipelines)
    if kind == "value_count":
        return {"value": int(merged["count"])}
    if kind == "min":
        return {"value": None if merged["count"] == 0 else merged["min"]}
    if kind == "max":
        return {"value": None if merged["count"] == 0 else merged["max"]}
    if kind == "sum":
        return {"value": merged["sum"]}
    if kind == "avg":
        return {"value": None if merged["count"] == 0 else merged["sum"] / merged["count"]}
    if kind == "stats":
        c = merged["count"]
        return {"count": int(c), "min": None if c == 0 else merged["min"],
                "max": None if c == 0 else merged["max"], "sum": merged["sum"],
                "avg": None if c == 0 else merged["sum"] / c}
    if kind == "extended_stats":
        c = merged["count"]
        if c == 0:
            return {"count": 0, "min": None, "max": None, "sum": 0.0, "avg": None,
                    "sum_of_squares": 0.0, "variance": None, "std_deviation": None}
        var = max(merged["sumsq"] / c - (merged["sum"] / c) ** 2, 0.0)
        return {"count": int(c), "min": merged["min"], "max": merged["max"],
                "sum": merged["sum"], "avg": merged["sum"] / c,
                "sum_of_squares": merged["sumsq"], "variance": var,
                "std_deviation": math.sqrt(var)}
    if kind == "cardinality":
        if merged.get("distinct") is not None:
            return {"value": int(merged["distinct"])}
        return {"value": int(round(_hll_estimate(merged["registers"])))}
    if kind == "percentiles":
        return {"values": _hist_percentiles(merged)}
    if kind == "percentile_ranks":
        return {"values": _hist_percentile_ranks(merged)}
    if kind == "top_hits":
        return {"hits": {"total": {"value": int(merged["total"]), "relation": "eq"},
                         "max_score": merged["hits"][0]["_score"] if merged["hits"] else None,
                         "hits": merged["hits"]}}
    if kind == "weighted_avg":
        w = merged.get("wsum", 0.0)
        return {"value": None if not w else merged["vwsum"] / w}
    if kind == "median_absolute_deviation":
        return {"value": _mad_from_hist(merged["hist"])}
    if kind == "geo_bounds":
        if not merged or merged.get("count", 0) == 0:
            return {}
        return {"bounds": {
            "top_left": {"lat": float(merged["top"]),
                         "lon": float(merged["left"])},
            "bottom_right": {"lat": float(merged["bottom"]),
                             "lon": float(merged["right"])}}}
    if kind == "geo_centroid":
        c = merged.get("count", 0)
        if not c:
            return {"count": 0}
        return {"location": {"lat": float(merged["slat"] / c),
                             "lon": float(merged["slon"] / c)},
                "count": int(c)}
    if kind == "scripted_metric":
        from ..script.painless_lite import execute
        body = node.body
        states = merged.get("states", [])
        reduce_src = body.get("reduce_script")
        if reduce_src:
            src, prm = _script_src(reduce_src)
            val = execute(src, {"states": states, "params": prm})
        else:
            val = states
        return {"value": val}
    if kind == "ip_range":
        buckets = []
        for key in merged["buckets"]:
            rec = merged["buckets"][key]
            entry = {"key": key, "doc_count": int(rec["doc_count"])}
            if rec.get("meta"):
                entry.update(rec["meta"])
            for sub in node.subs:
                entry[sub.name] = finalize(sub, rec["subs"].get(sub.name, {}),
                                           pipelines)
            buckets.append(entry)
        result = {"buckets": buckets}
        _apply_bucket_pipelines(node, result, "all" if pipelines else "early")
        return result
    if kind == "rare_terms":
        max_dc = int(node.body.get("max_doc_count", 1))
        items = sorted(((k, v) for k, v in merged["buckets"].items()
                        if 0 < v["doc_count"] <= max_dc),
                       key=lambda kv: (kv[1]["doc_count"], kv[0]))
        buckets = []
        for k, v in items:
            b = {"key": k, "doc_count": int(v["doc_count"])}
            for sub in node.subs:
                b[sub.name] = finalize(sub, v["subs"].get(sub.name, {}),
                                       pipelines)
            buckets.append(b)
        result = {"buckets": buckets}
        _apply_bucket_pipelines(node, result, "all" if pipelines else "early")
        return result
    if kind == "multi_terms":
        size = int(node.body.get("size", 10))
        items = sorted(((k, v) for k, v in merged["buckets"].items()
                        if v["doc_count"] > 0),
                       key=lambda kv: (-kv[1]["doc_count"], kv[0]))
        buckets = []
        for k, v in items[:size]:
            b = {"key": list(k),
                 "key_as_string": "|".join(str(x) for x in k),
                 "doc_count": int(v["doc_count"])}
            for sub in node.subs:
                b[sub.name] = finalize(sub, v["subs"].get(sub.name, {}),
                                       pipelines)
            buckets.append(b)
        total = sum(v["doc_count"] for _, v in items)
        shown = sum(b["doc_count"] for b in buckets)
        result = {"buckets": buckets,
                  "sum_other_doc_count": int(total - shown)}
        _apply_bucket_pipelines(node, result, "all" if pipelines else "early")
        return result
    if kind == "adjacency_matrix":
        buckets = []
        for key in sorted(merged["buckets"]):
            rec = merged["buckets"][key]
            if rec["doc_count"] <= 0:
                continue
            entry = {"key": key, "doc_count": int(rec["doc_count"])}
            for sub in node.subs:
                entry[sub.name] = finalize(sub, rec["subs"].get(sub.name, {}),
                                           pipelines)
            buckets.append(entry)
        result = {"buckets": buckets}
        _apply_bucket_pipelines(node, result, "all" if pipelines else "early")
        return result
    if kind == "auto_date_histogram":
        # the coordinator's final rounding (reference
        # InternalAutoDateHistogram#reduce, recalled): the finest (unit,
        # inner interval) under which the buckets from the least to the
        # greatest non-empty one number at most `buckets`; buckets of an
        # inner interval over 1 are merged from the least one on, empty
        # buckets between are part of the answer
        target = max(int(node.body.get("buckets", 10)), 1)
        unit = merged.get("unit", 0)
        buckets = {k: v for k, v in merged.get("buckets", {}).items()
                   if v["doc_count"] > 0}
        out_buckets, inner = [], 1
        while buckets:
            ids = {int(auto_unit_ids(k, unit)): k for k in buckets}
            lo, hi = min(ids), max(ids)
            inner = auto_inner_for(hi - lo + 1, unit, target)
            if inner is not None:
                break
            unit += 1
            buckets = _auto_accumulate(node, [(buckets, unit - 1)], unit)
        if buckets:
            groups: Dict[int, list] = {}
            for i, k in ids.items():
                groups.setdefault((i - lo) // inner, []).append(buckets[k])
            for g in range((hi - lo) // inner + 1):
                recs = groups.get(g, [])
                key = auto_unit_start_ms(lo + g * inner, unit)
                entry = {"key": key, "key_as_string": _format_epoch_ms(key),
                         "doc_count": int(sum(r["doc_count"] for r in recs))}
                subs = _merge_sub_metrics(node.subs,
                                          [r.get("subs") for r in recs])
                for sub in node.subs:
                    entry[sub.name] = finalize(sub, subs.get(sub.name, {}),
                                               pipelines)
                out_buckets.append(entry)
        interval = f"{inner}{AUTO_ROUNDINGS[unit][0]}"
        result = {"buckets": out_buckets, "interval": interval}
        _apply_bucket_pipelines(node, result, "all" if pipelines else "early")
        return result
    if kind == "significant_text":
        return _finalize_significant(node, merged, pipelines)
    raise ValueError(f"cannot finalize aggregation kind [{kind}]")


def _script_src(spec):
    """script spec (str or {"source", "params"}) -> (source, params)."""
    if isinstance(spec, str):
        return spec, {}
    return spec.get("source", ""), spec.get("params", {})


def _mad_from_hist(hist: np.ndarray) -> Optional[float]:
    """Median absolute deviation from the mergeable DDSketch histogram
    (reference MedianAbsoluteDeviationAggregator over TDigest): median of
    |bin center - median| weighted by bin counts."""
    from ..ops.aggs import ddsketch_value
    total = float(hist.sum())
    if total == 0:
        return None
    nz = np.nonzero(hist)[0]
    centers = np.array([ddsketch_value(int(b)) for b in nz])
    weights = hist[nz].astype(np.float64)

    def weighted_median(vals, ws):
        order = np.argsort(vals)
        v, w = vals[order], ws[order]
        cum = np.cumsum(w)
        half = cum[-1] / 2.0
        i = int(np.searchsorted(cum, half))
        if cum[i] == half and i + 1 < len(v):
            # even split: interpolate like numpy.median / TDigest
            return float((v[i] + v[i + 1]) / 2.0)
        return float(v[i])

    med = weighted_median(centers, weights)
    return weighted_median(np.abs(centers - med), weights)


def composite_sources(node: AggNode) -> List[tuple]:
    """[(name, source_type, config, order)] from the composite body."""
    out = []
    for s in node.body.get("sources", []):
        ((nm, spec),) = s.items()
        ((stype, scfg),) = spec.items()
        out.append((nm, stype, scfg, scfg.get("order", "asc")))
    return out


class _CompVal:
    """Per-source comparable honoring its order direction."""

    __slots__ = ("v", "desc")

    def __init__(self, v, desc: bool):
        self.v = v
        self.desc = desc

    def __lt__(self, other):
        return (self.v > other.v) if self.desc else (self.v < other.v)

    def __eq__(self, other):
        return self.v == other.v


def _finalize_composite(node: AggNode, merged: dict, pipelines: bool = True) -> dict:
    sources = composite_sources(node)
    size = int(node.body.get("size", 10))
    after = node.body.get("after")

    def comp(key_tuple):
        return tuple(_CompVal(v, o == "desc")
                     for v, (_, _, _, o) in zip(key_tuple, sources))

    items = [(k, v) for k, v in merged["buckets"].items() if v["doc_count"] > 0]
    items.sort(key=lambda kv: comp(kv[0]))
    if after is not None:
        after_tuple = tuple(after[nm] for nm, _, _, _ in sources)
        ac = comp(after_tuple)
        items = [kv for kv in items if comp(kv[0]) > ac]
    buckets = []
    for key, rec in items[:size]:
        b = {"key": {nm: v for (nm, _, _, _), v in zip(sources, key)},
             "doc_count": int(rec["doc_count"])}
        for sub in node.subs:
            b[sub.name] = finalize(sub, rec["subs"].get(sub.name, {}), pipelines)
        buckets.append(b)
    out = {"buckets": buckets}
    if buckets:
        out["after_key"] = buckets[-1]["key"]
    _apply_bucket_pipelines(node, out, "all" if pipelines else "early")
    return out


def _significance_score(fg: float, fg_total: float, bg: float, bg_total: float,
                        heuristic: str) -> float:
    """Reference significance heuristics (JLH default, chi_square,
    percentage) over foreground vs background frequencies."""
    if fg_total == 0 or bg_total == 0 or bg == 0:
        return 0.0
    fgp = fg / fg_total
    bgp = bg / bg_total
    if heuristic == "percentage":
        return fg / bg
    if heuristic == "chi_square":
        num = (fgp - bgp) ** 2
        den = bgp * (1 - bgp)
        return (num / den) * bg_total if den > 0 else 0.0
    # JLH: absolute change * relative change
    return (fgp - bgp) * (fgp / bgp) if fgp > bgp else 0.0


def _finalize_significant(node: AggNode, merged: dict, pipelines: bool = True) -> dict:
    body = node.body
    heuristic = next((h for h in ("jlh", "chi_square", "percentage")
                      if h in body), "jlh")
    size = int(body.get("size", 10))
    min_doc_count = int(body.get("min_doc_count", 3))
    fg_total, bg_total = merged["fg_total"], merged["bg_total"]
    scored = []
    for key, rec in merged["buckets"].items():
        fg = rec["doc_count"]
        bg = merged["bg"].get(key, fg)
        if fg < min_doc_count:
            continue
        score = _significance_score(fg, fg_total, bg, bg_total, heuristic)
        if score > 0:
            scored.append((score, key, fg, bg, rec))
    scored.sort(key=lambda t: (-t[0], t[1]))
    buckets = []
    for score, key, fg, bg, rec in scored[:size]:
        b = {"key": key, "doc_count": int(fg), "score": score,
             "bg_count": int(bg)}
        for sub in node.subs:
            b[sub.name] = finalize(sub, rec["subs"].get(sub.name, {}), pipelines)
        buckets.append(b)
    out = {"doc_count": int(fg_total), "bg_count": int(bg_total),
           "buckets": buckets}
    _apply_bucket_pipelines(node, out, "all" if pipelines else "early")
    return out


def _finalize_matrix_stats(merged: dict) -> dict:
    n = float(merged["count"])
    fields = merged["fields"]
    if n == 0:
        return {"doc_count": 0, "fields": []}
    s1, s2, s3, s4 = (np.asarray(merged[k], np.float64)
                      for k in ("s1", "s2", "s3", "s4"))
    xy = np.asarray(merged["xy"], np.float64)
    shift = np.asarray(merged.get("shift", np.zeros(len(fields))), np.float64)
    # device sums are centered about `shift`; `mean` below is the small
    # residual d = Σ(x-shift)/n, so the central-moment differences don't cancel
    mean = s1 / n
    m2 = s2 / n - mean ** 2
    var = m2 * n / max(n - 1, 1)  # unbiased, like the reference
    out_fields = []
    for i, f in enumerate(fields):
        m2i = max(m2[i], 0.0)
        m3 = s3[i] / n - 3 * mean[i] * s2[i] / n + 2 * mean[i] ** 3
        m4 = (s4[i] / n - 4 * mean[i] * s3[i] / n
              + 6 * mean[i] ** 2 * s2[i] / n - 3 * mean[i] ** 4)
        skew = m3 / m2i ** 1.5 if m2i > 0 else 0.0
        kurt = m4 / m2i ** 2 if m2i > 0 else 0.0
        cov = {}
        corr = {}
        for j, g in enumerate(fields):
            c = (xy[i, j] - s1[i] * s1[j] / n) / max(n - 1, 1)
            cov[g] = c
            denom = math.sqrt(var[i] * var[j])
            corr[g] = c / denom if denom > 0 else 0.0
        out_fields.append({"name": f, "count": int(n),
                           "mean": shift[i] + mean[i],
                           "variance": var[i], "skewness": skew,
                           "kurtosis": kurt, "covariance": cov,
                           "correlation": corr})
    return {"doc_count": int(n), "fields": out_fields}


def _empty_result(node: AggNode) -> dict:
    if node.kind in ("terms", "histogram", "date_histogram", "range",
                     "date_range", "filters", "geohash_grid", "geotile_grid",
                     "composite", "ip_range", "rare_terms", "multi_terms",
                     "adjacency_matrix", "auto_date_histogram"):
        return {"buckets": [] if node.kind != "filters" else {}}
    if node.kind in ("significant_terms", "significant_text"):
        return {"doc_count": 0, "bg_count": 0, "buckets": []}
    if node.kind in ("weighted_avg", "median_absolute_deviation"):
        return {"value": None}
    if node.kind == "geo_bounds":
        return {}
    if node.kind == "geo_centroid":
        return {"count": 0}
    if node.kind == "scripted_metric":
        return {"value": None}
    if node.kind == "matrix_stats":
        return {"doc_count": 0, "fields": []}
    if node.kind in ("filter", "global", "missing", "sampler", "nested",
                     "reverse_nested", "children", "parent",
                     "diversified_sampler"):
        return {"doc_count": 0}
    if node.kind in ("min", "max", "avg"):
        return {"value": None}
    if node.kind in ("sum", "value_count", "cardinality"):
        return {"value": 0}
    if node.kind == "stats":
        return {"count": 0, "min": None, "max": None, "sum": 0.0, "avg": None}
    if node.kind in ("percentiles", "percentile_ranks"):
        return {"values": {}}
    return {}


def _hll_estimate(regs: np.ndarray) -> float:
    m = len(regs)
    z = float(np.sum(np.exp2(-regs.astype(np.float64))))
    alpha = 0.7213 / (1.0 + 1.079 / m)
    est = alpha * m * m / z
    zeros = int(np.sum(regs == 0))
    if est <= 2.5 * m and zeros > 0:
        return m * math.log(m / zeros)
    return est


def _hist_percentiles(merged: dict) -> Dict[str, float]:
    from ..ops.aggs import ddsketch_value

    hist = merged["hist"].astype(np.float64)
    total = hist.sum()
    out: Dict[str, float] = {}
    if total == 0:
        return {f"{p:.1f}": None for p in merged["percents"]}
    cum = np.cumsum(hist)
    nb = len(hist)
    for p in merged["percents"]:
        target = max(p / 100.0 * total, 1e-9)
        b = int(np.searchsorted(cum, target, side="left"))
        out[f"{p:.1f}"] = ddsketch_value(min(b, nb - 1))
    return out


def _hist_percentile_ranks(merged: dict) -> Dict[str, float]:
    """percentile_ranks: the INVERSE of `_hist_percentiles` over the same
    DDSketch histogram (reference PercentileRanksAggregationBuilder,
    SearchModule.java:441) — for each requested value, the percentage of
    observations <= it. Inclusive cumulative count of the value's own bin,
    so rank(percentile(p)) round-trips to p within one bin's resolution."""
    from ..ops.aggs import ddsketch_bin

    hist = merged["hist"].astype(np.float64)
    total = hist.sum()
    out: Dict[str, float] = {}
    # keys are the full-precision value strings (reference
    # String.valueOf(double)): a fixed .1f format would collide distinct
    # sub-0.05 values like 0.01 and 0.04 onto one key
    if total == 0:
        return {str(float(v)): None for v in merged["values"]}
    cum = np.cumsum(hist)
    for v in merged["values"]:
        b = ddsketch_bin(float(v))
        out[str(float(v))] = float(cum[b] / total * 100.0)
    return out


def _format_epoch_ms(ms: int) -> str:
    import datetime as dt

    return dt.datetime.fromtimestamp(ms / 1000.0, dt.timezone.utc).strftime(
        "%Y-%m-%dT%H:%M:%S.%f")[:-3] + "Z"


def apply_pipelines_tree(node: AggNode, result) -> None:
    """Post-order application of DEFERRED pipelines over a finalized agg
    subtree — used for subtrees the refinement walk never reached (their
    early pipelines already ran in finalize; deferred ones run here with the
    same values). The coordinator's refinement-aware walk is
    executor._apply_deferred_tree."""
    if not isinstance(result, dict):
        return
    buckets = result.get("buckets")
    if isinstance(buckets, list):
        for b in buckets:
            for s in node.subs:
                apply_pipelines_tree(s, b.get(s.name))
    elif isinstance(buckets, dict):
        for bd in buckets.values():
            for s in node.subs:
                apply_pipelines_tree(s, bd.get(s.name))
    else:
        for s in node.subs:
            apply_pipelines_tree(s, result.get(s.name))
    _apply_bucket_pipelines(node, result, "deferred")


# ---------------- pipeline aggregations (host post-processing) ----------------

def _apply_pipelines(node: AggNode, buckets_ref) -> None:  # placeholder hook
    return


def _bucket_path_value(b: dict, path: str):
    """Resolve one buckets_path against a finalized bucket (reference
    BucketHelpers.resolveBucketValue): `_count`, `sub.value`, `sub.avg`,
    `sub>nested.value` chains."""
    if path == "_count":
        return float(b["doc_count"])
    node: Any = b
    parts = path.replace(">", ".").split(".")
    for part in parts:
        if not isinstance(node, dict):
            return None
        node = node.get(part)
    if isinstance(node, dict):
        node = node.get("value")
    return node


def _moving_fn_eval(script: str, values: List[float], params: dict):
    """moving_fn scripts: the reference MovingFunctions helpers, plus
    arbitrary painless-lite expressions over `values`."""
    fns = {"max": lambda v: max(v) if v else None,
           "min": lambda v: min(v) if v else None,
           "sum": lambda v: sum(v),
           "unweightedAvg": lambda v: sum(v) / len(v) if v else None,
           "stdDev": None,
           "linearWeightedAvg": lambda v: (sum((i + 1) * x for i, x in enumerate(v))
                                           / sum(range(1, len(v) + 1))) if v else None}
    import re as _re
    m = _re.match(r"\s*MovingFunctions\.(\w+)\(values(?:,\s*[\w.()]+)?\)\s*$", script)
    if m and m.group(1) in fns:
        name = m.group(1)
        if name == "stdDev":
            if not values:
                return None
            avg = sum(values) / len(values)
            return math.sqrt(sum((x - avg) ** 2 for x in values) / len(values))
        return fns[name](values)
    from ..script import painless_lite as pl
    return pl.execute(script, {"values": list(values), "params": params})


def _apply_bucket_pipelines(node: AggNode, result: dict,
                            which: str = "all") -> None:
    """Sibling pipeline aggs over this bucket agg's finalized buckets
    (reference `search/aggregations/pipeline/`): cumulative_sum, derivative,
    moving_avg/fn, serial_diff, bucket_script attach per-bucket;
    bucket_selector/bucket_sort mutate the bucket list; *_bucket /
    percentiles_bucket attach as sibling values.

    `which` selects the phase: "all" every pipeline, "early" only
    non-deferred, "deferred" only deferred (see AggNode.deferred)."""
    buckets = result.get("buckets")
    if not isinstance(buckets, list):
        return
    for p in node.pipelines:
        if which == "early" and p.deferred:
            continue
        if which == "deferred" and not p.deferred:
            continue
        raw_path = p.body.get("buckets_path", "_count")

        if p.kind in ("bucket_script", "bucket_selector"):
            from ..script import painless_lite as pl
            from .query_dsl import parse_script_spec
            src, sparams = parse_script_spec(p.body.get("script"))
            paths = raw_path if isinstance(raw_path, dict) else {"_value": raw_path}
            keep = []
            for b in buckets:
                variables = {"params": dict(sparams)}
                missing = False
                for var, pth in paths.items():
                    v = _bucket_path_value(b, pth)
                    if v is None:
                        missing = True
                    variables["params"][var] = v
                    variables[var] = v
                if missing:
                    # gap_policy=skip: retain the bucket unevaluated
                    # (reference BucketSelector/BucketScript PipelineAggregator)
                    if p.kind == "bucket_script":
                        b[p.name] = {"value": None}
                    keep.append(b)
                    continue
                try:
                    val = pl.execute(src, variables)
                except pl.ScriptError as e:
                    raise ValueError(f"[{p.name}] script error: {e}")
                if p.kind == "bucket_script":
                    b[p.name] = {"value": float(val) if val is not None else None}
                    keep.append(b)
                elif val:
                    keep.append(b)
            if p.kind == "bucket_selector":
                result["buckets"] = buckets = keep
            continue

        if p.kind == "bucket_sort":
            sorts = p.body.get("sort", [])
            frm = int(p.body.get("from", 0))
            size = p.body.get("size")

            def sort_key(b):
                key = []
                for s in sorts:
                    ((pth, spec),) = s.items() if isinstance(s, dict) else [(s, "asc")]
                    order = spec.get("order", "asc") if isinstance(spec, dict) else spec
                    v = _bucket_path_value(b, pth)
                    v = float("-inf") if v is None else v
                    key.append(-v if order == "desc" else v)
                return tuple(key)

            if sorts:
                buckets.sort(key=sort_key)
            end = frm + int(size) if size is not None else None
            result["buckets"] = buckets = buckets[frm:end]
            continue

        series = [_bucket_path_value(b, raw_path) for b in buckets]
        vals = [v for v in series if v is not None]
        if p.kind == "cumulative_sum":
            run = 0.0
            for b, v in zip(buckets, series):
                run += (v or 0.0)
                b[p.name] = {"value": run}
        elif p.kind == "derivative":
            prev = None
            for b, v in zip(buckets, series):
                b[p.name] = {"value": None if prev is None or v is None else v - prev}
                prev = v
        elif p.kind == "serial_diff":
            lag = int(p.body.get("lag", 1))
            for i, b in enumerate(series):
                cur = series[i]
                ref = series[i - lag] if i >= lag else None
                buckets[i][p.name] = {
                    "value": None if cur is None or ref is None else cur - ref}
        elif p.kind in ("moving_avg", "moving_fn"):
            window = int(p.body.get("window", 5))
            shift = int(p.body.get("shift", 0))
            # moving_avg includes the current bucket (reference
            # MovAvgPipelineAggregator); moving_fn's shift=0 excludes it
            if p.kind == "moving_avg":
                shift += 1
            for i, b in enumerate(buckets):
                lo = max(0, i - window + shift)
                hi = max(0, i + shift)
                win = [v for v in series[lo:hi] if v is not None]
                if p.kind == "moving_avg":
                    model = p.body.get("model", "simple")
                    if not win:
                        out = None
                    elif model == "linear":
                        wsum = sum(range(1, len(win) + 1))
                        out = sum((j + 1) * x for j, x in enumerate(win)) / wsum
                    else:
                        out = sum(win) / len(win)
                else:
                    src, sparams = None, {}
                    from .query_dsl import parse_script_spec
                    src, sparams = parse_script_spec(p.body.get("script"))
                    out = _moving_fn_eval(src, win, sparams)
                b[p.name] = {"value": out}
        elif p.kind == "percentiles_bucket":
            percents = p.body.get("percents", [1.0, 5.0, 25.0, 50.0, 75.0, 95.0, 99.0])
            svals = sorted(vals)
            out = {}
            for pc in percents:
                if not svals:
                    out[f"{pc:.1f}"] = None
                else:
                    idx = min(int(round(pc / 100.0 * len(svals) + 0.5)) - 1,
                              len(svals) - 1)
                    out[f"{pc:.1f}"] = svals[max(idx, 0)]
            result[p.name] = {"values": out}
        elif p.kind in ("avg_bucket", "sum_bucket", "min_bucket", "max_bucket", "stats_bucket"):
            if p.kind == "avg_bucket":
                result[p.name] = {"value": sum(vals) / len(vals) if vals else None}
            elif p.kind == "sum_bucket":
                result[p.name] = {"value": sum(vals)}
            elif p.kind == "min_bucket":
                result[p.name] = {"value": min(vals) if vals else None}
            elif p.kind == "max_bucket":
                result[p.name] = {"value": max(vals) if vals else None}
            else:
                result[p.name] = {"count": len(vals), "sum": sum(vals),
                                  "min": min(vals) if vals else None,
                                  "max": max(vals) if vals else None,
                                  "avg": sum(vals) / len(vals) if vals else None}
