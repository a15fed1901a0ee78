"""The plain reference of the deployment kind `http_logs`, and its rule.

numpy over the run's own columns (`@timestamp` in epoch milliseconds,
`status`, `size`), importing nothing of the program: a time range is a
mask, a total its sum, the hourly `date_histogram` a `bincount` of
`timestamp_ms // 3,600,000`, a sorted page the smallest (or largest) k + 1
values under the mask. Every pass is over the whole column, in int64.

`time_dtype` is the control's handle (`http_logs_control.py`): the same
arithmetic with the timestamps and the bounds in float32, the nearest
precision below what the deployment states, has to fail the rule.

The rule (`hold`), all exact, every limit 0: a total equal where the
response says `eq`, and a `gte` total never above the exact count; every
bucket key and count equal (buckets of count 0 are the response's to fill
or leave out); as many hits as the page has, the sort value equal at every
rank, and the id equal at every rank whose sort value differs from both
neighbours' (a tie's order is the engine's own)."""

from __future__ import annotations

import numpy as np

HOUR_MS = 3_600_000
SHAPES = ("range", "200s-in-range", "400s-in-range", "hourly_agg",
          "desc_sort_timestamp", "asc_sort_timestamp", "desc_sort_size",
          "asc_sort_size")
AGG_NAME = "by_hour"        # the name `hourly_agg` gives its aggregation
SORT_FIELD = {"timestamp": "@timestamp", "size": "size"}
LIMITS = {"total_mismatches": 0, "bucket_mismatches": 0,
          "sort_value_mismatches": 0, "rank_mismatches": 0,
          "error_responses": 0}


class Reference:
    """Answers a request spec (`shape`, `lo_ms`, `hi_ms`, `page`) from the
    columns: {"total", "buckets" {key_ms: count} or None, "page" [(sort
    value, doc)] of page + 1 entries or None}."""

    def __init__(self, ts_ms, status, size, time_dtype=np.int64):
        self.time_dtype = time_dtype
        self.ts = np.asarray(ts_ms, np.int64).astype(time_dtype)
        self.status = np.asarray(status, np.int64)
        self.size = np.asarray(size, np.int64)

    def mask(self, spec: dict) -> np.ndarray:
        lo = np.asarray(spec["lo_ms"], np.int64).astype(self.time_dtype)
        hi = np.asarray(spec["hi_ms"], np.int64).astype(self.time_dtype)
        m = (self.ts >= lo) & (self.ts < hi)
        if spec["shape"].endswith("s-in-range"):
            m &= self.status == int(spec["shape"][:3])
        return m

    def answer(self, spec: dict) -> dict:
        m = self.mask(spec)
        out = {"total": int(m.sum()), "buckets": None, "page": None}
        if spec["shape"] == "hourly_agg":
            t = self.ts[m]
            hours = (t // HOUR_MS if self.time_dtype is np.int64
                     else np.floor(t / self.time_dtype(HOUR_MS))
                     ).astype(np.int64)
            if len(hours):
                counts = np.bincount(hours - hours.min())
                out["buckets"] = {int((hours.min() + j) * HOUR_MS): int(c)
                                  for j, c in enumerate(counts) if c}
            else:
                out["buckets"] = {}
        elif "_sort_" in spec["shape"]:
            order, _sort, field = spec["shape"].split("_")
            docs = np.flatnonzero(m)
            vals = (self.ts if field == "timestamp" else self.size)[docs]
            key = -vals if order == "desc" else vals
            k = min(int(spec["page"]) + 1, len(docs))
            if k:
                head = np.argpartition(key, k - 1)[:k] if k < len(docs) \
                    else np.arange(len(docs))
                head = head[np.lexsort((docs[head], key[head]))]
                out["page"] = [(int(vals[i]), int(docs[i])) for i in head]
            else:
                out["page"] = []
        return out


def as_response(answer: dict, page: int, track_total: int = 10_000) -> dict:
    """An answer in the response's shape (what the control is held by)."""
    total = answer["total"]
    resp = {"hits": {"total": {"value": min(total, track_total),
                               "relation": "gte" if total > track_total
                               else "eq"},
                     "hits": [{"_id": str(doc), "sort": [value]}
                              for value, doc in (answer["page"] or [])[:page]]}}
    if answer["buckets"] is not None:
        resp["aggregations"] = {AGG_NAME: {"buckets": [
            {"key": k, "doc_count": c}
            for k, c in sorted(answer["buckets"].items())]}}
    return resp


def compare(spec: dict, resp: dict, want: dict) -> dict:
    """One response against the reference's answer -> the rule's counts."""
    bad = dict.fromkeys(LIMITS, 0)
    if "error" in resp or "hits" not in resp:
        bad["error_responses"] = 1
        return bad
    total = resp["hits"]["total"]
    if total["relation"] == "eq":
        bad["total_mismatches"] = int(total["value"] != want["total"])
    else:
        bad["total_mismatches"] = int(total["relation"] != "gte"
                                      or total["value"] > want["total"])
    if want["buckets"] is not None:
        got = {b["key"]: b["doc_count"] for b in resp.get(
            "aggregations", {}).get(AGG_NAME, {}).get("buckets", [])
            if b["doc_count"]}
        keys = set(got) | set(want["buckets"])
        bad["bucket_mismatches"] = sum(
            got.get(k) != want["buckets"].get(k) for k in keys)
    if want["page"] is not None:
        page = int(spec["page"])
        hits, ranks = resp["hits"]["hits"], want["page"]
        if len(hits) != min(page, len(ranks)):
            bad["rank_mismatches"] = abs(len(hits) - min(page, len(ranks)))
        for i, (hit, (value, doc)) in enumerate(zip(hits, ranks)):
            if not hit.get("sort") or hit["sort"][0] != value:
                bad["sort_value_mismatches"] += 1
                continue
            alone = ((i == 0 or ranks[i - 1][0] != value)
                     and (i + 1 >= len(ranks) or ranks[i + 1][0] != value))
            if alone and hit["_id"] != str(doc):
                bad["rank_mismatches"] += 1
    return bad


def hold(held: list, ref: Reference) -> dict:
    """(spec, response) pairs held to `ref` by the rule."""
    worst = dict.fromkeys(LIMITS, 0)
    for spec, resp in held:
        for k, v in compare(spec, resp, ref.answer(spec)).items():
            worst[k] += v
    return {"compared": len(held),
            "numbers": {k: [worst[k], LIMITS[k]] for k in LIMITS},
            "correct": bool(held) and all(worst[k] <= LIMITS[k]
                                          for k in LIMITS)}
