"""The plain reference of the deployment kind `nyc_taxis`, and its rule.

numpy over the run's own columns, importing nothing of the program: money
and distance in int64 hundredths, times in int64 epoch milliseconds. A
range is a mask and a total its sum; `distance_amount_agg` is a `bincount`
of `hundredths // 100` with the `stats` of `total_amount` a bucket (count,
the least and greatest value, the sum in float64); the date histograms are
`bincount`s of `ms // 86,400,000` (UTC days), `auto_date_histogram` by its
rule written out below; a sorted page is read off a stable sorted order of
the column, built once.

`auto_date_histogram`, as recalled from OpenSearch (the configuration says
so under `assumed`): the roundings are second, minute, hour, day, month and
year with the inner intervals of `ROUNDINGS`; the answer is the finest
(rounding, inner interval) under which the buckets from the least to the
greatest matched value's, merged `inner` at a time from the least one on,
number at most `buckets`; a bucket's key is its first unit's start in epoch
ms (UTC), empty buckets between are part of the answer, and `interval` is
the inner interval with the unit's letter (`1d`, `12h`).

`sum_dtype` and `interval_from` are the control's handles
(`nyc_taxis_control.py`): per-bucket sums accumulated one after another in
float32, and the rounding taken from the column's span instead of the
matched documents', each has to fail the rule.

The rule (`hold`): a total equal where the response says `eq`, and a `gte`
total never above the exact count; every bucket key and count equal, empty
buckets included, and `interval` equal; under a bucket the `stats`' count
equal, `min` / `max` equal to float32 of the stored value, `sum` and `avg`
within `sum_rtol` of the float64 sum of the stored values; as many hits as
the page has, the sort value equal at every rank, the id equal at every
rank whose sort value differs from both neighbours' (a tie's order is the
engine's own), and every returned id a document that matches and holds the
reported value. Every limit on a mismatch is 0."""

from __future__ import annotations

import numpy as np

DAY_MS = 86_400_000
SHAPES = ("range", "distance_amount_agg", "autohisto_agg",
          "date_histogram_agg", "desc_sort_tip_amount", "asc_sort_tip_amount",
          "desc_sort_passenger_count", "asc_sort_passenger_count")
AGG_NAME = {"distance_amount_agg": "distance_histo",
            "autohisto_agg": "dropoffs_over_time",
            "date_histogram_agg": "dropoffs_over_time"}
STATS_NAME = "total_amount_stats"
AUTO_BUCKETS = 20
# (the interval's letter, the unit, its inner intervals)
ROUNDINGS = (("s", "s", (1, 5, 10, 30)), ("m", "m", (1, 5, 10, 30)),
             ("h", "h", (1, 3, 12)), ("d", "D", (1, 7)),
             ("M", "M", (1, 3)), ("y", "Y", (1, 5, 10, 20, 50, 100)))
SUM_RTOL = 1e-5
LIMITS = {"total_mismatches": 0, "bucket_mismatches": 0,
          "interval_mismatches": 0, "stat_mismatches": 0,
          "sum_rel_err_max": SUM_RTOL, "sort_value_mismatches": 0,
          "rank_mismatches": 0, "membership_mismatches": 0,
          "error_responses": 0}


def unit_ids(ms, unit: str) -> np.ndarray:
    """Ids of the `unit` buckets (numpy's datetime64 units, UTC) that hold
    epoch-millisecond values: units since the epoch."""
    return np.asarray(ms, np.int64).astype("datetime64[ms]").astype(
        f"datetime64[{unit}]").astype(np.int64)


def unit_start_ms(bucket_id: int, unit: str) -> int:
    return int(np.datetime64(int(bucket_id), unit).astype(
        "datetime64[ms]").astype(np.int64))


def auto_rounding(lo_ms: int, hi_ms: int, buckets: int):
    """-> (index into ROUNDINGS, inner interval): the rule above."""
    for r, (_letter, unit, inners) in enumerate(ROUNDINGS):
        lo, hi = unit_ids([lo_ms, hi_ms], unit)
        for inner in inners:
            if -(-(int(hi) - int(lo) + 1) // inner) <= buckets:
                return r, inner
    return len(ROUNDINGS) - 1, ROUNDINGS[-1][2][-1]


class Reference:
    """Answers a request spec from the columns: {"total", "buckets"
    {key: count} or None, "interval" or None, "stats" {key: {count, min,
    max, sum}} or None, "page" [(sort value, doc)] of page + 1 entries or
    None}. Keys are epoch ms (date histograms) or the bucket's lower edge
    as a float (`distance_amount_agg`); sort values are what the response
    carries (hundredths / 100, or the count)."""

    def __init__(self, trips: dict, sum_dtype=np.float64,
                 interval_from: str = "matched"):
        self.pickup_ms = np.asarray(trips["pickup_s"], np.int64) * 1000
        self.dropoff_ms = np.asarray(trips["dropoff_s"], np.int64) * 1000
        self.total_c = np.asarray(trips["total_amount_c"], np.int64)
        self.dist_c = np.asarray(trips["trip_distance_c"], np.int64)
        self.sort_cols = {
            "tip_amount": np.asarray(trips["tip_amount_c"], np.int64),
            "passenger_count": np.asarray(trips["passenger_count"],
                                          np.int64)}
        self.sum_dtype, self.interval_from = sum_dtype, interval_from
        self._orders = {}

    def _ranged(self, spec: dict) -> np.ndarray:
        return {"total_amount_c": self.total_c,
                "trip_distance_c": self.dist_c, "pickup_ms": self.pickup_ms,
                "dropoff_ms": self.dropoff_ms}[spec["on"]]

    def mask(self, spec: dict) -> np.ndarray:
        col = self._ranged(spec)
        return (col >= spec["lo"]) & (col < spec["hi"])

    # -- aggregations ----------------------------------------------------

    def _distance_stats(self, m: np.ndarray) -> tuple:
        """-> ({key: count}, {key: stats}) over the whole miles from the
        least to the greatest matched one, the empty ones included."""
        miles, cents = self.dist_c[m] // 100, self.total_c[m]
        if not len(miles):
            return {}, {}
        first = int(miles.min())
        keys = miles - first
        n = int(keys.max()) + 1
        counts = np.bincount(keys, minlength=n)
        stored = cents / 100.0          # the stored float64 values
        if self.sum_dtype is np.float64:
            sums = np.bincount(keys, weights=stored, minlength=n)
        else:                           # one after another, in sum_dtype
            sums = np.zeros(n, np.float64)
            order = np.argsort(keys, kind="stable")
            ends = np.cumsum(counts)
            vals = stored[order].astype(self.sum_dtype)
            for k in np.flatnonzero(counts):
                sums[k] = np.cumsum(vals[ends[k] - counts[k]: ends[k]],
                                    dtype=self.sum_dtype)[-1]
        lo = np.full(n, np.iinfo(np.int64).max)
        hi = np.full(n, np.iinfo(np.int64).min)
        np.minimum.at(lo, keys, cents)
        np.maximum.at(hi, keys, cents)

        def stored32(c):                # float32 of a stored value
            return float(np.float32(c / 100.0))
        stats = {float(first + k): {
            "count": int(counts[k]),
            "min": stored32(lo[k]) if counts[k] else None,
            "max": stored32(hi[k]) if counts[k] else None,
            "sum": float(sums[k])} for k in range(n)}
        return {float(first + k): int(counts[k]) for k in range(n)}, stats

    def _day_buckets(self, m: np.ndarray) -> dict:
        days = self.dropoff_ms[m] // DAY_MS
        if not len(days):
            return {}
        counts = np.bincount(days - days.min())
        return {int((days.min() + j) * DAY_MS): int(c)
                for j, c in enumerate(counts)}

    def _auto_buckets(self, m: np.ndarray) -> tuple:
        t = self.dropoff_ms[m]
        if not len(t):
            return {}, None
        span = t if self.interval_from == "matched" else self.dropoff_ms
        r, inner = auto_rounding(int(span.min()), int(span.max()),
                                 AUTO_BUCKETS)
        letter, unit, _inners = ROUNDINGS[r]
        ids = unit_ids(t, unit)
        first = int(ids.min())
        counts = np.bincount((ids - first) // inner)
        return ({unit_start_ms(first + g * inner, unit): int(c)
                 for g, c in enumerate(counts)}, f"{inner}{letter}")

    # -- sorted pages ----------------------------------------------------

    def _page(self, m: np.ndarray, field: str, desc: bool, k: int) -> list:
        """The first `k` (value, doc) of the documents under `m` in sorted
        order: walked off a stable sorted order of the column, built once."""
        col = self.sort_cols[field]
        if field not in self._orders:
            self._orders[field] = np.argsort(col, kind="stable").astype(
                np.int32)
        order = self._orders[field]
        found, at, step = [], 0, 1 << 16
        while len(found) < k and at < len(order):
            chunk = (order[::-1][at: at + step] if desc
                     else order[at: at + step])
            found += chunk[m[chunk]].tolist()
            at, step = at + step, step * 4
        head = sorted(found, key=lambda d: ((-col[d] if desc else col[d]), d))
        return [(self._sort_value(field, d), int(d)) for d in head[:k]]

    def _sort_value(self, field: str, doc: int):
        """What a response carries as `doc`'s sort value: hundredths / 100
        of an amount, the count itself."""
        v = self.sort_cols[field][doc]
        return v / 100.0 if field.endswith("_amount") else int(v)

    def answer(self, spec: dict) -> dict:
        m = self.mask(spec)
        out = {"total": int(m.sum()), "buckets": None, "interval": None,
               "stats": None, "page": None}
        shape = spec["shape"]
        if shape == "distance_amount_agg":
            out["buckets"], out["stats"] = self._distance_stats(m)
        elif shape == "date_histogram_agg":
            out["buckets"] = self._day_buckets(m)
        elif shape == "autohisto_agg":
            out["buckets"], out["interval"] = self._auto_buckets(m)
        elif "_sort_" in shape:
            order, _sort, field = shape.split("_", 2)
            out["page"] = self._page(m, field, order == "desc",
                                     int(spec["page"]) + 1)
        return out

    def holds(self, spec: dict, doc: int, value) -> bool:
        """Does document `doc` match `spec` and hold sort value `value`?"""
        _order, _sort, field = spec["shape"].split("_", 2)
        if not (0 <= doc < len(self.sort_cols[field])
                and spec["lo"] <= self._ranged(spec)[doc] < spec["hi"]):
            return False
        return self._sort_value(field, doc) == value


def as_response(answer: dict, spec: dict, track_total: int = 10_000) -> dict:
    """An answer in the response's shape (what the control is held by)."""
    total = answer["total"]
    page = int(spec.get("page", 0))
    resp = {"hits": {"total": {"value": min(total, track_total),
                               "relation": "gte" if total > track_total
                               else "eq"},
                     "hits": [{"_id": str(doc), "sort": [value]}
                              for value, doc in (answer["page"] or [])[:page]]}}
    if answer["buckets"] is not None:
        buckets = []
        for k, c in sorted(answer["buckets"].items()):
            b = {"key": k, "doc_count": c}
            if answer["stats"] is not None:
                s = answer["stats"][k]
                b[STATS_NAME] = dict(s, avg=(s["sum"] / s["count"]
                                             if s["count"] else None))
            buckets.append(b)
        agg = {"buckets": buckets}
        if answer["interval"] is not None:
            agg["interval"] = answer["interval"]
        resp["aggregations"] = {AGG_NAME[spec["shape"]]: agg}
    return resp


def _rel_err(got, want: float) -> float:
    if got is None:
        return float("inf")
    return abs(got - want) / abs(want) if want else abs(got)


def compare(spec: dict, resp: dict, want: dict, ref: Reference) -> dict:
    """One response against the reference's answer -> the rule's numbers
    (counts of mismatches, and the largest relative error of a sum)."""
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
        agg = resp.get("aggregations", {}).get(AGG_NAME[spec["shape"]], {})
        got = {b["key"]: b for b in agg.get("buckets", [])}
        keys = set(got) | set(want["buckets"])
        bad["bucket_mismatches"] = sum(
            k not in got or got[k]["doc_count"] != want["buckets"].get(k)
            for k in keys)
        if want["interval"] is not None:
            bad["interval_mismatches"] = int(
                agg.get("interval") != want["interval"])
        for k, w in (want["stats"] or {}).items():
            s = got.get(k, {}).get(STATS_NAME)
            if s is None:
                bad["stat_mismatches"] += 1
                continue
            bad["stat_mismatches"] += int(
                (s.get("count"), s.get("min"), s.get("max"))
                != (w["count"], w["min"], w["max"]))
            if w["count"]:
                bad["sum_rel_err_max"] = max(
                    bad["sum_rel_err_max"], _rel_err(s.get("sum"), w["sum"]),
                    _rel_err(s.get("avg"), w["sum"] / w["count"]))
            elif s.get("sum") not in (0, 0.0) or s.get("avg") is not None:
                bad["stat_mismatches"] += 1
    if want["page"] is not None:
        page = int(spec["page"])
        hits, ranks = resp["hits"]["hits"], want["page"]
        if len(hits) != min(page, len(ranks)):
            bad["rank_mismatches"] = abs(len(hits) - min(page, len(ranks)))
        for i, (hit, (value, doc)) in enumerate(zip(hits, ranks)):
            if not hit.get("sort") or hit["sort"][0] != value:
                bad["sort_value_mismatches"] += 1
                continue
            if not ref.holds(spec, int(hit["_id"]), hit["sort"][0]):
                bad["membership_mismatches"] += 1
            alone = ((i == 0 or ranks[i - 1][0] != value)
                     and (i + 1 >= len(ranks) or ranks[i + 1][0] != value))
            if alone and hit["_id"] != str(doc):
                bad["rank_mismatches"] += 1
    return bad


def hold(held: list, ref: Reference) -> dict:
    """(spec, response) pairs held to `ref` by the rule."""
    worst = dict.fromkeys(LIMITS, 0)
    for spec, resp in held:
        for k, v in compare(spec, resp, ref.answer(spec), ref).items():
            worst[k] = max(worst[k], v) if k == "sum_rel_err_max" \
                else worst[k] + v
    return {"compared": len(held),
            "numbers": {k: [worst[k], LIMITS[k]] for k in LIMITS},
            "correct": bool(held) and all(worst[k] <= LIMITS[k]
                                          for k in LIMITS)}
