"""The plain reference of the deployment kind `big5`, and its rule.

numpy over the generator's own columns, importing nothing of the program:
a keyword is small-integer codes into a list of values
(`columns["kw"][field]`), `@timestamp` whole epoch seconds. A request's
range is a mask; `terms` is a `bincount` of the masked codes, the top
`size` by count and then by key, `sum_other_doc_count` the exact rest;
`multi_terms` and `composite` are `np.unique` of the masked rows' combined
codes, `multi_terms` ordered like `terms` over key tuples, `composite` paged
in key order under each source's `order` from the request's `after` on,
with the last key as `after_key`; `cardinality` is the exact number of
distinct values among the masked rows.

`count_dtype` and `top_before_mask` are the control's handles
(`big5_control.py`): counts accumulated one after another in float16, and
a top-N chosen over the whole column before the range is applied, each has
to fail the rule.

The rule (`hold`): a total equal where the response says `eq`, and a `gte`
total never above the exact count; the buckets equal rank for rank, key
and count (so the order is held too, ties by key included);
`sum_other_doc_count` equal and `doc_count_error_upper_bound` 0;
a composite's `after_key` the last bucket's key; a cardinality equal to
the exact count (this deployment's index is one segment, and the program
answers a keyword cardinality of one segment from its ordinals: the
configuration's `guarantees` say what holds once segments merge). Every
limit is 0."""

from __future__ import annotations

import numpy as np

SHAPES = ("keyword-terms", "keyword-terms-low-cardinality",
          "multi_terms-keyword", "composite-terms", "composite_terms-keyword",
          "cardinality-agg-low", "cardinality-agg-high")
# OSB's aggregation names, fields and sizes, as recalled
STREAM, PROCESS, REGION, AGENT = ("aws.cloudwatch.log_stream",
                                  "process.name", "cloud.region",
                                  "agent.name")
AGGS = {
    "keyword-terms": ("station", "terms", (STREAM,), 500),
    "keyword-terms-low-cardinality": ("station", "terms", (STREAM,), 50),
    "multi_terms-keyword": ("important_terms", "multi_terms",
                            (PROCESS, REGION), 10),
    "composite-terms": ("logs", "composite", (PROCESS, REGION), 10),
    "composite_terms-keyword": ("logs", "composite",
                                (PROCESS, REGION, STREAM), 10),
    "cardinality-agg-low": ("region", "cardinality", (REGION,), 0),
    "cardinality-agg-high": ("agent", "cardinality", (AGENT,), 0)}
COMPOSITE_SOURCES = (("process_name", PROCESS, "desc"),
                     ("cloud_region", REGION, "asc"),
                     ("cloudstream", STREAM, "asc"))
LIMITS = {"error_responses": 0, "total_mismatches": 0,
          "bucket_mismatches": 0, "other_count_mismatches": 0,
          "after_key_mismatches": 0, "cardinality_mismatches": 0}


def agg_body(shape: str, after: dict = None) -> dict:
    """The `aggs` object of operation `shape`, OSB's own."""
    name, kind, fields, size = AGGS[shape]
    if kind == "terms":
        return {name: {"terms": {"field": fields[0], "size": size}}}
    if kind == "multi_terms":
        return {name: {"multi_terms": {"terms": [{"field": f}
                                                 for f in fields]}}}
    if kind == "cardinality":
        return {name: {"cardinality": {"field": fields[0]}}}
    body = {"sources": [{nm: {"terms": {"field": f, "order": o}}}
                        for nm, f, o in COMPOSITE_SOURCES[:len(fields)]]}
    if after is not None:
        body["after"] = after
    return {name: {"composite": body}}


class Reference:
    def __init__(self, columns: dict, count_dtype=np.int64,
                 top_before_mask: bool = False):
        self.ts = columns["ts_s"]
        self.kw = columns["kw"]
        self.count_dtype = count_dtype
        self.top_before_mask = top_before_mask
        self._ranks = {}

    def _rank(self, field: str) -> np.ndarray:
        """code -> the rank of its value among the field's values in
        string order (a key's place in every ordering)."""
        if field not in self._ranks:
            values = self.kw[field][1]
            order = sorted(range(len(values)), key=values.__getitem__)
            rank = np.empty(len(values), np.int64)
            rank[order] = np.arange(len(values))
            self._ranks[field] = (rank, order)
        return self._ranks[field]

    def _mask(self, spec: dict) -> np.ndarray:
        return (self.ts >= spec["lo_s"]) & (self.ts < spec["hi_s"])

    def _counts(self, ids: np.ndarray, n: int) -> np.ndarray:
        if self.count_dtype is np.int64:
            return np.bincount(ids, minlength=n)
        acc = np.zeros(n, self.count_dtype)     # one after another
        np.add.at(acc, ids, self.count_dtype(1))
        return acc.astype(np.int64)

    def _combined(self, fields: tuple, m: np.ndarray, desc: tuple):
        """(codes i64 of the masked rows in the fields' key order, the
        radixes): a field's position is its value's rank, reversed under
        `desc`."""
        code, radix = np.zeros(int(m.sum()), np.int64), []
        for f, d in zip(fields, desc):
            rank, _order = self._rank(f)
            n = len(rank)
            r = rank[self.kw[f][0][m]]
            code = code * n + (n - 1 - r if d else r)
            radix.append(n)
        return code, radix

    def _keys(self, code: int, fields: tuple, radix: list,
              desc: tuple) -> tuple:
        out = []
        for f, n, d in reversed(list(zip(fields, radix, desc))):
            code, r = divmod(code, n)
            out.append(self.kw[f][1][self._rank(f)[1][n - 1 - r if d else r]])
        return tuple(reversed(out))

    def answer(self, spec: dict) -> dict:
        name, kind, fields, size = AGGS[spec["shape"]]
        m = self._mask(spec)
        out = {"total": int(m.sum()), "kind": kind}
        if kind == "cardinality":
            out["value"] = int(len(np.unique(self.kw[fields[0]][0][m])))
            return out
        if kind == "composite":
            desc = tuple(o == "desc" for _n, _f, o in
                         COMPOSITE_SOURCES[:len(fields)])
            code, radix = self._combined(fields, m, desc)
            codes, counts = np.unique(code, return_counts=True)
            if self.count_dtype is not np.int64:
                counts = self._counts(np.searchsorted(codes, code),
                                      len(codes))
            start = 0
            if spec.get("after") is not None:
                after = 0
                for (nm, f, _o), n, d in zip(COMPOSITE_SOURCES, radix, desc):
                    values = self.kw[f][1]
                    r = self._rank(f)[0][values.index(spec["after"][nm])]
                    after = after * n + (n - 1 - r if d else r)
                start = int(np.searchsorted(codes, after, side="right"))
            page = slice(start, start + size)
            names = [nm for nm, _f, _o in COMPOSITE_SOURCES[:len(fields)]]
            out["buckets"] = [
                (dict(zip(names, self._keys(int(c), fields, radix, desc))),
                 int(k)) for c, k in zip(codes[page], counts[page])]
            return out
        # terms / multi_terms: by count, then by key
        code, radix = self._combined(fields, m, (False,) * len(fields))
        space = int(np.prod(radix))
        if self.top_before_mask:
            whole, _r = self._combined(fields, np.ones(len(m), bool),
                                       (False,) * len(fields))
            rank_by = self._counts(whole, space)
        counts = self._counts(code, space)
        if not self.top_before_mask:
            rank_by = counts
        held = np.flatnonzero(rank_by > 0)
        top = held[np.lexsort((held, -rank_by[held]))][:size]
        top = top[counts[top] > 0]
        out["buckets"] = [(self._keys(int(c), fields, radix,
                                      (False,) * len(fields)),
                           int(counts[c])) for c in top]
        out["other"] = int(counts.sum() - counts[top].sum())
        return out


def as_response(answer: dict, spec: dict, track_total: int = 10_000) -> dict:
    """An answer in the response's shape (what the control is held by)."""
    name, kind, _fields, _size = AGGS[spec["shape"]]
    total = answer["total"]
    resp = {"hits": {"total": {"value": min(total, track_total),
                               "relation": "gte" if total > track_total
                               else "eq"}, "hits": []}}
    if kind == "cardinality":
        agg = {"value": answer["value"]}
    elif kind == "composite":
        agg = {"buckets": [{"key": k, "doc_count": c}
                           for k, c in answer["buckets"]]}
        if agg["buckets"]:
            agg["after_key"] = agg["buckets"][-1]["key"]
    elif kind == "terms":
        agg = {"doc_count_error_upper_bound": 0,
               "sum_other_doc_count": answer["other"],
               "buckets": [{"key": k[0], "doc_count": c}
                           for k, c in answer["buckets"]]}
    else:
        agg = {"sum_other_doc_count": answer["other"],
               "buckets": [{"key": list(k),
                            "key_as_string": "|".join(k), "doc_count": c}
                           for k, c in answer["buckets"]]}
    resp["aggregations"] = {name: agg}
    return resp


def compare(spec: dict, resp: dict, want: dict) -> dict:
    """One response against the reference's answer -> the rule's numbers
    (counts of mismatches)."""
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
    name, kind, _fields, _size = AGGS[spec["shape"]]
    agg = resp.get("aggregations", {}).get(name, {})
    if kind == "cardinality":
        bad["cardinality_mismatches"] = int(agg.get("value")
                                            != want["value"])
        return bad
    got = agg.get("buckets", [])
    if kind == "terms":
        got = [((b.get("key"),), b.get("doc_count")) for b in got]
    elif kind == "multi_terms":
        got = [(tuple(b.get("key", ())), b.get("doc_count")) for b in got]
        bad["bucket_mismatches"] += sum(
            b.get("key_as_string") != "|".join(map(str, b.get("key", ())))
            for b in agg.get("buckets", []))
    else:
        got = [(b.get("key"), b.get("doc_count")) for b in got]
    bad["bucket_mismatches"] += abs(len(got) - len(want["buckets"])) + sum(
        g != w for g, w in zip(got, want["buckets"]))
    if kind == "composite":
        last = want["buckets"][-1][0] if want["buckets"] else None
        bad["after_key_mismatches"] = int(agg.get("after_key") != last)
    else:
        bad["other_count_mismatches"] = int(
            agg.get("sum_other_doc_count") != want["other"]
            or (kind == "terms"
                and agg.get("doc_count_error_upper_bound") != 0))
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
