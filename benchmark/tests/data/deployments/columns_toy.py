"""A second deployment kind, for the tests only (in no `BENCHMARK.json`
entry): filters, an aggregation and a field sort over `corpus.plant_index`'s
own `status` / `price` columns. It is here so that the seam of
`run.load_kind` is not shaped by one user: a build that is no text CSR,
bodies that are no term lists, a twin that is no rotation, a rule that is
not BM25.

Three request shapes, dealt in turn: `filter` (a `bool` `filter` `range` on
`price`, `size` 0, exact totals), `agg` (the same filter under a `terms`
aggregation on `status`) and `sort` (a page of `match_all` by `price`
ascending with `_doc` as tie-break, at a `from` offset). Every drawn bound
is even and a twin shifts one bound by one, so no body comes twice: not in
the pool, not among the twins, not in the check's fresh draws. The rule is
exact: totals, bucket keys and counts, ids at every rank."""

from __future__ import annotations

import time

import numpy as np

import corpus

SHAPES = ("filter", "agg", "sort")
PRICES = 1000
DEEPEST = 200       # the furthest `from` of a sorted page


def build(config: dict, seed: int, client, index: str) -> dict:
    t0 = time.time()
    n = int(config["ndocs"])
    rng = np.random.default_rng([seed, 0])
    status = rng.integers(0, len(corpus.STATUSES), n).astype(np.int32)
    price = rng.integers(0, PRICES, n).astype(np.int64)
    # `plant_index` wants a `body`: one word that every document holds
    csr = (np.array([0, n], np.int64), np.arange(n, dtype=np.int32),
           np.ones(n, np.float32))
    corpus.plant_index(client, index, csr, corpus.vocab_strings(1),
                       np.ones(n, np.int64), status, price,
                       config["index_settings"])
    return {"status": status, "price": price, "build_s": time.time() - t0,
            "promote_s": 0.0, "readout": {"rows": n}}


def _price_filter(lo: int, hi: int) -> dict:
    return {"bool": {"filter": [{"range": {"price": {"gte": lo, "lt": hi}}}]}}


def _spec(shape: str, a: int, b: int) -> dict:
    """`filter` / `agg`: prices in [a, b). `sort`: ranks a to b."""
    if shape == "sort":
        body = {"query": {"match_all": {}}, "from": a, "size": b - a,
                "sort": [{"price": "asc"}, "_doc"]}
        return {"shape": shape, "a": a, "b": b, "body": body, "weight": b}
    body = {"query": _price_filter(a, b), "size": 0, "track_total_hits": True}
    if shape == "agg":
        body["aggs"] = {"by_status": {"terms": {"field": "status"}}}
    return {"shape": shape, "a": a, "b": b, "body": body, "weight": b - a}


class _Stream:
    def __init__(self, built: dict, traffic: dict, seed: int):
        self.size = int(traffic["size"])
        self._seen, self._turn = set(), 0
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        self._rng = np.random.default_rng([seed, 2])

    def take(self, n: int) -> list:
        out = []
        while len(out) < n:
            shape = SHAPES[self._turn % len(SHAPES)]
            if shape == "sort":
                a = 2 * int(self._rng.integers(1, DEEPEST // 2))
                b = a + self.size
            else:
                a, b = sorted(2 * int(x) for x in self._rng.choice(
                    PRICES // 2, 2, replace=False))
            if (shape, a, b) in self._seen:
                continue
            self._seen.add((shape, a, b))
            self._turn += 1
            out.append(_spec(shape, a, b))
        return out

    def twin(self, spec: dict) -> dict:
        """One bound shifted by one: odd, so no draw's body. A sorted page
        moves up a rank, and so asks for no more hits than its draw."""
        if spec["shape"] == "sort":
            return _spec("sort", spec["a"] - 1, spec["b"] - 1)
        return _spec(spec["shape"], spec["a"], spec["b"] + 1)


def stream(built: dict, traffic: dict, seed: int) -> _Stream:
    return _Stream(built, traffic, seed)


def _reference(spec: dict, built: dict) -> dict:
    status, price = built["status"], built["price"]
    if spec["shape"] == "sort":
        order = np.lexsort((np.arange(len(price)), price))
        return {"ids": [str(d) for d in order[spec["a"]: spec["b"]]]}
    inside = (price >= spec["a"]) & (price < spec["b"])
    out = {"total": int(inside.sum())}
    if spec["shape"] == "agg":
        counts = np.bincount(status[inside], minlength=len(corpus.STATUSES))
        out["buckets"] = {corpus.STATUSES[i]: int(c)
                          for i, c in enumerate(counts) if c}
    return out


def hold(held: list, built: dict, config: dict, traffic: dict) -> dict:
    worst = {"total_mismatches": 0, "bucket_mismatches": 0,
             "rank_mismatches": 0, "error_responses": 0}
    for spec, resp in held:
        if "error" in resp or "hits" not in resp:
            worst["error_responses"] += 1
            continue
        ref = _reference(spec, built)
        if "ids" in ref:
            got = [h["_id"] for h in resp["hits"]["hits"]]
            worst["rank_mismatches"] += int(got != ref["ids"])
            continue
        total = resp["hits"]["total"]
        worst["total_mismatches"] += int(
            total["relation"] != "eq" or total["value"] != ref["total"])
        if "buckets" in ref:
            got = {b["key"]: b["doc_count"] for b in
                   resp["aggregations"]["by_status"]["buckets"]}
            worst["bucket_mismatches"] += int(got != ref["buckets"])
    return {"compared": len(held),
            "numbers": {k: [v, 0] for k, v in worst.items()},
            "correct": bool(held) and not any(worst.values())}


def counters(client) -> dict:
    return {}
