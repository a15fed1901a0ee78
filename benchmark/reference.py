"""The plain reference and the rule that decides `correct`.

`dense_bm25` is a numpy dense BM25 over the CSR postings the run built:
every posting of every query term scored, no skipping, no index structure
of the program's (a copy of `chip_smoke.dense_bm25` as it stood at PR 24,
with the top page taken by a partition instead of a full sort). It imports
nothing of the program. `dtype` is float32 for the reference; the control
(`control.py`, `tests/test_control.py`) passes bfloat16, the nearest
precision below the float32 the configurations state.

The rule (`compare_page`, a copy of `chip_smoke.compare_page` that returns
its numbers instead of raising): hit totals equal where the response says
`eq`, a `gte` total never above the exact one; as many hits as the
reference; every score within `score_rtol` relative of the reference's;
doc ids equal at every rank of the page whose reference score is further
than that from its neighbours' (a tie leaves the order undecided). Every
request asks for one rank more than the page, so the last rank's gap is
known."""

from __future__ import annotations

import numpy as np

K1, B = 1.2, 0.75


class Reference:
    """The dense scorer over one corpus. `page(spec, size)` -> {"total",
    "relation", "ids", "scores"} for a query spec of `queries.QueryStream`."""

    def __init__(self, csr, dl, k1: float = K1, b: float = B,
                 dtype=np.float32):
        self.starts, self.doc_ids, self.tfs = csr
        self.n = len(dl)
        self.dtype = dtype
        avgdl = dl.sum() / self.n
        self.kdoc = (k1 * (1.0 - b + b * dl / avgdl)).astype(
            np.float32).astype(dtype)

    def page(self, spec: dict, size: int) -> dict:
        n, dt = self.n, self.dtype
        score = np.zeros(n, dt)
        hit = np.zeros(n, bool)
        for t in spec["terms"]:
            a, e = int(self.starts[t]), int(self.starts[t + 1])
            d, tf = self.doc_ids[a:e], self.tfs[a:e].astype(dt)
            idf = np.float32(np.log1p((n - (e - a) + 0.5) / ((e - a) + 0.5)))
            score[d] += dt(idf) * tf / (tf + self.kdoc[d])
            hit[d] = True
        total = int(hit.sum())
        s = np.where(hit, score.astype(np.float32), np.float32(-1.0))
        if total > size:
            kth = np.partition(s, n - size)[n - size]
            cand = np.flatnonzero(s >= kth)     # ties at the edge included
        else:
            cand = np.flatnonzero(hit)
        order = cand[np.lexsort((cand, -s[cand]))][:size]
        return {"total": total, "relation": "eq",
                "ids": [str(d) for d in order],
                "scores": [float(x) for x in s[order]]}


def page_of(resp: dict) -> dict:
    """A search response as the rule reads it."""
    h = resp["hits"]
    return {"total": h["total"]["value"], "relation": h["total"]["relation"],
            "ids": [x["_id"] for x in h["hits"]],
            "scores": [x["_score"] for x in h["hits"]]}


def compare_page(got: dict, ref: dict, page: int, rtol: float) -> dict:
    """Hold `got` to `ref` by the rule. -> the numbers compared:
    `score_rel_err` (the widest), and counts of violations."""
    out = {"score_rel_err": 0.0, "total_violations": 0,
           "length_violations": 0, "rank_violations": 0}
    if got["relation"] == "eq":
        out["total_violations"] = int(got["total"] != ref["total"])
    else:
        out["total_violations"] = int(got["total"] > ref["total"])
    if len(got["ids"]) != len(ref["ids"]):
        out["length_violations"] = 1
    m = min(len(got["ids"]), len(ref["ids"]))
    rs = np.asarray(ref["scores"][:m], np.float64)
    gs = np.asarray(got["scores"][:m], np.float64)
    if m:
        out["score_rel_err"] = float(np.max(
            np.abs(gs - rs) / np.maximum(np.abs(rs), 1e-30)))
    full = np.asarray(ref["scores"], np.float64)
    tol = rtol * np.maximum(np.abs(full), 1e-30)
    for i in range(min(page, m)):
        gaps = np.abs(np.delete(full, i) - full[i])
        if len(gaps) and gaps.min() <= tol[i]:
            continue            # tied in the reference: order not decided
        if got["ids"][i] != ref["ids"][i]:
            out["rank_violations"] += 1
    return out


def hold(pairs: list, reference: Reference, size: int, page: int,
         rtol: float) -> dict:
    """Hold (spec, response) pairs to the reference. -> {"compared",
    "numbers": {name: [value, limit]}, "correct", "first_failures"}."""
    worst = {"score_rel_err_max": 0.0, "total_violations": 0,
             "length_violations": 0, "rank_violations": 0,
             "error_responses": 0}
    failures = []
    for spec, resp in pairs:
        if "error" in resp or "hits" not in resp:
            worst["error_responses"] += 1
            continue
        got, ref = page_of(resp), reference.page(spec, size)
        c = compare_page(got, ref, page, rtol)
        err = c.pop("score_rel_err")
        worst["score_rel_err_max"] = max(worst["score_rel_err_max"], err)
        for k, v in c.items():
            worst[k] += v
        if (err > rtol or any(c.values())) and len(failures) < 3:
            failures.append({"terms": spec["terms"], "got": got, "ref": ref})
    limits = {"score_rel_err_max": rtol, "total_violations": 0,
              "length_violations": 0, "rank_violations": 0,
              "error_responses": 0}
    return {"compared": len(pairs),
            "numbers": {k: [worst[k], limits[k]] for k in worst},
            "correct": bool(pairs) and all(worst[k] <= limits[k]
                                           for k in worst),
            "first_failures": failures}
