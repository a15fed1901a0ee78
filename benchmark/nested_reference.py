"""The plain reference of the deployment kind `nested`, and its rule.

numpy over the generator's columns and the answers' row offsets (a
question's answers are rows `off[i]:off[i + 1]`); it imports nothing of the
program (a date's spelling is the generators') and builds no child segment. A child clause is a mask over the
answer rows (a `range` on the date, a `term` on the user, or both: a
conjunction INSIDE one answer), a question matches the `nested` clause
where the count of its masked answers is above 0; `match tag` is
membership of the tag's code in the question's tag list, scored as the
BM25 of a keyword term (norms omitted: `idf / (1 + k1)`, Lucene's idf over
the questions, deleted ones too, in float64); a filter-only `nested`
clause adds 0, a scoring one its `score_mode` over the matching answers'
scores. The page is the top `size` by (score descending, row ascending); a
nested sort's key is the `max` (or `min`) of the answers' dates a
question, questions without answers last; the inner hits of a hit are its
matching answers by (score descending, offset ascending), the first `size`
of them from `from`, and their count.

A spec (what `deployments/nested.py` deals and the tests build by hand):
`tag` (a tag's code) or None for `match_all`; `child`, None or a dict with
`date_lte_ms`, `user` (a code, a `term` in `bool.filter`), `score_users`
(codes: a `bool.must` of a `bool.should` of scoring `term`s) and
`score_mode`; `size`; `inner`, None or `{"size": n, "from": f}`; `sort`,
None or `{"mode": "max", "order": "desc"}` (and `missing`, where a body
spells the default out). `body(spec, names, user_name)` is the request OSB
sends for it."""

from __future__ import annotations

import numpy as np

from big5_events import iso_ms

PATH = "answers"
LIMITS = {"score_rel_err_max": 1e-5, "total_mismatches": 0,
          "length_mismatches": 0, "rank_mismatches": 0,
          "sort_value_mismatches": 0, "membership_mismatches": 0,
          "inner_total_mismatches": 0, "inner_offset_mismatches": 0,
          "error_responses": 0}


def body(spec: dict, tag_names, user_name) -> dict:
    """The request of `spec`, as OSB's operations spell it."""
    out: dict = {}
    must = []
    if spec.get("tag") is not None:
        must.append({"match": {"tag": tag_names[spec["tag"]]}})
    child = spec.get("child")
    if child is not None:
        inner: dict = {}
        if child.get("date_lte_ms") is not None:
            inner.setdefault("filter", []).append({"range": {
                PATH + ".date": {"lte": iso_ms(child["date_lte_ms"])}}})
        if child.get("user") is not None:
            inner.setdefault("filter", []).append({"term": {
                PATH + ".user": user_name(child["user"])}})
        if child.get("score_users"):
            inner["must"] = [{"bool": {"should": [
                {"term": {PATH + ".user": user_name(u)}}
                for u in child["score_users"]]}}]
        nested = {"path": PATH, "query": {"bool": inner}}
        if child.get("score_mode"):
            nested["score_mode"] = child["score_mode"]
        if spec.get("inner") is not None:
            nested["inner_hits"] = dict(spec["inner"])
        must.append({"nested": nested})
    if len(must) == 1 and child is None:
        out["query"] = must[0]
    elif must:
        out["query"] = {"bool": {"must": must}}
    else:
        out["query"] = {"match_all": {}}
    if spec.get("size") is not None:
        out["size"] = int(spec["size"])
    if spec.get("sort") is not None:
        # (`missing` only where the spec spells it: `_last` is the default)
        out["sort"] = [{PATH + ".date": dict(
            {k: spec["sort"][k] for k in ("mode", "order", "missing")
             if k in spec["sort"]}, nested={"path": PATH})}]
    return out


class Reference:
    """`any_answer`, `all_answers`, `sort_min` and `score_dtype` weaken it
    in the program's place (`nested_control.py`); the exact reference has
    none of them set."""

    def __init__(self, q: dict, live=None, k1: float = 1.2,
                 any_answer=False, all_answers=False, sort_min=False,
                 score_dtype=np.float64):
        self.q, self.k1 = q, float(k1)
        self.n = len(q["created_ms"])
        self.off = q["ans_off"]
        self.live = np.ones(self.n, bool) if live is None \
            else np.asarray(live, bool)
        self.any_answer, self.all_answers = any_answer, all_answers
        self.sort_min, self.score_dtype = sort_min, score_dtype
        # the questions of a tag, ascending: the tags sorted by code
        order = np.argsort(q["tags"], kind="stable")
        doc_of = np.repeat(np.arange(self.n, dtype=np.int64),
                           np.diff(q["tag_off"]))
        self._tag_rows = doc_of[order]
        self._tag_start = np.zeros(len(q["tag_names"]) + 1, np.int64)
        np.cumsum(np.bincount(q["tags"], minlength=len(q["tag_names"])),
                  out=self._tag_start[1:])
        self._user_df = None
        self._keys: dict = {}

    # -- pieces -----------------------------------------------------------

    def tag_rows(self, tag: int) -> np.ndarray:
        return self._tag_rows[self._tag_start[tag]: self._tag_start[tag + 1]]

    def _idf(self, df: int, ndocs: int) -> float:
        return float(np.log(1.0 + (ndocs - df + 0.5) / (df + 0.5)))

    def _term_score(self, df: int, ndocs: int) -> float:
        """BM25 of a keyword term (tf 1, no norms), in `score_dtype`."""
        t = self.score_dtype
        return float(t(self._idf(df, ndocs)) / (t(1.0) + t(self.k1)))

    def tag_score(self, tag: int) -> float:
        return self._term_score(len(self.tag_rows(tag)), self.n)

    def child_mask(self, child: dict):
        """(mask over the answer rows, their scores or None)."""
        q, nans = self.q, int(self.off[-1])
        mask, score = np.ones(nans, bool), None
        if child.get("date_lte_ms") is not None:
            mask &= q["ans_date_ms"] <= int(child["date_lte_ms"])
        if child.get("user") is not None:
            mask &= q["ans_user"] == int(child["user"])
        if child.get("score_users"):
            if self._user_df is None:
                self._user_df = np.bincount(q["ans_user"])
            score, hit = np.zeros(nans), np.zeros(nans, bool)
            for u in child["score_users"]:
                df = int(self._user_df[u]) if u < len(self._user_df) else 0
                at = q["ans_user"] == int(u)
                score[at] += self._term_score(df, nans)
                hit |= at
            mask &= hit
        return mask, score

    def _per_question(self, values: np.ndarray, how) -> np.ndarray:
        """`how.reduceat` of an answer column over the questions that have
        answers (the others read 0)."""
        out = np.zeros(self.n, values.dtype)
        has = np.diff(self.off) > 0
        out[has] = how.reduceat(values, self.off[:-1][has])
        return out

    def nested_match(self, child: dict):
        """(questions that match the clause, what it adds to their score,
        the answers' mask, the answers' scores)."""
        mask, score = self.child_mask(child)
        csum = np.concatenate(([0], np.cumsum(mask, dtype=np.int64)))
        cnt = csum[self.off[1:]] - csum[self.off[:-1]]
        if self.any_answer:         # the clause dropped: has any answer
            cnt = np.diff(self.off)
        adds = np.zeros(self.n)
        mode = child.get("score_mode") or "avg"
        if score is not None and mode != "none":
            s = np.where(mask, score, 0.0)
            if mode in ("sum", "avg"):
                ssum = np.concatenate(([0.0], np.cumsum(s)))
                adds = ssum[self.off[1:]] - ssum[self.off[:-1]]
                if mode == "avg":
                    adds = adds / np.maximum(cnt, 1)
            elif mode == "max":
                adds = self._per_question(np.where(mask, score, -np.inf),
                                          np.maximum)
            else:
                adds = self._per_question(np.where(mask, score, np.inf),
                                          np.minimum)
        elif mode == "none":
            adds = np.ones(self.n)
        return cnt > 0, adds, mask, score

    def sort_key(self, mode: str):
        """(key i64 a question, has an answer) of a nested sort by the
        answers' dates."""
        if mode not in self._keys:
            how = np.maximum if mode == "max" else np.minimum
            self._keys[mode] = (self._per_question(self.q["ans_date_ms"],
                                                   how),
                                np.diff(self.off) > 0)
        return self._keys[mode]

    def inner_hits(self, row: int, mask, score, inner: dict):
        """(count, offsets of the first `size` from `from`) of a hit's
        matching answers by (score descending, offset ascending)."""
        a, b = int(self.off[row]), int(self.off[row + 1])
        kept = np.arange(b - a) if self.all_answers \
            else np.flatnonzero(mask[a:b])
        if score is not None:
            kept = kept[np.argsort(-score[a:b][kept], kind="stable")]
        frm, size = int(inner.get("from", 0)), int(inner.get("size", 3))
        return len(kept), [int(o) for o in kept[frm: frm + size]]

    # -- a request --------------------------------------------------------

    def answer(self, spec: dict) -> dict:
        """{"total", "hits": [(row, score, sort value or None, inner or
        None)], "score_of": every question's score} of `spec`."""
        matched = self.live.copy()
        score = np.zeros(self.n)
        if spec.get("tag") is not None:
            has = np.zeros(self.n, bool)
            has[self.tag_rows(spec["tag"])] = True
            matched &= has
            score += self.tag_score(spec["tag"])
        elif spec.get("child") is None:
            score += 1.0                    # match_all
        mask = cscore = None
        if spec.get("child") is not None:
            ok, adds, mask, cscore = self.nested_match(spec["child"])
            matched &= ok
            score += adds
        rows = np.flatnonzero(matched)
        size = int(spec["size"]) if spec.get("size") is not None else 10
        values = None
        if spec.get("sort") is not None:
            mode = spec["sort"]["mode"]
            if self.sort_min:
                mode = "min" if mode == "max" else "max"
            key, has = self.sort_key(mode)
            desc = spec["sort"]["order"] == "desc"
            k = np.where(desc, -key[rows], key[rows])
            order = np.lexsort((rows, k, ~has[rows]))   # missing last
            values = [float(key[r]) if has[r] else None
                      for r in rows[order[:size]]]
        else:
            order = np.lexsort((rows, -score[rows]))
        page = rows[order[:size]]
        hits = []
        for i, r in enumerate(page.tolist()):
            inner = None
            if spec.get("inner") is not None and mask is not None:
                inner = self.inner_hits(r, mask, cscore, spec["inner"])
            hits.append((r, float(score[r]),
                         values[i] if values is not None else None, inner))
        return {"total": int(len(rows)), "hits": hits, "score_of": score}


def as_response(answer: dict, spec: dict) -> dict:
    """A reference's answer in the program's response shape (the controls
    answer in the program's place)."""
    hits = []
    for row, score, value, inner in answer["hits"]:
        hit = {"_id": str(row), "_score": score}
        if spec.get("sort") is not None:
            hit["sort"] = [value]
        if inner is not None:
            hit["inner_hits"] = {PATH: {"hits": {
                "total": {"value": inner[0], "relation": "eq"},
                "hits": [{"_nested": {"field": PATH, "offset": o}}
                         for o in inner[1]]}}}
        hits.append(hit)
    return {"hits": {"total": {"value": answer["total"], "relation": "eq"},
                     "hits": hits}}


def compare(spec: dict, resp: dict, ref: Reference) -> dict:
    """One response against the exact reference `ref`, by the rule (the
    module's docstring and `README.md`): the counts of `LIMITS`, and the
    largest relative error of a `_score`."""
    out = dict.fromkeys(LIMITS, 0)
    if not isinstance(resp, dict) or "error" in resp or "hits" not in resp:
        out["error_responses"] = 1
        return out
    want = ref.answer(spec)
    total = resp["hits"]["total"]
    if (total["value"] != want["total"] if total["relation"] == "eq"
            else total["value"] > want["total"]):
        out["total_mismatches"] = 1
    got = resp["hits"]["hits"]
    if len(got) != len(want["hits"]):
        out["length_mismatches"] = 1
    mask = cscore = None
    if spec.get("inner") is not None:
        mask, cscore = ref.child_mask(spec["child"])
    tagged = ref.tag_rows(spec["tag"]) \
        if spec.get("sort") is not None and spec.get("tag") is not None \
        else None       # ascending
    wanted_values = [h[2] for h in want["hits"]]
    rtol = LIMITS["score_rel_err_max"]
    scores = np.asarray([h[1] for h in want["hits"]])
    gaps = np.abs(np.diff(scores))
    near = bool(((gaps > 0) & (gaps <= rtol * np.abs(scores[1:]))).any())
    for rank, (hit, (row, score, value, _inner)) in enumerate(
            zip(got, want["hits"])):
        hid = int(hit["_id"])
        if hit.get("_score") is not None:
            err = abs(hit["_score"] - score) / max(abs(score), 1e-30) \
                if score else abs(hit["_score"])
            out["score_rel_err_max"] = max(out["score_rel_err_max"], err)
        if spec.get("sort") is None:
            # the id. Only where the page holds scores that differ by less
            # than the float limit and are not equal (a sum and a mean of
            # the same scores) may a question of such a score stand in
            # another's place; an exact tie breaks by row, and has to
            own = float(want["score_of"][hid])
            out["rank_mismatches"] += hid != row and not (
                near and abs(own - score) <= rtol * abs(score))
        else:
            got_value = hit.get("sort", [None])[0]
            if got_value != value:
                out["sort_value_mismatches"] += 1
            else:
                # the id where the value stands alone among its neighbours
                # (a tie's order is the program's), and an id that belongs
                # to the value's class wherever it stands
                alone = all(wanted_values[j] != value
                            for j in (rank - 1, rank + 1)
                            if 0 <= j < len(wanted_values))
                out["rank_mismatches"] += alone and hid != row
                key, has = ref.sort_key(spec["sort"]["mode"])
                own = float(key[hid]) if has[hid] else None
                out["membership_mismatches"] += own != value
            if tagged is not None:
                at = int(np.searchsorted(tagged, hid))
                out["membership_mismatches"] += not (
                    at < len(tagged) and int(tagged[at]) == hid)
        if spec.get("inner") is not None:
            n, offsets = ref.inner_hits(hid, mask, cscore, spec["inner"])
            ih = hit.get("inner_hits", {}).get(PATH, {}).get("hits")
            if ih is None or ih["total"]["value"] != n:
                out["inner_total_mismatches"] += 1
            elif [h["_nested"]["offset"] for h in ih["hits"]] != offsets:
                out["inner_offset_mismatches"] += 1
    return out


def hold(held: list, ref: Reference) -> dict:
    """(spec, response) pairs held to `ref` by the rule."""
    worst = dict.fromkeys(LIMITS, 0)
    for spec, resp in held:
        for k, v in compare(spec, resp, ref).items():
            worst[k] = max(worst[k], v) if k == "score_rel_err_max" \
                else worst[k] + v
    return {"compared": len(held),
            "numbers": {k: [worst[k], LIMITS[k]] for k in LIMITS},
            "correct": bool(held) and all(worst[k] <= LIMITS[k]
                                          for k in LIMITS)}
