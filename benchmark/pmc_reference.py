"""The plain reference of the deployment kind `pmc`: `match_phrase` over the
generator's **flat token stream and document offsets**, in numpy, importing
nothing of the program and reading no positional postings.

An occurrence of an m-word phrase is a flat index i with `tok[i + j] ==
term_j` for every j, inside one document; a document's phrase frequency f
is its count of occurrences; its score is Lucene's `PhraseWeight`: the sum
of the terms' BM25 idf (`ln(1 + (N - df + 0.5) / (df + 0.5))`, N and df over
every document, deleted ones too, as Lucene's statistics are until a merge)
times `f / (f + k1 (1 - b + b dl / avgdl))`, in float64. The page is the
top 11 by (score descending, document ascending) of the live documents with
f > 0 (the response shows 10: rank 10's gap to the next is known), the
total their number.

`Reference.learn(phrases)` reads what a batch of phrases needs in one pass
over the stream, in blocks of documents on threads: for every term of every
phrase its postings (document, term frequency), and for each phrase's
rarest term its flat indices, from which the occurrences are verified.
`how` weakens the answer for the control (`pmc_control.py`):
"conjunction" answers a phrase as the documents that hold every word, f the
least term frequency (what an index without positions can say); "presence"
caps f at 1 (`match_only_text`'s answer).

The rule is `bm25_match`'s (`reference.compare_page`): totals equal where
the response says `eq` and never above the exact one otherwise, as many
hits, every score within `score_rtol` relative, ids equal at every rank
whose reference score is further than that from its neighbours'.
`occurrence_bytes` is what `pmc_roofline.py` counts a phrase's least
reading from."""

from __future__ import annotations

import numpy as np

from reference import compare_page, page_of

BLOCK_TOKENS = 1 << 24
PAGE = 10
LIMITS = ("score_rel_err_max", "total_violations", "length_violations",
          "rank_violations", "error_responses")


def _blocks(offsets: np.ndarray) -> list:
    total = int(offsets[-1])
    cuts = np.searchsorted(offsets, np.arange(BLOCK_TOKENS, total,
                                              BLOCK_TOKENS))
    bounds = np.unique(np.concatenate([[0], cuts, [len(offsets) - 1]]))
    return list(zip(bounds[:-1].tolist(), bounds[1:].tolist()))


class Reference:
    def __init__(self, tok: np.ndarray, offsets: np.ndarray,
                 live: np.ndarray, k1: float = 1.2, b: float = 0.75,
                 how: str = "exact", threads: int = 8):
        assert how in ("exact", "conjunction", "presence")
        self.tok, self.offsets, self.live = tok, offsets, live
        self.k1, self.b, self.how, self.threads = k1, b, how, threads
        self.ndocs = len(offsets) - 1
        self.dl = np.diff(offsets).astype(np.float64)
        self.avgdl = float(offsets[-1]) / self.ndocs
        # tokens a term, a block at a time (`bincount` widens its input)
        self.cf = np.zeros(int(tok.max()) + 1 if len(tok) else 0, np.int64)
        for lo in range(0, len(tok), BLOCK_TOKENS):
            part = np.bincount(tok[lo: lo + BLOCK_TOKENS])
            self.cf[: len(part)] += part
        self.postings: dict = {}    # term -> (docs i64 ascending, tfs i64)
        self.where: dict = {}       # term -> flat indices, ascending

    # -- one pass over the stream for a batch of phrases -----------------

    def _block(self, a: int, b: int, code: np.ndarray, located: np.ndarray):
        """Of documents [a, b): (term code, document, tf) of the coded
        terms' postings, and the flat indices of the located ones."""
        lo, hi = int(self.offsets[a]), int(self.offsets[b])
        c = code[self.tok[lo:hi]]
        hit = np.flatnonzero(c >= 0)
        c = c[hit]
        doc = np.repeat(np.arange(a, b), np.diff(self.offsets[a: b + 1]))[hit]
        key, tf = np.unique(c.astype(np.int64) * self.ndocs + doc,
                            return_counts=True)
        at = located[c]
        return key // self.ndocs, key % self.ndocs, tf, c[at], hit[at] + lo

    def learn(self, phrases: list) -> None:
        """Postings of every term of `phrases` and the flat indices of each
        one's rarest term, for those not yet known."""
        from concurrent.futures import ThreadPoolExecutor
        rarest = {self._rarest_term(p) for p in phrases}
        need = sorted({int(t) for p in phrases for t in p
                       if 0 <= t < len(self.cf)}
                      - (set(self.postings) - (rarest - set(self.where))))
        if not need:
            return
        code = np.full(len(self.cf), -1, np.int32)
        code[need] = np.arange(len(need), dtype=np.int32)
        located = np.asarray([t in rarest for t in need])
        with ThreadPoolExecutor(self.threads) as pool:
            parts = list(pool.map(
                lambda ab: self._block(ab[0], ab[1], code, located),
                _blocks(self.offsets)))
        # the blocks ascend in document, so a stable sort by term leaves a
        # term's documents, and its flat indices, ascending
        terms, docs, tfs, wterm, windex = (
            np.concatenate([p[i] for p in parts]) for i in range(5))
        order = np.argsort(terms, kind="stable")
        cuts = np.searchsorted(terms[order], np.arange(len(need) + 1))
        worder = np.argsort(wterm, kind="stable")
        wcuts = np.searchsorted(wterm[worder], np.arange(len(need) + 1))
        for i, t in enumerate(need):
            sel = order[cuts[i]: cuts[i + 1]]
            self.postings[t] = (docs[sel], tfs[sel])
            if located[i]:
                self.where[t] = windex[worder[wcuts[i]: wcuts[i + 1]]]

    # -- one phrase -------------------------------------------------------

    def _postings(self, t: int):
        return self.postings.get(int(t), (np.empty(0, np.int64),) * 2)

    def _count(self, t: int) -> int:
        return int(self.cf[t]) if 0 <= t < len(self.cf) else 0

    def _rarest_term(self, terms) -> int:
        """The term of fewest tokens (the least id where tied)."""
        return min((int(t) for t in terms),
                   key=lambda t: (self._count(t), t))

    def frequencies(self, terms) -> tuple:
        """(documents ascending, phrase frequency of each > 0)."""
        terms = [int(t) for t in terms]
        self.learn([tuple(terms)])
        if self.how == "conjunction":
            docs, f = self._postings(terms[0])
            for t in terms[1:]:
                d2, f2 = self._postings(t)
                both, i, j = np.intersect1d(docs, d2, assume_unique=True,
                                            return_indices=True)
                docs, f = both, np.minimum(f[i], f2[j])
            return docs, f
        jr = terms.index(self._rarest_term(terms))
        start = self.where.get(terms[jr], np.empty(0, np.int64)) - jr
        m, tok = len(terms), self.tok
        start = start[(start >= 0) & (start + m <= len(tok))]
        for j, t in enumerate(terms):
            start = start[tok[start + j] == t]
        doc = np.searchsorted(self.offsets, start, side="right") - 1
        start = start[start + m <= self.offsets[doc + 1]]   # one document
        doc = np.searchsorted(self.offsets, start, side="right") - 1
        docs, f = np.unique(doc, return_counts=True)
        if self.how == "presence":
            f = np.minimum(f, 1)
        return docs, f

    def weight(self, terms) -> float:
        n = float(self.ndocs)
        df = np.asarray([len(self._postings(t)[0]) for t in terms],
                        np.float64)
        df = df[df > 0]
        return float(np.sum(np.log(1.0 + (n - df + 0.5) / (df + 0.5))))

    def page(self, terms, size: int = PAGE + 1) -> dict:
        docs, f = self.frequencies(terms)
        keep = self.live[docs]
        docs, f = docs[keep], f[keep].astype(np.float64)
        k = self.k1 * (1.0 - self.b + self.b * self.dl[docs] / self.avgdl)
        scores = self.weight(terms) * f / (f + k)
        order = np.lexsort((docs, -scores))[:size]
        return {"total": int(len(docs)),
                "ids": [str(int(d)) for d in docs[order]],
                "scores": [float(s) for s in scores[order]]}

    def occurrence_bytes(self, terms) -> float:
        """The bytes an exact phrase has to read: 4 for every posting of
        its rarest term, and 4 for every position of its two rarest terms
        inside the documents that hold every term of the phrase."""
        terms = [int(t) for t in terms]
        self.learn([tuple(terms)])
        by_cf = sorted(set(terms), key=lambda t: (self._count(t), t))
        docs = self._postings(by_cf[0])[0]
        for t in by_cf[1:]:
            docs = np.intersect1d(docs, self._postings(t)[0],
                                  assume_unique=True)
        inside = 0
        for t in by_cf[:2]:
            d, tf = self._postings(t)
            inside += int(tf[np.searchsorted(d, docs)].sum())
        return 4.0 * (len(self._postings(by_cf[0])[0]) + inside)


def hold(pairs: list, reference: Reference, rtol: float,
         page: int = PAGE) -> dict:
    """Hold (spec, response) pairs to the reference by `bm25_match`'s rule.
    -> {"compared", "numbers": {name: [value, limit]}, "correct",
    "first_failures"}."""
    worst = dict.fromkeys(LIMITS, 0)
    worst["score_rel_err_max"] = 0.0
    failures = []
    reference.learn([tuple(spec["terms"]) for spec, _resp in pairs])
    for spec, resp in pairs:
        if "error" in resp or "hits" not in resp:
            worst["error_responses"] += 1
            continue
        got, ref = page_of(resp), reference.page(spec["terms"], page + 1)
        c = compare_page(got, dict(ref, ids=ref["ids"][:page]), page, rtol)
        err = c.pop("score_rel_err")
        worst["score_rel_err_max"] = max(worst["score_rel_err_max"], err)
        for k, v in c.items():
            worst[k] += v
        if (err > rtol or any(c.values())) and len(failures) < 3:
            failures.append({"terms": spec["terms"], "got": got, "ref": ref})
    limits = dict.fromkeys(LIMITS, 0)
    limits["score_rel_err_max"] = rtol
    return {"compared": len(pairs),
            "numbers": {k: [worst[k], limits[k]] for k in LIMITS},
            "correct": bool(pairs) and all(worst[k] <= limits[k]
                                           for k in LIMITS),
            "first_failures": failures}


def as_response(page: dict, shown: int = PAGE) -> dict:
    """A reference page in the shape of a search response (the control
    holds a weakened reference's pages to the exact one's)."""
    return {"hits": {"total": {"value": page["total"], "relation": "eq"},
                     "hits": [{"_id": i, "_score": s} for i, s in
                              zip(page["ids"][:shown],
                                  page["scores"][:shown])]}}


# ---------------------------------------------------------------------
# the workload's other operations (tier-1 holds them at a small size; no
# cell times them)
# ---------------------------------------------------------------------

def term_page(reference: Reference, term: int, size: int = PAGE + 1) -> dict:
    """`{"term": {"body": <word>}}`: BM25 of one term over its postings."""
    reference.learn([(int(term),)])
    docs, tf = reference._postings(term)
    keep = reference.live[docs]
    docs, tf = docs[keep], tf[keep].astype(np.float64)
    k = reference.k1 * (1.0 - reference.b
                        + reference.b * reference.dl[docs] / reference.avgdl)
    scores = reference.weight([term]) * tf / (tf + k)
    order = np.lexsort((docs, -scores))[:size]
    return {"total": int(len(docs)),
            "ids": [str(int(d)) for d in docs[order]],
            "scores": [float(s) for s in scores[order]]}


def monthly_counts(ts_s: np.ndarray, live: np.ndarray) -> dict:
    """`date_histogram` of `timestamp` by calendar month: {epoch ms of the
    month's first instant: live documents in it}, the empty months between
    the first and the last included (min_doc_count 0 is the default)."""
    month = (ts_s[live] // 86400).astype("datetime64[D]").astype(
        "datetime64[M]").astype(np.int64)
    out = {}
    for m in range(int(month.min()), int(month.max()) + 1):
        key = np.datetime64(m, "M").astype("datetime64[ms]").astype(np.int64)
        out[int(key)] = int(np.count_nonzero(month == m))
    return out
