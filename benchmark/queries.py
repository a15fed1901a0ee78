"""Query generators, looked up by the name a traffic file gives.

A generator is a function `(df, rng, nterms, params, memo) -> term ids`
(`memo` is a dict the stream keeps for it between calls)
registered in `GENERATORS`; a later PR adds one by adding a module under
`benchmark/query_generators/<name>.py` that exposes `generate` (see
`generator()`), editing nothing here. `QueryStream` turns a generator into
a seeded, repeat-free stream: no body comes twice from one stream (so the
coordinator's request cache answers nothing), and the lengths come as
shuffled cycles over `min_terms..max_terms`. A run draws its pool from the
traffic file's `pool_seed` (the SAME queries for every `--seed`, which then
sends them in another order: the seed may not change the work), then
`reseed`s the stream with `--seed` for the fresh queries of the check.
`permuted` gives a query's warm-up twin: the same terms in another order,
which is another body (a request-cache miss) of the same program shapes.

`by_token_mass` and `df_rank_band` are copies of `bench.pick_queries_real`
and `bench.pick_queries` as they stood at PR 24."""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def by_token_mass(df, rng, nterms: int, params: dict, memo: dict) -> list:
    """`nterms` distinct terms drawn in proportion to corpus token mass
    (the corpus generator's own Zipf law), no df floor: stopword-class
    terms appear at their natural rate."""
    vocab = len(df)
    draw = nterms * int(params.get("oversample", 3))
    terms = rng.zipf(float(params.get("zipf_a", 1.15)), draw).astype(np.int64)
    terms = np.where(terms > vocab, rng.integers(1, vocab, draw), terms) - 1
    terms = terms[df[terms] > 0]
    uniq = list(dict.fromkeys(terms.tolist()))[:nterms]
    while len(uniq) < nterms:           # top up with any in-corpus term
        t = int(rng.integers(0, vocab))
        if df[t] > 0 and t not in uniq:
            uniq.append(t)
    return uniq


def df_rank_band(df, rng, nterms: int, params: dict, memo: dict) -> list:
    """`nterms` distinct terms from document-frequency ranks
    `rank_lo..rank_hi` (selective, keyword-search-like)."""
    pool = memo.get("pool")
    if pool is None:
        order = np.argsort(-df, kind="stable")
        pool = order[int(params["rank_lo"]): int(params["rank_hi"])]
        pool = memo["pool"] = pool[df[pool] > 0]
    return [int(t) for t in rng.choice(pool, nterms, replace=False)]


GENERATORS = {"by_token_mass": by_token_mass, "df_rank_band": df_rank_band}


def generator(name: str):
    """The generator a traffic file names: built in, or the `generate`
    function of `benchmark/query_generators/<name>.py`."""
    if name in GENERATORS:
        return GENERATORS[name]
    path = os.path.join(_HERE, "query_generators", name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no query generator {name!r} "
                         f"(looked in queries.py and {path})")
    spec = importlib.util.spec_from_file_location(f"query_gen_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate


class QueryStream:
    """A seeded, repeat-free stream. `take(n)` gives the next n query
    specs: {"terms", "body"}."""

    def __init__(self, df, vocab: list, seed: int, traffic: dict):
        self.df, self.vocab = df, vocab
        self.gen = generator(traffic["generator"])
        self.params = traffic.get("params", {})
        self.lo = int(self.params["min_terms"])
        self.hi = int(self.params["max_terms"])
        self.size = int(traffic["size"])
        self._rng = np.random.default_rng([seed, 2])
        self._lengths = []
        self._seen = set()
        self._memo = {}

    def _next_len(self) -> int:
        if not self._lengths:
            self._lengths.extend(int(x) for x in self._rng.permutation(
                np.arange(self.lo, self.hi + 1)))
        return self._lengths.pop()

    def reseed(self, seed: int) -> None:
        """Later draws come from `seed`; what was drawn stays excluded."""
        self._rng = np.random.default_rng([seed, 2])
        self._lengths = []

    def _body(self, terms: list) -> dict:
        text = " ".join(self.vocab[t] for t in terms)
        return {"query": {"match": {"body": text}}, "size": self.size}

    def permuted(self, spec: dict) -> dict:
        """The same query with its terms rotated by one: another body."""
        terms = spec["terms"][1:] + spec["terms"][:1]
        if terms == spec["terms"]:
            raise SystemExit("benchmark: a one-term query has no twin")
        return {"terms": terms, "body": self._body(terms)}

    def take(self, n: int) -> list:
        out = []
        while len(out) < n:
            nterms = self._next_len()
            for _ in range(1000):
                terms = self.gen(self.df, self._rng, nterms, self.params,
                                 self._memo)
                key = tuple(sorted(terms))
                if key not in self._seen:
                    break
            else:
                raise SystemExit("benchmark: the query generator cannot "
                                 "give a body this run has not sent")
            self._seen.add(key)
            out.append({"terms": terms, "body": self._body(terms)})
        return out
