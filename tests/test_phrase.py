"""Positional phrase / span / intervals queries: the device pair-join
(ops/positions.py) vs naive reference semantics (reference: Lucene
PhraseQuery / SloppyPhraseMatcher via `index/query/MatchPhraseQueryBuilder`)."""

import math

import numpy as np
import pytest

from opensearch_tpu.index.engine import Engine
from opensearch_tpu.index.mappings import Mappings
from opensearch_tpu.search.executor import ShardSearcher, search_shards

DOCS = [
    ("1", {"body": "the quick brown fox jumps over the lazy dog"}),
    ("2", {"body": "the brown quick fox is not a dog"}),           # swapped order
    ("3", {"body": "quick and nimble brown fox"}),                 # gap of 2
    ("4", {"body": "a fox that is brown and quick"}),              # far apart
    ("5", {"body": "quick brown fox quick brown fox"}),            # phrase tf 2
    ("6", {"body": "nothing relevant here"}),
]

MAPPING = {"properties": {"body": {"type": "text"}}}


@pytest.fixture(scope="module")
def searcher():
    e = Engine(Mappings(MAPPING))
    for i, s in DOCS:
        e.index_doc(i, s)
    e.refresh()
    return ShardSearcher(e)


def search(s, body):
    return search_shards([s], body, "idx")


def ids(resp):
    return [h["_id"] for h in resp["hits"]["hits"]]


def test_exact_phrase(searcher):
    r = search(searcher, {"query": {"match_phrase": {"body": "quick brown fox"}}})
    assert set(ids(r)) == {"1", "5"}


def test_exact_phrase_excludes_swapped_and_gapped(searcher):
    r = search(searcher, {"query": {"match_phrase": {"body": "brown fox"}}})
    assert set(ids(r)) == {"1", "3", "5"}
    r = search(searcher, {"query": {"match_phrase": {"body": "quick fox"}}})
    assert ids(r) == ["2"]  # "brown quick fox" has them adjacent
    r = search(searcher, {"query": {"match_phrase": {"body": "fox brown"}}})
    assert ids(r) == []  # order matters for exact phrases


def test_phrase_slop(searcher):
    # slop 2 lets "quick ... brown fox" (doc 3, quick displaced by 2) match,
    # and "brown quick fox" (doc 2: adjacent transposition costs 2 moves)
    r = search(searcher, {"query": {"match_phrase": {
        "body": {"query": "quick brown fox", "slop": 2}}}})
    assert set(ids(r)) == {"1", "2", "3", "5"}
    r = search(searcher, {"query": {"match_phrase": {
        "body": {"query": "quick brown fox", "slop": 1}}}})
    assert set(ids(r)) == {"1", "5"}
    # swapped adjacent terms need total displacement 2 as well
    r = search(searcher, {"query": {"match_phrase": {
        "body": {"query": "quick brown", "slop": 2}}}})
    assert "2" in ids(r)


def test_phrase_freq_scoring(searcher):
    """Doc 5 has the phrase twice -> freq 2 drives the BM25 tf curve with
    weight = sum of term idfs (Lucene PhraseWeight)."""
    r = search(searcher, {"query": {"match_phrase": {"body": "quick brown fox"}}})
    by_id = {h["_id"]: h["_score"] for h in r["hits"]["hits"]}
    N = 6
    dls = [9, 8, 5, 7, 6, 3]
    avgdl = sum(dls) / N

    def idf(df):
        return math.log(1 + (N - df + 0.5) / (df + 0.5))

    w = idf(5) + idf(5) + idf(5)  # quick df=5, brown df=5, fox df=5

    def bm25(freq, dl):
        k = 1.2 * (1 - 0.75 + 0.75 * dl / avgdl)
        return w * freq / (freq + k)

    assert abs(by_id["5"] - bm25(2.0, 6)) < 1e-5
    assert abs(by_id["1"] - bm25(1.0, 9)) < 1e-5
    assert by_id["5"] > by_id["1"]


def test_single_term_phrase_is_term_query(searcher):
    r = search(searcher, {"query": {"match_phrase": {"body": "nimble"}}})
    assert ids(r) == ["3"]


def test_match_phrase_prefix(searcher):
    r = search(searcher, {"query": {"match_phrase_prefix": {"body": "quick bro"}}})
    assert set(ids(r)) == {"1", "5"}
    r = search(searcher, {"query": {"match_phrase_prefix": {"body": "lazy d"}}})
    assert ids(r) == ["1"]


def test_phrase_in_bool(searcher):
    r = search(searcher, {"query": {"bool": {
        "must": [{"match_phrase": {"body": "brown fox"}}],
        "must_not": [{"match": {"body": "nimble"}}]}}})
    assert set(ids(r)) == {"1", "5"}


def test_span_near(searcher):
    r = search(searcher, {"query": {"span_near": {
        "clauses": [{"span_term": {"body": "quick"}},
                    {"span_term": {"body": "fox"}}],
        "slop": 1, "in_order": True}}})
    assert set(ids(r)) == {"1", "2", "5"}  # adjacent or one term between


def test_intervals_match(searcher):
    r = search(searcher, {"query": {"intervals": {"body": {
        "match": {"query": "quick fox", "max_gaps": 1}}}}})
    assert set(ids(r)) == {"1", "2", "5"}


def test_span_near_in_order_rejects_swapped(searcher):
    # doc 2 has "brown quick": unordered span_near matches, in_order doesn't
    body = {"query": {"span_near": {
        "clauses": [{"span_term": {"body": "quick"}},
                    {"span_term": {"body": "brown"}}],
        "slop": 2, "in_order": False}}}
    assert "2" in ids(search(searcher, body))
    body["query"]["span_near"]["in_order"] = True
    r = search(searcher, body)
    assert "2" not in ids(r)
    assert {"1", "3", "5"} <= set(ids(r))


def test_intervals_gaps_not_moves(searcher):
    # unordered intervals: adjacent transposition ("brown quick" in doc 2)
    # has 0 gaps even though it costs 2 moves
    r = search(searcher, {"query": {"intervals": {"body": {
        "match": {"query": "quick brown", "max_gaps": 0}}}}})
    assert "2" in ids(r)
    # ordered + max_gaps=0 excludes it again
    r = search(searcher, {"query": {"intervals": {"body": {
        "match": {"query": "quick brown", "max_gaps": 0, "ordered": True}}}}})
    assert "2" not in ids(r)
    # gaps budget is total across the span: "quick and nimble brown fox"
    # has 2 gap positions for "quick brown fox"
    r = search(searcher, {"query": {"intervals": {"body": {
        "match": {"query": "quick brown fox", "max_gaps": 1, "ordered": True}}}}})
    assert "3" not in ids(r)
    r = search(searcher, {"query": {"intervals": {"body": {
        "match": {"query": "quick brown fox", "max_gaps": 2, "ordered": True}}}}})
    assert "3" in ids(r)


def test_phrase_prefix_max_expansions():
    e = Engine(Mappings(MAPPING))
    for i, word in enumerate(["apple", "apricot", "avocado"]):
        e.index_doc(str(i), {"body": f"ripe {word}"})
    e.refresh()
    s = ShardSearcher(e)
    r = search(s, {"query": {"match_phrase_prefix": {"body": {"query": "ap"}}}})
    assert set(ids(r)) == {"0", "1"}
    r = search(s, {"query": {"match_phrase_prefix": {
        "body": {"query": "ap", "max_expansions": 1}}}})
    assert ids(r) == ["0"]  # only first expansion (sorted vocab: apple)


def test_ordered_span_skips_earlier_out_of_order_occurrence():
    # nearest occurrence of "fox" to the anchor is BEFORE it; the ordered
    # join must still find the later in-order one (greedy sequential)
    e = Engine(Mappings(MAPPING))
    e.index_doc("1", {"body": "fox quick one two three fox"})
    e.refresh()
    s = ShardSearcher(e)
    r = search(s, {"query": {"span_near": {
        "clauses": [{"span_term": {"body": "quick"}},
                    {"span_term": {"body": "fox"}}],
        "slop": 4, "in_order": True}}})
    assert ids(r) == ["1"]
    r = search(s, {"query": {"span_near": {
        "clauses": [{"span_term": {"body": "quick"}},
                    {"span_term": {"body": "fox"}}],
        "slop": 2, "in_order": True}}})
    assert ids(r) == []  # 3 gaps > 2
    # explain agrees with the device result
    r = search(s, {"query": {"span_near": {
        "clauses": [{"span_term": {"body": "quick"}},
                    {"span_term": {"body": "fox"}}],
        "slop": 4, "in_order": True}}, "explain": True})
    h = r["hits"]["hits"][0]
    assert abs(h["_explanation"]["value"] - h["_score"]) < 1e-4


def test_phrase_prefix_df_clamped_nonnegative():
    # union df of the prefix expansions exceeds maxDoc; scores must stay > 0
    e = Engine(Mappings(MAPPING))
    for i in range(4):
        e.index_doc(str(i), {"body": "ripe apple apricot avocado amber"})
    e.refresh()
    s = ShardSearcher(e)
    r = search(s, {"query": {"match_phrase_prefix": {"body": "ripe a"}}})
    assert len(ids(r)) == 4
    assert all(h["_score"] > 0 for h in r["hits"]["hits"])


def test_intervals_bad_rule_is_parse_error():
    from opensearch_tpu.search.query_dsl import QueryParseError, parse_query
    # shorthand match and fuzzy are supported rules now (full algebra);
    # unknown rules still 400
    parse_query({"intervals": {"body": {"match": "quick fox"}}})
    parse_query({"intervals": {"body": {"fuzzy": {"term": "x"}}}})
    with pytest.raises(QueryParseError):
        parse_query({"intervals": {"body": {"frob": {"x": 1}}}})
    with pytest.raises(QueryParseError):
        parse_query({"intervals": {"body": {"all_of": {"intervals": []}}}})


def test_phrase_prefix_highlight_marks_expanded_term():
    e = Engine(Mappings(MAPPING))
    e.index_doc("1", {"body": "the quick brown fox"})
    e.refresh()
    s = ShardSearcher(e)
    r = search(s, {"query": {"match_phrase_prefix": {"body": "quick bro"}},
                   "highlight": {"fields": {"body": {}}}})
    frags = r["hits"]["hits"][0]["highlight"]["body"]
    assert any("<em>quick</em> <em>brown</em>" in f for f in frags)


def test_phrase_explain_matches_score(searcher):
    r = search(searcher, {"query": {"match_phrase": {"body": "quick brown fox"}},
                          "explain": True})
    for h in r["hits"]["hits"]:
        assert abs(h["_explanation"]["value"] - h["_score"]) < 1e-4


def test_multi_match_phrase():
    e = Engine(Mappings({"properties": {"t": {"type": "text"},
                                        "b": {"type": "text"}}}))
    e.index_doc("1", {"t": "alpha beta", "b": "gamma delta"})
    e.index_doc("2", {"t": "beta alpha", "b": "delta gamma"})
    e.refresh()
    s = ShardSearcher(e)
    r = search(s, {"query": {"multi_match": {"query": "gamma delta",
                                             "fields": ["t", "b"],
                                             "type": "phrase"}}})
    assert ids(r) == ["1"]


def test_phrase_across_segments_and_deletes():
    e = Engine(Mappings(MAPPING))
    e.index_doc("a", {"body": "red green blue"})
    e.refresh()
    e.index_doc("b", {"body": "red green yellow"})
    e.index_doc("c", {"body": "green red blue"})
    e.refresh()
    s = ShardSearcher(e)
    r = search(s, {"query": {"match_phrase": {"body": "red green"}}})
    assert set(ids(r)) == {"a", "b"}
    e.delete_doc("b")
    e.refresh()
    s2 = ShardSearcher(e)
    r = search(s2, {"query": {"match_phrase": {"body": "red green"}}})
    assert set(ids(r)) == {"a"}


# ---------------------------------------------------------------------
# the join's search alone: `ops.positions.window_searchsorted`, a 128-ary
# descent through fence levels, against numpy's searchsorted over packed
# int64 keys
# ---------------------------------------------------------------------

SIZES = [0, 1, 127, 128, 129, 16_383, 16_384, 16_385, (1 << 21) + 1]
PER_DOC = 64        # positions a document: a pair is key // 64, key % 64


def _pairs(rng, n, span):
    """`n` distinct sorted (doc, position) pairs with keys under `span`."""
    keys = np.sort(rng.choice(span, n, replace=False)).astype(np.int64)
    return (keys // PER_DOC).astype(np.int32), (keys % PER_DOC).astype(
        np.int32)


def _pack(d, p):
    return (np.asarray(d, np.int64) << 32) + np.asarray(p, np.int64)


def _plane(rng, n, place):
    """A (doc, pos) plane that holds a window of `n` pairs and its offset.
    `first`: the window begins at slot 0; `last`: it ends at the plane's
    last slot, a whole number of rows in; `middle`: it begins and ends off
    a row boundary, a sentinel-padded tail behind its right neighbour. The
    neighbours are other terms' windows over the same key range: by value
    their pairs lie "inside"."""
    from opensearch_tpu.ops import positions as pos_ops
    span = 4 * n + 1024
    left = {"first": 0, "last": (-n) % pos_ops.ROW + 3 * pos_ops.ROW,
            "middle": 301 if (301 + n) % pos_ops.ROW else 300}[place]
    right = 0 if place == "last" else 333
    parts = [_pairs(rng, k, span) for k in (left, n, right)]
    d = np.concatenate([x[0] for x in parts])
    p = np.concatenate([x[1] for x in parts])
    if place == "middle":
        tail = 77 + (-len(d)) % pos_ops.ROW
        d = np.concatenate([d, np.full(tail, pos_ops.INT32_SENTINEL)])
        p = np.concatenate([p, np.zeros(tail, np.int32)])
        assert left % pos_ops.ROW and (left + n) % pos_ops.ROW
    return d.astype(np.int32), p.astype(np.int32), left


def _queries(rng, d, p, lo, n, count=300):
    """Keys below, inside, equal to and above the window's pairs."""
    span = 4 * n + 1024
    qd, qp = _pairs(rng, count, span)
    qd, qp = list(qd), list(qp)
    qd += [0, 0, -1, int(np.int32(2**31 - 1)), span // PER_DOC + 1]
    qp += [0, -5, 3, 0, 0]
    if n:
        at = rng.integers(lo, lo + n, 64)
        for i in [lo, lo + n - 1] + at.tolist():
            qd += [int(d[i])] * 3
            qp += [int(p[i]) - 1, int(p[i]), int(p[i]) + 1]
    return np.asarray(qd, np.int32), np.asarray(qp, np.int32)


def _want(d, p, lo, n, qd, qp):
    return lo + np.searchsorted(_pack(d[lo: lo + n], p[lo: lo + n]),
                                _pack(qd, qp), side="left")


@pytest.mark.parametrize("place", ["first", "last", "middle"])
@pytest.mark.parametrize("n", SIZES)
def test_window_searchsorted_is_numpys(n, place):
    """The slot of the first pair >= the key, `lo + n` where none is, for
    windows of every level count up to 4 wherever they lie in the plane,
    with the levels the window's length asks for and with every level the
    plane has."""
    import jax
    import jax.numpy as jnp
    from opensearch_tpu.ops import positions as pos_ops
    rng = np.random.default_rng([n, len(place)])
    d, p, lo = _plane(rng, n, place)
    qd, qp = _queries(rng, d, p, lo, n)
    dj, pj = jnp.asarray(d), jnp.asarray(p)
    planes = {"doc": dj, "pos": pj}
    for plane in ("doc", "pos"):
        for k, level in enumerate(pos_ops.fences(planes[plane]), 1):
            assert level.shape[0] % pos_ops.ROW == 0
            planes[pos_ops.plane_key(plane, k)] = level
    whole_plane = pos_ops.search_levels(len(d))
    assert set(planes) == set(pos_ops.plane_keys(whole_plane))
    want = _want(d, p, lo, n, qd, qp)
    for levels in sorted({pos_ops.search_levels(n), whole_plane}):
        search = jax.jit(lambda planes, lo, n, qd, qp, levels=levels:
                         pos_ops.window_searchsorted(
                             pos_ops.resident(planes, lo, n, levels), qd, qp))
        got = np.asarray(search(planes, np.int32(lo), np.int32(n), qd, qp))
        assert np.array_equal(got, want), (levels, np.flatnonzero(got != want))
    assert pos_ops.search_levels(n) == (
        1 if n <= 128 else 2 if n <= 16_384 else 3 if n <= 1 << 21 else 4)


@pytest.mark.parametrize("length", [1, 5, 64, 127, 200, 1000, 16_385, 20_001])
def test_window_searchsorted_over_arrays_of_its_own(length):
    """`whole`: an array of pairs whose length is no multiple of a row, its
    tail sentinel-padded, its fences made in the program; alone and a batch
    under `jax.vmap` (the mesh path's form)."""
    import jax
    import jax.numpy as jnp
    from opensearch_tpu.ops import positions as pos_ops
    rng = np.random.default_rng(length)
    batch = []
    for real in sorted({length, max(length - 3, 0), length // 2}):
        d, p = _pairs(rng, real, 4 * length + 1024)
        d = np.concatenate([d, np.full(length - real, pos_ops.INT32_SENTINEL)])
        p = np.concatenate([p, np.full(length - real, pos_ops.INT32_SENTINEL)])
        qd, qp = _queries(rng, d, p, 0, real)
        batch.append((d.astype(np.int32), p.astype(np.int32), qd, qp, real))

    def search(d, p, qd, qp):
        return pos_ops.window_searchsorted(pos_ops.whole(d, p), qd, qp)
    for d, p, qd, qp, real in batch:
        # the padding is part of the array's window: a key past every real
        # pair lands on the first sentinel, the sentinel key itself too
        got = np.asarray(jax.jit(search)(d, p, qd, qp))
        assert np.array_equal(got, _want(d, p, 0, length, qd, qp))
        for levels in (None, 4):    # its own, and a longer term's
            counted = pos_ops.whole(jnp.asarray(d), jnp.asarray(p),
                                    np.int32(real), levels)
            got = np.asarray(pos_ops.window_searchsorted(counted, qd, qp))
            assert np.array_equal(got, _want(d, p, 0, real, qd, qp))
    nq = min(len(b[2]) for b in batch)
    stacked = [np.stack([b[i][:nq] if i > 1 else b[i] for b in batch])
               for i in range(4)]
    got = np.asarray(jax.jit(jax.vmap(search))(*stacked))
    for row, (d, p, qd, qp, _real) in zip(got, batch):
        assert np.array_equal(row, _want(d, p, 0, length, qd[:nq], qp[:nq]))


def test_window_searchsorted_walks_the_anchors_in_passes(monkeypatch):
    """More queries than a pass holds: the same slots, whatever the pass
    (the `pmc` cell's buckets of 2^18 and 2^20 slots walk 2^16 a pass)."""
    import jax.numpy as jnp
    from opensearch_tpu.ops import positions as pos_ops
    rng = np.random.default_rng(7)
    d, p, lo = _plane(rng, 16_385, "middle")
    qd, qp = _queries(rng, d, p, lo, 16_385, count=1500)
    w = pos_ops.whole(jnp.asarray(d), jnp.asarray(p))._replace(
        lo=np.int32(lo), n=np.int32(16_385))
    want = _want(d, p, lo, 16_385, qd, qp)
    assert np.array_equal(np.asarray(
        pos_ops.window_searchsorted(w, qd, qp)), want)
    monkeypatch.setattr(pos_ops, "PASS", 256)
    assert len(qd) % 256 and len(qd) > 4 * 256
    assert np.array_equal(np.asarray(
        pos_ops.window_searchsorted(w, qd, qp)), want)
