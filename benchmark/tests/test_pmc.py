"""The kind `pmc` at a small size on the CPU: the generator's determinism
whatever the thread count, the inversion against a scan of the stream, the
planted segment against one the refresh path built, a stream that never
repeats a phrase and whose twins keep the program shape, the control (the
reference weakened twice has to fail the rule, each by its own limit), the
roofline's byte count, and the timed path broken three ways (`correct`
false each by its own limit)."""

import copy

import numpy as np
import pytest

import pmc_articles as articles
import pmc_control as control
import pmc_reference as reference
import pmc_roofline as roofline
import run

CELL = "pmc.search1.phrase"
DEVICE = {"platform": "cpu-rehearsal", "kind": "none", "count": 1}


def small(ndocs: int = 300, vocabulary: int = 20_000) -> dict:
    """The cell over a few hundred articles of about 2,000 tokens."""
    loaded = copy.deepcopy(run.load_cell(CELL))
    loaded["config"]["ndocs"] = ndocs
    loaded["config"]["generator"].update(
        vocabulary=vocabulary, journals=40, length_mu=7.5,
        length_clip=[100, 8000])
    t = loaded["traffic"]
    t["params"]["rarest_rank"] = [20, 1500]
    t["pool_requests"], t["check_sample"], t["check_fresh"] = 8, 8, 8
    return loaded


@pytest.fixture(scope="module")
def meter():
    return run.CompileMeter()


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    monkeypatch.setattr(run, "memory_peak_bytes", lambda: 0)


def test_the_generator_follows_corpus_seed_alone(monkeypatch):
    gen = small()["config"]["generator"]
    monkeypatch.setattr(articles, "BLOCK_TOKENS", 1 << 16)  # several blocks
    a = articles.generate(200, 7, gen)
    monkeypatch.setattr(articles, "threads", lambda: 1)
    b, c = articles.generate(200, 7, gen), articles.generate(200, 8, gen)
    for k in ("tok", "offsets", "table", "ts_s", "pmid", "journal"):
        assert np.array_equal(a[k], b[k]), k
    assert not np.array_equal(a["lens"], c["lens"])
    assert a["lens"].min() >= 100 and a["lens"].max() <= 8000
    assert len(set(a["pmid"].tolist())) == 200
    # a document's first token follows nothing; a follower is a partner
    tok, table = a["tok"], a["table"]
    led = (table[tok[:-1]] == tok[1:, None]).any(1)
    assert 0.25 < led.mean() < 0.45           # planted, and some by chance
    words = a["words"]
    assert words.of([0, 12345]) == [words[0], words[12345]]
    assert sorted(words.of(range(500))) == words.of(range(500))


def test_the_inversion_is_a_scan_of_the_stream(monkeypatch):
    from concurrent.futures import ThreadPoolExecutor
    monkeypatch.setattr(articles, "BLOCK_TOKENS", 1 << 15)
    a = articles.generate(150, 11, small()["config"]["generator"])
    tok, off = a["tok"], a["offsets"]
    with ThreadPoolExecutor(3) as pool:
        inv = articles.invert(tok, off, a["nterms"], pool)
    doc_of = np.repeat(np.arange(150), a["lens"])
    assert len(inv["positions"]) == len(tok)
    assert np.array_equal(inv["held"], np.unique(tok))
    for row in (0, 3, 40, len(inv["held"]) - 1):
        s0, s1 = inv["starts"][row], inv["starts"][row + 1]
        at = np.flatnonzero(tok == inv["held"][row])
        docs, tfs = np.unique(doc_of[at], return_counts=True)
        assert np.array_equal(inv["doc_ids"][s0:s1], docs)
        assert np.array_equal(inv["tfs"][s0:s1], tfs)
        assert np.array_equal(
            inv["positions"][inv["pos_starts"][s0]: inv["pos_starts"][s1]],
            at - off[doc_of[at]])


def test_the_planted_segment_is_what_a_refresh_builds():
    """60 short articles indexed through the client and refreshed, against
    the same articles planted: positional postings, columns, document
    lengths and impacts equal, array for array."""
    from opensearch_tpu.rest.client import RestClient
    n = 60
    gen = dict(small()["config"]["generator"], vocabulary=3000,
               length_mu=5.0, length_clip=[20, 400])
    arts = articles.generate(n, 41, gen)
    settings = {"number_of_shards": 1, "number_of_replicas": 0}
    planted = articles.plant_index(RestClient(), "bench", arts, settings)
    client = RestClient()
    client.indices.create("real", {"settings": settings,
                                   "mappings": articles.MAPPING})
    for i in range(n):
        client.index("real", planted.sources[i], id=str(i))
    client.indices.refresh("real")
    (built,) = client.node.indices["real"].shards[0].segments
    assert set(built.postings) == set(planted.postings) \
        == set(articles.TEXT_FIELDS) | {"name", "accession"}
    for f, a in built.postings.items():
        b = planted.postings[f]
        assert a.vocab == b.vocab, f
        names = ("starts", "doc_ids", "tfs") + (
            ("pos_starts", "positions") if f in articles.TEXT_FIELDS else ())
        for name in names:
            assert np.array_equal(getattr(a, name), getattr(b, name)), \
                (f, name)
        assert (a.impact is None) == (b.impact is None), f
        if a.impact is not None:
            assert np.array_equal(a.impact.q, b.impact.q)
            assert a.impact.scale == b.impact.scale
    assert planted.postings["body"].impact is not None
    assert set(built.numeric_cols) == set(planted.numeric_cols) \
        == {"timestamp", "pmid"}
    for f, a in built.numeric_cols.items():
        b = planted.numeric_cols[f]
        assert a.kind == b.kind and np.array_equal(a.values, b.values), f
    assert set(built.keyword_cols) == set(planted.keyword_cols)
    for f, a in built.keyword_cols.items():
        b = planted.keyword_cols[f]
        assert a.vocab == b.vocab
        for name in ("starts", "ords", "doc_of_value", "min_ord"):
            assert np.array_equal(getattr(a, name), getattr(b, name)), f
    assert set(built.doc_lens) == set(planted.doc_lens) \
        == set(articles.TEXT_FIELDS)
    for f in articles.TEXT_FIELDS:
        assert np.array_equal(built.doc_lens[f], planted.doc_lens[f]), f
    assert built.text_stats == planted.text_stats


def _stream(arts, loaded, seed):
    kind = run.load_kind("pmc")
    return kind.stream({"articles": arts}, loaded["traffic"], seed)


def test_a_stream_never_repeats_a_phrase_twins_included():
    loaded = small()
    arts = articles.generate(300, 5, loaded["config"]["generator"])
    stream = _stream(arts, loaded, 3)
    pool = stream.take(24)
    shapes = loaded["traffic"]["params"]["shapes"]
    assert [s["shape"] for s in pool] == (shapes * 3)
    assert shapes.count("phrase2") == 5 and shapes.count("phrase3") == 2 \
        and shapes.count("phrase3_common") == 1
    twins = [stream.twin(s) for s in pool]
    stream.reseed(4)
    fresh = stream.take(16)
    bodies = [str(s["body"]) for s in pool + twins + fresh]
    assert len(set(bodies)) == len(bodies)
    table, cf = arts["table"], articles.collection_frequency(arts)
    rank = np.empty(len(cf), np.int64)
    rank[np.argsort(-cf, kind="stable")] = np.arange(len(cf))
    for s, t in zip(pool, twins):
        assert t["shape"] == s["shape"]
        assert stream._key(tuple(t["terms"])) == stream._key(tuple(s["terms"]))
    for s in pool + twins + fresh:
        terms = s["terms"]
        assert all(b in table[a] for a, b in zip(terms, terms[1:]))
        assert 20 <= max(rank[t] for t in terms) <= 1500
        assert s["weight"] == min(cf[t] for t in terms)
        assert len(terms) == (2 if s["shape"] == "phrase2" else 3)
        if s["shape"] == "phrase3_common":
            assert rank[terms[1]] < 32
        assert s["body"] == {"query": {"match_phrase": {
            "body": " ".join(arts["words"][t] for t in terms)}}}


@pytest.mark.parametrize("corpus_seed", [5, 3000000019])
def test_the_weakened_references_fail_the_rule(corpus_seed):
    loaded = small()
    arts = articles.generate(300, corpus_seed, loaded["config"]["generator"])
    specs = _stream(arts, loaded, 1).take(16)
    exact = reference.Reference(arts["tok"], arts["offsets"], arts["live"])
    held = [(s, reference.as_response(exact.page(s["terms"])))
            for s in specs]
    assert reference.hold(held, exact, 1e-5)["correct"] is True
    out = control.run(arts, specs, 1e-5, exact)
    for how, limit in control.CONTROLS.items():
        value, bound = out[how]["numbers"][limit]
        assert out[how]["correct"] is False and value > bound, how
    assert out["presence"]["numbers"]["total_violations"] == [0, 0]


def test_the_roofline_counts_a_phrases_least_reading():
    # "1 2" in three documents: d0 = 1 2 9 1 2, d1 = 2 1 9, d2 = 9 9 1
    tok = np.asarray([1, 2, 9, 1, 2, 2, 1, 9, 9, 9, 1], np.int32)
    off = np.asarray([0, 5, 8, 11], np.int64)
    ref = reference.Reference(tok, off, np.ones(3, bool))
    docs, f = ref.frequencies([1, 2])
    assert docs.tolist() == [0] and f.tolist() == [2]
    # rarest term 2: two postings; both terms inside the documents that
    # hold both (d0, d1): term 2 three positions, term 1 three
    assert ref.occurrence_bytes([1, 2]) == 4.0 * (2 + 3 + 3)
    # the share's bytes are the traced requests' own: the window's first
    roofline.note_window(ref, [(1, 2), (9, 1), (2, 1)])
    assert roofline.query_bytes({"trace": {"requests": 1}}) == 32.0
    two = (32.0 + ref.occurrence_bytes([9, 1])) / 2
    assert roofline.query_bytes({"trace": {"requests": 2}}) == two
    roofline.note_window(ref, [])
    assert roofline.query_bytes({"trace": {"requests": 2}}) is None


def test_the_cell_holds_the_rule_at_300_articles(meter, tmp_path):
    result = run.run_cell(small(), 3000000011, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    numbers = result["compared"]
    assert numbers["score_rel_err_max"][0] <= 1e-5
    assert all(numbers[k] == [0, 0] for k in numbers
               if k != "score_rel_err_max")
    assert {"qps", "p50_ms", "setup_s"} <= set(result["metrics"])
    assert "p95_ms" not in result["metrics"]


def _broken(monkeypatch, breaker):
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.search

    def search(self, *a, **kw):
        resp = real(self, *a, **kw)
        if resp["hits"]["hits"]:
            breaker(resp["hits"])
        return resp
    monkeypatch.setattr(RestClient, "search", search)


def test_an_occurrence_dropped_is_not_correct(meter, tmp_path, monkeypatch):
    """A document that holds the phrase once goes missing from the total."""
    def drop(hits):
        hits["total"]["value"] -= 1
    _broken(monkeypatch, drop)
    result = run.run_cell(small(), 8, 60, False, DEVICE, meter, str(tmp_path))
    assert result["correct"] is False
    assert result["compared"]["total_violations"][0] > 0
    assert result["compared"]["rank_violations"] == [0, 0]


def test_a_score_moved_in_the_fourth_digit_is_not_correct(meter, tmp_path,
                                                           monkeypatch):
    def move(hits):
        for h in hits["hits"]:
            h["_score"] *= 1.0002
    _broken(monkeypatch, move)
    result = run.run_cell(small(), 9, 60, False, DEVICE, meter, str(tmp_path))
    assert result["correct"] is False
    assert result["compared"]["score_rel_err_max"][0] > 1e-5
    assert result["compared"]["total_violations"] == [0, 0]
    assert result["compared"]["rank_violations"] == [0, 0]


def test_two_ranks_swapped_is_not_correct(meter, tmp_path, monkeypatch):
    """The first two hits of distinct scores change places, ids alone (the
    scores stay in order)."""
    def swap(hits):
        h = hits["hits"]
        for i in range(len(h) - 1):
            if h[i]["_score"] > h[i + 1]["_score"] * (1 + 1e-3):
                h[i]["_id"], h[i + 1]["_id"] = h[i + 1]["_id"], h[i]["_id"]
                break
    _broken(monkeypatch, swap)
    result = run.run_cell(small(), 10, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is False
    assert result["compared"]["rank_violations"][0] > 0
    assert result["compared"]["score_rel_err_max"][0] <= 1e-5
    assert result["compared"]["total_violations"] == [0, 0]
