"""The kind `nested` at a small size on the CPU: the generator's
determinism and laws, the planted segments against what the refresh path
built, a stream that never repeats a body, the controls (the reference
weakened four ways has to fail the rule, each by the limit named), the
roofline's byte count, and a timed path broken four ways (`correct` false,
each by its own limit alone)."""

import copy
import json

import numpy as np
import pytest

import nested_control as control
import nested_questions as questions
import nested_reference as reference
import nested_roofline as roofline
import run

CELL = "nested.search1.answers"
DEVICE = {"platform": "cpu-rehearsal", "kind": "none", "count": 1}


def small(ndocs: int = 4000) -> dict:
    loaded = copy.deepcopy(run.load_cell(CELL))
    c, t = loaded["config"], loaded["traffic"]
    c["ndocs"] = ndocs
    c["generator"].update(tags=400, dictionary_words=2000)
    t["params"]["tag_rank"] = [1, 150]
    t["pool_requests"], t["check_sample"], t["check_fresh"] = 10, 10, 10
    return loaded


@pytest.fixture(scope="module")
def meter():
    return run.CompileMeter()


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    monkeypatch.setattr(run, "memory_peak_bytes", lambda: 0)


def test_the_generator_follows_corpus_seed_alone():
    gen = small()["config"]["generator"]
    a, b = questions.generate(5000, 7, gen), questions.generate(5000, 7, gen)
    c = questions.generate(5000, 8, gen)
    keys = ("created_ms", "asker", "tag_off", "tags", "title_off",
            "title_tok", "ans_off", "ans_date_ms", "ans_user")
    for k in keys:
        assert np.array_equal(a[k], b[k]), k
    assert not np.array_equal(a["ans_off"], c["ans_off"])
    # creation order inside the span; an answer no earlier than its question
    assert (np.diff(a["created_ms"]) >= 0).all()
    assert a["created_ms"][0] >= questions.SPAN_START_MS
    assert a["created_ms"][-1] < questions.SPAN_END_MS
    per = np.diff(a["ans_off"])
    assert (a["ans_date_ms"] >= np.repeat(a["created_ms"], per) + 60_000).all()
    assert (np.diff(a["ans_date_ms"]) < 0).any()        # in no row order
    # the laws: answers a question, tags a question, no tag twice
    assert per.min() == 0 and per.max() <= 30
    assert 0.09 < (per == 0).mean() < 0.16 and 1.4 < per.mean() < 2.0
    ntags = np.diff(a["tag_off"])
    assert ntags.min() >= 1 and ntags.max() <= 5 and 2.8 < ntags.mean() < 3.2
    doc = np.repeat(np.arange(5000), ntags)
    assert len(np.unique(doc * 1000 + a["tags"])) == len(a["tags"])
    words = np.diff(a["title_off"])
    assert words.min() >= 5 and words.max() <= 15
    assert len(set(a["tag_names"])) == len(a["tag_names"])


def test_the_tag_law_at_the_cells_parameters():
    """The commonest tag on about 8% of the questions, the 20,000th on a
    few dozen of 11.2M: read at 400,000 questions and scaled."""
    gen = run.load_cell(CELL)["config"]["generator"]
    n = 400_000
    off, tags = questions._tags(np.random.default_rng(3), n, gen)
    c = np.sort(np.bincount(tags, minlength=gen["tags"]))[::-1]
    assert 0.07 < c[0] / n < 0.09
    assert 20 < c[19_999] * (11_203_029 / n) < 120
    assert len(tags) / n == pytest.approx(3.0, abs=0.01)


def test_a_vocabulary_of_row_numbers_sorts_as_strings_do():
    for n in (1, 9, 10, 11, 101, 1234):
        v = questions._RowNumbers(n)
        assert list(v) == sorted(str(i) for i in range(n)), n
        assert [v.row_of(s) for s in v] == list(range(n))
    v = questions._RowNumbers(50)
    assert v.row_of("50") is None and v.row_of("07") is None \
        and v.row_of("x") is None
    u = questions._UserNames(np.asarray([3, 17, 2999999]))
    assert list(u) == ["u0000003", "u0000017", "u2999999"]
    assert [u.row_of(s) for s in u] == [0, 1, 2]
    assert u.row_of("u0000004") is None and u.row_of("v0000003") is None
    terms = questions._Terms(u, u.row_of)
    assert terms.get("u0000017", -1) == 1 and terms.get("nobody", -1) == -1
    assert "u0000003" in terms and len(terms) == 3


def test_postings_hold_a_row_past_two_to_the_24():
    """The child space has more rows than the parents' 2^24: a packed
    (term, row) key keeps both whole."""
    rows = np.asarray([1, 0, 1, 0, 1], np.int32)
    docs = np.asarray([(1 << 24) + 5, 3, 5, (1 << 25) - 1, 5], np.int64)
    starts, doc_ids, tfs = questions._by_term(rows, docs, 2)
    assert starts.tolist() == [0, 2, 4]
    assert doc_ids.tolist() == [3, (1 << 25) - 1, 5, (1 << 24) + 5]
    assert tfs.tolist() == [1.0, 1.0, 2.0, 1.0]


def _same_postings(a, b, tag):
    assert list(a.vocab) == list(b.vocab), tag
    for name in ("starts", "doc_ids", "tfs"):
        assert np.array_equal(getattr(a, name), getattr(b, name)), (tag, name)
    assert (a.impact is None) == (b.impact is None), tag
    if a.impact is not None:
        assert np.array_equal(a.impact.q, b.impact.q)
        assert a.impact.scale == b.impact.scale


def _same_segment(built, planted, tag):
    assert built.ndocs == planted.ndocs
    assert set(built.postings) == set(planted.postings), tag
    for f, a in built.postings.items():
        _same_postings(a, planted.postings[f], (tag, f))
    assert set(built.numeric_cols) == set(planted.numeric_cols)
    for f, a in built.numeric_cols.items():
        b = planted.numeric_cols[f]
        assert a.kind == b.kind and a.values.dtype == b.values.dtype, f
        assert np.array_equal(a.values, b.values), f
        assert np.array_equal(a.present, b.present)
    assert set(built.keyword_cols) == set(planted.keyword_cols)
    for f, a in built.keyword_cols.items():
        b = planted.keyword_cols[f]
        assert list(a.vocab) == list(b.vocab)
        for name in ("starts", "ords", "doc_of_value", "min_ord"):
            assert np.array_equal(getattr(a, name), getattr(b, name)), \
                (tag, f, name)
    assert set(built.doc_lens) == set(planted.doc_lens)
    for f in built.doc_lens:
        assert np.array_equal(built.doc_lens[f], planted.doc_lens[f])
    assert built.text_stats == planted.text_stats
    # (`corpus._LazyIds` has no end to iterate to: slice it)
    assert list(built.ids) == planted.ids[:], tag


def test_the_planted_segments_are_what_a_refresh_builds():
    """700 questions indexed through the client and refreshed, against the
    same questions planted: both row spaces equal array for array, the
    parent map, the ids and the `_source`s with them."""
    from opensearch_tpu.rest.client import RestClient
    n = 700
    q = questions.generate(n, 41, small()["config"]["generator"])
    settings = {"number_of_shards": 1, "number_of_replicas": 0}
    planted = questions.plant_index(RestClient(), "bench", q, settings)
    client = RestClient()
    client.indices.create("real", {"settings": settings,
                                   "mappings": questions.MAPPING})
    for i in range(n):
        client.index("real", planted.sources[i], id=str(i))
    client.indices.refresh("real")
    (built,) = client.node.indices["real"].shards[0].segments
    _same_segment(built, planted, "questions")
    a, b = built.nested[questions.PATH], planted.nested[questions.PATH]
    assert np.array_equal(a.parent_of, b.parent_of)
    assert a.parent_of.dtype == b.parent_of.dtype
    _same_segment(a.child, b.child, "answers")
    assert [built.sources[i] for i in (0, 5, n - 1)] == \
        [planted.sources[i] for i in (0, 5, n - 1)]
    assert [a.child.sources[i] for i in (0, 7)] == \
        [b.child.sources[i] for i in (0, 7)]
    assert planted.kw_multi_valued("tag") and not \
        planted.kw_multi_valued("user")


def test_a_stream_never_repeats_a_body_twins_included():
    loaded = small()
    q = questions.generate(4000, 5, loaded["config"]["generator"])
    kind = run.load_kind("nested")
    built = {"questions": q}
    stream = kind.stream(built, loaded["traffic"], 11)
    specs = stream.take(300)
    assert built["pool"] is specs
    assert [s["shape"] for s in specs[:5]] == \
        loaded["traffic"]["params"]["shapes"]
    stream.reseed(12)
    specs += stream.take(100)
    twins = [stream.twin(s) for s in specs]
    bodies = [json.dumps(s["body"], sort_keys=True) for s in specs + twins]
    assert len(set(bodies)) == len(bodies) == 800
    df = questions.tag_question_counts(q)
    ranks = np.argsort(np.argsort(-df, kind="stable"), kind="stable")
    for s, t in zip(specs, twins):
        assert ranks[s["tag"]] < 150 and s["weight"] == df[s["tag"]]
        assert t["shape"] == s["shape"] and t["size"] == s["size"]
        assert t["tag"] == s["tag"]
        if s["child"] is None:
            assert "sort" in s["body"] and "size" in s["body"]
            (by,) = s["body"]["sort"]
            assert list(by["answers.date"]) == ["mode", "order", "nested"]
            assert t["body"]["sort"][0]["answers.date"]["missing"] == "_last"
        else:
            d = s["child"]["date_lte_ms"]
            assert t["child"]["date_lte_ms"] == d + 1 and d % 2 == 0
            assert stream.dates[0] <= d <= stream.dates[1]
    big = [s for s in specs if s["shape"] == "inner_hits_big"][0]["body"]
    clause = big["query"]["bool"]["must"][1]["nested"]
    assert big["size"] == 100 and clause["inner_hits"] == {"size": 100}
    assert list(clause["query"]["bool"]) == ["filter"]


@pytest.mark.parametrize("corpus_seed", [20140914, 3000000043])
def test_the_weakened_references_fail_the_rule(corpus_seed):
    loaded = small()
    q = questions.generate(20_000, corpus_seed,
                           loaded["config"]["generator"])
    kind = run.load_kind("nested")
    specs = kind.stream({"questions": q}, loaded["traffic"], 5).take(30)
    out = control.run(q, specs)
    for how, limits in control.CONTROLS.items():
        numbers = out[how]["numbers"]
        assert out[how]["correct"] is False, how
        # each by the limits named for it, and by nothing else (but a page
        # that `any_answer` fills and the exact answer does not)
        for k, (v, limit) in numbers.items():
            if (how, k) != ("any_answer", "length_mismatches"):
                assert (v > limit) == (k in limits), (how, k, v)
    assert out["bfloat16_score"]["numbers"]["score_rel_err_max"][0] > 1e-4
    # and the exact reference holds its own answers
    exact = reference.Reference(q)
    held = [(s, reference.as_response(exact.answer(s), s)) for s in specs]
    own = reference.hold(held, exact)
    assert own["correct"] is True and own["compared"] == 30
    assert all(v == 0 for v, _limit in own["numbers"].values())


def test_the_roofline_counts_the_clause_from_the_data_alone():
    assert roofline.clause_bytes(19_000_000, 11_203_029) == \
        12 * 19_000_000 + 4 * 11_203_029
    ctx = {"trace": {"requests": 4}}
    roofline._window.clear()
    assert roofline.query_bytes(ctx) is None        # no window noted
    roofline.note_window(100, 10, [True, False, True, True, False])
    assert roofline.query_bytes(ctx) == 1240 * 3 / 4
    assert roofline.query_bytes({"trace": {"requests": 2}}) == 1240 / 2
    roofline._window.clear()


def test_the_cell_holds_the_rule_at_4000_questions(meter, tmp_path):
    result = run.run_cell(small(), 3000000011, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    assert all(v <= limit for v, limit in result["compared"].values())
    assert {"qps", "p50_ms", "setup_s"} <= set(result["metrics"])
    assert "p95_ms" not in result["metrics"]


def _broken(monkeypatch, change):
    """`RestClient.search` with `change(response)` applied where the
    response is produced."""
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.search

    def search(self, *a, **kw):
        resp = real(self, *a, **kw)
        change(resp)
        return resp
    monkeypatch.setattr(RestClient, "search", search)


def _alone(result, name):
    assert result["correct"] is False
    for k, (v, limit) in result["compared"].items():
        assert (v > limit) == (k == name), (k, v)


def test_a_matching_question_dropped_is_not_correct(meter, tmp_path,
                                                    monkeypatch):
    def one_less(resp):
        if resp["hits"]["total"]["value"]:
            resp["hits"]["total"]["value"] -= 1
    _broken(monkeypatch, one_less)
    _alone(run.run_cell(small(), 8, 60, False, DEVICE, meter,
                        str(tmp_path)), "total_mismatches")


def test_two_tied_ranks_swapped_are_not_correct(meter, tmp_path,
                                                monkeypatch):
    def swapped(resp):
        hits = resp["hits"]["hits"]
        if len(hits) > 1 and "sort" not in hits[0] \
                and hits[0]["_score"] == hits[1]["_score"]:
            hits[0], hits[1] = hits[1], hits[0]
    _broken(monkeypatch, swapped)
    _alone(run.run_cell(small(), 9, 60, False, DEVICE, meter,
                        str(tmp_path)), "rank_mismatches")


def test_an_inner_hit_one_offset_on_is_not_correct(meter, tmp_path,
                                                   monkeypatch):
    def moved(resp):
        for hit in resp["hits"]["hits"]:
            inner = hit.get("inner_hits", {}).get("answers")
            if inner and inner["hits"]["hits"]:
                inner["hits"]["hits"][0]["_nested"]["offset"] += 1
                return
    _broken(monkeypatch, moved)
    _alone(run.run_cell(small(), 10, 60, False, DEVICE, meter,
                        str(tmp_path)), "inner_offset_mismatches")


def test_a_sort_value_a_millisecond_on_is_not_correct(meter, tmp_path,
                                                      monkeypatch):
    def later(resp):
        hits = resp["hits"]["hits"]
        if hits and hits[0].get("sort") and hits[0]["sort"][0] is not None:
            hits[0]["sort"][0] += 1.0
    _broken(monkeypatch, later)
    _alone(run.run_cell(small(), 11, 60, False, DEVICE, meter,
                        str(tmp_path)), "sort_value_mismatches")
