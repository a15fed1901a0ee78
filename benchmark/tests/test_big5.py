"""The kind `big5` at a small size on the CPU: the generator's determinism,
the planted segment against one the refresh path built, a stream that never
repeats a body, the control (the reference weakened twice has to fail the
rule), the roofline's byte count, and a broken timed path (`correct`
false)."""

import copy
import json

import numpy as np
import pytest

import big5_control as control
import big5_events as events
import big5_reference as reference
import big5_roofline as roofline
import run

CELL = "big5.search1.terms"
DEVICE = {"platform": "cpu-rehearsal", "kind": "none", "count": 1}


def small(ndocs: int = 20_000) -> dict:
    loaded = copy.deepcopy(run.load_cell(CELL))
    loaded["config"]["ndocs"] = ndocs
    t = loaded["traffic"]
    t["pool_requests"], t["check_sample"], t["check_fresh"] = 14, 14, 7
    return loaded


@pytest.fixture(scope="module")
def meter():
    return run.CompileMeter()


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    monkeypatch.setattr(run, "memory_peak_bytes", lambda: 0)


def test_the_generator_follows_corpus_seed_alone():
    gen = small()["config"]["generator"]
    a, b = events.generate(5000, 7, gen), events.generate(5000, 7, gen)
    c = events.generate(5000, 8, gen)
    for k in ("ts_s", "ingested_ms", "ingestion_ms", "size", "tmin", "words",
              "agent", "host_octets"):
        assert np.array_equal(a[k], b[k]), k
    for f, (codes, values) in a["kw"].items():
        assert np.array_equal(codes, b["kw"][f][0]), f
        assert [values[i] for i in range(min(len(values), 50))] == \
            [b["kw"][f][1][i] for i in range(min(len(values), 50))]
    assert not np.array_equal(a["ts_s"], c["ts_s"])
    # arrival order, whole seconds inside the span
    assert (np.diff(a["ts_s"]) >= 0).all()
    assert a["ts_s"][0] >= events.SPAN_START_S
    assert a["ts_s"][-1] < events.SPAN_START_S + events.SPAN_S
    assert (a["ingested_ms"] >= a["ts_s"] * 1000 + 60_000).all()
    # a stream is one agent's, and the agent one region's
    stream, _names = a["kw"]["aws.cloudwatch.log_stream"]
    for field in ("agent.name", "cloud.region", "host.name",
                  "aws.cloudwatch.log_group", "log.file.path"):
        codes, _v = a["kw"][field]
        pairs = np.unique(np.stack([stream, codes]), axis=1)
        assert pairs.shape[1] == len(np.unique(stream)), field
    assert set(a["kw"]) == set(events.KEYWORDS)
    assert len(set(events.name_words(256)[0])) == 256
    assert len(set(a["dictionary"])) == len(a["dictionary"])


def test_the_planted_segment_is_what_a_refresh_builds():
    """1,500 events indexed through the client and refreshed, against the
    same events planted: postings (the analyzed message's included),
    columns, document lengths and impacts equal, array for array."""
    from opensearch_tpu.rest.client import RestClient
    n = 1500
    cols = events.generate(n, 41, small()["config"]["generator"])
    settings = {"number_of_shards": 1, "number_of_replicas": 0}
    planted = events.plant_index(RestClient(), "bench", cols, settings)
    client = RestClient()
    client.indices.create("real", {"settings": settings,
                                   "mappings": events.MAPPING})
    for i in range(n):
        client.index("real", planted.sources[i], id=str(i))
    client.indices.refresh("real")
    (built,) = client.node.indices["real"].shards[0].segments
    assert set(built.postings) == set(planted.postings) \
        == set(events.KEYWORDS) | {"message"}
    for f, a in built.postings.items():
        b = planted.postings[f]
        assert a.vocab == b.vocab, f
        for name in ("starts", "doc_ids", "tfs"):
            assert np.array_equal(getattr(a, name), getattr(b, name)), \
                (f, name)
        assert (a.impact is None) == (b.impact is None), f
        if a.impact is not None:
            assert np.array_equal(a.impact.q, b.impact.q)
            assert a.impact.scale == b.impact.scale
    assert planted.postings["message"].impact is not None
    assert set(built.numeric_cols) == set(planted.numeric_cols) \
        == set(events.DATES + events.LONGS)
    for f, a in built.numeric_cols.items():
        b = planted.numeric_cols[f]
        assert a.kind == b.kind and a.values.dtype == b.values.dtype, f
        assert np.array_equal(a.values, b.values), f
        assert np.array_equal(a.present, b.present)
    assert set(built.keyword_cols) == set(planted.keyword_cols) \
        == set(events.KEYWORDS)
    for f, a in built.keyword_cols.items():
        b = planted.keyword_cols[f]
        assert a.vocab == b.vocab
        for name in ("starts", "ords", "doc_of_value", "min_ord"):
            assert np.array_equal(getattr(a, name), getattr(b, name)), f
    assert set(built.doc_lens) == set(planted.doc_lens) == {"message"}
    assert np.array_equal(built.doc_lens["message"],
                          planted.doc_lens["message"])
    assert built.text_stats == planted.text_stats
    assert built.geo_cols == planted.geo_cols == {}


def test_a_stream_never_repeats_a_body_twins_included():
    loaded = small()
    kind = run.load_kind("big5")
    stream = kind.stream({}, loaded["traffic"], 11)
    specs = stream.take(350)
    assert [s["shape"] for s in specs[:7]] == list(reference.SHAPES)
    stream.reseed(12)
    specs += stream.take(350)
    bodies = [json.dumps(s["body"], sort_keys=True) for s in specs]
    bodies += [json.dumps(stream.twin(s)["body"], sort_keys=True)
               for s in specs]
    assert len(set(bodies)) == len(bodies) == 1400
    for s in specs:
        hours = (s["hi_s"] - s["lo_s"]) / 3600.0
        if s["shape"] in kind.RANGED:
            assert 2 - 1e-3 <= hours <= 24
        else:
            assert 0.5 * 24 * events.SPAN_DAYS - 1e-3 <= hours \
                <= 24 * events.SPAN_DAYS
        assert s["lo_s"] >= events.SPAN_START_S
        assert s["hi_s"] <= events.SPAN_START_S + events.SPAN_S
        assert s["lo_s"] % 2 == 0 and s["hi_s"] % 2 == 0
        t = stream.twin(s)
        assert (t["shape"], t["lo_s"]) == (s["shape"], s["lo_s"])
        assert t["hi_s"] == s["hi_s"] + 1
        assert s["body"]["size"] == 0


@pytest.mark.parametrize("corpus_seed", [20230101, 3000000043])
def test_the_weakened_references_fail_the_rule(corpus_seed):
    loaded = small()
    columns = events.generate(200_000, corpus_seed,
                              loaded["config"]["generator"])
    kind = run.load_kind("big5")
    specs = kind.stream({"columns": columns}, loaded["traffic"], 5).take(28)
    out = control.run(columns, specs)
    halves, early = out["float16_counts"], out["top_before_mask"]
    assert halves["correct"] is False and early["correct"] is False
    # each by its own limits, and by nothing else
    assert halves["numbers"]["bucket_mismatches"][0] > 20
    assert early["numbers"]["bucket_mismatches"][0] > 20
    for numbers in (halves["numbers"], early["numbers"]):
        assert all(v == [0, 0] for k, v in numbers.items() if k in (
            "error_responses", "total_mismatches", "cardinality_mismatches"))
    # and the exact reference holds its own answers
    exact = reference.Reference(columns)
    held = [(s, reference.as_response(exact.answer(s), s)) for s in specs]
    own = reference.hold(held, exact)
    assert own["correct"] is True and own["compared"] == 28


def test_the_reference_pages_a_composite_with_after():
    columns = events.generate(20_000, 5, small()["config"]["generator"])
    ref = reference.Reference(columns)
    spec = {"shape": "composite_terms-keyword",
            "lo_s": events.SPAN_START_S,
            "hi_s": events.SPAN_START_S + events.SPAN_S}
    seen, after = [], None
    while True:
        page = ref.answer(dict(spec, after=after))["buckets"]
        if not page:
            break
        seen += [tuple(k.values()) for k, _c in page]
        after = page[-1][0]
    m = np.ones(20_000, bool)
    triples = {tuple(columns["kw"][f][1][int(columns["kw"][f][0][d])]
                     for f in (reference.PROCESS, reference.REGION,
                               reference.STREAM)) for d in range(20_000)}
    assert len(seen) == len(set(seen)) == len(triples)
    assert set(seen) == triples and m.all()
    # process descending, then region and stream ascending
    assert seen == sorted(seen, key=lambda k: (
        tuple(-ord(ch) for ch in k[0]) + (1,), k[1], k[2]))


def test_the_roofline_counts_the_planes_a_request_names():
    rows = roofline.padded_rows(16_571_428)
    assert rows == 1 << 24 and roofline.padded_rows(16) == 16
    assert roofline.request_bytes("keyword-terms", rows) == 12.0 * rows
    assert roofline.request_bytes("composite_terms-keyword", rows) \
        == 16.0 * rows
    assert roofline.request_bytes("multi_terms-keyword", rows) == 12.0 * rows
    ctx = {"window": {"queries": 14, "counters": {
        "executor.launches": 14, "aggs.terms.ordinals": 1}}}
    want = 4.0 * rows * (3 + 3 + 3 + 3 + 4 + 3 + 3) / 7
    assert roofline.query_bytes(ctx) == pytest.approx(want)
    # a program without the counters: nothing to read
    assert roofline.query_bytes({"window": {"queries": 14, "counters": {
        "executor.launches": 14}}}) is None


def test_the_cell_holds_the_rule_at_20000_events(meter, tmp_path):
    result = run.run_cell(small(), 3000000011, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    assert all(v == [0, 0] for v in result["compared"].values())
    assert {"qps", "p50_ms", "setup_s"} <= set(result["metrics"])
    assert "p95_ms" not in result["metrics"]


def test_a_count_off_by_one_is_not_correct(meter, tmp_path, monkeypatch):
    """One bucket's count moved by one where it is produced."""
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.search

    def one_too_many(self, *a, **kw):
        resp = real(self, *a, **kw)
        for agg in resp.get("aggregations", {}).values():
            if agg.get("buckets"):
                agg["buckets"][0]["doc_count"] += 1
        return resp
    monkeypatch.setattr(RestClient, "search", one_too_many)
    result = run.run_cell(small(), 8, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is False
    assert result["compared"]["bucket_mismatches"][0] > 0
    assert result["compared"]["cardinality_mismatches"] == [0, 0]


def test_a_tie_broken_the_other_way_is_not_correct(meter, tmp_path,
                                                   monkeypatch):
    """Two neighbours of equal count swapped in every `terms` answer."""
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.search

    def ties_reversed(self, *a, **kw):
        resp = real(self, *a, **kw)
        b = resp.get("aggregations", {}).get("station", {}).get("buckets", [])
        for i in range(len(b) - 1):
            if b[i]["doc_count"] == b[i + 1]["doc_count"]:
                b[i], b[i + 1] = b[i + 1], b[i]
                break
        return resp
    monkeypatch.setattr(RestClient, "search", ties_reversed)
    result = run.run_cell(small(), 9, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is False
    assert result["compared"]["bucket_mismatches"][0] > 0
    assert result["compared"]["other_count_mismatches"] == [0, 0]


def test_a_cardinality_off_by_one_is_not_correct(meter, tmp_path,
                                                 monkeypatch):
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.search

    def one_agent_more(self, *a, **kw):
        resp = real(self, *a, **kw)
        if "agent" in resp.get("aggregations", {}):
            resp["aggregations"]["agent"]["value"] += 1
        return resp
    monkeypatch.setattr(RestClient, "search", one_agent_more)
    result = run.run_cell(small(), 10, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is False
    assert result["compared"]["cardinality_mismatches"][0] > 0
    assert result["compared"]["bucket_mismatches"] == [0, 0]
