"""The kind `http_logs` at 20,000 events on the CPU: its control (the
reference in float32 has to fail the rule), a broken timed path (`correct`
false), and the planted segment against one the refresh path built."""

import copy

import numpy as np
import pytest

import http_logs_control as control
import http_logs_events as events
import run

CELL = "httplogs.search1.dashboard"
DEVICE = {"platform": "cpu-rehearsal", "kind": "none", "count": 1}


def small(ndocs: int = 20_000) -> dict:
    loaded = copy.deepcopy(run.load_cell(CELL))
    loaded["config"]["ndocs"] = ndocs
    t = loaded["traffic"]
    t["pool_requests"], t["check_sample"], t["check_fresh"] = 16, 16, 8
    return loaded


@pytest.fixture(scope="module")
def meter():
    return run.CompileMeter()


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    monkeypatch.setattr(run, "memory_peak_bytes", lambda: 0)


@pytest.mark.parametrize("corpus_seed", [19980430, 3000000021])
def test_the_reference_in_float32_fails_the_rule(corpus_seed):
    loaded = small()
    columns = events.generate(20_000, corpus_seed,
                              loaded["config"]["generator"])
    kind = run.load_kind("http_logs")
    specs = kind.stream({"columns": columns}, loaded["traffic"], 5).take(64)
    out = control.run(columns, specs)
    assert out["correct"] is False and out["compared"] == 64
    n = out["numbers"]
    # by the bounds, the buckets and the sort values at once, not by one
    assert n["total_mismatches"][0] > 0
    assert n["bucket_mismatches"][0] > 0
    assert n["sort_value_mismatches"][0] > 0
    assert n["error_responses"] == [0, 0]


def test_the_cell_holds_the_rule_at_20000_events(meter, tmp_path):
    result = run.run_cell(small(), 3000000011, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    assert all(v == [0, 0] for v in result["compared"].values())
    assert {"qps", "p50_ms", "setup_s"} <= set(result["metrics"])


def test_a_count_off_by_one_is_not_correct(meter, tmp_path, monkeypatch):
    """One hourly bucket's count moved by one where it is produced."""
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.search

    def one_too_many(self, *a, **kw):
        resp = real(self, *a, **kw)
        for agg in resp.get("aggregations", {}).values():
            if agg["buckets"]:
                agg["buckets"][0]["doc_count"] += 1
        return resp
    monkeypatch.setattr(RestClient, "search", one_too_many)
    result = run.run_cell(small(), 8, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is False
    assert result["compared"]["bucket_mismatches"][0] > 0
    assert result["compared"]["sort_value_mismatches"] == [0, 0]


def test_a_dropped_hit_is_not_correct(meter, tmp_path, monkeypatch):
    """The first hit of every sorted page left out."""
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.search

    def first_hit_lost(self, *a, **kw):
        resp = real(self, *a, **kw)
        if resp["hits"]["hits"] and "sort" in resp["hits"]["hits"][0]:
            del resp["hits"]["hits"][0]
        return resp
    monkeypatch.setattr(RestClient, "search", first_hit_lost)
    result = run.run_cell(small(), 9, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is False
    assert result["compared"]["rank_mismatches"][0] > 0
    assert result["compared"]["bucket_mismatches"] == [0, 0]


def test_a_total_one_short_is_not_correct(meter, tmp_path, monkeypatch):
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.search

    def one_short(self, *a, **kw):
        resp = real(self, *a, **kw)
        if resp["hits"]["total"]["relation"] == "eq":
            resp["hits"]["total"]["value"] += 1
        return resp
    monkeypatch.setattr(RestClient, "search", one_short)
    result = run.run_cell(small(), 10, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is False
    assert result["compared"]["total_mismatches"][0] > 0


def test_the_planted_segment_is_what_a_refresh_builds():
    """3,000 events indexed through the client and refreshed, against the
    same events planted: postings, impacts, columns and lengths equal."""
    from opensearch_tpu.rest.client import RestClient
    n = 3000
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
    assert set(built.postings) == set(planted.postings)
    for f, a in built.postings.items():
        b = planted.postings[f]
        assert a.vocab == b.vocab
        for name in ("starts", "doc_ids", "tfs"):
            assert np.array_equal(getattr(a, name), getattr(b, name)), f
        assert (a.impact is None) == (b.impact is None)
        if a.impact is not None:
            assert np.array_equal(a.impact.q, b.impact.q)
    assert set(built.numeric_cols) == set(planted.numeric_cols)
    for f, a in built.numeric_cols.items():
        b = planted.numeric_cols[f]
        assert a.kind == b.kind and np.array_equal(a.values, b.values)
        assert np.array_equal(a.present, b.present)
    (f, a), = built.keyword_cols.items()
    b = planted.keyword_cols[f]
    assert a.vocab == b.vocab
    for name in ("starts", "ords", "doc_of_value", "min_ord"):
        assert np.array_equal(getattr(a, name), getattr(b, name))
    assert np.array_equal(built.doc_lens["request"],
                          planted.doc_lens["request"])
    assert built.text_stats == planted.text_stats
