"""The kind `nyc_taxis` at a small size on the CPU: the generator's
determinism, the planted segment against one the refresh path built, a
stream that never repeats a body, the control (the reference weakened twice
has to fail the rule), and a broken timed path (`correct` false)."""

import copy
import json

import numpy as np
import pytest

import nyc_taxis_control as control
import nyc_taxis_reference as reference
import nyc_taxis_trips as trips
import run

CELL = "nyctaxis.search1.analyst"
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


def test_the_generator_follows_corpus_seed_alone():
    gen = small()["config"]["generator"]
    a, b = trips.generate(5000, 7, gen), trips.generate(5000, 7, gen)
    c = trips.generate(5000, 8, gen)
    for k, v in a.items():
        assert (np.array_equal(v, b[k]) if isinstance(v, np.ndarray)
                else v == b[k]), k
    assert not np.array_equal(a["pickup_s"], c["pickup_s"])
    # file order: months one after another, no order of time inside one
    month = (a["pickup_s"].astype("datetime64[s]").astype("datetime64[M]")
             .astype(int))
    assert (np.diff(month) >= 0).all()
    assert (np.diff(a["pickup_s"]) < 0).any()
    assert a["total_amount_c"].dtype == np.int32
    parts = sum(a[f + "_c"].astype(np.int64) for f in trips.MONEY[1:])
    assert np.array_equal(parts, a["total_amount_c"])
    cash = a["payment_type"] != a["payment_type_values"].index("1")
    assert not a["tip_amount_c"][cash].any()
    assert int(a["trip_distance_c"].max()) <= 100_00


def test_the_planted_segment_is_what_a_refresh_builds():
    """2,000 trips indexed through the client and refreshed, against the
    same trips planted: postings, columns and coordinates equal, array
    for array."""
    from opensearch_tpu.rest.client import RestClient
    n = 2000
    cols = trips.generate(n, 41, small()["config"]["generator"])
    settings = {"number_of_shards": 1, "number_of_replicas": 0}
    planted = trips.plant_index(RestClient(), "bench", cols, settings)
    client = RestClient()
    client.indices.create("real", {"settings": settings,
                                   "mappings": trips.MAPPING})
    for i in range(n):
        client.index("real", planted.sources[i], id=str(i))
    client.indices.refresh("real")
    (built,) = client.node.indices["real"].shards[0].segments
    assert set(built.postings) == set(planted.postings) == set(trips.KEYWORDS)
    for f, a in built.postings.items():
        b = planted.postings[f]
        assert a.vocab == b.vocab
        for name in ("starts", "doc_ids", "tfs"):
            assert np.array_equal(getattr(a, name), getattr(b, name)), f
        assert a.impact is None and b.impact is None
    assert set(built.numeric_cols) == set(planted.numeric_cols)
    assert len(built.numeric_cols) == 11
    for f, a in built.numeric_cols.items():
        b = planted.numeric_cols[f]
        assert a.kind == b.kind and a.values.dtype == b.values.dtype, f
        assert np.array_equal(a.values, b.values), f
        assert np.array_equal(a.present, b.present)
    assert set(built.keyword_cols) == set(planted.keyword_cols)
    for f, a in built.keyword_cols.items():
        b = planted.keyword_cols[f]
        assert a.vocab == b.vocab
        for name in ("starts", "ords", "doc_of_value", "min_ord"):
            assert np.array_equal(getattr(a, name), getattr(b, name)), f
    assert set(built.geo_cols) == set(planted.geo_cols) == set(trips.GEO)
    for f, a in built.geo_cols.items():
        b = planted.geo_cols[f]
        assert a.lat.dtype == b.lat.dtype == np.float32
        for name in ("lat", "lon", "present"):
            assert np.array_equal(getattr(a, name), getattr(b, name)), f
    assert built.doc_lens == planted.doc_lens == {}
    assert built.text_stats == planted.text_stats == {}


def test_a_stream_never_repeats_a_body_twins_included():
    loaded = small()
    kind = run.load_kind("nyc_taxis")
    stream = kind.stream({}, loaded["traffic"], 11)
    specs = stream.take(400)
    assert [s["shape"] for s in specs[:8]] == list(reference.SHAPES)
    stream.reseed(12)
    specs += stream.take(400)
    bodies = [json.dumps(s["body"], sort_keys=True) for s in specs]
    bodies += [json.dumps(stream.twin(s)["body"], sort_keys=True)
               for s in specs]
    assert len(set(bodies)) == len(bodies) == 1600
    for s in specs:
        t = stream.twin(s)
        assert (t["shape"], t["lo"]) == (s["shape"], s["lo"])
        assert t["hi"] > s["hi"]


@pytest.mark.parametrize("corpus_seed", [20150101, 3000000021])
def test_the_weakened_references_fail_the_rule(corpus_seed):
    loaded = small()
    columns = trips.generate(200_000, corpus_seed,
                             loaded["config"]["generator"])
    kind = run.load_kind("nyc_taxis")
    specs = kind.stream({"columns": columns}, loaded["traffic"], 5).take(32)
    out = control.run(columns, specs)
    sums, span = out["float32_sums"], out["column_span"]
    assert sums["correct"] is False and span["correct"] is False
    # each by its own limit, and by nothing else
    worst, limit = sums["numbers"]["sum_rel_err_max"]
    assert worst > 5 * limit
    assert all(v == [0, 0] for k, v in sums["numbers"].items()
               if k != "sum_rel_err_max")
    assert span["numbers"]["interval_mismatches"][0] == 4
    assert span["numbers"]["bucket_mismatches"][0] > 0
    assert span["numbers"]["sum_rel_err_max"][0] == 0
    # and the exact reference holds its own answers
    exact = reference.Reference(columns)
    held = [(s, reference.as_response(exact.answer(s), s)) for s in specs]
    own = reference.hold(held, exact)
    assert own["correct"] is True and own["compared"] == 32


def test_the_cell_holds_the_rule_at_20000_trips(meter, tmp_path):
    result = run.run_cell(small(), 3000000011, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    n = result["compared"]
    assert n.pop("sum_rel_err_max")[0] < 1e-6
    assert all(v == [0, 0] for v in n.values())
    assert {"qps", "p50_ms", "setup_s"} <= set(result["metrics"])


def test_a_count_off_by_one_is_not_correct(meter, tmp_path, monkeypatch):
    """One bucket's count moved by one where it is produced."""
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


def test_a_sum_off_in_the_fifth_digit_is_not_correct(meter, tmp_path,
                                                     monkeypatch):
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.search

    def sum_moved(self, *a, **kw):
        resp = real(self, *a, **kw)
        for agg in resp.get("aggregations", {}).values():
            for b in agg["buckets"]:
                if b.get(reference.STATS_NAME, {}).get("count"):
                    b[reference.STATS_NAME]["sum"] *= 1 + 3e-5
        return resp
    monkeypatch.setattr(RestClient, "search", sum_moved)
    result = run.run_cell(small(), 10, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is False
    assert result["compared"]["sum_rel_err_max"][0] > 1e-5
    assert result["compared"]["stat_mismatches"] == [0, 0]
