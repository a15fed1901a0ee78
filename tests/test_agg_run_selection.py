"""How a date histogram's counts are taken is chosen from what the code
observes in the segment: the same events indexed in arrival order (an
append-only log) and shuffled give equal responses, the first counted as
runs (`ops.aggs.run_counts`), the second by scatter-add, and the counters
`executor.agg_bucket_launches` / `executor.agg_run_counted` say which."""

import itertools

import numpy as np
import pytest

from opensearch_tpu.search import compiler as C, planes as PN

NDOCS = 600
T0 = 893_980_800_000                # 1998-05-01T00:00:00Z, epoch ms
HOUR = 3_600_000


def _events() -> list:
    """600 events over three days in arrival order, a few with no
    timestamp."""
    rng = np.random.default_rng(31)
    ts = np.sort(rng.integers(T0, T0 + 72 * HOUR, NDOCS))
    out = []
    for i, t in enumerate(ts):
        ev = {"status": int(rng.choice([200, 200, 200, 304, 404])),
              "size": int(rng.integers(0, 50_000)), "n": i}
        if i % 41:
            ev["ts"] = int(t)
        out.append(ev)
    return out


@pytest.fixture(scope="module")
def client():
    """One plain node holding `ordered` and `shuffled`: the same events."""
    from opensearch_tpu.rest.client import RestClient
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        c = RestClient()
        events = _events()
        orders = {"ordered": np.arange(NDOCS),
                  "shuffled": np.random.default_rng(5).permutation(NDOCS)}
        for index, order in orders.items():
            c.indices.create(index, {
                "settings": {"number_of_shards": 1, "number_of_replicas": 0},
                "mappings": {"properties": {
                    "ts": {"type": "date"}, "status": {"type": "integer"},
                    "size": {"type": "integer"}, "n": {"type": "integer"}}}})
            body = []
            for i in order:
                body += [{"index": {"_index": index, "_id": str(i)}},
                         events[i]]
            assert c.bulk(body, refresh=True)["errors"] is False
        yield c


WINDOW = {"range": {"ts": {"gte": T0 + 7 * HOUR + 1234,
                           "lt": T0 + 55 * HOUR + 999}}}
HOURLY = {"date_histogram": {"field": "ts", "calendar_interval": "hour"}}
BODIES = {
    "under_a_range": {"size": 0, "query": WINDOW, "aggs": {"h": HOURLY}},
    "under_a_bool_filter": {"size": 0, "query": {"bool": {
        "must": [WINDOW], "filter": [{"term": {"status": 200}}]}},
        "aggs": {"h": HOURLY}},
    "with_a_stats_sub_aggregation": {"size": 0, "query": WINDOW, "aggs": {
        "h": dict(HOURLY, aggs={"s": {"stats": {"field": "size"}}})}},
    "fixed_interval_with_an_offset": {"size": 0, "aggs": {"h": {
        "date_histogram": {"field": "ts", "fixed_interval": "90m",
                           "offset": "+20m"}}}},
    "auto_date_histogram": {"size": 0, "query": WINDOW, "aggs": {"h": {
        "auto_date_histogram": {"field": "ts", "buckets": 12}}}},
}


_NONCE = itertools.count(10_000)


def _launched(client, index: str, body: dict):
    """-> (the response's buckets, bucket counts launched, run-counted).
    No body is sent twice (a threshold for the total that no index here
    reaches), so the request cache answers none of them."""
    b0 = C.EXECUTOR_STATS["agg_bucket_launches"]
    r0 = C.EXECUTOR_STATS["agg_run_counted"]
    resp = client.search(index, dict(body, track_total_hits=next(_NONCE)))
    assert "error" not in resp
    return (resp["aggregations"]["h"]["buckets"],
            C.EXECUTOR_STATS["agg_bucket_launches"] - b0,
            C.EXECUTOR_STATS["agg_run_counted"] - r0)


def _same_buckets(a: list, b: list) -> None:
    """Keys and counts equal; a `stats` sub-aggregation's float sums are
    added in another row order, so they agree to float32's rounding."""
    assert [(x["key"], x["doc_count"]) for x in a] \
        == [(x["key"], x["doc_count"]) for x in b]
    for x, y in zip(a, b):
        if "s" in x:
            assert x["s"]["count"] == y["s"]["count"]
            assert (x["s"]["min"], x["s"]["max"]) \
                == (y["s"]["min"], y["s"]["max"])
            assert x["s"]["sum"] == pytest.approx(y["s"]["sum"], rel=1e-5)


@pytest.mark.parametrize("name", list(BODIES))
def test_arrival_order_counts_runs_and_shuffled_scatters(client, name):
    body = BODIES[name]
    ordered, launched, runs = _launched(client, "ordered", body)
    assert launched == runs == 1
    shuffled, launched, runs = _launched(client, "shuffled", body)
    assert (launched, runs) == (1, 0)
    assert sum(b["doc_count"] for b in ordered) > 0
    _same_buckets(ordered, shuffled)


def test_the_counts_are_the_events_own(client):
    """Against a count made here, not only against the other form."""
    buckets, _l, _r = _launched(client, "ordered", BODIES["under_a_range"])
    lo, hi = WINDOW["range"]["ts"]["gte"], WINDOW["range"]["ts"]["lt"]
    want = {}
    for ev in _events():
        if "ts" in ev and lo <= ev["ts"] < hi:
            key = ev["ts"] // HOUR * HOUR
            want[key] = want.get(key, 0) + 1
    got = {b["key"]: b["doc_count"] for b in buckets if b["doc_count"]}
    assert got == want


def test_after_a_delete_by_query_both_forms_drop_the_same_events(client):
    for index in ("ordered", "shuffled"):
        out = client.delete_by_query(index, {"query": {"range": {
            "n": {"gte": 100, "lt": 160}}}}, refresh=True)
        assert out["deleted"] == 60
    ordered, launched, runs = _launched(client, "ordered",
                                        BODIES["under_a_range"])
    assert launched == runs == 1
    shuffled, launched, runs = _launched(client, "shuffled",
                                         BODIES["under_a_range"])
    assert (launched, runs) == (1, 0)
    _same_buckets(ordered, shuffled)
    lo, hi = WINDOW["range"]["ts"]["gte"], WINDOW["range"]["ts"]["lt"]
    assert sum(b["doc_count"] for b in ordered) == sum(
        1 for ev in _events()
        if "ts" in ev and lo <= ev["ts"] < hi and not 100 <= ev["n"] < 160)


def _segment(client, index: str):
    segs = client.node.indices[index].shards[0].segments
    assert len(segs) == 1
    return segs[0]


def test_the_boundaries_live_and_die_with_the_plane(client):
    """`starts` is kept in the plane's cache entry and charged with it, so
    a rematerialized field (`drop_segment_planes`) drops both and the HBM
    ledger's bytes return."""
    from opensearch_tpu.obs.hbm_ledger import LEDGER
    _launched(client, "ordered", BODIES["under_a_range"])
    _launched(client, "shuffled", BODIES["under_a_range"])
    key = ("ts", 1, 0, "hour")
    for index, has_starts in (("ordered", True), ("shuffled", False)):
        seg = _segment(client, index)
        plane, _min_b, nb, starts = seg._date_bucket_cache[key]
        assert plane.shape == (seg.ndocs_pad,)
        assert (starts is not None) == has_starts
        if has_starts:
            assert starts.shape == (nb + 1,) and int(starts[-1]) == seg.ndocs
        # every plane of the field goes (the earlier tests built others)
        charged = sum(p.nbytes + (0 if st is None else st.nbytes)
                      for p, _m, _n, st in seg._date_bucket_cache.values())
        assert charged >= seg.ndocs_pad * 4 + (nb + 1) * 4 * has_starts
        before = LEDGER.snapshot()["tenants"]["agg_bucket_plane"]["bytes"]
        PN.drop_segment_planes(seg, "ts")
        assert not [k for k in seg._date_bucket_cache if k[0] == "ts"]
        after = LEDGER.snapshot()["tenants"]["agg_bucket_plane"]["bytes"]
        assert before - after == charged
    # and the next request builds them again, in the same form
    _b, launched, runs = _launched(client, "ordered", BODIES["under_a_range"])
    assert launched == runs == 1
