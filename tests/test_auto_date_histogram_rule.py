"""`auto_date_histogram` follows the matched documents, not the column: the
rounding is the finest of OpenSearch's (second 1/5/10/30, minute 1/5/10/30,
hour 1/3/12, day 1/7, month 1/3, year 1/5/10/20/50/100) under which the
buckets from the least to the greatest matched value number at most
`buckets`; keys are the rounding's own, empty buckets between are part of
the answer, and the response names the `interval`."""

import datetime as dt

import numpy as np
import pytest

from opensearch_tpu.search import agg_compiler as AC, planes as PN

YEAR0 = int(dt.datetime(2015, 1, 1, tzinfo=dt.timezone.utc).timestamp()) * 1000
DAY = 86_400_000
# index -> (ms between two events, events)
INDEXES = {"dense": (1_300, 6_000), "year": (1_207_000, 26_000),
           "decades": (11 * DAY, 1_000)}


@pytest.fixture(scope="module")
def client():
    """Three indices of evenly spaced events, shuffled: `dense` (one every
    1.3 s for 130 minutes), `year` (one every 20 minutes and 7 seconds for
    a year) and `decades` (one every 11 days for 30 years)."""
    from opensearch_tpu.rest.client import RestClient
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        c = RestClient()
        for index, (step, n) in INDEXES.items():
            c.indices.create(index, {
                "settings": {"number_of_shards": 1, "number_of_replicas": 0},
                "mappings": {"properties": {"at": {"type": "date"},
                                            "v": {"type": "integer"}}}})
            order = np.random.default_rng(4).permutation(n)
            body = []
            for i in order:
                body += [{"index": {"_index": index, "_id": str(i)}},
                         {"at": YEAR0 + int(i) * step, "v": int(i) % 7}]
            assert c.bulk(body, refresh=True)["errors"] is False
        yield c


def ask(client, index, lo, hi, buckets, subs=None):
    agg = {"auto_date_histogram": {"field": "at", "buckets": buckets}}
    if subs:
        agg["aggs"] = subs
    resp = client.search(index, {"size": 0, "query": {"range": {"at": {
        "gte": lo, "lt": hi}}}, "aggs": {"h": agg}})
    return resp["aggregations"]["h"], resp["hits"]["total"]["value"]


# (index, window start offset, window length, buckets) -> interval
LADDER = [
    ("dense", 0, 19_000, 20, "1s"),
    ("dense", 0, 95_000, 20, "5s"),
    ("dense", 0, 190_000, 20, "10s"),
    ("dense", 0, 550_000, 20, "30s"),
    ("dense", 0, 19 * 60_000, 20, "1m"),
    ("dense", 0, 95 * 60_000, 20, "5m"),
    ("year", 0, 190 * 60_000, 20, "10m"),
    ("year", 0, 9 * 3_600_000, 20, "30m"),
    ("year", 0, 19 * 3_600_000, 20, "1h"),
    ("year", 0, 55 * 3_600_000, 20, "3h"),
    ("year", 0, 9 * DAY, 20, "12h"),
    ("year", 40 * DAY, 15 * DAY, 20, "1d"),
    ("year", 40 * DAY, 100 * DAY, 20, "7d"),
    ("year", 0, 360 * DAY, 20, "1M"),
    ("year", 0, 360 * DAY, 5, "3M"),
    ("decades", 0, 19 * 365 * DAY, 20, "1y"),
    ("decades", 0, 29 * 365 * DAY, 10, "5y"),
    ("decades", 0, 29 * 365 * DAY, 3, "10y"),
]


@pytest.mark.parametrize("index,start,length,buckets,interval", LADDER,
                         ids=[x[4] + f"-of-{x[3]}" for x in LADDER])
def test_the_interval_follows_the_matched_range(client, index, start, length,
                                                buckets, interval):
    lo, hi = YEAR0 + start, YEAR0 + start + length
    got, total = ask(client, index, lo, hi, buckets)
    assert got["interval"] == interval
    keys = [b["key"] for b in got["buckets"]]
    assert 0 < len(keys) <= buckets and keys == sorted(keys)
    assert sum(b["doc_count"] for b in got["buckets"]) == total > 0
    # keys are the rounding's own: every bucket starts where its unit does,
    # `inner` units after the one before, from the least matched unit on
    unit = next(u for u, r in enumerate(PN.AUTO_ROUNDINGS)
                if r[0] == interval[-1])
    inner = int(interval[:-1])
    ids = [int(PN.auto_unit_ids(k, unit)) for k in keys]
    assert keys == [PN.auto_unit_start_ms(i, unit) for i in ids]
    assert ids == [ids[0] + j * inner for j in range(len(ids))]
    step = INDEXES[index][0]
    first = -(-(lo - YEAR0) // step) * step + YEAR0    # least matched value
    assert ids[0] == int(PN.auto_unit_ids(first, unit))


def test_fifteen_days_inside_a_year_are_fifteen_daily_buckets(client):
    lo = YEAR0 + 100 * DAY
    got, total = ask(client, "year", lo, lo + 15 * DAY, 20)
    assert got["interval"] == "1d"
    assert [b["key"] for b in got["buckets"]] \
        == [lo + j * DAY for j in range(15)]
    assert got["buckets"][0]["key_as_string"] == "2015-04-11T00:00:00.000Z"
    # one event every 1,207 s: 71 or 72 a day
    assert all(b["doc_count"] in (71, 72) for b in got["buckets"])
    assert sum(b["doc_count"] for b in got["buckets"]) == total


def test_the_columns_span_no_longer_decides(client):
    """The old rule took the interval from the column (a year: `1M`), so a
    fortnight answered one or two buckets."""
    lo = YEAR0 + 200 * DAY
    got, _total = ask(client, "year", lo, lo + 14 * DAY, 20)
    assert got["interval"] == "1d" and len(got["buckets"]) == 14


def test_metrics_follow_their_buckets_through_the_merge(client):
    lo = YEAR0 + 40 * DAY
    got, total = ask(client, "year", lo, lo + 100 * DAY, 20,
                     {"s": {"stats": {"field": "v"}}})
    assert got["interval"] == "7d"
    assert sum(b["s"]["count"] for b in got["buckets"]) == total
    for b in got["buckets"]:
        first = -(-(max(b["key"], lo) - YEAR0) // 1_207_000)
        last = (min(b["key"] + 7 * DAY, lo + 100 * DAY) - 1 - YEAR0) \
            // 1_207_000
        want = [i % 7 for i in range(first, last + 1)]
        assert b["doc_count"] == len(want)
        assert b["s"]["sum"] == sum(want)
        assert (b["s"]["min"], b["s"]["max"]) == (min(want), max(want))


def test_no_match_is_no_bucket(client):
    got, total = ask(client, "year", YEAR0 - 10 * DAY, YEAR0 - 5 * DAY, 20)
    assert total == 0 and got["buckets"] == []


def test_segments_with_other_roundings_merge_to_the_coarsest(client):
    """Two segments whose matched ranges differ (an hour and a month) are
    brought to one rounding before the final one is chosen."""
    client.indices.create("two", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {"at": {"type": "date"}}}})
    for i in range(60):
        client.index("two", {"at": YEAR0 + i * 60_000}, id=f"a{i}")
    client.indices.refresh("two")
    for i in range(30):
        client.index("two", {"at": YEAR0 + 3 * DAY + i * DAY}, id=f"b{i}")
    client.indices.refresh("two")
    assert len(client.node.indices["two"].shards[0].segments) == 2
    resp = client.search("two", {"size": 0, "aggs": {"h": {
        "auto_date_histogram": {"field": "at", "buckets": 10}}}})
    got = resp["aggregations"]["h"]
    assert got["interval"] == "7d"
    assert [b["doc_count"] for b in got["buckets"]] == [64, 7, 7, 7, 5]
    assert got["buckets"][0]["key"] == YEAR0


def test_an_ordered_segment_still_counts_runs():
    """The windowed counts keep both forms: `run_counts` where the plane is
    in row order, and the same numbers as the scatter."""
    import jax.numpy as jnp
    n, nb, window = 4096, 40, 8
    ids = np.sort(np.random.default_rng(1).integers(0, nb, n)).astype(
        np.int32)
    starts = np.searchsorted(ids, np.arange(nb + 1)).astype(np.int32)
    match = (np.random.default_rng(2).random(n) < 0.6).astype(np.float32)
    params = {"p_dbuckets": jnp.asarray(ids), "p_dstarts": jnp.asarray(starts)}
    for first in (0, 5, 33, 38):
        want = np.bincount(ids[match > 0], minlength=nb + window)[
            first: first + window]
        for form in ("runs", "scatter"):
            counts, b = AC._date_bucket_counts(
                jnp, params, "p", jnp.asarray(match), nb, form,
                jnp.int32(first), window)
            assert np.array_equal(np.asarray(counts)[: len(want)],
                                  want[: window]), (first, form)
            held = (match > 0) & (ids >= first) & (ids < first + window)
            assert np.array_equal(np.asarray(b),
                                  np.where(held, ids - first, window))
