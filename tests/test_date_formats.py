"""Date formats, in a mapping and in a `range` query's `format`: the
patterns the engine reads, the rounding of a bound's missing parts (`lte` /
`gt` up, `gte` / `lt` down), and an unknown pattern as a 400 that names it
(never another pattern in its place)."""

import datetime as dt

import pytest

from opensearch_tpu.index.date_formats import (DateFormatError,
                                               compile_format, parse_date)


def ms(*parts, milli=0) -> int:
    return int(dt.datetime(*parts, tzinfo=dt.timezone.utc).timestamp()
               ) * 1000 + milli


@pytest.mark.parametrize("fmt,text,want", [
    ("dd/MM/yyyy", "21/01/2015", ms(2015, 1, 21)),
    ("dd/MM/yyyy", "01/02/2015", ms(2015, 2, 1)),       # 1 February
    ("yyyy-MM-dd HH:mm:ss", "2015-01-01 00:12:34", ms(2015, 1, 1, 0, 12, 34)),
    ("yyyy-MM-dd", "2015-12-31", ms(2015, 12, 31)),
    ("strict_date_optional_time", "2015-01-21T10:11:12.345Z",
     ms(2015, 1, 21, 10, 11, 12, milli=345)),
    ("strict_date_optional_time", "2015-01-21T10:11:12+02:00",
     ms(2015, 1, 21, 8, 11, 12)),
    ("strict_date_optional_time", "2015-01", ms(2015, 1, 1)),
    ("epoch_millis", "1420070400000", 1420070400000),
    ("epoch_second", "1420070400", 1420070400000),
    ("strict_date_optional_time||epoch_second", "893964617", 893964617000),
    ("strict_date_optional_time||epoch_millis", "2015", ms(2015, 1, 1)),
    ("dd/MM/yyyy||yyyy-MM-dd", "2015-03-04", ms(2015, 3, 4)),
    ("yyyy-MM-dd'T'HH:mm", "2015-03-04T05:06", ms(2015, 3, 4, 5, 6)),
    ("basic_date", "20150304", ms(2015, 3, 4)),
    ("yyyyMMdd", "20150304", ms(2015, 3, 4)),
    ("strict_date_time_no_millis", "2015-03-04T05:06:07Z",
     ms(2015, 3, 4, 5, 6, 7)),
])
def test_a_pattern_reads_its_text(fmt, text, want):
    assert parse_date(text, fmt) == want


@pytest.mark.parametrize("fmt,text,down,up", [
    ("dd/MM/yyyy", "21/01/2015", ms(2015, 1, 21),
     ms(2015, 1, 21, 23, 59, 59, milli=999)),
    ("yyyy-MM-dd HH:mm:ss", "2015-01-21 10:00:00", ms(2015, 1, 21, 10),
     ms(2015, 1, 21, 10, milli=999)),
    ("strict_date_optional_time", "2015-02", ms(2015, 2, 1),
     ms(2015, 2, 28, 23, 59, 59, milli=999)),
    ("strict_date_optional_time", "2016-02", ms(2016, 2, 1),
     ms(2016, 2, 29, 23, 59, 59, milli=999)),
    ("strict_date_optional_time", "2015", ms(2015, 1, 1),
     ms(2015, 12, 31, 23, 59, 59, milli=999)),
    ("strict_date_optional_time", "2015-01-21T10", ms(2015, 1, 21, 10),
     ms(2015, 1, 21, 10, 59, 59, milli=999)),
    (None, "2015-01-21", ms(2015, 1, 21),
     ms(2015, 1, 21, 23, 59, 59, milli=999)),
    ("epoch_second", "1420070400", 1420070400000, 1420070400000),
])
def test_missing_parts_round_down_or_up(fmt, text, down, up):
    assert parse_date(text, fmt) == down
    assert parse_date(text, fmt, round_up=True) == up


@pytest.mark.parametrize("fmt", ["foo_bar", "dd/MMM/yyyy", "dd/MM", "QQQ",
                                 "yyyy-MM-dd||nope", "yyyy 'open"])
def test_an_unknown_pattern_is_named(fmt):
    with pytest.raises(DateFormatError) as e:
        compile_format(fmt)
    assert f"[{fmt}]" in str(e.value)


@pytest.mark.parametrize("fmt,text", [
    ("dd/MM/yyyy", "2015-01-21"), ("dd/MM/yyyy", "32/01/2015"),
    ("dd/MM/yyyy", "21/13/2015"), ("yyyy-MM-dd", "2015-02-30"),
    ("strict_date_optional_time", "15-1-2"), ("epoch_millis", "12x"),
    ("yyyy-MM-dd HH:mm:ss", "2015-01-21")])
def test_a_text_outside_its_format_is_refused(fmt, text):
    with pytest.raises(ValueError):
        parse_date(text, fmt)


def test_numbers_follow_the_epoch_pattern_the_format_names():
    assert parse_date(5, None) == 5
    assert parse_date(1420070400000,
                      "strict_date_optional_time||epoch_millis") \
        == 1420070400000
    assert parse_date(1420070400,
                      "strict_date_optional_time||epoch_second") \
        == 1420070400000
    with pytest.raises(ValueError):
        parse_date(1420070400, "dd/MM/yyyy")
    with pytest.raises(ValueError):
        parse_date(True, None)


# ---------------------------------------------------------------------
# through the client: a mapping's format and a request's
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def client():
    from opensearch_tpu.rest.client import RestClient
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        c = RestClient()
        c.indices.create("trips", {
            "settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": {"properties": {
                "at": {"type": "date", "format": "yyyy-MM-dd HH:mm:ss"},
                "seen": {"type": "date",
                         "format": "strict_date_optional_time||epoch_second"},
                "plain": {"type": "date"}}}})
        for day in range(1, 29):
            c.index("trips", {"at": f"2015-01-{day:02d} 12:00:00",
                              "seen": 1420113600 + (day - 1) * 86400,
                              "plain": f"2015-01-{day:02d}T12:00:00Z"},
                    id=str(day))
        c.index("trips", {"at": "2015-02-01 00:00:00",
                          "seen": "2015-02-01T00:00:00Z",
                          "plain": 1422748800000}, id="29")
        c.indices.refresh("trips")
        yield c


def ids(client, field, bounds):
    resp = client.search("trips", {"size": 40, "query": {"range": {
        field: bounds}}})
    return sorted(int(h["_id"]) for h in resp["hits"]["hits"])


@pytest.mark.parametrize("field", ["at", "seen", "plain"])
@pytest.mark.parametrize("bounds,want", [
    # lte rounds up: the whole of the 21st; gte down
    ({"gte": "01/01/2015", "lte": "21/01/2015", "format": "dd/MM/yyyy"},
     list(range(1, 22))),
    # 01/02/2015 is 1 February under dd/MM/yyyy, not 2 January
    ({"gte": "01/02/2015", "format": "dd/MM/yyyy"}, [29]),
    ({"lt": "01/02/2015", "gt": "26/01/2015", "format": "dd/MM/yyyy"},
     [27, 28]),                 # gt rounds up past the 26th, lt down
    ({"gte": "2015-01-27", "lte": "2015-01", "format": "yyyy-MM-dd||yyyy-MM"},
     [27, 28]),                 # lte 2015-01 is the end of January
    ({"gte": 1422748800, "format": "epoch_second"}, [29]),
])
def test_a_requests_format_is_honoured(client, field, bounds, want):
    assert ids(client, field, bounds) == want


def test_a_mappings_format_reads_what_it_indexes_and_queries(client):
    # bounds in each mapping's own format, no `format` in the request
    assert ids(client, "at", {"gte": "2015-01-27 12:00:00",
                              "lt": "2015-01-28 12:00:00"}) == [27]
    assert ids(client, "seen", {"gte": 1420113600 + 26 * 86400,
                                "lte": "2015-01-28"}) == [27, 28]
    assert ids(client, "plain", {"gte": "2015-01-27",
                                 "lte": "2015-01-28"}) == [27, 28]
    src = client.get("trips", "3")["_source"]
    assert src["at"] == "2015-01-03 12:00:00"


def test_an_unknown_pattern_is_a_400_that_names_it(client):
    from opensearch_tpu.rest.client import ApiError
    with pytest.raises(ApiError) as e:
        client.search("trips", {"query": {"range": {"at": {
            "gte": "21/Jan/2015", "format": "dd/MMM/yyyy"}}}})
    assert e.value.status == 400 and "dd/MMM/yyyy" in str(e.value)
    with pytest.raises(ApiError) as e:
        client.indices.create("bad", {"mappings": {"properties": {
            "d": {"type": "date", "format": "week_of_year_ish"}}}})
    assert e.value.status == 400 and "week_of_year_ish" in str(e.value)
    assert not client.indices.exists("bad")
    with pytest.raises(ApiError) as e:
        client.indices.put_mapping("trips", {"properties": {
            "later": {"type": "date", "format": "QQQ yyyy"}}})
    assert e.value.status == 400 and "QQQ yyyy" in str(e.value)


def test_a_text_outside_the_mappings_format_is_a_400(client):
    from opensearch_tpu.rest.client import ApiError
    with pytest.raises(ApiError) as e:
        client.index("trips", {"at": "21/01/2015"}, id="x")
    assert e.value.status == 400
    with pytest.raises(ApiError) as e:
        client.search("trips", {"query": {"range": {"at": {
            "gte": "21/01/2015"}}}})
    assert e.value.status == 400
