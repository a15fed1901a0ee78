"""A query's row span (`compiler.row_span`): the rows outside which a
`range` over a column in row order lets nothing match, handed to the
program as two int32 that bound the block loops of its group-bys
(`ops.aggs`' dense and product forms). Through `RestClient` on small
segments, with the forms' blocks cut to 512 rows so that a segment of 3,000
rows is several: the span of a range over an ordered column, an unordered
one, one with rows that have no value, a float one, of a `bool`; the same
request answered alike on a segment whose rows were shuffled (it has no
span: the whole segment); `global` and `nested` under a window; after a
delete-by-query; two windows, one compiled program; the counters."""

import numpy as np
import pytest

from opensearch_tpu.ops import aggs as agg_ops
from opensearch_tpu.search import aggregations as A
from opensearch_tpu.search import compiler as C
from opensearch_tpu.search import plan as PL
from opensearch_tpu.search import programs as PG

NDOCS = 3000
BLOCK = 512
T0 = 1_700_000_000_000
MAPPING = {"mappings": {"properties": {
    "rs_ts": {"type": "date"}, "rs_d2": {"type": "date"},
    "rs_u": {"type": "long"}, "rs_gap": {"type": "long"},
    "rs_f": {"type": "float"}, "rs_v": {"type": "float"},
    "rs_k": {"type": "keyword"}, "rs_r": {"type": "keyword"},
    "rs_items": {"type": "nested",
                 "properties": {"tag": {"type": "keyword"}}}}}}


def _docs():
    rng = np.random.default_rng(49)
    docs = []
    for i in range(NDOCS):
        d = {"rs_ts": T0 + (i // 2) * 1000,     # in row order, in pairs
             "rs_d2": T0 + int(rng.integers(0, 3_600_000)),
             "rs_u": int(rng.integers(0, 10_000)),
             "rs_f": i * 0.1,                   # in row order, no float32
             "rs_v": round(float(rng.normal(3.0, 2.0)), 2),
             "rs_k": f"k{int(rng.integers(0, 40)):02d}",
             "rs_r": f"r{int(rng.integers(0, 5))}",
             "rs_items": [{"tag": f"t{int(rng.integers(0, 6))}"}
                          for _ in range(int(rng.integers(0, 3)))]}
        if i >= 5 and i % 7 != 3:               # in row order, with holes
            d["rs_gap"] = i * 10
        docs.append(d)
    return docs


@pytest.fixture(scope="module")
def clients():
    """(ordered, shuffled): the same documents in arrival order and in a
    shuffled one, each one segment on a plain node; the forms' blocks are
    512 rows while the module runs, and from 64 buckets on a count is a
    product."""
    from opensearch_tpu.rest.client import RestClient
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        mp.setattr(agg_ops, "_DENSE_BLOCK", BLOCK)
        mp.setattr(agg_ops, "_PRODUCT_BLOCK", BLOCK)
        mp.setattr(agg_ops, "_DENSE_BUCKETS", 64)
        mp.setattr(agg_ops, "_LIMB_BITS", {3: 16})
        mp.setattr(agg_ops, "sum_limb_plan",
                   lambda n, nb: (3, 16, min(BLOCK, max(n, 1))))
        C.clear_program_caches()
        docs = _docs()
        out = []
        for order in (np.arange(NDOCS),
                      np.random.default_rng(5).permutation(NDOCS)):
            client = RestClient()
            client.indices.create("rs", MAPPING)
            bulk = []
            for i in order:
                bulk += [{"index": {"_index": "rs", "_id": str(i)}},
                         docs[i]]
            client.bulk(bulk, refresh=True)
            out.append(client)
        yield tuple(out)
        C.clear_program_caches()


def _segment(client):
    (seg,) = client.node.indices["rs"].shards[0].segments
    return seg


def _rows(seg, field, lo, hi, inc_lo=True, inc_hi=False):
    """The rows the device's mask accepts, by numpy over the host column."""
    col = seg.numeric_cols[field]
    v = col.values if col.kind == "int" else col.values.astype(np.float32)
    if col.kind == "float":
        lo, hi = np.float32(lo), np.float32(hi)
    ok = col.present & ((v >= lo) if inc_lo else (v > lo)) \
        & ((v <= hi) if inc_hi else (v < hi))
    return np.flatnonzero(ok)


def _range(field, lo, hi, kind="int", inc_lo=True, inc_hi=False):
    return PL.LRange(field=field, kind=kind, lo=lo, hi=hi,
                     include_lo=inc_lo, include_hi=inc_hi)


# ---------------------------------------------------------------------
# `row_span` on the host
# ---------------------------------------------------------------------
@pytest.mark.parametrize("inc_lo,inc_hi", [(True, False), (False, True),
                                           (True, True), (False, False)])
@pytest.mark.parametrize("lo_s,hi_s", [(100, 400), (0, 1), (700, 700),
                                       (1499, 5000), (-5, 3), (2000, 3000)])
def test_a_range_over_an_ordered_column_is_its_rows(clients, lo_s, hi_s,
                                                    inc_lo, inc_hi):
    """Every row has a value and the values stand in pairs: the span is
    the first and the last row the mask accepts, at either inclusiveness."""
    seg = _segment(clients[0])
    lo, hi = T0 + lo_s * 1000, T0 + hi_s * 1000
    rows = _rows(seg, "rs_ts", lo, hi, inc_lo, inc_hi)
    got = C.row_span(_range("rs_ts", lo, hi, "int", inc_lo, inc_hi), seg)
    if rows.size:
        assert got == (rows[0], rows[-1] + 1)
    else:
        assert got[0] == got[1]
    assert C.can_match(_range("rs_ts", lo, hi, "int", inc_lo, inc_hi),
                       seg) or not rows.size


def test_open_bounds_reach_the_segments_ends(clients):
    seg = _segment(clients[0])
    assert C.row_span(_range("rs_ts", None, None), seg) == (0, NDOCS)
    assert C.row_span(_range("rs_ts", T0 + 10_000, None), seg) == (20, NDOCS)
    assert C.row_span(_range("rs_ts", None, T0 + 10_000), seg) == (0, 20)


@pytest.mark.parametrize("node", [
    _range("rs_u", 100, 200), _range("rs_d2", T0, T0 + 5),
    _range("rs_nowhere", 1, 2), PL.LMatchAll(),
    PL.LBool(shoulds=[_range("rs_ts", T0, T0 + 1000)], msm=1),
    PL.LBool(must_nots=[_range("rs_ts", T0, T0 + 1000)]),
    PL.LConstScore(child=_range("rs_ts", T0, T0 + 1000))])
def test_what_narrows_nothing_is_the_whole_segment(clients, node):
    """A range over a column in no row order, over no column, a node that
    is no range, and a `bool` that only has `should` or `must_not`: no
    span, and a launch is handed none (its loops keep their static
    length)."""
    seg = _segment(clients[0])
    assert C.row_span(node, seg) is None
    params = {}
    C.bind_row_span(node, seg, params)
    assert params == {} and PG.launch_span(params) is None


def test_the_shuffled_segment_has_no_column_in_row_order(clients):
    seg = _segment(clients[1])
    for field in ("rs_ts", "rs_gap", "rs_f"):
        assert seg.numeric_cols[field].in_row_order is None
        assert C.row_span(_range(field, 0, 1), seg) is None
    ordered = _segment(clients[0])
    assert ordered.numeric_cols["rs_ts"].in_row_order is \
        ordered.numeric_cols["rs_ts"].values        # no copy: all present
    assert ordered.numeric_cols["rs_u"].in_row_order is None


@pytest.mark.parametrize("lo,hi", [(0, 100), (40, 60), (1000, 1030),
                                   (1031, 1039), (29_990, 50_000), (-9, 55)])
def test_rows_without_a_value_take_no_part(clients, lo, hi):
    """`rs_gap` lacks a value in its first five rows and in every seventh:
    the rest is in row order, and the span holds every row the mask
    accepts (a row without a value stands where its neighbour before it
    does, and the mask drops it)."""
    seg = _segment(clients[0])
    col = seg.numeric_cols["rs_gap"]
    assert not col.present.all() and col.in_row_order is not None
    rows = _rows(seg, "rs_gap", lo, hi)
    first, end = C.row_span(_range("rs_gap", lo, hi), seg)
    assert 0 <= first <= end <= NDOCS
    if rows.size:
        assert first <= rows[0] and rows[-1] < end
        # tight but for rows without a value at its ends
        assert col.present[first:rows[0]].sum() == 0
        assert col.present[rows[-1] + 1:end].sum() == 0


@pytest.mark.parametrize("inc_lo,inc_hi", [(True, False), (False, True)])
@pytest.mark.parametrize("lo,hi", [(10.0, 20.0), (0.1, 0.3), (17.3, 17.3),
                                   (299.9, 1e9), (-1.0, 0.05)])
def test_a_float_range_holds_what_the_float32_mask_accepts(clients, lo, hi,
                                                           inc_lo, inc_hi):
    """The mask compares float32 roundings of the column and of the bounds;
    the span is searched in the host's float64 values one float32 outward,
    so it holds every accepted row and at most a few more."""
    seg = _segment(clients[0])
    rows = _rows(seg, "rs_f", lo, hi, inc_lo, inc_hi)
    first, end = C.row_span(_range("rs_f", lo, hi, "float", inc_lo, inc_hi),
                            seg)
    if rows.size:
        assert first <= rows[0] and rows[-1] < end
        assert rows[0] - first <= 2 and end - (rows[-1] + 1) <= 2
    else:
        assert end - first <= 3


def test_a_bool_is_the_intersection_of_what_it_requires(clients):
    seg = _segment(clients[0])
    a = _range("rs_ts", T0 + 100_000, T0 + 900_000)      # rows 200..1800
    b = _range("rs_gap", 5000, 12_000)                  # rows 500..1200
    assert C.row_span(a, seg) == (200, 1800)
    lo_b, hi_b = C.row_span(b, seg)
    assert C.row_span(PL.LBool(musts=[a], filters=[b]), seg) == (lo_b, hi_b)
    assert C.row_span(PL.LBool(filters=[a, b, _range("rs_u", 5, 9)],
                               shoulds=[_range("rs_ts", T0, T0 + 1)]),
                      seg) == (lo_b, hi_b)
    # two ranges that do not meet: empty, and never inverted
    c = _range("rs_ts", T0 + 1_000_000, T0 + 1_100_000)
    first, end = C.row_span(PL.LBool(musts=[b, c]), seg)
    assert first == end
    # a nested bool
    assert C.row_span(PL.LBool(musts=[PL.LBool(filters=[a])], filters=[b]),
                      seg) == (lo_b, hi_b)


# ---------------------------------------------------------------------
# the same answers on a segment whose rows were shuffled
# ---------------------------------------------------------------------
AGGS = {
    "terms": {"k": {"terms": {"field": "rs_k", "size": 40}, "aggs": {
        "v": {"stats": {"field": "rs_v"}}}}},
    "terms under the dense constant": {"r": {"terms": {"field": "rs_r"}}},
    "multi_terms": {"m": {"multi_terms": {"size": 20, "terms": [
        {"field": "rs_k"}, {"field": "rs_r"}]}}},
    "composite": {"c": {"composite": {"size": 25, "sources": [
        {"k": {"terms": {"field": "rs_k"}}},
        {"r": {"terms": {"field": "rs_r", "order": "desc"}}}]}}},
    "keyword cardinality": {"n": {"cardinality": {"field": "rs_k"}}},
    "histogram and stats": {"h": {"histogram": {
        "field": "rs_v", "interval": 0.5}, "aggs": {
        "s": {"extended_stats": {"field": "rs_u"}}}}},
    "date_histogram": {"d": {"date_histogram": {
        "field": "rs_d2", "fixed_interval": "5m"}, "aggs": {
        "a": {"avg": {"field": "rs_v"}}}}},
    "filter, filters, range, missing": {
        "f": {"filter": {"term": {"rs_r": "r2"}}, "aggs": {
            "k": {"terms": {"field": "rs_k", "size": 5}}}},
        "fs": {"filters": {"filters": {
            "a": {"term": {"rs_r": "r0"}}, "b": {"term": {"rs_r": "r4"}}}},
            "aggs": {"k": {"terms": {"field": "rs_k", "size": 3}}}},
        "rg": {"range": {"field": "rs_u", "ranges": [
            {"to": 3000}, {"from": 3000}]}, "aggs": {
            "r": {"terms": {"field": "rs_r"}}}},
        "ms": {"missing": {"field": "rs_gap"}, "aggs": {
            "k": {"terms": {"field": "rs_k", "size": 4}}}}},
}
# seconds from T0: inside one block, across several, to the end, all,
# none that `can_match` lets through
WINDOWS = [(100, 130), (240, 1100), (1400, 1500), (0, 1500), (700, 701)]


def _search(client, aggs, lo_s, hi_s, extra=None):
    rng = {"range": {"rs_ts": {"gte": T0 + lo_s * 1000,
                               "lt": T0 + hi_s * 1000}}}
    query = rng if extra is None else {"bool": {"filter": [rng, extra]}}
    resp = client.search("rs", {"size": 0, "query": query, "aggs": aggs})
    assert "error" not in resp
    return resp["hits"]["total"], resp["aggregations"]


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("name", AGGS)
def test_a_window_answers_as_on_the_shuffled_segment(clients, name, window):
    ordered, shuffled = clients
    got = _search(ordered, AGGS[name], *window)
    assert got == _search(shuffled, AGGS[name], *window)
    assert got[0]["value"] == 2 * (window[1] - window[0])


def test_a_second_range_over_an_unordered_column_in_the_same_bool(clients):
    ordered, shuffled = clients
    extra = {"range": {"rs_u": {"gte": 2000, "lt": 7000}}}
    got = _search(ordered, AGGS["terms"], 240, 1100, extra)
    assert got == _search(shuffled, AGGS["terms"], 240, 1100, extra)
    assert 0 < got[0]["value"] < 2 * 860


def test_global_and_nested_under_a_window_count_what_they_counted(clients):
    """`global` ignores the query and `nested` reduces another segment's
    rows: neither may take the window's span."""
    ordered, shuffled = clients
    aggs = {"all": {"global": {}, "aggs": {
                "k": {"terms": {"field": "rs_k", "size": 40}},
                "h": {"histogram": {"field": "rs_v", "interval": 1.0}}}},
            "items": {"nested": {"path": "rs_items"}, "aggs": {
                "t": {"terms": {"field": "rs_items.tag"}}}}}
    got = _search(ordered, aggs, 100, 130)
    assert got == _search(shuffled, aggs, 100, 130)
    docs = _docs()
    assert got[1]["all"]["doc_count"] == NDOCS
    want = np.unique([d["rs_k"] for d in docs], return_counts=True)
    assert {b["key"]: b["doc_count"] for b in got[1]["all"]["k"]["buckets"]} \
        == dict(zip(want[0].tolist(), want[1].tolist()))
    tags = [i["tag"] for d in docs[200:260] for i in d["rs_items"]]
    assert got[1]["items"]["doc_count"] == len(tags)
    want = np.unique(tags, return_counts=True)
    assert {b["key"]: b["doc_count"]
            for b in got[1]["items"]["t"]["buckets"]} \
        == dict(zip(want[0].tolist(), want[1].tolist()))


# ---------------------------------------------------------------------
# one program whatever the window; the counters
# ---------------------------------------------------------------------
def _launches(monkeypatch):
    seen = []
    count = PG._count_launch
    monkeypatch.setattr(PG, "_count_launch", lambda full, seg_arrays, cp: (
        seen.append((full, cp)), count(full, seg_arrays, cp))[1])
    return seen


def test_two_windows_of_different_lengths_hit_one_compiled_program(
        clients, monkeypatch):
    ordered, _ = clients
    seen = _launches(monkeypatch)
    _search(ordered, AGGS["multi_terms"], 10, 20)
    built = PG._build_executor.cache_info()
    _search(ordered, AGGS["multi_terms"], 300, 1450)
    _search(ordered, AGGS["multi_terms"], 1, 1500)  # (no body seen before:
    after = PG._build_executor.cache_info()         # the request cache)
    assert after.misses == built.misses and after.hits == built.hits + 2
    (spec,) = {full for full, _cp in seen}
    assert [cp[C.ROW_SPAN].tolist() for _f, cp in seen] \
        == [[20, 40], [600, 2900], [2, 3000]]
    # the bounds are operands of one shape and type, under the same keys:
    # nothing of a window is in what the program is traced or keyed by
    assert len({tuple(sorted(cp)) for _f, cp in seen}) == 1
    assert {(cp[C.ROW_SPAN].dtype, cp[C.ROW_SPAN].shape)
            for _f, cp in seen} == {(np.dtype(np.int32), (2,))}
    assert C.ROW_SPAN not in str(spec) and "2900" not in str(spec)


def _counted(client, aggs, lo_s, hi_s):
    before = {k: A.AGG_STATS[k] for k in A.AGG_STATS}
    _search(client, aggs, lo_s, hi_s)
    return {k: A.AGG_STATS[k] - v for k, v in before.items()}


@pytest.mark.parametrize("lo_s,hi_s,blocks", [
    (100, 130, 1), (240, 1100, 5), (1400, 1500, 1), (0, 1500, 6),
    (255, 257, 2)])
def test_the_counters_follow_the_span(clients, lo_s, hi_s, blocks):
    """`aggs.span.rows` is the window's rows and `aggs.blocked.rows` those
    of the blocks that meet it, a pass: the product's count (`multi_terms`
    into 256 slots), a dense count and the dense metric under it
    (`terms` over five regions with a `stats`)."""
    ordered, shuffled = clients
    n = _segment(ordered).ndocs_pad
    aggs = {"m": AGGS["multi_terms"]["m"],
            "r": {"terms": {"field": "rs_r"},
                  "aggs": {"v": {"stats": {"field": "rs_v"}}}}}
    got = _counted(ordered, aggs, lo_s, hi_s)
    assert got["span.rows"] == 2 * (hi_s - lo_s)
    assert got["span.segment_rows"] == n
    assert got["blocked.rows"] == 3 * min(blocks * BLOCK, n)
    assert got["scatter.updates"] == 0
    whole = _counted(shuffled, aggs, lo_s, hi_s)
    assert whole["span.rows"] == whole["span.segment_rows"] == n
    assert whole["blocked.rows"] == 3 * n


def test_a_launch_without_a_span_is_handed_none(clients, monkeypatch):
    """Aggregations under a range over a column in no row order: the
    launch carries no `row_span` (the program and its arguments are what
    they were before a span existed)."""
    _, shuffled = clients
    seen = _launches(monkeypatch)
    _search(shuffled, AGGS["multi_terms"], 7, 1333)
    assert [C.ROW_SPAN in cp for _f, cp in seen] == [False]


def test_no_aggregation_no_span(clients, monkeypatch):
    ordered, _ = clients
    seen = _launches(monkeypatch)
    before = A.AGG_STATS["span.segment_rows"]
    resp = ordered.search("rs", {"size": 3, "query": {"range": {"rs_ts": {
        "gte": T0 + 5000, "lt": T0 + 9000}}}})
    assert resp["hits"]["total"]["value"] == 8
    assert [C.ROW_SPAN in cp for _f, cp in seen] == [False]
    assert A.AGG_STATS["span.segment_rows"] == before


@pytest.mark.parametrize("counters,want", [
    ({}, None),                                     # a program without them
    ({"aggs.span.rows": 0}, None),
    ({"aggs.span.rows": 0, "aggs.span.segment_rows": 0}, None),
    ({"aggs.span.rows": 0, "aggs.span.segment_rows": 1 << 24}, 0.0),
    ({"aggs.span.rows": 1 << 22, "aggs.span.segment_rows": 1 << 24}, 25.0),
    ({"aggs.span.rows": 7 << 24, "aggs.span.segment_rows": 7 << 24}, 100.0)])
def test_the_row_span_share_reader(counters, want):
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [p for p in (os.path.join(root, "benchmark"), root)
                    if p not in sys.path]
    import run as harness
    ctx = {"window": {"counters": counters, "queries": 7}}
    assert harness.read_layer_metric("agg_row_span_share", ctx) == want


# ---------------------------------------------------------------------
# after a delete-by-query (last: it changes both segments)
# ---------------------------------------------------------------------
@pytest.mark.parametrize("name", ["terms", "multi_terms", "composite",
                                  "keyword cardinality",
                                  "histogram and stats", "date_histogram"])
def test_after_a_delete_by_query_a_window_answers_alike(clients, name):
    ordered, shuffled = clients
    for client in clients:
        if _segment(client).live_count == NDOCS:
            client.delete_by_query("rs", {"query": {"term": {"rs_r": "r1"}}},
                                   refresh=True)
        assert _segment(client).live_count < NDOCS
    got = _search(ordered, AGGS[name], 240, 1100)
    assert got == _search(shuffled, AGGS[name], 240, 1100)
    kept = sum(1 for d in _docs()[480:2200] if d["rs_r"] != "r1")
    assert got[0]["value"] == kept
    # deleted rows stay where they were: the span is the window's still
    assert C.row_span(_range("rs_ts", T0 + 240_000, T0 + 1_100_000),
                      _segment(ordered)) == (480, 2200)
