"""The log-analytics-by-keyword deployment (OpenSearch Benchmark `big5`,
benchmark kind `big5`) on the CPU at a small size: the program's column
executor against the kind's plain reference over the cell's seven request
shapes (`terms`, `multi_terms`, `composite`, keyword `cardinality` under
drawn ranges), and the pieces of the program the deployment forced: counts
that stay arrays by ordinal until the response's buckets are known, the
combinations that occur as a resident plane, a composite paged through all
of them whatever the product of its sources, a keyword cardinality exact
where one segment answers, and the counters that say so."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.join(ROOT, "benchmark"), ROOT)
                if p not in sys.path]

import big5_events as events               # noqa: E402
import big5_reference as reference         # noqa: E402
import run as harness                      # noqa: E402

from opensearch_tpu.ops import aggs as agg_ops         # noqa: E402
from opensearch_tpu.search import aggregations as A    # noqa: E402
from opensearch_tpu.index.segment import next_pow2      # noqa: E402
from opensearch_tpu.search import (agg_compiler as AC,     # noqa: E402
                                   compiler as C, planes as PN,
                                   programs as PG)

CELL = "big5.search1.terms"
NDOCS = 20_000
SEEDS = (7, 2147483693, 3000000043)
SPAN = (events.SPAN_START_S, events.SPAN_START_S + events.SPAN_S)
STREAM, PROCESS, REGION = (reference.STREAM, reference.PROCESS,
                           reference.REGION)


@pytest.fixture(scope="module")
def deployments():
    """seed -> (client, built, stream, kind) of a 20,000-event collection
    on a plain one-chip node (the cell's path; no mesh), built once a
    seed."""
    from opensearch_tpu.rest.client import RestClient
    made = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        kind = harness.load_kind("big5")
        loaded = harness.load_cell(CELL)

        def get(seed):
            if seed not in made:
                config = dict(loaded["config"], ndocs=NDOCS, corpus_seed=seed)
                client = RestClient()
                built = kind.build(config, seed, client, harness.INDEX)
                made[seed] = (client, built, kind.stream(
                    built, loaded["traffic"], seed), kind)
            return made[seed]
        yield get


def _segment(client):
    (seg,) = client.node.indices[harness.INDEX].shards[0].segments
    return seg


def _search(client, aggs: dict, lo_s: int = SPAN[0], hi_s: int = SPAN[1]):
    resp = client.search(harness.INDEX, {
        "size": 0, "query": {"range": {"@timestamp": {
            "gte": events.iso_seconds(lo_s), "lt": events.iso_seconds(hi_s)}}},
        "aggs": aggs})
    assert "error" not in resp
    return resp["aggregations"]


@pytest.mark.parametrize("shape", reference.SHAPES)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_answers_as_the_reference(deployments, seed, shape):
    client, built, stream, kind = deployments(seed)
    ref = kind.reference_of(built)
    specs = [s for s in stream.take(28) if s["shape"] == shape]
    assert len(specs) == 4
    held = []
    for spec in specs + [stream.twin(s) for s in specs]:
        held.append((spec, client.search(harness.INDEX, spec["body"])))
    out = reference.hold(held, ref)
    assert out["numbers"] == {k: [0, 0] for k in reference.LIMITS}
    assert out["correct"] is True and out["compared"] == 8
    assert all(ref.answer(s)["total"] > 0 for s, _r in held)


def test_all_26_fields_are_in_the_mapping_and_the_segment(deployments):
    client, built, _stream, _kind = deployments(SEEDS[0])
    props = client.indices.get_mapping(harness.INDEX)[harness.INDEX][
        "mappings"]["properties"]
    assert props == events.MAPPING["properties"]
    assert props["aws"]["properties"]["cloudwatch"]["properties"][
        "log_stream"] == {"type": "keyword"}
    assert props["message"] == {"type": "text"}
    seg = _segment(client)
    assert (len(seg.numeric_cols), len(seg.keyword_cols),
            len(seg.postings)) == (5, 20, 21)
    assert set(seg.keyword_cols) == set(events.KEYWORDS)
    assert seg.postings["message"].size > 17 * NDOCS
    # arrival order: @timestamp non-decreasing, the other dates not
    assert (np.diff(built["columns"]["ts_s"]) >= 0).all()
    assert (np.diff(built["columns"]["ingested_ms"]) < 0).any()
    src = seg.sources[3]
    assert src["tags"] == ["preserve_original_event"]
    assert src["message"].split()[4] == src["process"]["name"] + ":"


def test_a_tie_in_the_count_breaks_by_key_and_size_may_pass_the_vocabulary(
        deployments):
    client, built, _stream, _kind = deployments(SEEDS[0])
    codes, names = built["columns"]["kw"][STREAM]
    got = _search(client, {"s": {"terms": {"field": STREAM,
                                           "size": 100_000}}})["s"]
    counts = np.bincount(codes, minlength=len(names))
    want = sorted(((names[c], int(k)) for c, k in enumerate(counts) if k),
                  key=lambda kv: (-kv[1], kv[0]))
    assert [(b["key"], b["doc_count"]) for b in got["buckets"]] == want
    assert got["sum_other_doc_count"] == 0
    assert got["doc_count_error_upper_bound"] == 0
    # the quiet streams tie in their counts: many ties were broken
    ties = sum(a[1] == b[1] for a, b in zip(want, want[1:]))
    assert ties > 50
    # by key both ways, and the exact rest of a short page
    for order, rev in (("asc", False), ("desc", True)):
        page = _search(client, {"s": {"terms": {
            "field": STREAM, "size": 7, "order": {"_key": order}}}})["s"]
        keys = sorted((k for k, _c in want), reverse=rev)[:7]
        assert [b["key"] for b in page["buckets"]] == keys
        assert page["sum_other_doc_count"] == NDOCS - sum(
            b["doc_count"] for b in page["buckets"])
    least = _search(client, {"s": {"terms": {
        "field": STREAM, "size": 5, "order": {"_count": "asc"}}}})["s"]
    assert [(b["key"], b["doc_count"]) for b in least["buckets"]] == \
        sorted(want, key=lambda kv: (kv[1], kv[0]))[:5]


def _composite_pages(client, sources: list, size: int, lo_s=SPAN[0],
                     hi_s=SPAN[1]):
    seen, after = [], None
    while True:
        body = {"sources": sources, "size": size}
        if after is not None:
            body["after"] = after
        agg = _search(client, {"c": {"composite": body}}, lo_s, hi_s)["c"]
        if not agg["buckets"]:
            assert "after_key" not in agg
            return seen
        assert agg["after_key"] == agg["buckets"][-1]["key"]
        assert len(agg["buckets"]) <= size
        seen += [(tuple(b["key"].values()), b["doc_count"])
                 for b in agg["buckets"]]
        after = agg["after_key"]


def test_a_composite_pages_with_after_to_the_end(deployments):
    """The pages' union is every combination that occurs, none twice, in
    key order under each source's `order`."""
    client, built, _stream, _kind = deployments(SEEDS[1])
    kw = built["columns"]["kw"]
    sources = [{"p": {"terms": {"field": PROCESS, "order": "desc"}}},
               {"r": {"terms": {"field": REGION, "order": "asc"}}},
               {"s": {"terms": {"field": STREAM, "order": "asc"}}}]
    seen = _composite_pages(client, sources, 400)
    want = {}
    for d in range(NDOCS):
        key = tuple(kw[f][1][int(kw[f][0][d])]
                    for f in (PROCESS, REGION, STREAM))
        want[key] = want.get(key, 0) + 1
    assert len(seen) == len(want) > 3000
    assert dict(seen) == want
    assert [k for k, _c in seen] == sorted(want, key=lambda k: (
        tuple(-ord(ch) for ch in k[0]) + (1,), k[1], k[2]))
    # a window's pages too, and an `after` that names no bucket
    lo, hi = SPAN[0] + 86400, SPAN[0] + 3 * 86400
    window = _composite_pages(client, sources[:2], 10, lo, hi)
    m = (built["columns"]["ts_s"] >= lo) & (built["columns"]["ts_s"] < hi)
    pairs = {}
    for d in np.flatnonzero(m).tolist():
        key = tuple(kw[f][1][int(kw[f][0][d])] for f in (PROCESS, REGION))
        pairs[key] = pairs.get(key, 0) + 1
    assert dict(window) == pairs and len(window) == len(pairs)
    agg = _search(client, {"c": {"composite": {
        "sources": sources[:2], "size": 1000,
        "after": {"p": "kerneb", "r": "zz"}}}})["c"]
    assert [tuple(b["key"].values()) for b in agg["buckets"]] == \
        [k for k, _c in _composite_pages(client, sources[:2], 1000)
         if k[0] < "kerneb"]


def test_three_keyword_sources_past_2_to_the_22_answer(deployments):
    client, _built, _stream, _kind = deployments(SEEDS[2])
    seg = _segment(client)
    sizes = [len(seg.keyword_cols[f].vocab)
             for f in ("event.id", STREAM, PROCESS)]
    assert sizes[0] * sizes[1] * sizes[2] > 1 << 22
    before = A.AGG_STATS["composite.combinations"]
    sources = [{"e": {"terms": {"field": "event.id"}}},
               {"s": {"terms": {"field": STREAM}}},
               {"p": {"terms": {"field": PROCESS}}}]
    agg = _search(client, {"c": {"composite": {"sources": sources}}})["c"]
    assert len(agg["buckets"]) == 10
    assert agg["buckets"][0]["key"]["e"] == seg.keyword_cols[
        "event.id"].vocab[0]
    # the bucket space is the combinations that occur: at most the rows
    assert 0 < A.AGG_STATS["composite.combinations"] - before <= NDOCS
    assert sum(c for _k, c in _composite_pages(client, sources, 4000)) \
        == NDOCS


def _counted(client, body: dict) -> dict:
    before = {k: A.AGG_STATS[k] for k in A.AGG_STATS}
    stats = {k: C.EXECUTOR_STATS[k] for k in C.EXECUTOR_STATS}
    resp = client.search(harness.INDEX, body)
    assert "error" not in resp
    out = {k: A.AGG_STATS[k] - v for k, v in before.items()}
    out.update({k: C.EXECUTOR_STATS[k] - v for k, v in stats.items()})
    out["hits"] = resp["hits"]["total"]["value"]
    return out


def test_the_counters_say_what_a_launch_counted(deployments):
    client, _built, stream, _kind = deployments(SEEDS[1])
    seg = _segment(client)
    n = seg.ndocs_pad
    specs = {s["shape"]: s for s in stream.take(7)}
    for spec in specs.values():         # planes built
        client.search(harness.INDEX, stream.twin(spec)["body"])
    nstreams = len(seg.keyword_cols[STREAM].vocab)
    slots = next_pow2(nstreams)
    assert agg_ops.count_form(slots) == "dense"     # 400 streams here
    # (each body once: a second asking is the request cache's)
    counted = {shape: _counted(client, spec["body"])
               for shape, spec in specs.items()}
    got = counted["keyword-terms"]
    assert (got["terms.ordinals"], got["blocked.rows"],
            got["scatter.updates"], got["launches"]) == (slots, n, 0, 1)
    # the response's buckets are the records: not the vocabulary
    assert got["terms.records"] == min(500, nstreams)
    got = counted["keyword-terms-low-cardinality"]
    assert got["terms.records"] == 50
    got = counted["multi_terms-keyword"]
    assert got["terms.records"] == 10 and got["blocked.rows"] == n
    assert 0 < got["terms.ordinals"] <= next_pow2(12 * 26)
    got = counted["composite-terms"]
    assert got["terms.records"] == 10
    assert 0 < got["composite.combinations"] == got["terms.ordinals"] \
        <= 12 * 26
    got = counted["composite_terms-keyword"]
    assert got["terms.records"] == 10
    combos = got["composite.combinations"]
    assert 12 * 26 < combos <= NDOCS
    # from `_PRODUCT_BUCKETS` combinations on the count scatters
    assert (got["scatter.updates"], got["blocked.rows"]) == (
        (n, 0) if agg_ops.count_form(combos) == "scatter" else (0, n))
    # a keyword cardinality is the `terms_counts` under its registers
    for shape in ("cardinality-agg-low", "cardinality-agg-high"):
        got = counted[shape]
        assert got["blocked.rows"] + got["scatter.updates"] == n
        assert (got["terms.ordinals"], got["terms.records"]) == (0, 0)
    # every body stands under a range on `@timestamp`, which is in row
    # order and has a value in every row: the launch's row span is the
    # window's rows, to the row (PR 49). The loops read the blocks that
    # meet it, and one block of 2^15 rows is this whole segment (the
    # blocks themselves: tests/test_row_span.py)
    assert seg.numeric_cols["@timestamp"].in_row_order is not None
    for shape, got in counted.items():
        assert got["span.segment_rows"] == n == agg_ops.dense_block_rows(n)
        assert 0 < got["span.rows"] == got["hits"] < NDOCS, shape
        assert got["blocked.rows"] + got["scatter.updates"] == n
    # 2 to 24 hours of 14 days, and half of them or more
    assert counted["composite-terms"]["span.rows"] < 0.08 * NDOCS
    assert counted["keyword-terms"]["span.rows"] > 0.45 * NDOCS


@pytest.mark.parametrize("shape", reference.SHAPES)
def test_no_operation_gathers_the_match_by_value(deployments, shape):
    """Every keyword of the deployment holds one value a document at most
    (`tags` an array of one), so each is its ordinals by document on the
    device and no launch gathers the match through `doc_of_value`."""
    client, _built, stream, _kind = deployments(SEEDS[1])
    seg = _segment(client)
    assert not any(seg.kw_multi_valued(f) for f in seg.keyword_cols)
    assert all(set(kw) == {"min_ord"}
               for kw in seg.device_arrays()["keyword"].values())
    spec = next(s for s in stream.take(7) if s["shape"] == shape)
    got = _counted(client, spec["body"])
    assert got["launches"] == 1 and got["terms.gathered_rows"] == 0


@pytest.mark.parametrize("counters,want", [
    ({}, None),                                 # a program without it
    ({"aggs.terms.gathered_rows": 0}, 0.0),
    ({"aggs.terms.gathered_rows": 4 * (1 << 24)}, 4 * (1 << 24) / 7e6)])
def test_the_gathered_rows_reader(counters, want):
    ctx = {"window": {"counters": counters, "queries": 7}}
    assert harness.read_layer_metric(
        "terms_gathered_mrows_per_query", ctx) == want
    # the kind hands the benchmark every counter of the group
    assert "aggs.terms.gathered_rows" in harness.load_kind(
        "big5").counters(None)


@pytest.mark.parametrize("by_value", [True, False])
@pytest.mark.parametrize("nb,form", [
    (300, "blocked"), (5000, "blocked"),        # dense; a product
    (agg_ops._PRODUCT_BUCKETS, "scatter")])
def test_agg_cost_counts_a_keyword_cardinality_under_its_form(nb, form,
                                                              by_value):
    n = 1 << 12
    kw = {"min_ord": np.zeros(n, np.int32)}
    if by_value:            # three values a document, and their documents
        kw.update(ords=np.zeros(3 * n, np.int32),
                  doc_of_value=np.zeros(3 * n, np.int32))
    rows = 3 * n if by_value else n
    seg_arrays = {"live": np.zeros(n, np.float32), "keyword": {"k": kw}}
    cost = {"scatter": 0, "blocked": 0, "sub_buckets": 0, "ordinals": 0,
            "combinations": 0, "gathered": 0}
    PG.agg_cost(("card_kw", "p", "k", nb), seg_arrays, cost)
    want = dict.fromkeys(cost, 0)
    want[form] = rows
    want["gathered"] = rows if by_value else 0
    assert cost == want
    want.pop("gathered")
    cost = {"scatter": 0, "blocked": 0, "sub_buckets": 0, "ordinals": 0,
            "combinations": 0}
    PG.agg_cost(("composite", "p", None, nb, ()), seg_arrays, cost)
    assert cost == dict(want, **{form: n, "ordinals": nb,
                                 "combinations": nb})


@pytest.mark.parametrize("shape", reference.SHAPES)
def test_a_request_ships_no_plane_from_the_host(deployments, shape):
    """The second request's params hold scalars and a cardinality's hash
    table: no array of `ndocs_pad` elements."""
    client, _built, stream, _kind = deployments(SEEDS[0])
    seg = _segment(client)
    spec = next(s for s in stream.take(7) if s["shape"] == shape)
    client.search(harness.INDEX, stream.twin(spec)["body"])    # planes built
    before = C.EXECUTOR_STATS["params_h2d_bytes"]
    resp = client.search(harness.INDEX, spec["body"])
    assert "error" not in resp
    shipped = C.EXECUTOR_STATS["params_h2d_bytes"] - before
    table = 4 * next_pow2(len(seg.keyword_cols[reference.AGENT].vocab))
    assert 0 < shipped <= 256 + (table if "cardinality" in shape else 0)
    assert shipped < seg.ndocs_pad


def test_the_combination_planes_are_resident_and_leave_with_their_field(
        deployments):
    from opensearch_tpu.obs.hbm_ledger import LEDGER
    client, _built, stream, _kind = deployments(SEEDS[2])
    seg = _segment(client)
    specs = {s["shape"]: s for s in stream.take(7)}

    def planes():
        return LEDGER.snapshot()["tenants"].get("agg_bucket_plane",
                                                {"bytes": 0})["bytes"]
    for f in (PROCESS, STREAM):
        PN.drop_segment_planes(seg, f)
    base = planes()
    builds = PN.BUCKET_PLANE_STATS["builds"]
    for shape in ("multi_terms-keyword", "composite-terms",
                  "composite_terms-keyword"):
        client.search(harness.INDEX, specs[shape]["body"])
    # three planes of a value a padded row, each built once
    assert PN.BUCKET_PLANE_STATS["builds"] - builds == 3
    assert planes() - base == 3 * 4 * seg.ndocs_pad
    hits = PN.BUCKET_PLANE_STATS["hits"]
    for shape in ("multi_terms-keyword", "composite_terms-keyword"):
        client.search(harness.INDEX, stream.twin(specs[shape])["body"])
    assert PN.BUCKET_PLANE_STATS["builds"] - builds == 3
    assert PN.BUCKET_PLANE_STATS["hits"] > hits
    keys = set(seg._combo_plane_cache)
    assert {k[0] for k in keys} == {(PROCESS, REGION),
                                    (PROCESS, REGION, STREAM)}
    # a rematerialized field takes every plane it is part of with it
    PN.drop_segment_planes(seg, STREAM)
    assert planes() - base == 2 * 4 * seg.ndocs_pad
    PN.drop_segment_planes(seg, REGION)
    assert planes() == base and not seg._combo_plane_cache
    # and the next request builds anew, the answer the same
    _search(client, reference.agg_body("multi_terms-keyword"), SPAN[0] + 7)
    assert planes() - base == 4 * seg.ndocs_pad


def test_the_spans_cover_the_new_work(deployments):
    client, _built, stream, _kind = deployments(SEEDS[2])
    spec = next(s for s in stream.take(7)
                if s["shape"] == "composite_terms-keyword")
    client.node.tracer._traces.clear()
    client.search(harness.INDEX, spec["body"])
    names = set()

    def walk(node):
        names.add(node["name"])
        for c in node.get("children", []):
            walk(c)
    for t in client.get_traces()["traces"]:
        walk(t)
    assert {"search.aggs.prepare", "search.aggs.partial",
            "device.wait"} <= names


# ---------------------------------------------------------------------
# more than one segment: arrays merge by vocabulary, a cardinality by
# its registers
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def three_segments():
    """3,000 events of the generator indexed through the client in three
    refreshes: three segments with vocabularies of their own."""
    from opensearch_tpu.rest.client import RestClient
    loaded = harness.load_cell(CELL)
    cols = events.generate(3000, 99, loaded["config"]["generator"])
    sources = events._LazySources(cols)
    client = RestClient()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        client.indices.create("logs", {
            "settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": events.MAPPING})
        for i in range(3000):
            client.index("logs", sources[i], id=str(i))
            if i % 1000 == 999:
                client.indices.refresh("logs")
        assert len(client.node.indices["logs"].shards[0].segments) == 3
        yield client, cols


def test_segments_merge_by_vocabulary_array_to_array(three_segments):
    client, cols = three_segments
    ref = reference.Reference(cols)
    spec = {"shape": "keyword-terms", "lo_s": SPAN[0], "hi_s": SPAN[1]}
    for shape in ("keyword-terms", "multi_terms-keyword", "composite-terms",
                  "composite_terms-keyword"):
        spec = dict(spec, shape=shape)
        body = {"size": 0, "aggs": reference.agg_body(shape)}
        before = A.AGG_STATS["terms.records"]
        resp = client.search("logs", body)
        got = reference.compare(spec, resp, ref.answer(spec))
        assert not any(got.values()), (shape, got)
        # three partials, and still no record a vocabulary entry
        assert A.AGG_STATS["terms.records"] - before <= (
            500 if shape == "keyword-terms" else 30)
    # a metric under the buckets rides the arrays too
    resp = client.search("logs", {"size": 0, "aggs": {"s": {
        "terms": {"field": PROCESS, "size": 3},
        "aggs": {"m": {"stats": {"field": "metrics.size"}}}}}})
    codes, names = cols["kw"][PROCESS]
    for b in resp["aggregations"]["s"]["buckets"]:
        held = cols["size"][codes == names.index(b["key"])]
        assert b["m"] == {"count": len(held), "min": float(held.min()),
                          "max": float(held.max()),
                          "sum": float(held.sum()),
                          "avg": float(held.sum()) / len(held)}


def test_a_merged_cardinality_holds_within_three_standard_errors(
        three_segments):
    client, cols = three_segments
    for field in (REGION, reference.AGENT, "event.id"):
        exact = len(np.unique(cols["kw"][field][0]))
        resp = client.search("logs", {"size": 0, "aggs": {
            "c": {"cardinality": {"field": field}}}})
        value = resp["aggregations"]["c"]["value"]
        assert abs(value - exact) <= max(
            3 * 1.04 / np.sqrt(1 << AC.HLL_LOG2M) * exact, 0.5), field


def test_ordinal_buckets_merge_and_finalize_like_records():
    """`OrdinalBuckets.merged` + `finalize` against the record path over
    the same partials: equal responses."""
    rng = np.random.default_rng(5)
    node = A.parse_aggs({"t": {"terms": {"field": "k", "size": 7},
                               "aggs": {"m": {"stats": {"field": "v"}}}}})[0]
    arrays, records = [], []
    for part in range(3):
        keys = sorted(rng.choice(60, 30, replace=False).tolist())
        counts = rng.integers(0, 4, 30)
        cols = {"count": counts.astype(float),
                "sum": (rng.integers(0, 99, 30) * counts).astype(float),
                "sumsq": rng.integers(0, 99, 30).astype(float),
                "min": rng.random(30), "max": 1 + rng.random(30)}
        ob = A.OrdinalBuckets([f"k{k:02d}" for k in keys], counts,
                              {"m": cols})
        arrays.append({"buckets": ob})
        records.append({"buckets": dict(ob.items())})
    got = A.finalize(node, A.merge_partials(node, arrays))
    want = A.finalize(node, A.merge_partials(node, records))
    assert got["buckets"] and got == want
    assert [b["doc_count"] for b in got["buckets"]] == sorted(
        (b["doc_count"] for b in got["buckets"]), reverse=True)


def test_an_array_partial_crosses_a_process_boundary():
    """`cluster/distnode.py` pickles a shard's partials: the arrays, and a
    `ComboSpace` as their keys, come back whole."""
    import pickle
    space = PN.ComboSpace(np.asarray([0, 3, 4, 7], np.int64), [2, 4],
                          (True, False), [("terms", ["a", "b"]),
                                          ("terms", ["w", "x", "y", "z"])])
    ob = A.OrdinalBuckets(space, np.asarray([5, 0, 2, 1]), {})
    back = pickle.loads(pickle.dumps({"buckets": ob}))["buckets"]
    assert list(back.keys) == list(space) == [
        ("b", "w"), ("b", "z"), ("a", "w"), ("a", "z")]
    assert np.array_equal(back.counts, ob.counts)
    assert back.keys.first_after(("b", "x")) == 1
    assert back.keys.first_after(("b", "z")) == 2
    assert back.keys.first_after(("a", "z")) == 4
    assert back.keys.first_after(("c", "a")) == 0
