"""A keyword column in which no document holds two values is, on the
device, its ordinals by document (`min_ord`) and nothing else, and every
group-by over it (`terms` with and without a metric under it,
`significant_terms`, keyword `cardinality`, keyword `value_count`) counts
that plane under the mask. A column in which one does keeps the layout by
value and gathers the match through `doc_of_value`. The form is the
structure of the column's device dict (`ops.aggs.counts_by_value`), which
the segment chooses from its own data (`Segment.kw_multi_valued`): here the
two forms give equal arrays, equal to numpy's, over a segment with gaps,
deleted documents and padded rows, and two segments of one index that
differ in structure answer as one merged segment does."""

import numpy as np
import pytest

from opensearch_tpu.index import segment as segment_mod
from opensearch_tpu.index.segment import next_pow2
from opensearch_tpu.ops import aggs as agg_ops
from opensearch_tpu.search import (agg_compiler as AC, aggregations as AGG,
                                   compiler as C, planes as PN)

NDOCS = 6000                    # pads to 8,192 rows
FIELDS = {"few": 30, "many": 3000}      # values: the dense form, a scatter
QUERY = {"range": {"v": {"gte": 100, "lt": 4100}}}


def _columns(n: int = NDOCS, seed: int = 5):
    """Per document: an ordinal a field (-1 = the document lacks the
    field: one in seven), a numeric value, and whether it is deleted (one
    in eleven)."""
    rng = np.random.default_rng(seed)
    cols = {f: np.where(np.arange(n) % 7 == 3, -1,
                        rng.integers(0, nv, n)) for f, nv in FIELDS.items()}
    cols["v"] = rng.integers(0, 5000, n)
    cols["deleted"] = np.arange(n) % 11 == 5
    return cols


def _name(field: str, o: int) -> str:
    return f"{field}-{o:05d}"           # sorts as its number does


def _source(cols: dict, i: int) -> dict:
    src = {"v": int(cols["v"][i])}
    for f in FIELDS:
        if cols[f][i] >= 0:
            src[f] = _name(f, int(cols[f][i]))
    return src


MAPPING = {"properties": {"few": {"type": "keyword"},
                          "many": {"type": "keyword"},
                          "v": {"type": "long"}}}


def _client(index: str = "t"):
    from opensearch_tpu.rest.client import RestClient
    client = RestClient()
    client.indices.create(index, {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": MAPPING})
    return client


@pytest.fixture(scope="module")
def one_segment():
    """(client, columns, segment): NDOCS documents in one segment, every
    keyword single-valued with gaps, a document in eleven deleted."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        client, cols = _client(), _columns()
        for i in range(NDOCS):
            client.index("t", _source(cols, i), id=str(i))
        client.indices.refresh("t")
        for i in np.flatnonzero(cols["deleted"]):
            client.delete("t", str(i))
        client.indices.refresh("t")
        (seg,) = client.node.indices["t"].shards[0].segments
        assert seg.ndocs == NDOCS < seg.ndocs_pad
        assert seg.live_count == NDOCS - int(cols["deleted"].sum())
        yield client, cols, seg


def _by_value(seg, monkeypatch):
    """Hand the programs `seg`'s keyword columns laid out by value, as a
    multi-valued column's are, for the length of a test."""
    monkeypatch.setattr(segment_mod.Segment, "kw_multi_valued",
                        lambda self, field: True)
    seg.drop_device()


AGGS = {
    "terms": lambda f: {"terms": {"field": f, "size": 40}},
    "terms_stats": lambda f: {"terms": {"field": f, "size": 40},
                              "aggs": {"s": {"stats": {"field": "v"}}}},
    "significant_terms": lambda f: {"significant_terms": {
        "field": f, "size": 40, "min_doc_count": 1}},
    "cardinality": lambda f: {"cardinality": {"field": f}},
    "value_count": lambda f: {"value_count": {"field": f}},
}


def _expected(kind: str, field: str, cols: dict):
    """numpy's answer over the matching live documents."""
    ok = (~cols["deleted"] & (cols["v"] >= 100) & (cols["v"] < 4100)
          & (cols[field] >= 0))
    counts = np.bincount(cols[field][ok], minlength=FIELDS[field])
    if kind == "cardinality":
        return int((counts > 0).sum())
    if kind == "value_count":
        return int(ok.sum())
    if kind == "significant_terms":     # the counts of whatever it names
        return {_name(field, o): int(c) for o, c in enumerate(counts)}
    top = np.lexsort((np.arange(len(counts)), -counts))[:40]
    out = []
    for o in top[counts[top] > 0]:
        b = {"key": _name(field, int(o)), "doc_count": int(counts[o])}
        if kind == "terms_stats":
            held = cols["v"][ok & (cols[field] == o)]
            b["s"] = {"count": len(held), "min": float(held.min()),
                      "max": float(held.max()), "sum": float(held.sum()),
                      "avg": float(held.sum()) / len(held)}
        out.append(b)
    return out


@pytest.mark.parametrize("field", list(FIELDS))
@pytest.mark.parametrize("kind", list(AGGS))
def test_a_single_valued_column_is_counted_by_document(
        one_segment, monkeypatch, kind, field):
    client, cols, seg = one_segment
    body = {"size": 0, "query": QUERY, "aggs": {"a": AGGS[kind](field)}}
    kw = seg.device_arrays()["keyword"][field]
    assert set(kw) == {"min_ord"} and not agg_ops.counts_by_value(kw)
    before = AGG.AGG_STATS["terms.gathered_rows"]
    got = client.search("t", body)["aggregations"]["a"]
    assert AGG.AGG_STATS["terms.gathered_rows"] == before
    # the same segment through the by-value form: the same response
    _by_value(seg, monkeypatch)
    try:
        kw = seg.device_arrays()["keyword"][field]
        assert set(kw) == {"min_ord", "ords", "doc_of_value"}
        # (another body to the request cache, the same request)
        forced = client.search("t", dict(body, **{"from": 0}))[
            "aggregations"]["a"]
        assert AGG.AGG_STATS["terms.gathered_rows"] - before \
            == kw["ords"].shape[0]
    finally:
        monkeypatch.undo()
        seg.drop_device()
    assert got == forced
    want = _expected(kind, field, cols)
    if kind in ("cardinality", "value_count"):
        assert got["value"] == want
    elif kind == "significant_terms":
        assert got["buckets"]
        assert all(b["doc_count"] == want[b["key"]] for b in got["buckets"])
    else:
        assert got["buckets"] == want
        assert got["sum_other_doc_count"] == (
            _expected("value_count", field, cols)
            - sum(b["doc_count"] for b in want))


# ---------------------------------------------------------------------
# the ops themselves: equal arrays, and `np.bincount`'s
# ---------------------------------------------------------------------

def _planes(seg, field: str):
    import jax.numpy as jnp
    col = seg.keyword_cols[field]
    dpad = seg.ndocs_pad
    return (segment_mod._kw_field_arrays(col, dpad, jnp, False),
            segment_mod._kw_field_arrays(col, dpad, jnp, True))


@pytest.mark.parametrize("field", list(FIELDS))
@pytest.mark.parametrize("op", ["terms_counts", "terms_sub_metric",
                                "value_count", "cardinality"])
def test_the_two_forms_give_equal_arrays(one_segment, op, field):
    import jax
    import jax.numpy as jnp
    _client, cols, seg = one_segment
    by_doc, by_val = _planes(seg, field)
    assert not agg_ops.counts_by_value(by_doc)
    assert agg_ops.counts_by_value(by_val)
    assert agg_ops.group_by_rows(by_doc) == seg.ndocs_pad
    assert agg_ops.group_by_rows(by_val) == by_val["ords"].shape[0]
    nb = next_pow2(FIELDS[field])
    assert (agg_ops.count_form(nb) == "dense") == (field == "few")
    # the mask as the program hands it over: live documents that match,
    # and (the program never does) every padded row, which no id holds
    ok = ~cols["deleted"] & (cols["v"] % 3 > 0)
    match = np.ones(seg.ndocs_pad, np.float32)
    match[:NDOCS] = ok
    match = jnp.asarray(match)
    num = seg.device_arrays()["numeric"]["v"]
    inv = agg_ops.sum_scale_inv(5000.0)
    hashes = jnp.asarray(np.random.default_rng(1).integers(
        0, 1 << 32, nb, dtype=np.uint32))
    fn = {
        "terms_counts": lambda kw: agg_ops.terms_counts(kw, match, nb),
        "terms_sub_metric": lambda kw: agg_ops.terms_sub_metric(
            kw, match, num["f32"], num["present"], nb, inv, True),
        "value_count": lambda kw: agg_ops.value_count_keyword(kw, match),
        "cardinality": lambda kw: agg_ops.cardinality_keyword_registers(
            kw, match, nb, hashes, AC.HLL_LOG2M),
    }[op]
    a, b = jax.jit(fn)(by_doc), jax.jit(fn)(by_val)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    held = ok & (cols[field] >= 0)
    # the segment's ordinals: ranks among the values that occur
    ords = np.searchsorted(np.unique(cols[field][cols[field] >= 0]),
                           cols[field])
    counts = np.bincount(ords[held], minlength=nb)
    if op == "terms_counts":
        np.testing.assert_array_equal(np.asarray(a), counts)
    elif op == "terms_sub_metric":
        np.testing.assert_array_equal(np.asarray(a["count"]), counts)
        sums = np.bincount(ords[held], cols["v"][held], minlength=nb)
        np.testing.assert_array_equal(
            agg_ops.limb_sums_to_f64(a["sum"], a["scale"]), sums)
    elif op == "value_count":
        assert int(a) == int(held.sum())
    else:
        assert int(a[1]) == int((counts > 0).sum())


# ---------------------------------------------------------------------
# a multi-valued column still gathers, and the two structures merge
# ---------------------------------------------------------------------

def _two_structures(index: str, refresh_between: bool):
    """400 documents: the first 200 hold one value of `few` (or none), the
    last 200 two. With `refresh_between` they are two segments, one of
    each structure; without, one."""
    client, cols = _client(index), _columns(400, seed=9)
    for i in range(400):
        src = _source(cols, i)
        if i >= 200 and "few" in src:
            src["few"] = [src["few"], _name("few", (i * 13) % 30)]
        client.index(index, src, id=str(i))
        if i == 199 and refresh_between:
            client.indices.refresh(index)
    client.indices.refresh(index)
    return client


@pytest.fixture(scope="module")
def structures():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        two, one = _two_structures("two", True), _two_structures("one", False)
        assert len(two.node.indices["two"].shards[0].segments) == 2
        assert len(one.node.indices["one"].shards[0].segments) == 1
        yield two, one


def test_a_multi_valued_column_still_gathers(structures):
    two, _one = structures
    single, multi = two.node.indices["two"].shards[0].segments
    assert not single.kw_multi_valued("few") and multi.kw_multi_valued("few")
    assert set(single.device_arrays()["keyword"]["few"]) == {"min_ord"}
    kw = multi.device_arrays()["keyword"]["few"]
    assert "doc_of_value" in kw and agg_ops.counts_by_value(kw)
    # `many` holds one value a document in both
    assert set(multi.device_arrays()["keyword"]["many"]) == {"min_ord"}
    before = AGG.AGG_STATS["terms.gathered_rows"]
    two.search("two", {"size": 0, "aggs": {
        "a": {"terms": {"field": "few"}},
        "n": {"value_count": {"field": "few"}},
        "m": {"terms": {"field": "many"}}}})
    # the multi-valued segment's `terms` and its value count, nothing else
    assert AGG.AGG_STATS["terms.gathered_rows"] - before \
        == 2 * kw["ords"].shape[0] > 0
    # a rematerialized column is observed again
    multi.__dict__["_kw_multi_cache"]["few"] = False
    PN.drop_segment_planes(multi, "few")
    assert multi.kw_multi_valued("few")


@pytest.mark.parametrize("kind", ["terms", "terms_stats", "value_count",
                                  "significant_terms"])
def test_two_structures_answer_as_one_merged_segment(structures, kind):
    two, one = structures
    body = {"size": 0, "query": {"range": {"v": {"gte": 500}}},
            "aggs": {"a": AGGS[kind]("few")}}
    got = two.search("two", body)["aggregations"]["a"]
    want = one.search("one", body)["aggregations"]["a"]
    assert got == want
    assert got.get("buckets", True)
