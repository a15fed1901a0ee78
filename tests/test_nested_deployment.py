"""The Q&A deployment (OpenSearch Benchmark `nested`, benchmark kind
`nested`) on the CPU at a small size: the program's `nested` query, nested
sort and inner hits against the kind's plain reference through
`RestClient` over a few thousand questions (the cell's five shapes; two
clauses that have to hold in ONE answer; every `score_mode` with a scoring
child; a question with no answer and one with thirty; inner hits from an
offset; the sort by `min` ascending), planted as one segment and
bulk-indexed as three with deleted questions; and the pieces of the
program the deployment forced: the nested sort's key as a resident plane
of the segment, in the HBM ledger and gone with it, a sorted request that
hands its launch no array, inner hits that read the page's blocks and not
the child space, a child space's filter inlined in the join's program. The
workload's other operations are held to the reference once each."""

import gc
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.join(ROOT, "benchmark"), ROOT)
                if p not in sys.path]

import nested_questions as questions        # noqa: E402
import nested_reference as reference        # noqa: E402
import run as harness                       # noqa: E402

from opensearch_tpu.obs.hbm_ledger import LEDGER        # noqa: E402
from opensearch_tpu.search import compiler as C         # noqa: E402
from opensearch_tpu.search import planes as PN          # noqa: E402

CELL = "nested.search1.answers"
NDOCS = 2400
SEEDS = (7, 3000000043)
SHAPES = ("nested", "sorted_term", "inner_hits", "inner_hits_big")
LAYOUTS = ("planted", "bulk3")
H2D_LIMIT = 2048        # bytes a sorted or nested request may hand a launch
PATH = questions.PATH


def _loaded():
    loaded = harness.load_cell(CELL)
    loaded["config"]["generator"].update(tags=300, dictionary_words=1500)
    loaded["traffic"]["params"]["tag_rank"] = [1, 120]
    return loaded


def _bulk(client, q, live):
    """The questions indexed one by one through the client in three
    refreshes, then every question `live` clears deleted."""
    settings = {"number_of_shards": 1, "number_of_replicas": 0}
    client.indices.create(harness.INDEX, {"settings": settings,
                                          "mappings": questions.MAPPING})
    n = len(live)
    for i in range(n):
        client.index(harness.INDEX, questions.question_source(q, i),
                     id=str(i))
        if i + 1 in (n // 3, 2 * n // 3, n):
            client.indices.refresh(harness.INDEX)
    for i in np.flatnonzero(~live).tolist():
        client.delete(harness.INDEX, str(i))
    client.indices.refresh(harness.INDEX)


@pytest.fixture(scope="module")
def deployments():
    """(seed, layout) -> (client, questions, stream, reference) of 2,400
    questions on a plain one-chip node (the cell's path; no mesh):
    `planted` one segment as the benchmark plants it, `bulk3` three
    segments through the client with every eleventh question deleted."""
    from opensearch_tpu.rest.client import RestClient
    made = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        kind, loaded = harness.load_kind("nested"), _loaded()

        def get(seed, layout="planted"):
            if (seed, layout) not in made:
                q = questions.generate(NDOCS, seed,
                                       loaded["config"]["generator"])
                live = np.ones(NDOCS, bool)
                client = RestClient()
                if layout == "planted":
                    questions.plant_index(client, harness.INDEX, q,
                                          loaded["config"]["index_settings"])
                else:
                    live[4::11] = False
                    _bulk(client, q, live)
                made[seed, layout] = (
                    client, q, kind.stream({"questions": q},
                                           loaded["traffic"], seed),
                    reference.Reference(q, live))
            return made[seed, layout]
        yield get


def _segments(client):
    return client.node.indices[harness.INDEX].shards[0].segments


def _spec(q, **kw) -> dict:
    spec = dict({"tag": None, "child": None, "size": 10, "inner": None,
                 "sort": None}, **kw)
    spec["body"] = reference.body(spec, q["tag_names"], questions.user_name)
    return spec


def _hold(client, ref, specs) -> list:
    held = [(s, harness.send(client, "search", [s])[0]) for s in specs]
    out = reference.hold(held, ref)
    assert out["correct"], out["numbers"]
    return [r for _s, r in held]


def _common_tag(q) -> int:
    return int(np.argmax(questions.tag_question_counts(q)))


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_answers_as_the_reference(deployments, seed, shape,
                                              layout):
    client, q, stream, ref = deployments(seed, layout)
    assert len(_segments(client)) == (1 if layout == "planted" else 3)
    specs = [s for s in stream.take(20) if s["shape"] == shape][:3]
    for resp in _hold(client, ref, specs):
        assert resp["hits"]["total"]["relation"] == "eq"
        for hit in resp["hits"]["hits"]:    # a fetch returns the question
            row = int(hit["_id"])
            assert ref.live[row]
            assert hit["_source"] == questions.question_source(q, row)
            for ih in hit.get("inner_hits", {}).get(PATH, {}).get(
                    "hits", {}).get("hits", []):
                at = int(q["ans_off"][row]) + ih["_nested"]["offset"]
                assert ih["_nested"]["field"] == PATH
                assert ih["_id"] == hit["_id"]
                assert ih["_source"] == questions.answer_source(q, at)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_two_clauses_have_to_hold_in_one_answer(deployments, layout):
    """`answers.user` = u and `answers.date` <= d: a question whose answers
    hold each condition, but no ONE answer both, does not match: what
    makes `nested` not `object`."""
    client, q, _stream, ref = deployments(SEEDS[0], layout)
    per = np.diff(q["ans_off"])
    parent = np.repeat(np.arange(NDOCS), per)
    found = None
    for row in np.flatnonzero((per >= 2) & ref.live):
        a = int(q["ans_off"][row])
        dates, users = q["ans_date_ms"][a: a + per[row]], \
            q["ans_user"][a: a + per[row]]
        late = int(np.argmax(dates))
        early = int(np.argmin(dates))
        if users[late] != users[early] and dates[early] < dates[late] \
                and (users == users[late]).sum() == 1:
            found = (int(row), int(users[late]), int(dates[early]))
            break
    assert found is not None
    row, user, date = found
    child = {"user": user, "date_lte_ms": date}
    (resp,) = _hold(client, ref, [_spec(q, child=child, size=50)])
    mask, _score = ref.child_mask(child)
    want = np.unique(parent[mask])
    assert resp["hits"]["total"]["value"] == int(ref.live[want].sum())
    assert str(row) not in {h["_id"] for h in resp["hits"]["hits"]}
    # each condition alone holds in one of its answers
    for alone in ({"user": user}, {"date_lte_ms": date}):
        m, _s = ref.child_mask(alone)
        assert m[q["ans_off"][row]: q["ans_off"][row + 1]].any()


def _scoring_child(q, mode):
    users = np.argsort(-np.bincount(q["ans_user"]))[:2]
    return {"score_users": [int(u) for u in users], "score_mode": mode,
            "date_lte_ms": int(np.quantile(q["ans_date_ms"], 0.8))}


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("mode", ["avg", "sum", "max", "min", "none"])
def test_every_score_mode_with_a_scoring_child(deployments, mode, layout):
    client, q, _stream, ref = deployments(SEEDS[1], layout)
    child = _scoring_child(q, mode)
    (resp,) = _hold(client, ref, [_spec(q, child=child, size=30,
                                        inner={"size": 5})])
    assert resp["hits"]["total"]["value"] > 3
    ok, adds, _mask, score = ref.nested_match(child)
    assert len({round(s, 6) for s in score[score > 0]}) == 2
    if mode not in ("none", "avg"):
        # the modes differ: some question holds two matching answers
        _ok, other, _m, _s = ref.nested_match(dict(child, score_mode="avg"))
        assert not np.allclose(adds[ok], other[ok]) or mode == "max"


def test_a_question_without_answers_and_one_with_thirty(deployments):
    client, q, _stream, ref = deployments(SEEDS[0])
    per = np.diff(q["ans_off"])
    assert per.min() == 0 and per.max() == 30
    full = int(np.argmax(per))
    late = int(q["ans_date_ms"].max())
    # every answer matches: the questions without one do not
    (resp,) = _hold(client, ref, [_spec(q, child={"date_lte_ms": late},
                                        size=NDOCS)])
    got = {int(h["_id"]) for h in resp["hits"]["hits"]}
    assert got == set(np.flatnonzero(per > 0).tolist())
    # the thirty answers of one question, as inner hits in block order
    tag = int(q["tags"][q["tag_off"][full]])
    (resp,) = _hold(client, ref, [_spec(
        q, tag=tag, child={"date_lte_ms": late}, size=NDOCS,
        inner={"size": 100})])
    (hit,) = [h for h in resp["hits"]["hits"] if int(h["_id"]) == full]
    inner = hit["inner_hits"][PATH]["hits"]
    assert inner["total"] == {"value": 30, "relation": "eq"}
    assert [h["_nested"]["offset"] for h in inner["hits"]] == list(range(30))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_inner_hits_from_an_offset(deployments, layout):
    client, q, _stream, ref = deployments(SEEDS[0], layout)
    child = {"date_lte_ms": int(q["ans_date_ms"].max())}
    first, = _hold(client, ref, [_spec(q, tag=_common_tag(q), child=child,
                                       size=40, inner={"size": 30})])
    paged, = _hold(client, ref, [_spec(
        q, tag=_common_tag(q), child=child, size=40,
        inner={"size": 2, "from": 1})])
    seen = 0
    for a, b in zip(first["hits"]["hits"], paged["hits"]["hits"]):
        whole = a["inner_hits"][PATH]["hits"]
        part = b["inner_hits"][PATH]["hits"]
        assert part["total"] == whole["total"]
        assert [h["_nested"] for h in part["hits"]] == \
            [h["_nested"] for h in whole["hits"][1:3]]
        seen += len(part["hits"])
    assert seen > 5


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("mode,order", [("min", "asc"), ("max", "desc"),
                                        ("max", "asc"), ("min", "desc")])
def test_the_nested_sort_puts_unanswered_questions_last(deployments, mode,
                                                        order, layout):
    client, q, _stream, ref = deployments(SEEDS[1], layout)
    tag = _common_tag(q)
    rows = ref.tag_rows(tag)
    rows = rows[ref.live[rows]]
    (resp,) = _hold(client, ref, [_spec(
        q, tag=tag, size=len(rows), sort={"mode": mode, "order": order})])
    values = [h["sort"][0] for h in resp["hits"]["hits"]]
    per = np.diff(q["ans_off"])
    missing = int((per[rows] == 0).sum())
    assert missing > 0 and len(values) == len(rows)
    assert values[-missing:] == [None] * missing
    have = values[:-missing]
    assert None not in have
    assert have == sorted(have, reverse=order == "desc")
    for hit in resp["hits"]["hits"][:5]:
        a, b = q["ans_off"][int(hit["_id"])], q["ans_off"][int(hit["_id"]) + 1]
        fn = max if mode == "max" else min
        assert hit["sort"][0] == float(fn(q["ans_date_ms"][a:b]))


def _tenant_bytes(kind: str) -> int:
    return LEDGER.snapshot()["tenants"].get(kind, {"bytes": 0})["bytes"]


def test_the_sort_key_is_a_resident_plane_of_the_segment():
    """Built by the first sorted request, charged to the ledger as what it
    is on the device, hit by the second, handed to the launch as no array,
    and gone with the segment."""
    from opensearch_tpu.rest.client import RestClient
    loaded = _loaded()
    q = questions.generate(1200, 11, loaded["config"]["generator"])
    client = RestClient()
    seg = questions.plant_index(client, "sorted", q,
                                loaded["config"]["index_settings"])
    gc.collect()
    before = _tenant_bytes("nested_sort")
    spec = _spec(q, tag=_common_tag(q), sort={"mode": "max", "order": "desc"})
    c0 = dict(PN.NESTED_STATS.items())
    h0 = C.EXECUTOR_STATS["params_h2d_bytes"]
    resp = client.search("sorted", spec["body"])
    assert resp["hits"]["hits"]
    first_h2d = C.EXECUTOR_STATS["params_h2d_bytes"] - h0
    assert PN.NESTED_STATS["sort_plane_builds"] - c0["sort_plane_builds"] == 1
    assert _tenant_bytes("nested_sort") - before == 4 * seg.ndocs_pad
    key = (questions.DATE, PATH, "max")
    assert key in seg.__dict__["_sort_dev_cache"]
    # the second request builds nothing and carries kilobytes
    other = _spec(q, tag=int(q["tags"][0]),
                  sort={"mode": "max", "order": "desc"})
    h0 = C.EXECUTOR_STATS["params_h2d_bytes"]
    client.search("sorted", other["body"])
    assert PN.NESTED_STATS["sort_plane_builds"] - c0["sort_plane_builds"] == 1
    assert 0 < C.EXECUTOR_STATS["params_h2d_bytes"] - h0 <= H2D_LIMIT
    assert first_h2d <= H2D_LIMIT < 4 * seg.ndocs_pad
    # a rematerialized field's planes go, and the plane goes with the segment
    PN.drop_segment_planes(seg, questions.DATE)
    assert key not in seg.__dict__["_sort_dev_cache"]
    assert _tenant_bytes("nested_sort") == before
    client.search("sorted", dict(spec["body"], size=11))
    assert _tenant_bytes("nested_sort") - before == 4 * seg.ndocs_pad
    client.indices.delete("sorted")
    del seg, client, resp
    gc.collect()
    assert _tenant_bytes("nested_sort") == before


@pytest.mark.parametrize("shape,page,inner", [("inner_hits", 10, 3),
                                              ("inner_hits_big", 100, 100)])
def test_inner_hits_read_the_pages_blocks(deployments, shape, page, inner):
    """One launch a request over the rows of the page's parents' blocks
    (padded to a power of two), read back as a score and a match a row:
    bounded by the page, whatever the child space holds."""
    client, q, stream, ref = deployments(SEEDS[0])
    spec = [s for s in stream.take(10) if s["shape"] == shape][0]
    c0 = dict(PN.NESTED_STATS.items())
    (resp,) = _hold(client, ref, [spec])
    d = {k: v - c0[k] for k, v in PN.NESTED_STATS.items()}
    hits = resp["hits"]["hits"]
    assert 0 < len(hits) <= page
    per = np.diff(q["ans_off"])
    blocks = int(sum(per[int(h["_id"])] for h in hits))
    assert d["inner_hits_requests"] == 1
    assert blocks <= d["inner_hits_child_rows"] <= max(2 * blocks, 64)
    assert d["inner_hits_child_rows"] <= page * 30 * 2
    assert d["inner_hits_child_rows"] < int(q["ans_off"][-1]) // 4
    assert 4 * d["inner_hits_child_rows"] < d["inner_hits_readback_bytes"] \
        <= 8 * d["inner_hits_child_rows"]
    # the query phase joined the whole block once: one clause, its slots
    (seg,) = _segments(client)
    cpad = seg.nested[PATH].child.ndocs_pad
    assert d["queries"] == 1 and d["child_rows"] == cpad
    assert d["child_rows_real"] == int(q["ans_off"][-1])
    assert d["join_updates"] == 2 * cpad and d["parents"] == seg.ndocs_pad


def test_a_nested_request_hands_its_launch_no_plane(deployments):
    """The child clause's filter is inlined in the join's program (no mask
    of the child space through the host), the parent map and the child
    columns are resident: a request carries scalars and a term's rows."""
    client, q, stream, _ref = deployments(SEEDS[0])
    spec = [s for s in stream.take(5) if s["shape"] == "nested"][0]
    masks = C.filter_mask_cache_stats()["entries"]
    h0 = C.EXECUTOR_STATS["params_h2d_bytes"]
    l0 = C.EXECUTOR_STATS["launches"]
    assert "error" not in client.search(harness.INDEX, spec["body"])
    assert C.EXECUTOR_STATS["launches"] - l0 == 1
    assert 0 < C.EXECUTOR_STATS["params_h2d_bytes"] - h0 <= H2D_LIMIT
    assert C.filter_mask_cache_stats()["entries"] == masks


def test_deleted_questions_answers_match_nothing(deployments):
    client, q, _stream, ref = deployments(SEEDS[0], "bulk3")
    late = int(q["ans_date_ms"].max())
    per = np.diff(q["ans_off"])
    gone = np.flatnonzero(~ref.live & (per > 0))
    assert len(gone) > 10
    (resp,) = _hold(client, ref, [_spec(q, child={"date_lte_ms": late},
                                        size=NDOCS)])
    got = {int(h["_id"]) for h in resp["hits"]["hits"]}
    assert got == set(np.flatnonzero(ref.live & (per > 0)).tolist())
    assert not got & set(gone.tolist())
    # nor under a nested sort, nor in a nested aggregation's count
    tag = _common_tag(q)
    _hold(client, ref, [_spec(q, tag=tag, size=200,
                              sort={"mode": "max", "order": "desc"})])
    agg = client.search(harness.INDEX, {"size": 0, "aggs": {"a": {
        "nested": {"path": PATH}}}})
    assert agg["aggregations"]["a"]["doc_count"] == int(per[ref.live].sum())


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("operation", ["randomized-term-queries",
                                       "match-all"])
def test_the_workloads_other_operations(deployments, operation, layout):
    client, q, _stream, ref = deployments(SEEDS[0], layout)
    spec = _spec(q, tag=_common_tag(q)) \
        if operation == "randomized-term-queries" else _spec(q)
    (resp,) = _hold(client, ref, [spec])
    assert resp["hits"]["total"]["value"] == (
        int(ref.live[ref.tag_rows(spec["tag"])].sum())
        if spec["tag"] is not None else int(ref.live.sum()))


def test_the_query_cost_prices_the_clause_by_its_child_rows(deployments):
    from opensearch_tpu.obs import query_cost
    client, q, stream, _ref = deployments(SEEDS[0])
    (seg,) = _segments(client)
    blk = seg.nested[PATH]
    spec = [s for s in stream.take(5) if s["shape"] == "nested"][0]
    resp = client.search(harness.INDEX, dict(spec["body"], profile=True))
    cost = resp["profile"]["cost"]
    df = int(questions.tag_question_counts(q)[spec["tag"]])
    assert cost["predicted_scatter_adds"] == df + 2 * blk.child.ndocs
    assert cost["predicted_bytes_gathered"] >= \
        query_cost.NESTED_CHILD_BYTES * blk.child.ndocs
    assert cost["actual_scatter_adds"] >= 2 * blk.child.ndocs_pad
    assert cost["actual_bytes_gathered"] >= 4 * blk.child.ndocs_pad
