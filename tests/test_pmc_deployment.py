"""The full-text literature deployment (OpenSearch Benchmark `pmc`,
benchmark kind `pmc`) on the CPU at a small size: the program's
`match_phrase` against the kind's plain reference over a few hundred long
articles (the cell's three shapes and the phrases that are hard: one that
begins with the most frequent term, a word repeated, words that co-occur
and never meet, four words), in one segment and in three, with deleted
documents; and the pieces of the program the deployment forced: the
positions as resident planes of the segment, in the HBM ledger and gone
with it, a request that carries offsets and no plane, an exact phrase that
anchors on its cheapest slot and counts the same from any. The workload's
other operations are held to the reference once each."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.join(ROOT, "benchmark"), ROOT)
                if p not in sys.path]

import pmc_articles as articles            # noqa: E402
import pmc_reference as reference          # noqa: E402
import run as harness                      # noqa: E402

from opensearch_tpu.obs.hbm_ledger import LEDGER        # noqa: E402
from opensearch_tpu.ops import positions as pos_ops     # noqa: E402
from opensearch_tpu.search import compiler as C         # noqa: E402

CELL = "pmc.search1.phrase"
NDOCS = 240
SEEDS = (7, 3000000043)
SHAPES = ("phrase2", "phrase3", "phrase3_common")
RTOL = 1e-5
H2D_LIMIT = 2048        # bytes a phrase request may hand a launch


def _config(loaded, seed):
    config = dict(loaded["config"], ndocs=NDOCS, corpus_seed=seed)
    config["generator"] = dict(config["generator"], vocabulary=20_000,
                               journals=40, length_mu=7.5,
                               length_clip=[100, 8000])
    return config


@pytest.fixture(scope="module")
def deployments():
    """(seed, segments) -> (client, articles, stream, reference) of 240
    articles of about 2,000 tokens on a plain one-chip node (the cell's
    path; no mesh), every twelfth deleted, built once a key."""
    from opensearch_tpu.rest.client import RestClient
    made = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        kind = harness.load_kind("pmc")
        loaded = harness.load_cell(CELL)
        loaded["traffic"]["params"]["rarest_rank"] = [20, 1500]

        def get(seed, nsegs=1):
            if (seed, nsegs) not in made:
                config = _config(loaded, seed)
                arts = articles.generate(NDOCS, seed, config["generator"])
                arts["live"][5::12] = False
                client = RestClient()
                cuts = [NDOCS * i // nsegs for i in range(1, nsegs)]
                articles.plant_index(client, harness.INDEX, arts,
                                     config["index_settings"], cuts)
                ref = reference.Reference(arts["tok"], arts["offsets"],
                                          arts["live"])
                made[seed, nsegs] = (client, arts, kind.stream(
                    {"articles": arts}, loaded["traffic"], seed), ref)
            return made[seed, nsegs]
        yield get


def _segments(client):
    return client.node.indices[harness.INDEX].shards[0].segments


def _phrase(client, arts, terms, **extra):
    text = " ".join(arts["words"][t] for t in terms)
    resp = client.search(harness.INDEX, {"query": {"match_phrase": {
        "body": dict({"query": text}, **extra) if extra else text}}})
    assert "error" not in resp
    return resp


def _held(client, arts, ref, terms) -> dict:
    spec = {"terms": [int(t) for t in terms]}
    out = reference.hold([(spec, _phrase(client, arts, terms))], ref, RTOL)
    assert out["correct"], out
    return ref.page(terms)


@pytest.mark.parametrize("nsegs", [1, 3])
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_answers_as_the_reference(deployments, seed, shape,
                                              nsegs):
    client, arts, stream, ref = deployments(seed, nsegs)
    assert len(_segments(client)) == nsegs
    specs = [s for s in stream.take(16) if s["shape"] == shape][:2]
    held = [(s, harness.send(client, "search", [s])[0]) for s in specs]
    out = reference.hold(held, ref, RTOL)
    assert out["correct"], out
    assert out["numbers"]["score_rel_err_max"][0] <= RTOL
    for _spec, resp in held:
        assert resp["hits"]["total"]["relation"] == "eq"
        for hit in resp["hits"]["hits"]:    # a fetch returns the article
            assert not arts["live"][int(hit["_id"])] is False
            assert set(hit["_source"]) == set(articles.MAPPING["properties"])
            assert hit["_source"]["body"] == articles.body(
                arts, int(hit["_id"]))


def _hard_phrases(arts, ref) -> dict:
    """The phrases that are hard, found in the corpus itself."""
    tok, table = arts["tok"], arts["table"]
    cf = articles.collection_frequency(arts)
    top = int(np.argmax(cf))
    out = {"begins_with_the_most_frequent": (top, int(table[top, 0]))}
    # a word repeated: the commonest "a b a" of the stream
    aba = np.flatnonzero(tok[:-2] == tok[2:])
    pairs, counts = np.unique(tok[aba] * len(cf) + tok[aba + 1],
                              return_counts=True)
    best = int(pairs[np.argmax(counts)])
    out["a_word_repeated"] = (best // len(cf), best % len(cf),
                              best // len(cf))
    # every word co-occurs and the words never meet in this order
    for a in np.argsort(-cf)[40:400]:
        for b in np.argsort(-cf)[40:400]:
            if a != b and b not in table[a]:
                docs, _f = ref.frequencies([int(a), int(b)])
                both = np.intersect1d(ref._postings(a)[0],
                                      ref._postings(b)[0])
                if not len(docs) and len(both):
                    out["no_occurrence"] = (int(a), int(b))
                    break
        if "no_occurrence" in out:
            break
    # four words: a chain of the table that occurs
    a = int(np.argsort(-cf)[30])
    chain = [a]
    for _ in range(3):
        chain.append(int(table[chain[-1], 0]))
    out["four_words"] = tuple(chain)
    return out


@pytest.mark.parametrize("which", ["begins_with_the_most_frequent",
                                   "a_word_repeated", "no_occurrence",
                                   "four_words"])
@pytest.mark.parametrize("nsegs", [1, 3])
def test_the_hard_phrases(deployments, nsegs, which):
    client, arts, _stream, ref = deployments(SEEDS[0], nsegs)
    terms = _hard_phrases(arts, ref)[which]
    page = _held(client, arts, ref, terms)
    if which == "no_occurrence":
        assert page["total"] == 0
    else:
        assert page["total"] > 0
    if which == "a_word_repeated":
        assert terms[0] == terms[2] != terms[1]


def test_explain_counts_the_phrase_as_the_device_does(deployments):
    """`explain`'s host mirror (`executor._host_phrase_freq`, which leads
    with the first word) reads the frequency the device's join found from
    the cheapest slot, and the reference's."""
    client, arts, _stream, ref = deployments(SEEDS[0])
    terms = _hard_phrases(arts, ref)["a_word_repeated"]
    text = " ".join(arts["words"][t] for t in terms)
    resp = client.search(harness.INDEX, {
        "explain": True, "query": {"match_phrase": {"body": text}}})
    docs, f = ref.frequencies(terms)
    assert resp["hits"]["hits"]
    for hit in resp["hits"]["hits"]:
        want = int(f[np.searchsorted(docs, int(hit["_id"]))])
        exp = hit["_explanation"]
        assert f"sloppyFreq {want:.3f}/" in str(exp), exp
        assert exp["value"] == pytest.approx(hit["_score"], rel=1e-5)


def test_deleted_documents_score_nothing(deployments):
    client, arts, _stream, ref = deployments(SEEDS[0])
    terms = _hard_phrases(arts, ref)["begins_with_the_most_frequent"]
    docs, _f = ref.frequencies(terms)
    assert (~arts["live"][docs]).any()      # the phrase stands in one
    resp = _phrase(client, arts, terms)
    assert resp["hits"]["total"]["value"] == int(arts["live"][docs].sum())
    assert all(arts["live"][int(h["_id"])] for h in resp["hits"]["hits"])


def test_every_slot_as_anchor_counts_the_same(deployments):
    """`phrase_freqs` at slop 0 from each slot of a three-word phrase
    and of one with a word repeated: the frequencies of the reference."""
    import jax.numpy as jnp
    client, arts, _stream, ref = deployments(SEEDS[0])
    (seg,) = _segments(client)
    pb, planes = seg.postings["body"], seg.device_positions("body")
    hard = _hard_phrases(arts, ref)
    for terms in (hard["four_words"][:3], hard["a_word_repeated"]):
        docs, f = ref.frequencies(terms)
        want = np.zeros(seg.ndocs_pad, np.float32)
        want[docs] = f
        assert want.sum() > 0
        wins = []
        for t in terms:
            a, b = pb.row_slice(pb.row(arts["words"][t]))
            lo, hi = int(pb.pos_starts[a]), int(pb.pos_starts[b])
            wins.append(pos_ops.resident(
                planes, np.int32(lo), np.int32(hi - lo),
                pos_ops.search_levels(hi - lo)))
        for anchor in range(len(terms)):
            others = [i for i in range(len(terms)) if i != anchor]
            ad, ap = pos_ops.anchor_window(
                wins[anchor], pos_ops.anchor_bucket(int(wins[anchor].n)))
            got = pos_ops.phrase_freqs(
                ad, ap, [wins[i] for i in others], jnp.float32(0),
                seg.ndocs_pad, shifts=[i - anchor for i in others])
            assert np.array_equal(np.asarray(got), want), (terms, anchor)


def test_an_exact_phrase_anchors_on_its_cheapest_slot(deployments):
    """A phrase that begins with the most frequent term launches an anchor
    window of its rarer word's bucket; the sloppy form keeps slot 0."""
    client, arts, _stream, ref = deployments(SEEDS[0])
    cf = articles.collection_frequency(arts)
    top = int(np.argmax(cf))    # its rarest partner: no earlier test's body
    terms = (top, int(min(arts["table"][top], key=lambda t: cf[t])))
    assert cf[terms[0]] > 4 * cf[terms[1]]
    before = dict(C.PHRASE_STATS)
    _phrase(client, arts, terms)
    exact = {k: C.PHRASE_STATS[k] - before[k] for k in before}
    assert exact["queries"] == 1 and exact["host_pair_builds"] == 0
    assert exact["anchor_positions"] == cf[terms[1]]
    assert exact["anchor_slots"] == pos_ops.anchor_bucket(int(cf[terms[1]]))
    assert exact["window_positions"] == cf[terms[0]] + cf[terms[1]]
    levels = pos_ops.search_levels(int(cf[terms[0]]))
    assert exact["probe_elems"] == pos_ops.probe_elems(
        exact["anchor_slots"], 1, levels) \
        == exact["anchor_slots"] * (2 * (levels - 1) + 4)
    assert exact["probe_rows"] == exact["anchor_slots"] * 2 * (levels - 1)
    before = dict(C.PHRASE_STATS)
    sloppy = _phrase(client, arts, terms, slop=1)
    moved = {k: C.PHRASE_STATS[k] - before[k] for k in before}
    assert moved["anchor_positions"] == cf[terms[0]]
    assert sloppy["hits"]["total"]["value"] >= \
        ref.page(terms)["total"]


def test_the_planes_are_resident_and_leave_with_the_segment(deployments):
    client, arts, _stream, _ref = deployments(SEEDS[1])
    (seg,) = _segments(client)
    seg.device_arrays()
    planes = seg.device_positions("body")
    slots = planes["doc"].shape[0]
    assert slots >= len(arts["tok"]) and slots & (slots - 1) == 0
    assert planes["doc"].dtype == planes["pos"].dtype == np.int32
    pb = seg.postings["body"]
    n = len(pb.positions)
    assert np.array_equal(np.asarray(planes["pos"][:n]), pb.positions)
    assert np.array_equal(np.asarray(planes["doc"][:n]),
                          np.repeat(pb.doc_ids, np.diff(pb.pos_starts)))
    assert int(planes["doc"][n]) == 2**31 - 1 if slots > n else True

    def mine():
        return sum(a["bytes"] for a in LEDGER.top_tenants(10 ** 6)
                   if a["kind"] == "position_planes"
                   and a["label"] == f"segment-positions[{seg.name}]")
    # beside each plane its fence levels (every 128th slot, every
    # 16,384th ...: what a probe of the join's search reads a row of),
    # made with the planes, booked with them and dropped with them
    levels = pos_ops.search_levels(slots)
    assert levels >= 3 and set(planes) == set(pos_ops.plane_keys(levels))
    for plane in ("doc", "pos"):
        for k in range(1, levels):
            level = np.asarray(planes[pos_ops.plane_key(plane, k)])
            every = np.asarray(planes[plane])[::pos_ops.ROW ** k]
            assert len(level) % pos_ops.ROW == 0
            assert np.array_equal(level[: len(every)], every)
    want = sum(int(a.nbytes) for p in seg._device_positions[None].values()
               for a in p.values())
    held = mine()           # this segment's, and its namesakes' elsewhere
    assert held >= want > 2 * 4 * slots
    assert sum(int(a.nbytes) for a in planes.values()) \
        <= 2 * 4 * (slots + slots // 127 + 3 * pos_ops.ROW)
    seg.drop_device()
    assert mine() == held - want and seg._device_positions == {}
    # and they come back with the next request
    _phrase(client, arts, (int(arts["tok"][0]), int(arts["tok"][1])))
    assert mine() == held


def test_no_eviction_falls_between_promotion_and_the_read(deployments):
    """On a miss `device_positions` promotes and reads under the build
    lock: the pressure evictor, on another thread right after the
    promotion, is refused. Idle, it takes the planes with the arrays, and
    the next phrase's `prepare` promotes both again."""
    import threading
    client, arts, _stream, ref = deployments(SEEDS[1])
    (seg,) = _segments(client)
    seg.drop_device()
    promote, pressed = seg.device_arrays, []

    def promote_then_press(device=None):
        out = promote(device)
        t = threading.Thread(
            target=lambda: pressed.append(seg.evict_device()))
        t.start()
        t.join()
        return out
    seg.device_arrays = promote_then_press
    try:
        planes = seg.device_positions("body")
    finally:
        del seg.device_arrays
    assert pressed == [False]
    assert planes is seg._device_positions[None]["body"]
    assert seg.evict_device() is True
    assert seg._device_positions == {} and not seg._device_cache
    # (a phrase no other test asks: the request cache holds theirs)
    _held(client, arts, ref, (int(arts["tok"][10]), int(arts["tok"][11])))
    assert set(seg._device_positions) == set(seg._device_cache) == {None}


def test_a_replicas_phrase_reads_the_planes_of_its_own_device(deployments):
    """A searcher whose segments are hosted on a device of its own hands
    `prepare` that device: the planes come from that residency, and no
    second copy of the segment appears on the process default."""
    import jax
    from opensearch_tpu.search.executor import ShardSearcher, search_shards
    client, arts, _stream, ref = deployments(SEEDS[1])
    shard = client.node.indices[harness.INDEX].shards[0]
    (seg,) = shard.segments
    seg.drop_device()
    dev = jax.devices()[0]
    terms = (int(arts["tok"][0]), int(arts["tok"][1]))
    text = " ".join(arts["words"][t] for t in terms)
    resp = search_shards([ShardSearcher(shard, device=dev)],
                         {"query": {"match_phrase": {"body": text}}},
                         harness.INDEX)
    assert resp["hits"]["total"]["value"] == ref.page(terms)["total"]
    assert set(seg._device_cache) == set(seg._device_positions) == {dev}
    seg.drop_device()


def test_a_phrase_request_hands_the_launch_offsets_and_no_plane(deployments):
    client, arts, stream, _ref = deployments(SEEDS[1])
    for spec in stream.take(8):
        before = dict(C.EXECUTOR_STATS)
        harness.send(client, "search", [spec])
        launches = C.EXECUTOR_STATS["launches"] - before["launches"]
        handed = C.EXECUTOR_STATS["params_h2d_bytes"] \
            - before["params_h2d_bytes"]
        assert launches == 1 and 0 < handed <= H2D_LIMIT, (spec, handed)
    (seg,) = _segments(client)
    assert "_phrase_unions" not in seg.__dict__


def test_a_prefix_union_is_built_on_the_host_and_bounded(deployments):
    client, arts, _stream, ref = deployments(SEEDS[1])
    (seg,) = _segments(client)
    words, tok = arts["words"], arts["tok"]
    first, nxt = int(tok[0]), int(tok[1])
    before = C.PHRASE_STATS["host_pair_builds"]
    resp = client.search(harness.INDEX, {"query": {"match_phrase_prefix": {
        "body": f"{words[first]} {words[nxt][:2]}"}}})
    assert resp["hits"]["total"]["value"] >= ref.page([first, nxt])["total"] \
        > 0
    assert C.PHRASE_STATS["host_pair_builds"] == before + 1
    assert 1 <= len(seg._phrase_unions) <= C.UNION_PAIRS_MAX
    seg.drop_device()
    assert "_phrase_unions" not in seg.__dict__


# ---------------------------------------------------------------------
# the workload's other operations, once each
# ---------------------------------------------------------------------

def test_the_term_operation(deployments):
    client, arts, _stream, ref = deployments(SEEDS[0], 3)
    cf = articles.collection_frequency(arts)
    term = int(np.argsort(-cf)[60])
    resp = client.search(harness.INDEX, {"query": {"term": {
        "body": arts["words"][term]}}})
    got, want = reference.page_of(resp), reference.term_page(ref, term)
    c = reference.compare_page(got, dict(want, ids=want["ids"][:10]), 10,
                               RTOL)
    assert c["score_rel_err"] <= RTOL and not any(
        v for k, v in c.items() if k != "score_rel_err"), c


def test_the_default_operation(deployments):
    client, arts, _stream, _ref = deployments(SEEDS[0], 3)
    resp = client.search(harness.INDEX, {"query": {"match_all": {}}})
    assert resp["hits"]["total"] == {"value": int(arts["live"].sum()),
                                     "relation": "eq"}
    assert len(resp["hits"]["hits"]) == 10


def test_the_monthly_date_histogram(deployments):
    client, arts, _stream, _ref = deployments(SEEDS[0], 3)
    resp = client.search(harness.INDEX, {"size": 0, "aggs": {
        "articles_over_time": {"date_histogram": {
            "field": "timestamp", "calendar_interval": "month"}}}})
    got = {b["key"]: b["doc_count"]
           for b in resp["aggregations"]["articles_over_time"]["buckets"]}
    assert got == reference.monthly_counts(arts["ts_s"], arts["live"])


def test_a_scroll_of_three_pages(deployments):
    client, arts, _stream, _ref = deployments(SEEDS[0], 3)
    page = client.search(harness.INDEX, {"query": {"match_all": {}},
                                         "size": 50}, scroll="1m")
    seen = []
    for _ in range(3):
        assert len(page["hits"]["hits"]) == 50
        seen += [int(h["_id"]) for h in page["hits"]["hits"]]
        page = client.scroll(page["_scroll_id"], scroll="1m")
    assert len(set(seen)) == 150 and all(arts["live"][seen])
    client.clear_scroll(page["_scroll_id"])
