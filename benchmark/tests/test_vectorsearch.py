"""The kind `vectorsearch` at a small size on the CPU: the generator's
determinism and shape, the planted segment against one the refresh path
built, the cell's four members through `run_cell`, the control (the
reference with bfloat16 products has to fail the rule, by `score_rtol`
alone), a broken timed path (`correct` false), the bytes function on two
hand-worked cases and the five metric readers on a recorded context."""

import copy
import os

import numpy as np
import pytest

import run
import vectorsearch_control as control
import vectorsearch_reference as reference
import vectorsearch_roofline as roofline
import vectorsearch_vectors as vectors

CELL = "cohere10m.search1.knn100"
DEVICE = {"platform": "cpu-rehearsal", "kind": "none", "count": 1}


def small(ndocs: int = 20_000, topics: int = 164) -> dict:
    loaded = copy.deepcopy(run.load_cell(CELL))
    loaded["config"]["ndocs"] = ndocs
    # a hundredth of the cell's rows holds a hundredth of its topics
    loaded["config"]["generator"]["topics"] = topics
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
    n = 2 * vectors.BLOCK + 100
    a, b = vectors.generate(n, 7, gen), vectors.generate(n, 7, gen)
    c = vectors.generate(n, 8, gen)
    assert np.array_equal(a["vectors"], b["vectors"])
    assert not np.array_equal(a["vectors"], c["vectors"])
    # rows do not depend on how many threads made them
    one, _t = vectors.draw(a["mixture"], n, 7, vectors.CORPUS_STREAM,
                           threads=1)
    assert np.array_equal(one, a["vectors"])
    v = a["vectors"]
    assert v.dtype == np.float32 and v.shape == (n, 768)
    norms = np.linalg.norm(v, axis=1)
    assert norms.max() / norms.min() > 1.5          # not unit length
    sizes = np.bincount(a["topic"], minlength=gen["topics"])
    assert sizes.max() > 5 * np.median(sizes) > 0          # uneven topics


def test_the_planted_segment_is_what_a_refresh_builds():
    """300 vectors indexed through the client and refreshed, against the
    same vectors planted: the column, array for array."""
    from opensearch_tpu.rest.client import RestClient
    config = dict(small()["config"], ndocs=300, dimension=16)
    config["generator"] = dict(config["generator"], dims=16, topics=8,
                               subjects=2, spread_rank=8)
    corpus = vectors.generate(300, 41, config["generator"])
    planted = vectors.plant_index(RestClient(), "bench", corpus, config)
    client = RestClient()
    client.indices.create("real", {"settings": config["index_settings"],
                                   "mappings": vectors.mapping(config)})
    for i in range(300):
        client.index("real", planted.sources[i], id=planted.ids[i])
    client.indices.refresh("real")
    (built,) = client.node.indices["real"].shards[0].segments
    assert set(built.vector_cols) == set(planted.vector_cols) \
        == {vectors.MAPPING_FIELD}
    a = built.vector_cols[vectors.MAPPING_FIELD]
    b = planted.vector_cols[vectors.MAPPING_FIELD]
    assert a.values.dtype == b.values.dtype == np.float32
    assert np.array_equal(a.values, b.values)
    assert np.array_equal(a.present, b.present)
    assert (a.similarity, a.method) == (b.similarity, b.method) \
        == ("dot_product", {"name": "ivf", "nlist": None, "nprobe": None})
    assert list(built.ids) == planted.ids[:300]
    assert built.postings == planted.postings == {}
    assert built.numeric_cols == planted.numeric_cols == {}


@pytest.mark.parametrize("corpus_seed", [20221201, 3000000021])
def test_the_bfloat16_reference_fails_the_rule(corpus_seed):
    loaded = small()
    config, g = loaded["config"], loaded["config"]["guarantees"]
    corpus = vectors.generate(20_000, corpus_seed, config["generator"])
    kind = run.load_kind("vectorsearch")
    specs = kind.stream({"corpus": corpus}, loaded["traffic"], 5).take(16)
    args = (config["space_type"], int(config["k"]), float(g["score_rtol"]),
            float(g["recall_at_k_floor"]))
    out = control.run(corpus["vectors"], specs, *args)["bfloat16_products"]
    assert out["correct"] is False
    # by its own limit, thirty times over, and by nothing else: its
    # pages are in order, of k distinct ids, and nearly the exact ones
    worst, limit = out["numbers"]["score_rel_err_max"]
    assert worst > 30 * limit
    assert all(out["numbers"][k] == [0, 0] for k in
               ("order_violations", "page_violations", "error_responses"))
    assert out["numbers"]["recall_at_k_mean"][0] > 0.9
    # and the exact reference holds its own pages
    exact = reference.Reference(corpus["vectors"], config["space_type"])
    scores = exact.scores(np.stack([s["vector"] for s in specs]))
    held = [(s, reference.as_response(exact.page(scores[i], 100)))
            for i, s in enumerate(specs)]
    own = reference.hold(held, exact, *args[1:])
    assert own["correct"] is True and own["compared"] == 16
    assert own["numbers"]["recall_at_k_mean"][0] == 1.0
    assert own["numbers"]["score_rel_err_max"][0] < 1e-7


def test_the_cell_holds_the_rule_at_20000_vectors(meter, tmp_path):
    # 20,000 rows are 141 lists of 213 slots, and a page of 100 is half a
    # list: with 164 topics the defaults' recall reads 0.91-0.96 by the
    # k-means' first centres (CPU counts, PR 36), around the floor the
    # chip's readings at 2,000,000 rows set (0.993-0.997); with 40 topics
    # of several lists each it reads 0.98
    result = run.run_cell(small(topics=40), 3000000011, 60, False, DEVICE,
                          meter, str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    n = result["compared"]
    assert n["score_rel_err_max"][0] < 1e-6
    assert n["recall_at_k_mean"][0] >= n["recall_at_k_mean"][1]
    assert all(n[k] == [0, 0] for k in ("order_violations",
                                        "page_violations",
                                        "error_responses"))
    assert {"qps", "p50_ms", "setup_s"} == set(result["metrics"])


def test_a_score_off_in_the_fourth_digit_is_not_correct(meter, tmp_path,
                                                        monkeypatch):
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.search

    def score_moved(self, *a, **kw):
        resp = real(self, *a, **kw)
        resp["hits"]["hits"][-1]["_score"] *= 1 - 1e-4
        return resp
    monkeypatch.setattr(RestClient, "search", score_moved)
    result = run.run_cell(small(), 8, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is False
    assert result["compared"]["score_rel_err_max"][0] > 5e-5
    assert result["compared"]["page_violations"] == [0, 0]


def test_a_dropped_hit_is_not_correct(meter, tmp_path, monkeypatch):
    from opensearch_tpu.rest.client import RestClient
    real = RestClient.search

    def first_hit_lost(self, *a, **kw):
        resp = real(self, *a, **kw)
        del resp["hits"]["hits"][0]
        return resp
    monkeypatch.setattr(RestClient, "search", first_hit_lost)
    result = run.run_cell(small(), 9, 60, False, DEVICE, meter,
                          str(tmp_path))
    assert result["correct"] is False
    assert result["compared"]["page_violations"][0] == 24
    assert result["compared"]["order_violations"] == [0, 0]


def test_a_page_out_of_order_is_not_correct():
    vecs = vectors.generate(2000, 3, dict(
        small()["config"]["generator"], topics=16))["vectors"]
    ref = reference.Reference(vecs)
    q = vecs[:1] * 0.9
    page = ref.page(ref.scores(q)[0], 100)
    page[3], page[4] = page[4], page[3]
    out = reference.hold([({"vector": q[0]}, reference.as_response(page))],
                         ref, 100, 1e-5, 0.9)
    assert out["correct"] is False
    assert out["numbers"]["order_violations"] == [1, 0]
    assert out["numbers"]["recall_at_k_mean"][0] == 1.0


def test_the_bytes_a_query_has_to_read():
    # the cell: centroids 1,414 x 768 x 4 B = 4,343,808; 176 lists of mean
    # fill 2,000,000 / 1,414 = 1,414.43 rows x 3,072 B = 764,741,160
    assert roofline.stage_bytes("ivf", 2_000_000, 768, 1414, 176) \
        == pytest.approx((1414 + 176 * 2_000_000 / 1414) * 3072)
    assert roofline.stage_bytes("ivf", 2_000_000, 768, 1414, 176) \
        == pytest.approx(769_084_968, rel=1e-6)
    # a toy: 4 lists of 25 rows of 8 floats, 2 probed: (4 + 2 * 25) * 32
    assert roofline.stage_bytes("ivf", 100, 8, 4, 2) == 1728
    # the exact scan reads every row once
    assert roofline.stage_bytes("flat", 2_000_000, 768) == 6_144_000_000
    with pytest.raises(ValueError):
        roofline.stage_bytes("hnsw", 1, 1)


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """A context as `run_cell` hands the readers, recorded by hand: 10
    traced queries of 12 ms in the `knn.*` scopes, a window of 100 IVF
    queries at nprobe 176 over 768 floats, and a build read-out of
    2,000,000 rows in 1,414 lists."""
    import span_reduce
    import xplane_scopes
    trace = tmp_path / "cell" / "trace" / "t.xplane.pb"
    os.makedirs(trace.parent)
    trace.write_bytes(b"")
    seconds = {"knn.centroids": 0.002, "knn.gather": 0.030,
               "knn.score": 0.028, "knn.scatter": 0.060, "knn.scan": 0.0}
    monkeypatch.setattr(span_reduce, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(xplane_scopes, "scope_seconds",
                        lambda path, scope: (seconds[scope], 0.5))
    monkeypatch.setattr(roofline, "build_readout", lambda: {
        "build_s": 41.5, "rows": 2_000_000, "spilled_rows": 400_000,
        "nlist": 1414, "cap": 2122})
    roofline._seconds_of.cache_clear()
    return {"trace": {"queries": 10, "requests": 10},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "window": {"queries": 100, "counters": {
                "knn.queries": 100, "knn.ann_queries": 100,
                "knn.exact_queries": 0, "knn.lists_probed": 17600,
                "knn.candidate_slots": 100 * 176 * 2122,
                "knn.query_vector_bytes": 100 * 3072}}}


def test_the_five_readers_on_a_recorded_context(recorded):
    read = run.read_layer_metric
    assert read("knn_stage_ms_per_query", recorded) == pytest.approx(12.0)
    assert read("knn_scatter_share", recorded) == pytest.approx(75.0)
    # 769.1 MB at 819 GB/s is 0.939 ms of the 12 a query took
    assert read("knn_stage_hbm_roofline_share", recorded) \
        == pytest.approx(100 * 769_084_968 / 819e9 / 0.012, rel=1e-6)
    assert read("knn_candidate_kslots_per_query", recorded) \
        == pytest.approx(373.472)
    assert read("ivf_build_s", recorded) == 41.5


def test_the_readers_read_nothing_from_a_program_without_their_source(
        recorded, monkeypatch):
    """The parent of the PR that added them: no `knn.*` counters, no
    scopes in the trace's ops, no `IVF_STATS`."""
    import xplane_scopes
    monkeypatch.setattr(xplane_scopes, "scope_seconds",
                        lambda path, scope: (0.0, 0.5))
    monkeypatch.setattr(roofline, "build_readout", lambda: None)
    roofline._seconds_of.cache_clear()
    recorded["window"]["counters"] = {}
    for name in ("knn_stage_ms_per_query", "knn_scatter_share",
                 "knn_stage_hbm_roofline_share",
                 "knn_candidate_kslots_per_query", "ivf_build_s"):
        assert run.read_layer_metric(name, recorded) is None
    # and the real read-out reads the program's own counter group
    monkeypatch.undo()
    from opensearch_tpu.ops import ann
    assert roofline.build_readout() == dict(ann.IVF_STATS.items())
