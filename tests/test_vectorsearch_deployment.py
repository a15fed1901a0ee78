"""The vector-search deployment (OpenSearch Benchmark `vectorsearch`,
benchmark kind `vectorsearch`) on the CPU at a small size: the program's
`knn` query against the kind's plain reference on the generator's own
vectors, for the three spaces by the k-NN plugin's names and both methods,
and the pieces of the program the deployment forced: `space_type` read
from `method`, the plugin's names, OSB's `hnsw` body refused by name, a
build that reads the resident matrix, `docvalue_fields: ["_id"]`, and the
counters and scopes the cell's metrics read."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.join(ROOT, "benchmark"), ROOT)
                if p not in sys.path]

import run as harness                          # noqa: E402
import vectorsearch_reference as reference     # noqa: E402
import vectorsearch_vectors as vectors         # noqa: E402

from opensearch_tpu.ops import ann                                 # noqa: E402
from opensearch_tpu.rest.client import ApiError, RestClient        # noqa: E402
from opensearch_tpu.search import compiler as C                    # noqa: E402

CELL = "cohere10m.search1.knn100"
NDOCS, DIMS, K = 4096, 64, 100
RTOL, FLOOR = 1e-5, 0.9
FIELD = vectors.MAPPING_FIELD
SPACES = ("innerproduct", "l2", "cosinesimil")


def small_config(**over) -> dict:
    loaded = harness.load_cell(CELL)
    config = dict(loaded["config"], ndocs=NDOCS, dimension=DIMS,
                  corpus_seed=5)
    # a corpus of 4,096 rows holds 16 topics, not 16,384: a list is 64
    # rows here and a page asks for 100, so a topic spans a few lists
    config["generator"] = dict(config["generator"], dims=DIMS, topics=16,
                               subjects=4, subject_share=0.3,
                               spread_rank=24)
    config.update(over)
    return config


@pytest.fixture(scope="module")
def deployments():
    """(space, method) -> (client, built, stream, config): 4,096 vectors of
    64 floats of the generator's own mixture, planted and promoted by the
    kind's `build`, on a plain one-chip node."""
    made = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        kind = harness.load_kind("vectorsearch")
        traffic = harness.load_cell(CELL)["traffic"]

        def get(space, method):
            if (space, method) not in made:
                config = small_config(space_type=space,
                                      method={"name": method})
                client = RestClient()
                built = kind.build(config, 5, client, harness.INDEX)
                made[space, method] = (client, built, kind.stream(
                    built, traffic, 5), config)
            return made[space, method]
        yield get


def _held(client, specs, **knn_extra):
    held = []
    for spec in specs:
        body = dict(spec["body"])
        if knn_extra:
            knn = dict(body["query"]["knn"][FIELD], **knn_extra)
            body["query"] = {"knn": {FIELD: knn}}
        held.append((spec, client.search(harness.INDEX, body)))
    return held


@pytest.mark.parametrize("method", ["flat", "ivf"])
@pytest.mark.parametrize("space", SPACES)
def test_the_program_answers_as_the_reference(deployments, space, method):
    """Page ids and scores by the kind's rule; `flat` finds every exact
    neighbour, `ivf` at its defaults at least the floor."""
    client, built, stream, config = deployments(space, method)
    ref = reference.Reference(built["corpus"]["vectors"], space)
    out = reference.hold(_held(client, stream.take(12)), ref, K, RTOL, FLOOR)
    n = out["numbers"]
    assert n["score_rel_err_max"][0] < 2e-6
    assert n["order_violations"] == n["page_violations"] \
        == n["error_responses"] == [0, 0]
    assert out["correct"] is True and out["compared"] == 12
    assert n["recall_at_k_mean"][0] >= (1.0 if method == "flat" else FLOOR)


@pytest.mark.parametrize("space", SPACES)
def test_ivf_probing_every_list_is_the_exact_scan(deployments, space):
    client, built, stream, config = deployments(space, "ivf")
    flat_client = deployments(space, "flat")[0]
    nlist = built["readout"]["ivf"]["nlist"]
    specs = stream.take(6)
    full = _held(client, specs, method_parameters={"nprobe": nlist})
    flat = _held(flat_client, specs)
    for (_s, a), (_s2, b) in zip(full, flat):
        rows_a = [reference.hit_row(h) for h in a["hits"]["hits"]]
        rows_b = [reference.hit_row(h) for h in b["hits"]["hits"]]
        assert rows_a == rows_b and len(rows_a) == K
        assert [h["_score"] for h in a["hits"]["hits"]] == pytest.approx(
            [h["_score"] for h in b["hits"]["hits"]], rel=1e-6)


def test_the_page_carries_ids_as_doc_values_and_no_source(deployments):
    client, _built, stream, _config = deployments("innerproduct", "ivf")
    (spec,) = stream.take(1)
    assert set(spec["body"]) == {"size", "query", "docvalue_fields",
                                 "stored_fields"}
    hits = client.search(harness.INDEX, spec["body"])["hits"]["hits"]
    assert len(hits) == K
    assert all(h["fields"] == {"_id": [h["_id"]]} and "_source" not in h
               for h in hits)


def test_a_stream_never_repeats_a_vector_twins_included(deployments):
    _client, built, _stream, _config = deployments("innerproduct", "ivf")
    kind = harness.load_kind("vectorsearch")
    stream = kind.stream(built, harness.load_cell(CELL)["traffic"], 11)
    specs = stream.take(40)
    stream.reseed(12)
    specs += stream.take(40)
    specs += [stream.twin(s) for s in specs]
    seen = {s["vector"].tobytes() for s in specs}
    assert len(seen) == len(specs) == 160
    corpus = {v.tobytes() for v in built["corpus"]["vectors"]}
    assert not seen & corpus                    # held out, never a row
    stream.reseed(11)                           # the same seed, the same
    assert np.array_equal(stream.take(1)[0]["vector"], specs[0]["vector"])


def test_the_planted_segment_is_what_a_refresh_builds():
    """300 vectors indexed through the client and refreshed, against the
    same vectors planted: the column array for array, and the same page."""
    config = small_config(ndocs=300, dimension=16)
    config["generator"] = dict(config["generator"], dims=16, topics=8,
                               subjects=2, spread_rank=8)
    corpus = vectors.generate(300, 41, config["generator"])
    planter, client = RestClient(), RestClient()
    planted = vectors.plant_index(planter, "v", corpus, config)
    client.indices.create("v", {"settings": config["index_settings"],
                                "mappings": vectors.mapping(config)})
    for i in range(300):
        client.index("v", planted.sources[i], id=planted.ids[i])
    client.indices.refresh("v")
    (built,) = client.node.indices["v"].shards[0].segments
    a, b = built.vector_cols[FIELD], planted.vector_cols[FIELD]
    assert a.values.dtype == b.values.dtype == np.float32
    assert np.array_equal(a.values, b.values)
    assert np.array_equal(a.present, b.present)
    assert (a.similarity, a.method) == (b.similarity, b.method) \
        == ("dot_product", {"name": "ivf", "nlist": None, "nprobe": None})
    assert list(built.ids) == planted.ids[:300]
    body = {"size": 10, "query": {"knn": {FIELD: {
        "vector": corpus["vectors"][7].tolist(), "k": 10}}}}
    pages = [[(h["_id"], h["_score"]) for h in
              c.search("v", body)["hits"]["hits"]] for c in (client, planter)]
    assert pages[0] == pages[1] and len(pages[0]) == 10


# ---- the mapping the deployment forced --------------------------------

def _mapping(**field):
    return {"mappings": {"properties": {FIELD: dict(
        {"type": "knn_vector", "dimension": 8}, **field)}}}


@pytest.mark.parametrize("given,known", [
    ("l2", "l2_norm"), ("innerproduct", "dot_product"),
    ("cosinesimil", "cosine"), ("l2_norm", "l2_norm"),
    ("dot_product", "dot_product"), ("cosine", "cosine")])
@pytest.mark.parametrize("where", ["field", "method"])
def test_space_type_is_read_from_the_field_and_from_method(given, known,
                                                           where):
    """OpenSearch 2.x puts `space_type` inside `method`; the plugin's names
    stand beside the repo's. `engine` and HNSW's parameters ride along."""
    client = RestClient()
    method = {"name": "ivf", "engine": "faiss",
              "parameters": {"m": 16, "ef_construction": 256}}
    if where == "method":
        client.indices.create("v", _mapping(method=dict(method,
                                                        space_type=given)))
    else:
        client.indices.create("v", _mapping(space_type=given, method=method))
    ft = client.node.indices["v"].mappings.resolve_field(FIELD)
    assert ft.vector_similarity == known
    assert ft.vector_method == {"name": "ivf", "nlist": None, "nprobe": None}


def test_osbs_hnsw_body_is_a_400_that_names_ivf_and_flat():
    body = _mapping(method={"name": "hnsw", "space_type": "innerproduct",
                            "engine": "faiss",
                            "parameters": {"ef_construction": 256, "m": 16}})
    with pytest.raises(ApiError) as e:
        RestClient().indices.create("v", body)
    assert e.value.status == 400
    assert "hnsw" in str(e.value) and "ivf" in str(e.value) \
        and "flat" in str(e.value)


def test_an_unknown_space_is_a_400_that_names_the_known_ones():
    """A misspelt space used to be scored as l2 without a word."""
    with pytest.raises(ApiError) as e:
        RestClient().indices.create("v", _mapping(space_type="cosinesim"))
    assert e.value.status == 400
    for name in ("l2", "innerproduct", "cosinesimil", "cosine"):
        assert name in str(e.value)


# ---- residency, counters, scopes --------------------------------------

def test_the_ivf_build_reads_the_resident_matrix_and_counts(deployments):
    """`device_arrays` hands `build_ivf` the matrix it has just put on the
    device: the same index as a build from the host rows, no second copy
    of the vectors, and the `ivf.*` counters say what it made."""
    client, built, _stream, _config = deployments("innerproduct", "ivf")
    vecs = built["corpus"]["vectors"]
    before = dict(ann.IVF_STATS.items())
    host = ann.build_ivf(vecs, np.ones(len(vecs), bool))
    import jax.numpy as jnp
    dev = ann.build_ivf(jnp.asarray(vecs), np.ones(len(vecs), bool))
    def list_of(ivf):
        out = np.empty(NDOCS, np.int64)
        rows = ivf.lists >= 0
        out[ivf.lists[rows]] = np.nonzero(rows)[0]
        return out
    # the same index, but for a row whose two nearest centroids tie in
    # the last float32 digit (a threaded product's sum has no fixed order)
    assert (list_of(host) == list_of(dev)).mean() > 0.99
    assert np.allclose(host.centroids, dev.centroids, rtol=1e-3, atol=1e-4)
    after = dict(ann.IVF_STATS.items())
    assert after["rows"] - before["rows"] == 2 * NDOCS
    assert after["build_s"] > before["build_s"]
    assert (after["nlist"], after["cap"]) == (host.nlist, host.cap) \
        == (64, 96)
    filed = host.lists[host.lists >= 0]
    assert sorted(filed.tolist()) == list(range(NDOCS))
    spilled = (after["spilled_rows"] - before["spilled_rows"]) // 2
    assert 0 < spilled < NDOCS // 2             # uneven topics do spill
    ro = built["readout"]["ivf"]
    # (`rows` counts every build of the process, another file's too)
    assert (ro["nlist"], ro["cap"]) == (64, 96) and ro["rows"] >= NDOCS
    assert set(ann.IVF_STATS) == {"build_s", "rows", "spilled_rows",
                                  "nlist", "cap", "list_rows_bytes"}
    # the rows once more in list order, charged where the centroids are
    vc = client.node.indices[harness.INDEX].shards[0].segments[0] \
        .device_arrays()["vector"][FIELD]
    assert "ivf_lists" not in vc
    assert vc["ivf_rows"].shape == (len(host.order), 128)
    assert ro["list_rows_bytes"] == vc["ivf_rows"].nbytes \
        == len(host.order) * 128 * 4
    from opensearch_tpu.obs.hbm_ledger import LEDGER
    assert LEDGER.snapshot()["tenants"]["ann_ivf"]["bytes"] \
        >= ro["list_rows_bytes"]


def test_knn_stats_count_a_launch_by_its_route(deployments):
    assert set(C.KNN_STATS) == {"queries", "ann_queries", "exact_queries",
                                "lists_probed", "candidate_slots",
                                "rows_by_id", "query_vector_bytes"}
    client, built, _stream, _config = deployments("innerproduct", "ivf")
    flat_client = deployments("innerproduct", "flat")[0]
    # vectors no other test has sent: the request cache answers a body it
    # has seen, and a cached answer launches (and counts) nothing
    stream = harness.load_kind("vectorsearch").stream(
        built, harness.load_cell(CELL)["traffic"], 99)
    nlist, cap = (built["readout"]["ivf"][k] for k in ("nlist", "cap"))
    c0 = dict(C.KNN_STATS.items())
    _held(client, stream.take(3))
    c1 = dict(C.KNN_STATS.items())
    assert {k: c1[k] - c0[k] for k in c1} == {
        "queries": 3, "ann_queries": 3, "exact_queries": 0,
        "lists_probed": 3 * (nlist // 8),
        "candidate_slots": 3 * (nlist // 8) * cap, "rows_by_id": 0,
        "query_vector_bytes": 3 * 128 * 4}      # 64 floats pad to 128
    _held(flat_client, stream.take(2))
    _held(client, stream.take(1), exact=True)
    c2 = dict(C.KNN_STATS.items())
    assert c2["exact_queries"] - c1["exact_queries"] == 3
    assert c2["ann_queries"] == c1["ann_queries"]
    assert c2["candidate_slots"] == c1["candidate_slots"]


@pytest.mark.parametrize("method,want", [
    ("ivf", {"knn.centroids", "knn.gather", "knn.score", "knn.scatter"}),
    ("flat", {"knn.scan"})])
def test_the_program_names_its_knn_scopes(deployments, method, want):
    """The scopes the `knn_*` trace metrics sum, in the lowered program's
    op names; the scoring product names its precision."""
    import jax
    client, built, stream, _config = deployments("innerproduct", method)
    seg = client.node.indices[harness.INDEX].shards[0].segments[0]
    (spec,) = stream.take(1)
    qvec = np.zeros(128, np.float32)
    qvec[:DIMS] = spec["vector"]
    params = {"q1_vec": qvec, "q1_qsq": np.float32(1.0),
              "q1_boost": np.float32(1.0)}
    node = ("knn", 1, FIELD, True, "dot_product", None,
            (4, built["readout"]["ivf"]["cap"]) if method == "ivf" else None)
    text = jax.jit(lambda a, p: C.emit(node, a, p).scores).lower(
        seg.device_arrays(), params).as_text(debug_info=True)
    scopes = {s for s in ("knn.centroids", "knn.gather", "knn.score",
                          "knn.scatter", "knn.scan") if s in text}
    assert scopes == want
    assert "HIGHEST" in text


def test_a_span_covers_the_knn_prepare(deployments):
    client, _built, stream, _config = deployments("innerproduct", "ivf")
    _held(client, stream.take(1))

    def names(node):
        yield node["name"], node
        for ch in node.get("children", []):
            yield from names(ch)
    spans = dict(names(client.get_traces()["traces"][-1]))
    assert "knn.prepare" in spans
    assert any(ch["name"] == "knn.prepare"
               for ch in spans["search.prepare"]["children"])
