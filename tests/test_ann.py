"""Balanced-IVF ANN kNN (ops/ann.py + compiler "knn" probe path).

Reference analog: the k-NN plugin's ANN method param on knn_vector fields
(HNSW/faiss there; balanced IVF here — see ops/ann.py for why that is the
TPU-native layout). Invariant under test everywhere: nprobe == nlist
recovers the exact brute-force result bit-for-bit in rank order.
"""

import numpy as np
import pytest

from opensearch_tpu.ops.ann import build_ivf
from opensearch_tpu.rest.client import ApiError, RestClient

RNG = np.random.default_rng(7)
DIMS = 32
NDOCS = 400


def _clustered(n, d, ncenters=12, spread=0.4, rng=RNG):
    centers = rng.normal(size=(ncenters, d)).astype(np.float32) * 2.5
    v = centers[rng.integers(0, ncenters, n)] + \
        rng.normal(size=(n, d)).astype(np.float32) * spread
    return v.astype(np.float32)


class TestBuildIvf:
    def test_partition_is_exact(self):
        v = _clustered(500, 16)
        pres = np.ones(500, bool)
        pres[::13] = False
        ivf = build_ivf(v, pres, nlist=16)
        flat = ivf.lists.reshape(-1)
        flat = flat[flat >= 0]
        assert sorted(flat.tolist()) == np.nonzero(pres)[0].tolist()
        assert ivf.lists.shape == (ivf.nlist, ivf.cap)

    def test_empty_column(self):
        assert build_ivf(np.zeros((5, 8), np.float32),
                         np.zeros(5, bool)) is None

    def test_nlist_clamped_to_present(self):
        v = _clustered(10, 8)
        ivf = build_ivf(v, np.ones(10, bool), nlist=64)
        assert ivf.nlist <= 10


def _vector_segment(n, dims, absent_every=0, deleted=(), sim="dot_product"):
    """One planted segment holding one IVF-mapped vector column."""
    from opensearch_tpu.index.segment import Segment, VectorColumn
    # (a generator of its own: the cases below draw from the module's)
    vecs = _clustered(n, dims, rng=np.random.default_rng(n))
    pres = np.ones(n, bool)
    if absent_every:
        pres[::absent_every] = False
    col = VectorColumn("emb", vecs, pres, sim, method={"name": "ivf"})
    seg = Segment(name="v0", ndocs=n, postings={}, numeric_cols={},
                  keyword_cols={}, geo_cols={}, doc_lens={}, text_stats={},
                  ids=[str(i) for i in range(n)], sources=[None] * n,
                  vector_cols={"emb": col})
    for d in deleted:
        seg.delete_doc(d)
    return seg, vecs, pres


@pytest.mark.parametrize("n,absent_every,deleted", [
    (300, 0, ()), (300, 7, ()), (5000, 0, ()), (5000, 13, ()),
    (5000, 0, (3, 1700, 4999)), (20000, 0, ()), (20000, 11, ())])
class TestListOrderedResidency:
    """The device keeps a list's rows next to one another (`ann.list_rows`):
    the partition `IvfIndex.lists` describes, row for row, and a probe of
    every list through it is the exact scan."""

    def test_the_rows_in_list_order_are_the_partition(self, n, absent_every,
                                                      deleted):
        seg, _vecs, pres = _vector_segment(n, DIMS, absent_every, deleted)
        ivf = seg.vector_cols["emb"].ivf()
        vc = seg.device_arrays()["vector"]["emb"]
        assert "ivf_lists" not in vc
        mat, rows_l, ids = (np.asarray(vc[k])
                            for k in ("mat", "ivf_rows", "ivf_ids"))
        offset, fill = np.asarray(vc["ivf_offset"]), np.asarray(vc["ivf_fill"])
        assert (ids == ivf.order).all() and len(rows_l) == len(ids)
        assert (offset[: ivf.nlist] == ivf.offset).all()
        assert (fill[: ivf.nlist] == ivf.fill).all()
        assert not fill[ivf.nlist:].any()       # a padded centroid: no rows
        # every present row once (a deleted one too: `live` masks it)
        assert sorted(ids[ids >= 0].tolist()) == np.nonzero(pres)[0].tolist()
        assert (fill[: ivf.nlist] == (ivf.lists >= 0).sum(axis=1)).all()
        for li in range(ivf.nlist):
            o, f = offset[li], fill[li]
            assert (ids[o: o + f] == ivf.lists[li, :f]).all()
            assert (ivf.lists[li, f:] == -1).all()
            assert np.array_equal(rows_l[o: o + f], mat[ivf.lists[li, :f]])
        # no row where no id is; the tail a whole window of them
        assert not rows_l[ids < 0].any()
        assert (ids[-ivf.cap:] == -1).all()
        assert offset[ivf.nlist - 1] + ivf.cap <= len(ids)

    def test_probing_every_list_is_the_exact_scan(self, n, absent_every,
                                                  deleted):
        import jax
        from opensearch_tpu.search import compiler as C
        seg, vecs, _pres = _vector_segment(n, DIMS, absent_every, deleted)
        ivf = seg.vector_cols["emb"].ivf()
        arrays = seg.device_arrays()
        q = np.zeros(128, np.float32)
        q[:DIMS] = vecs[5] + 0.05
        params = {"q1_vec": q, "q1_qsq": np.float32(q @ q),
                  "q1_boost": np.float32(1.0)}

        def plane(probe):
            node = ("knn", 1, "emb", True, "dot_product", None, probe)
            sm = jax.jit(lambda a, p: C.emit(node, a, p))(arrays, params)
            return np.asarray(sm.scores), np.asarray(sm.count)
        (ann_s, ann_m), (exact_s, exact_m) = \
            plane((ivf.nlist, ivf.cap)), plane(None)
        assert (ann_m == exact_m).all()
        assert ann_m.sum() == seg.live_count - (~_pres & seg.live).sum()
        page = np.argsort(-exact_s, kind="stable")[:50]
        assert (np.argsort(-ann_s, kind="stable")[:50] == page).all()
        assert ann_s[page] == pytest.approx(exact_s[page], rel=1e-6)
        # a narrow probe reaches whole lists, and only present live rows
        part_s, part_m = plane((max(1, ivf.nlist // 8), ivf.cap))
        assert 0 < part_m.sum() < exact_m.sum()
        assert not (part_m > exact_m).any()
        assert part_s[part_m > 0] == pytest.approx(exact_s[part_m > 0],
                                                   rel=1e-6)


@pytest.fixture(scope="module", params=["cosine", "l2_norm", "dot_product"])
def ann_client(request):
    sim = request.param
    c = RestClient()
    c.indices.create("v", body={"mappings": {"properties": {
        "emb": {"type": "dense_vector", "dims": DIMS, "similarity": sim,
                "method": {"name": "ivf",
                           "parameters": {"nlist": 16, "nprobe": 4}}},
        "tag": {"type": "keyword"}}}})
    vecs = _clustered(NDOCS, DIMS)
    for i in range(NDOCS):
        c.index("v", {"emb": vecs[i].tolist(),
                      "tag": "even" if i % 2 == 0 else "odd"}, id=str(i))
    c.indices.refresh("v")
    return c, vecs, sim


class TestAnnSearch:
    def test_full_probe_equals_exact(self, ann_client):
        c, vecs, sim = ann_client
        q = vecs[3] + RNG.normal(size=DIMS).astype(np.float32) * 0.05
        body_ann = {"size": 10, "query": {"knn": {"emb": {
            "vector": q.tolist(), "k": 10,
            "method_parameters": {"nprobe": 16}}}}}
        body_exact = {"size": 10, "query": {"knn": {"emb": {
            "vector": q.tolist(), "k": 10, "exact": True}}}}
        ra = c.search("v", body_ann)
        re_ = c.search("v", body_exact)
        assert [h["_id"] for h in ra["hits"]["hits"]] == \
               [h["_id"] for h in re_["hits"]["hits"]]
        for ha, he in zip(ra["hits"]["hits"], re_["hits"]["hits"]):
            assert ha["_score"] == pytest.approx(he["_score"], rel=1e-5)

    def test_default_nprobe_recall(self, ann_client):
        c, vecs, sim = ann_client
        hits_at_10 = 0
        for qi in range(10):
            q = vecs[qi * 7] + RNG.normal(size=DIMS).astype(np.float32) * 0.05
            ra = c.search("v", {"size": 10, "query": {"knn": {"emb": {
                "vector": q.tolist(), "k": 10}}}})
            re_ = c.search("v", {"size": 10, "query": {"knn": {"emb": {
                "vector": q.tolist(), "k": 10, "exact": True}}}})
            exact_ids = {h["_id"] for h in re_["hits"]["hits"]}
            ann_ids = {h["_id"] for h in ra["hits"]["hits"]}
            hits_at_10 += len(exact_ids & ann_ids)
        assert hits_at_10 / 100 >= 0.8   # recall@10 over 10 queries

    def test_ann_with_filter(self, ann_client):
        c, vecs, sim = ann_client
        q = vecs[8]
        r = c.search("v", {"size": 5, "query": {"knn": {"emb": {
            "vector": q.tolist(), "k": 5,
            "filter": {"term": {"tag": "even"}}}}}})
        assert r["hits"]["hits"]
        assert all(int(h["_id"]) % 2 == 0 for h in r["hits"]["hits"])

    def test_top_level_knn_ann(self, ann_client):
        c, vecs, sim = ann_client
        q = vecs[11]
        r = c.search("v", {"size": 5, "knn": {
            "field": "emb", "query_vector": q.tolist(), "k": 5,
            "method_parameters": {"nprobe": 16}}})
        r2 = c.search("v", {"size": 5, "knn": {
            "field": "emb", "query_vector": q.tolist(), "k": 5,
            "exact": True}})
        assert [h["_id"] for h in r["hits"]["hits"]] == \
               [h["_id"] for h in r2["hits"]["hits"]]

    def test_self_query_finds_self(self, ann_client):
        c, vecs, sim = ann_client
        r = c.search("v", {"size": 1, "query": {"knn": {"emb": {
            "vector": vecs[42].tolist(), "k": 1}}}})
        if sim == "dot_product":
            # MIPS: the top hit may be a higher-norm vector, not the query
            # itself — just require agreement with the exact scan
            re_ = c.search("v", {"size": 1, "query": {"knn": {"emb": {
                "vector": vecs[42].tolist(), "k": 1, "exact": True}}}})
            assert (r["hits"]["hits"][0]["_id"]
                    == re_["hits"]["hits"][0]["_id"])
        else:
            assert r["hits"]["hits"][0]["_id"] == "42"


class TestPersistenceAndMerge:
    def test_method_survives_flush_reload(self, tmp_path):
        path = str(tmp_path / "data")
        c = RestClient(data_path=path)
        c.indices.create("pv", body={"mappings": {"properties": {
            "emb": {"type": "dense_vector", "dims": 8,
                    "method": {"name": "ivf", "parameters": {"nlist": 4}}}}}})
        vecs = _clustered(50, 8)
        for i in range(50):
            c.index("pv", {"emb": vecs[i].tolist()}, id=str(i))
        c.indices.refresh("pv")
        c.indices.flush("pv")
        c2 = RestClient(data_path=path)
        seg = c2.node.get_index("pv").shards[0].segments[0]
        assert seg.vector_cols["emb"].method["name"] == "ivf"
        r = c2.search("pv", {"size": 1, "query": {"knn": {"emb": {
            "vector": vecs[7].tolist(), "k": 1}}}})
        assert r["hits"]["hits"][0]["_id"] == "7"

    def test_method_survives_force_merge(self):
        c = RestClient()
        c.indices.create("mv", body={
            "settings": {"number_of_shards": 1},
            "mappings": {"properties": {
                "emb": {"type": "dense_vector", "dims": 8,
                        "method": {"name": "ivf",
                                   "parameters": {"nlist": 4}}}}}})
        vecs = _clustered(60, 8)
        for i in range(60):
            c.index("mv", {"emb": vecs[i].tolist()}, id=str(i))
            if i % 20 == 19:
                c.indices.refresh("mv")
        c.indices.refresh("mv")
        c.indices.forcemerge("mv")
        segs = c.node.get_index("mv").shards[0].segments
        assert len(segs) == 1
        assert segs[0].vector_cols["emb"].method["name"] == "ivf"
        r = c.search("mv", {"size": 1, "query": {"knn": {"emb": {
            "vector": vecs[33].tolist(), "k": 1}}}})
        assert r["hits"]["hits"][0]["_id"] == "33"


class TestMappingValidation:
    def test_unknown_method_rejected(self):
        c = RestClient()
        with pytest.raises((ApiError, ValueError)):
            c.indices.create("bad", body={"mappings": {"properties": {
                "emb": {"type": "dense_vector", "dims": 8,
                        "method": {"name": "hnsw"}}}}})
