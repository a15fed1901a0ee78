"""Segment codec v2 (impact-quantized eager postings) — format,
compat, and oracle-exactness.

Covers the ISSUE 8 compat contract: v1 segments built by the old path
load, serve, and merge with v2 segments into a v2 result with
byte-identical hits vs the host oracle; plus the quantization-error
bound property — on random corpora, served pages never differ from
exact f32 BM25 at k=10, whatever the impact path prunes.
"""

import json
import os

import numpy as np
import pytest

from opensearch_tpu.cluster.node import Node
from opensearch_tpu.index.mappings import Mappings
from opensearch_tpu.index.merge import merge_segments
from opensearch_tpu.index.segment import (CODEC_V1, CODEC_V2, IMPACT_BLOCK,
                                          ImpactPlane, Segment,
                                          build_impact_plane, build_segment)
from opensearch_tpu.ops.device_merge import quantize_impacts
from opensearch_tpu.ops.scoring import dequant_impact_np
from opensearch_tpu.rest.client import RestClient
from opensearch_tpu.search import impactpath


def _mk_docs(m, rng, n, vocab=50, lo=3, hi=40, prefix=""):
    docs = []
    for i in range(n):
        toks = rng.choice([f"w{j}" for j in range(vocab)],
                          size=int(rng.integers(lo, hi)))
        docs.append(m.parse(f"{prefix}{i}", {"body": " ".join(toks)}))
    return docs


def _mappings():
    return Mappings({"properties": {"body": {"type": "text"}}})


def _v1_segment(name, docs, m):
    """What a commit from before the format rev loads as: nothing builds
    codec v1 any more, so a built segment is demoted."""
    seg = build_segment(name, docs, m)
    seg.drop_impacts()
    return seg


def _client(nshards=1):
    c = RestClient(node=Node(mesh_service=False))
    c.indices.create("ct", {
        "settings": {"number_of_shards": nshards, "number_of_replicas": 0},
        "mappings": {"properties": {"body": {"type": "text"},
                                    "status": {"type": "keyword"}}}})
    return c


class TestPlaneBuild:
    def test_quantization_error_within_bound(self):
        m = _mappings()
        rng = np.random.default_rng(0)
        seg = build_segment("_0", _mk_docs(m, rng, 300), m)
        assert seg.codec_version == CODEC_V2
        pb = seg.postings["body"]
        ip = pb.impact
        dl = seg.doc_lens["body"]
        st = seg.text_stats["body"]
        avg = st.sum_dl / st.doc_count
        dlof = dl[pb.doc_ids].astype(np.float32)
        kfac = ip.k1 * (1.0 - ip.b + ip.b * dlof / avg)
        exact = pb.tfs / (pb.tfs + kfac)
        err = np.abs(exact - dequant_impact_np(ip.q, ip.scale))
        assert float(err.max()) <= ip.quant_err()

    def test_block_max_sidecar_is_exact_quantized_upper_bound(self):
        m = _mappings()
        rng = np.random.default_rng(1)
        seg = build_segment("_0", _mk_docs(m, rng, 400), m)
        ip = seg.postings["body"].impact
        pb = seg.postings["body"]
        for r in range(pb.nterms):
            a, b = ip.row_block_range(r)
            s, e = pb.row_slice(r)
            # blocks tile the row
            assert b - a == -(-(e - s) // IMPACT_BLOCK)
            for bi in range(a, b):
                off = int(ip.block_off[bi])
                ln = min(IMPACT_BLOCK, e - off)
                assert int(ip.block_max[bi]) == int(ip.q[off:off + ln].max())

    def test_u8_bits_env(self, monkeypatch):
        monkeypatch.setenv("OPENSEARCH_TPU_IMPACT_BITS", "8")
        m = _mappings()
        rng = np.random.default_rng(2)
        seg = build_segment("_0", _mk_docs(m, rng, 100), m)
        ip = seg.postings["body"].impact
        assert ip.bits == 8 and ip.q.dtype == np.uint8
        assert ip.block_max.dtype == np.uint8

    def test_device_quantize_matches_numpy(self):
        rng = np.random.default_rng(3)
        tfs = rng.integers(1, 30, 5000).astype(np.float32)
        dlof = rng.integers(5, 200, 5000).astype(np.float32)
        q_dev, scale_dev = quantize_impacts(tfs, dlof, 1.2, 0.75, 50.0,
                                            65535)
        kfac = 1.2 * (1.0 - 0.75 + 0.75 * dlof / 50.0)
        imp = tfs / (tfs + kfac)
        m = float(imp.max())
        scale = m / 65535
        q_np = np.minimum(np.round(imp / np.float32(scale)), 65535)
        assert scale_dev == pytest.approx(scale, rel=1e-6)
        # the plane only steers candidates/bounds (served pages are
        # certified against the exact oracle regardless), so device/host
        # build parity is a quality property: within one quantization
        # step everywhere (XLA f32 division rounds a few ULP apart)
        diff = np.abs(np.asarray(q_dev).astype(np.int64)
                      - q_np.astype(np.int64))
        assert int(diff.max()) <= 1
        assert float((diff > 0).mean()) < 0.01

    def test_drift_bound_zero_at_build_params_and_sound_off_them(self):
        ip = ImpactPlane(q=np.zeros(1, np.uint16), scale=1e-5, bits=16,
                         k1=1.2, b=0.75, avgdl=50.0, dl_max=200,
                         block_starts=np.zeros(2, np.int64),
                         block_off=np.zeros(1, np.int64),
                         block_max=np.zeros(1, np.uint16))
        assert ip.drift_bound(1.2, 0.75, 50.0) == 0.0
        d = ip.drift_bound(1.2, 0.75, 80.0)
        assert d > 0.0
        # brute-force the true max |f_q - f_b| over the (tf, dl) grid
        tf = np.arange(1, 50, dtype=np.float64)[:, None]
        dl = np.arange(0, 201, dtype=np.float64)[None, :]
        f_b = tf / (tf + 1.2 * (0.25 + 0.75 * dl / 50.0))
        f_q = tf / (tf + 1.2 * (0.25 + 0.75 * dl / 80.0))
        assert d >= float(np.abs(f_q - f_b).max())


class TestPersistenceAndCompat:
    def test_v2_save_load_roundtrip(self, tmp_path):
        m = _mappings()
        rng = np.random.default_rng(4)
        seg = build_segment("_0", _mk_docs(m, rng, 120), m)
        seg.save(str(tmp_path / "s"))
        seg2 = Segment.load(str(tmp_path / "s"))
        assert seg2.codec_version == CODEC_V2
        ip, ip2 = seg.postings["body"].impact, seg2.postings["body"].impact
        assert np.array_equal(ip.q, ip2.q)
        assert np.array_equal(ip2.block_max, ip.block_max)
        assert np.array_equal(ip2.block_off, ip.block_off)
        assert (ip2.scale, ip2.bits, ip2.avgdl) == (ip.scale, ip.bits,
                                                    ip.avgdl)

    def test_v1_segment_loads_and_has_no_plane(self, tmp_path):
        m = _mappings()
        rng = np.random.default_rng(5)
        seg = _v1_segment("_0", _mk_docs(m, rng, 80), m)
        assert seg.codec_version == CODEC_V1
        seg.save(str(tmp_path / "s"))
        seg2 = Segment.load(str(tmp_path / "s"))
        assert seg2.codec_version == CODEC_V1
        assert seg2.postings["body"].impact is None
        # v1 device layout keeps the tf plane
        arrs = seg2.device_arrays()
        assert "tfs" in arrs["postings"]["body"]
        assert "impacts" not in arrs["postings"]["body"]
        seg2.drop_device()

    def test_pre_rev_meta_without_codec_key_loads_as_v1(self, tmp_path):
        m = _mappings()
        seg = _v1_segment("_0", _mk_docs(m, np.random.default_rng(6), 20), m)
        seg.save(str(tmp_path / "s"))
        meta_path = tmp_path / "s" / "meta.json"
        meta = json.loads(meta_path.read_text())
        meta.pop("codec")
        meta.pop("impacts", None)
        meta_path.write_text(json.dumps(meta))
        seg2 = Segment.load(str(tmp_path / "s"))
        assert seg2.codec_version == CODEC_V1

    def test_v1_plus_v2_merge_yields_v2(self):
        m = _mappings()
        rng = np.random.default_rng(7)
        v1 = _v1_segment("_0", _mk_docs(m, rng, 60, prefix="a"), m)
        v2 = build_segment("_1", _mk_docs(m, rng, 60, prefix="b"), m)
        assert (v1.codec_version, v2.codec_version) == (CODEC_V1, CODEC_V2)
        merged = merge_segments("_m0", [v1, v2])
        assert merged.codec_version == CODEC_V2
        ip = merged.postings["body"].impact
        assert ip is not None and len(ip.q) == merged.postings["body"].size
        # merged plane is consistent with the merged tf/dl at the merged
        # avgdl (rebuilt, not carried)
        st = merged.text_stats["body"]
        assert ip.avgdl == pytest.approx(st.sum_dl / st.doc_count)

    def test_all_v1_merge_yields_v2(self):
        """A merge is where an index written before the format rev is
        upgraded: no input has a plane, the output has one."""
        m = _mappings()
        rng = np.random.default_rng(8)
        a = _v1_segment("_0", _mk_docs(m, rng, 30, prefix="a"), m)
        b = _v1_segment("_1", _mk_docs(m, rng, 30, prefix="b"), m)
        merged = merge_segments("_m0", [a, b])
        assert merged.codec_version == CODEC_V2
        assert merged.postings["body"].impact is not None


def _hits(resp):
    return [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]


def _assert_pages_equal(got, want):
    """Page parity vs the exact XLA path: identical ids in identical
    order; scores agree to within a few f32 ULP. (The impact ladder
    serves the HOST-ORACLE f32 domain — term-ordered numpy accumulation,
    the same domain fastpath's rescued pages serve — while the XLA dense
    program may contract mul+add chains into FMA, a ≤1-ULP-per-posting
    delta. See `test_served_scores_bit_exact_vs_f32_host_oracle` for the
    strict-domain check.)"""
    hg, hw = got["hits"]["hits"], want["hits"]["hits"]
    assert [h["_id"] for h in hg] == [h["_id"] for h in hw]
    sg = np.asarray([h["_score"] for h in hg], np.float32)
    sw = np.asarray([h["_score"] for h in hw], np.float32)
    assert np.allclose(sg, sw, rtol=3e-6, atol=0.0)


def _index_random(c, rng, n, vocab=80, lo=3, hi=50, index="ct"):
    bulk = []
    for i in range(n):
        toks = np.minimum(rng.zipf(1.3, int(rng.integers(lo, hi))), vocab)
        bulk.append({"index": {"_index": index, "_id": str(i)}})
        bulk.append({"body": " ".join(f"w{t}" for t in toks)})
    c.bulk(bulk)
    c.indices.refresh(index)


class TestServingParity:
    """Served pages over codec v2 must be byte-identical to the exact
    host oracle (the v1 XLA path with the impact ladder disabled)."""

    def _oracle(self, c, bodies):
        os.environ["OPENSEARCH_TPU_NO_IMPACT"] = "1"
        try:
            return [c.search("ct", b) for b in bodies]
        finally:
            del os.environ["OPENSEARCH_TPU_NO_IMPACT"]

    def test_pages_byte_identical_random_corpora(self):
        """The quantization-error-bound property test: random corpora,
        random queries, k=10 — the served page (ids AND f32 scores) never
        differs from exact f32 BM25, whatever the block-max prune and
        quantized first pass did."""
        for seed in range(4):
            rng = np.random.default_rng(seed)
            c = _client()
            _index_random(c, rng, 3000)
            bodies = []
            for _ in range(25):
                ts = rng.integers(1, 40, int(rng.integers(1, 4)))
                bodies.append({"query": {"match": {
                    "body": " ".join(f"w{t}" for t in ts)}}})
            bodies.append({"query": {"match": {"body": {
                "query": "w1 w2 w3", "minimum_should_match": 2}}}})
            bodies.append({"query": {"term": {"body": "w1"}}})
            got = [c.search("ct", b) for b in bodies]
            want = self._oracle(c, bodies)
            for g, w in zip(got, want):
                _assert_pages_equal(g, w)

    def test_served_pages_match_naive_python_bm25(self):
        """Independent oracle: scores recomputed from scratch in python
        (not through any engine path) agree with the served page at
        k=10 within f32 tolerance and EXACT rank order."""
        rng = np.random.default_rng(42)
        c = _client()
        docs = {}
        for i in range(1500):
            toks = [f"w{t}" for t in
                    np.minimum(rng.zipf(1.3, int(rng.integers(3, 40))), 60)]
            docs[str(i)] = toks
        bulk = []
        for did, toks in docs.items():
            bulk.append({"index": {"_index": "ct", "_id": did}})
            bulk.append({"body": " ".join(toks)})
        c.bulk(bulk)
        c.indices.refresh("ct")
        N = len(docs)
        avgdl = sum(len(t) for t in docs.values()) / N
        import math
        for qterms in (["w1", "w2"], ["w5"], ["w2", "w9", "w17"]):
            exp = {}
            df = {t: sum(1 for toks in docs.values() if t in toks)
                  for t in qterms}
            for did, toks in docs.items():
                s, matched = 0.0, False
                for t in qterms:
                    tf = toks.count(t)
                    if tf:
                        matched = True
                        idf = math.log(1 + (N - df[t] + 0.5) / (df[t] + 0.5))
                        s += idf * tf / (tf + 1.2 * (0.25 + 0.75
                                                     * len(toks) / avgdl))
                if matched:
                    exp[did] = s
            expected = sorted(exp.items(),
                              key=lambda kv: (-kv[1], int(kv[0])))
            got = _hits(c.search("ct", {"query": {"match": {
                "body": " ".join(qterms)}}}))
            assert len(got) == min(10, len(expected))
            for (gid, gscore), (eid, escore) in zip(got, expected):
                assert abs(gscore - escore) < 5e-3, qterms

    def test_served_scores_bit_exact_vs_f32_host_oracle(self):
        """Strict-domain check: the served scores ARE the host oracle's
        term-ordered f32 accumulation, bit for bit, independent of what
        the quantized pass and the block prune selected."""
        rng = np.random.default_rng(33)
        c = _client()
        _index_random(c, rng, 2000)
        shard = c.node.indices["ct"].shards[0]
        seg = shard.segments[0]
        pb = seg.postings["body"]
        dl = seg.doc_lens["body"]
        st = seg.text_stats["body"]
        avgdl = st.sum_dl / st.doc_count
        N = seg.ndocs
        import math
        for qterms in (["w1", "w2"], ["w3"], ["w4", "w7", "w15"]):
            before = impactpath.stats()["served"]
            r = c.search("ct", {"query": {"match": {
                "body": " ".join(qterms)}}})
            assert impactpath.stats()["served"] == before + 1
            # f32 host-oracle mirror over every doc, term-ordered
            scores = np.zeros(N, np.float32)
            matched = np.zeros(N, bool)
            dl_f = dl.astype(np.float32)
            kfac = 1.2 * (1.0 - 0.75 + 0.75 * dl_f
                          / max(float(avgdl), 1e-9))
            for t in qterms:
                row = pb.row(t)
                if row < 0:
                    continue
                df = pb.doc_freq(t)
                w = np.float32(math.log(1.0 + (N - df + 0.5) / (df + 0.5)))
                a, b = pb.row_slice(row)
                ids = pb.doc_ids[a:b]
                tf = pb.tfs[a:b]
                scores[ids] += (w * tf / (tf + kfac[ids])).astype(
                    np.float32)
                matched[ids] = True
            order = np.lexsort((np.arange(N), -np.where(matched, scores,
                                                        -np.inf)))
            exp = [(str(d), float(scores[d])) for d in order[:10]
                   if matched[d]]
            assert _hits(r) == exp

    def test_multi_segment_avgdl_drift_stays_exact(self):
        """Query-time avgdl aggregates across segments and differs from
        every plane's build-time avgdl — the drift bound must keep served
        pages oracle-exact."""
        rng = np.random.default_rng(11)
        c = _client()
        # two refreshes with very different doc lengths -> avgdl drift
        bulk = []
        for i in range(800):
            toks = np.minimum(rng.zipf(1.3, int(rng.integers(3, 10))), 40)
            bulk.append({"index": {"_index": "ct", "_id": f"a{i}"}})
            bulk.append({"body": " ".join(f"w{t}" for t in toks)})
        c.bulk(bulk)
        c.indices.refresh("ct")
        bulk = []
        for i in range(800):
            toks = np.minimum(rng.zipf(1.3, int(rng.integers(40, 80))), 40)
            bulk.append({"index": {"_index": "ct", "_id": f"b{i}"}})
            bulk.append({"body": " ".join(f"w{t}" for t in toks)})
        c.bulk(bulk)
        c.indices.refresh("ct")
        shard = c.node.indices["ct"].shards[0]
        assert len(shard.segments) >= 2
        planes = [s.postings["body"].impact for s in shard.segments]
        assert all(p is not None for p in planes)
        bodies = [{"query": {"match": {"body": f"w{t} w{t2}"}}}
                  for t, t2 in rng.integers(1, 30, (15, 2))]
        got = [c.search("ct", b) for b in bodies]
        want = self._oracle(c, bodies)
        for g, w in zip(got, want):
            _assert_pages_equal(g, w)

    def test_track_total_hits_disables_pruning_totals_exact(self):
        rng = np.random.default_rng(12)
        c = _client()
        _index_random(c, rng, 4000)
        body = {"query": {"match": {"body": "w1 w2"}},
                "track_total_hits": True}
        got = c.search("ct", body)
        want = self._oracle(c, [body])[0]
        assert got["hits"]["total"] == want["hits"]["total"]
        _assert_pages_equal(got, want)

    def test_pruned_totals_are_gte_lower_bounds(self):
        rng = np.random.default_rng(13)
        c = _client()
        _index_random(c, rng, 20000, vocab=200, lo=4, hi=60)
        before = impactpath.stats()["pruned_served"]
        body = {"query": {"match": {"body": "w1 w2"}}}
        got = c.search("ct", body)
        want = self._oracle(c, [body])[0]
        _assert_pages_equal(got, want)
        tot = got["hits"]["total"]
        exact_tot = want["hits"]["total"]["value"]
        if impactpath.stats()["pruned_served"] > before:
            assert tot["relation"] == "gte"
            assert tot["value"] <= exact_tot
        else:
            assert tot["value"] == exact_tot

    def test_u8_serving_stays_exact(self, monkeypatch):
        monkeypatch.setenv("OPENSEARCH_TPU_IMPACT_BITS", "8")
        rng = np.random.default_rng(14)
        c = _client()
        _index_random(c, rng, 2500)
        assert c.node.indices["ct"].shards[0].segments[0] \
                .postings["body"].impact.bits == 8
        bodies = [{"query": {"match": {"body": f"w{t} w{t2}"}}}
                  for t, t2 in rng.integers(1, 40, (12, 2))]
        got = [c.search("ct", b) for b in bodies]
        want = self._oracle(c, bodies)
        for g, w in zip(got, want):
            _assert_pages_equal(g, w)

    def test_escalation_is_safe_under_hostile_margin(self, monkeypatch):
        """Force the planner to prune far past what it can certify: every
        query must escalate through the ladder and still serve the exact
        page (the certificate, not the heuristic, carries correctness)."""
        monkeypatch.setattr(impactpath, "PRUNE_MARGIN", 1e9)
        monkeypatch.setattr(impactpath, "KEEP_MIN", 64)
        monkeypatch.setattr(impactpath, "KEEP_FACTOR", 1)
        rng = np.random.default_rng(15)
        c = _client()
        _index_random(c, rng, 8000, vocab=100)
        bodies = [{"query": {"match": {"body": f"w{t} w{t2}"}}}
                  for t, t2 in rng.integers(1, 30, (10, 2))]
        got = [c.search("ct", b) for b in bodies]
        want = self._oracle(c, bodies)
        for g, w in zip(got, want):
            _assert_pages_equal(g, w)

    def test_deleted_docs_respected(self):
        rng = np.random.default_rng(16)
        c = _client()
        _index_random(c, rng, 1000)
        for i in range(0, 1000, 3):
            c.delete("ct", str(i))
        body = {"query": {"match": {"body": "w1 w2"}}}
        got = c.search("ct", body)
        want = self._oracle(c, [body])[0]
        _assert_pages_equal(got, want)
        assert all(int(h[0]) % 3 != 0 for h in _hits(got))


class TestLazyTfPlane:
    def test_hot_path_never_ships_tfs(self):
        rng = np.random.default_rng(20)
        c = _client()
        _index_random(c, rng, 500)
        c.search("ct", {"query": {"match": {"body": "w1 w2"}}})
        seg = c.node.indices["ct"].shards[0].segments[0]
        post = seg.device_arrays()["postings"]["body"]
        assert "impacts" in post and "tfs" not in post

    def test_exact_program_promotes_tfs(self):
        rng = np.random.default_rng(21)
        c = _client()
        _index_random(c, rng, 500)
        # a bool tree with a scoring term group declines the pure impact
        # path and runs the exact program -> tf plane promoted
        r = c.search("ct", {"query": {"bool": {
            "must": [{"match": {"body": "w1"}}],
            "filter": [{"term": {"body": "w2"}}]}}})
        assert "hits" in r
        seg = c.node.indices["ct"].shards[0].segments[0]
        post = seg.device_arrays()["postings"]["body"]
        assert "tfs" in post and "impacts" in post

    def test_ledger_tenants_present(self):
        from opensearch_tpu.obs.hbm_ledger import LEDGER
        rng = np.random.default_rng(22)
        c = _client()
        _index_random(c, rng, 400)
        c.search("ct", {"query": {"match": {"body": "w1"}}})
        snap = LEDGER.snapshot()
        kinds = snap["tenants"]
        assert kinds.get("impact_postings", {}).get("bytes", 0) > 0
        assert kinds.get("block_max", {}).get("bytes", 0) > 0
        stats = c.nodes_stats()
        node = next(iter(stats["nodes"].values()))
        assert "impactpath" in node
        assert node["impactpath"]["blocks_total"] >= 0

    def test_drop_impacts_demotes_to_v1(self):
        rng = np.random.default_rng(23)
        c = _client()
        _index_random(c, rng, 300)
        seg = c.node.indices["ct"].shards[0].segments[0]
        body = {"query": {"match": {"body": "w1 w2"}}}
        want = c.search("ct", body)
        seg.drop_impacts()
        assert seg.codec_version == CODEC_V1
        got = c.search("ct", body)
        _assert_pages_equal(got, want)
        assert "tfs" in seg.device_arrays()["postings"]["body"]


class TestBuildHelpers:
    def test_build_impact_plane_empty_row_field(self):
        # a field whose rows include empties must still produce a sane
        # block CSR (0 blocks for empty rows)
        m = _mappings()
        docs = [m.parse("0", {"body": "a b c"}), m.parse("1", {"body": "a"})]
        seg = build_segment("_0", docs, m)
        ip = seg.postings["body"].impact
        assert int(ip.block_starts[-1]) == len(ip.block_max)

    def test_build_impact_plane_none_for_empty(self):
        m = _mappings()
        pbless = build_segment("_0", [m.parse("0", {"body": ""})], m)
        pb = pbless.postings.get("body")
        assert pb is None or pb.impact is None or pb.size > 0
