"""Impact-ordered head pruning (search/fastpath.py L_HEAD path) — the device
analog of Lucene block-max pruning (reference
`search/query/TopDocsCollectorContext.java`). The Pallas kernel itself is
TPU-only, so these tests drive the FULL pruned pipeline (head build →
prepare → launch → host verify → dense escalation → REST totals relation)
against a numpy simulator of the kernel's exact semantics, monkeypatched in
place of `fused_bm25_topk_tfdl`."""

import numpy as np
import pytest

from opensearch_tpu.index.engine import Engine
from opensearch_tpu.index.mappings import Mappings
from opensearch_tpu.ops.pallas_bm25 import DL_BITS, DL_MASK, LANES
from opensearch_tpu.search import plan as PL
from opensearch_tpu.search import fastpath
from opensearch_tpu.search import query_dsl as dsl
from opensearch_tpu.search.executor import ShardSearcher


def sim_fused_bm25_topk_tfdl(d_docs, d_tfdl, rowstarts, nrows, lens, skips,
                             weights, msm, avgdl, dlo, dhi, T, L, K, k1, b):
    """Numpy reference of the kernel: per query, stream each term's window,
    scatter-add contributions, count appearances, msm-filter, top-K by
    (score desc, doc asc). Mirrors ops/pallas_bm25._bm25_tfdl_kernel."""
    docs_a = np.asarray(d_docs).ravel()
    tfdl_a = np.asarray(d_tfdl).ravel()
    QB = rowstarts.shape[0]
    out_s = np.full((QB, 128), -np.inf, np.float32)
    out_d = np.full((QB, 128), -1, np.int32)
    out_t = np.zeros((QB, 128), np.int32)
    for q in range(QB):
        scores: dict = {}
        counts: dict = {}
        for t in range(T):
            if nrows[q, t] == 0:
                continue
            base = int(rowstarts[q, t]) * LANES + int(skips[q, t])
            ln = int(lens[q, t])
            w = float(weights[q, t])
            window_docs = docs_a[base: base + ln]
            window_tfdl = tfdl_a[base: base + ln]
            for d, packed in zip(window_docs, window_tfdl):
                if not (dlo[q, 0] <= d < dhi[q, 0]):
                    continue
                tf = float((packed >> DL_BITS) & ((1 << 11) - 1))
                dl = float(packed & DL_MASK)
                k = k1 * (1.0 - b + b * dl / float(avgdl[q, 0]))
                scores[d] = scores.get(d, 0.0) + np.float32(
                    np.float32(w) * np.float32(tf) / np.float32(tf + k))
                counts[d] = counts.get(d, 0) + 1
        passing = [(s, d) for d, s in scores.items()
                   if counts[d] >= msm[q, 0]]
        out_t[q, :] = len(passing)
        passing.sort(key=lambda sd: (-sd[0], sd[1]))
        for j, (s, d) in enumerate(passing[:K]):
            out_s[q, j] = s
            out_d[q, j] = d
    return out_s, out_d, out_t


def sim_fused_bm25_topk_impact(d_docs, d_imp, rowstarts, nrows, lens,
                               skips, weights, msm, dlo, dhi, T, L, K):
    """Numpy reference of the codec-v2 impact frontier kernel
    (fused_bm25_topk_impact): one multiply per posting over the aligned
    quantized plane, msm counting, top-K by (approx desc, doc asc) —
    the v2 frontier rung these corpora now take by default (ISSUE 11)."""
    docs_a = np.asarray(d_docs).ravel()
    imp_a = np.asarray(d_imp).ravel()
    QB = rowstarts.shape[0]
    out_s = np.full((QB, 128), -np.inf, np.float32)
    out_d = np.full((QB, 128), -1, np.int32)
    out_t = np.zeros((QB, 128), np.int32)
    for q in range(QB):
        scores: dict = {}
        counts: dict = {}
        for t in range(T):
            ln = int(lens[q, t])
            if ln == 0:
                continue
            base = int(rowstarts[q, t]) * LANES + int(skips[q, t])
            w = float(weights[q, t])
            dd = docs_a[base: base + ln]
            ii = imp_a[base: base + ln]
            sel = (dd >= dlo[q, 0]) & (dd < dhi[q, 0])
            for d, v in zip(dd[sel], ii[sel]):
                d = int(d)
                scores[d] = scores.get(d, 0.0) + w * float(v)
                counts[d] = counts.get(d, 0) + 1
        passing = [(s, d) for d, s in scores.items()
                   if counts[d] >= msm[q, 0]]
        out_t[q, :] = len(passing)
        passing.sort(key=lambda sd: (-sd[0], sd[1]))
        for j, (s, d) in enumerate(passing[:K]):
            out_s[q, j] = np.float32(s)
            out_d[q, j] = d
    return out_s, out_d, out_t


@pytest.fixture()
def small_head(monkeypatch):
    """Shrink L_HEAD so a 5k-doc corpus exercises clamping, and stand the
    simulators in for the TPU kernels (both frontier variants: the v2
    impact kernel serves codec-v2 segments by default, the tf·dl kernel
    serves v1 / negative-boost shapes)."""
    monkeypatch.setattr(fastpath, "L_HEAD", 64)
    monkeypatch.setattr(fastpath, "fused_bm25_topk_tfdl",
                        sim_fused_bm25_topk_tfdl)
    monkeypatch.setattr(fastpath, "fused_bm25_topk_impact",
                        sim_fused_bm25_topk_impact)
    monkeypatch.setattr(fastpath, "_backend_ok", True)


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    m = Mappings({"properties": {"body": {"type": "text"}}})
    eng = Engine(m)
    for i in range(5000):
        parts = []
        # `common` df ~ 3500 >> L_HEAD=64; tf varies 1..4 so impact order
        # differs from doc order; rare terms stay under the head size
        if rng.random() < 0.7:
            parts.extend(["common"] * int(rng.integers(1, 5)))
        if rng.random() < 0.5:
            parts.append("half%d" % int(rng.integers(0, 2)))
        parts.append(f"rare{int(rng.integers(0, 300))}")
        parts.extend(f"pad{int(x)}" for x in rng.integers(0, 1000, 3))
        eng.index_doc(str(i), {"body": " ".join(parts)})
    eng.refresh()
    eng.force_merge(1)
    s = ShardSearcher(eng)
    return eng.segments[0], s.context()


def _spec(ctx, body_query, window=10, body=None):
    q = dsl.parse_query(body_query)
    node = PL.rewrite(q, ctx, scoring=True)
    return fastpath.make_spec(node, [], [], [], None, window, body or {})


class TestHeadBuild:
    def test_head_is_top_impact_doc_ascending(self, corpus, small_head):
        seg, ctx = corpus
        seg.__dict__.pop("_fastpath_aligned", None)
        al = fastpath.get_aligned(seg, "body")
        pb = seg.postings["body"]
        dl = seg.doc_lens["body"]
        r = pb.row("common")
        a, b = pb.row_slice(r)
        df = b - a
        assert df > fastpath.L_HEAD
        assert int(al.head_lens[r]) == fastpath.L_HEAD
        # head region contents
        docs = np.asarray(al.d_docs)
        tfdl = np.asarray(al.d_tfdl)
        start = int(al.head_starts_rows[r]) * LANES
        h_docs = docs[start: start + fastpath.L_HEAD]
        h_tf = (tfdl[start: start + fastpath.L_HEAD] >> DL_BITS) & 0x7FF
        # doc-ascending (kernel merge invariant)
        assert (np.diff(h_docs) > 0).all()
        # selected set = top-L_HEAD by impact under the nominal params
        tf_all = pb.tfs[a:b].astype(np.float32)
        dl_all = dl[pb.doc_ids[a:b]].astype(np.float32)
        avg = max(float(dl_all.mean()), 1.0)
        c = tf_all / (tf_all + 1.2 * (0.25 + 0.75 * dl_all / avg))
        kth = np.sort(c)[-fastpath.L_HEAD]
        head_set = set(int(d) for d in h_docs)
        # every selected posting's impact >= the L_HEAD-th largest
        sel = np.isin(pb.doc_ids[a:b], h_docs)
        assert (c[sel] >= kth - 1e-7).all()
        # the remainder frontier is a true bound: every non-kept posting's
        # contribution under arbitrary params stays below the frontier max
        rest = ~sel
        assert al.clamped(r)
        for k1_q, b_q, avg_q in ((1.2, 0.75, avg), (0.9, 0.4, avg * 1.7),
                                 (2.0, 0.0, 1.0)):
            ub = al.rem_bound(r, k1_q, b_q, avg_q)
            kq = k1_q * (1.0 - b_q + b_q * dl_all[rest] / max(avg_q, 1e-9))
            c_rest = tf_all[rest] / (tf_all[rest] + np.maximum(kq, 1e-9))
            assert float(c_rest.max()) <= ub + 1e-6
        # unclamped rare term: head view == full view
        rr = pb.row("rare5")
        assert int(al.head_lens[rr]) == int(al.lens[rr])
        assert int(al.head_starts_rows[rr]) == int(al.starts_rows[rr])
        assert not al.clamped(rr)


class TestPrunedParity:
    @pytest.mark.parametrize("query,window", [
        ({"match": {"body": "common"}}, 10),                   # clamped 1-term
        ({"match": {"body": "common rare7"}}, 10),             # mixed df
        ({"match": {"body": "rare3 rare9"}}, 10),              # unclamped
        ({"match": {"body": "common half0"}}, 20),             # 2 clamped?
        ({"match": {"body": {"query": "common half1",
                             "operator": "and"}}}, 10),        # conjunction
        ({"match": {"body": {"query": "common half0 rare2",
                             "minimum_should_match": 2}}}, 10),  # msm
    ])
    def test_pruned_equals_dense(self, corpus, small_head, query, window):
        seg, ctx = corpus
        seg.__dict__.pop("_fastpath_aligned", None)
        spec = _spec(ctx, query, window)
        assert spec is not None and spec.kind == "pure" and spec.prune_ok
        out_pruned = fastpath.batch_search(seg, ctx, [spec], window)[0]
        # dense reference: same pipeline, pruning off
        spec_d = _spec(ctx, query, window, body={"track_total_hits": True})
        assert not spec_d.prune_ok
        out_dense = fastpath.batch_search(seg, ctx, [spec_d], window)[0]
        assert out_pruned is not None and out_dense is not None
        pd_, dd = out_pruned["topk_idx"], out_dense["topk_idx"]
        ps, ds = out_pruned["topk_scores"], out_dense["topk_scores"]
        n = min(window, int((np.isfinite(ds)).sum()))
        assert list(pd_[:n]) == list(dd[:n]), query
        np.testing.assert_allclose(ps[:n], ds[:n], rtol=2e-5)
        # totals: exact when nothing clamped, else a gte lower bound
        if out_pruned["total_rel"] == "eq":
            assert out_pruned["total"] == out_dense["total"]
        else:
            assert out_pruned["total"] <= out_dense["total"]

    def test_escalation_counter_and_correctness(self, corpus, small_head):
        """A query whose bound check must fail (tiny idf spread, deep
        window) still returns the exact dense answer via escalation."""
        seg, ctx = corpus
        seg.__dict__.pop("_fastpath_aligned", None)
        before = dict(fastpath.STATS)
        # window 100 over a clamped term: theta is the 100th score, almost
        # certainly below the remainder bound -> dense rerun
        spec = _spec(ctx, {"match": {"body": "common"}}, 100)
        out = fastpath.batch_search(seg, ctx, [spec], 100)[0]
        spec_d = _spec(ctx, {"match": {"body": "common"}}, 100,
                       body={"track_total_hits": True})
        ref = fastpath.batch_search(seg, ctx, [spec_d], 100)[0]
        assert list(out["topk_idx"]) == list(ref["topk_idx"])
        assert fastpath.STATS["pruned_escalated"] > before["pruned_escalated"]
        # escalated results are exact again
        assert out["total_rel"] == "eq"
        assert out["total"] == ref["total"]


class TestPrunedProperty:
    def test_random_queries_parity(self, corpus, small_head):
        """Randomized: pruned pipeline must match dense for arbitrary term
        mixes, windows, and msm — ties broken identically (stable impact
        selection + doc-asc ordering)."""
        seg, ctx = corpus
        seg.__dict__.pop("_fastpath_aligned", None)
        rng = np.random.default_rng(23)
        vocab = (["common", "half0", "half1"]
                 + [f"rare{i}" for i in range(0, 300, 17)]
                 + [f"pad{i}" for i in range(0, 1000, 91)])
        for trial in range(40):
            nt = int(rng.integers(1, 4))
            terms = list(rng.choice(vocab, size=nt, replace=False))
            msm = int(rng.integers(1, nt + 1))
            window = int(rng.integers(1, 30))
            q = {"match": {"body": {"query": " ".join(terms),
                                    "minimum_should_match": msm}}}
            spec = _spec(ctx, q, window)
            if spec is None:
                continue
            out = fastpath.batch_search(seg, ctx, [spec], window)[0]
            spec_d = _spec(ctx, q, window,
                           body={"track_total_hits": True})
            ref = fastpath.batch_search(seg, ctx, [spec_d], window)[0]
            assert out is not None and ref is not None, terms
            n = min(window, int(np.isfinite(ref["topk_scores"]).sum()))
            assert list(out["topk_idx"][:n]) == list(ref["topk_idx"][:n]), \
                (terms, msm, window)
            np.testing.assert_allclose(out["topk_scores"][:n],
                                       ref["topk_scores"][:n], rtol=2e-5)


class TestFilteredPure:
    def test_filtered_bool_rides_pruned_pure_pipeline(self, monkeypatch):
        """Family-only bool specs over a dense hot filter serve through
        the pure pruned pipeline on the FilteredSegView, matching the XLA
        filtered path exactly."""
        from opensearch_tpu.rest.client import RestClient

        monkeypatch.setattr(fastpath, "L_HEAD", 64)
        monkeypatch.setattr(fastpath, "fused_bm25_topk_tfdl",
                            sim_fused_bm25_topk_tfdl)
        monkeypatch.setattr(fastpath, "fused_bm25_topk_impact",
                            sim_fused_bm25_topk_impact)
        monkeypatch.setattr(fastpath, "_backend_ok", True)
        monkeypatch.setattr(fastpath, "_MATERIALIZE_MIN_DOCS", 16)
        # skip the warm-up hop through the (TPU-only) bool kernel: treat
        # the retained filter as hot immediately so every call takes the
        # specialized-view pure path the test is about
        monkeypatch.setattr(fastpath, "_dense_hot",
                            lambda seg, fl, nslots: fl.mask is not None)
        rng = np.random.default_rng(41)
        c = RestClient()
        c.indices.create("fb", {
            "settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": {"properties": {
                "status": {"type": "keyword"}, "body": {"type": "text"}}}})
        for i in range(4000):
            body = []
            if rng.random() < 0.6:
                body.extend(["common"] * int(rng.integers(1, 4)))
            body.append(f"w{int(rng.integers(0, 30))}")
            c.index("fb", {"body": " ".join(body),
                           "status": ("pub", "draft")[i % 2]},
                    id=f"{i:05d}")
        c.indices.refresh("fb")
        c.indices.forcemerge("fb")
        bodies = [
            {"query": {"bool": {"must": [{"match": {"body": "common w3"}}],
                                "filter": [{"term": {"status": "pub"}}]}},
             "size": 10},
            {"query": {"bool": {
                "must": [{"match": {"body": {"query": "common w5",
                                             "operator": "and"}}}],
                "filter": [{"term": {"status": "pub"}}]}}, "size": 10},
        ]
        for body in bodies:
            # first call warms the filter (merge-slot path), the second
            # takes the dense-hot specialized view
            for rep in range(3):
                before = dict(fastpath.STATS)
                rm = c.search("fb", dict(body, _rep=rep))
                assert fastpath.STATS["bool_served"] == \
                    before["bool_served"] + 1
                fastpath.set_enabled(False)
                try:
                    rh = c.search("fb", dict(body, _ref=rep))
                finally:
                    fastpath.set_enabled(True)
                assert rm["hits"]["total"]["value"] <= \
                    rh["hits"]["total"]["value"]
                if rm["hits"]["total"]["relation"] == "eq":
                    assert rm["hits"]["total"] == rh["hits"]["total"]
                assert [h["_id"] for h in rm["hits"]["hits"]] == \
                    [h["_id"] for h in rh["hits"]["hits"]], (body, rep)
                sm = [round(h["_score"], 4) for h in rm["hits"]["hits"]]
                sh = [round(h["_score"], 4) for h in rh["hits"]["hits"]]
                assert sm == sh, (body, rep)
        # the view path genuinely engaged (pruned or exact over the view)
        assert fastpath.STATS["pruned_served"] + \
            fastpath.STATS["pruned_escalated"] > 0
        # regression: a term whose FILTERED row is empty (present in the
        # vocab, zero postings pass the filter) must not crash the verify
        # rescore — index a draft-only term and query it under status=pub
        c.index("fb", {"body": "draftonly common", "status": "draft"},
                id="dr1")
        c.indices.refresh("fb")
        c.indices.forcemerge("fb")
        body = {"query": {"bool": {
            "must": [{"match": {"body": "common draftonly"}}],
            "filter": [{"term": {"status": "pub"}}]}}, "size": 5}
        rm = c.search("fb", dict(body, _e=1))
        fastpath.set_enabled(False)
        try:
            rh = c.search("fb", dict(body, _e=2))
        finally:
            fastpath.set_enabled(True)
        assert [h["_id"] for h in rm["hits"]["hits"]] == \
            [h["_id"] for h in rh["hits"]["hits"]]


class TestShardView:
    def test_multi_segment_single_launch_parity(self, small_head):
        """A many-segment shard serves pure term-group queries as ONE
        kernel launch over the concatenated shard view, matching the
        per-segment XLA path exactly (the TPU answer to reference
        ConcurrentQueryPhaseSearcher)."""
        from opensearch_tpu.rest.client import RestClient

        rng = np.random.default_rng(31)
        words = [f"v{i}" for i in range(40)]
        cm = RestClient()
        ch = RestClient()
        for c in (cm, ch):
            rng2 = np.random.default_rng(31)
            c.indices.create("sv", {
                "settings": {"number_of_shards": 1,
                             "number_of_replicas": 0}})
            for wave in range(3):     # 3 refreshes -> >= 3 segments
                for i in range(wave * 80, wave * 80 + 80):
                    c.index("sv", {"body": " ".join(
                        rng2.choice(words, 6))}, id=f"{i:04d}")
                c.indices.refresh("sv")
        assert len(cm.node.indices["sv"].shards[0].segments) >= 2
        # ch runs with fastpath disabled -> per-segment XLA reference
        before = dict(fastpath.STATS)
        for q, size in (("v1 v2", 10), ("v3", 25), ("v4 v5 v6", 7)):
            rm = cm.search("sv", {"query": {"match": {"body": q}},
                                  "size": size})
            fastpath.set_enabled(False)
            try:
                rh = ch.search("sv", {"query": {"match": {"body": q}},
                                      "size": size, "_ref": 1})
            finally:
                fastpath.set_enabled(True)
            assert rm["hits"]["total"]["value"] >= \
                len(rm["hits"]["hits"])
            assert [h["_id"] for h in rm["hits"]["hits"]] == \
                [h["_id"] for h in rh["hits"]["hits"]], q
            sm = [round(h["_score"], 4) for h in rm["hits"]["hits"]]
            sh = [round(h["_score"], 4) for h in rh["hits"]["hits"]]
            assert sm == sh, q
        assert fastpath.STATS["shard_view_served"] > \
            before["shard_view_served"]


class TestRestRelation:
    def test_totals_relation_via_rest(self, small_head):
        from opensearch_tpu.rest.client import RestClient

        c = RestClient()
        # replicas off: replica searchers are device-pinned and bypass the
        # fastpath on the virtual-CPU mesh; the primary (device None) prunes
        c.indices.create("pr", {
            "settings": {"number_of_replicas": 0},
            "mappings": {"properties": {"body": {"type": "text"}}}})
        bulk = []
        for i in range(1200):
            bulk.append({"index": {"_index": "pr", "_id": str(i)}})
            # strictly decreasing impact (unique doc length per doc) so the
            # remainder bound sits strictly below the window threshold and
            # the pruned result is provably exact without escalation
            body = "needle needle needle " + " ".join(
                f"p{j}" for j in range(i))
            bulk.append({"body": body})
        c.bulk(bulk)
        c.indices.refresh("pr")
        c.indices.forcemerge("pr")
        r = c.search("pr", {"query": {"match": {"body": "needle"}},
                            "size": 5})
        # df(needle)=1200 > L_HEAD=64: served pruned, totals undercount
        # flagged gte (the reference's default 10k-cap contract)
        assert r["hits"]["total"]["relation"] == "gte"
        assert 0 < r["hits"]["total"]["value"] <= 1200
        assert len(r["hits"]["hits"]) == 5
        # exact totals on demand
        r2 = c.search("pr", {"query": {"match": {"body": "needle"}},
                             "size": 5, "track_total_hits": True})
        assert r2["hits"]["total"] == {"value": 1200, "relation": "eq"}
        # both orderings agree
        assert [h["_id"] for h in r["hits"]["hits"]] == \
            [h["_id"] for h in r2["hits"]["hits"]]


class TestQualityView:
    """Quality-tier (static index pruning) escalation rung: one batched
    exact launch over the high-impact-doc view, certified by the
    out-of-view frontiers."""

    def test_dview_serves_and_matches_dense(self, monkeypatch):
        monkeypatch.setattr(fastpath, "L_HEAD", 64)
        monkeypatch.setattr(fastpath, "fused_bm25_topk_tfdl",
                            sim_fused_bm25_topk_tfdl)
        monkeypatch.setattr(fastpath, "fused_bm25_topk_impact",
                            sim_fused_bm25_topk_impact)
        monkeypatch.setattr(fastpath, "_backend_ok", True)
        monkeypatch.setattr(fastpath, "QUALITY_MIN_NDOCS", 2048)
        rng = np.random.default_rng(21)
        m = Mappings({"properties": {"body": {"type": "text"}}})
        eng = Engine(m)
        # 512 short high-impact docs, 3584 long tf=1 docs: the quality
        # tier keeps the short docs, so a deep window is provably served
        # from the view while phase 1/2 bounds fail
        for i in range(4096):
            if i % 8 == 0:
                body = "common common common w1"
            else:
                body = "common " + " ".join(
                    rng.choice([f"f{j}" for j in range(50)], 14))
            eng.index_doc(str(i), {"body": body})
        eng.refresh()
        seg = eng.segments[0]
        ctx = ShardSearcher(eng).context()
        before = dict(fastpath.STATS)
        # 2-term: no single-term tie witness, both rows clamped, and the
        # remainder impacts tie the window boundary -> phase 1/2 fail,
        # the quality view (which holds EVERY w1 posting) serves
        spec = _spec(ctx, {"match": {"body": "common w1"}}, 64)
        out = fastpath.batch_search(seg, ctx, [spec], 64)[0]
        spec_d = _spec(ctx, {"match": {"body": "common w1"}}, 64,
                       body={"track_total_hits": True})
        ref = fastpath.batch_search(seg, ctx, [spec_d], 64)[0]
        assert out is not None and ref is not None
        assert list(out["topk_idx"])[:64] == list(ref["topk_idx"])[:64]
        np.testing.assert_allclose(out["topk_scores"][:64],
                                   ref["topk_scores"][:64], rtol=2e-5)
        d = {k: fastpath.STATS[k] - before[k] for k in before
             if fastpath.STATS[k] != before[k]}
        assert d.get("pruned_dview", 0) >= 1, d
        # gte totals: the view undercounts matches by design
        assert out["total"] <= ref["total"]

    def test_dview_declines_small_segments(self, corpus, small_head):
        seg, ctx = corpus
        assert fastpath._quality_tier(seg, "body") is None

    def test_dview_skips_shard_view_segments(self, monkeypatch):
        # regression: multi-segment shards run _run_pure over a ShardView
        # facade (no .uid); the quality rung must decline it, not crash
        monkeypatch.setattr(fastpath, "L_HEAD", 64)
        monkeypatch.setattr(fastpath, "fused_bm25_topk_tfdl",
                            sim_fused_bm25_topk_tfdl)
        monkeypatch.setattr(fastpath, "_backend_ok", True)
        monkeypatch.setattr(fastpath, "QUALITY_MIN_NDOCS", 2048)
        rng = np.random.default_rng(21)
        m = Mappings({"properties": {"body": {"type": "text"}}})
        eng = Engine(m)
        for wave in range(2):
            for i in range(wave * 2048, wave * 2048 + 2048):
                if i % 8 == 0:
                    body = "common common common w1"
                else:
                    body = "common " + " ".join(
                        rng.choice([f"f{j}" for j in range(50)], 14))
                eng.index_doc(str(i), {"body": body})
            eng.refresh()
        assert len(eng.segments) >= 2
        from opensearch_tpu.search.executor import search_shards
        s = ShardSearcher(eng)
        body = {"query": {"match": {"body": "common w1"}}, "size": 64}
        out = search_shards([s], dict(body))
        fastpath.set_enabled(False)
        ref = search_shards([s], dict(body, _ref=1))
        fastpath.set_enabled(True)
        # tie-fair comparison: this corpus makes 512 docs score
        # identically, and the slow path's cross-segment tie order
        # differs from the shard-view kernel's (pre-existing nuance);
        # the guard here is the CRASH, plus rank-wise score equality
        outs = [round(h["_score"], 4) for h in out["hits"]["hits"]]
        refs = [round(h["_score"], 4) for h in ref["hits"]["hits"]]
        assert outs == refs
        assert len(out["hits"]["hits"]) == 64
