"""Host-side fast-path logic (search/fastpath.py) that runs without a TPU:
aligned-layout construction, doc-range chunk decomposition invariants, and
eligibility gating. Kernel-vs-XLA parity runs on real TPU in
tests_tpu/test_fastpath.py."""

import numpy as np
import pytest

from opensearch_tpu.index.engine import Engine
from opensearch_tpu.index.mappings import Mappings
from opensearch_tpu.ops.pallas_bm25 import (DL_BITS, DL_MASK, HBM_ALIGN,
                                            LANES, align_csr_rows)
from opensearch_tpu.search import plan as PL
from opensearch_tpu.search import fastpath
from opensearch_tpu.search import query_dsl as dsl
from opensearch_tpu.search.executor import ShardSearcher


@pytest.fixture(scope="module")
def seg_ctx():
    rng = np.random.default_rng(7)
    m = Mappings({"properties": {"body": {"type": "text"}}})
    eng = Engine(m)
    for i in range(5000):
        parts = []
        if rng.random() < 0.7:
            parts.append("common")
        parts.append(f"rare{int(rng.integers(0, 200))}")
        eng.index_doc(str(i), {"body": " ".join(parts)})
    eng.refresh()
    eng.force_merge(1)
    s = ShardSearcher(eng)
    return eng.segments[0], s.context()


def _lterms(ctx, text, field="body"):
    q = dsl.parse_query({"match": {field: text}})
    node = PL.rewrite(q, ctx, scoring=True)
    assert isinstance(node, PL.LTerms)
    return node


class TestAlignedLayout:
    def test_rows_aligned_and_lossless(self, seg_ctx):
        seg, ctx = seg_ctx
        al = fastpath.get_aligned(seg, "body")
        assert al is not None
        pb = seg.postings["body"]
        docs = np.asarray(al.d_docs)
        tfdl = np.asarray(al.d_tfdl)
        dl = seg.doc_lens["body"]
        for term in ("common", "rare3"):
            r = pb.row(term)
            a, b = pb.row_slice(r)
            start = int(al.starts_rows[r]) * LANES
            assert start % LANES == 0
            n = b - a
            assert int(al.lens[r]) == n
            np.testing.assert_array_equal(docs[start: start + n],
                                          pb.doc_ids[a:b])
            got_tf = tfdl[start: start + n] >> DL_BITS
            got_dl = tfdl[start: start + n] & DL_MASK
            np.testing.assert_array_equal(got_tf, pb.tfs[a:b].astype(np.int64))
            np.testing.assert_array_equal(got_dl, dl[pb.doc_ids[a:b]])

    def test_align_csr_rows_preserves_dtype(self):
        starts = np.array([0, 3, 5], np.int64)
        docs = np.array([1, 5, 9, 2, 4], np.int32)
        vals_i = np.array([10, 20, 30, 40, 50], np.int32)
        ns, nd, nv = align_csr_rows(starts, docs, vals_i, margin=1024)
        assert nv.dtype == np.int32
        assert ns[1] % HBM_ALIGN == 0


class TestChunkDecomposition:
    def test_small_query_single_vquery(self, seg_ctx):
        seg, ctx = seg_ctx
        lt = _lterms(ctx, "rare3 rare5")
        vls = fastpath._prepare_vqueries(seg, ctx, [lt], {})
        assert vls is not None and len(vls[0]) == 1
        vq = vls[0][0]
        assert vq.dlo == 0 and vq.dhi == int(fastpath.INT_MAX)

    def test_oversized_chunks_partition_doc_space(self, seg_ctx):
        seg, ctx = seg_ctx
        pb = seg.postings["body"]
        al = fastpath.get_aligned(seg, "body")
        lt = _lterms(ctx, "common rare3")
        rows = np.array([pb.row("common"), pb.row("rare3")], np.int64)
        # force chunking regardless of corpus size (budget must stay above
        # the 1024-element DMA alignment slop per chunk)
        old_l, old_tl = fastpath.MAX_L, fastpath.MAX_TL
        fastpath.MAX_L, fastpath.MAX_TL = 1 << 12, 1 << 13
        try:
            chunks = fastpath._chunk_slices(al, pb, rows, seg.ndocs)
        finally:
            fastpath.MAX_L, fastpath.MAX_TL = old_l, old_tl
        assert chunks is not None and len(chunks) >= 2
        # doc ranges tile [0, ndocs) without gap or overlap
        assert chunks[0][0] == 0
        for (lo1, hi1, *_), (lo2, hi2, *_) in zip(chunks, chunks[1:]):
            assert hi1 == lo2
        assert chunks[-1][1] >= seg.ndocs
        # every chunk's DMA start is tile-aligned and the postings of each
        # term are fully covered across chunks
        covered = {i: 0 for i in range(len(rows))}
        for lo, hi, rowstarts, nrows, lens, skips in chunks:
            for i, r in enumerate(rows):
                if lens[i] == 0:
                    continue
                assert (rowstarts[i] * LANES) % HBM_ALIGN == 0
                assert nrows[i] * LANES >= lens[i] + skips[i]
                a, b = pb.row_slice(r)
                d = pb.doc_ids[a:b]
                covered[i] += int(np.sum((d >= lo) & (d < hi)))
        for i, r in enumerate(rows):
            a, b = pb.row_slice(r)
            assert covered[i] == b - a


class TestEligibility:
    def test_eligible_plain_match(self, seg_ctx):
        seg, ctx = seg_ctx
        lt = _lterms(ctx, "rare3 rare5")
        assert fastpath.query_eligible(lt, [], [], [], None, 10, {})

    def test_ineligible_shapes(self, seg_ctx):
        seg, ctx = seg_ctx
        lt = _lterms(ctx, "rare3")
        assert not fastpath.query_eligible(lt, [], ["agg"], [], None, 10, {})
        assert not fastpath.query_eligible(lt, [], [], ["nm"], None, 10, {})
        assert not fastpath.query_eligible(lt, [], [], [], [1], 10, {})
        assert not fastpath.query_eligible(
            lt, [{"field": "price", "order": "asc"}], [], [], None, 10, {})
        assert not fastpath.query_eligible(lt, [], [], [], None, 4096, {})
        assert not fastpath.query_eligible(lt, [], [], [], None, 10,
                                           {"collapse": {"field": "x"}})
        # score-desc explicit sort is still the hot path
        assert fastpath.query_eligible(
            lt, [{"field": "_score", "order": "desc"}], [], [], None, 10, {})

    def test_filter_mode_and_non_bm25_ineligible(self, seg_ctx):
        seg, ctx = seg_ctx
        lt = _lterms(ctx, "rare3")
        import dataclasses
        assert not fastpath.query_eligible(
            dataclasses.replace(lt, mode="filter"), [], [], [], None, 10, {})


def _spec(ctx, qbody, **kw):
    q = dsl.parse_query(qbody)
    node = PL.rewrite(q, ctx, scoring=True)
    return fastpath.make_spec(node, kw.get("sort", []), kw.get("aggs", []),
                              kw.get("named", []), kw.get("after"),
                              kw.get("window", 10), kw.get("body", {}))


class TestBoolSpec:
    """FastSpec flattening of bool trees onto the weighted-threshold slot
    model (kernel parity itself runs in tests_tpu/test_fastpath_bool.py)."""

    def test_pure_match_is_pure(self, seg_ctx):
        _, ctx = seg_ctx
        s = _spec(ctx, {"match": {"body": "rare1 rare2"}})
        assert s is not None and s.kind == "pure"

    def test_filtered_match(self, seg_ctx):
        _, ctx = seg_ctx
        s = _spec(ctx, {"bool": {
            "must": [{"match": {"body": "rare1 rare2"}}],
            "filter": [{"term": {"body": "common"}}]}})
        assert s is not None and s.kind == "bool"
        # OR-match group: both terms optional (family) with msm 1
        assert [cw for _, _, cw in s.slots] == [1.0, 1.0]
        assert s.fam_msm == 1
        assert len(s.filter_clauses) == 1
        assert s.n_required == 0

    def test_and_match_promotes_to_required(self, seg_ctx):
        _, ctx = seg_ctx
        s = _spec(ctx, {"bool": {
            "must": [{"match": {"body": {"query": "rare1 rare2",
                                         "operator": "and"}}}],
            "filter": [{"term": {"body": "common"}}]}})
        assert s is not None
        assert all(cw == fastpath.REQ_W for _, _, cw in s.slots)

    def test_bonus_shoulds_zero_count_weight(self, seg_ctx):
        _, ctx = seg_ctx
        s = _spec(ctx, {"bool": {
            "must": [{"term": {"body": "common"}}],
            "should": [{"term": {"body": "rare1"}}]}})
        assert s is not None
        assert [cw for _, _, cw in s.slots] == [fastpath.REQ_W, 0.0]
        assert s.fam_msm == 0

    def test_should_msm_family(self, seg_ctx):
        _, ctx = seg_ctx
        s = _spec(ctx, {"bool": {
            "should": [{"term": {"body": "rare1"}},
                       {"term": {"body": "rare2"}},
                       {"term": {"body": "rare3"}}],
            "minimum_should_match": 2,
            "filter": [{"term": {"body": "common"}}]}})
        assert s is not None
        assert [cw for _, _, cw in s.slots] == [1.0, 1.0, 1.0]
        assert s.fam_msm == 2

    def test_two_constrained_families_fall_back(self, seg_ctx):
        _, ctx = seg_ctx
        s = _spec(ctx, {"bool": {
            "must": [{"match": {"body": {"query": "rare1 rare2 rare5",
                                         "minimum_should_match": 2}}},
                     {"match": {"body": {"query": "rare3 rare4 rare6",
                                         "minimum_should_match": 2}}}]}})
        assert s is None
        # msm == nterms promotes to all-required: two such groups are fine
        s2 = _spec(ctx, {"bool": {
            "must": [{"match": {"body": {"query": "rare1 rare2",
                                         "minimum_should_match": 2}}},
                     {"match": {"body": {"query": "rare3 rare4",
                                         "minimum_should_match": 2}}}]}})
        assert s2 is not None and s2.n_required == 4

    def test_filter_only_and_const_score(self, seg_ctx):
        _, ctx = seg_ctx
        s = _spec(ctx, {"bool": {"filter": [{"term": {"body": "common"}}]}})
        assert s is not None and s.const_score == 0.0 and not s.slots
        s2 = _spec(ctx, {"constant_score": {
            "filter": {"term": {"body": "common"}}, "boost": 2.0}})
        assert s2 is not None and s2.const_score == 2.0

    def test_nested_bool_falls_back(self, seg_ctx):
        _, ctx = seg_ctx
        s = _spec(ctx, {"bool": {
            "must": [{"bool": {"must": [{"term": {"body": "rare1"}}]}}],
            "filter": [{"term": {"body": "common"}}]}})
        assert s is None

    def test_empty_bool_falls_back(self, seg_ctx):
        _, ctx = seg_ctx
        assert _spec(ctx, {"bool": {}}) is None

    def test_body_gates_apply(self, seg_ctx):
        _, ctx = seg_ctx
        q = {"bool": {"must": [{"term": {"body": "rare1"}}],
                      "filter": [{"term": {"body": "common"}}]}}
        assert _spec(ctx, q, aggs=["a"]) is None
        assert _spec(ctx, q, window=4096) is None

    def test_filter_list_build(self, seg_ctx):
        seg, ctx = seg_ctx
        q = dsl.parse_query({"term": {"body": "common"}})
        node = PL.rewrite(q, ctx, scoring=False)
        fl = fastpath._filter_list(seg, ctx, [(node, False)])
        assert fl is not None
        pb = seg.postings["body"]
        r = pb.row("common")
        a, b = pb.row_slice(r)
        np.testing.assert_array_equal(fl.host_docs, pb.doc_ids[a:b])
        # negated clause = complement
        fl2 = fastpath._filter_list(seg, ctx, [(node, True)])
        assert fl2.n == seg.ndocs - fl.n
        # cached on repeat
        assert fastpath._filter_list(seg, ctx, [(node, False)]) is fl


class TestClueWebScaleChunking:
    """ClueWeb-class rows (config 5): chunk planning must keep EVERY df on
    the kernel — including an every-doc stopword at 50M docs (r4 verdict:
    the old 256-chunk cap topped out at ~16.7M postings/term)."""

    def test_stopword_row_50m_docs_plans_on_kernel(self):
        ndocs = 50_000_000
        # stopword: one posting in 3 of every 5 docs -> df = 30M
        docs = np.arange(0, ndocs, dtype=np.int64)
        docs = docs[(docs % 5) < 3]
        assert len(docs) == 30_000_000
        t_total = 8                       # worst-case term-slot padding
        slots = [(docs, 0), None, (docs[: 1 << 20], 1 << 25)] + [None] * 5
        plan = fastpath._chunk_slots(slots, ndocs, t_total)
        assert plan is not None, "fell off-kernel"
        assert len(plan) <= fastpath.MAX_CHUNKS
        budget = fastpath.MAX_TL // t_total
        covered = 0
        prev_hi = 0
        for dlo, dhi, rowstarts, nrows, lens, skips in plan:
            assert dlo == prev_hi          # disjoint, gapless doc ranges
            prev_hi = dhi
            for i in range(t_total):
                assert skips[i] + lens[i] <= budget
            covered += int(lens[0])
        assert covered == len(docs)        # every posting in exactly 1 chunk

    def test_chunk_start_prediction_matches_doubling(self):
        # the predicted starting nchunk must agree with what pure doubling
        # finds (no over-chunking beyond one pow2 step)
        ndocs = 1_000_000
        docs = np.arange(ndocs, dtype=np.int64)
        plan = fastpath._chunk_slots([(docs, 0)], ndocs, 1)
        assert plan is not None
        budget = fastpath.MAX_TL // 1
        need = -(-len(docs) // budget)
        assert len(plan) <= 2 * (1 << (need - 1).bit_length())


class TestTieServesF32Domain:
    """ADVICE r5 `fastpath.py:823`: `_tie_serves` must detect boundary ties
    in the SERVED f32 domain. A frontier contribution half an ulp below
    theta in f64 rounds UP to theta after `_exact_rescore`'s f32 cast — it
    IS a tie, and its id witness must be checked before the pruned page is
    served as exact.

    NOTE `_frontier` emits f32 arrays today, so production inputs never hit
    the f64 promotion; these tests feed f64 frontiers deliberately to pin
    the INVARIANT (compares run in f32 no matter what dtype a future
    frontier variant carries) rather than to reproduce a live bug."""

    class _Al:
        def __init__(self, fr):
            self.rem_frontiers = fr

    def _setup(self, witness_id, k1=1.2):
        # find a tf whose f64 contribution tf/(tf+k1) rounds UP in f32
        tf = next(t for t in range(1, 5000)
                  if float(np.float32(t / (t + k1))) > t / (t + k1))
        c64 = tf / (tf + k1)
        theta = float(np.float32(c64))      # theta lives in the f32 domain
        assert c64 < theta                  # ...but the f64 value sits below
        # pre-fix counterfactual: the uncast f64 ARRAY compare (NEP50
        # promotes f64 array vs f32 scalar to f64) sees NO tie at all
        c64a = np.array([c64])
        assert not np.any(c64a > np.float32(theta))
        assert not np.any(c64a == np.float32(theta))
        fr = (np.array([tf], np.float64), np.array([0.0], np.float64),
              np.array([witness_id], np.int64),
              np.array([witness_id], np.int64))
        vq = fastpath._VQuery(rows=np.array([0]),
                              weights=np.array([1.0], np.float32),
                              k1=k1, b_eff=0.0, avgdl=10.0)
        cand = np.array([10], np.int64)     # boundary member is doc 10
        order = np.array([0], np.int64)
        return self._Al({0: fr}), vq, theta, cand, order

    def test_rounding_tie_with_smaller_id_escalates(self):
        # witness doc 7 sorts before boundary doc 10 under (score desc,
        # doc asc): the page is NOT provably exact -> False (pre-fix the
        # f64 compare classified the doc as below theta and served)
        al, vq, theta, cand, order = self._setup(witness_id=7)
        assert fastpath._tie_serves(al, vq, theta, cand, order, 1) is False

    def test_rounding_tie_with_larger_id_serves(self):
        # same tie, but the min attaining id sorts after the boundary:
        # the witness proves the served page exact
        al, vq, theta, cand, order = self._setup(witness_id=20)
        assert fastpath._tie_serves(al, vq, theta, cand, order, 1) is True


class TestQualityTierBreaker:
    """ADVICE r5 `fastpath.py:1009`: the `_quality_tier` FilterList's
    ndocs-sized mask + host_docs bytes must be charged to the fastpath
    breaker and released when the cached list is dropped."""

    def test_charge_and_release_on_eviction(self, monkeypatch):
        import gc

        from opensearch_tpu.utils.breaker import CircuitBreaker

        rng = np.random.default_rng(11)
        m = Mappings({"properties": {"body": {"type": "text"}}})
        eng = Engine(m)
        for i in range(2048):
            tf = int(rng.integers(1, 40))
            pad = int(rng.integers(1, 40))
            eng.index_doc(str(i), {"body": " ".join(
                ["alpha"] * tf + [f"u{i}"] * pad)})
        eng.refresh()
        eng.force_merge(1)
        seg = eng.segments[0]
        # prewarm the aligned layout so its (separate) charge does not
        # land on the test breaker
        assert fastpath.get_aligned(seg, "body") is not None
        monkeypatch.setattr(fastpath, "QUALITY_MIN_NDOCS", 256)
        br = CircuitBreaker("test-fielddata", 1 << 30)
        # the ledger is the sole charge path now (OSL506): install the
        # test breaker as its charge target (monkeypatch restores)
        from opensearch_tpu.obs.hbm_ledger import LEDGER
        monkeypatch.setattr(LEDGER, "_breaker", br)

        qt = fastpath._quality_tier(seg, "body")
        assert qt is not None
        fl, _frontier_of = qt
        nbytes = fl.mask.nbytes + fl.host_docs.nbytes
        assert nbytes > 0
        assert fl.nbytes == nbytes          # FilterList self-reports bytes
        assert br.used == nbytes            # ...and the breaker holds them

        # eviction: dropping the cached list releases the exact charge
        seg._fastpath_quality.clear()
        del fl, qt
        gc.collect()
        assert br.used == 0


# ----------------------------------------------------------------------
# codec-v2 impact frontier kernel in the pure ladder (ISSUE 11): aligned
# plane construction, epsilon soundness, launch-group splitting, and the
# certify-or-escalate verify. The Pallas kernel itself is EMULATED in
# numpy here (same contract: approx scores from the aligned quantized
# plane, msm counting, (score desc, doc asc) top-K, exact totals) —
# tier-1 runs on CPU; kernel-vs-emulator parity belongs to tests_tpu/.
# ----------------------------------------------------------------------

def _emulate_impact_kernel(d_docs, d_imp, rowstarts, nrows, lens, skips,
                           weights, msm, dlo, dhi, T, L, K):
    docs = np.asarray(d_docs).reshape(-1)
    imp = np.asarray(d_imp).reshape(-1)
    QB = rowstarts.shape[0]
    scores_out = np.full((QB, LANES), -np.inf, np.float32)
    docs_out = np.full((QB, LANES), -1, np.int32)
    totals = np.zeros((QB, LANES), np.int32)
    for q in range(QB):
        acc, cnt = {}, {}
        for t in range(T):
            ln = int(lens[q, t])
            if ln == 0:
                continue
            start = int(rowstarts[q, t]) * LANES + int(skips[q, t])
            w = float(weights[q, t])
            dd = docs[start: start + ln]
            ii = imp[start: start + ln]
            sel = (dd >= dlo[q, 0]) & (dd < dhi[q, 0])
            for d, v in zip(dd[sel], ii[sel]):
                d = int(d)
                acc[d] = acc.get(d, 0.0) + w * float(v)
                cnt[d] = cnt.get(d, 0) + 1
        items = sorted(((d, s) for d, s in acc.items()
                        if cnt[d] >= float(msm[q, 0])),
                       key=lambda x: (-x[1], x[0]))
        totals[q, :] = len(items)
        for j, (d, s) in enumerate(items[:K]):
            scores_out[q, j] = np.float32(s)
            docs_out[q, j] = d
    return scores_out, docs_out, totals


def _emulate_tfdl_kernel(d_docs, d_tfdl, rowstarts, nrows, lens, skips,
                         weights, msm, avg, dlo, dhi, T, L, K, k1, b):
    docs = np.asarray(d_docs).reshape(-1)
    tfdl = np.asarray(d_tfdl).reshape(-1).astype(np.int64)
    QB = rowstarts.shape[0]
    scores_out = np.full((QB, LANES), -np.inf, np.float32)
    docs_out = np.full((QB, LANES), -1, np.int32)
    totals = np.zeros((QB, LANES), np.int32)
    for q in range(QB):
        acc, cnt = {}, {}
        for t in range(T):
            ln = int(lens[q, t])
            if ln == 0:
                continue
            start = int(rowstarts[q, t]) * LANES + int(skips[q, t])
            w = np.float32(weights[q, t])
            dd = docs[start: start + ln]
            packed = tfdl[start: start + ln]
            tf = (packed >> DL_BITS).astype(np.float32)
            dl = (packed & DL_MASK).astype(np.float32)
            kfac = np.float32(k1) * (1.0 - b + b * dl
                                     / np.float32(avg[q, 0]))
            contrib = (w * tf / (tf + kfac)).astype(np.float32)
            sel = (dd >= dlo[q, 0]) & (dd < dhi[q, 0])
            for d, s in zip(dd[sel], contrib[sel]):
                d = int(d)
                acc[d] = np.float32(acc.get(d, np.float32(0.0))
                                    + np.float32(s))
                cnt[d] = cnt.get(d, 0) + 1
        items = sorted(((d, s) for d, s in acc.items()
                        if cnt[d] >= float(msm[q, 0])),
                       key=lambda x: (-x[1], x[0]))
        totals[q, :] = len(items)
        for j, (d, s) in enumerate(items[:K]):
            scores_out[q, j] = s
            docs_out[q, j] = d
    return scores_out, docs_out, totals


@pytest.fixture(scope="module")
def v2_seg_ctx():
    rng = np.random.default_rng(21)
    m = Mappings({"properties": {"body": {"type": "text"}}})
    eng = Engine(m)
    words = [f"q{i:03d}" for i in range(60)]
    for i in range(4000):
        k = int(rng.integers(2, 30))
        toks = [words[int(t) % 60] for t in rng.zipf(1.4, k)]
        eng.index_doc(str(i), {"body": " ".join(toks)})
    eng.refresh()
    eng.force_merge(1)
    s = ShardSearcher(eng)
    seg = eng.segments[0]
    assert seg.postings["body"].impact is not None
    return seg, s.context()


class TestImpactFrontier:
    def test_aligned_layout_carries_quantized_plane(self, v2_seg_ctx):
        seg, ctx = v2_seg_ctx
        al = fastpath.get_aligned(seg, "body")
        assert al is not None and al.d_imp is not None
        # aligned impacts widened to i32, zero-filled at sentinel slots
        a_imp = np.asarray(al.d_imp)
        a_docs = np.asarray(al.d_docs)
        assert a_imp.dtype == np.int32 and len(a_imp) == len(a_docs)
        pb = seg.postings["body"]
        r = pb.row("q001")
        a, b = pb.row_slice(r)
        st = int(al.starts_rows[r]) * LANES
        assert np.array_equal(a_imp[st: st + (b - a)],
                              pb.impact.q[a:b].astype(np.int32))

    def test_prepare_marks_impact_pass_with_eps(self, v2_seg_ctx):
        seg, ctx = v2_seg_ctx
        lt = _lterms(ctx, "q001 q002")
        vq_lists = fastpath._prepare_vqueries(seg, ctx, [lt], {},
                                              prune=[True])
        vq = vq_lists[0][0]
        assert vq.head and vq.impact_pass
        assert vq.eps > 0.0
        plane = seg.postings["body"].impact
        wsum = float(np.abs(vq.weights).sum())
        # eps at least the summed quantization half-steps (soundness floor)
        assert vq.eps >= wsum * plane.quant_err()

    def test_env_gate_pins_frontier_off(self, v2_seg_ctx, monkeypatch):
        seg, ctx = v2_seg_ctx
        monkeypatch.setenv("OPENSEARCH_TPU_NO_IMPACT_FRONTIER", "1")
        lt = _lterms(ctx, "q001 q002")
        vq = fastpath._prepare_vqueries(seg, ctx, [lt], {},
                                        prune=[True])[0][0]
        assert vq.head and not vq.impact_pass and vq.eps == 0.0

    def test_v1_segment_never_marks_impact(self, v2_seg_ctx):
        seg, ctx = v2_seg_ctx
        import copy
        v1 = copy.copy(seg)
        v1.codec_version = 1
        v1.__dict__.pop("_fastpath_aligned", None)
        v1._device_cache = {}
        v1._device_live_dirty = {}
        v1.__dict__.pop("_hbm_allocs", None)
        v1.__dict__.pop("_field_device_allocs", None)
        lt = _lterms(ctx, "q001 q002")
        vq = fastpath._prepare_vqueries(v1, ctx, [lt], {},
                                        prune=[True])[0][0]
        assert not vq.impact_pass
        v1.__dict__.pop("_fastpath_aligned", None)

    def test_run_pure_serves_oracle_exact_pages(self, v2_seg_ctx,
                                                monkeypatch):
        """End-to-end ladder with the emulated kernels: served pages are
        the exact BM25 top-k (scores bit-equal to the host oracle), the
        frontier pass actually rode the impact kernel, and certify-or-
        escalate stays green."""
        seg, ctx = v2_seg_ctx
        monkeypatch.setattr(fastpath, "fused_bm25_topk_impact",
                            _emulate_impact_kernel)
        monkeypatch.setattr(fastpath, "fused_bm25_topk_tfdl",
                            _emulate_tfdl_kernel)
        queries = ["q001 q002", "q000", "q003 q007 q011", "q040 q001"]
        lts = [_lterms(ctx, q) for q in queries]
        specs = [fastpath.make_spec(lt, [], [], [], None, 10, {})
                 for lt in lts]
        assert all(s is not None and s.kind == "pure" for s in specs)
        before = dict(fastpath.STATS)
        outs = fastpath._run_pure(seg, ctx, lts, specs, 10)
        assert outs is not None
        assert fastpath.STATS["impact_frontier"] > before["impact_frontier"]
        for lt, out in zip(lts, outs):
            assert out is not None
            vq_rows = np.array([seg.postings["body"].row(t)
                                for t in lt.terms], np.int64)
            vq = fastpath._VQuery(
                qi=0, T_pad=len(vq_rows), rows=vq_rows,
                weights=np.asarray(lt.weights, np.float32),
                msm=float(lt.msm), msm_true=float(lt.msm),
                avgdl=np.float32(ctx.avgdl("body")),
                k1=float(lt.sim.k1), b_eff=float(lt.sim.b),
                field="body", L=0, rowstarts=None, nrows=None,
                lens=None, skips=None, dlo=0, dhi=0)
            cand = np.arange(seg.ndocs, dtype=np.int64)
            exact, counts = fastpath._exact_rescore(seg, vq, cand)
            exact = np.where(counts >= 1, exact, -np.inf)
            order = np.lexsort((cand, -exact))[:10]
            want = [(int(cand[i]), np.float32(exact[i])) for i in order
                    if np.isfinite(exact[i])]
            got = [(int(d), s) for d, s in zip(out["topk_idx"],
                                               out["topk_scores"])
                   if d >= 0 and np.isfinite(s)]
            assert got == want, lt.terms

    def test_verify_impact_exact_escalates_when_bound_crosses_theta(
            self, v2_seg_ctx):
        seg, ctx = v2_seg_ctx
        lt = _lterms(ctx, "q001 q002")
        vq = fastpath._prepare_vqueries(seg, ctx, [lt], {},
                                        prune=[True])[0][0]
        assert vq.impact_pass
        # fabricate a FULL kernel window whose deepest partial ties the
        # window boundary: bound = partial_k + eps >= theta -> escalate
        pbk = seg.postings["body"]
        r = pbk.row("q001")
        a, b = pbk.row_slice(r)
        cand_pool = pbk.doc_ids[a: a + LANES].astype(np.int32)
        vq2 = vq
        exact, counts = fastpath._exact_rescore(
            seg, vq2, cand_pool.astype(np.int64))
        sc = np.sort(exact)[::-1][:LANES].astype(np.float32)
        dc = cand_pool[np.argsort(-exact, kind="stable")][:LANES]
        # serving window == the full kernel window: theta is the deepest
        # exact candidate, and the deepest partial ties it exactly, so
        # bound = partial_k + eps >= theta — a lost doc could deserve
        # the boundary slot and the verifier must escalate
        ver = fastpath._verify_impact_exact(seg, vq2, sc, dc,
                                            int(LANES), int(LANES), 10)
        assert ver is None

    def test_impact_and_tfdl_groups_split(self, v2_seg_ctx, monkeypatch):
        seg, ctx = v2_seg_ctx
        launched = []

        def spy_imp(*a, **kw):
            launched.append("impact")
            return _emulate_impact_kernel(*a, **kw)

        def spy_tfdl(*a, **kw):
            launched.append("tfdl")
            return _emulate_tfdl_kernel(*a, **kw)

        monkeypatch.setattr(fastpath, "fused_bm25_topk_impact", spy_imp)
        monkeypatch.setattr(fastpath, "fused_bm25_topk_tfdl", spy_tfdl)
        lts = [_lterms(ctx, "q001 q002"), _lterms(ctx, "q003 q004")]
        specs = [fastpath.make_spec(lt, [], [], [], None, 10, {})
                 for lt in lts]
        # one impact launch coalesces both head queries; dense redos (if
        # any) ride tfdl — so the impact kernel launches exactly once
        fastpath._run_pure(seg, ctx, lts, specs, 10)
        assert launched.count("impact") == 1

    def test_profile_names_impact_kernel_via_rest(self, monkeypatch):
        """ISSUE 11 acceptance: `fused_bm25_topk_impact` is reachable
        from the SERVING fastpath — the device_plan profile names it —
        and the page it serves is identical to the fastpath-disabled
        rerun (certify-or-escalate parity)."""
        from opensearch_tpu.rest.client import RestClient
        monkeypatch.setattr(fastpath, "fused_bm25_topk_impact",
                            _emulate_impact_kernel)
        monkeypatch.setattr(fastpath, "fused_bm25_topk_tfdl",
                            _emulate_tfdl_kernel)
        monkeypatch.setattr(fastpath, "_backend_ok", True)
        c = RestClient()
        # replicas off: replica searchers are device-pinned and bypass
        # the fastpath on the virtual-CPU mesh
        c.indices.create("ipk", {
            "settings": {"number_of_replicas": 0},
            "mappings": {"properties": {"body": {"type": "text"}}}})
        rng = np.random.default_rng(7)
        words = [f"q{i:03d}" for i in range(60)]
        bulk = []
        for i in range(3000):
            k = int(rng.integers(2, 30))
            toks = [words[int(t) % 60] for t in rng.zipf(1.4, k)]
            bulk.append({"index": {"_index": "ipk", "_id": str(i)}})
            bulk.append({"body": " ".join(toks)})
        c.bulk(bulk)
        c.indices.refresh("ipk")
        c.indices.forcemerge("ipk")
        body = {"query": {"match": {"body": "q001 q002"}}, "size": 10}
        r = c.search("ipk", {**body, "explain": "device_plan"})
        segs = r["device_plan"]["segments"]
        assert any(e.get("path") == "fused_bm25_topk_impact"
                   for e in segs), segs
        page = [(h["_id"], h["_score"]) for h in r["hits"]["hits"]]
        assert len(page) == 10
        # parity: same docs in the same order as the general-path page,
        # scores equal to f32 accumulation order (the ladder serves the
        # host-oracle f32 domain; XLA reassociates the same sum).
        # "_bench" varies the request-cache key, nothing else
        monkeypatch.setenv("OPENSEARCH_TPU_NO_FASTPATH", "1")
        r2 = c.search("ipk", {**body, "_bench": "nofp"})
        page2 = [(h["_id"], h["_score"]) for h in r2["hits"]["hits"]]
        assert [d for d, _ in page] == [d for d, _ in page2]
        np.testing.assert_allclose([s for _, s in page],
                                   [s for _, s in page2], rtol=1e-6)


class TestReorderTieParity:
    """Code-review regression: kernel-verbatim windows on a BP-reordered
    segment break exact-score ties by PERMUTED internal id. `_assemble`
    must re-break them by arrival rank, and DECLINE (per-query fallback)
    when the tie class reaches the end of the extracted window — an
    unextracted doc could deserve the slot."""

    @staticmethod
    def _fake_seg(ndocs=256):
        tr = np.arange(ndocs, dtype=np.int64)[::-1].copy()

        class _S:
            def tie_ranks(self):
                return tr

        return _S()

    def test_assemble_rebreaks_kernel_ties_by_arrival(self):
        seg = self._fake_seg()
        K = 8
        # kernel order: score desc, PERMUTED doc asc — 20-doc tie class
        # at the top, distinct tail. Arrival rank is the REVERSE of the
        # internal id here, so the served page must flip the tie class.
        sc = np.concatenate([np.full(20, 1.0, np.float32),
                             np.linspace(0.9, 0.1, LANES - 20,
                                         dtype=np.float32)])
        dc = np.arange(LANES, dtype=np.int32)
        vq = object()
        out = fastpath._assemble([[vq]], {id(vq): (sc, dc, 300, "eq")},
                                 K, seg=seg)
        assert out[0] is not None
        assert list(out[0]["topk_idx"]) == list(range(19, 11, -1))
        assert all(s == np.float32(1.0) for s in out[0]["topk_scores"])

    def test_assemble_declines_when_tie_reaches_window_end(self):
        seg = self._fake_seg()
        # every extracted lane ties: the class extends past the window,
        # so the earliest-arrival member may not even be extracted
        sc = np.full(LANES, 1.0, np.float32)
        dc = np.arange(LANES, dtype=np.int32)
        vq = object()
        before = dict(fastpath.STATS).get("reorder_tie_fallback", 0)
        out = fastpath._assemble([[vq]], {id(vq): (sc, dc, 300, "eq")},
                                 8, seg=seg)
        assert out[0] is None
        assert dict(fastpath.STATS)["reorder_tie_fallback"] == before + 1

    def test_assemble_trusts_exact_entries_verbatim(self):
        seg = self._fake_seg()
        sc = np.linspace(1.0, 0.5, 8, dtype=np.float32)
        dc = np.arange(8, dtype=np.int32)
        vq = object()
        out = fastpath._assemble([[vq]], {id(vq): (sc, dc, 8, "gte")},
                                 8, seg=seg, exact_ids={id(vq)})
        # verify/rescue-produced pages are already arrival-ordered exact:
        # no re-sort, no decline
        assert list(out[0]["topk_idx"]) == list(range(8))
        assert out[0]["total_rel"] == "gte"

    @pytest.fixture()
    def tie_seg_ctx(self, monkeypatch):
        monkeypatch.setenv("OPENSEARCH_TPU_REORDER", "1")
        monkeypatch.setenv("OPENSEARCH_TPU_REORDER_MIN_DOCS", "256")
        rng = np.random.default_rng(3)
        m = Mappings({"properties": {"body": {"type": "text"}}})
        eng = Engine(m)
        words = [f"q{i:03d}" for i in range(60)]
        for i in range(1500):
            if i % 5 == 0:
                body = "q001 q002 q003"     # 300-doc exact-tie class
            else:
                k = int(rng.integers(2, 30))
                body = " ".join(words[int(t) % 60]
                                for t in rng.zipf(1.4, k))
            eng.index_doc(str(i), {"body": body})
        eng.refresh()
        eng.force_merge(1)
        return eng.segments[0], ShardSearcher(eng).context()

    def test_reordered_tie_pages_match_arrival_oracle(self, tie_seg_ctx,
                                                      monkeypatch):
        """End-to-end ladder over a reordered segment whose page boundary
        sits INSIDE a large exact-tie class: every served page must equal
        the arrival-rank host oracle (what the unreordered arm serves)."""
        seg, ctx = tie_seg_ctx
        tr = seg.tie_ranks()
        assert tr is not None, "reorder did not permute this segment"
        monkeypatch.setattr(fastpath, "fused_bm25_topk_impact",
                            _emulate_impact_kernel)
        monkeypatch.setattr(fastpath, "fused_bm25_topk_tfdl",
                            _emulate_tfdl_kernel)
        queries = ["q001 q002", "q001", "q002 q003"]
        lts = [_lterms(ctx, q) for q in queries]
        specs = [fastpath.make_spec(lt, [], [], [], None, 10, {})
                 for lt in lts]
        assert all(s is not None and s.kind == "pure" for s in specs)
        outs = fastpath._run_pure(seg, ctx, lts, specs, 10)
        assert outs is not None
        for lt, out in zip(lts, outs):
            vq_rows = np.array([seg.postings["body"].row(t)
                                for t in lt.terms], np.int64)
            vq = fastpath._VQuery(
                qi=0, T_pad=len(vq_rows), rows=vq_rows,
                weights=np.asarray(lt.weights, np.float32),
                msm=float(lt.msm), msm_true=float(lt.msm),
                avgdl=np.float32(ctx.avgdl("body")),
                k1=float(lt.sim.k1), b_eff=float(lt.sim.b),
                field="body", L=0, rowstarts=None, nrows=None,
                lens=None, skips=None, dlo=0, dhi=0)
            cand = np.arange(seg.ndocs, dtype=np.int64)
            exact, counts = fastpath._exact_rescore(seg, vq, cand)
            exact = np.where(counts >= 1, exact, -np.inf)
            order = np.lexsort((tr[cand], -exact))[:10]
            want = [(int(cand[i]), np.float32(exact[i])) for i in order
                    if np.isfinite(exact[i])]
            if out is None:
                # a boundary tie the ladder could not resolve declines to
                # the general path — acceptable, parity served there
                continue
            got = [(int(d), s) for d, s in zip(out["topk_idx"],
                                               out["topk_scores"])
                   if d >= 0 and np.isfinite(s)]
            assert got == want, lt.terms
