"""Device-side phase-2 rescore (ops/rescore.py) — host/device parity on the
CPU backend. The escalation ladder's middle rung (candidate-union exact
rescore) can run as a batched jit launch over the aligned postings buffers;
these tests pin it BIT-FOR-BIT against the host numpy oracle
(`fastpath._exact_rescore`): exact f32 scores, match counts, and the
serve/escalate decisions they feed (`_tie_serves`/theta32 semantics depend
on exact f32 equality, so allclose is not enough)."""

import numpy as np
import pytest

import jax.numpy as jnp

from opensearch_tpu.index.engine import Engine
from opensearch_tpu.index.mappings import Mappings
from opensearch_tpu.ops.pallas_bm25 import (DL_BITS, INT_SENTINEL, LANES,
                                            align_csr_rows)
from opensearch_tpu.ops import rescore
from opensearch_tpu.ops.rescore import (exact_rescore_batch,
                                        host_exact_rescore_batch,
                                        probe_rounds)
from opensearch_tpu.search import compiler as C, plan as PL
from opensearch_tpu.search import fastpath
from opensearch_tpu.search import query_dsl as dsl
from opensearch_tpu.search.executor import ShardSearcher
from tests.test_pruned import (sim_fused_bm25_topk_impact,
                               sim_fused_bm25_topk_tfdl)


@pytest.fixture(params=["plane_in_vmem", "plane_in_hbm"])
def plane(request, monkeypatch):
    """Both forms of the search over the tests' small planes: unrolled to
    the plane's depth (a plane XLA keeps in VMEM), and one `while` a term
    slot (a plane in HBM, here by calling every plane too large). The form
    is fixed when the program is traced, so traces of the other one go."""
    if request.param == "plane_in_hbm":
        monkeypatch.setattr(rescore, "VMEM_PLANE_BYTES", 0)
    exact_rescore_batch.clear_cache()
    yield request.param
    exact_rescore_batch.clear_cache()


class TestKernelParity:
    """exact_rescore_batch vs the numpy mirror on raw padded operands."""

    def _mk(self, rng, nterms=6, maxdf=800, ndocs=4000):
        starts = [0]
        docs, tfdl = [], []
        for _ in range(nterms):
            df = int(rng.integers(1, maxdf))
            ids = np.sort(rng.choice(ndocs, size=df, replace=False))
            tf = rng.integers(1, 30, df)
            dl = rng.integers(1, 500, df)
            docs.append(ids.astype(np.int32))
            tfdl.append(((tf.astype(np.int64) << DL_BITS)
                         | dl).astype(np.int32))
            starts.append(starts[-1] + df)
        a_starts, a_docs, a_tfdl = align_csr_rows(
            np.asarray(starts, np.int64), np.concatenate(docs),
            np.concatenate(tfdl), margin=1024, alignment=LANES)
        return a_starts, a_docs, a_tfdl, nterms

    @pytest.mark.parametrize("seed", [3, 17])
    def test_bitwise_equal(self, seed, plane):
        rng = np.random.default_rng(seed)
        a_starts, a_docs, a_tfdl, nterms = self._mk(rng)
        T, CC, QB = 4, 256, 4
        starts = np.zeros((QB, T), np.int32)
        lens = np.zeros((QB, T), np.int32)
        weights = np.zeros((QB, T), np.float32)
        avgdl = np.zeros((QB, 1), np.float32)
        cand = np.full((QB, CC), INT_SENTINEL, np.int32)
        for q in range(QB):
            for t in range(T):
                if rng.random() < 0.2:
                    continue          # absent slot (lens stays 0)
                r = int(rng.integers(0, nterms))
                a, b = int(a_starts[r]), int(a_starts[r + 1])
                # true window length = non-sentinel prefix of the aligned row
                starts[q, t] = a
                lens[q, t] = int(np.sum(a_docs[a:b] != INT_SENTINEL))
                weights[q, t] = np.float32(rng.uniform(0.1, 4.0))
            avgdl[q, 0] = np.float32(rng.uniform(1.0, 300.0))
            n = int(rng.integers(1, CC))
            cand[q, :n] = np.sort(rng.choice(4000, size=n, replace=False))
        for k1, b in ((1.2, 0.75), (0.9, 0.0)):
            dx, dc = exact_rescore_batch(
                jnp.asarray(a_docs), jnp.asarray(a_tfdl), starts, lens,
                weights, avgdl, cand, probe_rounds(lens, len(a_docs)),
                T=T, C=CC, k1=k1, b=b)
            hx, hc = host_exact_rescore_batch(
                a_docs, a_tfdl, starts, lens, weights, avgdl, cand,
                k1=k1, b=b)
            assert np.asarray(dx).tobytes() == hx.tobytes()
            assert (np.asarray(dc) == hc).all()


NDOCS_PD = 6000


def _planes(rng, row_lens, first=0):
    """Aligned planes of rows with the given posting counts, 128-aligned so
    a row of 256 postings is followed at once by the next row's first doc."""
    starts, docs, tfdl = [0], [], []
    for n in row_lens:
        ids = first + np.sort(rng.choice(NDOCS_PD, size=n, replace=False))
        docs.append(ids.astype(np.int32))
        tfdl.append(((rng.integers(1, 30, n).astype(np.int64) << DL_BITS)
                     | rng.integers(1, 500, n)).astype(np.int32))
        starts.append(starts[-1] + n)
    return align_csr_rows(np.asarray(starts, np.int64), np.concatenate(docs),
                          np.concatenate(tfdl), margin=1024, alignment=LANES)


def _launch(rng, row_lens, slots, cand_ids, first=0):
    """Operands of one launch: `slots[q][t]` is a row index or None (absent
    term), `cand_ids(q)` the query's candidate ids."""
    a_starts, a_docs, a_tfdl = _planes(rng, row_lens, first)
    QB, T, CC = len(slots), len(slots[0]), 1024
    starts = np.zeros((QB, T), np.int32)
    lens = np.zeros((QB, T), np.int32)
    weights = rng.uniform(0.1, 4.0, (QB, T)).astype(np.float32)
    avgdl = rng.uniform(1.0, 300.0, (QB, 1)).astype(np.float32)
    cand = np.full((QB, CC), INT_SENTINEL, np.int32)
    for q, row in enumerate(slots):
        for t, r in enumerate(row):
            if r is not None:
                starts[q, t], lens[q, t] = a_starts[r], row_lens[r]
        ids = np.unique(cand_ids(q))[:CC]
        cand[q, : len(ids)] = ids
    return a_docs, a_tfdl, starts, lens, weights, avgdl, cand


def _every_doc(first=0):
    return lambda q: first + np.arange(0, NDOCS_PD, 7)


PROBE_DEPTH_CASES = {
    # rows of 1, 2^k - 1, 2^k, 2^k + 1 postings in one launch
    "rows_1_255_256_257": dict(
        row_lens=[1, 255, 256, 257], slots=[[0, 1, 2, 3]],
        want_rounds=[1, 8, 9, 9]),
    "rows_1_3_4_5": dict(
        row_lens=[1, 3, 4, 5], slots=[[0, 1, 2, 3]],
        want_rounds=[1, 2, 3, 3]),
    # a slot no query of the launch has: rounds 0, nothing probed there
    "absent_slot": dict(
        row_lens=[300, 2000], slots=[[0, None, 1, None]],
        want_rounds=[9, 0, 11, 0]),
    # QB 2, each slot's depth set by a different query
    "qb2_maxima_from_different_queries": dict(
        row_lens=[3000, 40, 17, 1500], slots=[[0, 2], [1, 3]],
        want_rounds=[12, 11]),
    # every candidate below the row's first / above its last doc id (ids
    # start at 1000; the 256-long row abuts the next row's first doc)
    "candidates_below_first": dict(
        row_lens=[256, 100], slots=[[0, 1]], first=1000,
        cand_ids=lambda q: np.arange(0, 1000, 3), want_rounds=[9, 7]),
    "candidates_above_last": dict(
        row_lens=[256, 100], slots=[[0, 1]], first=1000,
        cand_ids=lambda q: 1000 + NDOCS_PD + np.arange(500),
        want_rounds=[9, 7]),
    # deeper than needed (the retired static depth of a 2.2M-doc plane)
    "rounds_larger_than_needed": dict(
        row_lens=[1, 256, 2000, 31], slots=[[0, 1, 2, 3], [3, 2, 1, 0]],
        rounds=[27, 27, 27, 27], want_rounds=[5, 11, 11, 5]),
}


@pytest.mark.parametrize("plane", ["plane_in_hbm"], indirect=True)
class TestProbeDepth:
    """Over a plane in HBM the probe depth follows the rows of the launch,
    per term slot; the scores stay the host mirror's, byte for byte."""

    @pytest.mark.parametrize("case", sorted(PROBE_DEPTH_CASES))
    def test_depth_per_slot_is_bitwise_the_host(self, case, plane):
        spec = PROBE_DEPTH_CASES[case]
        first = spec.get("first", 0)
        a_docs, a_tfdl, starts, lens, weights, avgdl, cand = _launch(
            np.random.default_rng(29), spec["row_lens"], spec["slots"],
            spec.get("cand_ids", _every_doc(first)), first)
        assert probe_rounds(lens, len(a_docs)).tolist() \
            == spec["want_rounds"]
        rounds = np.asarray(spec.get("rounds", spec["want_rounds"]),
                            np.int32)
        T = lens.shape[1]
        dx, dc = exact_rescore_batch(
            jnp.asarray(a_docs), jnp.asarray(a_tfdl), starts, lens, weights,
            avgdl, cand, rounds, T=T, C=cand.shape[1], k1=1.2, b=0.75)
        hx, hc = host_exact_rescore_batch(
            a_docs, a_tfdl, starts, lens, weights, avgdl, cand,
            k1=1.2, b=0.75)
        assert np.asarray(dx).tobytes() == hx.tobytes()
        assert (np.asarray(dc) == hc).all()
        assert hc.any() == ("candidates" not in case)


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    m = Mappings({"properties": {"body": {"type": "text"}}})
    eng = Engine(m)
    for i in range(5000):
        parts = []
        if rng.random() < 0.7:
            parts.extend(["common"] * int(rng.integers(1, 5)))
        if rng.random() < 0.5:
            parts.append("half%d" % int(rng.integers(0, 2)))
        parts.append(f"rare{int(rng.integers(0, 300))}")
        parts.extend(f"pad{int(x)}" for x in rng.integers(0, 1000, 3))
        eng.index_doc(str(i), {"body": " ".join(parts)})
    eng.refresh()
    eng.force_merge(1)
    return eng.segments[0], ShardSearcher(eng).context()


@pytest.fixture()
def small_head(monkeypatch):
    monkeypatch.setattr(fastpath, "L_HEAD", 64)
    monkeypatch.setattr(fastpath, "fused_bm25_topk_tfdl",
                        sim_fused_bm25_topk_tfdl)
    # codec-v2 segments ride the impact frontier kernel now (ISSUE 11)
    monkeypatch.setattr(fastpath, "fused_bm25_topk_impact",
                        sim_fused_bm25_topk_impact)
    monkeypatch.setattr(fastpath, "_backend_ok", True)


def _spec(ctx, q, window):
    node = PL.rewrite(dsl.parse_query(q), ctx, scoring=True)
    return fastpath.make_spec(node, [], [], [], None, window, {})


QUERIES = [
    ({"match": {"body": "common half0"}}, 20),
    ({"match": {"body": "common half1 half0"}}, 25),
    ({"match": {"body": {"query": "common half0 rare2",
                         "minimum_should_match": 2}}}, 10),
    ({"match": {"body": "common"}}, 30),
]


class TestOracleParity:
    def test_rescore_many_matches_exact_rescore(self, corpus, small_head,
                                                plane):
        """The batched device dispatcher returns EXACTLY what the per-query
        host oracle returns for the same (vq, candidate-union) jobs."""
        seg, ctx = corpus
        seg.__dict__.pop("_fastpath_aligned", None)
        al = fastpath.get_aligned(seg, "body")
        pb = seg.postings["body"]
        prune = [True] * len(QUERIES)
        lts = []
        for q, _w in QUERIES:
            node = PL.rewrite(dsl.parse_query(q), ctx, scoring=True)
            lts.append(node)
        vq_lists = fastpath._prepare_vqueries(seg, ctx, lts, {}, prune)
        jobs = []
        for vqs in vq_lists:
            vq = vqs[0]
            cand = fastpath._p2_candidates(vq, pb, al.head_ids.get)
            assert cand is not None
            jobs.append((vq, cand))
        fastpath.set_rescore_mode("device")
        try:
            dev = fastpath._rescore_many(seg, jobs)
        finally:
            fastpath.set_rescore_mode(None)
        for (vq, cand), (dx, dc) in zip(jobs, dev):
            hx, hc = fastpath._exact_rescore(seg, vq, cand)
            assert dx.tobytes() == hx.tobytes()
            assert (dc == hc).all()

    def test_serve_decisions_bit_identical(self, corpus, small_head):
        """End-to-end: the full pruned pipeline produces the same docs,
        bit-identical f32 scores, totals, and relation whether the middle
        rung rescores on host or device."""
        seg, ctx = corpus
        outs = {}
        for mode in ("host", "device"):
            seg.__dict__.pop("_fastpath_aligned", None)
            fastpath.set_rescore_mode(mode)
            try:
                res = []
                for q, w in QUERIES:
                    out = fastpath.batch_search(seg, ctx,
                                                [_spec(ctx, q, w)], w)[0]
                    assert out is not None
                    res.append(out)
            finally:
                fastpath.set_rescore_mode(None)
            outs[mode] = res
        for (q, _w), h, d in zip(QUERIES, outs["host"], outs["device"]):
            assert list(h["topk_idx"]) == list(d["topk_idx"]), q
            assert h["topk_scores"].tobytes() == \
                d["topk_scores"].tobytes(), q
            assert (h["total"], h["total_rel"]) == \
                (d["total"], d["total_rel"]), q

    def test_batch_launch_count_and_buckets(self, corpus, small_head):
        """An msearch-style batch of escalating queries rides FEW device
        launches (grouped per shape bucket), and candidate counts inside
        one bucket reuse one cached program."""
        seg, ctx = corpus
        seg.__dict__.pop("_fastpath_aligned", None)
        # same T_pad bucket (2 terms -> T_pad 2) so tier-1 groups
        batch = [({"match": {"body": "common half0"}}, 20),
                 ({"match": {"body": "common half1"}}, 20)]
        specs = [_spec(ctx, q, w) for q, w in batch]
        before = dict(fastpath.RESCORE_STATS)
        ci0 = C.build_rescore_program.cache_info()
        fastpath.set_rescore_mode("device")
        try:
            outs = fastpath.batch_search(seg, ctx, specs, 20)
        finally:
            fastpath.set_rescore_mode(None)
        assert all(o is not None for o in outs)
        dq = fastpath.RESCORE_STATS["device_queries"] \
            - before["device_queries"]
        dl = fastpath.RESCORE_STATS["device_launches"] \
            - before["device_launches"]
        assert dq >= 2
        # both tier-1 jobs shared one launch (tier-2 retries add their own)
        assert dl < dq
        ci1 = C.build_rescore_program.cache_info()
        assert ci1.currsize >= ci0.currsize
        # one more query with a DIFFERENT candidate count in the same
        # bucket: no new program (canonicalized shape hit)
        seg.__dict__.pop("_fastpath_aligned", None)
        fastpath.set_rescore_mode("device")
        try:
            fastpath.batch_search(
                seg, ctx, [_spec(ctx, {"match": {"body": "common half1"}},
                                 15)], 15)
        finally:
            fastpath.set_rescore_mode(None)
        ci2 = C.build_rescore_program.cache_info()
        assert ci2.currsize == ci1.currsize
        assert ci2.hits > ci1.hits

    def test_probe_elems_counted_and_no_program_per_row_length(
            self, corpus, small_head, plane):
        """`device_probe_elems` grows by exactly QB * C * sum(rounds) of a
        launch (per slot the bit length of its longest row over a plane in
        HBM, the plane's own in every slot over one in VMEM), and a launch
        whose rows have other lengths reuses the program: the depth is an
        operand, not a key."""
        seg, ctx = corpus
        seg.__dict__.pop("_fastpath_aligned", None)
        al = fastpath.get_aligned(seg, "body")
        pb = seg.postings["body"]

        def jobs_of(*texts):
            nodes = [PL.rewrite(dsl.parse_query({"match": {"body": t}}), ctx,
                                scoring=True) for t in texts]
            vqs = fastpath._prepare_vqueries(seg, ctx, nodes, {},
                                             [True] * len(nodes))
            return [(v[0], fastpath._p2_candidates(v[0], pb,
                                                   al.head_ids.get))
                    for v in vqs]

        def launch(jobs):
            """-> (counter's growth, QB * C * sum of per-slot depths)"""
            deepest = np.max([[int(al.lens[int(r)]) for r in vq.rows]
                              for vq, _cand in jobs], axis=0)
            if plane == "plane_in_vmem":
                deepest[:] = al.d_docs.shape[0]
            assert {C.rescore_cand_bucket(len(c)) for _vq, c in jobs} \
                == {C.RESCORE_C_MIN}
            before = fastpath.RESCORE_STATS["device_probe_elems"]
            fastpath._rescore_many_device(seg, jobs)
            return (fastpath.RESCORE_STATS["device_probe_elems"] - before,
                    len(jobs) * C.RESCORE_C_MIN
                    * sum(int(n).bit_length() for n in deepest))

        grew, want = launch(jobs_of("common half0", "half1 rare7"))
        assert grew == want > 0
        built = C.build_rescore_program.cache_info()
        traced = exact_rescore_batch._cache_size()
        grew2, want2 = launch(jobs_of("rare3 rare5", "half0 rare9"))
        assert grew2 == want2 > 0
        assert (want2 < want) == (plane == "plane_in_hbm")
        after = C.build_rescore_program.cache_info()
        assert (after.currsize, after.misses) == \
            (built.currsize, built.misses)
        assert exact_rescore_batch._cache_size() == traced

    def test_bucket_canonicalization(self):
        assert C.rescore_cand_bucket(1) == C.RESCORE_C_MIN
        assert C.rescore_cand_bucket(C.RESCORE_C_MIN + 1) == \
            2 * C.RESCORE_C_MIN
        assert C.rescore_cand_bucket(C.RESCORE_C_MAX) == C.RESCORE_C_MAX
        assert C.rescore_cand_bucket(C.RESCORE_C_MAX + 1) is None
        assert C.rescore_cand_bucket(0) is None
