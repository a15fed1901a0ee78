"""TPU-hardware checks for the codec-v2 impact-gather kernel
(ops/pallas_bm25.fused_bm25_topk_impact): on a real chip the kernel's
quantized partial scores must reproduce the host mirror (weight × raw
quantized impact, one f32 multiply per posting) bit-for-bit, and the
block-compacted DMA windows must never leak skipped-block postings into
the result. Run on a real chip:
`python -m pytest tests_tpu/test_impact_tpu.py -q`."""

import numpy as np
import pytest

import jax

from opensearch_tpu.ops.pallas_bm25 import (HBM_ALIGN, INT_SENTINEL, LANES,
                                            align_csr_rows,
                                            fused_bm25_topk_impact)

pytestmark = pytest.mark.skipif(jax.default_backend() != "tpu",
                                reason="needs a real TPU chip")


def _host_mirror(docs_l, imps_l, weights, msm, k):
    """Exact host mirror of the kernel: per-doc sum of w·q over the
    supplied (doc, q) postings, msm-filtered, (score desc, doc asc)."""
    acc = {}
    cnt = {}
    for t, (ids, qs) in enumerate(zip(docs_l, imps_l)):
        for d, qv in zip(ids, qs):
            acc[d] = np.float32(acc.get(d, np.float32(0.0))
                                + np.float32(weights[t])
                                * np.float32(qv))
            cnt[d] = cnt.get(d, 0) + 1
    hits = [(d, s) for d, s in acc.items() if cnt[d] >= msm]
    hits.sort(key=lambda x: (-x[1], x[0]))
    return hits[:k]


@pytest.mark.parametrize("seed", [3, 11])
def test_impact_kernel_matches_host_mirror(seed):
    rng = np.random.default_rng(seed)
    nterms, ndocs = 4, 30_000
    starts_l = [0]
    docs_l, imps_l = [], []
    for _ in range(nterms):
        df = int(rng.integers(100, 5000))
        ids = np.sort(rng.choice(ndocs, size=df, replace=False))
        q = rng.integers(1, 65536, df)
        docs_l.append(ids.astype(np.int32))
        imps_l.append(q.astype(np.int32))
        starts_l.append(starts_l[-1] + df)
    starts = np.asarray(starts_l, np.int64)
    a_starts, a_docs, a_imp = align_csr_rows(
        starts, np.concatenate(docs_l), np.concatenate(imps_l),
        margin=1 << 16, alignment=LANES)

    T = 4
    K = 128
    weights = rng.uniform(0.1, 4.0, nterms).astype(np.float32)
    rowstarts = np.zeros((1, T), np.int32)
    nrows = np.zeros((1, T), np.int32)
    lens = np.zeros((1, T), np.int32)
    skips = np.zeros((1, T), np.int32)
    L = 1 << 13
    for t in range(nterms):
        abs_el = int(a_starts[t])
        dma_el = (abs_el // HBM_ALIGN) * HBM_ALIGN
        skip = abs_el - dma_el
        ln = int(starts[t + 1] - starts[t])
        rowstarts[0, t] = dma_el // LANES
        nr = 8
        while nr * LANES < skip + ln:
            nr *= 2
        nrows[0, t] = nr
        lens[0, t] = ln
        skips[0, t] = skip
        L = max(L, nr * LANES)
    w = weights[None, :]
    msm = np.array([[1.0]], np.float32)
    dlo = np.array([[0]], np.int32)
    dhi = np.array([[2**31 - 1]], np.int32)
    scores, out_docs, totals = jax.device_get(fused_bm25_topk_impact(
        jax.device_put(a_docs), jax.device_put(a_imp),
        rowstarts, nrows, lens, skips, w, msm, dlo, dhi,
        T=T, L=int(L), K=K))
    exp = _host_mirror(docs_l, imps_l, weights, 1, K)
    got = [(int(d), np.float32(s)) for s, d in zip(scores[0], out_docs[0])
           if d >= 0]
    assert len(got) == min(K, len(exp))
    for (gd, gs), (ed, es) in zip(got, exp):
        assert gd == ed
        assert gs == np.float32(es)    # bit-exact f32


def test_block_compacted_windows_exclude_skipped_postings():
    """Windows covering only a prefix of a row (the host block prune's
    compacted form) must score exactly that prefix."""
    ids = np.arange(0, 4096, 2, dtype=np.int32)      # 2048 postings
    q = np.full(2048, 100, np.int32)
    starts = np.asarray([0, 2048], np.int64)
    a_starts, a_docs, a_imp = align_csr_rows(
        starts, ids, q, margin=1 << 16, alignment=LANES)
    keep = 1024                                      # first 8 blocks only
    rowstarts = np.array([[int(a_starts[0]) // LANES]], np.int32)
    nrows = np.array([[8]], np.int32)
    lens = np.array([[keep]], np.int32)
    skips = np.array([[0]], np.int32)
    w = np.array([[2.0]], np.float32)
    msm = np.array([[1.0]], np.float32)
    dlo = np.array([[0]], np.int32)
    dhi = np.array([[2**31 - 1]], np.int32)
    scores, out_docs, totals = jax.device_get(fused_bm25_topk_impact(
        jax.device_put(a_docs), jax.device_put(a_imp),
        rowstarts, nrows, lens, skips, w, msm, dlo, dhi,
        T=1, L=1024, K=128))
    assert int(totals[0][0]) == keep
    assert int(out_docs[0].max()) < 2 * keep         # no skipped docs
    assert np.all(scores[0][:128] == np.float32(200.0))


# ---------------------------------------------------------------------
# the XLA first pass (`compiler.build_impact_program`) at the size the
# benchmark's `treccovid.search1.long` launches it
# ---------------------------------------------------------------------

def _flat_search_program(bucket, C):
    """The first pass as it stood before a slot's block was its row: one
    flat bucket of slots, each binary-searching the cumulative block
    lengths for its block. Kept here as the reference and nowhere else."""
    import jax.numpy as jnp

    def program(d_docs, d_impacts, live, bstart, blen, bweight, msm):
        cum = jnp.cumsum(blen)
        i = jnp.arange(bucket, dtype=jnp.int32)
        b_idx = jnp.minimum(
            jnp.searchsorted(cum, i, side="right").astype(jnp.int32),
            bstart.shape[0] - 1)
        prev = jnp.where(b_idx > 0, cum[jnp.maximum(b_idx - 1, 0)], 0)
        valid = i < cum[-1]
        src = jnp.clip(bstart[b_idx] + (i - prev), 0, d_docs.shape[0] - 1)
        docs = jnp.where(valid, d_docs[src], jnp.int32(2**31 - 1))
        contrib = jnp.where(
            valid, d_impacts[src].astype(jnp.float32) * bweight[b_idx], 0.0)
        n = live.shape[0]
        scores = jnp.zeros(n, jnp.float32).at[docs].add(contrib, mode="drop")
        counts = jnp.zeros(n, jnp.float32).at[docs].add(
            jnp.where(valid, 1.0, 0.0), mode="drop")
        ok = (counts >= msm) & (live > 0)
        vals, idx = jax.lax.top_k(jnp.where(ok, scores, -jnp.inf), C)
        return vals, idx, jnp.sum(ok.astype(jnp.int32))
    return jax.jit(program)


def cell_sized_plan(seed, nblocks=4000, B_pad=4096, nterms=11,
                    ndocs=171_332, P=18_500_000):
    """A plan of the cell's shape: `nterms` rows, each a run of whole
    128-posting blocks and one partial block at its end, `nblocks` in all,
    over planes of the cell's lengths (2^25 postings, 2^18 documents)."""
    rng = np.random.default_rng(seed)
    d_docs = np.full(1 << 25, 2**31 - 1, np.int32)
    d_docs[:P] = rng.integers(0, ndocs, P, dtype=np.int32)
    d_imp = np.zeros(1 << 25, np.uint16)
    d_imp[:P] = rng.integers(1, 65536, P).astype(np.uint16)
    live = np.zeros(1 << 18, np.float32)
    live[:ndocs] = 1.0
    per = np.diff(np.linspace(0, nblocks, nterms + 1).astype(int))
    row0 = np.sort(rng.choice(P // 128 - nblocks, nterms, replace=False)
                   ) * 128 + rng.integers(0, 128, nterms)
    bstart = np.zeros(B_pad, np.int32)
    blen = np.zeros(B_pad, np.int32)
    bweight = np.zeros(B_pad, np.float32)
    at = 0
    for t, n in enumerate(per):
        bstart[at: at + n] = row0[t] + 128 * np.arange(n)
        blen[at: at + n] = 128
        blen[at + n - 1] = rng.integers(1, 128)
        bweight[at: at + n] = rng.uniform(0.5, 6.0) * 1.7e-5
        at += n
    return d_docs, d_imp, live, bstart, blen, bweight


def test_first_pass_equals_the_flat_search_form_at_the_cells_size():
    from opensearch_tpu.search import compiler as C
    d_docs, d_imp, live, bstart, blen, bweight = cell_sized_plan(39)
    planes = [jax.device_put(a) for a in (d_docs, d_imp, live)]
    args = (*planes, bstart, blen, bweight, np.float32(1.0))
    want = jax.device_get(_flat_search_program(4096 * 128, 32)(*args))
    got = jax.device_get(C.build_impact_program(4096, 32, 16)(*args))
    assert int(got[2]) == int(want[2]) > 0
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[0], want[0])     # the same f32 sums
