"""Fused BM25 top-k Pallas kernel — the flagship device kernel, and the
PRODUCTION scorer for term/match queries (see search/fastpath.py).

Replaces Lucene's per-doc BulkScorer loop (reference
`search/query/QueryPhase.java` + BM25Similarity) with one fused TPU program
per query:

    HBM CSR postings ──async DMA──▶ VMEM [T, L] (docs, packed tf·dl)
      ─▶ decode + BM25 (VPU) ─▶ bitonic MERGE of T doc-sorted runs
      ─▶ shift-add dedup (runs ≤ T) ─▶ iterative top-k extraction
      ─▶ [K] (scores, doc_ids) per query

Why not XLA: on TPU, XLA `gather`, `scatter-add` and `sort` on this access
pattern each cost ~100ms for a 512-query batch (measured on v5e) — they
serialize or relayout. Everything here is DMA + dense VPU ops:

- The CSR gather is contiguous per term -> plain async DMA (posting rows are
  1024-element-aligned at build time so DMA slices are tile-aligned).
- Each term's DMA covers only ITS OWN pow2 bucket (static-size branches on a
  prefetched row count), not the batch-wide max — rare terms don't pay the
  frequent term's bandwidth.
- Postings carry (doc_id, tf·dl packed in one i32); BM25 is computed on the
  VPU with the SAME f32 expression the XLA path uses, so both paths are
  bit-identical per posting (no pre-rounded "eager impact" drift) and the
  avgdl collection statistic stays a query-time scalar.
- The per-term posting lists are ALREADY doc-sorted, so we need a merge
  network, not a sort: log2(n) compare-exchange stages, each a pair of
  `pltpu.roll`s + selects (strides >= 128 roll sublanes, < 128 roll lanes).
- Duplicate docs across terms form runs of length <= T in the merged order,
  so per-doc score sums are T-1 shifted adds — no segment scatter.
- top-k for k<=K_MAX is k rounds of (max-reduce, arg-select, mask), each a
  full-array VPU reduction.

All shapes are static per (T, L, K) bucket; the host picks L = pow2 of the
longest posting list among the query's terms (from host row pointers — no
device sync) so one compiled kernel serves all queries in that bucket.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT_SENTINEL = np.int32(2**31 - 1)
NEG_SENTINEL = np.int32(-2**31)
LANES = 128
# 1D HBM memrefs are tiled at 1024 elements (i32/f32): DMA slice starts and
# sizes must be 1024-aligned, so CSR rows are packed to this alignment
HBM_ALIGN = 1024
NEG_INF = float("-inf")


# ---------------------------------------------------------------------
# flattened [R, 128] helpers: rolls that emulate ops on the flat [R*128] order
# ---------------------------------------------------------------------

def _ids(shape):
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return rows, lanes


def _roll(x, shift: int, axis: int):
    """pltpu.roll with negative shifts normalized (it requires shift >= 0)."""
    n = x.shape[axis]
    return pltpu.roll(x, shift % n, axis)


def _cx(keys, payload, s: int):
    """One ascending compare-exchange stage at element stride `s` (partner =
    index XOR s) over the flattened [R,128] array. Moves `payload` with keys
    (a single array or a tuple of arrays, all selected by the same mask)."""
    single = not isinstance(payload, tuple)
    ps = (payload,) if single else payload
    shape = keys.shape
    rows, lanes = _ids(shape)
    if s >= LANES:
        r = s // LANES
        kf = _roll(keys, -r, 0)
        kb = _roll(keys, r, 0)
        pf = [_roll(p, -r, 0) for p in ps]
        pb = [_roll(p, r, 0) for p in ps]
        first = ((rows // r) % 2) == 0
    else:
        kf = _roll(keys, -s, 1)
        kb = _roll(keys, s, 1)
        pf = [_roll(p, -s, 1) for p in ps]
        pb = [_roll(p, s, 1) for p in ps]
        first = ((lanes // s) % 2) == 0
    nk = jnp.where(first, jnp.minimum(keys, kf), jnp.maximum(keys, kb))
    # NB: selecting between bool arrays with jnp.where trips a Mosaic i8->i1
    # truncation bug; keep predicates in pure i1 logic
    take_self = (first & (keys <= kf)) | ((~first) & (keys >= kb))
    nps = tuple(jnp.where(take_self, p, jnp.where(first, f, b))
                for p, f, b in zip(ps, pf, pb))
    return nk, (nps[0] if single else nps)


def _swap(x, s: int):
    """Unconditional exchange at element stride s (index XOR s)."""
    shape = x.shape
    rows, lanes = _ids(shape)
    if s >= LANES:
        r = s // LANES
        xf = _roll(x, -r, 0)
        xb = _roll(x, r, 0)
        first = ((rows // r) % 2) == 0
    else:
        xf = _roll(x, -s, 1)
        xb = _roll(x, s, 1)
        first = ((lanes // s) % 2) == 0
    return jnp.where(first, xf, xb)


def _block_flip(x, block: int):
    """Reverse every `block`-length run of the flattened order (index XOR
    (block-1)) by composing unconditional stride swaps over all bits."""
    s = 1
    while s < block:
        x = _swap(x, s)
        s *= 2
    return x


def _merge_pairs(keys, payload, half: int):
    """Merge adjacent sorted runs of length `half` into sorted runs of
    2*half (Batcher bitonic merge, ascending). `payload` may be one array
    or a tuple of arrays that all ride the same permutation."""
    single = not isinstance(payload, tuple)
    ps = (payload,) if single else payload
    kf = _block_flip(keys, 2 * half)
    pf = [_block_flip(p, 2 * half) for p in ps]
    rows, lanes = _ids(keys.shape)
    idx = rows * LANES + lanes
    first = (idx % (2 * half)) < half
    take_self = (first & (keys <= kf)) | ((~first) & (keys >= kf))
    nk = jnp.where(take_self, keys, kf)
    npay = tuple(jnp.where(take_self, p, f) for p, f in zip(ps, pf))
    s = half // 2
    while s >= 1:
        nk, npay = _cx(nk, npay, s)
        s //= 2
    return nk, (npay[0] if single else npay)


def _flat_shift_down(x, fill):
    """y[i] = x[i-1] over the flattened order (y[0] = fill)."""
    rows, lanes = _ids(x.shape)
    a = _roll(x, 1, 1)                      # lane l <- l-1 (lane0 wraps)
    b = _roll(_roll(x, 1, 0), 1, 1)         # row r-1, lane 127 at lane 0
    y = jnp.where(lanes == 0, b, a)
    return jnp.where((rows == 0) & (lanes == 0), fill, y)


def _flat_shift_up(x, fill):
    """y[i] = x[i+1] (y[last] = fill)."""
    rows, lanes = _ids(x.shape)
    nrows = x.shape[0]
    a = _roll(x, -1, 1)
    b = _roll(_roll(x, -1, 0), -1, 1)
    y = jnp.where(lanes == LANES - 1, b, a)
    return jnp.where((rows == nrows - 1) & (lanes == LANES - 1), fill, y)


# ---------------------------------------------------------------------
# production variant: packed (tf, dl) postings + per-term DMA buckets
# ---------------------------------------------------------------------

# tf and doc length packed losslessly into one i32 per posting:
#   packed = tf << DL_BITS | dl    (tf < 2^TF_BITS, dl < 2^DL_BITS)
# Segments violating the bounds (tf >= 2048 or a 2M-token doc) fall back to
# the XLA path — see search/fastpath.py.
TF_BITS = 11
DL_BITS = 21
DL_MASK = (1 << DL_BITS) - 1
TF_MAX = (1 << TF_BITS) - 1
DL_MAX = DL_MASK


def _bm25_tfdl_kernel(T: int, L: int, K: int, k1: float, b: float,
                      sizes: tuple,
                      rowstart_ref, nrows_ref, lens_ref, skips_ref,
                      weights_ref, msm_ref, avgdl_ref, dlo_ref, dhi_ref,
                      docs_hbm, tfdl_hbm, out_scores, out_docs, out_totals,
                      docs_v, tfdl_v, sems):
    q = pl.program_id(0)
    rows_per_term = L // LANES

    # ---- per-term DMA at the term's own pow2 bucket ----
    # `nrows_ref[t, q]` is the pow2 number of 128-lane rows this term needs
    # (0 = absent term, no DMA). DMA sizes must be static, so each size in
    # `sizes` is its own predicated start; rare terms move KBs while a
    # frequent term in the same query moves its full row — no shared max-L.
    for t in range(T):
        nr = nrows_ref[t, q]
        row_start = pl.multiple_of(rowstart_ref[t, q], HBM_ALIGN // LANES)
        for s in sizes:
            @pl.when(nr == s)
            def _(t=t, s=s, row_start=row_start):
                pltpu.make_async_copy(docs_hbm.at[pl.ds(row_start, s)],
                                      docs_v.at[t, pl.ds(0, s)],
                                      sems.at[2 * t]).start()
                pltpu.make_async_copy(tfdl_hbm.at[pl.ds(row_start, s)],
                                      tfdl_v.at[t, pl.ds(0, s)],
                                      sems.at[2 * t + 1]).start()
    for t in range(T):
        nr = nrows_ref[t, q]
        row_start = pl.multiple_of(rowstart_ref[t, q], HBM_ALIGN // LANES)
        for s in sizes:
            @pl.when(nr == s)
            def _(t=t, s=s, row_start=row_start):
                pltpu.make_async_copy(docs_hbm.at[pl.ds(row_start, s)],
                                      docs_v.at[t, pl.ds(0, s)],
                                      sems.at[2 * t]).wait()
                pltpu.make_async_copy(tfdl_hbm.at[pl.ds(row_start, s)],
                                      tfdl_v.at[t, pl.ds(0, s)],
                                      sems.at[2 * t + 1]).wait()

    # ---- decode + BM25 on the VPU (tails beyond each term's true length are
    # masked by position, so un-DMA'd scratch garbage never contributes) ----
    R = (T * L) // LANES
    docs2 = docs_v[:].reshape(R, LANES)
    tfdl2 = tfdl_v[:].reshape(R, LANES)
    rows, lanes = _ids((R, LANES))
    term_of_row = rows // rows_per_term
    pos_in_term = (rows % rows_per_term) * LANES + lanes

    w_row = jnp.zeros((R, LANES), jnp.float32)
    len_row = jnp.zeros((R, LANES), jnp.int32)
    skip_row = jnp.zeros((R, LANES), jnp.int32)
    for t in range(T):
        sel = term_of_row == t
        w_row = jnp.where(sel, weights_ref[t, q], w_row)
        len_row = jnp.where(sel, lens_ref[t, q], len_row)
        skip_row = jnp.where(sel, skips_ref[t, q], skip_row)
    # posting rows are 128-lane aligned; each DMA starts at the 1024-aligned
    # HBM block below the window, so `skip` masks the spilled-in prefix
    # (which may belong to the PREVIOUS row) positionally. Oversized rows
    # additionally split into [dlo, dhi) doc ranges. The merge network needs
    # each slot ASCENDING, so excluded-but-in-window docs below range map to
    # a NEGATIVE sentinel (front of the run, excluded at the end) — mapping
    # them to +sentinel would break sortedness and split dedup runs.
    dlo = dlo_ref[0, q]
    dhi = dhi_ref[0, q]
    in_pos = (pos_in_term >= skip_row) & (pos_in_term < skip_row + len_row)
    valid = in_pos & (docs2 >= dlo) & (docs2 < dhi)
    # the skip prefix must sort to the FRONT of the slot (NEG_SENTINEL):
    # +sentinel there would break the merge network's ascending-run
    # invariant, exactly like below-range docs in chunked windows
    is_prefix = pos_in_term < skip_row
    keys = jnp.where(is_prefix | (in_pos & (docs2 < dlo)), NEG_SENTINEL,
                     jnp.where(valid, docs2, INT_SENTINEL))

    # mask after the shift: tf >= 1024 sets the i32 sign bit and >> is
    # arithmetic (sign-extending)
    tf = ((tfdl2 >> DL_BITS) & TF_MAX).astype(jnp.float32)
    dl = (tfdl2 & DL_MASK).astype(jnp.float32)
    avgdl = avgdl_ref[0, q]
    # EXACTLY the XLA path's expression (ops/scoring.py posting_contrib,
    # SIM_BM25) so both paths agree bit-for-bit per posting
    k = k1 * (1.0 - b + b * dl / avgdl)
    contrib = jnp.where(valid, w_row * tf / (tf + k), 0.0)

    # ---- merge the T doc-sorted runs (each of length L) ----
    half = L
    while half < T * L:
        keys, contrib = _merge_pairs(keys, contrib, half)
        half *= 2

    # ---- dedup: runs of equal doc have length <= T ----
    score = contrib
    kk = keys
    cc = contrib
    count = jnp.ones((R, LANES), jnp.float32)
    for _ in range(T - 1):
        kk = _flat_shift_down(kk, INT_SENTINEL)
        cc = _flat_shift_down(cc, 0.0)
        eq = (kk == keys) & (keys < INT_SENTINEL)
        score = score + jnp.where(eq, cc, 0.0)
        count = count + jnp.where(eq, 1.0, 0.0)
    knext = _flat_shift_up(keys, INT_SENTINEL)
    is_last = (knext != keys) & (keys < INT_SENTINEL) & (keys > NEG_SENTINEL)
    msm = msm_ref[0, q]
    final = jnp.where(is_last & (count >= msm), score, NEG_INF)

    total = jnp.sum((final > NEG_INF).astype(jnp.int32))
    out_totals[q, :] = jnp.full((LANES,), total, jnp.int32)

    # ---- iterative top-K extraction ----
    acc_s = jnp.full((1, LANES), NEG_INF, jnp.float32)
    acc_d = jnp.full((1, LANES), -1, jnp.int32)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    for j in range(K):
        best = jnp.max(final)
        sel = final == best
        bdoc = jnp.min(jnp.where(sel, keys, INT_SENTINEL))
        got = best > NEG_INF
        best_or = jnp.where(got, best, NEG_INF)
        bdoc_or = jnp.where(got, bdoc, -1)
        hit = out_lane == j
        acc_s = jnp.where(hit, best_or, acc_s)
        acc_d = jnp.where(hit, bdoc_or, acc_d)
        final = jnp.where(sel & (keys == bdoc), NEG_INF, final)
    out_scores[q, :] = acc_s[0]
    out_docs[q, :] = acc_d[0]


@functools.partial(jax.jit, static_argnames=("T", "L", "K", "k1", "b"))
def fused_bm25_topk_tfdl(docs_hbm: jnp.ndarray, tfdl_hbm: jnp.ndarray,
                         rowstarts: jnp.ndarray, nrows: jnp.ndarray,
                         lens: jnp.ndarray, skips: jnp.ndarray,
                         weights: jnp.ndarray,
                         msm: jnp.ndarray, avgdl: jnp.ndarray,
                         dlo: jnp.ndarray, dhi: jnp.ndarray,
                         T: int, L: int, K: int, k1: float, b: float):
    """Batched fused BM25 top-k over packed (tf, dl) postings.

    docs_hbm  i32[P] — doc ids, CSR-flat, rows 128-lane aligned
    tfdl_hbm  i32[P] — tf << DL_BITS | dl per posting (lossless)
    rowstarts i32[QB, T] — DMA starts in 128-lane ROW units, 1024-element
              aligned (host aligns the window start DOWN to the HBM tile)
    nrows     i32[QB, T] — pow2 rows to DMA per term (0 = absent)
    lens      i32[QB, T] — true window posting counts (element units)
    skips     i32[QB, T] — spilled-in prefix length before the window
    weights   f32[QB, T] — query-time idf * boost
    msm       f32[QB, 1] — minimum matching terms
    avgdl     f32[QB, 1] — query-time average doc length scalar
    dlo/dhi   i32[QB, 1] — doc-id window [dlo, dhi) (0, INT_MAX = whole)
    k1, b     static similarity params (b already zeroed when norms are off)
    Returns (scores f32[QB, 128], doc_ids i32[QB, 128], totals i32[QB, 128]).
    """
    QB = rowstarts.shape[0]
    rowstarts = rowstarts.T
    nrows = nrows.T
    lens = lens.T
    skips = skips.T
    weights = weights.T
    msm = msm.T
    avgdl = avgdl.T
    dlo = dlo.T
    dhi = dhi.T
    assert docs_hbm.shape[0] % LANES == 0
    docs_hbm = docs_hbm.reshape(-1, LANES)
    tfdl_hbm = tfdl_hbm.reshape(-1, LANES)
    min_rows = HBM_ALIGN // LANES
    sizes = []
    s = min_rows
    while s <= L // LANES:
        sizes.append(s)
        s *= 2
    kernel = functools.partial(_bm25_tfdl_kernel, T, L, K, float(k1), float(b),
                               tuple(sizes))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(QB,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((T, L // LANES, LANES), jnp.int32),
            pltpu.VMEM((T, L // LANES, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2 * T,)),
        ],
    )
    out_shape = [
        jax.ShapeDtypeStruct((QB, LANES), jnp.float32),
        jax.ShapeDtypeStruct((QB, LANES), jnp.int32),
        jax.ShapeDtypeStruct((QB, LANES), jnp.int32),
    ]
    scores, doc_ids, totals = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        name="fused_bm25_topk_tfdl",
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
    )(rowstarts, nrows, lens, skips, weights, msm, avgdl, dlo, dhi,
      docs_hbm, tfdl_hbm)
    return scores, doc_ids, totals


# ---------------------------------------------------------------------
# bool/filtered variant: weighted-threshold clause semantics
# ---------------------------------------------------------------------
#
# Generalizes the tfdl kernel to Lucene BooleanQuery shapes (reference
# `search/BooleanScorer` / `ConjunctionDISI`): each slot carries a COUNT
# WEIGHT `cw` alongside its score weight, and a doc passes iff the summed
# count weight of its matching slots reaches `thresh`. With required slots
# (must / filter) at cw=REQ_W and optional slots (should, or the terms of
# one multi-term group) at cw=1, `thresh = REQ_W*n_required + msm` encodes
# "ALL required AND >= msm optional" exactly (REQ_W > max optional count,
# so optionals can never substitute for a missing required slot).
#
# Filters ride as one extra slot whose doc list comes from a SEPARATE HBM
# buffer (`filt_hbm`, built host-side from the cached dense filter mask of
# the XLA path — reference IndicesQueryCache bitsets) with score weight 0
# and cw=REQ_W: the same merge network that dedups scoring terms performs
# the filter intersection, so no per-doc gather is ever needed.
REQ_W = 1024.0


def _bm25_bool_kernel(TS: int, L: int, K: int, k1: float, b: float,
                      sizes: tuple, filtered: bool,
                      rowstart_ref, nrows_ref, lens_ref, skips_ref,
                      weights_ref,
                      cw_ref, thresh_ref, avgdl_ref, dlo_ref, dhi_ref,
                      docs_hbm, tfdl_hbm, filt_hbm,
                      out_scores, out_docs, out_totals,
                      docs_v, tfdl_v, sems):
    q = pl.program_id(0)
    T = 2 * TS if filtered else TS
    rows_per_term = L // LANES

    # ---- per-slot DMA at the slot's own pow2 bucket ----
    # term slots [0, TS) move (docs, tfdl) from the postings buffers; the
    # filter slot TS (when present) moves docs only, from filt_hbm. Slots
    # with nrows=0 (absent term / dead padding) match no size branch -> no
    # DMA, and their VMEM garbage is masked below by len_row=0.
    for t in range(TS):
        nr = nrows_ref[t, q]
        row_start = pl.multiple_of(rowstart_ref[t, q], HBM_ALIGN // LANES)
        for s in sizes:
            @pl.when(nr == s)
            def _(t=t, s=s, row_start=row_start):
                pltpu.make_async_copy(docs_hbm.at[pl.ds(row_start, s)],
                                      docs_v.at[t, pl.ds(0, s)],
                                      sems.at[2 * t]).start()
                pltpu.make_async_copy(tfdl_hbm.at[pl.ds(row_start, s)],
                                      tfdl_v.at[t, pl.ds(0, s)],
                                      sems.at[2 * t + 1]).start()
    if filtered:
        nr = nrows_ref[TS, q]
        row_start = pl.multiple_of(rowstart_ref[TS, q], HBM_ALIGN // LANES)
        for s in sizes:
            @pl.when(nr == s)
            def _(s=s, row_start=row_start):
                pltpu.make_async_copy(filt_hbm.at[pl.ds(row_start, s)],
                                      docs_v.at[TS, pl.ds(0, s)],
                                      sems.at[2 * TS]).start()
    for t in range(TS):
        nr = nrows_ref[t, q]
        row_start = pl.multiple_of(rowstart_ref[t, q], HBM_ALIGN // LANES)
        for s in sizes:
            @pl.when(nr == s)
            def _(t=t, s=s, row_start=row_start):
                pltpu.make_async_copy(docs_hbm.at[pl.ds(row_start, s)],
                                      docs_v.at[t, pl.ds(0, s)],
                                      sems.at[2 * t]).wait()
                pltpu.make_async_copy(tfdl_hbm.at[pl.ds(row_start, s)],
                                      tfdl_v.at[t, pl.ds(0, s)],
                                      sems.at[2 * t + 1]).wait()
    if filtered:
        nr = nrows_ref[TS, q]
        row_start = pl.multiple_of(rowstart_ref[TS, q], HBM_ALIGN // LANES)
        for s in sizes:
            @pl.when(nr == s)
            def _(s=s, row_start=row_start):
                pltpu.make_async_copy(filt_hbm.at[pl.ds(row_start, s)],
                                      docs_v.at[TS, pl.ds(0, s)],
                                      sems.at[2 * TS]).wait()

    # ---- decode + BM25 + per-slot count weights ----
    R = (T * L) // LANES
    docs2 = docs_v[:].reshape(R, LANES)
    tfdl2 = tfdl_v[:].reshape(R, LANES)
    rows, lanes = _ids((R, LANES))
    term_of_row = rows // rows_per_term
    pos_in_term = (rows % rows_per_term) * LANES + lanes

    w_row = jnp.zeros((R, LANES), jnp.float32)
    len_row = jnp.zeros((R, LANES), jnp.int32)
    skip_row = jnp.zeros((R, LANES), jnp.int32)
    cw_row = jnp.zeros((R, LANES), jnp.float32)
    for t in range(T):
        sel = term_of_row == t
        len_row = jnp.where(sel, lens_ref[t, q], len_row)
        skip_row = jnp.where(sel, skips_ref[t, q], skip_row)
        cw_row = jnp.where(sel, cw_ref[t, q], cw_row)
        if t < TS:
            w_row = jnp.where(sel, weights_ref[t, q], w_row)
    dlo = dlo_ref[0, q]
    dhi = dhi_ref[0, q]
    in_pos = (pos_in_term >= skip_row) & (pos_in_term < skip_row + len_row)
    valid = in_pos & (docs2 >= dlo) & (docs2 < dhi)
    # the skip prefix must sort to the FRONT of the slot (NEG_SENTINEL):
    # +sentinel there would break the merge network's ascending-run
    # invariant, exactly like below-range docs in chunked windows
    is_prefix = pos_in_term < skip_row
    keys = jnp.where(is_prefix | (in_pos & (docs2 < dlo)), NEG_SENTINEL,
                     jnp.where(valid, docs2, INT_SENTINEL))

    tf = ((tfdl2 >> DL_BITS) & TF_MAX).astype(jnp.float32)
    dl = (tfdl2 & DL_MASK).astype(jnp.float32)
    avgdl = avgdl_ref[0, q]
    kd = k1 * (1.0 - b + b * dl / avgdl)
    # filter-slot rows score 0 (their tfdl scratch is never DMA'd garbage)
    is_term = term_of_row < TS
    contrib = jnp.where(valid & is_term, w_row * tf / (tf + kd), 0.0)
    cw = jnp.where(valid, cw_row, 0.0)

    # ---- merge the T doc-sorted runs, carrying (score, count-weight) ----
    half = L
    payload = (contrib, cw)
    while half < T * L:
        keys, payload = _merge_pairs(keys, payload, half)
        half *= 2
    contrib, cw = payload

    # ---- dedup: runs of equal doc have length <= T ----
    score = contrib
    cnt = cw
    kk = keys
    cc = contrib
    aa = cw
    for _ in range(T - 1):
        kk = _flat_shift_down(kk, INT_SENTINEL)
        cc = _flat_shift_down(cc, 0.0)
        aa = _flat_shift_down(aa, 0.0)
        eq = (kk == keys) & (keys < INT_SENTINEL)
        score = score + jnp.where(eq, cc, 0.0)
        cnt = cnt + jnp.where(eq, aa, 0.0)
    knext = _flat_shift_up(keys, INT_SENTINEL)
    is_last = (knext != keys) & (keys < INT_SENTINEL) & (keys > NEG_SENTINEL)
    final = jnp.where(is_last & (cnt >= thresh_ref[0, q]), score, NEG_INF)

    total = jnp.sum((final > NEG_INF).astype(jnp.int32))
    out_totals[q, :] = jnp.full((LANES,), total, jnp.int32)

    # ---- iterative top-K extraction ----
    acc_s = jnp.full((1, LANES), NEG_INF, jnp.float32)
    acc_d = jnp.full((1, LANES), -1, jnp.int32)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    for j in range(K):
        best = jnp.max(final)
        sel = final == best
        bdoc = jnp.min(jnp.where(sel, keys, INT_SENTINEL))
        got = best > NEG_INF
        best_or = jnp.where(got, best, NEG_INF)
        bdoc_or = jnp.where(got, bdoc, -1)
        hit = out_lane == j
        acc_s = jnp.where(hit, best_or, acc_s)
        acc_d = jnp.where(hit, bdoc_or, acc_d)
        final = jnp.where(sel & (keys == bdoc), NEG_INF, final)
    out_scores[q, :] = acc_s[0]
    out_docs[q, :] = acc_d[0]


@functools.partial(jax.jit,
                   static_argnames=("TS", "L", "K", "k1", "b", "filtered"))
def fused_bm25_bool_topk(docs_hbm: jnp.ndarray, tfdl_hbm: jnp.ndarray,
                         filt_hbm: jnp.ndarray,
                         rowstarts: jnp.ndarray, nrows: jnp.ndarray,
                         lens: jnp.ndarray, skips: jnp.ndarray,
                         weights: jnp.ndarray,
                         cw: jnp.ndarray, thresh: jnp.ndarray,
                         avgdl: jnp.ndarray, dlo: jnp.ndarray,
                         dhi: jnp.ndarray,
                         TS: int, L: int, K: int, k1: float, b: float,
                         filtered: bool):
    """Batched fused bool/filtered BM25 top-k.

    Slots [0, TS) are scoring terms over (docs_hbm, tfdl_hbm); when
    `filtered`, slot TS is the filter doc list in filt_hbm (i32[Pf], rows
    1024-aligned, INT_SENTINEL padded) and slots (TS, 2*TS) are dead
    padding (nrows=0). Per-query arrays are [QB, T] (T = 2*TS when
    filtered else TS) except weights [QB, TS] and thresh/avgdl/dlo/dhi
    [QB, 1]. `cw` carries per-slot count weights (REQ_W required / 1.0
    optional / 0 dead); a doc passes when its summed cw >= thresh.
    Returns (scores f32[QB, 128], doc_ids i32[QB, 128], totals i32[QB, 128]).
    """
    QB = rowstarts.shape[0]
    rowstarts = rowstarts.T
    nrows = nrows.T
    lens = lens.T
    skips = skips.T
    weights = weights.T
    cw = cw.T
    thresh = thresh.T
    avgdl = avgdl.T
    dlo = dlo.T
    dhi = dhi.T
    T = 2 * TS if filtered else TS
    assert docs_hbm.shape[0] % LANES == 0
    assert filt_hbm.shape[0] % LANES == 0
    docs_hbm = docs_hbm.reshape(-1, LANES)
    tfdl_hbm = tfdl_hbm.reshape(-1, LANES)
    filt_hbm = filt_hbm.reshape(-1, LANES)
    min_rows = HBM_ALIGN // LANES
    sizes = []
    s = min_rows
    while s <= L // LANES:
        sizes.append(s)
        s *= 2
    kernel = functools.partial(_bm25_bool_kernel, TS, L, K, float(k1),
                               float(b), tuple(sizes), bool(filtered))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=10,
        grid=(QB,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((T, L // LANES, LANES), jnp.int32),
            pltpu.VMEM((T, L // LANES, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2 * T,)),
        ],
    )
    out_shape = [
        jax.ShapeDtypeStruct((QB, LANES), jnp.float32),
        jax.ShapeDtypeStruct((QB, LANES), jnp.int32),
        jax.ShapeDtypeStruct((QB, LANES), jnp.int32),
    ]
    scores, doc_ids, totals = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        name="fused_bm25_bool_topk",
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
    )(rowstarts, nrows, lens, skips, weights, cw, thresh, avgdl, dlo, dhi,
      docs_hbm, tfdl_hbm, filt_hbm)
    return scores, doc_ids, totals


# ---------------------------------------------------------------------
# codec-v2 variant: quantized eager impacts (BM25S), no per-posting math
# ---------------------------------------------------------------------
#
# The tfdl kernel spends VPU work per posting on the BM25 saturation
# (shift/mask decode + div) and needs avgdl/k1/b per query. With codec v2
# (index/segment.py ImpactPlane) the saturation was evaluated at index
# time: the posting payload is the quantized impact held in an i32 lane
# (the HBM 1D tiling is i32-granular; the u8/u16 density win belongs to
# the XLA path's resident planes), and the per-posting math collapses to
# ONE multiply by a weight that folds idf·boost·scale. Block-max skipping
# happens where the DMA windows are planned: the HOST prices each
# IMPACT_BLOCK run off the plane's block-max sidecar (exact in the
# quantized domain) and passes only the kept, compacted windows through
# rowstarts/nrows/lens/skips — a skipped block never leaves HBM, the same
# contract as the impact-ordered head regions. Exactness of served pages
# stays with the fastpath verify ladder: results of this kernel are
# candidate partials whose certification must add the caller's
# quantization-error margin (ImpactPlane.quant_err/drift_bound) to the
# unseen-doc bound.


def _bm25_impact_kernel(T: int, L: int, K: int, sizes: tuple,
                        rowstart_ref, nrows_ref, lens_ref, skips_ref,
                        weights_ref, msm_ref, dlo_ref, dhi_ref,
                        docs_hbm, imp_hbm, out_scores, out_docs, out_totals,
                        docs_v, imp_v, sems):
    q = pl.program_id(0)
    rows_per_term = L // LANES

    for t in range(T):
        nr = nrows_ref[t, q]
        row_start = pl.multiple_of(rowstart_ref[t, q], HBM_ALIGN // LANES)
        for s in sizes:
            @pl.when(nr == s)
            def _(t=t, s=s, row_start=row_start):
                pltpu.make_async_copy(docs_hbm.at[pl.ds(row_start, s)],
                                      docs_v.at[t, pl.ds(0, s)],
                                      sems.at[2 * t]).start()
                pltpu.make_async_copy(imp_hbm.at[pl.ds(row_start, s)],
                                      imp_v.at[t, pl.ds(0, s)],
                                      sems.at[2 * t + 1]).start()
    for t in range(T):
        nr = nrows_ref[t, q]
        row_start = pl.multiple_of(rowstart_ref[t, q], HBM_ALIGN // LANES)
        for s in sizes:
            @pl.when(nr == s)
            def _(t=t, s=s, row_start=row_start):
                pltpu.make_async_copy(docs_hbm.at[pl.ds(row_start, s)],
                                      docs_v.at[t, pl.ds(0, s)],
                                      sems.at[2 * t]).wait()
                pltpu.make_async_copy(imp_hbm.at[pl.ds(row_start, s)],
                                      imp_v.at[t, pl.ds(0, s)],
                                      sems.at[2 * t + 1]).wait()

    R = (T * L) // LANES
    docs2 = docs_v[:].reshape(R, LANES)
    imp2 = imp_v[:].reshape(R, LANES)
    rows, lanes = _ids((R, LANES))
    term_of_row = rows // rows_per_term
    pos_in_term = (rows % rows_per_term) * LANES + lanes

    w_row = jnp.zeros((R, LANES), jnp.float32)
    len_row = jnp.zeros((R, LANES), jnp.int32)
    skip_row = jnp.zeros((R, LANES), jnp.int32)
    for t in range(T):
        sel = term_of_row == t
        w_row = jnp.where(sel, weights_ref[t, q], w_row)
        len_row = jnp.where(sel, lens_ref[t, q], len_row)
        skip_row = jnp.where(sel, skips_ref[t, q], skip_row)
    dlo = dlo_ref[0, q]
    dhi = dhi_ref[0, q]
    in_pos = (pos_in_term >= skip_row) & (pos_in_term < skip_row + len_row)
    valid = in_pos & (docs2 >= dlo) & (docs2 < dhi)
    is_prefix = pos_in_term < skip_row
    keys = jnp.where(is_prefix | (in_pos & (docs2 < dlo)), NEG_SENTINEL,
                     jnp.where(valid, docs2, INT_SENTINEL))

    # the WHOLE per-posting score: one multiply (weights fold
    # idf·boost·scale — the designated dequant shape, oslint OSL507)
    contrib = jnp.where(valid, w_row * imp2.astype(jnp.float32), 0.0)

    half = L
    while half < T * L:
        keys, contrib = _merge_pairs(keys, contrib, half)
        half *= 2

    score = contrib
    kk = keys
    cc = contrib
    count = jnp.ones((R, LANES), jnp.float32)
    for _ in range(T - 1):
        kk = _flat_shift_down(kk, INT_SENTINEL)
        cc = _flat_shift_down(cc, 0.0)
        eq = (kk == keys) & (keys < INT_SENTINEL)
        score = score + jnp.where(eq, cc, 0.0)
        count = count + jnp.where(eq, 1.0, 0.0)
    knext = _flat_shift_up(keys, INT_SENTINEL)
    is_last = (knext != keys) & (keys < INT_SENTINEL) & (keys > NEG_SENTINEL)
    msm = msm_ref[0, q]
    final = jnp.where(is_last & (count >= msm), score, NEG_INF)

    total = jnp.sum((final > NEG_INF).astype(jnp.int32))
    out_totals[q, :] = jnp.full((LANES,), total, jnp.int32)

    acc_s = jnp.full((1, LANES), NEG_INF, jnp.float32)
    acc_d = jnp.full((1, LANES), -1, jnp.int32)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    for j in range(K):
        best = jnp.max(final)
        sel = final == best
        bdoc = jnp.min(jnp.where(sel, keys, INT_SENTINEL))
        got = best > NEG_INF
        best_or = jnp.where(got, best, NEG_INF)
        bdoc_or = jnp.where(got, bdoc, -1)
        hit = out_lane == j
        acc_s = jnp.where(hit, best_or, acc_s)
        acc_d = jnp.where(hit, bdoc_or, acc_d)
        final = jnp.where(sel & (keys == bdoc), NEG_INF, final)
    out_scores[q, :] = acc_s[0]
    out_docs[q, :] = acc_d[0]


@functools.partial(jax.jit, static_argnames=("T", "L", "K"))
def fused_bm25_topk_impact(docs_hbm: jnp.ndarray, imp_hbm: jnp.ndarray,
                           rowstarts: jnp.ndarray, nrows: jnp.ndarray,
                           lens: jnp.ndarray, skips: jnp.ndarray,
                           weights: jnp.ndarray, msm: jnp.ndarray,
                           dlo: jnp.ndarray, dhi: jnp.ndarray,
                           T: int, L: int, K: int):
    """Batched fused top-k over codec-v2 quantized impacts.

    docs_hbm  i32[P] — doc ids, CSR-flat, rows 128-lane aligned
    imp_hbm   i32[P] — quantized impact per posting (u8/u16 widened to
              the i32 HBM lane granularity)
    weights   f32[QB, T] — idf · boost · plane scale, folded on host
    (rowstarts/nrows/lens/skips/msm/dlo/dhi as in fused_bm25_topk_tfdl;
    the host's block-max prune compacts skipped blocks OUT of these
    windows.) No similarity statics: the kernel is one multiply per
    posting, and one compiled (T, L, K) variant serves every similarity
    the plane was built under.
    Returns (scores f32[QB, 128], doc_ids i32[QB, 128], totals)."""
    QB = rowstarts.shape[0]
    rowstarts = rowstarts.T
    nrows = nrows.T
    lens = lens.T
    skips = skips.T
    weights = weights.T
    msm = msm.T
    dlo = dlo.T
    dhi = dhi.T
    assert docs_hbm.shape[0] % LANES == 0
    docs_hbm = docs_hbm.reshape(-1, LANES)
    imp_hbm = imp_hbm.reshape(-1, LANES)
    min_rows = HBM_ALIGN // LANES
    sizes = []
    s = min_rows
    while s <= L // LANES:
        sizes.append(s)
        s *= 2
    kernel = functools.partial(_bm25_impact_kernel, T, L, K, tuple(sizes))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(QB,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((T, L // LANES, LANES), jnp.int32),
            pltpu.VMEM((T, L // LANES, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2 * T,)),
        ],
    )
    out_shape = [
        jax.ShapeDtypeStruct((QB, LANES), jnp.float32),
        jax.ShapeDtypeStruct((QB, LANES), jnp.int32),
        jax.ShapeDtypeStruct((QB, LANES), jnp.int32),
    ]
    scores, doc_ids, totals = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        name="fused_bm25_topk_impact",
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
    )(rowstarts, nrows, lens, skips, weights, msm, dlo, dhi,
      docs_hbm, imp_hbm)
    return scores, doc_ids, totals


def align_csr_rows(starts: np.ndarray, doc_ids: np.ndarray, *vals: np.ndarray,
                   margin: int, alignment: int = HBM_ALIGN):
    """Re-pack CSR postings so every row begins at a 128-aligned offset
    (sentinel-padded gaps), with `margin` sentinel slack at the end so a
    fixed-size DMA window never runs off the buffer. Returns
    (new_starts i64[nrows+1 -> aligned row starts], docs, *aligned vals) —
    each extra `vals` array (tfs, impacts, per-posting dl, ...) is scattered
    to the same aligned layout with zero fill."""
    nrows = len(starts) - 1
    lens = np.diff(starts)
    aligned_lens = ((lens + alignment - 1) // alignment) * alignment
    new_starts = np.zeros(nrows + 1, dtype=np.int64)
    np.cumsum(aligned_lens, out=new_starts[1:])
    total = int(new_starts[-1]) + margin
    total = ((total + LANES - 1) // LANES) * LANES
    new_docs = np.full(total, INT_SENTINEL, dtype=np.int32)
    # vectorized row scatter
    src_idx = np.arange(len(doc_ids), dtype=np.int64)
    row_of = np.searchsorted(starts, src_idx, side="right") - 1
    offset_in_row = src_idx - starts[row_of]
    dst = new_starts[row_of] + offset_in_row
    new_docs[dst] = doc_ids
    out_vals = []
    for v in vals:
        nv = np.zeros(total, dtype=v.dtype)
        nv[dst] = v
        out_vals.append(nv)
    return (new_starts, new_docs, *out_vals)
