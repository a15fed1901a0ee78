"""Device-side scoring primitives: the TPU replacement for Lucene's per-doc
scoring loop (reference: `search/query/QueryPhase.java` driving Lucene's
BulkScorer + BM25Similarity).

The shape of the computation, per (segment, query term group):

    rows ──starts──▶ (row_start, row_len) ──flat iota + searchsorted──▶
    flat gather of (doc_id, tf) ──VPU: sim formula──▶ contrib ──scatter-add──▶
    dense scores[ndocs_pad] ──▶ combinators (masks) ──▶ fused top-k

All shapes are static: the flat gather width `bucket` is a power-of-two chosen
on the host from the *host* row pointers (no device sync), and segment arrays
are pow2-padded (see segment.py), so XLA compiles a handful of kernels that
get reused across queries and segments. The `searchsorted` there is over the
`T` <= 16 row lengths of one term group (`gather_postings`,
`gather_docs_only`). The codec-v2 impact pass searches nothing and gathers no
element alone: its unit is the fixed-width posting block, so
`gather_impact_blocks` reads one row of IMPACT_BLOCK slots a kept block (a
slot's block is its row index), each as the two rows of the planes'
[P / 128, 128] view that its window lies in:

    kept blocks (bstart, blen, bweight)[B_pad] ──two row reads a block,
    a select a lane──▶ (doc_id, quantized impact)[B_pad, 128]
    ──one dequant multiply──▶ contrib ──row-major scatter-add──▶
    dense scores[ndocs_pad] ──▶ masked top-C

Scatter-adds here are the analog of Lucene accumulating scores doc-at-a-time;
on TPU they run at HBM bandwidth over the whole posting block at once.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = np.float32(-np.inf)  # numpy, not jnp: a module-level jax.Array
# becomes a device-resident trace constant that the jit fast path can hoist
# into an extra executable parameter (buffer-count mismatch on cache hits)

# similarity ids (static switch inside traced code)
SIM_BM25 = 0
SIM_CLASSIC = 1      # Lucene ClassicSimilarity (TF-IDF)
SIM_BOOLEAN = 2
SIM_LM_DIRICHLET = 3


class ScoredMask(NamedTuple):
    """Dense per-doc (scores, match_count) pair — every query node evaluates
    to one of these; `count` is the number of matching leaf terms (drives
    minimum_should_match and must semantics)."""

    scores: jnp.ndarray   # f32[ndocs_pad]
    count: jnp.ndarray    # f32[ndocs_pad]

    @property
    def matched(self) -> jnp.ndarray:
        return self.count > 0


def gather_postings(starts: jnp.ndarray, doc_ids: jnp.ndarray, tfs: jnp.ndarray,
                    rows: jnp.ndarray, bucket: int):
    """Flatten the postings of `rows` (i32[T], -1 = term absent) into static
    width `bucket`. Returns (docs i32[B], tf f32[B], term_idx i32[B],
    valid bool[B])."""
    nrows_pad = starts.shape[0]
    # absent terms -> the guaranteed-empty padding row (start == end == P)
    rows = jnp.where(rows < 0, nrows_pad - 2, rows)
    row_start = starts[rows]
    row_end = starts[rows + 1]
    lens = row_end - row_start
    cum = jnp.cumsum(lens)
    total = cum[-1]
    i = jnp.arange(bucket, dtype=jnp.int32)
    term_idx = jnp.searchsorted(cum, i, side="right").astype(jnp.int32)
    term_idx = jnp.minimum(term_idx, rows.shape[0] - 1)
    prev = jnp.where(term_idx > 0, cum[jnp.maximum(term_idx - 1, 0)], 0)
    src = row_start[term_idx] + (i - prev)
    valid = i < total
    src = jnp.clip(src, 0, doc_ids.shape[0] - 1)
    docs = jnp.where(valid, doc_ids[src], jnp.int32(2**31 - 1))
    tf = jnp.where(valid, tfs[src], 0.0)
    return docs, tf, term_idx, valid


def posting_contrib(sim_id: int, tf, dl, weight, aux, k1: float, b: float, avgdl):
    """Per-posting score contribution under similarity `sim_id` (static).

    BM25 follows modern Lucene BM25Similarity (no (k1+1) factor, LUCENE-8563):
        idf * tf / (tf + k1*(1 - b + b*dl/avgdl))
    classic follows ClassicSimilarity: idf^2 * sqrt(tf) * 1/sqrt(dl) * boost
    (idf^2 because weight already folds one idf and queryNorm is gone).
    lm_dirichlet: log(1 + tf/(mu*p_c)) + log(mu/(dl+mu)), aux = p_c, k1 = mu.
    """
    if sim_id == SIM_BM25:
        k = k1 * (1.0 - b + b * dl / avgdl)
        return weight * tf / (tf + k)
    if sim_id == SIM_CLASSIC:
        inv_sqrt_dl = jnp.where(dl > 0, jax.lax.rsqrt(jnp.maximum(dl, 1.0)), 1.0)
        return weight * jnp.sqrt(tf) * inv_sqrt_dl
    if sim_id == SIM_BOOLEAN:
        return weight * jnp.ones_like(tf)
    if sim_id == SIM_LM_DIRICHLET:
        mu = k1
        core = jnp.log1p(tf / (mu * jnp.maximum(aux, 1e-12)))
        norm = jnp.log(mu / (dl + mu))
        return weight * (core + norm)
    raise ValueError(f"unknown sim_id {sim_id}")


def score_term_group(field_arrays: dict, dl: jnp.ndarray, live: jnp.ndarray,
                     rows: jnp.ndarray, weights: jnp.ndarray, aux: jnp.ndarray,
                     bucket: int, ndocs_pad: int, sim_id: int,
                     k1: float, b: float, avgdl) -> ScoredMask:
    """Score one group of weighted terms over a segment field: the fused
    gather→VPU→scatter pass. Returns dense (scores, term-match counts)."""
    docs, tf, term_idx, valid = gather_postings(
        field_arrays["starts"], field_arrays["doc_ids"], field_arrays["tfs"], rows, bucket)
    dsafe = jnp.minimum(docs, ndocs_pad - 1)
    dl_g = dl[dsafe]
    w = weights[term_idx]
    a = aux[term_idx]
    contrib = posting_contrib(sim_id, tf, dl_g, w, a, k1, b, avgdl)
    contrib = jnp.where(valid, contrib, 0.0)
    scores = jnp.zeros(ndocs_pad, jnp.float32).at[docs].add(contrib, mode="drop")
    counts = jnp.zeros(ndocs_pad, jnp.float32).at[docs].add(
        jnp.where(valid & (tf > 0), 1.0, 0.0), mode="drop")
    live_ok = live > 0
    return ScoredMask(jnp.where(live_ok, scores, 0.0), jnp.where(live_ok, counts, 0.0))


# ---------------- codec v2: quantized-impact domain ----------------
#
# u8/u16 impact planes may only enter f32 score math through these two
# designated dequant helpers (oslint OSL507): the quantized domain is
# where block-max prune compares stay exact, and every implicit
# int->float promotion outside the helpers is a bound the serve
# certificates don't know about.


def dequant_impact(q: jnp.ndarray, scale) -> jnp.ndarray:
    """THE device-side dequantizer: quantized impact plane -> f32 score
    contributions. `scale` may be a scalar (the plane's global scale) or
    a broadcastable array with weights pre-folded in."""
    return q.astype(jnp.float32) * scale


def dequant_impact_np(q, scale):
    """Host mirror of `dequant_impact` (planning bounds, head
    selection)."""
    return np.asarray(q).astype(np.float32) * np.float32(scale)


def gather_impact_blocks(doc_ids: jnp.ndarray, impacts: jnp.ndarray,
                         bstart: jnp.ndarray, blen: jnp.ndarray,
                         block: int):
    """Read explicit posting-block windows [bstart_b, bstart_b+blen_b),
    blen_b <= `block`, as one row of `block` slots each — the
    block-granular analog of `gather_postings` for the codec-v2 impact
    path, where the host's block-max prune selects WHICH blocks are
    gathered at all (skipped blocks never move bytes). A slot's block is
    its row, so nothing is searched, and no element is gathered alone:
    the planes are viewed as [P / block, block] (the 1-D tile itself),
    a window starting at lane `off` of plane row `r` lies in rows `r` and
    `r + 1`, and slot (b, l) takes lane `l` of whichever of the two holds
    a posting of the window there (row `r` where `l >= off`). The window
    arrives rotated by `off`: slot (b, l) is posting `(l - off) mod block`
    of block b, a posting where that is under `blen[b]`. Blocks keep the
    plan's order; inside a block the rotation is free, because a block's
    postings are one term's and so distinct documents: every document
    still meets its contributions block after block.
    Returns (docs i32[B, block], iq uint[B, block], valid bool[B, block]);
    a slot that is no posting reads docs 2**31-1, iq 0. iq stays in the
    quantized integer domain — callers dequantize via `dequant_impact`."""
    nrows = max(2, -(-doc_ids.shape[0] // block))
    pad = nrows * block - doc_ids.shape[0]
    if pad:     # a plane of under two rows (segment planes are pow2-padded)
        doc_ids, impacts = jnp.pad(doc_ids, (0, pad)), jnp.pad(impacts,
                                                               (0, pad))
    r = bstart // block
    off = (bstart - r * block)[:, None]
    r_next = jnp.minimum(r + 1, nrows - 1)
    lane = jnp.arange(block, dtype=jnp.int32)[None, :]
    in_first = lane >= off
    nth = jnp.where(in_first, lane - off, lane - off + block)
    valid = nth < blen[:, None]

    def window(plane):
        rows = plane.reshape(nrows, block)
        return jnp.where(in_first, rows[r], rows[r_next])
    docs = jnp.where(valid, window(doc_ids), jnp.int32(2**31 - 1))
    iq = jnp.where(valid, window(impacts), 0)
    return docs, iq, valid


def impact_score_blocks(doc_ids: jnp.ndarray, impacts: jnp.ndarray,
                        live: jnp.ndarray, bstart: jnp.ndarray,
                        blen: jnp.ndarray, bweight: jnp.ndarray,
                        block: int, ndocs_pad: int) -> ScoredMask:
    """The codec-v2 eager hot loop: gather quantized impacts over the
    kept blocks (one row of `block` slots a block), one dequant multiply
    (weight·scale pre-folded per block on the host, broadcast along the
    row), scatter-add in row-major order. NO per-posting tf/doclen math —
    the BM25 saturation was evaluated at index time (BM25S eager
    scoring). Counts are exact for the gathered blocks: postings
    partition (term, doc) pairs, so counting postings counts matching
    terms."""
    with jax.named_scope("impact.gather"):
        docs, iq, valid = gather_impact_blocks(doc_ids, impacts,
                                               bstart, blen, block)
        contrib = jnp.where(valid, dequant_impact(iq, bweight[:, None]),
                            0.0)
        docs, contrib, valid = (docs.reshape(-1), contrib.reshape(-1),
                                valid.reshape(-1))
    with jax.named_scope("impact.accumulate"):
        scores = jnp.zeros(ndocs_pad, jnp.float32).at[docs].add(
            contrib, mode="drop")
        counts = jnp.zeros(ndocs_pad, jnp.float32).at[docs].add(
            jnp.where(valid, 1.0, 0.0), mode="drop")
        live_ok = live > 0
        return ScoredMask(jnp.where(live_ok, scores, 0.0),
                          jnp.where(live_ok, counts, 0.0))


def gather_docs_only(starts: jnp.ndarray, doc_ids: jnp.ndarray,
                     rows: jnp.ndarray, bucket: int):
    """`gather_postings` without the tf plane: (docs, valid) only. The
    codec-v2 layout has no resident f32 tfs, and non-scoring consumers
    (filter masks) never needed them — a real posting always has tf>0."""
    nrows_pad = starts.shape[0]
    rows = jnp.where(rows < 0, nrows_pad - 2, rows)
    row_start = starts[rows]
    row_end = starts[rows + 1]
    lens = row_end - row_start
    cum = jnp.cumsum(lens)
    total = cum[-1]
    i = jnp.arange(bucket, dtype=jnp.int32)
    term_idx = jnp.searchsorted(cum, i, side="right").astype(jnp.int32)
    term_idx = jnp.minimum(term_idx, rows.shape[0] - 1)
    prev = jnp.where(term_idx > 0, cum[jnp.maximum(term_idx - 1, 0)], 0)
    src = row_start[term_idx] + (i - prev)
    valid = i < total
    src = jnp.clip(src, 0, doc_ids.shape[0] - 1)
    docs = jnp.where(valid, doc_ids[src], jnp.int32(2**31 - 1))
    return docs, valid


def term_match_mask(field_arrays: dict, live: jnp.ndarray,
                    rows: jnp.ndarray, bucket: int,
                    ndocs_pad: int) -> jnp.ndarray:
    """Non-scoring terms filter over the codec-v2 layout: identical
    semantics to `term_filter_mask` (every real posting has tf > 0) with
    no tf plane touched — 4 bytes gathered per slot instead of 8."""
    docs, valid = gather_docs_only(field_arrays["starts"],
                                   field_arrays["doc_ids"], rows, bucket)
    hits = jnp.zeros(ndocs_pad, jnp.float32).at[docs].add(
        jnp.where(valid, 1.0, 0.0), mode="drop")
    return (hits > 0) & (live > 0)


def gather_tf_dense(field_arrays: dict, rows: jnp.ndarray, bucket: int,
                    ndocs_pad: int, t_pad: int) -> jnp.ndarray:
    """Per-term dense raw term frequencies: f32[t_pad, ndocs_pad].
    combined_fields (BM25F) needs tf BEFORE saturation so fields can be
    weighted and summed; one flat scatter builds all T rows at once."""
    docs, tf, term_idx, valid = gather_postings(
        field_arrays["starts"], field_arrays["doc_ids"], field_arrays["tfs"],
        rows, bucket)
    # clamp BEFORE the flat-index multiply: sentinel doc ids would overflow
    dsafe = jnp.clip(docs, 0, ndocs_pad - 1)
    flat = jnp.where(valid, term_idx * ndocs_pad + dsafe,
                     t_pad * ndocs_pad)   # OOB -> dropped
    out = jnp.zeros(t_pad * ndocs_pad, jnp.float32).at[flat].add(
        jnp.where(valid, tf, 0.0), mode="drop")
    return out.reshape(t_pad, ndocs_pad)


def term_filter_mask(field_arrays: dict, live: jnp.ndarray, rows: jnp.ndarray,
                     bucket: int, ndocs_pad: int) -> jnp.ndarray:
    """Non-scoring terms filter -> bool[ndocs_pad] (reference: filter clauses
    skip scoring entirely, BooleanWeight with needsScores=false)."""
    docs, tf, _, valid = gather_postings(
        field_arrays["starts"], field_arrays["doc_ids"], field_arrays["tfs"], rows, bucket)
    hits = jnp.zeros(ndocs_pad, jnp.float32).at[docs].add(
        jnp.where(valid & (tf > 0), 1.0, 0.0), mode="drop")
    return (hits > 0) & (live > 0)


def feature_score(field_arrays: dict, live: jnp.ndarray, rows: jnp.ndarray,
                  bucket: int, ndocs_pad: int, contrib_fn) -> ScoredMask:
    """Score a feature-postings row group (rank_feature / sparse dot):
    gather (doc, weight) postings, apply `contrib_fn(weight, term_idx)` on the
    VPU, scatter-add. Matches only docs carrying the feature(s) (reference
    RankFeatureQuery / learned-sparse dot product)."""
    docs, w, term_idx, valid = gather_postings(
        field_arrays["starts"], field_arrays["doc_ids"], field_arrays["tfs"],
        rows, bucket)
    contrib = jnp.where(valid, contrib_fn(w, term_idx), 0.0)
    scores = jnp.zeros(ndocs_pad, jnp.float32).at[docs].add(contrib, mode="drop")
    counts = jnp.zeros(ndocs_pad, jnp.float32).at[docs].add(
        jnp.where(valid, 1.0, 0.0), mode="drop")
    live_ok = live > 0
    return ScoredMask(jnp.where(live_ok, scores, 0.0),
                      jnp.where(live_ok, counts, 0.0))


def rank_feature_value(w, fn_id: str, p1, p2, positive: bool):
    """The four reference rank_feature scoring functions (RankFeatureQuery):
    saturation w/(w+pivot), log ln(scaling+w), sigmoid w^e/(w^e+p^e), linear.
    `positive=False` flips saturation/sigmoid (p/(p+w) style) like
    positive_score_impact=false."""
    if fn_id == "linear":
        return w
    if fn_id == "saturation":
        return p1 / (p1 + w) if not positive else w / (w + p1)
    if fn_id == "log":
        return jnp.log(p1 + w)
    if fn_id == "sigmoid":
        we = jnp.power(jnp.maximum(w, 0.0), p2)
        pe = jnp.power(p1, p2)
        return pe / (pe + we) if not positive else we / (we + pe)
    raise ValueError(f"unknown rank_feature function [{fn_id}]")


# ---------------- dense column predicates ----------------

def int64_range_mask(col: dict, lo_hi: jnp.ndarray, lo_lo: jnp.ndarray,
                     hi_hi: jnp.ndarray, hi_lo: jnp.ndarray,
                     include_lo: bool, include_hi: bool) -> jnp.ndarray:
    """Exact 64-bit range predicate over a (hi, lo)-split int column
    (reference: LongPoint range query). Bounds arrive as traced i32 scalars."""
    vhi, vlo = col["hi"], col["lo"]

    def ge(ahi, alo, bhi, blo, strict):
        gt = (ahi > bhi) | ((ahi == bhi) & (alo > blo))
        if strict:
            return gt
        return gt | ((ahi == bhi) & (alo == blo))

    lower_ok = ge(vhi, vlo, lo_hi, lo_lo, strict=not include_lo)
    upper_ok = ge(hi_hi, hi_lo, vhi, vlo, strict=not include_hi)
    return lower_ok & upper_ok & col["present"]


def float_range_mask(col: dict, lo: jnp.ndarray, hi: jnp.ndarray,
                     include_lo: bool, include_hi: bool) -> jnp.ndarray:
    v = col["f32"]
    lower = (v >= lo) if include_lo else (v > lo)
    upper = (v <= hi) if include_hi else (v < hi)
    return lower & upper & col["present"]


def exists_mask(present: jnp.ndarray, live: jnp.ndarray) -> jnp.ndarray:
    return present & (live > 0)


def docs_mask(doc_list: jnp.ndarray, ndocs_pad: int) -> jnp.ndarray:
    """ids query: a padded i32 doc-id list -> mask (sentinel-padded)."""
    hits = jnp.zeros(ndocs_pad, jnp.float32).at[doc_list].add(1.0, mode="drop")
    return hits > 0


def point_in_polygon_mask(geo: dict, plat: jnp.ndarray,
                          plon: jnp.ndarray) -> jnp.ndarray:
    """geo_polygon: ray-cast on the VPU. plat/plon are the query's closed
    ring padded by repeating the last vertex (degenerate edges cross
    nothing), so the [ndocs, V] crossing matrix is static-shape.
    Reference analog GeoPolygonQueryBuilder (deprecated there, still
    served)."""
    x = geo["lon"][:, None]
    y = geo["lat"][:, None]
    x1, y1 = plon[None, :-1], plat[None, :-1]
    x2, y2 = plon[None, 1:], plat[None, 1:]
    spans = ((y1 <= y) & (y < y2)) | ((y2 <= y) & (y < y1))
    denom = jnp.where(y2 == y1, 1e-30, y2 - y1)
    xin = x1 + (y - y1) / denom * (x2 - x1)
    crossings = jnp.sum((spans & (x < xin)).astype(jnp.int32), axis=1)
    return (crossings % 2 == 1) & geo["present"]


def geo_distance_vec(geo: dict, lat: jnp.ndarray,
                     lon: jnp.ndarray) -> jnp.ndarray:
    """Haversine distance in meters to (lat, lon), f32[ndocs] on the VPU."""
    r = 6371008.8
    p1 = jnp.deg2rad(geo["lat"])
    p2 = jnp.deg2rad(lat)
    dphi = p2 - p1
    dlmb = jnp.deg2rad(lon - geo["lon"])
    a = (jnp.sin(dphi / 2.0) ** 2
         + jnp.cos(p1) * jnp.cos(p2) * jnp.sin(dlmb / 2.0) ** 2)
    return 2.0 * r * jnp.arcsin(jnp.sqrt(jnp.clip(a, 0.0, 1.0)))


def geo_distance_mask(geo: dict, lat: jnp.ndarray, lon: jnp.ndarray,
                      radius_m: jnp.ndarray,
                      inclusive: bool = True) -> jnp.ndarray:
    """Haversine distance filter on the VPU (reference GeoDistanceQuery)."""
    r = 6371008.8
    p1 = jnp.deg2rad(geo["lat"])
    p2 = jnp.deg2rad(lat)
    dphi = p2 - p1
    dlmb = jnp.deg2rad(lon - geo["lon"])
    a = jnp.sin(dphi / 2) ** 2 + jnp.cos(p1) * jnp.cos(p2) * jnp.sin(dlmb / 2) ** 2
    d = 2 * r * jnp.arcsin(jnp.sqrt(jnp.clip(a, 0.0, 1.0)))
    return ((d <= radius_m) if inclusive else (d < radius_m)) & geo["present"]


# ---------------- scatter-free sort-merge scoring ----------------

def sortmerge_topk(docs: jnp.ndarray, contribs: jnp.ndarray, k: int,
                   msm=None):
    """Top-k doc scores from flat (doc, contribution) postings WITHOUT a
    dense scatter (XLA scatter serializes on TPU — the dense path costs ~ms;
    this path is sort + cumsum + gathers, all MXU/VPU-friendly).

    Sort postings by doc id, then per-doc totals fall out of a cumulative-sum
    difference between run boundaries; the run start index comes from a
    prefix-max scan, so the whole reduction is dense ops. Returns
    (scores f32[k], doc_ids i32[k]) with -inf/-1 padding. `msm` (traced
    scalar) keeps only docs matched by >= msm distinct terms — each term
    contributes at most one posting per doc, so run length == match count.

    This is the TAAT->sort-merge reformulation of Lucene's BulkScorer loop:
    work is O(B log B) in the number of query postings B, independent of
    corpus size (the dense path is O(ndocs) + serialized scatter).
    """
    B = docs.shape[0]
    order = jnp.argsort(docs)
    d = docs[order]
    c = contribs[order]
    idx = jnp.arange(B, dtype=jnp.int32)
    is_first = jnp.concatenate([jnp.array([True]), d[1:] != d[:-1]])
    is_last = jnp.concatenate([d[:-1] != d[1:], jnp.array([True])])
    csum = jnp.cumsum(c)
    # index of the start of each position's run, via prefix max
    run_start = jax.lax.associative_scan(jnp.maximum,
                                         jnp.where(is_first, idx, -1))
    pre = jnp.where(run_start > 0, csum[jnp.maximum(run_start - 1, 0)], 0.0)
    run_total = csum - pre
    run_len = (idx - run_start + 1).astype(jnp.float32)
    valid = is_last & (d < jnp.int32(2**31 - 1))
    if msm is not None:
        valid = valid & (run_len >= msm)
    masked = jnp.where(valid, run_total, NEG_INF)
    k = min(k, B)
    vals, pos = jax.lax.top_k(masked, k)
    out_docs = jnp.where(vals > NEG_INF, d[pos], -1)
    return vals, out_docs


def count_matches_sortmerge(docs: jnp.ndarray, msm=None) -> jnp.ndarray:
    """Total distinct matching docs from flat postings, scatter-free."""
    d = jnp.sort(docs)
    is_last = jnp.concatenate([d[:-1] != d[1:], jnp.array([True])])
    valid = is_last & (d < jnp.int32(2**31 - 1))
    if msm is not None:
        idx = jnp.arange(d.shape[0], dtype=jnp.int32)
        is_first = jnp.concatenate([jnp.array([True]), d[1:] != d[:-1]])
        run_start = jax.lax.associative_scan(jnp.maximum,
                                             jnp.where(is_first, idx, -1))
        run_len = (idx - run_start + 1).astype(jnp.float32)
        valid = valid & (run_len >= msm)
    return jnp.sum(valid.astype(jnp.int32))


# ---------------- top-k ----------------

def collapse_topk(key: jnp.ndarray, matched: jnp.ndarray, live: jnp.ndarray,
                  ords: jnp.ndarray, n_ord_pad: int, k: int):
    """Field-collapsed top-k: one best doc per group ordinal (reference
    `search/collapse/CollapseBuilder.java` + CollapsingTopDocsCollector).

    Three dense passes, no sorting: scatter-max of the ranking key into group
    space, top-k over groups, then scatter-min of doc ids restricted to each
    group's best key (ties -> lowest doc id, like the plain collector).
    Docs with ord < 0 (missing field) share one null group (last slot)."""
    ndocs_pad = key.shape[0]
    masked = jnp.where(matched & (live > 0), key, NEG_INF)
    g = jnp.where(ords >= 0, ords, n_ord_pad - 1).astype(jnp.int32)
    g = jnp.clip(g, 0, n_ord_pad - 1)
    gbest = jnp.full(n_ord_pad, NEG_INF, jnp.float32).at[g].max(masked)
    doc_iota = jnp.arange(ndocs_pad, dtype=jnp.int32)
    valid = masked > NEG_INF
    cand = jnp.where(valid & (masked == gbest[g]), doc_iota,
                     jnp.int32(2**31 - 1))
    gdoc = jnp.full(n_ord_pad, 2**31 - 1, jnp.int32).at[g].min(cand)
    kk = min(k, n_ord_pad)
    vals, gsel = jax.lax.top_k(gbest, kk)
    docs = jnp.minimum(gdoc[gsel], ndocs_pad - 1)
    return vals, docs


# `topk_blocks` cuts a plane only where that hands `lax.top_k` at most
# 1 / _TOPK_BLOCKED_GAIN of its keys. Read on a v5e (PERF.md section 6,
# PR 29): the blocked form is never slower, wins from 2^18 keys up, and
# compiles in 0.2 s where one `lax.top_k` over 2^13 keys takes 1.5 s and
# over 2^15 or more 12-24 s; at 16 a (2^26, 128) top-k kept an 11 s sort.
_TOPK_BLOCKED_GAIN = 8


def topk_blocks(n: int, k: int):
    """How a top `k` over a plane of `n` keys is cut: `(R, C)`, R blocks of
    C consecutive positions, or None for one `lax.top_k` over all of it.
    A function of the two static sizes alone, so the program and the
    `executor.topk_keys_sorted` counter cannot drift.

    C is the power of two nearest sqrt(n / k), which minimises the
    R + k * C keys that go on. None where C does not divide n or the cut
    does not pay (R + k * C over n / _TOPK_BLOCKED_GAIN), which also
    covers every shape it cannot be built for: k >= R makes k * C >= n."""
    if k < 1:
        return None
    c = 1 << ((n // k).bit_length() // 2)
    r, rem = divmod(n, c)
    if rem or (r + k * c) * _TOPK_BLOCKED_GAIN > n:
        return None
    return r, c


def topk_keys_sorted(n: int, k: int) -> int:
    """Keys that `topk_docs` hands to `lax.top_k`, all calls together, for
    a plane of n keys and a top k (`executor.topk_keys_sorted`)."""
    k = min(k, n)
    blocks = topk_blocks(n, k)
    if blocks is None:
        return n
    r, c = blocks
    return topk_keys_sorted(r, k) + topk_keys_sorted(k * c, k)


def _topk_lowest_first(x: jnp.ndarray, k: int):
    """`lax.top_k(x, k)` (equal keys: the lower position first) without
    sorting all of x where `topk_blocks` cuts it. One pass reduces each
    block of C consecutive positions to its maximum; the top k of the R
    maxima picks k blocks; their rows, laid out in ascending block order
    so that position stays monotone, give the top k. Both inner top-ks
    are this function again, so no `lax.top_k` sees a long row.

    Exact, ties included: order elements by (key descending, position
    ascending) and blocks by their best element; an element of the true
    top k outside the k best blocks would have k elements of k other
    blocks before it."""
    blocks = topk_blocks(x.shape[0], k)
    if blocks is None:
        return jax.lax.top_k(x, k)
    r, c = blocks
    rows = x.reshape(r, c)
    _, best = _topk_lowest_first(jnp.max(rows, axis=1), k)
    best = jnp.sort(best)
    vals, pos = _topk_lowest_first(rows[best].reshape(k * c), k)
    return vals, best[pos // c] * c + pos % c


def topk_docs(scores: jnp.ndarray, matched: jnp.ndarray, live: jnp.ndarray, k: int):
    """Exact masked top-k over one doc-id plane: the k best keys among the
    matched live docs, best first, and their doc ids.

    Ties break by ascending doc id, like Lucene's TopScoreDocCollector.
    With fewer than k matches the remaining lanes hold -inf; their ids are
    unspecified but within [0, n), and every reader drops those lanes by
    value. Values and ids above -inf are those of `lax.top_k` over the
    whole masked plane, whichever form `topk_blocks` picks for (n, k)."""
    masked = jnp.where(matched & (live > 0), scores, NEG_INF)
    return _topk_lowest_first(masked, min(k, scores.shape[0]))


def total_hits(matched: jnp.ndarray, live: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(jnp.where(matched & (live > 0), 1, 0))


# ---------------- host-side helpers ----------------

def bm25_idf(n_docs: int, df: int) -> float:
    """Lucene BM25Similarity.idfExplain: ln(1 + (N - df + 0.5)/(df + 0.5))."""
    return math.log(1.0 + (n_docs - df + 0.5) / (df + 0.5))


def classic_idf(n_docs: int, df: int) -> float:
    """Lucene ClassicSimilarity: 1 + ln((N+1)/(df+1))."""
    return 1.0 + math.log((n_docs + 1.0) / (df + 1.0))


def pick_bucket(total_postings: int, floor: int = 256) -> int:
    n = max(int(total_postings), floor)
    return 1 << (n - 1).bit_length()
