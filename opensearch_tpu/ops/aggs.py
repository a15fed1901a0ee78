"""Device aggregation kernels. Analog of reference
`search/aggregations/bucket/*` and `metrics/*` aggregators, which walk
matching docs one at a time; here each aggregation is a masked columnar
reduction (bincount / segment reduce / scatter-max) over the whole segment.

All kernels take `match` — the query's dense f32 0/1 match vector (already
live-masked) — so aggregations run in the same jitted program as scoring and
XLA fuses the mask with the reduction.

Two forms count documents per bucket. `bucket_counts` is a scatter-add of
one update a row and serves ids in any order (`terms_counts`, `hist`,
`geo_grid`, `composite`, `multi_terms`, `ord_counts`, a date histogram
over a segment whose timestamps are out of order). `run_counts` serves a
plane whose ids are non-decreasing in row order (a date histogram over an
append-only log segment): each bucket is one run of rows, so a count is a
difference of two prefix sums of the weights, read at the runs' boundaries.
`search/compiler._date_bucket_plane` observes the order once a plane and
`prepare_agg` selects the form.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32_MAX = np.float32(3.4e38)  # numpy, not jnp (see ops/scoring.NEG_INF note)


def _gather_match(match: jnp.ndarray, docs: jnp.ndarray) -> jnp.ndarray:
    safe = jnp.minimum(docs, match.shape[0] - 1)
    return jnp.where(docs < match.shape[0], match[safe], 0.0)


def bucket_counts(bucket_ids: jnp.ndarray, w: jnp.ndarray,
                  nbuckets: int) -> jnp.ndarray:
    """Documents per bucket, i32[nbuckets]: `w` is a 0/1 weight per row and
    ids outside [0, nbuckets) are dropped. Counts accumulate in int32: a
    float32 count stops at 2^24 = 16,777,216, and one bucket of a large
    segment can hold more. The form for ids in any order: one scatter
    update a row, which the TPU runs one after another (8.7 ns each);
    ids that are sorted by row take `run_counts`."""
    return jnp.zeros(nbuckets, jnp.int32).at[bucket_ids].add(
        (w > 0).astype(jnp.int32), mode="drop")


# rows a block of `run_counts` (the probe on the chip read 512 to 4,096
# alike at 67,108,864 rows, 8,192 slower, and 128 at 24 s of compile:
# PERF.md, PR 31); a boundary reads one block, so a block is also capped at
# the rows a boundary has on average, and the boundaries together read at
# most the plane once
_RUN_BLOCK = 2048


def run_blocks(n: int, nbounds: int) -> Optional[Tuple[int, int]]:
    """(R, C): `run_counts`'s cut of `n` rows into R blocks of C, the
    largest power of two that divides `n` and is at most `_RUN_BLOCK` and
    `n // nbounds`; None where that leaves under 8 rows a block (an odd
    `n`, more boundaries than rows): the scatter-add serves those."""
    c = min(_RUN_BLOCK, n // nbounds)
    if c < 8:
        return None
    c = math.gcd(1 << (c.bit_length() - 1), n)
    return (n // c, c) if c >= 8 else None


def run_counts(w: jnp.ndarray, starts: jnp.ndarray) -> jnp.ndarray:
    """Documents per bucket, i32[nbuckets], where each bucket is one run of
    rows: `w` i32[n] is a 0/1 weight per row, `starts` i32[nbuckets + 1]
    the non-decreasing row at which each bucket's run begins (`starts[b]`
    <= `n`; rows before `starts[0]` and from `starts[nbuckets]` on belong
    to no bucket). Equal to `bucket_counts` over the ids the runs spell,
    exact in int32: block sums in one pass over `w`, their running total,
    and for each boundary the weights before it inside its own block."""
    n, nb = w.shape[0], starts.shape[0] - 1
    cut = run_blocks(n, nb + 1)
    if cut is None:
        ids = jnp.searchsorted(starts, jnp.arange(n, dtype=jnp.int32),
                               side="right").astype(jnp.int32) - 1
        return bucket_counts(jnp.where(ids < 0, nb, ids), w, nb)
    r, c = cut
    # rows of 128: a 1-D plane's own tiling on the TPU, so this view is no
    # copy ([n] -> [R, C] is a relayout of the whole plane: PERF.md, PR 29)
    lane = min(c, 128)
    g = c // lane
    tiles = w.reshape(n // lane, lane)
    sums = jnp.sum(jnp.sum(tiles, axis=1).reshape(r, g), axis=1)
    before = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(sums)])
    blk, off = starts // c, starts % c
    idx = ((jnp.minimum(blk, r - 1) * g)[:, None]
           + jnp.arange(g, dtype=jnp.int32)[None, :])
    rows = tiles[idx].reshape(nb + 1, c)
    inside = jnp.sum(jnp.where(
        jnp.arange(c, dtype=jnp.int32)[None, :] < off[:, None], rows, 0),
        axis=1)
    prefix = before[blk] + inside
    return prefix[1:] - prefix[:-1]


def terms_counts(kw: dict, match: jnp.ndarray, nvocab_pad: int) -> jnp.ndarray:
    """Keyword terms agg: per-ordinal doc counts (reference
    GlobalOrdinalsStringTermsAggregator). Returns i32[nvocab_pad]."""
    return bucket_counts(kw["ords"], _gather_match(match, kw["doc_of_value"]),
                         nvocab_pad)


def terms_sub_metric(kw: dict, match: jnp.ndarray, values_f32: jnp.ndarray,
                     present: jnp.ndarray, nvocab_pad: int):
    """Per-ordinal (sum, count, min, max) of a numeric column — powers metric
    sub-aggregations under a terms bucket in a single fused pass."""
    docs = kw["doc_of_value"]
    safe = jnp.minimum(docs, values_f32.shape[0] - 1)
    w = _gather_match(match, docs) * jnp.where(present[safe], 1.0, 0.0)
    v = values_f32[safe]
    ords = kw["ords"]
    sums = jnp.zeros(nvocab_pad, jnp.float32).at[ords].add(w * v, mode="drop")
    cnts = jnp.zeros(nvocab_pad, jnp.float32).at[ords].add(w, mode="drop")
    mins = jnp.full(nvocab_pad, F32_MAX).at[ords].min(
        jnp.where(w > 0, v, F32_MAX), mode="drop")
    maxs = jnp.full(nvocab_pad, -F32_MAX).at[ords].max(
        jnp.where(w > 0, v, -F32_MAX), mode="drop")
    sumsq = jnp.zeros(nvocab_pad, jnp.float32).at[ords].add(w * v * v, mode="drop")
    return sums, cnts, mins, maxs, sumsq


def histogram_counts(values_f32: jnp.ndarray, present: jnp.ndarray, match: jnp.ndarray,
                     interval: float, offset: float, min_bucket: int, nbuckets: int):
    """Fixed-interval histogram (reference HistogramAggregator). The bucket
    window [min_bucket, min_bucket+nbuckets) is static, derived on the host
    from segment column stats."""
    b = jnp.floor((values_f32 - offset) / interval).astype(jnp.int32) - min_bucket
    w = match * jnp.where(present, 1.0, 0.0)
    b = jnp.where((b >= 0) & (b < nbuckets), b, nbuckets)  # OOB -> dropped
    return bucket_counts(b, w, nbuckets)


def range_counts(values_f32: jnp.ndarray, present: jnp.ndarray, match: jnp.ndarray,
                 lows: jnp.ndarray, highs: jnp.ndarray):
    """range agg: [low, high) per reference RangeAggregator. lows/highs are
    f32[nranges] traced arrays; returns i32[nranges] counts."""
    v = values_f32[None, :]
    in_range = (v >= lows[:, None]) & (v < highs[:, None])
    ok = ((match > 0) & present)[None, :]
    return jnp.sum((in_range & ok).astype(jnp.int32), axis=1)


def stats_agg(values_f32: jnp.ndarray, present: jnp.ndarray, match: jnp.ndarray):
    """count/sum/min/max/sumsq in one pass (reference StatsAggregator /
    ExtendedStatsAggregator)."""
    w = match * jnp.where(present, 1.0, 0.0)
    v = values_f32
    count = jnp.sum(w)
    s = jnp.sum(w * v)
    ssq = jnp.sum(w * v * v)
    mn = jnp.min(jnp.where(w > 0, v, F32_MAX))
    mx = jnp.max(jnp.where(w > 0, v, -F32_MAX))
    return count, s, mn, mx, ssq


def value_count_keyword(kw: dict, match: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(_gather_match(match, kw["doc_of_value"]))


def weighted_avg_agg(v: jnp.ndarray, v_present: jnp.ndarray,
                     w: jnp.ndarray, w_present: jnp.ndarray,
                     match: jnp.ndarray,
                     v_missing, w_missing,
                     has_v_missing: bool, has_w_missing: bool):
    """Σ value·weight and Σ weight over matched docs (reference
    WeightedAvgAggregator): docs missing value or weight are skipped unless
    the corresponding `missing` default is configured."""
    veff = jnp.where(v_present, v, v_missing)
    weff = jnp.where(w_present, w, w_missing)
    ok = match > 0
    if not has_v_missing:
        ok = ok & v_present
    if not has_w_missing:
        ok = ok & w_present
    okf = ok.astype(jnp.float32)
    return (jnp.sum(okf * veff * weff), jnp.sum(okf * weff), jnp.sum(okf))


def geo_bounds_agg(lat: jnp.ndarray, lon: jnp.ndarray, present: jnp.ndarray,
                   match: jnp.ndarray):
    """(top, bottom, left, right, count) masked extremes (reference
    GeoBoundsAggregator, wrap_longitude=false semantics)."""
    ok = (match > 0) & present
    count = jnp.sum(ok.astype(jnp.float32))
    top = jnp.max(jnp.where(ok, lat, -F32_MAX))
    bottom = jnp.min(jnp.where(ok, lat, F32_MAX))
    left = jnp.min(jnp.where(ok, lon, F32_MAX))
    right = jnp.max(jnp.where(ok, lon, -F32_MAX))
    return top, bottom, left, right, count


def geo_centroid_agg(lat: jnp.ndarray, lon: jnp.ndarray, present: jnp.ndarray,
                     match: jnp.ndarray):
    """(Σlat, Σlon, count) (reference GeoCentroidAggregator)."""
    w = match * jnp.where(present, 1.0, 0.0)
    return jnp.sum(w * lat), jnp.sum(w * lon), jnp.sum(w)


def ord_counts(ords: jnp.ndarray, match: jnp.ndarray, nord_pad: int
               ) -> jnp.ndarray:
    """Doc-major single-valued ordinal bincount (multi_terms combined ords,
    grid ords): ord < 0 = missing -> dropped."""
    o = jnp.where(ords >= 0, ords, nord_pad)
    return bucket_counts(o, match, nord_pad)


def cardinality_keyword(kw: dict, match: jnp.ndarray, nvocab_pad: int) -> jnp.ndarray:
    """Exact distinct count via ordinals (the reference uses global ords +
    HLL; segment-local ords are exact on-device, merged across segments on
    the host via vocab union)."""
    counts = terms_counts(kw, match, nvocab_pad)
    return jnp.sum(jnp.where(counts > 0, 1, 0))


def _hash_f32(v: jnp.ndarray) -> jnp.ndarray:
    """Cheap 32-bit integer mix (fmix32 from MurmurHash3) of float bit patterns."""
    h = jax.lax.bitcast_convert_type(v, jnp.int32).astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def hll_registers(hashes_u32: jnp.ndarray, valid: jnp.ndarray, log2m: int = 14) -> jnp.ndarray:
    """HyperLogLog registers from 32-bit hashes via scatter-max (the
    mergeable core of reference CardinalityAggregator's HLL++; merge across
    segments/shards = elementwise max on the host). Returns i32[2^log2m]."""
    m = 1 << log2m
    reg = (hashes_u32 & jnp.uint32(m - 1)).astype(jnp.int32)
    rest = hashes_u32 >> log2m
    # rank = position of the first set bit in the remaining 32-log2m bits
    nbits = 32 - log2m
    rank = (nbits + 1) - jnp.ceil(jnp.log2(rest.astype(jnp.float32) + 1.0)).astype(jnp.int32)
    rank = jnp.clip(rank, 1, nbits + 1)
    reg = jnp.where(valid, reg, m)  # invalid -> dropped
    return jnp.zeros(m, jnp.int32).at[reg].max(jnp.where(valid, rank, 0), mode="drop")


def cardinality_numeric_registers(values_f32: jnp.ndarray, present: jnp.ndarray,
                                  match: jnp.ndarray, log2m: int = 14) -> jnp.ndarray:
    return hll_registers(_hash_f32(values_f32), (match > 0) & present, log2m)


def cardinality_keyword_registers(kw: dict, match: jnp.ndarray, nvocab_pad: int,
                                  ord_hashes_u32: jnp.ndarray, log2m: int = 14) -> jnp.ndarray:
    """Keyword cardinality: HLL over per-ordinal string hashes (host-computed
    once per segment), activated by matched ordinals."""
    counts = terms_counts(kw, match, nvocab_pad)
    return hll_registers(ord_hashes_u32, counts > 0, log2m)


# DDSketch-style log-binned quantile sketch: bins are GLOBAL constants
# (value-independent), so per-segment/per-shard histograms merge by plain
# addition — the mergeability property the reference gets from TDigest.
# Layout: [0..HALF) negative magnitudes (reversed), HALF zero, (HALF..2*HALF]
# positive magnitudes. gamma^HALF spans MIN_MAG..MAX_MAG => ~0.5% rel. error.
DD_HALF = 4096
DD_MIN_MAG = 1e-9
DD_MAX_MAG = 1e9
DD_LN_GAMMA = (np.log(DD_MAX_MAG) - np.log(DD_MIN_MAG)) / DD_HALF
DD_NBINS = 2 * DD_HALF + 1


def ddsketch_hist(values_f32: jnp.ndarray, present: jnp.ndarray,
                  match: jnp.ndarray) -> jnp.ndarray:
    """f32[DD_NBINS] mergeable quantile histogram of matched values."""
    w = match * jnp.where(present, 1.0, 0.0)
    mag = jnp.abs(values_f32)
    idx = jnp.floor((jnp.log(jnp.maximum(mag, DD_MIN_MAG)) - np.log(DD_MIN_MAG))
                    / DD_LN_GAMMA).astype(jnp.int32)
    idx = jnp.clip(idx, 0, DD_HALF - 1)
    b = jnp.where(values_f32 > 0, DD_HALF + 1 + idx,
                  jnp.where(values_f32 < 0, DD_HALF - 1 - idx, DD_HALF))
    b = jnp.where(w > 0, b, DD_NBINS)  # dropped
    return jnp.zeros(DD_NBINS, jnp.float32).at[b].add(w, mode="drop")


def ddsketch_bin(v: float) -> int:
    """Host-side bin index of one value — the same arithmetic as
    `ddsketch_hist` (f32 log/floor, so a stored value and a queried value
    land in the same bin bit-for-bit; percentile_ranks inverts percentiles
    through this)."""
    # every step in f32, mirroring the device (jnp canonicalizes the f64
    # log/gamma constants to f32 before the subtract/divide; a host f64
    # intermediate shifts ~1e-4 of values one bin off the device's)
    mag = np.float32(abs(v))
    ln = np.log(np.maximum(mag, np.float32(DD_MIN_MAG)))
    idx = int(np.floor((ln - np.float32(np.log(DD_MIN_MAG)))
                       / np.float32(DD_LN_GAMMA)))
    idx = min(max(idx, 0), DD_HALF - 1)
    if v > 0:
        return DD_HALF + 1 + idx
    if v < 0:
        return DD_HALF - 1 - idx
    return DD_HALF


def ddsketch_value(b: int) -> float:
    """Representative value of bin b (host-side finalize)."""
    if b == DD_HALF:
        return 0.0
    if b > DD_HALF:
        return float(DD_MIN_MAG * np.exp((b - DD_HALF - 1 + 0.5) * DD_LN_GAMMA))
    return float(-DD_MIN_MAG * np.exp((DD_HALF - 1 - b + 0.5) * DD_LN_GAMMA))


def min_ord_sort_key(min_ord: jnp.ndarray, descending: bool, missing_last: bool) -> jnp.ndarray:
    """Keyword sort keys from per-doc min ordinals; missing docs pushed to the
    configured end (reference: SortedSetSortField missing _first/_last)."""
    key = min_ord.astype(jnp.float32)
    big = jnp.float32(2.0**30)
    missing_val = big if (missing_last != descending) else -big
    key = jnp.where(min_ord < 0, missing_val, key)
    return -key if descending else key
