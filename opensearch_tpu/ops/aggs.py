"""Device aggregation kernels. Analog of reference
`search/aggregations/bucket/*` and `metrics/*` aggregators, which walk
matching docs one at a time; here each aggregation is a masked columnar
reduction (bincount / segment reduce / scatter-max) over the whole segment.

All kernels take `match` — the query's dense f32 0/1 match vector (already
live-masked) — so aggregations run in the same jitted program as scoring and
XLA fuses the mask with the reduction.

Four forms reduce rows per bucket, and the sizes choose among them.
Ids in any order (`terms_counts`, `hist`, `geo_grid`, `composite`,
`multi_terms`, `ord_counts`, a date histogram over a segment whose
timestamps are out of order) take one of three, named by
`count_form(nbuckets)` from a static shape and two constants of the chip.
Where the buckets are few (under `_DENSE_BUCKETS`) the dense form compares
each block of rows against every bucket id while the block is on the chip
and adds, or takes the minimum / maximum, into that block's partial
accumulators (rows x buckets lane operations and no update a row). From
there up to `_PRODUCT_BUCKETS` a count is a product on the matrix unit: a
slot is `hi * L + lo`, so the count of slot (hi, lo) is the sum over the
rows of [the row's hi] x [the row's lo], the one-hot of `hi` times the
one-hot of `lo` contracted over a block's rows (rows x (H + L) compares
and rows x buckets multiply-adds of 0 and 1, exact). At or over that a
scatter issues one update a row, which the TPU runs one after another
(6.7-8.7 ns each). All serve `bucket_counts` and give equal arrays;
`bucket_sums_exact` and `bucketed_sub_metric` are dense under
`_DENSE_BUCKETS` and scatter from there on, but for the metric's count,
which is a `bucket_counts`.
The dense and the product form are loops over blocks of rows, and a caller
that knows a half-open range of rows outside which no row weighs anything
(`span`: `search/compiler.row_span`, a `range` over a column in row order)
hands it down: the loops then visit the blocks that meet it and no other
(`_block_range`). The weights still decide every row of those blocks, so
the arrays are the whole plane's, bit for bit; the default is the whole
plane.
`run_counts` serves a plane whose ids are non-decreasing in row order (a
date histogram over an append-only log segment): each bucket is one run of
rows, so a count is a difference of two prefix sums of the weights, read at
the runs' boundaries. `search/planes.date_bucket_plane` observes the
order once a plane and `prepare_agg` selects that form.

A group-by over a keyword column (`terms_counts` and through it a keyword
`cardinality`, `terms_sub_metric`, `value_count_keyword`) takes the form the
column's device dict has (`counts_by_value`; `index/segment._kw_field_arrays`
chooses it from the segment's own data). A column in which no document
holds two values is its ordinals by document, `min_ord`, and is counted
under the mask in one streaming pass, as any other id plane. A column in
which one does is laid out by value (`ords` with `doc_of_value` beside
them), and the mask is first gathered to the values, one element a value
(`_gather_match`: 141-144 ms at 2^24 values on a v5e, PERF.md, PR 43). The
two share `bucket_counts` / `bucketed_sub_metric` and nothing else.
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


def counts_by_value(kw: dict) -> bool:
    """Whether a group-by over the keyword column `kw` (its device dict)
    counts the column's flat values, the match gathered to them through
    `doc_of_value`, and not `min_ord` by document under the mask: the one
    predicate `terms_counts`, `terms_sub_metric` and `value_count_keyword`
    choose by, and `programs.agg_cost` counts by (`group_by_rows`)."""
    return "doc_of_value" in kw


def group_by_rows(kw: dict) -> int:
    """Length of the plane a group-by over `kw` reads."""
    return kw["ords" if counts_by_value(kw) else "min_ord"].shape[0]


# buckets under which a per-bucket reduction takes the dense form. Its cost
# is rows x buckets where the scatter's is rows x 8.7 ns, so the crossover
# is a property of the chip. On a v5e at 33,554,432 rows (PERF.md, PR 33)
# a count reads 4.4 / 11.3 / 24.2 / 49.7 / 141.9 ms at 101 / 366 / 1,023 /
# 2,048 / 4,096 buckets where the scatter reads 295 (225 from 2,048 on),
# and a metric's six accumulators 17 / 60 / 134 / 262 / 773 where six
# scatters read 1,340-1,770: the forms cross near 6,000 buckets, and the
# constant stands where the dense form still wins four times over
_DENSE_BUCKETS = 2048
# buckets under which a count at `_DENSE_BUCKETS` buckets or more is the
# product of two one-hots and not a scatter. The product's cost is rows x
# (H + L) compares and rows x H x L multiply-adds where the scatter's is
# rows x 6.7-8.7 ns, so this crossover too is a property of the chip. On a
# v5e at 16,777,216 rows (PERF.md, PR 45) a count reads 2.7 / 4.3 / 12.9 /
# 24.1 / 47.3 / 82.8 / 92.2 ms at 2,048 / 16,384 / 65,536 / 131,072 /
# 262,144 / 461,089 / 524,288 slots (1.9 ms + 0.17 ms a thousand slots of
# H x L: the matrix unit at 86% of its bfloat16 peak, int8 operands the
# same to 0.1 ms) where the scatter reads 112.3-112.8 whatever the slots
# (ids drawn evenly; 146 over a log's own combinations): the forms cross
# near 650,000 slots, and the constant stands at the last power of two
# under that, where the product wins by a fifth at the least and 2.4 times
# over at half as many
_PRODUCT_BUCKETS = 1 << 19
# the forms name their ops in the device trace (`jax.named_scope`:
# metadata of an op, read by `benchmark/launch_reduce.py`), one scope a
# form, inside whatever scope the caller stands in
DENSE_SCOPE = "aggs.dense"
PRODUCT_SCOPE = "aggs.product"
SCATTER_SCOPE = "aggs.scatter"
RUN_COUNTS_SCOPE = "aggs.run_counts"


def count_form(nbuckets: int) -> str:
    """The form a reduction into `nbuckets` buckets over ids in any order
    takes: "dense" (compare a block of rows against every bucket),
    "product" (a bucket count as two one-hots multiplied on the matrix
    unit; the sums and the extremes scatter there) or "scatter": the one
    predicate `bucket_counts`, `bucket_sums_exact` and
    `bucketed_sub_metric` choose by, and `programs.agg_cost` counts by."""
    if nbuckets < _DENSE_BUCKETS:
        return "dense"
    return "product" if nbuckets < _PRODUCT_BUCKETS else "scatter"


def _held_ids(bucket_ids: jnp.ndarray, w: jnp.ndarray,
              nbuckets: int) -> jnp.ndarray:
    """Bucket ids with `nbuckets` where the row does not count: `w` is 0 or
    the id lies outside [0, nbuckets)."""
    ok = (w > 0) & (bucket_ids >= 0) & (bucket_ids < nbuckets)
    return jnp.where(ok, bucket_ids, nbuckets)


# rows a block of the dense form where nothing else cuts the rows (the sums
# are cut by `sum_limb_plan`): what the probe ran, and 128 KiB a plane of
# the chip's on-chip memory
_DENSE_BLOCK = 1 << 15


def _row_blocks(x: jnp.ndarray, rows: int, fill) -> jnp.ndarray:
    """The plane `x` in blocks of `rows` rows, as [blocks, R, 128]: the
    1-D plane's own tiling on the TPU, so a view, where [blocks, rows] is
    a relayout of the plane (PERF.md, PR 29, PR 31 and PR 33). More than
    one block needs `rows` in whole tiles; the tail is padded with `fill`."""
    n = x.shape[0]
    nblk = max(-(-n // rows), 1)
    per = -(-rows // 128) * 128
    assert nblk == 1 or per == rows, (n, rows)
    if nblk * per != n:
        x = jnp.pad(x, (0, nblk * per - n), constant_values=fill)
    return x.reshape(nblk, per // 128, 128)


def _block_range(span, rows: int, nblk: int) -> tuple:
    """(first, end) of the blocks of `rows` rows, of `nblk`, that meet the
    rows [lo, hi) of `span` (None is the whole plane): what the block loops
    run over. An empty or inverted span meets none. Two int32 scalars of a
    trace give traced bounds; the host's integers (`span_rows`) give
    numpy's, by the same arithmetic."""
    if span is None:
        return 0, nblk
    lo, hi = span
    xp = np if isinstance(lo, (int, np.integer)) else jnp
    first = xp.clip(lo // rows, 0, nblk)
    return first, xp.where(hi > lo, xp.clip(-(-hi // rows), first, nblk),
                           first)


def span_rows(span, rows: int, n: int) -> int:
    """Host: the rows, of a plane of `n`, in the blocks of `rows` rows that
    `_block_range` names for `span`: what the loop reads."""
    first, end = _block_range(
        span if span is None else (int(span[0]), int(span[1])), rows,
        max(-(-n // rows), 1))
    return int(min(end * rows, n) - min(first * rows, n))


def _block_at(blocks: Optional[jnp.ndarray], i) -> Optional[jnp.ndarray]:
    return None if blocks is None else jax.lax.dynamic_index_in_dim(
        blocks, i, 0, keepdims=False)


def _dense_reduce(held: jnp.ndarray, nbuckets: int, rows: int,
                  v: Optional[jnp.ndarray] = None, parts=None,
                  extremes: bool = False, span=None) -> tuple:
    """The dense form: for every block of `rows` rows and every bucket, the
    count of the rows of `held` (`_held_ids`) in it; the sum over them of
    each int32 plane that `parts(block of v, block of rows that count)`
    makes of the block's values (a sum's limbs: made a block at a time,
    they never exist as whole planes); with `extremes` the least and the
    greatest of `v` among them -> i32[blocks, nbuckets] each, the extremes
    f32 (an empty bucket reads 0, `F32_MAX`, `-F32_MAX`). A loop over the
    blocks that meet `span` (`_block_range`; a block it does not visit reads
    as one in which no row counts): each is read from HBM once, laid along
    the lanes and compared against all bucket ids at once, and every
    accumulator is a reduction over that one comparison, so the [buckets,
    rows] one-hot exists a block at a time, inside a fusion. The planes
    enter as `_row_blocks` views, the tail padded with rows that count
    nowhere."""
    per = -(-rows // 128) * 128
    ids = jnp.arange(nbuckets, dtype=jnp.int32)[:, None]

    def one(t, vals):
        hot = t.reshape(1, per) == ids
        out = [jnp.sum(hot.astype(jnp.int32), axis=1)]
        if parts is not None:
            out += [jnp.sum(jnp.where(hot, a.reshape(1, per), 0), axis=1)
                    for a in parts(vals, t < nbuckets)]
        if extremes:
            vals = vals.reshape(1, per)
            out += [jnp.min(jnp.where(hot, vals, F32_MAX), axis=1),
                    jnp.max(jnp.where(hot, vals, -F32_MAX), axis=1)]
        return tuple(out)

    with jax.named_scope(DENSE_SCOPE):
        blocks = _row_blocks(held, rows, nbuckets)
        vblocks = None if v is None else _row_blocks(v, rows, 0.0)
        nblk = blocks.shape[0]
        outs = jax.eval_shape(
            lambda: one(_block_at(blocks, 0), _block_at(vblocks, 0)))
        empty = [0] * len(outs)
        if extremes:
            empty[-2:] = F32_MAX, -F32_MAX
        first, end = _block_range(span, rows, nblk)
        return jax.lax.fori_loop(
            first, end,
            lambda i, acc: tuple(
                jax.lax.dynamic_update_index_in_dim(a, got, i, 0)
                for a, got in zip(
                    acc, one(_block_at(blocks, i), _block_at(vblocks, i)))),
            tuple(jnp.full((nblk,) + o.shape, e, o.dtype)
                  for o, e in zip(outs, empty)))


# rows a block of the product form: one `dot_general` contracts a block's
# rows into float32, exact while no slot's partial passes 2^24, and each
# block's partial is added as int32, so a block holds at most 2^24 rows.
# A span's ends each read a whole block, so a narrow span wants small
# blocks and the whole plane does not mind: into 461,089 slots at 2^24
# rows, spans of 0.6% / 2.6% / 7% / 100% of the rows read 3.33 / 4.51 /
# 8.22 / 83.6 ms at 2^18 rows a block, 2.51 / 4.05 / 7.63 / 82.6 at 2^16,
# 2.29 / 4.18 / 7.93 / 83.9 at 2^14 (launch and read included: PERF.md,
# PR 49; PR 45 had read whole planes alone and 2^18 a percent under 2^15)
_PRODUCT_BLOCK = 1 << 16


def product_block_rows(n: int) -> int:
    """Rows a block of the product form over a plane of `n` rows."""
    return min(_PRODUCT_BLOCK, -(-max(n, 1) // 128) * 128)


def product_split(nbuckets: int) -> Tuple[int, int]:
    """(H, L) of the product form: slot = hi * L + lo with `L` the power
    of two next above sqrt(`nbuckets`), a whole tile of 128 lanes or more
    (256 x 256 for 65,536 slots, 128 x 128 for 16,384), and `H` the rows
    of `L` slots that hold `nbuckets`."""
    l = max(1 << ((nbuckets - 1).bit_length() + 1) // 2, 128)
    return -(-nbuckets // l), l


def _product_counts(held: jnp.ndarray, nbuckets: int,
                    span=None) -> jnp.ndarray:
    """The product form: the count of the rows of `held` (`_held_ids`) in
    every bucket -> i32[nbuckets]. A loop over the blocks of rows that meet
    `span` (the plane viewed as `_row_blocks`; `_block_range`): a block's
    ids laid along the lanes,
    `hi == arange(H)` and `lo == arange(L)` made while the block is on the
    chip (XLA fuses both comparisons into the product's operands: no
    one-hot is written) and contracted over the block's rows by one
    `dot_general` of 0s and 1s, exact in bfloat16, accumulated in float32
    and added to the [H, L] counts as int32. A row that holds `nbuckets`
    falls in a slot past the last bucket, or where `H x L` is `nbuckets`
    in no row of the one-hot at all."""
    h, l = product_split(nbuckets)
    per = product_block_rows(held.shape[0])
    assert per <= 1 << 24, per
    shift = l.bit_length() - 1
    his = jnp.arange(h, dtype=jnp.int32)[:, None]
    los = jnp.arange(l, dtype=jnp.int32)[:, None]

    with jax.named_scope(PRODUCT_SCOPE):
        blocks = _row_blocks(held, per, nbuckets)

        def one(i, acc):
            t = _block_at(blocks, i).reshape(1, per)
            hot_hi = ((t >> shift) == his).astype(jnp.bfloat16)
            hot_lo = ((t & (l - 1)) == los).astype(jnp.bfloat16)
            part = jax.lax.dot_general(
                hot_hi, hot_lo, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return acc + part.astype(jnp.int32)

        acc = jax.lax.fori_loop(
            *_block_range(span, per, blocks.shape[0]), one,
            jnp.zeros((h, l), jnp.int32))
        return acc.reshape(h * l)[:nbuckets]


def dense_block_rows(n: int) -> int:
    """Rows a block of the dense count over a plane of `n` rows."""
    return max(min(n, _DENSE_BLOCK), 1)


def bucket_counts(bucket_ids: jnp.ndarray, w: jnp.ndarray,
                  nbuckets: int, span=None) -> jnp.ndarray:
    """Documents per bucket, i32[nbuckets]: `w` is a 0/1 weight per row and
    ids outside [0, nbuckets) are dropped. Counts accumulate in int32: a
    float32 count stops at 2^24 = 16,777,216, and one bucket of a large
    segment can hold more. The forms for ids in any order (the module's
    docstring, `count_form`): dense under `_DENSE_BUCKETS` buckets, the
    product of two one-hots under `_PRODUCT_BUCKETS`, else one scatter
    update a row; ids that are sorted by row take `run_counts`. No row
    outside `span` has weight (the module's docstring): the dense and the
    product form read the blocks that meet it, the scatter issues an update
    a row whatever it is."""
    held = _held_ids(bucket_ids, w, nbuckets)
    form = count_form(nbuckets)
    if form == "dense":
        rows = dense_block_rows(held.shape[0])
        return jnp.sum(_dense_reduce(held, nbuckets, rows, span=span)[0],
                       axis=0)
    if form == "product":
        return _product_counts(held, nbuckets, span)
    with jax.named_scope(SCATTER_SCOPE):
        return jnp.zeros(nbuckets, jnp.int32).at[held].add(1, mode="drop")


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
    with jax.named_scope(RUN_COUNTS_SCOPE):
        tiles = w.reshape(n // lane, lane)
        sums = jnp.sum(jnp.sum(tiles, axis=1).reshape(r, g), axis=1)
        before = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                  jnp.cumsum(sums)])
        blk, off = starts // c, starts % c
        idx = ((jnp.minimum(blk, r - 1) * g)[:, None]
               + jnp.arange(g, dtype=jnp.int32)[None, :])
        rows = tiles[idx].reshape(nb + 1, c)
        inside = jnp.sum(jnp.where(
            jnp.arange(c, dtype=jnp.int32)[None, :] < off[:, None], rows,
            0), axis=1)
        prefix = before[blk] + inside
        return prefix[1:] - prefix[:-1]


# ---------------------------------------------------------------------
# sums whose error does not grow with the bucket
# ---------------------------------------------------------------------
# A float32 accumulator past 2^24 times its addends' size rounds every
# addend to its own spacing (a bucket of 8M values near 9.35 sums to 7.5e7,
# spacing 8: the sum is wrong in the second digit), whatever the order. So
# a sum is taken in fixed point: each value, scaled by a power of two so
# that the column's largest magnitude lies under 1, is cut into limbs of L
# bits (48 bits or more in all: every float32 within 2^24 of the column's
# largest magnitude is held exactly, a smaller one to 2^-48 of that
# magnitude), the limbs are added in int32 over blocks of 2^(31 - L) rows
# (a block's sum cannot overflow), and each block sum is handed on as its
# high and low 16 bits summed over the blocks (int32 again: at most 2^15
# blocks). The host finishes in float64 (`limb_sums_to_f64`). Absolute error
# of a sum of `count` values: at most count x 2^-48 x the column's largest
# magnitude (rounded up to a power of two), whatever `count` is.
SUB_METRIC_SCOPE = "aggs.bucketed_sub"
# limb bits by number of limbs: the host reads L off the output's shape
_LIMB_BITS = {3: 16, 4: 12, 6: 8, 8: 6, 10: 5}
_SUM_ACC_MAX = 1 << 22      # int32 elements of one limb's accumulators


def sum_limb_plan(n: int, nbuckets: int) -> Tuple[int, int, int]:
    """(limbs, L, rows a block) for `n` rows into `nbuckets` buckets: the
    widest limbs whose per-block accumulators ((n / rows) x nbuckets int32
    a limb) stay under `_SUM_ACC_MAX`."""
    for limbs, bits in _LIMB_BITS.items():
        rows = 1 << (31 - bits)
        if -(-n // rows) * nbuckets <= _SUM_ACC_MAX:
            return limbs, bits, min(rows, max(n, 1))
    limbs, bits = next(reversed(_LIMB_BITS.items()))
    return limbs, bits, min(1 << (31 - bits), max(n, 1))


def sum_scale_inv(max_abs: float) -> np.float32:
    """2^-e with 2^e > `max_abs` (the column's largest magnitude): the
    factor that brings every value under 1, exact in float32."""
    top = float(np.float32(abs(max_abs)))       # as the device holds it
    e = int(np.clip(math.frexp(top)[1], -100, 120)) if np.isfinite(top) \
        else 120
    return np.float32(2.0 ** -e)


def _limbs(v: jnp.ndarray, w: jnp.ndarray, inv, limbs: int, bits: int):
    """The signed int32 limbs of `v * inv` (|v * inv| < 1), most
    significant first; rows with `w` 0 (or false) give zeros. Every step
    is exact in float32 but the last limb's rounding."""
    x = jnp.abs(v) * inv
    sign = jnp.where(w > 0, jnp.where(v < 0, -1, 1), 0).astype(jnp.int32)
    out = []
    for i in range(limbs):
        x = x * np.float32(1 << bits)
        # (the last limb rounds, and stays a limb: a block of 2^(31 - L)
        # rows of it cannot pass int32)
        l = (jnp.minimum(jnp.round(x), np.float32((1 << bits) - 1))
             if i == limbs - 1 else jnp.floor(x))
        x = x - l
        out.append(l.astype(jnp.int32) * sign)
    return out


def _fold_blocks(acc: jnp.ndarray) -> list:
    """i32[blocks, nb] block sums -> their high and low 16 bits, each summed
    over the blocks (acc = hi * 65536 + lo, lo in [0, 65536))."""
    return [jnp.sum(acc >> 16, axis=0), jnp.sum(acc & 0xFFFF, axis=0)]


def _scatter_block_sums(held: jnp.ndarray, planes: list, nbuckets: int,
                        rows: int) -> list:
    """The scatter form of `_dense_reduce`'s sums: each int32 plane added
    into i32[blocks, nbuckets] by one scatter keyed by (block of `rows`
    rows, bucket)."""
    n = held.shape[0]
    nblk = -(-n // rows)
    blk = jnp.arange(n, dtype=jnp.int32) // rows
    ids = jnp.where(held < nbuckets, blk * nbuckets + held, nblk * nbuckets)
    with jax.named_scope(SCATTER_SCOPE):
        return [jnp.zeros(nblk * nbuckets, jnp.int32).at[ids].add(
            plane, mode="drop").reshape(nblk, nbuckets) for plane in planes]


def _folded(accs: list) -> jnp.ndarray:
    """A sum's limbs as block sums -> i32[2 x limbs, nbuckets]."""
    return jnp.stack([half for acc in accs for half in _fold_blocks(acc)])


def bucket_sums_exact(bucket_ids: jnp.ndarray, v: jnp.ndarray,
                      w: jnp.ndarray, nbuckets: int, inv,
                      span=None) -> jnp.ndarray:
    """Per-bucket sums of `v` over the rows with `w` > 0 and an id in
    [0, nbuckets), as i32[2 x limbs, nbuckets] for `limb_sums_to_f64`:
    each limb summed in int32 by (block of rows, bucket), densely or by
    one scatter-add a limb (`count_form`: the product form is a count's
    alone, so its range scatters here). `span` as `bucket_counts` takes it:
    a block the dense form does not visit hands on zero partials."""
    limbs, bits, rows = sum_limb_plan(bucket_ids.shape[0], nbuckets)
    held = _held_ids(bucket_ids, w, nbuckets)
    if count_form(nbuckets) == "dense":
        return _folded(_dense_reduce(
            held, nbuckets, rows, v,
            lambda vb, ok: _limbs(vb, ok, inv, limbs, bits), span=span)[1:])
    return _folded(_scatter_block_sums(
        held, _limbs(v, w, inv, limbs, bits), nbuckets, rows))


def sums_exact(v: jnp.ndarray, w: jnp.ndarray, inv) -> jnp.ndarray:
    """The sum of `v` over the rows with `w` > 0, as i32[2 x limbs, 1]:
    `bucket_sums_exact` with one bucket and no scatter."""
    n = v.shape[0]
    limbs, bits, rows = sum_limb_plan(n, 1)
    nblk = -(-n // rows)
    out = []
    for limb in _limbs(v, w, inv, limbs, bits):
        if nblk * rows != n:
            limb = jnp.pad(limb, (0, nblk * rows - n))
        out += _fold_blocks(jnp.sum(limb.reshape(nblk, rows),
                                    axis=1)[:, None])
    return jnp.stack(out)


def limb_sums_to_f64(parts: np.ndarray, inv) -> np.ndarray:
    """Host: i32[2 x limbs, nb] of `bucket_sums_exact` / `sums_exact` and
    the scale they were cut with -> f64[nb] sums."""
    parts = np.asarray(parts).astype(np.int64)
    limbs = parts.shape[0] // 2
    bits = _LIMB_BITS[limbs]
    total = np.zeros(parts.shape[1], np.float64)
    for i in reversed(range(limbs)):
        whole = parts[2 * i] * 65536 + parts[2 * i + 1]
        total += whole.astype(np.float64) * 2.0 ** (-bits * (i + 1))
    return total / float(inv)


def sub_metric_scatters(n: int, nbuckets: int, sumsq: bool) -> int:
    """Scatters `bucketed_sub_metric` issues for `n` rows where it is not
    dense (`count_form`): the minimum, the maximum, a limb each of the sum
    (and of the squares), and the count where that is no product."""
    limbs = sum_limb_plan(n, nbuckets)[0]
    return ((2 if count_form(nbuckets) == "product" else 3)
            + limbs * (2 if sumsq else 1))


def bucketed_sub_metric(bucket_ids: jnp.ndarray, v: jnp.ndarray,
                        w: jnp.ndarray, nbuckets: int, inv,
                        sumsq: bool, span=None) -> dict:
    """count / min / max / sum (and the sum of squares where `sumsq`) of
    `v` per bucket over the rows with `w` > 0: counts in int32, extremes as
    they are stored, sums in limbs (`bucket_sums_exact`). `inv` is
    `sum_scale_inv` of the column, handed back as `scale` for the host.
    Under `_DENSE_BUCKETS` buckets every accumulator is a reduction over
    one comparison of the rows with the bucket ids; else each is a
    scatter, but for the count, which is a `bucket_counts` (a product
    under `_PRODUCT_BUCKETS`). `span` as `bucket_counts` takes it."""
    # the scope names these ops in the device trace (an op's provenance:
    # the benchmark's `agg_bucketed_sub_share` sums their time)
    with jax.named_scope(SUB_METRIC_SCOPE):
        b = _held_ids(bucket_ids, w, nbuckets)
        limbs, bits, rows = sum_limb_plan(b.shape[0], nbuckets)

        def parts(v, w):
            out = _limbs(v, w, inv, limbs, bits)
            if sumsq:
                # a square is rounded once to float32 (2^-24 of itself,
                # the same for a bucket of any size), then summed exactly
                out += _limbs(v * v, w, inv * inv, limbs, bits)
            return out

        if count_form(nbuckets) == "dense":     # one pass for them all
            count, *accs, lo, hi = _dense_reduce(
                b, nbuckets, rows, v, parts, extremes=True, span=span)
            count = jnp.sum(count, axis=0)
            lo, hi = jnp.min(lo, axis=0), jnp.max(hi, axis=0)
        else:
            count = bucket_counts(b, w, nbuckets, span)
            with jax.named_scope(SCATTER_SCOPE):
                lo = jnp.full(nbuckets, F32_MAX).at[b].min(v, mode="drop")
                hi = jnp.full(nbuckets, -F32_MAX).at[b].max(v, mode="drop")
            accs = _scatter_block_sums(b, parts(v, w), nbuckets, rows)
        out = {"count": count, "min": lo, "max": hi,
               "sum": _folded(accs[:limbs]), "scale": inv}
        if sumsq:
            out["sumsq"] = _folded(accs[limbs:])
    return out


def terms_counts(kw: dict, match: jnp.ndarray, nvocab_pad: int,
                 span=None) -> jnp.ndarray:
    """Keyword terms agg: per-ordinal doc counts (reference
    GlobalOrdinalsStringTermsAggregator). Returns i32[nvocab_pad]. `span`
    is in documents: a column laid out by value (its rows are values)
    reads every block."""
    if not counts_by_value(kw):     # (-1 and padded rows: `_held_ids`)
        return bucket_counts(kw["min_ord"], match, nvocab_pad, span)
    return bucket_counts(kw["ords"], _gather_match(match, kw["doc_of_value"]),
                         nvocab_pad)


def terms_sub_metric(kw: dict, match: jnp.ndarray, values_f32: jnp.ndarray,
                     present: jnp.ndarray, nvocab_pad: int, inv,
                     sumsq: bool, span=None) -> dict:
    """Per-ordinal count / min / max / sum of a numeric column: the metric
    sub-aggregations under a terms bucket (`bucketed_sub_metric` over the
    ordinals by document, the column read in place, or over the flat
    values' ordinals). `span` as `terms_counts` takes it."""
    if not counts_by_value(kw):
        return bucketed_sub_metric(
            kw["min_ord"], values_f32, match * jnp.where(present, 1.0, 0.0),
            nvocab_pad, inv, sumsq, span)
    docs = kw["doc_of_value"]
    safe = jnp.minimum(docs, values_f32.shape[0] - 1)
    w = _gather_match(match, docs) * jnp.where(present[safe], 1.0, 0.0)
    return bucketed_sub_metric(kw["ords"], values_f32[safe], w, nvocab_pad,
                               inv, sumsq)


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


def stats_agg(values_f32: jnp.ndarray, present: jnp.ndarray,
              match: jnp.ndarray, inv, sumsq: bool) -> dict:
    """count / sum / min / max (and the sum of squares) in one pass
    (reference StatsAggregator / ExtendedStatsAggregator): the count in
    int32 (a float32 count stops at 2^24), the sums in limbs
    (`sums_exact`), `inv` the column's `sum_scale_inv`."""
    w = match * jnp.where(present, 1.0, 0.0)
    v = values_f32
    out = {"count": jnp.sum((w > 0).astype(jnp.int32)),
           "sum": sums_exact(v, w, inv), "scale": inv,
           "min": jnp.min(jnp.where(w > 0, v, F32_MAX)),
           "max": jnp.max(jnp.where(w > 0, v, -F32_MAX))}
    if sumsq:
        out["sumsq"] = sums_exact(v * v, w, inv * inv)
    return out


def value_count_keyword(kw: dict, match: jnp.ndarray) -> jnp.ndarray:
    if not counts_by_value(kw):
        return jnp.sum(match * (kw["min_ord"] >= 0))
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


def ord_counts(ords: jnp.ndarray, match: jnp.ndarray, nord_pad: int,
               span=None) -> jnp.ndarray:
    """Doc-major single-valued ordinal bincount (multi_terms combined ords,
    grid ords): ord < 0 = missing -> dropped."""
    o = jnp.where(ords >= 0, ords, nord_pad)
    return bucket_counts(o, match, nord_pad, span)


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
    with jax.named_scope(SCATTER_SCOPE):
        return jnp.zeros(m, jnp.int32).at[reg].max(
            jnp.where(valid, rank, 0), mode="drop")


def cardinality_numeric_registers(values_f32: jnp.ndarray, present: jnp.ndarray,
                                  match: jnp.ndarray, log2m: int = 14) -> jnp.ndarray:
    return hll_registers(_hash_f32(values_f32), (match > 0) & present, log2m)


def cardinality_keyword_registers(kw: dict, match: jnp.ndarray, nvocab_pad: int,
                                  ord_hashes_u32: jnp.ndarray, log2m: int = 14,
                                  span=None):
    """Keyword cardinality: HLL over per-ordinal string hashes (host-computed
    once per segment), activated by matched ordinals -> (registers, the
    number of matched ordinals: the segment's exact distinct count, which
    is the answer where one segment gives it and nothing is merged)."""
    held = terms_counts(kw, match, nvocab_pad, span) > 0
    return (hll_registers(ord_hashes_u32, held, log2m),
            jnp.sum(held.astype(jnp.int32)))


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
    with jax.named_scope(SCATTER_SCOPE):
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
