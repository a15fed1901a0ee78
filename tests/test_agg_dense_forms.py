"""The forms of a per-bucket reduction over ids in any order (`ops.aggs`:
dense under `_DENSE_BUCKETS` buckets, a count the product of two one-hots
under `_PRODUCT_BUCKETS`, else a scatter) give equal arrays, dtypes
included, for `bucket_counts`, `bucket_sums_exact` and
`bucketed_sub_metric`; the two constants alone choose (`count_form`); and
`programs.agg_cost` counts `aggs.blocked.rows` / `aggs.scatter.updates` by
the predicate the emit chooses by. A test steers the form by moving the
constants (the program has no option for it)."""

import numpy as np
import pytest

import jax

from opensearch_tpu.ops import aggs as agg_ops
from opensearch_tpu.search import programs as PG

# (`_DENSE_BUCKETS`, `_PRODUCT_BUCKETS`) that give every size one form
DENSE, PRODUCT, SCATTER = (1 << 30, 1 << 30), (0, 1 << 30), (0, 0)


def _forms(monkeypatch, forms, fn, *args):
    """`fn(*args)` jitted under each of `forms` -> their outputs as numpy."""
    out = []
    for dense, product in forms:
        monkeypatch.setattr(agg_ops, "_DENSE_BUCKETS", dense)
        monkeypatch.setattr(agg_ops, "_PRODUCT_BUCKETS", product)
        # a new function object a form: `jax.jit` of one it has traced
        # answers from its cache, whatever the constants have become
        got = jax.jit(lambda *a: fn(*a))(*args)
        out.append(jax.tree_util.tree_map(np.asarray, got))
    return out


def _both(monkeypatch, fn, *args):
    """-> (dense, scatter)."""
    return _forms(monkeypatch, (DENSE, SCATTER), fn, *args)


def _same(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape, (x.dtype, y.dtype)
        assert np.array_equal(x, y), (x, y)


def _rows(n, nb, seed, out_of_range=True, w_zero=0.2):
    """ids with some equal to `nb`, beyond it and below 0; values of both
    signs in hundredths; weights with zeros."""
    rng = np.random.default_rng(seed)
    lo, hi = (-2, nb + 3) if out_of_range else (0, nb)
    b = rng.integers(lo, hi, n).astype(np.int32)
    v = np.round(rng.normal(3.0, 40.0, n), 2).astype(np.float32)
    w = (rng.random(n) >= w_zero).astype(np.float32)
    inv = agg_ops.sum_scale_inv(float(np.abs(v).max()) if n else 1.0)
    return b, v, w, inv


# rows that a block divides and rows that it does not, one row, fewer rows
# than a tile of 128, more than one block of the dense count's 32,768
SIZES = [(1, 1), (16, 3), (1000, 7), (4096, 101), (4097, 64), (70001, 366),
         (131072, 256)]


@pytest.mark.parametrize("n,nb", SIZES)
def test_bucket_counts_forms_agree(monkeypatch, n, nb):
    b, _v, w, _inv = _rows(n, nb, n + nb)
    dense, scatter = _both(
        monkeypatch, lambda b, w: agg_ops.bucket_counts(b, w, nb), b, w)
    _same(dense, scatter)
    ok = (w > 0) & (b >= 0) & (b < nb)
    assert dense.dtype == np.int32 and dense.shape == (nb,)
    assert np.array_equal(dense, np.bincount(b[ok], minlength=nb))


# slots a power of two and not, one that `L` does not divide (461,089 is the
# big5 composite's), one row, rows a tile of 128 divides and does not
# (blocks of fewer rows: the test of the float32 block, below); (1, 1) and
# (4096, 101) are under a tile of lanes a side
PRODUCT_SIZES = [(1, 1), (4096, 101), (1, 2048), (1000, 2048), (70001, 4097),
                 (40000, 16384), (33000, 65536), (5000, 461089)]


@pytest.mark.parametrize("n,nb", PRODUCT_SIZES)
def test_bucket_counts_product_equals_the_scatter(monkeypatch, n, nb):
    """Ids at `nb`, beyond it and below 0 and rows of weight 0 count
    nowhere in either form; the same int32s slot for slot."""
    b, _v, w, _inv = _rows(n, nb, 7 * n + nb)
    product, scatter = _forms(
        monkeypatch, (PRODUCT, SCATTER),
        lambda b, w: agg_ops.bucket_counts(b, w, nb), b, w)
    _same(product, scatter)
    ok = (w > 0) & (b >= 0) & (b < nb)
    assert product.dtype == np.int32 and product.shape == (nb,)
    assert np.array_equal(product, np.bincount(b[ok], minlength=nb))


@pytest.mark.parametrize("nb", [2048, 4097, 16384, 65536, 131072, 461089])
def test_product_split_holds_every_slot(nb):
    h, l = agg_ops.product_split(nb)
    assert l >= 128 and l & (l - 1) == 0 and (h - 1) * l < nb <= h * l
    assert l // 2 < max(nb ** 0.5, 128) <= l


@pytest.mark.parametrize("sumsq", [False, True])
@pytest.mark.parametrize("n,nb", [(1000, 2048), (70001, 4097)])
def test_bucketed_sub_metric_in_the_products_range(monkeypatch, n, nb,
                                                   sumsq):
    """Between the constants only the metric's count is a product: the
    whole dict equals the scatter's."""
    b, v, w, inv = _rows(n, nb, 9 * n + nb)
    product, scatter = _forms(
        monkeypatch, ((0, 1 << 30), SCATTER),
        lambda b, v, w: agg_ops.bucketed_sub_metric(b, v, w, nb, inv, sumsq),
        b, v, w)
    _same(product, scatter)
    sums = _forms(
        monkeypatch, ((0, 1 << 30), SCATTER),
        lambda b, v, w: agg_ops.bucket_sums_exact(b, v, w, nb, inv), b, v, w)
    _same(*sums)


@pytest.mark.parametrize("n,nb", SIZES)
def test_bucket_sums_exact_forms_agree(monkeypatch, n, nb):
    b, v, w, inv = _rows(n, nb, 3 * n + nb)
    dense, scatter = _both(
        monkeypatch,
        lambda b, v, w: agg_ops.bucket_sums_exact(b, v, w, nb, inv), b, v, w)
    _same(dense, scatter)
    limbs = agg_ops.sum_limb_plan(n, nb)[0]
    assert dense.dtype == np.int32 and dense.shape == (2 * limbs, nb)
    ok = (w > 0) & (b >= 0) & (b < nb)
    want = np.bincount(b[ok], weights=v[ok].astype(np.float64), minlength=nb)
    got = agg_ops.limb_sums_to_f64(dense, inv)
    assert np.allclose(got, want, rtol=0, atol=1e-9 * max(n, 1))


@pytest.mark.parametrize("sumsq", [False, True])
@pytest.mark.parametrize("n,nb", SIZES)
def test_bucketed_sub_metric_forms_agree(monkeypatch, n, nb, sumsq):
    b, v, w, inv = _rows(n, nb, 5 * n + nb)
    dense, scatter = _both(
        monkeypatch,
        lambda b, v, w: agg_ops.bucketed_sub_metric(b, v, w, nb, inv, sumsq),
        b, v, w)
    assert set(dense) == {"count", "min", "max", "sum", "scale"} | (
        {"sumsq"} if sumsq else set())
    _same(dense, scatter)
    assert dense["count"].dtype == np.int32
    assert dense["min"].dtype == dense["max"].dtype == np.float32
    ok = (w > 0) & (b >= 0) & (b < nb)
    for k in range(nb):
        vals = v[ok & (b == k)]
        assert dense["count"][k] == vals.size
        if vals.size:
            assert dense["min"][k] == vals.min()
            assert dense["max"][k] == vals.max()


def test_buckets_left_empty_read_the_identities(monkeypatch):
    """Every row in bucket 2 of 5, or nowhere: the others read count 0,
    `F32_MAX` / `-F32_MAX` and a zero sum in both forms."""
    n, nb = 3000, 5
    _b, v, w, inv = _rows(n, nb, 11)
    b = np.where(np.arange(n) % 3 == 0, nb, 2).astype(np.int32)
    dense, scatter = _both(
        monkeypatch,
        lambda b, v, w: agg_ops.bucketed_sub_metric(b, v, w, nb, inv, True),
        b, v, w)
    _same(dense, scatter)
    empty = np.arange(nb) != 2
    assert (dense["count"][empty] == 0).all() and dense["count"][2] > 0
    assert (dense["min"][empty] == agg_ops.F32_MAX).all()
    assert (dense["max"][empty] == -agg_ops.F32_MAX).all()
    assert (dense["sum"][:, empty] == 0).all()
    assert (dense["sumsq"][:, empty] == 0).all()


def test_no_row_counts(monkeypatch):
    n, nb = 500, 9
    b, v, _w, inv = _rows(n, nb, 12)
    w = np.zeros(n, np.float32)
    dense, scatter = _both(
        monkeypatch,
        lambda b, v, w: agg_ops.bucketed_sub_metric(b, v, w, nb, inv, False),
        b, v, w)
    _same(dense, scatter)
    assert not dense["count"].any() and not dense["sum"].any()


@pytest.mark.parametrize("sign", [1.0, -1.0])
def test_limb_partials_near_their_bound(monkeypatch, sign):
    """One bucket holds every row and every value is the column's largest
    magnitude just under a power of two, so each 16-bit limb is 0xFFFF and a
    block's partial sum (rows x 65,535) stands at its int32 bound in
    miniature: more than one block (the plan is moved to blocks of 1,024
    rows), a tail block that is part empty, either sign. Equal in both
    forms and exact against float64."""
    monkeypatch.setattr(agg_ops, "_LIMB_BITS", {3: 16})
    monkeypatch.setattr(
        agg_ops, "sum_limb_plan", lambda n, nb: (3, 16, min(1024, max(n, 1))))
    n, nb = 5 * 1024 + 300, 4
    top = np.float32(sign * (2.0 - 2.0 ** -23))     # 24 ones: 0xFFFF, 0xFF00
    v = np.full(n, top, np.float32)
    b = np.full(n, 1, np.int32)
    w = np.ones(n, np.float32)
    inv = agg_ops.sum_scale_inv(float(abs(top)))
    dense, scatter = _both(
        monkeypatch,
        lambda b, v, w: agg_ops.bucket_sums_exact(b, v, w, nb, inv), b, v, w)
    _same(dense, scatter)
    got = agg_ops.limb_sums_to_f64(dense, inv)
    assert got[1] == float(top) * n and not got[[0, 2, 3]].any()
    # the first limb's block sums really are rows x 0xFFFF
    whole = dense[0].astype(np.int64) * 65536 + dense[1]
    assert abs(whole[1]) == n * 0xFFFF


def test_a_bucket_of_more_than_2_to_24_rows_worth_of_weight(monkeypatch):
    """A count past float32's 2^24 in miniature: the dense count sums 0/1
    in int32 over blocks, so 40,000 rows of one bucket read 40,000 whatever
    the block, as the scatter reads them (a float32 accumulator is what PR 32
    removed; here the check is that no block's partial is narrowed)."""
    n, nb = 40_000, 3
    b = np.zeros(n, np.int32)
    w = np.ones(n, np.float32)
    monkeypatch.setattr(agg_ops, "_DENSE_BLOCK", 1 << 10)
    dense, scatter = _both(
        monkeypatch, lambda b, w: agg_ops.bucket_counts(b, w, nb), b, w)
    _same(dense, scatter)
    assert dense.tolist() == [n, 0, 0]


@pytest.mark.parametrize("block", [128, 1024])
def test_a_slot_past_the_products_float32_block(monkeypatch, block):
    """A slot's count past what one block's float32 partial may hold, in
    miniature: blocks of `block` rows, and 40,000 rows of one slot (and
    2,500 of the last) read 40,000 and 2,500 as the scatter reads them:
    every block's partial is added as int32, none is narrowed."""
    n, nb = 42_500, 4097
    b = np.where(np.arange(n) % 17 == 0, nb - 1, 300).astype(np.int32)
    w = np.ones(n, np.float32)
    monkeypatch.setattr(agg_ops, "_PRODUCT_BLOCK", block)
    product, scatter = _forms(
        monkeypatch, (PRODUCT, SCATTER),
        lambda b, w: agg_ops.bucket_counts(b, w, nb), b, w)
    _same(product, scatter)
    assert product[300] == 40_000 and product[nb - 1] == 2_500
    assert product.sum() == n


@pytest.mark.parametrize("nb", [2048, 4097, 65536])
def test_between_the_constants_the_count_is_one_product(nb):
    """From `_DENSE_BUCKETS` up to `_PRODUCT_BUCKETS` the jaxpr of
    `bucket_counts` holds one `dot_general` and no scatter; the sums and
    the extremes keep their scatters and only a metric's count rides the
    product; at `_PRODUCT_BUCKETS` no `dot_general` is left."""
    assert agg_ops.count_form(nb) == "product"
    assert agg_ops.count_form(agg_ops._PRODUCT_BUCKETS - 1) == "product"
    assert agg_ops.count_form(agg_ops._PRODUCT_BUCKETS) == "scatter"
    b, v, w, inv = _rows(2048, nb, 3)
    count = str(jax.make_jaxpr(
        lambda b, w: agg_ops.bucket_counts(b, w, nb))(b, w))
    assert "scatter" not in count and count.count("dot_general") == 1
    metric = str(jax.make_jaxpr(
        lambda b, v, w: agg_ops.bucketed_sub_metric(b, v, w, nb, inv, False)
    )(b, v, w))
    limbs = agg_ops.sum_limb_plan(2048, nb)[0]
    assert metric.count("dot_general") == 1
    assert (metric.count("scatter-add") + metric.count("scatter_add")
            == limbs)
    assert agg_ops.sub_metric_scatters(2048, nb, False) == 2 + limbs
    at = agg_ops._PRODUCT_BUCKETS
    over = str(jax.make_jaxpr(
        lambda b, w: agg_ops.bucket_counts(b, w, at))(b, w))
    assert "dot_general" not in over and "scatter" in over
    assert agg_ops.sub_metric_scatters(2048, at, False) == 3 + \
        agg_ops.sum_limb_plan(2048, at)[0]


def test_the_product_ops_carry_their_scope():
    b, _v, w, _inv = _rows(2048, 4097, 4)
    text = jax.jit(
        lambda b, w: agg_ops.bucket_counts(b, w, 4097)
    ).lower(b, w).as_text(debug_info=True)
    assert f"{agg_ops.PRODUCT_SCOPE}/" in text
    assert agg_ops.SCATTER_SCOPE not in text
    assert "stablehlo.scatter" not in text


@pytest.mark.parametrize("nb", [1, 101, 366])
def test_under_the_constant_no_scatter_is_built(nb):
    """The form is chosen by `nbuckets` against the constants: under the
    first the jaxpr of all three entries holds no scatter, at it they all
    do (the count alone is a product there)."""
    assert agg_ops.count_form(nb) == "dense"
    assert agg_ops.count_form(agg_ops._DENSE_BUCKETS - 1) == "dense"
    assert agg_ops.count_form(agg_ops._DENSE_BUCKETS) != "dense"
    b, v, w, inv = _rows(2048, nb, 1)

    def all_three(nb):
        def fn(b, v, w):
            return (agg_ops.bucket_counts(b, w, nb),
                    agg_ops.bucket_sums_exact(b, v, w, nb, inv),
                    agg_ops.bucketed_sub_metric(b, v, w, nb, inv, True))
        return str(jax.make_jaxpr(fn)(b, v, w))
    assert "scatter" not in all_three(nb)
    assert "dot_general" not in all_three(nb)
    assert all_three(agg_ops._DENSE_BUCKETS).count("scatter") >= 3


def test_the_dense_ops_carry_the_sub_metric_scope():
    b, v, w, inv = _rows(2048, 16, 2)
    text = jax.jit(
        lambda b, v, w: agg_ops.bucketed_sub_metric(b, v, w, 16, inv, False)
    ).lower(b, v, w).as_text(debug_info=True)
    # the loop over the blocks and what runs inside it are under the scope,
    # and under the dense form's own inside it (PR 38)
    assert (f"{agg_ops.SUB_METRIC_SCOPE}/{agg_ops.DENSE_SCOPE}/while/body"
            in text)
    assert "stablehlo.scatter" not in text


# ---------------------------------------------------------------------
# a row span bounds the blocks the dense and the product form read
# ---------------------------------------------------------------------
SPAN_BLOCK = 1024
SPAN_N = 5 * SPAN_BLOCK + 300           # the last block is part padding
SPANS = {
    "whole": (0, SPAN_N),
    "empty": (2000, 2000),
    "inside one block": (SPAN_BLOCK + 5, SPAN_BLOCK + 77),
    "straddling two": (2 * SPAN_BLOCK - 3, 2 * SPAN_BLOCK + 9),
    "ending in the padded tail": (4 * SPAN_BLOCK + 1, SPAN_N),
    "not aligned to 128": (131, 3 * SPAN_BLOCK + 1),
}
# what reduces under a span: a count in the dense and in the product form,
# sums and a metric's accumulators with its extremes in the dense form
SPAN_FORMS = {
    "dense": (DENSE, 101, lambda nb, inv, span: lambda b, v, w, lo, hi:
              agg_ops.bucket_counts(b, w, nb, span(lo, hi))),
    "product": (PRODUCT, 4097, lambda nb, inv, span: lambda b, v, w, lo, hi:
                agg_ops.bucket_counts(b, w, nb, span(lo, hi))),
    "dense sums": (DENSE, 64, lambda nb, inv, span: lambda b, v, w, lo, hi:
                   agg_ops.bucket_sums_exact(b, v, w, nb, inv,
                                             span(lo, hi))),
    "dense metric and extremes": (
        DENSE, 7, lambda nb, inv, span: lambda b, v, w, lo, hi:
        agg_ops.bucketed_sub_metric(b, v, w, nb, inv, True, span(lo, hi))),
}


def _span_blocks(monkeypatch):
    """Blocks of `SPAN_BLOCK` rows in every form, the sums' included."""
    monkeypatch.setattr(agg_ops, "_DENSE_BLOCK", SPAN_BLOCK)
    monkeypatch.setattr(agg_ops, "_PRODUCT_BLOCK", SPAN_BLOCK)
    monkeypatch.setattr(agg_ops, "_LIMB_BITS", {3: 16})
    monkeypatch.setattr(agg_ops, "sum_limb_plan", lambda n, nb: (
        3, 16, min(SPAN_BLOCK, max(n, 1))))


def _span_rows(form, span, outside=False):
    _consts, nb, _fn = SPAN_FORMS[form]
    b, v, w, inv = _rows(SPAN_N, nb, 13 * nb + span[1])
    inside = np.zeros(SPAN_N, bool)
    inside[span[0]:span[1]] = True
    return nb, b, v, (w if outside else w * inside), inv


def _span_run(monkeypatch, form, args, span, traced=True):
    consts, nb, make = SPAN_FORMS[form]
    _span_blocks(monkeypatch)
    b, v, w, inv = args
    lo, hi = np.int32(span[0]), np.int32(span[1])
    fn = make(nb, inv, (lambda lo, hi: (lo, hi)) if traced
              else (lambda lo, hi: None))
    (got,) = _forms(monkeypatch, (consts,), fn, b, v, w, lo, hi)
    return got


@pytest.mark.parametrize("span", SPANS)
@pytest.mark.parametrize("form", SPAN_FORMS)
def test_a_form_under_a_row_span_equals_numpy_over_the_same_rows(
        monkeypatch, form, span):
    """No row outside the span weighs anything (the contract): what the
    loop over the span's blocks returns is numpy's over the same rows, and
    the call without a span's, dtypes included."""
    nb, b, v, w, inv = _span_rows(form, SPANS[span])
    got = _span_run(monkeypatch, form, (b, v, w, inv), SPANS[span])
    _same(got, _span_run(monkeypatch, form, (b, v, w, inv), SPANS[span],
                         traced=False))
    ok = (w > 0) & (b >= 0) & (b < nb)
    counts = np.bincount(b[ok], minlength=nb)
    if form in ("dense", "product"):
        assert got.dtype == np.int32 and np.array_equal(got, counts)
        return
    want = np.bincount(b[ok], weights=v[ok].astype(np.float64), minlength=nb)
    sums = got if form == "dense sums" else got["sum"]
    assert np.allclose(agg_ops.limb_sums_to_f64(sums, inv), want, rtol=0,
                       atol=1e-9 * SPAN_N)
    if form == "dense sums":
        return
    assert np.array_equal(got["count"], counts)
    for k in range(nb):
        vals = v[ok & (b == k)]
        assert got["min"][k] == (vals.min() if vals.size else agg_ops.F32_MAX)
        assert got["max"][k] == (vals.max() if vals.size
                                 else -agg_ops.F32_MAX)


@pytest.mark.parametrize("span", ["inside one block", "straddling two",
                                  "empty"])
@pytest.mark.parametrize("form", SPAN_FORMS)
def test_a_block_the_span_does_not_meet_is_not_read(monkeypatch, form, span):
    """Rows that weigh something outside the span (what no caller hands
    over) show which blocks the loop visits: those of the blocks that meet
    the span count, the mask deciding each of them; no other block does."""
    lo, hi = SPANS[span]
    nb, b, v, w, inv = _span_rows(form, (lo, hi), outside=True)
    got = _span_run(monkeypatch, form, (b, v, w, inv), (lo, hi))
    visited = np.zeros(SPAN_N, bool)
    if hi > lo:
        visited[lo // SPAN_BLOCK * SPAN_BLOCK: -(-hi // SPAN_BLOCK)
                * SPAN_BLOCK] = True
    _same(got, _span_run(monkeypatch, form, (b, v, w * visited, inv),
                         (0, SPAN_N)))
    counts = got if form in ("dense", "product") else (
        got["count"] if isinstance(got, dict) else None)
    if counts is not None:
        ok = visited & (w > 0) & (b >= 0) & (b < nb)
        assert counts.sum() == ok.sum() < ((w > 0) & (b >= 0)
                                           & (b < nb)).sum()


@pytest.mark.parametrize("span,rows,n,want", [
    (None, 1024, 5420, 5420), ((0, 5420), 1024, 5420, 5420),
    ((2000, 2000), 1024, 5420, 0), ((2048, 2048), 1024, 5420, 0),
    ((1029, 1101), 1024, 5420, 1024), ((2045, 2057), 1024, 5420, 2048),
    ((4097, 5420), 1024, 5420, 1324), ((0, 1 << 30), 1024, 5420, 5420),
    ((7000, 9000), 1024, 5420, 0), ((300, 200), 1024, 5420, 0),
    ((5, 9), 4096, 100, 100)])
def test_span_rows_counts_the_blocks_the_loop_visits(span, rows, n, want):
    assert agg_ops.span_rows(span, rows, n) == want
    if span is not None:        # the host's reckoning is the trace's
        nblk = max(-(-n // rows), 1)
        first, end = jax.jit(
            lambda lo, hi: agg_ops._block_range((lo, hi), rows, nblk))(
                np.int32(span[0]), np.int32(span[1]))
        assert min(int(end) * rows, n) - min(int(first) * rows, n) == want


# ---------------------------------------------------------------------
# `agg_cost` counts by the predicate the emit chooses by
# ---------------------------------------------------------------------
N = 4096
# "k" is laid out by value (three values a document), "k1" by document
SEG = {"live": np.zeros(N, np.float32),
       "keyword": {"k": {"ords": np.zeros(3 * N, np.int32),
                         "doc_of_value": np.zeros(3 * N, np.int32),
                         "min_ord": np.zeros(N, np.int32)},
                   "k1": {"min_ord": np.zeros(N, np.int32)}}}
STATS = ("stats", "s0", "f", True, False)
EXT = ("stats", "s0", "f", True, True)
GONE = ("stats", "s0", "f", False, False)       # the column does not exist


def _spec(kind, nb, subs, form=None):
    if kind == "hist":
        return ("hist", "p", "f", 1.0, 0.0, 0, nb, subs)
    if kind == "date_hist":
        return ("date_hist", "p", "f", 1, 0, None, 0, nb, subs, form)
    if kind == "auto_date_hist":
        return ("auto_date_hist", "p", "f", "d", 20, 0, 4 * nb, nb, subs,
                form)
    if kind in ("terms", "terms_by_doc"):
        return ("terms", "p", "k" if kind == "terms" else "k1", nb, subs)
    if kind == "geo_grid":
        return ("geo_grid", "p", "geohash", "f", 5, nb, subs)
    raise AssertionError(kind)


def _cost(spec):
    cost = {"scatter": 0, "blocked": 0, "sub_buckets": 0}
    PG.agg_cost(spec, SEG, cost)
    return cost


@pytest.mark.parametrize("kind,form,rows", [
    ("hist", None, N), ("date_hist", "scatter", N),
    ("auto_date_hist", "scatter", N), ("terms", None, 3 * N),
    ("terms_by_doc", None, N), ("geo_grid", None, N)])
@pytest.mark.parametrize("subs", [(), (STATS,), (EXT,), (STATS, GONE, EXT)])
def test_agg_cost_follows_the_predicate(kind, form, rows, subs):
    few, many = agg_ops._DENSE_BUCKETS - 1, agg_ops._PRODUCT_BUCKETS
    counted = sum(1 for s in subs if s[3])
    # under the first constant: a pass for the count and one a sub-metric
    got = _cost(_spec(kind, few, subs, form))
    assert got == {"scatter": 0, "blocked": rows * (1 + counted),
                   "sub_buckets": few * counted}
    # at the second: one scatter for the count and `sub_metric_scatters`
    # a metric
    got = _cost(_spec(kind, many, subs, form))
    scatters = 1 + sum(agg_ops.sub_metric_scatters(rows, many, s[4])
                       for s in subs if s[3])
    assert got == {"scatter": rows * scatters, "blocked": 0,
                   "sub_buckets": many * counted}


@pytest.mark.parametrize("kind,form,rows", [
    ("hist", None, N), ("date_hist", "scatter", N),
    ("auto_date_hist", "scatter", N), ("terms", None, 3 * N),
    ("terms_by_doc", None, N), ("geo_grid", None, N)])
@pytest.mark.parametrize("subs", [(), (STATS,), (EXT,), (STATS, GONE, EXT)])
@pytest.mark.parametrize("at", ["first", "last"])
def test_agg_cost_between_the_constants(kind, form, rows, subs, at):
    """In the product's range the count, and each metric's count, is a
    pass under `aggs.blocked.rows`; a metric's minimum, maximum and limbs
    are scatters still: one fewer than past the second constant."""
    nb = (agg_ops._DENSE_BUCKETS if at == "first"
          else agg_ops._PRODUCT_BUCKETS - 1)
    assert agg_ops.count_form(nb) == "product"
    counted = sum(1 for s in subs if s[3])
    got = _cost(_spec(kind, nb, subs, form))
    scatters = sum(agg_ops.sub_metric_scatters(rows, nb, s[4])
                   for s in subs if s[3])
    limbs = agg_ops.sum_limb_plan(rows, nb)[0]
    assert scatters == sum(2 + limbs * (2 if s[4] else 1)
                           for s in subs if s[3])
    assert got == {"scatter": rows * scatters,
                   "blocked": rows * (1 + counted),
                   "sub_buckets": nb * counted}


@pytest.mark.parametrize("nb", [64, 5000, 1 << 20])
def test_agg_cost_of_a_run_counted_plane(nb):
    """The count of a plane in row order is `run_counts`' one pass whatever
    the buckets; the metric under it follows the predicate."""
    got = _cost(_spec("date_hist", nb, (STATS,), "runs"))
    form = agg_ops.count_form(nb)
    limbs = agg_ops.sum_limb_plan(N, nb)[0]
    if form == "dense":
        assert got == {"scatter": 0, "blocked": 2 * N, "sub_buckets": nb}
    elif form == "product":     # the metric's count is a product
        assert got == {"scatter": (2 + limbs) * N, "blocked": 2 * N,
                       "sub_buckets": nb}
    else:
        assert got == {"scatter": (3 + limbs) * N, "blocked": N,
                       "sub_buckets": nb}


@pytest.mark.parametrize("kind", ["hist", "date_hist", "terms_by_doc",
                                  "geo_grid"])
@pytest.mark.parametrize("at", ["dense", "product"])
def test_agg_cost_under_a_row_span(monkeypatch, kind, at):
    """`aggs.blocked.rows` is the rows of the blocks each loop visits: the
    count's by its form, a dense metric's by the sums' blocks; a scatter,
    `run_counts` and a keyword column laid out by value read every row
    whatever the span; under `global` the span is whole again."""
    monkeypatch.setattr(agg_ops, "_DENSE_BLOCK", 256)
    monkeypatch.setattr(agg_ops, "_PRODUCT_BLOCK", 512)
    monkeypatch.setattr(agg_ops, "sum_limb_plan",
                        lambda n, nb: (3, 16, min(1024, n)))
    nb = 50 if at == "dense" else agg_ops._DENSE_BUCKETS
    span = (1000, 1100)     # 256: [768, 1280); 512: [512, 1536); 1024: two
    spec = _spec(kind, nb, (STATS,), "scatter")
    cost = {"scatter": 0, "blocked": 0, "sub_buckets": 0}
    PG.agg_cost(spec, SEG, cost, span)
    if at == "dense":
        assert cost == {"scatter": 0, "blocked": 512 + 2048,
                        "sub_buckets": nb}
    else:       # the count and the metric's count are products
        limbs = 3
        assert cost == {"scatter": N * (2 + limbs), "blocked": 2 * 1024,
                        "sub_buckets": nb}
    whole = {"scatter": 0, "blocked": 0, "sub_buckets": 0}
    PG.agg_cost(("global", "g", (spec,)), SEG, whole, span)
    assert whole == _cost(spec)
    same = {"scatter": 0, "blocked": 0, "sub_buckets": 0}
    PG.agg_cost(("filter", "p", "q", (spec,)), SEG, same, span)
    assert same == cost


@pytest.mark.parametrize("spec", [
    ("terms", "p", "k", 50, ()),                        # laid out by value
    ("date_hist", "p", "f", 1, 0, None, 0, 50, (), "runs"),
    ("hist", "p", "f", 1.0, 0.0, 0, 1 << 20, ())])      # a scatter
def test_agg_cost_of_what_takes_no_span(monkeypatch, spec):
    monkeypatch.setattr(agg_ops, "_DENSE_BLOCK", 256)
    cost = {"scatter": 0, "blocked": 0, "sub_buckets": 0}
    PG.agg_cost(spec, SEG, cost, (1000, 1100))
    assert cost == _cost(spec)


def test_agg_cost_walks_containers():
    inner = _spec("hist", 50, (STATS,))
    got = _cost(("filter", "p", "q", (inner, ("avg", "x"))))
    assert got == {"scatter": 0, "blocked": 2 * N, "sub_buckets": 50}
