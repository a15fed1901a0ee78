"""TPU-hardware check of `ops.aggs`' dense form at the trip-analytics cell's
size: 33,554,432 rows, ids in no row order, 101 / 256 / 366 buckets (the
cell's `distance_amount_agg`, `autohisto_agg`, `date_histogram_agg`): the
form the constant chooses equals the scatter's output exactly, for
`bucket_counts` and for `bucketed_sub_metric`, and its time is read beside
the scatter's; and both are read on either side of `_DENSE_BUCKETS`, which is
where the constant comes from (the product form that takes a count from
there on is read in `test_big5_tpu.py`). A test moves the constants to get
another form (the program has no option for it). Run on a real chip:
`python -m pytest tests_tpu/test_agg_dense_tpu.py -q -s`."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from opensearch_tpu.ops import aggs as agg_ops

pytestmark = pytest.mark.skipif(jax.default_backend() != "tpu",
                                reason="needs a real TPU chip")

N = 1 << 25


@pytest.fixture(scope="module")
def rows():
    """Host and device planes: ids over [0, 4096) to be folded to a test's
    buckets, fares in hundredths of both signs, a 0/1 weight."""
    rng = np.random.default_rng(33)
    ids = rng.integers(0, 4096, N).astype(np.int32)
    v = np.round(rng.gamma(2.0, 9.0, N) - 5.0, 2).astype(np.float32)
    w = (rng.random(N) < 0.9).astype(np.float32)
    inv = agg_ops.sum_scale_inv(float(np.abs(v).max()))
    return (ids, v, w), tuple(jnp.asarray(x) for x in (ids, v, w)), inv


def _ids(ids, nb):
    """Ids in [0, nb] out of ids in [0, 4096): `nb` (dropped) now and then."""
    return jnp.where(ids % 64 == 63, nb, ids % nb)


def _counts(nb):
    return lambda ids, v, w: agg_ops.bucket_counts(_ids(ids, nb), w, nb)


def _sub(nb, inv):
    """`stats` under the cell's buckets; `extended_stats` (the squares too)
    at 256."""
    def fn(ids, v, w):
        out = agg_ops.bucketed_sub_metric(_ids(ids, nb), v, w, nb, inv,
                                          nb == 256)
        out.pop("scale")
        return out
    return fn


def _timed(fn, args, reps):
    """(output as numpy, median ms of `reps` launches with their read)."""
    jfn = jax.jit(fn)
    out = jax.tree_util.tree_map(np.asarray, jfn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.tree_util.tree_map(np.asarray, jfn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, float(np.median(times))


def _forms(monkeypatch, make, args):
    """(dense output, ms), (scatter output, ms) of one entry."""
    monkeypatch.setattr(agg_ops, "_DENSE_BUCKETS", 1 << 30)
    dense = _timed(make(), args, 7)
    monkeypatch.setattr(agg_ops, "_DENSE_BUCKETS", 0)
    monkeypatch.setattr(agg_ops, "_PRODUCT_BUCKETS", 0)
    scatter = _timed(make(), args, 2)
    return dense, scatter


def _equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and np.array_equal(x, y) for x, y in zip(la, lb))


@pytest.mark.parametrize("nb", [101, 256, 366])
def test_the_chosen_form_equals_the_scatter_at_the_cells_size(
        rows, monkeypatch, nb):
    (ids_h, v_h, w_h), dev, inv = rows
    assert agg_ops.count_form(nb) == "dense"    # the form the program takes
    chosen = jax.tree_util.tree_map(np.asarray, jax.jit(_sub(nb, inv))(*dev))
    (dense, dense_ms), (scatter, scatter_ms) = _forms(
        monkeypatch, lambda: _sub(nb, inv), dev)
    assert _equal(chosen, dense) and _equal(dense, scatter)
    b_h = np.where(ids_h % 64 == 63, nb, ids_h % nb)
    ok = (w_h > 0) & (b_h < nb)
    assert np.array_equal(dense["count"], np.bincount(b_h[ok], minlength=nb))
    want = np.bincount(b_h[ok], weights=v_h[ok].astype(np.float64),
                       minlength=nb)
    got = agg_ops.limb_sums_to_f64(dense["sum"], inv)
    assert np.abs(got - want).max() <= 1e-12 * np.abs(want).max()
    print(f"bucketed_sub_metric n={N} nbuckets={nb}: dense {dense_ms:.2f} ms,"
          f" scatter {scatter_ms:.1f} ms (launch + read, median)")
    (dense, dense_ms), (scatter, scatter_ms) = _forms(
        monkeypatch, lambda: _counts(nb), dev)
    assert _equal(dense, scatter)
    assert np.array_equal(dense, np.bincount(b_h[ok], minlength=nb))
    print(f"bucket_counts n={N} nbuckets={nb}: dense {dense_ms:.2f} ms,"
          f" scatter {scatter_ms:.1f} ms (launch + read, median)")


@pytest.mark.parametrize("nb", [1023, 2047, 4096])
def test_where_the_constant_stands(rows, monkeypatch, nb):
    """Both forms on either side of `_DENSE_BUCKETS`: the dense form's time
    grows with the buckets and the scatter's does not; the constant lies
    where the dense form still wins by a wide margin."""
    _host, dev, inv = rows
    (dense, d_sub), (scatter, s_sub) = _forms(
        monkeypatch, lambda: _sub(nb, inv), dev)
    assert _equal(dense, scatter)
    (dense, d_cnt), (scatter, s_cnt) = _forms(
        monkeypatch, lambda: _counts(nb), dev)
    assert _equal(dense, scatter)
    print(f"n={N} nbuckets={nb}: bucketed_sub_metric dense {d_sub:.1f} ms,"
          f" scatter {s_sub:.1f}; bucket_counts dense {d_cnt:.1f} ms,"
          f" scatter {s_cnt:.1f}")
    monkeypatch.undo()
    if agg_ops.count_form(nb) == "dense":
        assert d_sub < s_sub and d_cnt < s_cnt
