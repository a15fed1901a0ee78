"""`ops.aggs.run_counts`: per-bucket counts of a plane whose ids are
non-decreasing in row order, read as differences of prefix sums at the
runs' boundaries, against `np.bincount` and the scatter-add
(`bucket_counts`) it stands in for; and `planes.run_starts`, which
observes the order and places the boundaries."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from opensearch_tpu.ops import aggs as agg_ops
from opensearch_tpu.search import planes as PN


def _ids(kind: str, n: int, nb: int, rng) -> np.ndarray:
    """Sorted bucket ids of `n` rows, some rows without a value (-1)."""
    if kind == "spread":            # every bucket likely held
        ids = np.sort(rng.integers(0, nb, n))
    elif kind == "empty_buckets":   # three buckets of nb hold every row
        ids = np.sort(rng.choice([0, nb // 2, nb - 1], n))
    elif kind == "one_bucket":
        ids = np.full(n, nb // 3)
    elif kind == "block_edges":     # runs that begin where a block does
        _r, c = agg_ops.run_blocks(n, nb + 1)
        edges = np.sort(rng.choice(np.arange(0, n + 1, c), nb - 1))
        ids = np.searchsorted(edges, np.arange(n), side="right")
    else:
        raise AssertionError(kind)
    ids = ids.astype(np.int32)
    if kind != "block_edges":
        ids[rng.random(n) < 0.1] = -1       # interleaved rows with no value
    return ids


def _mask(kind: str, n: int, rng) -> np.ndarray:
    return {"all0": np.zeros(n), "all1": np.ones(n),
            "random": rng.random(n) < 0.4}[kind].astype(np.float32)


@pytest.mark.parametrize("mask", ["all0", "all1", "random"])
@pytest.mark.parametrize("ids_kind", ["spread", "empty_buckets",
                                      "one_bucket", "block_edges"])
@pytest.mark.parametrize("n,nb", [(1 << 15, 37), (4096, 6), (256, 5),
                                  (1 << 17, 24)])
def test_run_counts_equal_bincount_and_the_scatter_add(n, nb, ids_kind, mask):
    rng = np.random.default_rng([n, nb, len(ids_kind), len(mask)])
    ids, m = _ids(ids_kind, n, nb, rng), _mask(mask, n, rng)
    starts = PN.run_starts(ids, nb, n)
    assert starts is not None and starts.dtype == np.int32
    assert starts.shape == (nb + 1,) and starts[-1] == n
    assert (np.diff(starts) >= 0).all()
    held = (m > 0) & (ids >= 0)
    got = np.asarray(jax.jit(agg_ops.run_counts)(
        jnp.asarray(held.astype(np.int32)), jnp.asarray(starts)))
    assert got.dtype == np.int32 and got.shape == (nb,)
    assert np.array_equal(got, np.bincount(ids[held], minlength=nb))
    scatter = agg_ops.bucket_counts(jnp.asarray(np.where(held, ids, nb)),
                                    jnp.asarray(m), nb)
    assert np.array_equal(got, np.asarray(scatter))


@pytest.mark.parametrize("starts", [
    [0, 2048, 4096, 8192],              # every boundary on a block edge
    [0, 0, 0, 8192],                    # empty runs at the front
    [8192, 8192, 8192, 8192],           # every run empty: start == n
    [5, 2047, 2049, 8000],              # rows before starts[0], after the last
])
def test_a_boundary_on_a_block_edge_or_at_the_end(starts):
    n = 8192
    assert agg_ops.run_blocks(n, 4) == (4, 2048)
    w = (np.random.default_rng(3).random(n) < 0.5).astype(np.int32)
    got = np.asarray(agg_ops.run_counts(jnp.asarray(w),
                                        jnp.asarray(starts, jnp.int32)))
    pre = np.concatenate([[0], np.cumsum(w)])
    assert np.array_equal(got, np.diff(pre[np.asarray(starts)]))


@pytest.mark.parametrize("n,nbounds,cut", [
    (1 << 26, 2113, (32768, 2048)),     # the log-analytics cell's plane
    (32768, 2113, (4096, 8)),           # the same hours over 20,000 rows
    (16, 2, (2, 8)),
    (1000, 4, (125, 8)),
    (1001, 4, None),                    # odd: no power of two divides it
    (64, 101, None),                    # more boundaries than rows
    (4100, 3, None),                    # 4 is the largest power that divides
])
def test_the_cut_follows_from_the_static_sizes(n, nbounds, cut):
    assert agg_ops.run_blocks(n, nbounds) == cut
    if cut is not None:
        r, c = cut
        assert r * c == n and c & (c - 1) == 0 and nbounds * c <= max(n, c)


@pytest.mark.parametrize("n,nb", [(1001, 3), (64, 100), (4100, 2)])
def test_where_no_cut_can_be_built_the_scatter_add_counts(n, nb):
    """The fall-back, chosen from the static sizes alone: the ids are read
    back from the boundaries and scatter-added."""
    rng = np.random.default_rng(n)
    ids = np.sort(rng.integers(0, nb, n)).astype(np.int32)
    ids[: n // 10] = -1                 # rows before the first run
    # rows after the last run count nothing
    starts = np.minimum(np.searchsorted(ids, np.arange(nb + 1)),
                        n - 7).astype(np.int32)
    w = (rng.random(n) < 0.5).astype(np.int32)
    assert agg_ops.run_blocks(n, nb + 1) is None
    got = np.asarray(jax.jit(agg_ops.run_counts)(jnp.asarray(w),
                                                 jnp.asarray(starts)))
    pre = np.concatenate([[0], np.cumsum(w)])
    assert got.dtype == np.int32
    assert np.array_equal(got, np.diff(pre[starts]))
    assert PN.run_starts(ids, nb, n) is None


@pytest.mark.parametrize("ids,want", [
    ([0, 0, 1, 3, 3], [0, 2, 3, 3, 5]),
    ([-1, 0, -1, 2, -1], [1, 3, 3, 5]),          # -1 rows carry no order
    ([-1, -1, 1, 1], [2, 2, 4]),                 # bucket 0 empty
    ([2, 1, 2], None),                           # out of order
    ([0, -1, 1, -1, 0], None),                   # out of order across a gap
])
def test_run_starts_observes_the_order_of_the_rows_that_have_a_value(ids,
                                                                     want):
    ids = np.asarray(ids, np.int32)
    nb = int(ids.max()) + 1
    got = PN.run_starts(ids, nb, 1024)
    if want is None:
        assert got is None
    else:
        assert got.tolist() == want
        # the runs the boundaries spell hold exactly each bucket's rows
        for b in range(nb):
            rows = np.flatnonzero(ids == b)
            assert ((rows >= got[b]) & (rows < got[b + 1])).all()
