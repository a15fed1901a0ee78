"""TPU-hardware check of `ops.aggs.run_counts` at the log-analytics cell's
size: 67,108,864 rows (49,449,819 of them events, the rest padding) in 2,112
buckets of sorted ids, against numpy, exact in int32, for a window, a random
mask and every row; and against the scatter-add it stands in for. Run on a
real chip: `python -m pytest tests_tpu/test_run_counts_tpu.py -q`."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from opensearch_tpu.ops import aggs as agg_ops
from opensearch_tpu.search import planes as PN

pytestmark = pytest.mark.skipif(jax.default_backend() != "tpu",
                                reason="needs a real TPU chip")

N, NDOCS, NB = 1 << 26, 49_449_819, 2112


@pytest.fixture(scope="module")
def plane():
    """(host ids i32[N] with -1 rows, device ids, device starts)."""
    rng = np.random.default_rng(31)
    cuts = np.sort(rng.integers(0, NDOCS, NB - 1))
    ids = np.full(N, -1, np.int32)
    ids[:NDOCS] = np.searchsorted(cuts, np.arange(NDOCS), side="right")
    ids[:NDOCS][rng.random(NDOCS) < 0.01] = -1      # rows without a value
    starts = PN.run_starts(ids[:NDOCS], NB, N)
    assert starts is not None and starts[-1] == NDOCS
    return ids, jnp.asarray(ids), jnp.asarray(starts)


def _counts(match, ids, starts):
    held = (match > 0) & (ids >= 0)
    return agg_ops.run_counts(held.astype(jnp.int32), starts)


@pytest.mark.parametrize("mask", ["window", "random", "all"])
def test_run_counts_equal_numpy_at_the_cells_size(plane, mask):
    ids_h, ids, starts = plane
    rng = np.random.default_rng(7)
    m = {"window": lambda: ((np.arange(N) >= 20_000_000)
                            & (np.arange(N) < 31_000_000)),
         "random": lambda: rng.random(N) < 0.3,
         "all": lambda: np.ones(N, bool)}[mask]().astype(np.float32)
    want = np.bincount(ids_h[(m > 0) & (ids_h >= 0)], minlength=NB)
    fn = jax.jit(_counts)
    dm = jnp.asarray(m)
    got = np.asarray(fn(dm, ids, starts))
    assert got.dtype == np.int32 and np.array_equal(got, want)
    assert int(got.sum()) == int(((m > 0) & (ids_h >= 0)).sum())
    times = []
    for _ in range(10):
        t0 = time.perf_counter()
        np.asarray(fn(dm, ids, starts))
        times.append((time.perf_counter() - t0) * 1e3)
    print(f"run_counts n={N} nbuckets={NB} mask={mask}: launch + read "
          f"median {np.median(times):.3f} ms (min {min(times):.3f})")


def test_the_scatter_add_agrees_and_is_what_it_replaces(plane):
    ids_h, ids, starts = plane
    m = jnp.ones(N, jnp.float32)

    def scatter(match, ids):
        held = (match > 0) & (ids >= 0)
        return agg_ops.bucket_counts(jnp.where(held, ids, NB), held, NB)
    fn = jax.jit(scatter)
    got = np.asarray(fn(m, ids))
    assert np.array_equal(got, np.asarray(jax.jit(_counts)(m, ids, starts)))
    t0 = time.perf_counter()
    np.asarray(fn(m, ids))
    print(f"bucket_counts (scatter-add) n={N} nbuckets={NB}: launch + read "
          f"{(time.perf_counter() - t0) * 1e3:.1f} ms")
