"""Approximate kNN: balanced IVF-flat, the TPU-native ANN layout.

Reference analog: the k-NN plugin's ANN indexes (HNSW/faiss — graph walks
with data-dependent branching, a shape XLA cannot tile). The TPU-first
design is inverted-file with BALANCED clusters instead:

- Build: k-means on device (chunked Lloyd iterations — assignment is one
  [B,D]x[D,nlist] MXU matmul per block, centroid update a scatter-add),
  then a vectorized host pass that caps every cluster at `cap` rows,
  spilling overflow to the row's second-best cluster (the ScaNN-style
  trade: bounded list length buys static shapes and dense DMA).
- Layout: the build's product is `lists`, a DENSE i32[nlist, cap] matrix of
  doc ids (-1 padded), which stays on the host. On the device a list's
  ROWS lie next to one another (`list_rows`): one compact matrix
  f32[rows + cap, D], list 0's rows, then list 1's, ..., and a tail of
  `cap` zero rows so that a window of `cap` rows from the last list's
  start stays in bounds; beside it each slot's doc id (i32[rows + cap],
  -1 in the tail) and each list's first slot and fill (`order`, `offset`,
  `fill` below). A list starts wherever the one before it ends: on the
  chip a window that starts off a multiple of 8 rows (the float32 tile's
  sublanes) reads no slower than one on it (tests_tpu/test_knn_tpu.py).
  Compact, not [nlist, cap, D]: at slack 1.5 the padded form is half again
  the size of the vectors. The copy costs what the vectors cost (3.07 GB a
  million rows of 768 floats), beside the doc-ordered matrix the exact
  scan and every other reader keep.
- Search (in search/compiler.py emit "knn"): centroid matvec -> static
  top-nprobe -> for each probed list the window of `cap` rows at its
  offset, read in place by the scoring product (rows past the list's fill
  belong to the next list and are masked) -> scatter scores back into the
  dense per-doc score space, so ANN kNN composes with every other plan
  node (bool, filters, aggs) exactly like the exact path. No dynamic
  shapes anywhere: a probe is `nprobe` dense slices, never a fetch by doc
  id.

Setting nprobe = nlist provably recovers the exact search (every row is
in exactly one list), which the tests assert.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Optional

import numpy as np

from ..utils.metrics import METRICS, CounterGroup

# what the builds of the process cost and made (`build_ivf` adds at its
# end): `build_s` wall seconds, k-means, assignment and the balanced fill;
# `rows` the present rows filed; `spilled_rows` those that did not fit
# their nearest list and went to the second-best (or, rarely, to any list
# with room): a probe that would have found them in their own list has to
# reach the other one; `nlist` / `cap` of the last build. `list_rows` adds
# its seconds (the device gather's compile and launch) to `build_s` and
# sets `list_rows_bytes`, the resident bytes of the last list-ordered copy
IVF_STATS = CounterGroup(METRICS, "ivf", {"build_s": 0.0, "rows": 0,
                                          "spilled_rows": 0, "nlist": 0,
                                          "cap": 0, "list_rows_bytes": 0})


@dataclass
class IvfIndex:
    centroids: np.ndarray   # f32[nlist, D] (same space as the scored matrix)
    lists: np.ndarray       # i32[nlist, cap], -1 = empty slot
    nlist: int
    cap: int
    default_nprobe: int
    # the lists end to end, as the device keeps their rows (`list_rows`)
    order: np.ndarray       # i32[rows + cap]: a slot's doc id, -1 = no row
    offset: np.ndarray      # i32[nlist]: a list's first slot
    fill: np.ndarray        # i32[nlist]: its rows (slots offset .. offset+fill)


def _next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


_BLOCK = 8192


def _blocked(a):
    """[N, ...] -> [nb, B, ...] with B the scan's block (N is a multiple of
    it: `build_ivf` sees to that). Inside a jit this reshape moves nothing,
    so a build reads the caller's matrix where it lies."""
    return a.reshape(-1, min(_BLOCK, a.shape[0]), *a.shape[1:])


def _kmeans_device(vals, pres, init, iters: int):
    """Lloyd iterations over the rows in blocks. vals: f32[N, D],
    pres: f32[N], init: f32[nlist, D]. Returns f32[nlist, D]."""
    import jax.numpy as jnp
    from jax import lax

    nlist = init.shape[0]
    vals_b, pres_b = _blocked(vals), _blocked(pres)

    def one_iter(cents, _):
        csq = jnp.sum(cents * cents, axis=1)  # [nlist]

        def block(carry, blk):
            sums, counts = carry
            v, p = blk
            # ||v-c||^2 up to a per-row constant: -2 v.c + ||c||^2
            d2 = csq - 2.0 * jnp.dot(v, cents.T,
                                     preferred_element_type=jnp.float32)
            a = jnp.argmin(d2, axis=1)
            a = jnp.where(p > 0, a, nlist)      # absent rows drop out of bounds
            sums = sums.at[a].add(v * p[:, None], mode="drop")
            counts = counts.at[a].add(p, mode="drop")
            return (sums, counts), None

        (sums, counts), _ = lax.scan(
            block, (jnp.zeros_like(cents), jnp.zeros(nlist, jnp.float32)),
            (vals_b, pres_b))
        newc = sums / jnp.maximum(counts, 1.0)[:, None]
        return jnp.where((counts > 0)[:, None], newc, cents), None

    cents, _ = lax.scan(one_iter, init, None, length=iters)
    return cents


def _assign_top2_device(vals, cents):
    """Per row: (best cluster, 2nd-best cluster, best distance).
    vals: f32[N, D] -> (i32[N], i32[N], f32[N])."""
    import jax.numpy as jnp
    from jax import lax

    csq = jnp.sum(cents * cents, axis=1)
    vals_b = _blocked(vals)

    def block(_, v):
        d2 = csq - 2.0 * jnp.dot(v, cents.T,
                                 preferred_element_type=jnp.float32)
        a1 = jnp.argmin(d2, axis=1)
        d1 = jnp.min(d2, axis=1)
        d2b = d2.at[jnp.arange(v.shape[0]), a1].set(jnp.inf)
        a2 = jnp.argmin(d2b, axis=1)
        return None, (a1.astype(jnp.int32), a2.astype(jnp.int32), d1)

    _, (a1, a2, d1) = lax.scan(block, None, vals_b)
    return a1.reshape(-1), a2.reshape(-1), d1.reshape(-1)


def build_ivf(values, present: np.ndarray,
              nlist: Optional[int] = None, nprobe: Optional[int] = None,
              iters: int = 8, seed: int = 0, slack: float = 1.5
              ) -> Optional[IvfIndex]:
    """values: f32[N, D] — pass the SAME matrix the scorer uses (unit-normed
    for cosine) so centroid geometry matches search geometry. A host array
    is padded and put on the device for the build; a device array (the
    segment's resident matrix, whose row count is a power of two; rows
    past `present` are padding) is read where it lies: the build then
    holds no copy of the vectors of its own. `present`: bool[n], host."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    present = np.asarray(present, bool)
    n = min(values.shape[0], len(present))
    pres_idx = np.nonzero(present[:n])[0]
    npres = len(pres_idx)
    if npres == 0:
        return None
    nlist = int(min(nlist or max(1, round(npres ** 0.5)), npres))
    cap = max(1, int(np.ceil(npres * slack / nlist)))
    default_nprobe = int(min(nprobe or max(1, nlist // 8), nlist))

    rng = np.random.default_rng(seed)
    chosen = rng.choice(pres_idx, nlist, replace=False)
    if isinstance(values, np.ndarray):
        # block + pad for the scan (padded rows carry weight 0)
        values = np.asarray(values, np.float32)
        npad = ((n + _BLOCK - 1) // _BLOCK) * _BLOCK if n > _BLOCK else n
        vb = np.zeros((npad, values.shape[1]), np.float32)
        vb[:n] = values[:n]
        vals = jnp.asarray(vb)
        del vb
    else:
        vals = values
        npad = vals.shape[0]
        if npad % min(_BLOCK, npad):
            raise ValueError(f"a device matrix of {npad} rows does not "
                             f"split into blocks of {_BLOCK}")
    pb = np.zeros(npad, np.float32)
    pb[:n] = present[:n]
    init = vals[jnp.asarray(chosen)]

    kmeans = jax.jit(partial(_kmeans_device, iters=iters))
    cents = kmeans(vals, jnp.asarray(pb), init)
    a1, a2, d1 = jax.jit(_assign_top2_device)(vals, cents)
    del vals
    cents = np.asarray(cents)
    a1 = np.asarray(a1)[:n]
    a2 = np.asarray(a2)[:n]
    d1 = np.asarray(d1)[:n]

    # ---- balanced fill (vectorized host pass) ----
    # round 1: rows claim their primary cluster, closest-first
    lists = np.full((nlist, cap), -1, np.int32)
    fill = np.zeros(nlist, np.int64)
    rows = pres_idx[np.lexsort((d1[pres_idx], a1[pres_idx]))]
    c = a1[rows]
    # rank of each row within its cluster run
    starts = np.searchsorted(c, np.arange(nlist))
    rank = np.arange(len(rows)) - starts[c]
    keep = rank < cap
    kept_rows, kept_c, kept_rank = rows[keep], c[keep], rank[keep]
    lists[kept_c, kept_rank] = kept_rows
    fill = np.bincount(kept_c, minlength=nlist).astype(np.int64)

    # round 2: spilled rows go to their 2nd-best cluster if it has room
    spill = rows[~keep]
    if len(spill):
        c2 = a2[spill]
        order2 = np.argsort(c2, kind="stable")
        spill, c2 = spill[order2], c2[order2]
        starts2 = np.searchsorted(c2, np.arange(nlist))
        rank2 = (np.arange(len(spill)) - starts2[c2]) + fill[c2]
        keep2 = rank2 < cap
        lists[c2[keep2], rank2[keep2]] = spill[keep2]
        fill = np.bincount(c2[keep2], minlength=nlist).astype(np.int64) + fill
        # round 3 (rare): round-robin into whatever still has room
        left = spill[~keep2]
        if len(left):
            open_slots = np.nonzero(lists.reshape(-1) == -1)[0]
            take = open_slots[: len(left)]
            lists.reshape(-1)[take] = left
    IVF_STATS.inc("build_s", time.perf_counter() - t0)
    IVF_STATS.inc("rows", npres)
    IVF_STATS.inc("spilled_rows", int(len(spill)))
    IVF_STATS["nlist"], IVF_STATS["cap"] = nlist, cap
    order, offset, fill = _list_order(lists)
    return IvfIndex(centroids=cents, lists=lists, nlist=nlist, cap=cap,
                    default_nprobe=default_nprobe, order=order,
                    offset=offset, fill=fill)


def _list_order(lists: np.ndarray):
    """(order, offset, fill) of `IvfIndex`: list l's filled slots (a prefix
    of its row: every round of the fill appends) go to slots
    offset[l] .. offset[l] + fill[l]."""
    filled = lists >= 0
    fill = filled.sum(axis=1)
    order = np.concatenate([lists[filled],
                            np.full(lists.shape[1], -1, np.int32)])
    return (order, (np.cumsum(fill) - fill).astype(np.int32),
            fill.astype(np.int32))


def _rows_in_order(mat, order):
    import jax.numpy as jnp

    return jnp.where((order >= 0)[:, None], mat[jnp.maximum(order, 0)], 0.0)


def list_rows(mat, order):
    """The rows in list order, made on the device: `mat[order]`, zero where
    a slot holds no row. mat: f32[N, D] (the resident doc-ordered matrix),
    order: i32[S] on the same device -> f32[S, D]. The vectors never come
    back to the host; the gather's seconds count as build (its compile and
    launch: the caller holds the segment's build lock, so nothing waits
    here for the device)."""
    import jax

    t0 = time.perf_counter()
    rows = jax.jit(_rows_in_order)(mat, order)
    IVF_STATS.inc("build_s", time.perf_counter() - t0)
    IVF_STATS["list_rows_bytes"] = int(rows.nbytes)
    return rows
