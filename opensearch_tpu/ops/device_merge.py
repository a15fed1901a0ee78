"""Device-side multiway sorted-run merge: the compute core of segment
merging (reference: Lucene SegmentMerger's doc-id remap + postings merge).

The merge pipeline (index/merge.py) is: remap each input segment's postings
to (union_row, new_doc, tf) triples, sort them lexicographically, and slice
CSR runs. The sort is the O(P log P) hot part — this module runs it on the
TPU as a two-key `lax.sort` over the concatenated runs, carrying the tf and
a source-index payload so the host can regather ragged position runs with
the SAME order (bit-identical output to the numpy path).

Shapes are pow2-padded; invalid padding sorts to the end via row = n_rows.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

# below this many postings the device round trip costs more than numpy
DEVICE_MERGE_MIN = 1 << 16


@partial(jax.jit, static_argnames=("n_rows",))
def _sort_runs(rows, docs, tfs, src, n_rows: int):
    r, d, t, s = jax.lax.sort((rows, docs, tfs, src), num_keys=2,
                              is_stable=True)
    counts = jnp.zeros(n_rows + 1, jnp.int32).at[jnp.minimum(r, n_rows)].add(
        jnp.where(r < n_rows, 1, 0))
    return r, d, t, s, counts


def merge_sorted_runs(rows: np.ndarray, docs: np.ndarray, tfs: np.ndarray,
                      n_rows: int
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray, np.ndarray]:
    """-> (rows, docs, tfs, order, per-row counts), sorted by (row, doc).

    `order` is the permutation applied (positions regather uses it).
    Equivalent to np.lexsort((docs, rows)) + bincount, executed on device.
    """
    n = len(rows)
    pad = 1 << int(np.ceil(np.log2(max(n, 2))))
    # bucket the static row count too, or every new vocab-union size would
    # recompile _sort_runs; padding rows sort as n_rows_pad (past all valid)
    n_rows_pad = 1 << int(np.ceil(np.log2(max(n_rows, 2))))
    rows_p = np.full(pad, n_rows_pad, np.int32)
    rows_p[:n] = rows           # the assignment casts int64 -> int32
    docs_p = np.zeros(pad, np.int32)
    docs_p[:n] = docs
    tfs_p = np.zeros(pad, np.float32)
    tfs_p[:n] = tfs
    src_p = np.arange(pad, dtype=np.int32)
    r, d, t, s, counts = _sort_runs(rows_p, docs_p, tfs_p, src_p, n_rows_pad)
    r = np.asarray(r)[:n]
    d = np.asarray(d)[:n]
    t = np.asarray(t)[:n]
    s = np.asarray(s)[:n]
    counts = np.asarray(counts)[:n_rows]
    return r, d, t, s, counts


def use_device_merge(total_postings: int) -> bool:
    return total_postings >= DEVICE_MERGE_MIN


# ---------------------------------------------------------------------
# codec v2: device-side impact quantization (index/refresh/merge time)
# ---------------------------------------------------------------------
#
# The eager-impact build (index/segment.py build_impact_plane) is an O(P)
# dense map — exactly the shape the device does at HBM bandwidth while the
# host packer is busy. The f32 expression mirrors the host oracle
# (fastpath._exact_rescore) so the quantization-error bound measured
# against the exact serve domain holds for either build path; the plane
# only steers candidate selection and prune bounds, so host/device build
# parity is a quality property, not a correctness requirement (the
# impact ladder's certify-or-escalate rungs keep served pages oracle-
# exact regardless — see docs/INDEX_FORMAT.md).

DEVICE_IMPACT_MIN = 1 << 16


@partial(jax.jit, static_argnames=("k1", "b", "qmax"))
def _quantize_impacts(tfs, dl_of, avgdl, k1: float, b: float, qmax: int):
    kfac = k1 * (1.0 - b + b * dl_of / avgdl)
    imp = tfs / (tfs + kfac)
    m = jnp.max(imp, initial=jnp.float32(0.0))
    scale = jnp.where(m > 0, m / qmax, 1.0).astype(jnp.float32)
    q = jnp.minimum(jnp.round(imp / scale), qmax).astype(jnp.int32)
    return q, scale


def quantize_impacts(tfs: np.ndarray, dl_of: np.ndarray, k1: float,
                     b: float, avgdl: float, qmax: int
                     ) -> Tuple[np.ndarray, float]:
    """-> (q i32[P], scale): quantized eager impacts computed on device.
    Shapes are pow2-padded (tf=0 padding quantizes to 0) so segment sizes
    don't storm the jit cache."""
    n = len(tfs)
    pad = 1 << int(np.ceil(np.log2(max(n, 2))))
    tfs_p = np.zeros(pad, np.float32)
    tfs_p[:n] = tfs
    dl_p = np.zeros(pad, np.float32)
    dl_p[:n] = dl_of
    q, scale = _quantize_impacts(tfs_p, dl_p,
                                 np.float32(max(avgdl, 1e-9)),
                                 float(k1), float(b), int(qmax))
    return np.asarray(q)[:n], float(np.asarray(scale))


def use_device_impacts(total_postings: int) -> bool:
    return total_postings >= DEVICE_IMPACT_MIN
