"""Device-side phase-2 exact rescore — the escalation ladder's middle rung
without the host round trip (the device analog of Lucene re-walking a WAND
candidate, reference `search/query/QueryPhase.java` two-phase iteration).

`search/fastpath.py`'s pruned pipeline escalates a clamped query by exact-
rescoring a CANDIDATE UNION (every doc any impact head mentions, ≤ T·4·L_HEAD
ids) against the FULL posting rows. The r5 implementation was a host numpy
pass (`_exact_rescore`) sandwiched between kernel launches: per escalated
query, T vectorized `searchsorted`s over rows that can span millions of
postings — serialized on the host exactly when the query is already slowest.
This module moves that pass onto the device as ONE jit launch batched across
the whole escalation queue:

    per (query, term, candidate):  branchless lower-bound binary search over
    the term's CSR window in the ALREADY-RESIDENT aligned postings buffers
    (the same `AlignedPostings.d_docs/d_tfdl` the dense scorer DMAs from) —
    no new device-resident state, no per-query transfer beyond the padded
    candidate ids — then gather packed (tf, dl), decode, and accumulate
    exact f32 BM25 + per-term match counts.

PROBE DEPTH: a lower_bound over a window of `len` postings is done after
exactly `len.bit_length()` halvings; deeper changes no byte. Where the plane
lives decides the form (`plane_in_vmem`, a static property of the plane):
  * a plane too large for VMEM (a 2.2M-document shard: 460 MB) is probed
    from HBM, where a launch's time IS its gather count (24-29 ns a
    gathered element at 8,192 to 262,144 elements a gather, the same in a
    `while` as unrolled). Each term slot `t` runs its own `lax.fori_loop`
    (one `while`, trip count the traced operand `rounds[t]`) over its
    [QB, C] bounds: as deep as the longest row any query of the launch has
    in that slot (`probe_rounds`), not at all for a slot no query has. The
    depth is an operand, never a compile key.
  * a plane of up to 112 MiB (a 171k-document collection: 97 MB) is
    prefetched into VMEM once a launch, but only for gathers in the entry
    computation: 7 ns an element unrolled, 26 ns inside a `while`. There
    the search stays unrolled to the plane's own bit length over
    [QB, T, C]; halving the probes at 3.7 x the cost each would lose.
`RESCORE_STATS["device_probe_elems"]` counts what the searches gathered,
QB * C * sum(rounds) a launch, in either form.

Why `jnp` and not a Pallas kernel: the access pattern is C·T independent
binary searches (dependent random gathers each) — there is no contiguous
DMA window to stage into VMEM, which is the only thing the fused scorer's
Pallas formulation buys. On a v5e the probes were 86% of a (T 4, C 8192)
launch over the large plane at the plane's 27 rounds and are 67% at the
rows' own 10-14 (PERF.md, PR 26); what stays is the two [QB, T, C] gathers
after the search and the launch itself.

BIT-PARITY CONTRACT: the accumulation mirrors `fastpath._exact_rescore`
op-for-op in f32 (same expression shapes, same term order, weak-typed
scalars rounding at the same points), so `_tie_serves`/theta32 comparisons
made on device scores are bit-identical to the host oracle's. The host pass
stays as the `JAX_PLATFORMS=cpu` fallback and the parity oracle
(tests/test_rescore.py asserts exact equality, not allclose).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .pallas_bm25 import DL_BITS, DL_MASK, INT_SENTINEL, TF_MAX


@functools.partial(jax.jit, static_argnames=("T", "C", "k1", "b"))
def exact_rescore_batch(docs_hbm: jnp.ndarray, tfdl_hbm: jnp.ndarray,
                        starts: jnp.ndarray, lens: jnp.ndarray,
                        weights: jnp.ndarray, avgdl: jnp.ndarray,
                        cand: jnp.ndarray, rounds: jnp.ndarray,
                        T: int, C: int, k1: float, b: float):
    """Exact BM25 scores + match counts of candidate docs vs full rows.

    docs_hbm  i32[P] — aligned CSR doc ids (fastpath AlignedPostings.d_docs:
              each row doc-ascending within its true window)
    tfdl_hbm  i32[P] — packed tf << DL_BITS | dl per posting
    starts    i32[QB, T] — ELEMENT offset of each term's full-row window
    lens      i32[QB, T] — true posting count per window (0 = absent term)
    weights   f32[QB, T] — query-time idf * boost
    avgdl     f32[QB, 1]
    cand      i32[QB, C] — candidate doc ids, INT_SENTINEL padded
    rounds    i32[T] — `probe_rounds(lens, P)`: the probe depth per term
              slot of a plane in HBM (0 = no query of the launch has the
              slot; deeper than needed changes no byte); not read where the
              plane is small enough for VMEM
    k1, b     static similarity params (b pre-zeroed when norms are off)
    Returns (exact f32[QB, C], counts i32[QB, C]) — 0 on padding slots.
    """
    P = docs_hbm.shape[0]
    c = cand[:, None, :]

    def halve(lo, hi, ids):
        # mid = lo + (hi-lo)//2 keeps i32 safe for buffers past 2^30 elements
        mid = lo + (hi - lo) // 2
        go = docs_hbm[jnp.clip(mid, 0, P - 1)] < ids
        return jnp.where(go, mid + 1, lo), jnp.where(go, hi, mid)

    # lower_bound over [start, start+len): branchless bisection. A window of
    # `len` is empty after exactly `len.bit_length()` halvings, and further
    # ones do not move `pos_c` below. Two stages by `jax.named_scope`:
    # `rescore.probe` the searches, `rescore.score` all that follows
    with jax.named_scope("rescore.probe"):
        if plane_in_vmem(P):
            # every gather in the entry computation, so XLA prefetches the
            # plane into VMEM once: the plane's own depth over [QB, T, C],
            # unrolled
            lo = jnp.broadcast_to(starts[:, :, None], starts.shape + (C,))
            hi = lo + lens[:, :, None]
            for _ in range(int(P).bit_length()):
                lo, hi = halve(lo, hi, c)
        else:
            # one `while` per term slot over that slot's [QB, C] bounds,
            # `rounds[t]` trips deep
            los = []
            for t in range(T):
                lo_t = jnp.broadcast_to(starts[:, t, None], cand.shape)
                los.append(jax.lax.fori_loop(
                    0, rounds[t], lambda _, lo_hi: halve(*lo_hi, cand),
                    (lo_t, lo_t + lens[:, t, None]))[0])
            lo = jnp.stack(los, axis=1)
    with jax.named_scope("rescore.score"):
        end = (starts + lens)[:, :, None]
        # mirror the host's clamped probe: pos_c = min(pos, row_end - 1)
        pos_c = jnp.clip(jnp.minimum(lo, end - 1), 0, P - 1)
        found = ((docs_hbm[pos_c] == c) & (lens[:, :, None] > 0)
                 & (c < INT_SENTINEL))
        tfdl = tfdl_hbm[pos_c]
        tf = jnp.where(found, ((tfdl >> DL_BITS) & TF_MAX), 0
                       ).astype(jnp.float32)
        # the candidate's doc length, recovered from any matched posting (all
        # postings of one doc in one field carry the same dl; candidates are
        # head members, so a real candidate matches >= 1 full row). Padding /
        # no-match candidates get dl 0 — their contribution is masked to 0
        # anyway, matching the host oracle's zero output for them.
        dl_c = jnp.max(jnp.where(found, (tfdl & DL_MASK), 0),
                       axis=1).astype(jnp.float32)
        # EXACTLY `fastpath._exact_rescore`'s expression and evaluation order:
        # (1.0 - b) folds at trace time in f64 then rounds to f32 on the add,
        # the same NEP50 weak-scalar rounding the numpy pass performs
        avg = jnp.maximum(avgdl, jnp.float32(1e-9))           # [QB, 1]
        kfac = k1 * ((1.0 - b) + b * dl_c / avg)              # [QB, C] f32
        exact = jnp.zeros(kfac.shape, jnp.float32)
        counts = jnp.zeros(kfac.shape, jnp.int32)
        # term-order f32 accumulation: adding a masked 0.0f is an exact
        # identity on the non-negative partial sums, so skipped/absent slots
        # leave the running sum bit-identical to the host loop's
        for t in range(T):
            tft = tf[:, t, :]
            foundt = found[:, t, :]
            contrib = jnp.where(foundt,
                                weights[:, t:t + 1] * tft / (tft + kfac), 0.0)
            exact = exact + contrib.astype(jnp.float32)
            counts = counts + foundt.astype(jnp.int32)
    return exact, counts


# XLA prefetches an entry parameter of up to this many bytes into VMEM for
# the whole launch (v5e: its 128 MiB less the 16 MiB scoped to kernels), but
# only for uses in the entry computation, never for a `while` body's. A
# gather from a plane there costs a quarter of one from HBM (7 against 26 ns
# an element), which no probe depth in a `while` wins back.
VMEM_PLANE_BYTES = 112 << 20


def plane_in_vmem(P: int) -> bool:
    return 4 * P <= VMEM_PLANE_BYTES


def probe_rounds(lens: np.ndarray, P: int) -> np.ndarray:
    """`rounds` of a launch from its `lens` i32[QB, T] over a plane of `P`
    elements: per term slot, the bit length of the longest row any query of
    the launch has there; the plane's own bit length in every slot where the
    launch takes the unrolled form (`plane_in_vmem`)."""
    if plane_in_vmem(P):
        return np.full(lens.shape[1], int(P).bit_length(), np.int32)
    return np.asarray([int(n).bit_length() for n in lens.max(axis=0)],
                      np.int32)


def rescore_elem_budget(T: int, C: int, max_elems: int = 1 << 24) -> int:
    """Max queries per launch so the [QB, T, C] probe intermediates stay
    inside a bounded HBM transient (~max_elems * ~16B live at the widest
    point). The fastpath splits bigger batches into sequential launches.
    Returned as a POWER OF TWO: the caller pads QB to pow2, so a non-pow2
    step would let the padded launch overshoot the budget by up to 2x."""
    n = max(1, max_elems // max(T * C, 1))
    return 1 << (n.bit_length() - 1)


def host_exact_rescore_batch(docs: np.ndarray, tfdl: np.ndarray,
                             starts: np.ndarray, lens: np.ndarray,
                             weights: np.ndarray, avgdl: np.ndarray,
                             cand: np.ndarray, k1: float, b: float):
    """Numpy mirror of `exact_rescore_batch` over the SAME padded operands —
    the parity oracle tests pin the device path against (the per-query
    production host path stays `fastpath._exact_rescore`)."""
    QB, C = cand.shape
    T = starts.shape[1]
    exact = np.zeros((QB, C), np.float32)
    counts = np.zeros((QB, C), np.int32)
    for q in range(QB):
        valid = cand[q] < INT_SENTINEL
        dl_c = np.zeros(C, np.float32)
        tf_q = np.zeros((T, C), np.float32)
        found_q = np.zeros((T, C), bool)
        for t in range(T):
            a = int(starts[q, t])
            ln = int(lens[q, t])
            if ln <= 0:
                continue
            rowdocs = docs[a: a + ln]
            pos = np.searchsorted(rowdocs, cand[q])
            pos_c = np.minimum(pos, ln - 1)
            found = (rowdocs[pos_c] == cand[q]) & valid
            packed = tfdl[a + pos_c]
            tf_q[t] = np.where(found, (packed >> DL_BITS) & TF_MAX,
                               0.0).astype(np.float32)
            dl_c = np.maximum(dl_c, np.where(found, packed & DL_MASK,
                                             0).astype(np.float32))
            found_q[t] = found
        kfac = k1 * (1.0 - b + b * dl_c / max(float(avgdl[q, 0]), 1e-9))
        for t in range(T):
            tft = tf_q[t]
            contrib = np.where(found_q[t],
                               np.float32(weights[q, t]) * tft
                               / (tft + kfac), 0.0).astype(np.float32)
            exact[q] += contrib
            counts[q] += found_q[t]
    return exact, counts
