"""Codec-v2 eager-impact serving path: quantized gather → scatter-add
with device block-max pruning, certified exact against the f32 oracle.

The XLA hot path for plain BM25 term/match top-k (the same shape class
`search/fastpath.py` serves through the Pallas kernels on TPU) over
codec-v2 segments (index/segment.py `ImpactPlane`). Per query:

1. **Plan (host).** The per-row block-max sidecar prices every
   IMPACT_BLOCK-posting block at `w_t · scale · block_max` and keeps the
   top-valued blocks until the kept posting mass covers the candidate
   window; every pruned block is *skipped at gather time* — its bytes
   never move (GPUSparse-style block-level metadata, arxiv 2606.26441).
   The pruned remainder is summarized as one sound scalar `B_rem =
   Σ_t w_t·scale·max(pruned block_max of t)`.
2. **First pass (device).** ONE jit program (compiler.build_impact_program,
   keyed by the codec layout: impact bits, block-slot bucket `B_pad`,
   candidate window): integer impact gather over the kept block windows,
   one row of IMPACT_BLOCK slots a block slot (a slot's block is its
   row: nothing is searched; a window is read as the two rows of the
   planes' [P / IMPACT_BLOCK, IMPACT_BLOCK] view it lies in, a select a
   lane, so no element is gathered alone; the `B_pad * IMPACT_BLOCK`
   slots in row-major order keep the plan's block order, each window
   rotated by its first lane, which reorders no document's additions:
   a block's postings are distinct documents), a single dequant
   multiply through `ops.scoring.dequant_impact` (weights pre-folded per
   block, broadcast along the row), scatter-add, masked top-C. No
   per-posting tf/doclen math anywhere — the BM25 saturation was
   evaluated at index time (BM25S eager scoring, arxiv 2407.03618).
3. **Certify (host).** Candidates are exact-rescored against the full
   f32 BM25 expression (the same arithmetic the v1 XLA program and the
   fastpath oracle serve — parity-tested bit-for-bit). The served window
   is proven exact when no non-candidate doc can displace it:
   `max(approx_floor + E + B_rem, B_rem) < θ`, where θ is the window
   boundary's exact score and `E` folds the quantization half-step,
   the build→query similarity-param drift bound and f32 accumulation
   slack (ImpactPlane.quant_err / drift_bound).
4. **Escalate.** A failed certificate first widens candidates to every
   doc any kept block mentions (the fastpath `_phase2_batch` trick — the
   union bound drops to `B_rem` alone), then falls back to the exact
   dense program (the caller reruns the v1-style XLA plan; codec v2
   promotes the tf plane lazily for exactly this rung).

Totals are exact (`eq`) on unpruned passes and a lower bound (`gte`)
under pruning — the same contract as the reference's default
track-total-hits cap; bodies with an explicit `track_total_hits` are
planned unpruned. Pruning also requires msm == 1 (a pruned pass cannot
count matched terms exactly; multi-msm queries ride the unpruned impact
pass, which still moves 5/6 bytes per slot instead of 8).

Served scores live in the HOST-ORACLE f32 domain (term-ordered numpy
accumulation — the same domain the fastpath ladder's rescued pages
serve). The XLA dense program may contract mul+add chains into FMA and
land ~1 ULP away on individual scores; page IDS and order agree. For
that reason the path only engages on MESH-LESS serving (see
`_MESH_ATTACHED`): a mesh-attached node's host loop must stay
byte-identical to its coalesced SPMD siblings.
"""

from __future__ import annotations

import contextvars
import os
from typing import List, Optional, Sequence

import numpy as np

from ..index.segment import (CODEC_V1, CODEC_V2, IMPACT_BLOCK, Segment,
                             next_pow2)
from ..obs import flight_recorder as _fr
from ..obs import insights as _ins
from ..obs import query_cost as _qc
from ..ops.scoring import dequant_impact_np
from ..utils.metrics import METRICS, CounterGroup
from ..utils.trace import TRACER
from .fastpath import _body_eligible, _ok_group

# candidate window floor for the first pass; the block prune keeps at
# least KEEP_FACTOR * C postings so the candidate pool stays deep enough
# to certify without an escalation on well-behaved corpora
CAND_FLOOR = 32
KEEP_FACTOR = 8
KEEP_MIN = 512

STATS = CounterGroup(METRICS, "impactpath", {
    "served": 0, "pruned_served": 0, "phase2_served": 0,
    "escalated": 0, "fallback": 0,
    "blocks_total": 0, "blocks_skipped": 0,
    "postings_total": 0, "postings_skipped": 0})


# bit-consistency gate: the impact ladder serves the HOST-ORACLE f32
# domain (term-ordered numpy accumulation); batched SPMD mesh programs
# and device-pinned replica searchers serve XLA's (FMA-contracted)
# domain, and the two can differ by ~1 ULP per posting. When a node's
# serving is multi-domain — an SPMD mesh owns the hot path (declines,
# scheduler bypasses and degradation retries must stay BYTE-identical to
# their coalesced siblings), or replica read copies round-robin with the
# primary — the node pins this contextvar around search_shards and the
# impact path stands down. Single-domain serving (no mesh, no replica
# copies: single-device nodes, the direct-path benches) gets the eager
# path unconditionally.
_MESH_ATTACHED: contextvars.ContextVar = contextvars.ContextVar(
    "impactpath_mesh_attached", default=False)


def mesh_attached_token(attached: bool):
    return _MESH_ATTACHED.set(bool(attached))


def reset_mesh_attached(token) -> None:
    _MESH_ATTACHED.reset(token)


def enabled() -> bool:
    if _MESH_ATTACHED.get():
        return False
    return not os.environ.get("OPENSEARCH_TPU_NO_IMPACT")


def stats() -> dict:
    return dict(STATS)


def block_skip_rate() -> float:
    """Fraction of sidecar blocks the device never gathered (planned
    queries only): `block_skip_rate` of `_nodes/stats` "impactpath"."""
    total = STATS["blocks_total"]
    return (STATS["blocks_skipped"] / total) if total else 0.0


class ImpactSpec:
    """A search the impact path can serve: the pure BM25 term-group
    top-k shape (kind "bm25") or the pure learned-sparse dot-product
    top-k shape over a feature-impact field (kind "sparse") — single
    unfiltered group, _score sort, no aggs."""

    __slots__ = ("lt", "window", "prune_ok", "kind")

    def __init__(self, lt, window: int, prune_ok: bool,
                 kind: str = "bm25"):
        self.lt = lt
        self.window = window
        self.prune_ok = prune_ok
        self.kind = kind


def _ok_sparse(lroot) -> bool:
    """LSparseDot usable as the sparse impact-ladder root: a plain
    `neural_sparse` dot product (non-negative token weights — the plan's
    witness/remainder bounds assume monotone contributions)."""
    from . import plan as PL

    if not isinstance(lroot, PL.LSparseDot):
        return False
    if not len(lroot.tokens):
        return False
    w = np.asarray(lroot.weights, np.float32)
    return bool(np.all(w >= 0)) and float(lroot.boost) >= 0.0


def make_spec(lroot, sort_specs: List[dict], agg_nodes, named_nodes,
              search_after, window: int, body: dict
              ) -> Optional[ImpactSpec]:
    if not enabled():
        return None
    if not _body_eligible(sort_specs, agg_nodes, named_nodes, search_after,
                          window, body):
        return None
    if _ok_group(lroot):
        # pruning changes total-hit semantics (lower bound, "gte") and
        # relaxed-msm counting is unsound — explicit total tracking or
        # msm > 1 ride the unpruned impact pass
        prune_ok = ("track_total_hits" not in body
                    and int(lroot.msm) <= 1)
        return ImpactSpec(lroot, int(window), prune_ok)
    if _ok_sparse(lroot):
        # learned-sparse: any-token match (msm == 1 semantics), so only
        # explicit total tracking blocks the prune
        return ImpactSpec(lroot, int(window),
                          "track_total_hits" not in body, kind="sparse")
    return None


# pruned-remainder budget as a fraction of θ̂: the per-term cut keeps
# Σ_t max(pruned_t) ≤ PRUNE_MARGIN·θ̂ < θ̂ ≤ θ2 (the θ̂-witness blocks are
# priced ≥ θ̂ > τ, so their docs are always in the phase-2 union), which
# makes a pruned plan certify by construction up to live/tie edge cases.
# 0.5 leaves enough headroom that the PHASE-1 certificate
# (approx_C + E + rem < θ) usually passes outright — the phase-2 union
# rescore stays an escalation rung, not a per-query tax; raising the
# margin prunes more and leans harder on phase 2.
PRUNE_MARGIN = 0.5

# ---------------------------------------------------------------------
# doc-range (live-block) pruning — the plan equal-idf multi-term
# queries need, and the one BP doc-id reordering (index/reorder.py)
# feeds. The per-term cut above is structurally blind to them: with T
# equal weights, τ = PRUNE_MARGIN·θ̂/T sits BELOW the smallest possible
# posting impact (tf=1 at dl_max still lands ~0.3·max), so no block of
# any term can ever price under it. The doc-space cut works on the SUM:
# partition doc ids into 2^DOC_RANGE_SHIFT-doc ranges, upper-bound every
# range at Σ_t w_t·scale·max_q(t, range), and prune ranges that cannot
# reach RANGE_MARGIN·θ̂. Soundness: a doc in a pruned range scores
# ≤ bound(range) + Σ_t w_t·eps ≤ rem, and the existing certificate /
# phase-2 machinery consumes that rem unchanged; a doc in a KEPT range
# is fully gathered (every posting of it lies in a 128-posting block
# that intersects its kept range, and blocks are kept per intersection),
# so the seen-but-lost analysis is also unchanged. On an arrival-order
# corpus every block spans nearly the whole doc space and intersects
# some kept range — nothing skips, which is why this plan only fires
# after the merge-time reorder clusters each term's impact mass into
# narrow doc runs (the classic BMW/live-block force-multiplier).
# Per-row range maxima are query-independent and cached on the plane.
DOC_RANGE_SHIFT = 7          # 128-doc ranges (the BP leaf granularity)
RANGE_MARGIN = 0.99          # prune ranges priced under 0.99·θ̂: the 1%
#                              keep-band is certify headroom — rem lands
#                              ≤ 0.99·θ̂ + eps, strictly under θ, so the
#                              phase-1 certificate holds with room for E
#                              (the probe witness keeps θ̂ within ~eps of
#                              the real boundary, so the band is real)
PROBE_TOP = 32               # top postings per row feeding the probe-doc
#                              witness (sound multi-term θ̂ sharpener)


def _probe_witness(pb, plane, act_rows, act_w, window: int,
                   eps_sum: float) -> float:
    """Sharper sound θ̂ for multi-term queries: take each row's top
    PROBE_TOP postings by quantized impact (REAL docs), sum each probe
    doc's approx score across ALL queried rows, and witness the
    window-th highest minus the summed error. The single-term kth
    witness only ever sees one row; when query terms co-occur the true
    boundary sits near the SUM and this witness finds it — which is
    what lets the doc-range cut price single-term ranges out."""
    if window > PROBE_TOP:
        return 0.0
    docs_l = []
    for row in act_rows:
        cache = plane.__dict__.setdefault("_probe_top", {})
        got = cache.get(row)
        if got is None:
            a, b = pb.row_slice(row)
            qs = plane.q[a:b]
            m = min(PROBE_TOP, b - a)
            sel = np.argpartition(qs, b - a - m)[b - a - m:] if b - a > m \
                else np.arange(b - a)
            got = pb.doc_ids[a:b][sel].astype(np.int64)
            if len(cache) >= (1 << 15):
                cache.clear()   # <=PROBE_TOP i64 per row; hard cap ~8MB
            cache[row] = got
        docs_l.append(got)
    probe = np.unique(np.concatenate(docs_l))
    if len(probe) < window:
        return 0.0
    approx = np.zeros(len(probe), np.float64)
    scale = float(plane.scale)
    for row, w in zip(act_rows, act_w):
        a, b = pb.row_slice(row)
        rowdocs = pb.doc_ids[a:b]
        pos = np.searchsorted(rowdocs, probe)
        pos_c = np.minimum(pos, b - a - 1)
        found = rowdocs[pos_c] == probe
        approx += np.where(found,
                           w * scale * plane.q[a:b][pos_c].astype(
                               np.float64), 0.0)
    kth = float(np.partition(approx, len(approx) - window)
                [len(approx) - window])
    return kth - eps_sum


_RANGE_MAX_CACHE_BYTES = 1 << 25    # 32MB per plane, then start over


def _row_range_max(pb, plane, row: int, shift: int):
    """(range_ids i64[R], max_q[R]) of one row — max quantized impact
    per touched doc range; cached (query-independent). Entries are
    O(touched ranges) arrays (~9 B/range — a 1M-doc stopword row is
    ~70KB), so the cache is byte-capped, not entry-capped: a long-lived
    node serving a wide vocabulary must not accumulate host memory
    proportional to every row ever queried."""
    cache = plane.__dict__.setdefault("_range_max", {})
    got = cache.get(row)
    if got is None:
        a, b = pb.row_slice(row)
        docs = pb.doc_ids[a:b]
        buck = (docs >> shift).astype(np.int64)
        head = np.flatnonzero(np.diff(buck)) + 1
        idx = np.concatenate(([np.int64(0)], head))
        maxq = np.maximum.reduceat(plane.q[a:b], idx) if b > a \
            else np.zeros(0, plane.q.dtype)
        got = (buck[idx] if b > a else np.zeros(0, np.int64), maxq)
        nb = int(got[0].nbytes) + int(got[1].nbytes)
        used = plane.__dict__.get("_range_max_bytes", 0)
        if used + nb > _RANGE_MAX_CACHE_BYTES:
            cache.clear()       # benign to race: values are deterministic
            used = 0
        plane.__dict__["_range_max_bytes"] = used + nb
        cache[row] = got
    return got


def _range_plan(pb, plane, act_rows, act_w, offs, lens,
                theta_hat: float, eps: float, ndocs: int):
    """Doc-range plan over the active rows' blocks. Returns
    (keep_mask bool[nblocks], rem) or None when the cut keeps everything
    (or prices itself out)."""
    if ndocs <= 0 or theta_hat <= 0.0:
        return None
    shift = DOC_RANGE_SHIFT
    nb = ((ndocs - 1) >> shift) + 1
    bound = np.zeros(nb, np.float64)
    scale = float(plane.scale)
    eps_sum = 0.0
    for row, w in zip(act_rows, act_w):
        bids, maxq = _row_range_max(pb, plane, row, shift)
        bound[bids] += w * scale * maxq.astype(np.float64)
        eps_sum += w * eps
    tau_r = RANGE_MARGIN * theta_hat - eps_sum
    if tau_r <= 0.0:
        return None
    kept_r = bound >= tau_r
    if kept_r.all():
        return None
    # block kept iff its doc span intersects any kept range
    cum = np.zeros(nb + 1, np.int64)
    np.cumsum(kept_r, out=cum[1:])
    first = pb.doc_ids[offs].astype(np.int64) >> shift
    last = pb.doc_ids[offs + lens.astype(np.int64) - 1].astype(
        np.int64) >> shift
    keep_b = (cum[last + 1] - cum[first]) > 0
    pruned_b = bound[~kept_r]
    rem = float(pruned_b.max() + eps_sum) if len(pruned_b) else 0.0
    return keep_b, rem


def _plan_blocks(pb, plane, rows: np.ndarray, weights: np.ndarray,
                 C: int, prune: bool, window: int, eps: float,
                 ndocs: int = 0):
    """Select the gathered block set. Returns (bstart i64[NB], blen
    i32[NB], bweight f32[NB], kept_postings, rem_bound, n_total_blocks,
    total_postings) — bweight folds w_t·scale so the device does ONE
    multiply per posting.

    The prune threshold is derived from a SOUND lower bound θ̂ on the
    true window-boundary score: distinct blocks of one row are distinct
    docs, and each block contains a posting attaining its block_max, so
    the window-th highest block_max of any single term witnesses `window`
    real docs scoring ≥ w·(scale·bmax − eps) (eps = quantization +
    param-drift error). Pruning only blocks priced below
    `PRUNE_MARGIN·θ̂/T` keeps the remainder bound Σ_t max(pruned_t) ≤
    PRUNE_MARGIN·θ̂ < θ — so a pruned plan certifies by construction
    (phase 2 at the latest) instead of escalating to the dense rerun.
    `eps` also prices the abstention: when quantization/drift error
    swamps θ̂, nothing is pruned."""
    offs_l, lens_l, w_l, term_l, val_l, act_w = [], [], [], [], [], []
    act_rows = []
    scale = np.float32(plane.scale)
    row_ends = pb.starts[1:]
    for i, r in enumerate(rows):
        if r < 0:
            continue
        a, b = plane.row_block_range(int(r))
        if b <= a:
            continue
        act_rows.append(int(r))
        off = plane.block_off[a:b]
        ln = np.minimum(np.int64(IMPACT_BLOCK),
                        int(row_ends[int(r)]) - off).astype(np.int32)
        bm = plane.block_max[a:b]
        offs_l.append(off)
        lens_l.append(ln)
        w_l.append(np.full(b - a, np.float32(weights[i]) * scale,
                           np.float32))
        term_l.append(np.full(b - a, i, np.int32))
        val_l.append(dequant_impact_np(bm, float(weights[i])
                                       * float(plane.scale)))
        act_w.append(abs(float(weights[i])))
    if not offs_l:
        z = np.zeros(0, np.int64)
        return (z, np.zeros(0, np.int32), np.zeros(0, np.float32),
                0, 0.0, 0, 0)
    offs = np.concatenate(offs_l)
    lens = np.concatenate(lens_l)
    bw = np.concatenate(w_l)
    terms = np.concatenate(term_l)
    vals = np.concatenate(val_l)
    total_post = int(lens.sum())
    nblocks = len(offs)
    keep_min = max(KEEP_FACTOR * C, KEEP_MIN)
    if not prune or total_post <= keep_min:
        return offs, lens, bw, total_post, 0.0, nblocks, total_post
    # θ̂: best single-term witness on the window-th highest impact,
    # error-deducted. Postings of one row are distinct docs, so the
    # window-th highest quantized impact of ANY term witnesses `window`
    # real docs scoring ≥ w·(scale·q − eps) — sharper than the
    # block-level witness (top postings can concentrate in few blocks)
    # and exactly the MaxScore insight: one rare high-idf term alone can
    # price every stopword block out of the gather. Rows past the
    # partition budget fall back to the block-max witness (each block
    # max is attained by a distinct doc, so it is also sound).
    theta_hat = 0.0
    n_active = len(val_l)
    kcache = plane.__dict__.setdefault("_kth_cache", {})
    for r, bm_v, w_i in zip(act_rows, val_l, act_w):
        a, b = int(pb.starts[r]), int(pb.starts[r + 1])
        if b - a >= window and b - a <= (1 << 17):
            # cached per (row, window): the partition over a stopword
            # row is the plan's only O(df) step, and zipf queries repeat
            # rows constantly (benign to race — value is deterministic)
            kth_q = kcache.get((r, window))
            if kth_q is None:
                kth_q = float(np.partition(plane.q[a:b], b - a - window)
                              [b - a - window])
                if len(kcache) >= (1 << 16):
                    kcache.clear()      # scalar entries; hard cap ~6MB
                # one float per (row, window), never an ndocs-scale
                # array, and the cap above bounds the dict itself
                kcache[(r, window)] = kth_q  # oslint: disable=OSL301
            wit = float(dequant_impact_np(
                np.float32(kth_q), w_i * float(plane.scale)))
            theta_hat = max(theta_hat, wit - w_i * eps)
        elif len(bm_v) >= window:
            kth = float(np.partition(bm_v, len(bm_v) - window)
                        [len(bm_v) - window])
            theta_hat = max(theta_hat, kth - w_i * eps)
    # probe-doc witness: real docs' summed approx scores — sharpens θ̂
    # past the single-term kth when query terms co-occur
    eps_sum = float(sum(act_w)) * eps
    theta_hat = max(theta_hat,
                    _probe_witness(pb, plane, act_rows, act_w, window,
                                   eps_sum))
    if theta_hat <= 0.0:
        return offs, lens, bw, total_post, 0.0, nblocks, total_post
    tau = PRUNE_MARGIN * theta_hat / max(n_active, 1)
    prune_mask = vals < tau
    kept_post = int(lens[~prune_mask].sum())
    if kept_post < keep_min:
        # un-prune the priciest pruned blocks back to the posting floor
        pruned_idx = np.nonzero(prune_mask)[0]
        order = pruned_idx[np.argsort(-vals[pruned_idx], kind="stable")]
        cum = kept_post + np.cumsum(lens[order])
        back = int(np.searchsorted(cum, keep_min, side="left")) + 1
        prune_mask[order[:back]] = False
        kept_post = int(lens[~prune_mask].sum())
    rem = 0.0
    if prune_mask.any():
        # per-term max pruned block value, summed — the sound bound on
        # any doc's missing (never-gathered) contribution
        T = int(rows.shape[0])
        pruned_idx = np.nonzero(prune_mask)[0]
        per_term = np.zeros(T, np.float64)
        np.maximum.at(per_term, terms[pruned_idx],
                      vals[pruned_idx].astype(np.float64))
        rem = float(per_term.sum())

    # doc-range plan (the equal-idf multi-term cut): compete against the
    # per-term plan and take whichever ships fewer postings — on a
    # BP-reordered segment the range cut usually wins multi-term shapes
    # outright, on arrival-order corpora it keeps everything and the
    # per-term plan stands
    if n_active >= 1:
        rp = _range_plan(pb, plane, act_rows, act_w, offs, lens,
                         theta_hat, eps, ndocs)
        if rp is not None:
            keep_b, rem_r = rp
            kept_post_r = int(lens[keep_b].sum())
            if kept_post_r >= keep_min and kept_post_r < kept_post:
                kept = np.nonzero(keep_b)[0]
                return (offs[kept], lens[kept], bw[kept], kept_post_r,
                        rem_r, nblocks, total_post)
    kept = np.nonzero(~prune_mask)[0]
    return (offs[kept], lens[kept], bw[kept], kept_post,
            rem, nblocks, total_post)


def _exact_scores(seg: Segment, field: str, rows: np.ndarray,
                  weights: np.ndarray, k1: float, b_eff: float,
                  avgdl: float, cand: np.ndarray, dot: bool = False):
    """Exact f32 scores of `cand` against the FULL rows — term-ordered
    accumulation mirroring the fastpath host oracle (`_exact_rescore`)
    bit for bit, which is the domain served pages live in. `dot=True` is
    the learned-sparse domain: contribution w_t · weight(t, d) (the CSR
    "tf" slot of a feature field IS the stored weight) instead of the
    BM25 saturation."""
    pb = seg.postings.get(field)
    dl = seg.doc_lens.get(field)
    dl_c = (dl[cand].astype(np.float32) if dl is not None
            else np.zeros(len(cand), np.float32))
    kfac = float(k1) * (1.0 - b_eff + b_eff * dl_c
                        / max(float(avgdl), 1e-9))
    exact = np.zeros(len(cand), np.float32)
    counts = np.zeros(len(cand), np.int64)
    for i, r in enumerate(rows):
        if r < 0:
            continue
        a, b = pb.row_slice(int(r))
        if b <= a:
            continue
        rowdocs = pb.doc_ids[a:b]
        pos = np.searchsorted(rowdocs, cand)
        pos_c = np.minimum(pos, b - a - 1)
        found = rowdocs[pos_c] == cand
        tf = np.where(found, pb.tfs[a + pos_c], 0.0).astype(np.float32)
        contrib = (np.float32(weights[i]) * tf if dot
                   else np.float32(weights[i]) * tf / (tf + kfac))
        exact += np.where(found, contrib, 0.0).astype(np.float32)
        counts += found
    return exact, counts


def _error_bound(plane, weights: np.ndarray, rows: np.ndarray,
                 k1q: float, bq: float, avgdlq: float,
                 drift: Optional[float] = None) -> float:
    """Sound |exact − approx| per-doc bound: per-term quantization
    half-step + build→query param drift, plus f32 accumulation slack on
    both sums (≤ T adds each against the max representable score).
    Feature planes pass drift=0.0 explicitly — their weights are
    query-independent, so drift_bound (a BM25 construct) never applies
    (ImpactPlane.kind, OSL507)."""
    quant = plane.quant_err()
    if drift is None:
        drift = plane.drift_bound(k1q, bq, avgdlq)
    wsum = float(np.abs(weights[rows >= 0]).sum())
    e = wsum * (quant + drift)
    t = int((rows >= 0).sum())
    umax = max(wsum * float(plane.scale) * plane.qmax, 1e-30)
    e += 4.0 * (t + 2) * float(np.spacing(np.float32(umax)))
    return e


def _result(exact_m: np.ndarray, cand: np.ndarray, order: np.ndarray,
            window: int, total: int, rel: str) -> dict:
    keep = order[:window]
    sc = exact_m[keep]
    dc = cand[keep].astype(np.int32)
    finite = np.isfinite(sc)
    sc = np.where(finite, sc, -np.inf).astype(np.float32)
    dc = np.where(finite, dc, -1)
    ms = float(sc[0]) if len(sc) and np.isfinite(sc[0]) else -np.inf
    return {"topk_key": sc, "topk_idx": dc, "topk_scores": sc,
            "total": int(total), "max_score": ms, "total_rel": rel}


def segment_search(seg: Segment, ctx, spec: ImpactSpec, k: int
                   ) -> Optional[dict]:
    """Serve one pure spec over one codec-v2 segment, or None to fall
    back to the exact dense program. Codec-version gate consults
    Segment.codec_version (OSL507); v1 segments and facade views (shard
    views, filtered views — their PostingsBlocks carry no plane) decline
    here, so every caller keeps serving the legacy path unchanged."""
    lt = spec.lt
    if getattr(seg, "codec_version", CODEC_V1) < CODEC_V2:
        return None
    pb = seg.postings.get(lt.field)
    if pb is None or pb.impact is None or pb.size == 0:
        return None
    import jax

    from . import compiler as C

    plane = pb.impact
    is_sparse = spec.kind == "sparse"
    # plane/spec kind agreement (OSL507 version-discipline sibling): a
    # BM25 group must read a BM25 plane, a learned-sparse dot a FEATURE
    # plane — the dequant domain is baked into the quantized values
    if (plane.kind if plane.kind else "bm25") != (
            "feature" if is_sparse else "bm25"):
        return None
    window = max(int(spec.window or k), 1)
    ndocs_pad = seg.ndocs_pad
    Ccand = min(next_pow2(max(2 * window, CAND_FLOOR)), ndocs_pad)
    if is_sparse:
        # learned-sparse dot: rows are feature vocab entries. The PLAN
        # (τ/θ̂/rem pricing) works in the boost-folded domain
        # (w·boost), but the SERVED exact scores mirror the generic
        # sparse_dot XLA program's ordering — term-ordered Σ w·weight,
        # THEN one multiply by boost — so certified and escalated
        # segments of one query serve the same score domain. The ≤ ~T-
        # ULP gap between Σ(w·boost)·tf and (Σ w·tf)·boost is inside
        # the certificate's f32 accumulation slack (_error_bound).
        tokens = list(lt.tokens)
        nt = len(tokens)
        rows = np.full(nt, -1, np.int64)
        for i, t in enumerate(tokens):
            rows[i] = pb.row(t)
        exact_weights = np.asarray(lt.weights, np.float32)[:nt]
        exact_scale = np.float32(lt.boost)
        weights = exact_weights * exact_scale
        k1q, b_eff, avgdlq = 0.0, 0.0, 1.0
        msm = 1.0
        drift = 0.0
    else:
        nt = len(lt.terms)
        rows = np.full(nt, -1, np.int64)
        for i, t in enumerate(lt.terms):
            rows[i] = pb.row(t)
        weights = np.asarray(lt.weights, np.float32)[:nt]
        sim = lt.sim
        k1q = float(sim.k1)
        b_eff = float(sim.b) if lt.has_norms else 0.0
        avgdlq = float(ctx.avgdl(lt.field))
        msm = float(lt.msm)
        drift = None
        exact_weights = weights
        exact_scale = np.float32(1.0)
    if np.any(weights < 0):
        return None              # negative boosts void the prune bounds

    eps_imp = plane.quant_err() + (
        0.0 if is_sparse else plane.drift_bound(k1q, b_eff, avgdlq))
    offs, lens, bw, kept_post, rem, nblocks, total_post = _plan_blocks(
        pb, plane, rows, weights, Ccand, spec.prune_ok, window, eps_imp,
        ndocs=seg.ndocs)
    pruned = rem > 0.0 or kept_post < total_post
    STATS.inc("blocks_total", nblocks)
    STATS.inc("blocks_skipped", nblocks - len(offs))
    STATS.inc("postings_total", total_post)
    STATS.inc("postings_skipped", total_post - kept_post)
    # per-SHAPE skip attribution (obs/insights.py): the global STATS
    # smear under concurrency; the request's observation doesn't
    _ins.note_blocks(nblocks, nblocks - len(offs))
    if kept_post == 0:
        # no queried term has postings here: an exact empty page
        STATS.inc("served")
        z = np.full(window, -np.inf, np.float32)
        return {"topk_key": z, "topk_idx": np.full(window, -1, np.int32),
                "topk_scores": z, "total": 0, "max_score": -np.inf,
                "total_rel": "eq"}

    B_pad = next_pow2(len(offs), floor=8)
    bstart = np.zeros(B_pad, np.int32)
    blen = np.zeros(B_pad, np.int32)
    bweight = np.zeros(B_pad, np.float32)
    bstart[: len(offs)] = offs.astype(np.int32)
    blen[: len(offs)] = lens
    bweight[: len(offs)] = bw
    # the gather reads IMPACT_BLOCK slots a block slot (a slot's block is
    # its row), so the program is keyed by B_pad alone
    slots = B_pad * IMPACT_BLOCK

    arrs = seg.device_arrays()
    post = arrs["postings"][lt.field]
    cost = _qc.current()
    if cost is not None:
        # actual moved bytes of the eager pass: doc i32 + u8/u16 impact
        # per gathered slot — the codec-v2 byte-volume claim, measured.
        # A block slot's window is read as the two plane rows it lies in
        cost.note_actual(2 * slots * (4 + plane.bits // 8), kept_post,
                         Ccand, path="impact", segment=seg)
    with TRACER.span("impactpath.gather", blocks=int(len(offs)),
                     slots=slots), METRICS.timer("impactpath.gather"):
        prog = C.build_impact_program(B_pad, Ccand, plane.bits)
        launched = prog(
            post["doc_ids"], post["impacts"], arrs["live"], bstart, blen,
            bweight, np.float32(1.0 if pruned else msm))
        with TRACER.span("device.wait", program="impact"):
            vals, idx, total = jax.device_get(launched)
    vals = np.asarray(vals)
    idx = np.asarray(idx)
    nvalid = int((vals > -np.inf).sum())
    total = int(total)
    rel = "gte" if pruned else "eq"

    if nvalid == 0:
        if pruned:
            # matches may hide entirely in pruned blocks
            STATS.inc("escalated")
            _ins.note_escalation()
            return None
        STATS.inc("served")
        z = np.full(window, -np.inf, np.float32)
        return {"topk_key": z, "topk_idx": np.full(window, -1, np.int32),
                "topk_scores": z, "total": 0, "max_score": -np.inf,
                "total_rel": "eq"}

    cand = idx[:nvalid].astype(np.int64)
    exact, counts = _exact_scores(seg, lt.field, rows, exact_weights,
                                  k1q, b_eff, avgdlq, cand,
                                  dot=is_sparse)
    if exact_scale != np.float32(1.0):
        exact = (exact * exact_scale).astype(np.float32)
    pass_msm = counts >= msm
    exact_m = np.where(pass_msm, exact, -np.inf).astype(np.float32)
    n_pass = int(pass_msm.sum())
    # score ties break on the layout-invariant arrival rank (== doc id
    # on unreordered segments): the BP reorder parity contract
    tr = seg.tie_ranks()
    order = np.lexsort((cand if tr is None else tr[cand], -exact_m))
    theta = (float(exact_m[order[window - 1]]) if n_pass >= window
             else -np.inf)
    E = _error_bound(plane, weights, rows, k1q, b_eff, avgdlq,
                     drift=drift)

    # displacement bound for every non-candidate doc: seen-but-lost docs
    # (only exist when the kernel window filled) carry approx ≤ the C-th
    # approx value plus quant/drift error plus whatever pruning hid;
    # never-seen docs are bounded by the pruned remainder PLUS the same
    # error term (the sidecar prices blocks in the quantized domain —
    # the true f32 contribution can sit up to eps above it)
    bound = (rem + E) if pruned else -np.inf
    if nvalid == Ccand:
        bound = max(bound, float(vals[nvalid - 1]) + E + rem)
    if theta > -np.inf and bound < theta:
        STATS.inc("served")
        if pruned:
            STATS.inc("pruned_served")
        tot = total if not pruned or msm <= 1 else n_pass
        return _result(exact_m, cand, order, window, tot, rel)
    if not pruned and nvalid < Ccand:
        # the candidate set IS every matching doc: exact by construction
        # (window may be short — that's the true result set)
        STATS.inc("served")
        return _result(exact_m, cand, order, window, total, "eq")

    # ---- phase 2: widen to every doc any kept block mentions — unseen
    # docs are then bounded by the pruned remainder alone ----
    if pruned:
        if _fr.RECORDER.enabled and _fr.current():
            _fr.RECORDER.record(_fr.current(), "impactpath.rung",
                                rung="phase2_union", blocks=int(len(offs)))
        with TRACER.span("impactpath.phase2", postings=kept_post), \
                METRICS.timer("impactpath.phase2"):
            ids = [pb.doc_ids[int(o): int(o) + int(l)]
                   for o, l in zip(offs, lens)]
            union = np.unique(np.concatenate(ids)).astype(np.int64)
            if len(union) and seg.live_count != seg.ndocs:
                union = union[seg.live[union]]
            exact2, counts2 = _exact_scores(seg, lt.field, rows,
                                            exact_weights, k1q, b_eff,
                                            avgdlq, union,
                                            dot=is_sparse)
            if exact_scale != np.float32(1.0):
                exact2 = (exact2 * exact_scale).astype(np.float32)
            pass2 = counts2 >= msm
            exact2_m = np.where(pass2, exact2, -np.inf).astype(np.float32)
            n2 = int(pass2.sum())
            order2 = np.lexsort((union if tr is None else tr[union],
                                 -exact2_m))
            theta2 = (float(exact2_m[order2[window - 1]])
                      if n2 >= window else -np.inf)
            # + E: the remainder is a quantized-domain price; the true
            # exact contribution of a pruned posting can exceed it by
            # the per-term quant/drift epsilon
            if theta2 > -np.inf and rem + E < theta2:
                STATS.inc("served")
                STATS.inc("pruned_served")
                STATS.inc("phase2_served")
                return _result(exact2_m, union, order2, window, n2, "gte")

    STATS.inc("escalated")
    _ins.note_escalation()
    if _fr.RECORDER.enabled and _fr.current():
        _fr.RECORDER.record(_fr.current(), "impactpath.rung",
                            rung="dense_escalation")
    return None
