"""Production Pallas fast path for the term/match hot path.

Routes single-group BM25 term queries (term / terms / match / multi-term
match with minimum_should_match — the traffic Lucene serves through
BulkScorer, reference `search/query/QueryPhase.java`) through the fused
Pallas kernel `ops/pallas_bm25.fused_bm25_topk_tfdl` instead of the XLA
gather→scatter path. The XLA path stays as the general fallback for complex
plans, segments with deletes, non-BM25 similarities, or posting rows larger
than the VMEM bucket cap.

Per (segment, field) we lazily build a DMA-friendly postings layout:
128-lane-aligned CSR rows of (doc_id i32, tf<<21|dl i32); DMA windows
align down to the 1024-element HBM tile with a positional skip mask. The packing is
lossless (tf < 2048, dl < 2^21 — segments violating it are ineligible), and
the kernel evaluates the SAME f32 BM25 expression as the XLA path with avgdl
as a query-time scalar, so both paths rank identically.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from ..index.segment import CODEC_V1, CODEC_V2, Segment, next_pow2
from ..ops import scoring as ops
from ..ops.pallas_bm25 import (DL_BITS, DL_MAX, HBM_ALIGN, INT_SENTINEL,
                               LANES, REQ_W, TF_MAX, align_csr_rows,
                               fused_bm25_bool_topk, fused_bm25_topk_impact,
                               fused_bm25_topk_tfdl)

MAX_T = 8            # pow2-padded term slots per query group
MAX_L = 1 << 16      # per-term VMEM bucket cap (elements)
MAX_TL = 1 << 17     # T_pad * L cap (~16MB VMEM incl. merge working set)
MAX_K = 128          # top-k lanes the kernel returns
MAX_CHUNKS = 4096    # doc-range split bound. Postings are <=1 per doc, so a
                     # chunk spanning W doc ids holds <=W postings per term;
                     # at 4096 chunks a 50M-doc ClueWeb-class segment has
                     # W ~= 12.2K <= the per-term VMEM budget even at
                     # T_pad=8 (MAX_TL/8 = 16K) — EVERY df, including an
                     # every-doc stopword, stays on-kernel (config 5).
                     # _chunk_slots starts at the predicted count, so the
                     # planning loop doesn't crawl up from 2 by doubling.
INT_MAX = np.int32(2**31 - 1)

# Impact-ordered head pruning (the device analog of Lucene's block-max
# pruning, reference `search/query/TopDocsCollectorContext.java` over
# Lucene MAXSCORE/WAND): a term with more than L_HEAD postings keeps an
# extra on-device copy of its L_HEAD HIGHEST-IMPACT postings (selected by
# tf/(tf+k·norm), stored doc-ascending so the kernel's merge network is
# unchanged). Pruned queries stream heads only — fixed cost per term no
# matter the df — then a host verify pass proves the result exact against
# the remainder's upper bound, or reruns that query dense. See
# `_verify_pruned` for the bound.
L_HEAD = 1 << 12

_enabled = True      # flipped by tests / OPENSEARCH_TPU_NO_FASTPATH

# served/fallback counters (surfaced in _nodes/stats; also used by tests to
# prove the kernel actually engaged rather than silently falling back).
# CounterGroup: dict-shaped reads (same keys/values as the old plain dict)
# with atomic inc() writes through the metrics registry — concurrent
# searches no longer lose counts to the `d[k] += 1` read-modify-write race
from ..utils.metrics import METRICS, CounterGroup
from ..utils.trace import TRACER
# flight-recorder (obs/): escalation-ladder rung events on the ambient
# request timeline. Emission discipline (oslint OSL505): every record()
# below is guarded by RECORDER.enabled so the disabled path never builds
# an event payload
from ..obs import flight_recorder as _fr
# per-query device cost accounting (obs/query_cost.py): every kernel
# launch notes the bytes its DMA windows actually move — reconciled
# against the plan-time CSR-stat prediction in the profile `cost` block
from ..obs import query_cost as _qc

STATS = CounterGroup(METRICS, "fastpath", {
    "pure_served": 0, "bool_served": 0, "fallback": 0,
    "pruned_served": 0, "pruned_dview": 0, "pruned_rescued": 0,
    "pruned_rescued2": 0, "pruned_escalated": 0,
    "shard_view_served": 0, "impact_frontier": 0,
    "reorder_tie_fallback": 0})

# phase-2 rescore instrumentation (surfaced in _nodes/stats, read by the
# benchmark's `bm25_match` counters and chip_smoke.py): where the
# candidate-union rescore ran and what it cost. wall_ms includes the device_get sync, so device
# numbers are honest end-to-end, not launch-and-forget.
RESCORE_STATS = CounterGroup(METRICS, "fastpath.rescore", {
    "host_calls": 0, "host_wall_ms": 0.0,
    "device_launches": 0, "device_queries": 0,
    "device_cands": 0, "device_probe_elems": 0, "device_wall_ms": 0.0})

_rescore_override: Optional[str] = None   # tests/scripts pin a path


def set_rescore_mode(mode: Optional[str]) -> None:
    """Force the phase-2 rescore path: "device", "host", or None (auto).
    Rejects anything else — a silently-ignored typo would make a parity
    harness compare the host path against itself."""
    global _rescore_override
    if mode not in (None, "device", "host"):
        raise ValueError(f"rescore mode must be 'device', 'host' or None, "
                         f"got {mode!r}")
    _rescore_override = mode


def rescore_mode() -> str:
    """Where the candidate-union rescore runs. Auto: device on TPU, host
    numpy under JAX_PLATFORMS=cpu (the fallback + parity oracle).
    `set_rescore_mode` overrides."""
    if _rescore_override in ("device", "host"):
        return _rescore_override
    import jax
    return "device" if jax.default_backend() == "tpu" else "host"


def rescore_stats() -> dict:
    return dict(RESCORE_STATS)

# memory accounting: aligned postings, filter lists, filtered copies and
# quality-tier views register with the HBM ledger (obs/hbm_ledger.py),
# which derives the fielddata-breaker charge — the ledger is the sole
# charge path (oslint OSL506). Released when the owning layout object
# (or its segment) is GC'd; segments are immutable and replaced on
# refresh/merge.


def set_enabled(flag: bool) -> None:
    global _enabled
    _enabled = flag


_backend_ok = None


def enabled() -> bool:
    import os
    global _backend_ok
    if _backend_ok is None:
        import jax
        _backend_ok = jax.default_backend() == "tpu"
    return (_enabled and _backend_ok
            and not os.environ.get("OPENSEARCH_TPU_NO_FASTPATH"))


def _frontier(tfs: np.ndarray, dls: np.ndarray, ids: np.ndarray = None
              ) -> tuple:
    """(tf -> min dl over docs with that tf) of a posting set — its Pareto
    frontier under the BM25 contribution tf/(tf+k(dl)), which is increasing
    in tf and decreasing in dl. The max contribution of the set under ANY
    (k1, b, avgdl) is attained on this frontier, so ~a dozen (tf, dl) pairs
    give an EXACT set bound for every query-time similarity.

    With `ids`, additionally returns per frontier point TWO tie witnesses:
    the MIN doc id among postings attaining the point exactly (tf == tf_i
    and dl == min dl — the attainer set when length norms matter) and the
    MIN doc id over the whole tf class (the attainer set when b_eff ~ 0
    makes dl irrelevant). The verifier needs these to prove a boundary TIE
    non-displacing under the (score desc, doc asc) result order."""
    if len(tfs) == 0:
        z = np.zeros(0, np.float32)
        zi = np.zeros(0, np.int64)
        return (z, z) if ids is None else (z, z, zi, zi)
    tf = tfs.astype(np.int64)
    dl_s32 = dls.astype(np.float32)
    if ids is not None:
        order = np.lexsort((ids, dl_s32, tf))
        tf_s = tf[order]
        id_s = ids[order].astype(np.int64)
        first = np.flatnonzero(
            np.concatenate(([True], tf_s[1:] != tf_s[:-1])))
        id_any = np.minimum.reduceat(id_s, first)
        return (tf_s[first].astype(np.float32), dl_s32[order][first],
                id_s[first], id_any)
    order = np.argsort(tf, kind="stable")
    tf_s = tf[order]
    dl_s = dl_s32[order]
    # min dl per distinct tf via reduceat
    heads = np.flatnonzero(np.concatenate(([True], tf_s[1:] != tf_s[:-1])))
    return (tf_s[heads].astype(np.float32),
            np.minimum.reduceat(dl_s, heads).astype(np.float32))


def _frontier_bound(fr: Tuple[np.ndarray, np.ndarray], k1: float,
                    b_eff: float, avgdl: float) -> float:
    """Max contribution tf/(tf+k1·(1-b+b·dl/avgdl)) over a frontier."""
    tf, dl = fr[0], fr[1]
    if len(tf) == 0:
        return 0.0
    k = k1 * (1.0 - b_eff + b_eff * dl / max(avgdl, 1e-9))
    return float(np.max(tf / (tf + np.maximum(k, 1e-9))))


class AlignedPostings:
    """Device-resident aligned (doc, tf·dl) postings for one segment field,
    plus the impact-selected heads of oversized rows (appended to the same
    buffer) and the remainder frontiers that make pruned results provable."""

    __slots__ = ("starts_rows", "lens", "d_docs", "d_tfdl", "nbytes",
                 "head_starts_rows", "head_lens", "rem_frontiers",
                 "head_ids", "_full_frontiers", "_head2", "d_imp")

    def __init__(self, starts_rows: np.ndarray, lens: np.ndarray,
                 d_docs, d_tfdl, nbytes: int,
                 head_starts_rows: Optional[np.ndarray] = None,
                 head_lens: Optional[np.ndarray] = None,
                 rem_frontiers: Optional[dict] = None,
                 head_ids: Optional[dict] = None,
                 d_imp=None):
        self.starts_rows = starts_rows    # i64[nterms] aligned start / LANES
        self.lens = lens                  # i64[nterms] true posting counts
        self.d_docs = d_docs
        self.d_tfdl = d_tfdl
        self.nbytes = nbytes
        # head view: == (starts_rows, lens) for rows with <= L_HEAD postings;
        # points at the appended impact-head region for clamped rows
        self.head_starts_rows = (head_starts_rows if head_starts_rows
                                 is not None else starts_rows)
        self.head_lens = (head_lens if head_lens is not None
                          else np.minimum(lens, L_HEAD))
        # row -> frontier of the postings OUTSIDE the head (clamped rows
        # only); absence means the head is the whole row
        self.rem_frontiers = rem_frontiers or {}
        # row -> np doc ids of the head postings (clamped rows only) — the
        # candidate-union escalation path rescores exactly these
        self.head_ids = head_ids or {}
        self._full_frontiers: dict = {}
        # row -> (ids, remainder frontier) of the TIER-2 head (4x deeper,
        # host-only): built lazily on first escalation past tier 1, cached
        self._head2: dict = {}
        # codec v2 only: the quantized impact plane in the SAME aligned
        # layout as d_docs (u8/u16 widened to the i32 lane granularity) —
        # the frontier pass then rides `fused_bm25_topk_impact`, one
        # multiply per posting, no per-query tf/doclen math
        self.d_imp = d_imp

    def head2(self, pb, dl_col, row: int) -> tuple:
        """Lazy 4x-deeper head for the second escalation rung: top
        4*L_HEAD postings by nominal impact (ids only — the rescore is a
        host pass) plus the frontier of what remains. O(df log df) once
        per queried row, amortized across every later escalation."""
        got = self._head2.get(row)
        if got is None:
            a, b = pb.row_slice(row)
            dls = (dl_col[pb.doc_ids[a:b]] if dl_col is not None
                   else np.zeros(b - a, np.int64))
            plane = getattr(pb, "impact", None)
            keep, fr = _head_select(pb.doc_ids[a:b], pb.tfs[a:b],
                                    np.asarray(dls, np.int64),
                                    l_head=4 * L_HEAD,
                                    imp=(_plane_impacts_slice(plane, a, b)
                                         if plane is not None
                                         else None))
            got = (pb.doc_ids[a:b][keep], fr)
            self._head2[row] = got
        return got

    def clamped(self, row: int) -> bool:
        return row in self.rem_frontiers

    def rem_bound(self, row: int, k1: float, b_eff: float,
                  avgdl: float) -> float:
        """Upper bound of one remaining (non-head) posting's contribution
        for this row under query-time similarity params."""
        fr = self.rem_frontiers.get(row)
        return 0.0 if fr is None else _frontier_bound(fr, k1, b_eff, avgdl)

    def full_bound(self, pb, row: int, k1: float, b_eff: float,
                   avgdl: float, dl_col) -> float:
        """Upper bound of ANY single posting's contribution in this row
        (lazy per-row frontier, cached — O(df) once per queried term)."""
        fr = self._full_frontiers.get(row)
        if fr is None:
            a, b = pb.row_slice(row)
            dls = (dl_col[pb.doc_ids[a:b]] if dl_col is not None
                   else np.zeros(b - a, np.float32))
            fr = _frontier(pb.tfs[a:b], dls)
            self._full_frontiers[row] = fr
        return _frontier_bound(fr, k1, b_eff, avgdl)


def get_aligned(seg: Segment, field: str) -> Optional[AlignedPostings]:
    """Build (or fetch cached) aligned postings; None when the segment is
    ineligible (tf/dl exceed the lossless packing bounds, or no postings)."""
    cache = seg.__dict__.setdefault("_fastpath_aligned", {})
    if field in cache:
        return cache[field]
    out = _build_aligned(seg, field)
    cache[field] = out
    return out


def _nominal_impact(tfs: np.ndarray, dls: np.ndarray,
                    avg: float) -> np.ndarray:
    """The ONE nominal-similarity impact (k1=1.2, b=0.75) both pruning
    mechanisms order by: head selection and the quality tier must never
    diverge on what 'high impact' means."""
    return tfs / (tfs + 1.2 * (0.25 + 0.75 * dls / avg))


def _plane_impacts(pb) -> Optional[np.ndarray]:
    """Codec-v2 fast source for the nominal impact order: the segment
    already carries quantized eager impacts built with the SAME nominal
    params (index/segment.py IMPACT_K1/IMPACT_B), so head selection and
    the quality tier reuse them instead of re-deriving an O(P) f32 map
    per (segment, field) layout build. Ordering by the quantized plane
    is sound — selection only steers which postings are kept; the exact
    (tf, dl) remainder frontiers still carry correctness. None on v1
    segments and facade views (recompute path unchanged)."""
    plane = getattr(pb, "impact", None)
    if plane is None:
        return None
    from ..ops.scoring import dequant_impact_np
    return dequant_impact_np(plane.q, plane.scale)


def _plane_impacts_slice(plane, a: int, b: int) -> np.ndarray:
    """Dequantized impacts of ONE row slice — per-row consumers (tier-2
    head cuts) must stay O(df), not O(P) over the whole field plane."""
    from ..ops.scoring import dequant_impact_np
    return dequant_impact_np(plane.q[a:b], plane.scale)


def _head_select(doc_ids: np.ndarray, tfs: np.ndarray, dl_of: np.ndarray,
                 l_head: int = None, imp: Optional[np.ndarray] = None
                 ) -> Tuple[np.ndarray, tuple]:
    """Pick the L_HEAD highest-impact postings of one oversized row.
    Impact = tf/(tf + k1·(1-b+b·dl/avgdl)) with nominal params — the order
    only steers which postings we keep; correctness rides on the returned
    REMAINDER FRONTIER (tf -> min dl of the non-kept postings), which
    bounds any remaining posting's contribution under any query-time
    similarity. On codec v2 `imp` carries the row's precomputed quantized
    impacts (`_plane_impacts`) so no per-posting math reruns here.
    Returns (kept positions ASCENDING — i.e. doc-ascending, as the
    kernel's merge network requires —, remainder frontier)."""
    tf = tfs.astype(np.float32)
    dlf = dl_of.astype(np.float32)
    if imp is not None:
        c = imp
    else:
        avg = max(float(dlf.mean()), 1.0)
        c = _nominal_impact(tf, dlf, avg)
    # stable sort: impact ties keep doc-ascending order, matching the exact
    # path's doc-id tie-break so a tied top-k boundary selects the same docs
    order = np.argsort(-c, kind="stable")
    lh = L_HEAD if l_head is None else l_head
    keep = order[:lh]
    rest = order[lh:]
    return np.sort(keep), _frontier(tf[rest], dlf[rest], doc_ids[rest])


def _build_aligned(seg: Segment, field: str) -> Optional[AlignedPostings]:
    import jax

    pb = seg.postings.get(field)
    dl = seg.doc_lens.get(field)
    if pb is None or pb.size == 0:
        return None
    tfs = pb.tfs
    if len(tfs) and tfs.max() > TF_MAX:
        return None
    dl_of = (dl[pb.doc_ids].astype(np.int64) if dl is not None
             else np.zeros(len(pb.doc_ids), np.int64))
    if len(dl_of) and dl_of.max() > DL_MAX:
        return None
    packed = ((tfs.astype(np.int64) << DL_BITS) | dl_of).astype(np.int32)
    lens = np.diff(pb.starts).astype(np.int64)
    nterms = len(lens)

    # impact heads for oversized rows, appended as EXTRA CSR rows so one
    # aligned buffer serves both the dense path (original row region,
    # offsets unchanged) and the pruned path (head region for big rows)
    big = np.nonzero(lens > L_HEAD)[0]
    rem_frontiers: dict = {}
    head_ids: dict = {}
    cat_starts = pb.starts
    cat_docs = pb.doc_ids
    cat_packed = packed
    # codec v2 (gate: Segment.codec_version, OSL507): carry the quantized
    # impact plane through the SAME aligned layout (widened to i32 — the
    # impact kernel's HBM lane granularity) so the frontier pass can ride
    # `fused_bm25_topk_impact`
    plane = (pb.impact
             if getattr(seg, "codec_version", CODEC_V1) >= CODEC_V2
             else None)
    cat_imp = (plane.q.astype(np.int32) if plane is not None else None)
    if len(big):
        plane_imp = _plane_impacts(pb)
        h_docs, h_packed, h_lens, h_imp = [], [], [], []
        for r in big:
            a, b = int(pb.starts[r]), int(pb.starts[r + 1])
            keep, rem_fr = _head_select(pb.doc_ids[a:b], tfs[a:b],
                                        dl_of[a:b],
                                        imp=(plane_imp[a:b]
                                             if plane_imp is not None
                                             else None))
            h_docs.append(pb.doc_ids[a:b][keep])
            h_packed.append(packed[a:b][keep])
            h_lens.append(len(keep))
            if cat_imp is not None:
                h_imp.append(plane.q[a:b][keep].astype(np.int32))
            rem_frontiers[int(r)] = rem_fr
            head_ids[int(r)] = h_docs[-1]
        cat_docs = np.concatenate([pb.doc_ids] + h_docs)
        cat_packed = np.concatenate([packed] + h_packed)
        if cat_imp is not None:
            cat_imp = np.concatenate([cat_imp] + h_imp)
        cat_starts = np.concatenate([
            pb.starts,
            pb.starts[-1] + np.cumsum(np.asarray(h_lens, np.int64))])

    # rows align to 128 lanes only; DMA windows align DOWN to the 1024
    # HBM tile and mask the spilled prefix positionally (skip) — the Zipf
    # long tail would otherwise pay up to 1023 pad slots per rare term
    extra = (cat_imp,) if cat_imp is not None else ()
    aligned = align_csr_rows(cat_starts, cat_docs, cat_packed, *extra,
                             margin=MAX_L, alignment=LANES)
    a_starts, a_docs, a_packed = aligned[0], aligned[1], aligned[2]
    a_imp = aligned[3] if cat_imp is not None else None
    nbytes = a_docs.nbytes + a_packed.nbytes \
        + (a_imp.nbytes if a_imp is not None else 0)
    from ..obs.hbm_ledger import LEDGER
    LEDGER.register("aligned_postings", nbytes, owner=seg, segment=seg,
                    label=f"fastpath[{seg.name}][{field}]")
    starts_rows = (a_starts[:-1] // LANES).astype(np.int64)
    head_starts_rows = starts_rows[:nterms].copy()
    head_lens = np.minimum(lens, L_HEAD)
    if len(big):
        head_starts_rows[big] = starts_rows[nterms:]
    return AlignedPostings(starts_rows[:nterms], lens,
                           jax.device_put(a_docs), jax.device_put(a_packed),
                           nbytes, head_starts_rows, head_lens,
                           rem_frontiers, head_ids,
                           d_imp=(jax.device_put(a_imp)
                                  if a_imp is not None else None))


def _body_eligible(sort_specs: List[dict], agg_nodes, named_nodes,
                   search_after, window: int, body: dict) -> bool:
    """Non-query body checks shared by every fastpath shape."""
    if agg_nodes or named_nodes or search_after is not None:
        return False
    if window > MAX_K or window < 1:
        return False
    if sort_specs and not (len(sort_specs) == 1
                           and sort_specs[0]["field"] == "_score"
                           and sort_specs[0].get("order", "desc") == "desc"):
        return False
    if body.get("collapse") or body.get("suggest") or body.get("knn"):
        return False
    return True


def _ok_group(lt) -> bool:
    """LTerms usable as a fastpath scoring clause (plain BM25 term group)."""
    from . import plan as PL

    if not isinstance(lt, PL.LTerms):
        return False
    if lt.mode != "score" or lt.sim is None or lt.sim.sim_id != ops.SIM_BM25:
        return False
    nt = len(lt.terms)
    if nt < 1:
        return False
    if lt.aux is not None and np.any(np.asarray(lt.aux)[:nt] != 0.0):
        return False
    return True


def query_eligible(lroot, sort_specs: List[dict], agg_nodes, named_nodes,
                   search_after, window: int, body: dict) -> bool:
    """Host-cheap check that this search is the plain BM25 top-k hot path
    (single unfiltered term group — the original fused kernel shape)."""
    if not _ok_group(lroot):
        return False
    if next_pow2(len(lroot.terms), floor=1) > MAX_T:
        return False
    return _body_eligible(sort_specs, agg_nodes, named_nodes, search_after,
                          window, body)


class FastSpec:
    """A search the fastpath can serve. kind 'pure' = single term group on
    the original kernel; kind 'bool' = weighted-threshold bool/filtered
    shape on `fused_bm25_bool_topk` (reference BooleanQuery semantics,
    `search/query/QueryPhase.java`): required slots (single-term musts +
    the combined filter/must_not mask), one optional count-constrained
    family (a multi-term group's msm, or shoulds under the outer
    minimum_should_match), and zero-count bonus shoulds."""

    __slots__ = ("kind", "lt", "slots", "fam_msm", "filter_clauses",
                 "field", "sim", "has_norms", "boost", "const_score",
                 "window", "prune_ok")

    def __init__(self, kind: str, **kw):
        self.kind = kind
        self.lt = None
        self.slots = []            # [(term, weight, cw)] cw in {REQ_W, 1, 0}
        self.fam_msm = 0
        self.filter_clauses = []   # [(LNode, negated)] ANDed dense masks
        self.field = None
        self.sim = None
        self.has_norms = True
        self.boost = 1.0
        self.const_score = None    # fixed score for every hit (filter-only)
        self.window = None         # requested from+size (for pruned verify)
        self.prune_ok = False      # body allows impact-head pruning
        for k, v in kw.items():
            setattr(self, k, v)

    @property
    def n_required(self) -> int:
        return sum(1 for _, _, cw in self.slots if cw == REQ_W)


def _flatten_bool(lroot) -> Optional[FastSpec]:
    """Map an LBool/LConstScore tree onto the weighted-threshold slot model;
    None = not expressible (falls back to the XLA plan path)."""
    from . import plan as PL

    if isinstance(lroot, PL.LConstScore):
        if lroot.child is None or lroot.boost < 0:
            return None
        return FastSpec("bool", filter_clauses=[(lroot.child, False)],
                        const_score=float(lroot.boost), boost=1.0)
    if not isinstance(lroot, PL.LBool):
        return None
    b = lroot
    if b.boost <= 0:
        # boost 0 zeroes every score BEFORE top-k on the XLA path (ties then
        # break by doc id); the kernel ranks pre-boost, so fall back
        return None
    for g in b.musts + b.shoulds:
        if not _ok_group(g):
            return None
    groups = b.musts + b.shoulds
    field = sim = None
    has_norms = True
    if groups:
        field, sim, has_norms = (groups[0].field, groups[0].sim,
                                 groups[0].has_norms)
        for g in groups:
            if (g.field != field or g.sim.k1 != sim.k1 or g.sim.b != sim.b
                    or g.has_norms != has_norms):
                return None

    req: List[Tuple[str, float]] = []
    fam: List[Tuple[str, float]] = []
    bonus: List[Tuple[str, float]] = []
    fam_msm = 0

    def slot_weights(g):
        return [(t, float(np.asarray(g.weights)[i]))
                for i, t in enumerate(g.terms)]

    for m in b.musts:
        if len(m.terms) == 1 or m.msm >= len(m.terms):
            req.extend(slot_weights(m))        # AND semantics: all required
        elif not fam:
            fam.extend(slot_weights(m))        # the one constrained family
            fam_msm = max(int(m.msm), 1)
        else:
            return None
    if b.shoulds:
        outer = int(b.msm)
        if outer == 0:
            # pure score bonus: no count constraint, cw=0 so bonus matches
            # can never stand in for a missing required/family slot
            for s in b.shoulds:
                if len(s.terms) > 1 and s.msm > 1:
                    return None
                bonus.extend(slot_weights(s))
        else:
            if fam:
                return None                    # two constrained families
            if all(len(s.terms) == 1 for s in b.shoulds):
                for s in b.shoulds:
                    fam.extend(slot_weights(s))
                fam_msm = outer
            elif len(b.shoulds) == 1 and outer == 1:
                g = b.shoulds[0]
                fam.extend(slot_weights(g))
                fam_msm = max(int(g.msm), 1)
            else:
                return None

    filter_clauses = ([(f, False) for f in b.filters]
                      + [(n, True) for n in b.must_nots])
    slots = ([(t, w, REQ_W) for t, w in req]
             + [(t, w, 1.0) for t, w in fam]
             + [(t, w, 0.0) for t, w in bonus])
    if not slots and not filter_clauses:
        return None                            # empty bool = match_all
    if len(slots) > MAX_T:
        return None
    return FastSpec("bool", slots=slots, fam_msm=fam_msm,
                    filter_clauses=filter_clauses, field=field, sim=sim,
                    has_norms=has_norms, boost=float(b.boost),
                    const_score=0.0 if not slots else None)


def make_spec(lroot, sort_specs: List[dict], agg_nodes, named_nodes,
              search_after, window: int, body: dict) -> Optional[FastSpec]:
    """-> FastSpec when this search can ride a fused kernel, else None."""
    if not _body_eligible(sort_specs, agg_nodes, named_nodes, search_after,
                          window, body):
        return None
    # pruning changes total-hit semantics on clamped terms (lower bound,
    # relation "gte" — same contract as the reference's default 10k
    # total-hits cap); an explicit track_total_hits demands exact counts,
    # so those bodies ride the dense kernel
    prune_ok = "track_total_hits" not in body
    if _ok_group(lroot) and next_pow2(len(lroot.terms), floor=1) <= MAX_T:
        return FastSpec("pure", lt=lroot, field=lroot.field, window=window,
                        prune_ok=prune_ok)
    spec = _flatten_bool(lroot)
    if spec is not None:
        spec.window = window
        spec.prune_ok = prune_ok
    return spec


class _VQuery:
    """One kernel-row: a whole query, one doc-range chunk of it, or its
    impact-head pruned form (`head=True`)."""

    __slots__ = ("qi", "T_pad", "L", "rowstarts", "nrows", "lens", "skips",
                 "weights", "msm", "avgdl", "dlo", "dhi", "k1", "b_eff",
                 "field", "head", "clamped", "miss", "msm_true", "rows",
                 "impact_pass", "eps")

    def __init__(self, **kw):
        self.head = False       # streams impact heads instead of full rows
        self.clamped = False    # at least one term's head excludes postings
        self.miss = None        # f32[T_pad]: w_t * remainder bound per term
        self.msm_true = 1.0     # real msm (kernel gets 1.0 when clamped)
        self.rows = None        # i64[T_pad] term-dict rows (for rescore)
        self.impact_pass = False  # frontier pass rides the impact kernel
        self.eps = 0.0          # per-doc |exact - kernel| bound (impact
        #                         kernel only; 0.0 = exact f32 kernel)
        for k, v in kw.items():
            setattr(self, k, v)


def _chunk_slots(slots: List[Optional[Tuple[np.ndarray, int]]], ndocs: int,
                 T_total: int, nchunk: int = 2
                 ) -> Optional[List[tuple]]:
    """Split a query whose slot windows exceed the VMEM budget into
    doc-range chunks: uniform doc-id edges, verified against exact
    per-(slot, chunk) posting counts (host searchsorted over the ORIGINAL
    sorted doc lists), doubling the chunk count until every chunk fits.
    `slots[i]` = (sorted_docs, aligned_start_elem) or None for an absent
    slot (term/filter buffers alike — rowstarts are per-buffer row units).
    Returns a list of (dlo, dhi, rowstarts, nrows, lens) tuples covering
    disjoint doc ranges; None -> fall back."""
    budget = MAX_TL // T_total        # elements per slot
    # start at the provably-needed chunk count instead of doubling up from
    # the caller's floor: a slot of L postings needs >= L/budget chunks
    max_len = max((len(s[0]) for s in slots if s is not None), default=0)
    if max_len > budget:
        nchunk = max(nchunk, next_pow2(-(-max_len // budget), floor=2))
    while nchunk <= MAX_CHUNKS:
        edges = np.linspace(0, ndocs, nchunk + 1).astype(np.int64)
        edges[-1] = np.int64(2**31 - 1)
        ok = True
        per_chunk = []
        for c in range(nchunk):
            rowstarts = np.zeros(T_total, np.int32)
            nrows = np.zeros(T_total, np.int32)
            lens = np.zeros(T_total, np.int32)
            skips = np.zeros(T_total, np.int32)
            max_nr = HBM_ALIGN // LANES
            for i, slot in enumerate(slots):
                if slot is None:
                    continue
                seg_docs, start_el = slot
                lo_off = int(np.searchsorted(seg_docs, edges[c], "left"))
                hi_off = int(np.searchsorted(seg_docs, edges[c + 1], "left"))
                if hi_off == lo_off:
                    continue
                # DMA starts at the 1024 HBM tile below the window; the
                # spilled prefix (which may belong to the previous row) is
                # masked positionally by `skip` in the kernel
                abs_el = start_el + lo_off
                dma_el = (abs_el // HBM_ALIGN) * HBM_ALIGN
                skip = abs_el - dma_el
                ln = hi_off - lo_off
                if skip + ln > budget:
                    ok = False
                    break
                rowstarts[i] = dma_el // LANES
                nr = next_pow2((skip + ln + LANES - 1) // LANES,
                               floor=HBM_ALIGN // LANES)
                nrows[i] = nr
                lens[i] = ln
                skips[i] = skip
                max_nr = max(max_nr, nr)
            if not ok:
                break
            if T_total * max_nr * LANES > MAX_TL:
                ok = False
                break
            per_chunk.append((int(edges[c]), int(edges[c + 1]),
                              rowstarts, nrows, lens, skips))
        if ok:
            return per_chunk
        nchunk *= 2
    return None


def _impact_eps(plane, weights: np.ndarray, rows: np.ndarray, k1: float,
                b_eff: float, avgdl: float) -> float:
    """Sound per-doc |exact f32 score − impact-kernel score| bound —
    THE impactpath._error_bound serve margin (one definition: the
    frontier kernel's verify rungs must certify against exactly the
    epsilon the XLA impact pass uses, or a future bound fix silently
    diverges the two ladders)."""
    from .impactpath import _error_bound
    return _error_bound(plane, weights, rows, k1, b_eff, avgdl)


def impact_frontier_enabled() -> bool:
    """The codec-v2 frontier-kernel gate: on by default, pinned off via
    OPENSEARCH_TPU_NO_IMPACT_FRONTIER (ablation / rollback — the dense
    tf·dl kernel then serves the frontier pass as before the rev).
    `=0` means "not disabled", matching the `!= "0"` parse every other
    flag in this module family uses (OPENSEARCH_TPU_REORDER & co.)."""
    import os
    return os.environ.get("OPENSEARCH_TPU_NO_IMPACT_FRONTIER", "0") \
        in ("", "0")


def _term_slot(al: AlignedPostings, pb, r: int
               ) -> Optional[Tuple[np.ndarray, int]]:
    if r < 0:
        return None
    a, b = pb.row_slice(r)
    return pb.doc_ids[a:b], int(al.starts_rows[r]) * LANES


def _chunk_slices(al: AlignedPostings, pb, rows: np.ndarray, ndocs: int
                  ) -> Optional[List[tuple]]:
    """Doc-range chunk decomposition for the pure term-group path."""
    return _chunk_slots([_term_slot(al, pb, int(r)) for r in rows], ndocs,
                        len(rows))


@TRACER.spanned("fastpath.prepare")
def _prepare_vqueries(seg: Segment, ctx, lts: Sequence, avgdl_cache: dict,
                      prune: Optional[Sequence[bool]] = None
                      ) -> Optional[List[List[_VQuery]]]:
    """-> per input query, its list of kernel rows (1 or NCHUNK); None entry
    = that query falls back to the XLA path. When `prune[qi]` is true the
    query streams impact heads (always single-launch) and carries the
    verify metadata; otherwise the full rows, chunked when oversized."""
    out: List[Optional[List[_VQuery]]] = []
    for qi, lt in enumerate(lts):
        al = get_aligned(seg, lt.field)
        pb = seg.postings.get(lt.field)
        if al is None or pb is None:
            out.append(None)
            continue
        nt = len(lt.terms)
        T_pad = next_pow2(nt, floor=1)
        rows = np.full(T_pad, -1, np.int64)
        for i, t in enumerate(lt.terms):
            rows[i] = pb.row(t)
        weights = np.zeros(T_pad, np.float32)
        weights[:nt] = np.asarray(lt.weights, np.float32)[:nt]
        if lt.field not in avgdl_cache:
            avgdl_cache[lt.field] = np.float32(ctx.avgdl(lt.field))
        sim = lt.sim
        b_eff = float(sim.b) if lt.has_norms else 0.0
        common = dict(qi=qi, T_pad=T_pad, weights=weights,
                      msm=float(lt.msm), avgdl=avgdl_cache[lt.field],
                      k1=float(sim.k1), b_eff=b_eff, field=lt.field)
        use_head = bool(prune[qi]) if prune is not None else False
        src_starts = al.head_starts_rows if use_head else al.starts_rows
        src_lens = al.head_lens if use_head else al.lens

        # single-launch case: every row fits the per-term bucket (always
        # true for heads: L_HEAD <= MAX_L)
        min_rows = HBM_ALIGN // LANES
        rowstarts = np.zeros(T_pad, np.int32)
        nrows = np.zeros(T_pad, np.int32)
        lens = np.zeros(T_pad, np.int32)
        skips = np.zeros(T_pad, np.int32)
        max_nr = min_rows
        fits = True
        clamped = False
        miss = np.zeros(T_pad, np.float32)
        for i, r in enumerate(rows):
            if r < 0:
                continue
            ln = int(src_lens[r])
            if use_head and al.clamped(int(r)):
                clamped = True
                miss[i] = float(weights[i]) * al.rem_bound(
                    int(r), float(sim.k1), b_eff, float(common["avgdl"]))
            if ln == 0:
                continue
            abs_el = int(src_starts[r]) * LANES
            dma_el = (abs_el // HBM_ALIGN) * HBM_ALIGN
            skip = abs_el - dma_el
            if skip + ln > MAX_L:
                fits = False
                break
            rowstarts[i] = dma_el // LANES
            nr = next_pow2((skip + ln + LANES - 1) // LANES, floor=min_rows)
            nrows[i] = nr
            lens[i] = ln
            skips[i] = skip
            max_nr = max(max_nr, nr)
        if fits and T_pad * max_nr * LANES <= MAX_TL:
            vq = _VQuery(L=max_nr * LANES, rowstarts=rowstarts,
                         nrows=nrows, lens=lens, skips=skips, dlo=0,
                         dhi=int(INT_MAX), **common)
            if use_head:
                vq.head = True
                vq.clamped = clamped
                vq.miss = miss
                vq.msm_true = float(lt.msm)
                vq.rows = rows
                # codec-v2 frontier kernel: the head pass scores from the
                # aligned quantized impact plane (fused_bm25_topk_impact,
                # ONE multiply per posting) and the verify rungs absorb
                # the kernel epsilon — outputs are candidate partials
                # either way. Negative boosts void the one-sided error
                # bound; those stay on the exact tf·dl kernel.
                plane = getattr(pb, "impact", None)
                if (plane is not None and al.d_imp is not None
                        and impact_frontier_enabled()
                        and not np.any(weights[:nt] < 0)):
                    vq.impact_pass = True
                    vq.eps = _impact_eps(plane, weights, rows,
                                         float(sim.k1), b_eff,
                                         float(common["avgdl"]))
                if clamped and vq.msm_true > 1.0:
                    # kernel collects by raw sum; the true msm filter runs
                    # in the exact rescore (a doc matching all terms but
                    # only some heads must not be dropped on partial counts)
                    vq.msm = 1.0
            out.append([vq])
            continue

        # oversized: doc-range chunk decomposition (each doc's postings live
        # in exactly one chunk, so msm counting and score sums stay exact)
        chunks = _chunk_slices(al, pb, rows, seg.ndocs)
        if chunks is None:
            out.append(None)
            continue
        vqs = []
        for dlo, dhi, rowstarts, nrows, lens, skips in chunks:
            L = int(max(nrows.max(), min_rows)) * LANES
            vqs.append(_VQuery(L=L, rowstarts=rowstarts, nrows=nrows,
                               lens=lens, skips=skips, dlo=dlo, dhi=dhi,
                               **common))
        out.append(vqs)
    return out


# the (kernel, plane and batch shapes, statics) a kernel was launched with:
# a launch whose key is new traces, lowers and compiles, and its
# `device.dispatch` span says so (`first_call`), as `_TimedProgram`'s does
# for the XLA programs; one entry a compiled kernel program
_LAUNCHED_SHAPES: set = set()


def _first_launch(*key) -> bool:
    if key in _LAUNCHED_SHAPES:
        return False
    _LAUNCHED_SHAPES.add(key)
    return True


def _launch_pure_groups_async(seg: Segment,
                              vq_lists: List[Optional[List[_VQuery]]],
                              K: int) -> list:
    """LAUNCH stage: group all kernel rows by shape, enqueue one kernel
    per group, and return the pending launches WITHOUT any device sync
    (oslint OSL504) — `_fetch_pure_groups` turns them into host results.
    -> [(gvqs, K_keep, unfetched (scores, docs, totals)), ...]."""
    tie_aware = _seg_tie_aware(seg)
    groups = {}
    for vqs in vq_lists:
        if vqs is None:
            continue
        for vq in vqs:
            # impact-frontier rows compile a DIFFERENT kernel (no
            # similarity statics), so they group apart from tf·dl rows —
            # and BECAUSE it takes no statics, (k1, b) must not split
            # their groups: one launch coalesces rows whose similarity
            # params differ (k1/b only feed each row's eps + host rescore)
            key = ((vq.field, vq.T_pad, None, None, True) if vq.impact_pass
                   else (vq.field, vq.T_pad, vq.k1, vq.b_eff, False))
            groups.setdefault(key, []).append(vq)
    pending = []
    for (field, T_pad, k1, b_eff, impact), gvqs in groups.items():
        al = get_aligned(seg, field)
        # ONE launch per group: DMA volume is set by per-term `nrows`, not L,
        # so every row rides the group's max-L variant — launch (and its
        # host<->device round trip) amortizes across the whole batch while
        # rare terms still move only their own bytes
        L = max(v.L for v in gvqs)
        # clamped (pruned) queries extract the FULL 128 output lanes, not
        # just the page window: the verifier's unseen-doc bound uses the
        # deepest kernel partial, and a 10-candidate pool leaves it so
        # high that every realistic multi-term query escalates (the
        # balanced mid-partial docs the page needs sit at ranks 10..128).
        # Impact-kernel rows do the same — their verify certifies seen-
        # but-lost docs against the deepest (approx + eps) partial.
        K_launch = (LANES if any(v.head and (v.clamped or v.impact_pass)
                                 for v in gvqs)
                    else K)
        if tie_aware:
            # BP-reordered segment: the kernel breaks score ties by
            # PERMUTED doc id, so `_assemble` re-breaks them by arrival
            # rank on host — extract the full lane window so the re-sort
            # sees past the page boundary (a tie class cut exactly at K
            # would otherwise keep the wrong member)
            K_launch = max(K_launch, LANES)
        rowstarts = np.stack([v.rowstarts for v in gvqs])
        nrows = np.stack([v.nrows for v in gvqs])
        lens = np.stack([v.lens for v in gvqs])
        skips = np.stack([v.skips for v in gvqs])
        weights = np.stack([v.weights for v in gvqs])
        msm = np.array([[v.msm] for v in gvqs], np.float32)
        avg = np.array([[v.avgdl] for v in gvqs], np.float32)
        dlo = np.array([[v.dlo] for v in gvqs], np.int32)
        dhi = np.array([[v.dhi] for v in gvqs], np.int32)
        # per-launch attribution: served queries over launches is the
        # coalescing ratio (`/_metrics`)
        METRICS.counter("fastpath.launches").inc()
        cost = _qc.current()
        if impact:
            # frontier pass on the quantized plane: weights fold
            # idf·boost·scale so the kernel is ONE multiply per posting
            # (the designated dequant shape, oslint OSL507); no
            # similarity statics — one compiled (T, L, K) variant serves
            # every (k1, b). Only codec-v2 segments emit impact_pass rows
            # (the aligned-layout build consults Segment.codec_version)
            assert getattr(seg, "codec_version", CODEC_V1) >= CODEC_V2
            plane = seg.postings[field].impact
            w_fold = (weights * np.float32(plane.scale)).astype(np.float32)
            if cost is not None:
                # the profile `cost` block names the kernel (acceptance:
                # fused_bm25_topk_impact reachable from the fastpath)
                cost.note_actual(int(nrows.sum()) * LANES * 8,
                                 int(lens.sum()), K_launch * len(gvqs),
                                 path="fused_bm25_topk_impact",
                                 segment=seg)
            STATS.inc("impact_frontier", len(gvqs))
            with TRACER.span("device.dispatch", program="frontier",
                             kernel="fused_bm25_topk_impact",
                             first_call=_first_launch(
                                 "impact", al.d_docs.shape, len(gvqs),
                                 T_pad, L, K_launch)):
                launched = fused_bm25_topk_impact(
                    al.d_docs, al.d_imp, rowstarts, nrows, lens, skips,
                    w_fold, msm, dlo, dhi, T=T_pad, L=L, K=K_launch)
            pending.append((gvqs, K_launch, launched))
            continue
        if cost is not None:
            # actual bytes moved = the kernel's DMA windows: per term,
            # nrows lane-rows of 8-byte (doc, packed tf·dl) slots;
            # scatter work = the true posting counts; top-k work = the
            # K output lanes extracted per kernel row
            cost.note_actual(int(nrows.sum()) * LANES * 8,
                             int(lens.sum()), K_launch * len(gvqs),
                             path="kernel")
        with TRACER.span("device.dispatch", program="frontier",
                         kernel="fused_bm25_topk_tfdl",
                         first_call=_first_launch(
                             "tfdl", al.d_docs.shape, len(gvqs), T_pad, L,
                             K_launch, k1, b_eff)):
            launched = fused_bm25_topk_tfdl(
                al.d_docs, al.d_tfdl, rowstarts, nrows, lens, skips,
                weights, msm, avg, dlo, dhi, T=T_pad, L=L, K=K_launch,
                k1=k1, b=b_eff)
        pending.append((gvqs, K_launch, launched))
    return pending


def _fetch_pure_groups(pending: list, K: int,
                       tie_aware: bool = False) -> dict:
    """FETCH stage for `_launch_pure_groups_async`:
    -> id(vq) -> (scores, docs, total, relation). `tie_aware` (the
    launching segment is BP-reordered) keeps every extracted lane so
    `_assemble`'s arrival-rank re-sort sees the full window."""
    # ONE device->host transfer for ALL groups' outputs: each np.asarray
    # is its own synchronizing round trip — per-array fetches would
    # multiply the batch-1 latency floor
    import jax
    with TRACER.span("device.wait", program="frontier"):
        fetched = jax.device_get([arrs for _gvqs, _kl, arrs in pending])
    results = {}
    for (gvqs, K_launch, _), (scores, docs, totals) in zip(pending,
                                                           fetched):
        for j, vq in enumerate(gvqs):
            keep = (K_launch
                    if (vq.head and (vq.clamped or vq.impact_pass))
                    or tie_aware else K)
            results[id(vq)] = (scores[j][:keep], docs[j][:keep],
                               int(totals[j][0]), "eq")
    return results


def _launch_pure_groups(seg: Segment,
                        vq_lists: List[Optional[List[_VQuery]]],
                        K: int) -> dict:
    """Synchronous launch+fetch (escalation rungs, host-loop callers)."""
    return _fetch_pure_groups(_launch_pure_groups_async(seg, vq_lists, K),
                              K, tie_aware=_seg_tie_aware(seg))


def _unseen_bound(al: AlignedPostings, pb, dl_col, vq: _VQuery,
                  partial_k: float) -> float:
    """Max possible TRUE score of any doc OUTSIDE the kernel's candidate
    set — the MaxScore-style analysis adapted to head pruning.

    An unseen doc misses some (possibly empty) subset S of the clamped
    terms' heads. Its score splits as (contributions from terms whose rows/
    heads contain it) + (remainder contributions of terms in S):
      - in-head part: <= partial_k (it lost the kernel top-K) AND
                      <= sum_{t not in S} w_t * full_bound_t
      - remainder:    <= sum_{t in S} miss_t  (exact frontier bounds)
    Take min of the two in-head bounds per subset, max over NONEMPTY
    subsets. S = {} (doc fully scored by the kernel but outside its top-K)
    is NOT a displacement threat when msm == 1: every candidate's exact
    score dominates its kernel score, so theta >= partial_k and the kernel
    already ranked the loser under the (score desc, doc asc) result order —
    it sorts strictly after every window member even on an exact tie.
    With msm > 1 that argument breaks (the kernel collects with msm
    relaxed to 1, and the host msm filter can drop high-kernel-score
    candidates, pushing theta BELOW partial_k), so the S = {} bound must
    stay in. The IMPACT frontier kernel (vq.eps > 0) breaks it too: its
    partials live in the quantized domain, so a candidate's exact score
    no longer dominates its kernel score — callers pass partial_k
    already inflated by eps, and S = {} stays in."""
    T = len(vq.rows)
    cl = [i for i in range(T) if vq.miss is not None and vq.miss[i] > 0.0]
    # per-term single-posting bounds (lazy frontier, cached on the layout)
    fb = np.zeros(T, np.float32)
    for i, r in enumerate(vq.rows):
        if r >= 0:
            fb[i] = vq.weights[i] * al.full_bound(
                pb, int(r), vq.k1, vq.b_eff, float(vq.avgdl), dl_col)
    best = partial_k if (vq.msm_true > 1.0 or vq.eps > 0.0) else -np.inf
    for mask in range(1, 1 << len(cl)):
        in_s = [cl[j] for j in range(len(cl)) if mask >> j & 1]
        rem_part = float(sum(vq.miss[i] for i in in_s))
        inhead = float(sum(fb[i] for i in range(T) if i not in in_s))
        best = max(best, min(partial_k + rem_part, inhead + rem_part))
    return best


def _tie_serves(al: AlignedPostings, vq: _VQuery, theta: float,
                cand: np.ndarray, order: np.ndarray, window: int) -> bool:
    """Boundary-tie witness for SINGLE-term pruned queries: when the unseen
    bound exactly ties theta, the only docs that can attain it are remainder
    postings on the frontier points whose contribution equals the bound.
    The frontier stores the MIN doc id attaining each point; head selection
    is a stable impact sort (ties keep doc-ascending order), so those ids
    are typically larger than every in-head tie.  A tying unseen doc
    displaces the window iff its id sorts before the window's worst member —
    so min attaining id > id(window[-1]) proves the served page exact."""
    if len(vq.rows) != 1 or theta == -np.inf:
        return False
    fr = al.rem_frontiers.get(int(vq.rows[0]))
    if fr is None or len(fr) != 4:
        return False
    tfv, dlv, id_dlmin, id_any = fr
    if len(tfv) == 0:
        return False
    # MIRROR `_verify_pruned`'s exact-rescore arithmetic (same dtypes, same
    # op order) so tie detection is BIT-exact in the f32 domain theta lives
    # in: any frontier point strictly above theta escalates; only bit-equal
    # points count as attainers needing the id witness
    avg = max(float(vq.avgdl), 1e-9)
    kfac = vq.k1 * (1.0 - vq.b_eff + vq.b_eff * dlv / avg)
    # the final f32 cast PINS the compare to `_exact_rescore`'s per-term
    # rounding whatever dtype the frontier carries: an f64 contribution
    # half an ulp below theta would silently promote the whole compare to
    # f64 (NEP50) and miss a tie that exists in the served f32 domain.
    # `_frontier` emits f32 today, so this is an enforced invariant, not a
    # live-bug fix — see TestTieServesF32Domain
    contrib = (vq.weights[0] * tfv / (tfv + kfac)).astype(np.float32)
    theta32 = np.float32(theta)
    if np.any(contrib > theta32):
        return False                      # genuinely above: real displacer
    att = contrib == theta32
    if not att.any():
        return True                       # no remainder doc reaches theta
    # the dl_min witness covers a point only when one dl step strictly
    # lowers the f32 contribution (then no longer-doc posting can tie);
    # otherwise fall back to the whole-tf-class min id (always sound)
    kfac2 = vq.k1 * (1.0 - vq.b_eff
                     + vq.b_eff * (dlv + np.float32(1.0)) / avg)
    contrib2 = (vq.weights[0] * tfv / (tfv + kfac2)).astype(np.float32)
    ids = np.where(contrib2 < contrib, id_dlmin, id_any)
    return int(ids[att].min()) > int(cand[order[window - 1]])


def _seg_tie_aware(seg) -> bool:
    """True when `seg` is BP-reordered (index/reorder.py): host sorts
    must re-break score ties by arrival rank, and kernel-verbatim
    windows cannot be served past an unresolved boundary tie."""
    f = getattr(seg, "tie_ranks", None)
    return f is not None and f() is not None


def _tie_key(seg, cand: np.ndarray) -> np.ndarray:
    """Layout-invariant tie-break key for host (score, tie) sorts: the
    arrival rank on BP-reordered segments (index/reorder.py parity
    contract — pages must not depend on the permuted internal ids), the
    doc id everywhere else (identical by construction when doc order IS
    arrival order, so unreordered segments keep their historical
    ordering bit for bit)."""
    f = getattr(seg, "tie_ranks", None)
    tr = f() if f is not None else None
    return tr[cand] if tr is not None else cand


def _arrival_sort(seg, sc: np.ndarray, dc: np.ndarray):
    """Re-break a kernel window's score ties by arrival rank (invalid
    lanes last). Returns (sc, dc, full) — `full` True when every lane is
    valid, i.e. the extraction saturated and a boundary tie class may
    extend past its edge. THE one definition shared by every serving
    rung that re-sorts kernel-verbatim windows (a divergent copy here is
    a parity hole)."""
    ok = np.isfinite(sc) & (dc >= 0)
    key = np.where(ok, _tie_key(seg, np.maximum(dc, 0)),
                   np.int64(np.iinfo(np.int64).max))
    order = np.lexsort((key, -sc))
    return sc[order], dc[order], int(ok.sum()) == len(sc)


def _tie_cut_at_edge(sc: np.ndarray, full: bool, K: int) -> bool:
    """True when the page-boundary tie class reaches the END of a
    saturated extracted window: an unextracted doc with the same score
    but earlier arrival may deserve the slot — the caller must decline
    to a rung that resolves the class exactly."""
    return full and len(sc) >= K and sc[K - 1] == sc[-1]


def _chunk_tie_ambiguous(parts, sc: np.ndarray, dc: np.ndarray,
                        K: int) -> bool:
    """Multi-chunk analog of `_tie_cut_at_edge` over the merged window:
    a FULL chunk window whose deepest lane ties (or beats) the merged
    page boundary may have cut an arrival-earlier tie member at its own
    extraction edge (unextracted chunk docs score <= its deepest lane,
    so a strictly lower deepest lane proves the chunk complete above the
    boundary)."""
    if len(sc) < K or not np.isfinite(sc[K - 1]) or int(dc[K - 1]) < 0:
        return False
    boundary = float(sc[K - 1])
    for p in parts:
        psc, pdc = p[0], p[1]
        pok = np.isfinite(psc) & (pdc >= 0)
        if len(psc) and int(pok.sum()) == len(psc) \
                and float(psc[-1]) >= boundary:
            return True
    return False


def _exact_rescore(seg: Segment, vq: _VQuery, cand: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact scores + per-term match counts of `cand` against the FULL
    rows (vectorized searchsorted per term — the analog of Lucene
    re-walking a WAND candidate)."""
    pb = seg.postings.get(vq.field)
    dl = seg.doc_lens.get(vq.field)
    dl_c = (dl[cand].astype(np.float32) if dl is not None
            else np.zeros(len(cand), np.float32))
    kfac = vq.k1 * (1.0 - vq.b_eff
                    + vq.b_eff * dl_c / max(float(vq.avgdl), 1e-9))
    exact = np.zeros(len(cand), np.float32)
    counts = np.zeros(len(cand), np.int64)
    for i, r in enumerate(vq.rows):
        if r < 0:
            continue
        a, b = pb.row_slice(int(r))
        if b <= a:
            continue   # term has no postings here (e.g. empty FILTERED row)
        rowdocs = pb.doc_ids[a:b]
        pos = np.searchsorted(rowdocs, cand)
        pos_c = np.minimum(pos, b - a - 1)
        found = rowdocs[pos_c] == cand
        tf = np.where(found, pb.tfs[a + pos_c], 0.0).astype(np.float32)
        exact += np.where(found, vq.weights[i] * tf / (tf + kfac),
                          0.0).astype(np.float32)
        counts += found
    return exact, counts


def _noheads_bound(al: AlignedPostings, vq: _VQuery,
                   frontier_of=None, rows_all: bool = False) -> float:
    """Max TRUE score of any doc outside EVERY queried head (the unseen
    docs of the candidate-union escalation): all of its contributions come
    from clamped remainders and share ONE doc length d, so
        bound = max_d  sum_t  g_t(d),
    where g_t(d) = w_t * max{tf/(tf+k(d)) : (tf, dlmin) in rem frontier of
    t, dlmin <= d} and d ranges over the frontier dl minima (contribution
    is decreasing and feasibility increasing in d, so the max over real
    lengths is attained on that grid). Docs matching fewer than msm terms
    can't pass, so grid points with too few feasible terms are skipped.
    Unclamped rows don't appear: any doc matching one is a candidate.
    `frontier_of` overrides the per-row remainder frontier (the tier-2
    rescue passes its deeper-cut frontiers); `rows_all` makes EVERY valid
    row participate (the quality-tier view restricts every row, so every
    term has out-of-view postings an unseen doc could match)."""
    if rows_all:
        cl = [i for i, r in enumerate(vq.rows) if r >= 0]
    else:
        cl = [i for i, r in enumerate(vq.rows)
              if r >= 0 and al.clamped(int(r))]
    if not cl:
        return -np.inf
    fronts = []
    ds = []
    for i in cl:
        row = int(vq.rows[i])
        fr = (frontier_of(row) if frontier_of is not None
              else al.rem_frontiers.get(row))
        if fr is None:
            continue
        tfv = np.asarray(fr[0], np.float64)
        dlv = np.asarray(fr[1], np.float64)
        if len(tfv):
            fronts.append((i, tfv, dlv))
            ds.append(dlv)
    if not fronts:
        return -np.inf
    avg = max(float(vq.avgdl), 1e-9)
    best = -np.inf
    for d in np.unique(np.concatenate(ds)):
        k = max(vq.k1 * (1.0 - vq.b_eff + vq.b_eff * float(d) / avg),
                1e-9)
        total = 0.0
        nfeas = 0
        for i, tfv, dlv in fronts:
            feas = dlv <= d
            if not feas.any():
                continue
            nfeas += 1
            total += float(vq.weights[i]) * float(
                np.max(tfv[feas] / (tfv[feas] + k)))
        if nfeas and nfeas >= vq.msm_true:
            best = max(best, total)
    return best


def _p2_candidates(vq: _VQuery, pb, ids_of) -> Optional[np.ndarray]:
    """The candidate union of one query: every doc any queried head
    mentions (`ids_of(row)`; None = the head is the full row)."""
    ids = []
    for r in vq.rows:
        if r < 0:
            continue
        r = int(r)
        hid = ids_of(r)
        if hid is None:
            a, b = pb.row_slice(r)
            hid = pb.doc_ids[a:b]
        ids.append(np.asarray(hid, np.int64))
    if not ids:
        return None
    cand = np.unique(np.concatenate(ids))
    return cand if len(cand) else None


def _p2_decide(al: AlignedPostings, vq: _VQuery, cand: np.ndarray,
               exact: np.ndarray, counts: np.ndarray, window: int, K: int,
               frontier_of, tie: Optional[np.ndarray] = None
               ) -> Optional[tuple]:
    """Serve-or-escalate decision on exact-rescored candidates: certify the
    window against the dl-consistent `_noheads_bound` or return None."""
    pass_msm = counts >= vq.msm_true
    n_pass = int(pass_msm.sum())
    exact_m = np.where(pass_msm, exact, -np.inf).astype(np.float32)
    order = np.lexsort((cand if tie is None else tie, -exact_m))
    theta = (float(exact_m[order[window - 1]]) if n_pass >= window
             else -np.inf)
    bound = _noheads_bound(al, vq, frontier_of)
    # equality escalates (frontier bounds are attained), as in phase 1
    if bound >= theta:
        return None
    keep = order[pass_msm[order]][:K]
    sc2 = np.full(K, -np.inf, np.float32)
    dc2 = np.full(K, -1, np.int32)
    sc2[: len(keep)] = exact_m[keep]
    dc2[: len(keep)] = cand[keep].astype(np.int32)
    return (sc2, dc2, n_pass, "gte")


def _rescore_many(seg: Segment, jobs: List[tuple]) -> List[tuple]:
    """Exact scores + match counts for a BATCH of (vq, cand) rescore jobs.

    rescore_mode() "device": one jit launch per (field, T, candidate
    bucket, sim) group over the already-resident aligned buffers
    (ops/rescore.exact_rescore_batch via compiler.build_rescore_program)
    — the whole escalation queue rides a handful of launches instead of a
    host searchsorted pass per query. "host": the numpy oracle
    `_exact_rescore` per job (JAX_PLATFORMS=cpu fallback; also the path
    parity tests pin the device results against, bit for bit)."""
    import time
    if not jobs:
        return []
    if rescore_mode() != "device":
        t0 = time.perf_counter()
        out = [_exact_rescore(seg, vq, cand) for vq, cand in jobs]
        dt_ms = (time.perf_counter() - t0) * 1e3
        RESCORE_STATS.inc("host_calls", len(jobs))
        RESCORE_STATS.inc("host_wall_ms", dt_ms)
        METRICS.histogram("fastpath.rescore.host_ms").record(dt_ms)
        return out
    return _rescore_many_device(seg, jobs)


def _rescore_many_device(seg: Segment, jobs: List[tuple]) -> List[tuple]:
    import time

    import jax

    from . import compiler as C
    from ..ops.rescore import probe_rounds, rescore_elem_budget

    t0 = time.perf_counter()
    out: List[Optional[tuple]] = [None] * len(jobs)
    groups: dict = {}
    host_jobs: List[int] = []
    for j, (vq, cand) in enumerate(jobs):
        cb = C.rescore_cand_bucket(len(cand))
        al = get_aligned(seg, vq.field)
        # ineligible shapes (union past the bucket cap, element offsets
        # beyond i32 on a pathologically large buffer) take the host pass
        # for just that job — the rest of the batch stays on device
        if (cb is None or al is None
                or int(al.starts_rows[-1] + 1) * LANES + int(al.lens[-1])
                > 2**31 - 1):
            host_jobs.append(j)
            continue
        key = (vq.field, len(vq.rows), cb, vq.k1, vq.b_eff)
        groups.setdefault(key, []).append(j)
    for (field, T, cb, k1, b_eff), idxs in groups.items():
        al = get_aligned(seg, field)
        run = C.build_rescore_program(T, cb, k1, b_eff)
        # bounded [QB, T, C] probe intermediates: split oversized groups
        # into sequential launches
        step = rescore_elem_budget(T, cb)
        for lo in range(0, len(idxs), step):
            part = idxs[lo: lo + step]
            QB = next_pow2(len(part), floor=1)
            starts = np.zeros((QB, T), np.int32)
            lens = np.zeros((QB, T), np.int32)
            weights = np.zeros((QB, T), np.float32)
            avgdl = np.ones((QB, 1), np.float32)
            cands = np.full((QB, cb), INT_MAX, np.int32)
            for qj, j in enumerate(part):
                vq, cand = jobs[j]
                for i, r in enumerate(vq.rows):
                    if r < 0:
                        continue
                    starts[qj, i] = int(al.starts_rows[int(r)]) * LANES
                    lens[qj, i] = int(al.lens[int(r)])
                weights[qj] = vq.weights
                avgdl[qj, 0] = vq.avgdl
                cands[qj, : len(cand)] = cand.astype(np.int32)
            rounds = probe_rounds(lens, al.d_docs.shape[0])
            launched = run(al.d_docs, al.d_tfdl, starts, lens, weights,
                           avgdl, cands, rounds)
            with TRACER.span("device.wait", program="rescore"):
                exact, counts = jax.device_get(launched)
            for qj, j in enumerate(part):
                n = len(jobs[j][1])
                out[j] = (exact[qj, :n], counts[qj, :n].astype(np.int64))
            RESCORE_STATS.inc("device_launches")
            RESCORE_STATS.inc("device_queries", len(part))
            RESCORE_STATS.inc("device_cands", int(
                sum(len(jobs[j][1]) for j in part)))
            RESCORE_STATS.inc("device_probe_elems",
                              QB * cb * int(rounds.sum()))
    t_host = 0.0
    for j in host_jobs:
        vq, cand = jobs[j]
        th = time.perf_counter()
        out[j] = _exact_rescore(seg, vq, cand)
        t_host += time.perf_counter() - th
        RESCORE_STATS.inc("host_calls")
    # per-path attribution: a host-ineligible job's numpy time must not
    # inflate device_wall_ms — that's the serialization signal these
    # stats exist to expose
    dev_ms = (time.perf_counter() - t0 - t_host) * 1e3
    RESCORE_STATS.inc("host_wall_ms", t_host * 1e3)
    RESCORE_STATS.inc("device_wall_ms", dev_ms)
    METRICS.histogram("fastpath.rescore.device_ms").record(dev_ms)
    return out


def _phase2_batch(seg: Segment, vq_lists, specs: Sequence, results: dict,
                  redo: List[int], K: int) -> List[int]:
    """Candidate-union escalation — the cheap middle rung between the
    pruned kernel pass and the dense rerun, batched across every query the
    phase-1 verify failed. The kernel's top-K-by-PARTIAL misses 'balanced'
    docs whose per-term partials are mid-pack but whose sum is competitive
    (measured: 100% of clamped multi-term bench queries escalated on it).
    Rescoring the ENTIRE head union (every doc any head mentions,
    <= T*L_HEAD candidates) recovers exactly those docs: a doc outside ALL
    heads is then bounded by the dl-consistent `_noheads_bound`, which
    sits well below the top-K threshold on real corpora. Totals stay the
    'gte' contract.

    Tier 1 rescores every failed query's head union in ONE `_rescore_many`
    batch; the still-unproven tail retries on lazily-built 4x-deeper
    tier-2 heads (the remainder bound drops with the cut depth, catching
    most of the multi-term stopword-class tail) as a second batch. Returns
    the queries still unproven (-> quality-tier rung, then dense)."""
    jobs: List[tuple] = []
    meta: List[tuple] = []          # (qi, vq, cand)
    still: List[int] = []
    for qi in redo:
        vq = vq_lists[qi][0]
        pb = seg.postings.get(vq.field)
        al = get_aligned(seg, vq.field)
        cand = _p2_candidates(vq, pb, al.head_ids.get)
        if cand is None:
            still.append(qi)
            continue
        jobs.append((vq, cand))
        meta.append((qi, vq, cand))
    tier2: List[tuple] = []
    for (qi, vq, cand), (exact, counts) in zip(meta,
                                               _rescore_many(seg, jobs)):
        al = get_aligned(seg, vq.field)
        ver = _p2_decide(al, vq, cand, exact, counts,
                         int(specs[qi].window or K), K, None,
                         tie=_tie_key(seg, cand))
        if ver is not None:
            results[id(vq)] = ver
            STATS.inc("pruned_rescued")
        else:
            tier2.append((qi, vq))
    jobs2: List[tuple] = []
    meta2: List[tuple] = []
    for qi, vq in tier2:
        pb = seg.postings.get(vq.field)
        al = get_aligned(seg, vq.field)
        dl_col = seg.doc_lens.get(vq.field)
        h2 = {int(r): al.head2(pb, dl_col, int(r))
              for r in vq.rows if r >= 0 and al.clamped(int(r))}
        cand = _p2_candidates(
            vq, pb, lambda row: h2[row][0] if row in h2 else None)
        if cand is None:
            still.append(qi)
            continue
        jobs2.append((vq, cand))
        meta2.append((qi, vq, cand, h2))
    for (qi, vq, cand, h2), (exact, counts) in zip(
            meta2, _rescore_many(seg, jobs2)):
        al = get_aligned(seg, vq.field)
        ver = _p2_decide(al, vq, cand, exact, counts,
                         int(specs[qi].window or K), K,
                         lambda row, _h2=h2, _al=al:
                         _h2[row][1] if row in _h2
                         else _al.rem_frontiers.get(row),
                         tie=_tie_key(seg, cand))
        if ver is not None:
            results[id(vq)] = ver
            STATS.inc("pruned_rescued")
            STATS.inc("pruned_rescued2")
        else:
            still.append(qi)
    return still


def _phase2_rescore(seg: Segment, vq: _VQuery, window: int, K: int
                    ) -> Optional[tuple]:
    """Single-query wrapper over the batched middle rung (kept for tests
    and external callers; `_run_pure` batches via `_phase2_batch`)."""
    results: dict = {}

    class _S:
        pass

    s = _S()
    s.window = window
    still = _phase2_batch(seg, [[vq]], [s], results, [0], K)
    return None if still else results[id(vq)]


QUALITY_SHARE = 8       # quality tier keeps ~ndocs/QUALITY_SHARE docs
QUALITY_MIN_NDOCS = 1 << 16   # below this, dense is already cheap


def _quality_tier(seg: Segment, field: str):
    """Query-independent static index pruning (the device analog of the
    'quality-tier' / static pruning literature Lucene-world engines use
    for service tiers): keep the ~1/QUALITY_SHARE docs whose BEST
    per-posting nominal impact is highest. Scores on the restricted view
    are EXACT for view docs (the view restricts DOCS, so a kept doc keeps
    every posting), and every posting of an outside doc has nominal
    impact < tau by construction — the per-row out-of-view frontiers
    certify the served window under any query-time similarity. One
    vectorized pass per (segment, field), cached.

    Returns (FilterList, frontier_of) or None (segment too small /
    ineligible layout)."""
    cache = seg.__dict__.setdefault("_fastpath_quality", {})
    if field in cache:
        return cache[field]
    out = None
    pb = seg.postings.get(field)
    dl = seg.doc_lens.get(field)
    # real Segments only: the filter-cache infrastructure keys on seg.uid,
    # which ShardView/FilteredSegView facades don't have — those continue
    # to the dense rung as before
    if (pb is not None and pb.size > 0 and seg.ndocs >= QUALITY_MIN_NDOCS
            and getattr(seg, "uid", None) is not None
            and get_aligned(seg, field) is not None):
        imp = _plane_impacts(pb)     # codec v2: precomputed, no O(P) map
        if imp is None:
            dl_of = (dl[pb.doc_ids].astype(np.float32) if dl is not None
                     else np.zeros(len(pb.doc_ids), np.float32))
            avg = max(float(dl_of.mean()), 1.0)
            imp = _nominal_impact(pb.tfs, dl_of, avg)
        docmax = np.zeros(seg.ndocs, np.float32)
        np.maximum.at(docmax, pb.doc_ids, imp)
        target = max(seg.ndocs // QUALITY_SHARE, QUALITY_MIN_NDOCS // 4)
        tau = np.float32(np.partition(docmax, seg.ndocs - target)
                         [seg.ndocs - target])
        mask = docmax >= tau
        # impact ties at tau can inflate the kept set far past the
        # target, inverting the rung's cost model — decline rather than
        # launch a near-dense-sized view
        if 0 < mask.sum() <= 2 * target:
            host_docs = np.flatnonzero(mask).astype(np.int32)
            nbytes = mask.nbytes + host_docs.nbytes
            fl = FilterList(host_docs, None, len(host_docs), nbytes, mask,
                            ("_quality", field, QUALITY_SHARE))
            from ..obs.hbm_ledger import LEDGER
            LEDGER.register(
                "quality_tier", nbytes, owner=fl, segment=seg,
                label=f"fastpath-quality[{seg.name}][{field}]")
            frontiers: dict = {}

            def frontier_of(row: int, _f=frontiers, _pb=pb, _dl=dl,
                            _mask=mask):
                # per-row slices derived on demand: only the tiny
                # frontiers are retained, not per-posting arrays
                fr = _f.get(row)
                if fr is None:
                    a, b = _pb.row_slice(row)
                    rd = _pb.doc_ids[a:b]
                    sel = ~_mask[rd]
                    dls = (_dl[rd[sel]].astype(np.float32)
                           if _dl is not None
                           else np.zeros(int(sel.sum()), np.float32))
                    fr = _frontier(_pb.tfs[a:b][sel], dls)
                    _f[row] = fr
                return fr

            out = (fl, frontier_of)
    cache[field] = out
    return out


def _dview_rescue(seg: Segment, ctx, lts: Sequence, specs: Sequence,
                  vq_lists, results: dict, redo: List[int], K: int
                  ) -> List[int]:
    """Quality-tier escalation rung: run ALL still-unproven queries as ONE
    batched dense launch over the quality view (exact scores, ~1/8 the
    postings), certify each against the out-of-view frontiers, and return
    the queries that still need the full dense pass. Mixed-field batches
    group per field (one view launch each)."""
    by_field: dict = {}
    for qi in redo:
        by_field.setdefault(vq_lists[qi][0].field, []).append(qi)
    still: List[int] = []
    for field, qis in by_field.items():
        still.extend(_dview_rescue_field(seg, ctx, lts, specs, vq_lists,
                                         results, qis, K, field))
    STATS.inc("pruned_dview", len(redo) - len(still))
    return still


def _dview_rescue_field(seg: Segment, ctx, lts: Sequence, specs: Sequence,
                        vq_lists, results: dict, redo: List[int], K: int,
                        field: str) -> List[int]:
    qt = _quality_tier(seg, field)
    if qt is None:
        return redo
    fl, frontier_of = qt
    fp = _filtered_postings(seg, field, fl)
    if fp is None:
        return redo
    view = _filtered_view(seg, field, fp, (seg.uid, field, fl.key))
    al = get_aligned(seg, field)
    dlists = _prepare_vqueries(view, ctx, [lts[qi] for qi in redo], {})
    if dlists is None:
        return redo
    vres = _launch_pure_groups(view, dlists, K)
    tie_aware = _seg_tie_aware(seg)
    still = []
    for qi, dvqs in zip(redo, dlists):
        served = False
        ambiguous = False
        if dvqs is not None:
            if len(dvqs) == 1:
                sc, dc, total, _ = vres[id(dvqs[0])]
                if tie_aware:
                    # reordered segment: re-break the device window's
                    # score ties in arrival order (view docs are
                    # original ids, so the parent plane applies) —
                    # decline on a boundary tie at the extraction edge:
                    # this rung serves into `exact_ids`, so nothing
                    # downstream would re-check
                    sc, dc, full = _arrival_sort(seg, sc, dc)
                    ambiguous = _tie_cut_at_edge(sc, full, K)
            else:
                parts = [vres[id(v)] for v in dvqs]
                sc = np.concatenate([p[0] for p in parts])
                dc = np.concatenate([p[1] for p in parts])
                total = sum(p[2] for p in parts)
                ok = dc >= 0
                key = np.where(ok, _tie_key(seg, np.maximum(dc, 0)),
                               np.int64(np.iinfo(np.int64).max))
                order = np.lexsort((key, -sc))[:K]
                sc, dc = sc[order], dc[order]
                if tie_aware:
                    ambiguous = _chunk_tie_ambiguous(parts, sc, dc, K)
            if ambiguous:
                STATS.inc("reorder_tie_fallback")
        if dvqs is not None and not ambiguous:
            valid = np.isfinite(sc) & (dc >= 0)
            window = int(specs[qi].window or K)
            theta = (float(sc[valid][window - 1])
                     if int(valid.sum()) >= window else -np.inf)
            # the ORIGINAL (pruned) vq carries .rows/.weights — same term
            # rows as the view launch, which runs the dense shape
            ovq = vq_lists[qi][0]
            bound = _noheads_bound(al, ovq, frontier_of, rows_all=True)
            if bound < theta:
                results[id(ovq)] = (sc[:K], dc[:K], int(total), "gte")
                served = True
        if not served:
            still.append(qi)
    return still


def _verify_pruned(seg: Segment, vq: _VQuery, sc: np.ndarray, dc: np.ndarray,
                   total: int, window: int, K: int) -> Optional[tuple]:
    """Prove a clamped pruned result exact, or None -> rerun dense.

    The kernel saw only each term's impact head, so candidate partial
    scores may miss contributions (doc outside some term's head). Exact-
    rescore the candidates on host (the analog of Lucene re-walking a WAND
    candidate), then accept iff the `_unseen_bound` subset analysis proves
    no unseen doc can displace the served window. Totals become a lower
    bound (relation "gte"), the contract the reference's default
    track-total-hits cap already has."""
    pb = seg.postings.get(vq.field)
    dl = seg.doc_lens.get(vq.field)
    al = get_aligned(seg, vq.field)
    valid = np.isfinite(sc) & (dc >= 0)
    cand = dc[valid].astype(np.int64)
    if len(cand) == 0:
        # heads matched nothing; matches could still exist past the heads
        if any(vq.miss[i] > 0 for i in range(len(vq.rows))):
            return None
        return (sc[:K], dc[:K], total, "eq")
    exact, counts = _exact_rescore(seg, vq, cand)
    pass_msm = counts >= vq.msm_true
    n_pass = int(pass_msm.sum())
    exact_m = np.where(pass_msm, exact, -np.inf).astype(np.float32)
    # the unseen-doc in-head bound: the DEEPEST kernel partial. Zero when
    # the kernel window wasn't full — then every head-matched doc is
    # already a candidate and an unseen doc has no in-head part at all.
    # Impact-kernel partials are quantized-domain: + eps lifts them to a
    # sound exact-domain bound (eps == 0.0 on the tf·dl kernel)
    partial_k = (float(sc[valid][-1]) + vq.eps
                 if len(cand) == len(sc) else 0.0)
    bound = _unseen_bound(al, pb, dl, vq, partial_k)
    tie = _tie_key(seg, cand)
    order = np.lexsort((tie, -exact_m))
    theta = (float(exact_m[order[window - 1]]) if n_pass >= window
             else -np.inf)
    # >= not >: the frontier bounds are ATTAINED by real docs, so an unseen
    # doc can tie theta exactly and would deserve the window slot under the
    # doc-id tie-break — equality must escalate to the dense pass, UNLESS
    # the tie witness below proves every attaining doc sorts after the
    # window boundary (single-term case: score quantization makes boundary
    # ties the COMMON case, and escalating on them re-runs dense every
    # time). The witness argument needs the EXACT kernel domain, so
    # impact-frontier passes (eps > 0) always escalate on a tie; so do
    # reordered segments (tie is the ARRIVAL rank there, and the frontier
    # id witness only bounds the permuted-id order).
    if bound >= theta:
        if (vq.eps > 0.0 or tie is not cand
                or not _tie_serves(al, vq, theta, cand, order, window)):
            return None
    keep = order[pass_msm[order]][:K]
    sc2 = np.full(K, -np.inf, np.float32)
    dc2 = np.full(K, -1, np.int32)
    sc2[: len(keep)] = exact_m[keep]
    dc2[: len(keep)] = cand[keep]
    total_out = n_pass if vq.msm_true > 1 else total
    return (sc2, dc2, total_out, "gte")


def _verify_impact_exact(seg: Segment, vq: _VQuery, sc: np.ndarray,
                         dc: np.ndarray, total: int, window: int, K: int
                         ) -> Optional[tuple]:
    """Certify an UNCLAMPED impact-kernel frontier pass (heads were the
    full rows, so the kernel saw EVERY posting — but its partials live in
    the quantized domain and cannot serve directly). Candidates are
    exact-rescored; when the kernel window wasn't full the candidate set
    is every matching doc and the page is exact by construction;
    otherwise a seen-but-lost doc carries kernel partial <= the deepest
    extracted value, so exact <= that + eps — certify it under theta or
    escalate. Totals are exact either way (the kernel counts every
    matching doc)."""
    valid = np.isfinite(sc) & (dc >= 0)
    cand = dc[valid].astype(np.int64)
    if len(cand) == 0:
        return (sc[:K], dc[:K], total, "eq")    # truly empty result set
    exact, counts = _exact_rescore(seg, vq, cand)
    pass_msm = counts >= vq.msm_true
    n_pass = int(pass_msm.sum())
    exact_m = np.where(pass_msm, exact, -np.inf).astype(np.float32)
    order = np.lexsort((_tie_key(seg, cand), -exact_m))
    if len(cand) == len(sc):
        theta = (float(exact_m[order[window - 1]]) if n_pass >= window
                 else -np.inf)
        bound = float(sc[valid][-1]) + vq.eps
        # equality escalates: a lost doc's exact score can tie theta and
        # would deserve the slot under the doc-id tie-break
        if bound >= theta:
            return None
    keep = order[pass_msm[order]][:K]
    sc2 = np.full(K, -np.inf, np.float32)
    dc2 = np.full(K, -1, np.int32)
    sc2[: len(keep)] = exact_m[keep]
    dc2[: len(keep)] = cand[keep]
    return (sc2, dc2, total, "eq")


def _launch_pure(seg: Segment, ctx, lts: Sequence,
                 specs: Sequence[FastSpec], K: int) -> Optional[tuple]:
    """LAUNCH stage of the pure term-group path: vquery prep + the
    impact-head (pruned) kernel first pass, enqueued but unfetched.
    Returns opaque state for `_finish_pure`, or None to fall back."""
    prune = [bool(s.prune_ok) for s in specs]
    vq_lists = _prepare_vqueries(seg, ctx, lts, {}, prune=prune)
    if vq_lists is None:
        return None
    # frontier rung: the impact-head (pruned) kernel first pass
    with TRACER.span("fastpath.frontier", queries=len(lts)), \
            METRICS.timer("fastpath.frontier"):
        pending = _launch_pure_groups_async(seg, vq_lists, K)
    return (vq_lists, pending)


def _finish_pure(seg: Segment, ctx, lts: Sequence,
                 specs: Sequence[FastSpec], K: int,
                 state: tuple) -> Optional[List[Optional[dict]]]:
    """FETCH stage of the pure path: device sync of the frontier pass,
    then host verification and the escalation ladder (whose rungs launch
    their own follow-up device work synchronously — only the hard tail
    pays a sync here) and final assembly."""
    vq_lists, pending = state
    results = _fetch_pure_groups(pending, K,
                                 tie_aware=_seg_tie_aware(seg))
    redo = []
    # id(vq) whose served entry the verify/rescue rungs produced in exact
    # arrival order — _assemble's reorder tie handling skips these
    exact_ids = set()
    with TRACER.span("fastpath.verify"), METRICS.timer("fastpath.verify"):
        for qi, vqs in enumerate(vq_lists):
            if vqs is None or len(vqs) != 1 or not vqs[0].head:
                continue
            vq = vqs[0]
            if not vq.clamped and not vq.impact_pass:
                continue                # heads were the full rows: exact
            sc, dc, total, _ = results[id(vq)]
            if vq.clamped:
                ver = _verify_pruned(seg, vq, sc, dc, total,
                                     int(specs[qi].window or K), K)
            else:
                # impact kernel over full rows: exact-rescore + certify
                # against (deepest approx partial + eps)
                ver = _verify_impact_exact(seg, vq, sc, dc, total,
                                           int(specs[qi].window or K), K)
            if ver is None:
                redo.append(qi)
            else:
                results[id(vq)] = ver
                exact_ids.add(id(vq))
    # rescued CLAMPED queries only: `pruned_served` below counts clamped
    # heads, so rescued impact-frontier (unclamped) queries must not be
    # subtracted from it — they were never in its base (the counter is
    # monotonic; an unmatched subtraction drives it negative)
    rescued_clamped = 0
    if redo:
        # middle rung: the candidate-union rescore for ALL failed queries,
        # batched into as few device launches as their shape buckets allow
        # (host numpy under JAX_PLATFORMS=cpu — see _rescore_many)
        n_redo = len(redo)
        if _fr.RECORDER.enabled and _fr.current():
            _fr.RECORDER.record(_fr.current(), "fastpath.rung",
                                rung="phase2_rescore", queries=n_redo,
                                mode=rescore_mode())
        with TRACER.span("fastpath.phase2_rescore", queries=n_redo,
                         mode=rescore_mode()), \
                METRICS.timer("fastpath.phase2_rescore"):
            before = redo
            redo = _phase2_batch(seg, vq_lists, specs, results, redo, K)
        for qi in set(before) - set(redo):
            vq = vq_lists[qi][0]
            exact_ids.add(id(vq))
            if vq.clamped:
                rescued_clamped += 1
    if redo:
        # last rung before dense: ONE batched exact launch over the
        # quality-tier view (~1/8 the postings). Only the hard tail pays
        # it; a certify saves the 8x-bigger dense launch, a miss adds a
        # small fraction of the dense cost it was about to pay anyway
        n_redo = len(redo)
        if _fr.RECORDER.enabled and _fr.current():
            _fr.RECORDER.record(_fr.current(), "fastpath.rung",
                                rung="quality_tier", queries=n_redo)
        with TRACER.span("fastpath.quality_tier", queries=n_redo), \
                METRICS.timer("fastpath.quality_tier"):
            before = redo
            redo = _dview_rescue(seg, ctx, lts, specs, vq_lists, results,
                                 redo, K)
        for qi in set(before) - set(redo):
            vq = vq_lists[qi][0]
            exact_ids.add(id(vq))
            if vq.clamped:
                rescued_clamped += 1
    if redo:
        STATS.inc("pruned_escalated", len(redo))
        if _fr.RECORDER.enabled and _fr.current():
            _fr.RECORDER.record(_fr.current(), "fastpath.rung",
                                rung="dense_escalation", queries=len(redo))
        with TRACER.span("fastpath.dense", queries=len(redo)), \
                METRICS.timer("fastpath.dense"):
            dense_lists = _prepare_vqueries(seg, ctx,
                                            [lts[qi] for qi in redo], {})
            if dense_lists is None:
                dense_lists = [None] * len(redo)
            for qi, dvqs in zip(redo, dense_lists):
                vq_lists[qi] = dvqs
            results.update(_launch_pure_groups(seg, dense_lists, K))
    STATS.inc("pruned_served", sum(
        1 for vqs in vq_lists
        if vqs is not None and len(vqs) == 1 and vqs[0].head
        and vqs[0].clamped) - rescued_clamped)
    return _assemble(vq_lists, results, K, seg=seg, exact_ids=exact_ids)


def _run_pure(seg: Segment, ctx, lts: Sequence, specs: Sequence[FastSpec],
              K: int) -> Optional[List[Optional[dict]]]:
    """The pure term-group path, synchronous: pruned first pass, host
    verification, dense rerun for the (rare) queries whose bound check
    fails. Launch/fetch split available via `_launch_pure`/`_finish_pure`
    (the serving pipeline's seam)."""
    state = _launch_pure(seg, ctx, lts, specs, K)
    if state is None:
        return None
    return _finish_pure(seg, ctx, lts, specs, K, state)


@TRACER.spanned("fastpath.assemble")
def _assemble(vq_lists, results: dict, K: int, transform=None,
              seg=None, exact_ids=frozenset()) -> List[Optional[dict]]:
    """Reassemble per-query outputs from per-kernel-row results (chunked
    queries merge their chunk top-Ks on host; stable merge: score desc,
    doc asc on ties, matching the kernel — arrival-rank ties on
    reordered segments when `seg` is passed). `exact_ids`: id(vq) of
    entries the verify/rescue rungs already produced in exact arrival
    order (they skip the reorder tie handling)."""
    tie_aware = seg is not None and _seg_tie_aware(seg)
    out: List[Optional[dict]] = []
    for qi, vqs in enumerate(vq_lists):
        if vqs is None:
            out.append(None)
            continue
        rel = "eq"
        if len(vqs) == 1:
            entry = results[id(vqs[0])]
            sc, dc, total = entry[0], entry[1], entry[2]
            if len(entry) > 3:
                rel = entry[3]
            if tie_aware and id(vqs[0]) not in exact_ids:
                # kernel-verbatim window on a BP-reordered segment: the
                # kernel broke score ties by PERMUTED id — re-break by
                # arrival rank (reorder parity contract). The deep
                # K_launch extraction (tie_aware launch) makes this sort
                # see past the page boundary. Entries the verify/rescue
                # rungs produced (`exact_ids`) are already arrival-
                # ordered exact pages and skip this.
                sc, dc, full = _arrival_sort(seg, sc, dc)
                if _tie_cut_at_edge(sc, full, K):
                    # decline: the general path widens its extraction
                    # window until the boundary class is whole
                    STATS.inc("reorder_tie_fallback")
                    out.append(None)
                    continue
                sc, dc = sc[:K], dc[:K]
        else:
            parts = [results[id(v)] for v in vqs]
            sc_all = np.concatenate([p[0] for p in parts])
            dc_all = np.concatenate([p[1] for p in parts])
            total = sum(p[2] for p in parts)
            if seg is not None:
                key = np.where(dc_all >= 0,
                               _tie_key(seg, np.maximum(dc_all, 0)),
                               np.int64(np.iinfo(np.int64).max))
            else:
                key = dc_all
            order = np.lexsort((key, -sc_all))[:K]
            sc = sc_all[order]
            dc = dc_all[order]
            if tie_aware and _chunk_tie_ambiguous(parts, sc, dc, K):
                STATS.inc("reorder_tie_fallback")
                out.append(None)
                continue
        if transform is not None:
            sc = transform(qi, sc)
        total_i = int(total)
        ms = float(sc[0]) if total_i > 0 and np.isfinite(sc[0]) else -np.inf
        out.append({"topk_key": sc, "topk_idx": dc, "topk_scores": sc,
                    "total": total_i, "max_score": ms, "total_rel": rel})
    return out


# ---------------------------------------------------------------------
# bool/filtered path: filter doc lists + weighted-threshold kernel rows
# ---------------------------------------------------------------------

class FilterList:
    """Aligned sorted doc-id list for one (segment, filter conjunction) —
    the fastpath analog of the reference's cached filter bitsets
    (IndicesQueryCache): built once from the XLA path's dense masks, then
    every query carrying this filter rides it as a merge slot (selective
    filters) or triggers filter-specialized postings (dense filters)."""

    __slots__ = ("host_docs", "d_docs", "n", "nbytes", "mask", "key",
                 "hits", "__weakref__")

    def __init__(self, host_docs: np.ndarray, d_docs, n: int, nbytes: int,
                 mask: np.ndarray, key):
        self.host_docs = host_docs
        self.d_docs = d_docs
        self.n = n
        self.nbytes = nbytes
        self.mask = mask          # dense bool[ndocs] (for materialization)
        self.key = key
        self.hits = 0


_MAX_FILTER_LISTS = 32      # per segment


def _filter_list(seg: Segment, ctx, clauses) -> Optional[FilterList]:
    """Combined (ANDed) filter doc list for [(node, negated), ...]; cached
    per segment (LRU) keyed by the clauses' mask-cache digests — a cache hit
    costs only the host-cheap spec hashing, no mask materialization. None ->
    fall back (a clause's params were too big to hash)."""
    import collections

    import jax

    from . import compiler as C

    cache = seg.__dict__.setdefault("_fastpath_filters",
                                    collections.OrderedDict())
    key_parts = []
    prepped = []
    for node, neg in clauses:
        local: dict = {}
        spec = C.prepare(node, seg, ctx, local)
        mkey, mapping = C.filter_cache_key(spec, local, seg)
        if mkey is None:
            return None
        key_parts.append((mkey, neg))
        prepped.append((mkey, spec, local, mapping, neg))
    key = tuple(key_parts)
    fl = cache.get(key)
    if fl is not None:
        cache.move_to_end(key)
        return fl
    nd = seg.ndocs
    combined = np.ones(nd, bool)
    for (node, neg), (mkey, spec, local, mapping, _n) in zip(clauses,
                                                             prepped):
        mask = np.asarray(C.mask_for_key(mkey, spec, local, mapping, seg,
                                         needs=C.node_needs(node)))
        m = mask[:nd].astype(bool)
        combined &= ~m if neg else m
    docs = np.nonzero(combined)[0].astype(np.int32)
    n = len(docs)
    total = ((n + LANES - 1) // LANES) * LANES + MAX_L
    buf = np.full(total, INT_SENTINEL, np.int32)
    buf[:n] = docs
    # keep the dense mask only when this filter could ever take the
    # materialized-postings route; breaker-charge what we actually retain
    dense_capable = (n > _MATERIALIZE_MIN_DOCS
                     and n * _MATERIALIZE_DENSITY > seg.ndocs)
    mask_kept = combined if dense_capable else None
    fl = FilterList(docs, jax.device_put(buf), n, buf.nbytes, mask_kept, key)
    from ..obs.hbm_ledger import LEDGER
    charged = buf.nbytes + (combined.nbytes if dense_capable else 0)
    LEDGER.register("filter_list", charged, owner=fl, segment=seg,
                    label=f"fastpath-filter[{seg.name}]")
    while len(cache) >= _MAX_FILTER_LISTS:
        cache.popitem(last=False)
    cache[key] = fl
    return fl


# ---------------------------------------------------------------------
# dense filters: filter-specialized postings
# ---------------------------------------------------------------------
#
# The list-slot intersection pays O(filter size) merge work per query —
# right for selective filters (Lucene's conjunction likewise walks the
# rarer side), but a dense guardrail filter (status:published over half
# the corpus) would cost more than the scoring itself. The TPU answer is
# layout specialization: pre-intersect the postings with the filter ONCE
# per (segment, field, filter), realign, and run every later query at
# full pure-kernel speed — beating the reference, which re-walks its
# cached bitset on every query (reference IndicesQueryCache +
# ConjunctionDISI). Materialized on the filter's second use (dense +
# hot), byte-bounded global LRU.

_MATERIALIZE_MIN_DOCS = 1 << 18    # absolute floor
_MATERIALIZE_DENSITY = 8           # n * density > ndocs -> "dense" (>12.5%)
_FILTERED_MAX_BYTES = 6 << 30
_FILTERED_LRU: "OrderedDict[tuple, FilteredPostings]" = __import__(
    "collections").OrderedDict()
_FILTERED_BYTES = [0]
# msearch's per-body fallback runs searches on a thread pool; the LRU's
# move_to_end/popitem and the byte counter are not atomic under that
_FILTERED_LOCK = __import__("threading").RLock()


class FilteredPostings:
    """Filter-specialized aligned postings for one (segment, field,
    filter): the term rows of `field` restricted to filter-passing docs."""

    __slots__ = ("al", "starts", "host_docs", "host_tfs", "nbytes",
                 "view", "__weakref__")

    def __init__(self, al: AlignedPostings, starts: np.ndarray,
                 host_docs: np.ndarray, host_tfs: np.ndarray, nbytes: int):
        self.al = al
        self.starts = starts       # i64[nterms+1] filtered CSR row bounds
        self.host_docs = host_docs  # i32 filtered doc ids (chunk windows)
        self.host_tfs = host_tfs    # f32 filtered tfs (pruned-path rescore)
        self.nbytes = nbytes
        self.view = None            # lazy FilteredSegView (pruned bool path)


def _purge_filtered_for_uid(uid: int) -> None:
    with _FILTERED_LOCK:
        for k in [k for k in _FILTERED_LRU if k[0] == uid]:
            _FILTERED_BYTES[0] -= _FILTERED_LRU[k].nbytes
            del _FILTERED_LRU[k]


def _filtered_postings(seg: Segment, field: str, fl: FilterList
                       ) -> Optional[FilteredPostings]:
    import jax

    key = (seg.uid, field, fl.key)
    with _FILTERED_LOCK:
        fp = _FILTERED_LRU.get(key)
        if fp is not None:
            _FILTERED_LRU.move_to_end(key)
            return fp
    if get_aligned(seg, field) is None:     # validates tf/dl pack bounds
        return None
    pb = seg.postings.get(field)
    dl = seg.doc_lens.get(field)
    keep = fl.mask[pb.doc_ids]
    kc = np.zeros(len(pb.doc_ids) + 1, np.int64)
    np.cumsum(keep, out=kc[1:])
    new_starts = kc[pb.starts]
    new_docs = pb.doc_ids[keep]
    tfs = pb.tfs[keep]
    dl_of = (dl[new_docs].astype(np.int64) if dl is not None
             else np.zeros(len(new_docs), np.int64))
    packed = ((tfs.astype(np.int64) << DL_BITS) | dl_of).astype(np.int32)
    a_starts, a_docs, a_packed = align_csr_rows(new_starts, new_docs, packed,
                                                margin=MAX_L,
                                                alignment=LANES)
    nbytes = a_docs.nbytes + a_packed.nbytes
    al = AlignedPostings((a_starts[:-1] // LANES).astype(np.int64),
                         np.diff(new_starts).astype(np.int64),
                         jax.device_put(a_docs), jax.device_put(a_packed),
                         nbytes)
    fp = FilteredPostings(al, new_starts, new_docs, tfs, nbytes)
    from ..obs.hbm_ledger import LEDGER
    LEDGER.register("filtered_postings", nbytes, owner=fp, segment=seg,
                    label=f"fastpath-filtered[{seg.name}][{field}]")
    if not hasattr(seg, "_filtered_fin"):
        import weakref
        seg._filtered_fin = weakref.finalize(seg, _purge_filtered_for_uid,
                                             seg.uid)
    with _FILTERED_LOCK:
        # two threads can race the same miss: keep the winner so the byte
        # counter never double-counts one key (the loser's breaker charge is
        # released by its weakref finalizer when `fp` is dropped)
        prev = _FILTERED_LRU.get(key)
        if prev is not None:
            _FILTERED_LRU.move_to_end(key)
            return prev
        _FILTERED_LRU[key] = fp
        _FILTERED_BYTES[0] += nbytes
        while _FILTERED_BYTES[0] > _FILTERED_MAX_BYTES \
                and len(_FILTERED_LRU) > 1:
            _k, _v = _FILTERED_LRU.popitem(last=False)
            _FILTERED_BYTES[0] -= _v.nbytes
    return fp


class FilteredSegView:
    """Segment facade over filter-specialized postings: the filtered CSR
    (ORIGINAL doc ids) presented as a one-field segment, so the PURE
    pipeline — impact heads, remainder frontiers, verified pruning — runs
    unchanged on filtered bool queries. Doc lens/live come from the real
    segment (doc ids are original); docs outside the filter appear in no
    row, so match counts and totals are filtered automatically."""

    def __init__(self, seg: Segment, field: str, fp: "FilteredPostings"):
        from ..index.segment import PostingsBlock

        pb = seg.postings[field]
        self.name = f"{seg.name}|filtered"
        self.ndocs = seg.ndocs
        self.ndocs_pad = seg.ndocs_pad
        self.live_count = seg.live_count
        self.postings = {field: PostingsBlock(
            field=field, vocab=pb.vocab, terms=pb.terms,
            starts=fp.starts.astype(np.int64), doc_ids=fp.host_docs,
            tfs=fp.host_tfs)}
        self.doc_lens = seg.doc_lens
        # doc ids are original, so the parent's arrival tie ranks apply
        # verbatim (reorder parity: ties must not break on permuted ids)
        self.tie_ranks = seg.tie_ranks


def _filtered_view(seg: Segment, field: str, fp: "FilteredPostings",
                   key) -> FilteredSegView:
    with _FILTERED_LOCK:
        if fp.view is None:
            view = FilteredSegView(seg, field, fp)
            # build the view's aligned layout eagerly and charge it to the
            # SAME byte budget as fp itself: it is a second device copy of
            # the filtered postings, and the LRU cap must see both. Only
            # account while fp is still a live LRU member — a concurrent
            # eviction already subtracted fp.nbytes, and inflating the
            # counter for a dead entry would never be undone
            al = get_aligned(view, field)
            if al is not None and _FILTERED_LRU.get(key) is fp:
                fp.nbytes += al.nbytes
                _FILTERED_BYTES[0] += al.nbytes
            fp.view = view
    return fp.view


class _PseudoLT:
    """LTerms-shaped adapter for a family-only bool spec, so it can ride
    the pure pruned pipeline over a FilteredSegView."""

    def __init__(self, spec: FastSpec):
        self.field = spec.field
        self.terms = [t for t, _w, _c in spec.slots]
        self.weights = np.asarray([w for _t, w, _c in spec.slots],
                                  np.float32)
        self.raw_boosts = self.weights
        # all-required slots (operator=and) == msm over every term
        self.msm = (len(spec.slots) if spec.n_required == len(spec.slots)
                    else max(int(spec.fam_msm), 1))
        self.sim = spec.sim
        self.has_norms = spec.has_norms
        self.aux = None


def _family_only(spec: FastSpec) -> bool:
    """bool spec == a single term group + filters, where the pass rule is
    a plain minimum-match count: either one counted family (shoulds /
    msm), or ALL slots required (operator=and -> msm = nterms). Both are
    a pure msm term group over the filtered doc set."""
    if not (spec.kind == "bool" and spec.filter_clauses
            and spec.const_score is None and spec.field is not None
            and len(spec.slots) > 0
            and spec.sim is not None and spec.sim.sim_id == ops.SIM_BM25):
        return False
    counted_family = (spec.fam_msm >= 1
                      and all(cw == 1 for _t, _w, cw in spec.slots))
    all_required = (spec.n_required == len(spec.slots)
                    and spec.fam_msm == 0)
    return counted_family or all_required


def _dense_hot(seg: Segment, fl: FilterList, nslots: int) -> bool:
    """Materialize when the filter is dense-capable (mask retained) AND
    either repeated (hits counted AFTER this check, so >=1 means second
    use) or too large for the list path at all — falling back to the XLA
    plan there would cost far more than one pre-intersection."""
    if fl.mask is None:
        return False
    ts = next_pow2(max(nslots, 1), floor=1)
    list_cap = MAX_CHUNKS * (MAX_TL // (2 * ts))
    return fl.hits >= 1 or fl.n > list_cap // 2


_dummy_hbm_arr = None


def _dummy_hbm():
    """Minimal aligned HBM operand for the unused buffer slots."""
    global _dummy_hbm_arr
    if _dummy_hbm_arr is None:
        import jax
        # one 4KB process-lifetime sentinel buffer; attributing it
        # would be noise, not accounting
        _dummy_hbm_arr = jax.device_put(  # oslint: disable=OSL506
            np.full(HBM_ALIGN, INT_SENTINEL, np.int32))
    return _dummy_hbm_arr


class _BVQuery:
    """One bool-kernel row: a whole query, or one doc-range chunk of it."""

    __slots__ = ("qi", "TS", "T", "L", "filtered", "rowstarts", "nrows",
                 "lens", "skips", "weights", "cw", "thresh", "avgdl", "dlo",
                 "dhi", "field", "k1", "b_eff", "fl", "albuf")

    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


@TRACER.spanned("fastpath.prepare")
def _prepare_bool_vqueries(seg: Segment, ctx, specs: Sequence[FastSpec],
                           avgdl_cache: dict
                           ) -> List[Optional[List[_BVQuery]]]:
    out: List[Optional[List[_BVQuery]]] = []
    for qi, spec in enumerate(specs):
        fl = None
        fp = None
        nslots = len(spec.slots)
        if spec.filter_clauses:
            fl = _filter_list(seg, ctx, spec.filter_clauses)
            if fl is None:
                out.append(None)
                continue
            # specialized postings only see docs that match SOME term, so
            # the route is sound only when passing requires a term match
            # (required slot or a counted family) — a bonus-only bool's
            # hits are the whole filter and need the filter slot
            needs_term = spec.n_required > 0 or spec.fam_msm >= 1
            if (nslots and needs_term and spec.field is not None
                    and _dense_hot(seg, fl, nslots)):
                # dense hot filter: run on filter-specialized postings at
                # full kernel speed instead of merging a huge doc list
                fp = _filtered_postings(seg, spec.field, fl)
            fl.hits += 1
        TS = next_pow2(max(nslots, 1), floor=1)
        filtered = fl is not None and fp is None
        T = 2 * TS if filtered else TS
        al = pb = None
        if nslots:
            al = fp.al if fp is not None else get_aligned(seg, spec.field)
            pb = seg.postings.get(spec.field)
            if al is None or pb is None:
                out.append(None)
                continue
        weights = np.zeros(TS, np.float32)
        cw = np.zeros(T, np.float32)
        slot_descs: List[Optional[Tuple[np.ndarray, int]]] = [None] * T
        for i, (term, w, cwv) in enumerate(spec.slots):
            weights[i] = w
            cw[i] = cwv
            r = pb.row(term)
            if r < 0:
                continue
            if fp is not None:
                a, b = int(fp.starts[r]), int(fp.starts[r + 1])
                if a < b:
                    slot_descs[i] = (fp.host_docs[a:b],
                                     int(al.starts_rows[r]) * LANES)
            else:
                slot_descs[i] = _term_slot(al, pb, r)
        if filtered:
            cw[TS] = REQ_W
            slot_descs[TS] = (fl.host_docs, 0)
        thresh = REQ_W * (spec.n_required + (1 if filtered else 0)) \
            + spec.fam_msm
        if spec.field is not None and spec.field not in avgdl_cache:
            avgdl_cache[spec.field] = np.float32(ctx.avgdl(spec.field))
        avgdl = avgdl_cache.get(spec.field, np.float32(1.0))
        k1 = float(spec.sim.k1) if spec.sim is not None else 1.2
        b_eff = (float(spec.sim.b)
                 if spec.sim is not None and spec.has_norms else 0.0)
        chunks = _chunk_slots(slot_descs, seg.ndocs, T, nchunk=1)
        if chunks is None:
            out.append(None)
            continue
        vqs = []
        for dlo, dhi, rowstarts, nrows, lens, skips in chunks:
            L = int(max(int(nrows.max()), HBM_ALIGN // LANES)) * LANES
            vqs.append(_BVQuery(qi=qi, TS=TS, T=T, L=L, filtered=filtered,
                                rowstarts=rowstarts, nrows=nrows, lens=lens,
                                skips=skips, weights=weights, cw=cw,
                                thresh=np.float32(thresh), avgdl=avgdl,
                                dlo=dlo, dhi=dhi, field=spec.field, k1=k1,
                                b_eff=b_eff, fl=fl if filtered else None,
                                albuf=al))
        out.append(vqs)
    return out


def _launch_bool(seg: Segment, ctx, specs: Sequence[FastSpec], K: int
                 ) -> tuple:
    """LAUNCH stage of the bool/filtered path: one kernel enqueue per
    shape group, no device sync. Returns state for `_finish_bool`."""
    vq_lists = _prepare_bool_vqueries(seg, ctx, specs, {})
    # BP-reordered segment: extract the full lane window so _assemble's
    # arrival-rank re-sort sees past the page boundary (reorder parity —
    # the kernel's own tie order is the permuted id)
    K_extract = max(K, LANES) if _seg_tie_aware(seg) else K
    groups = {}
    for vqs in vq_lists:
        if vqs is None:
            continue
        for vq in vqs:
            gk = (id(vq.albuf), vq.TS, vq.filtered,
                  id(vq.fl) if vq.fl is not None else None, vq.k1, vq.b_eff)
            groups.setdefault(gk, []).append(vq)
    pending = []
    for (_alid, TS, filtered, _flid, k1, b_eff), gvqs in groups.items():
        al = gvqs[0].albuf
        if al is not None:
            d_docs, d_tfdl = al.d_docs, al.d_tfdl
        else:
            d_docs = d_tfdl = _dummy_hbm()
        fl = gvqs[0].fl
        filt = fl.d_docs if fl is not None else _dummy_hbm()
        L = max(v.L for v in gvqs)
        rowstarts = np.stack([v.rowstarts for v in gvqs])
        nrows = np.stack([v.nrows for v in gvqs])
        lens = np.stack([v.lens for v in gvqs])
        skips = np.stack([v.skips for v in gvqs])
        weights = np.stack([v.weights for v in gvqs])
        cw = np.stack([v.cw for v in gvqs])
        thresh = np.array([[v.thresh] for v in gvqs], np.float32)
        avg = np.array([[v.avgdl] for v in gvqs], np.float32)
        dlo = np.array([[v.dlo] for v in gvqs], np.int32)
        dhi = np.array([[v.dhi] for v in gvqs], np.int32)
        METRICS.counter("fastpath.launches").inc()
        cost = _qc.current()
        if cost is not None:
            cost.note_actual(int(nrows.sum()) * LANES * 8,
                             int(lens.sum()), K_extract * len(gvqs),
                             path="kernel_bool")
        with TRACER.span("device.dispatch", program="frontier_bool",
                         kernel="fused_bm25_bool_topk",
                         first_call=_first_launch(
                             "bool", d_docs.shape, filt.shape, len(gvqs),
                             TS, L, K_extract, k1, b_eff, filtered)):
            launched = fused_bm25_bool_topk(
                d_docs, d_tfdl, filt, rowstarts, nrows, lens, skips,
                weights, cw, thresh, avg, dlo, dhi, TS=TS, L=L,
                K=K_extract, k1=k1, b=b_eff, filtered=filtered)
        pending.append((gvqs, launched))
    return (vq_lists, pending)


def _finish_bool(specs: Sequence[FastSpec], K: int, state: tuple,
                 seg=None) -> List[Optional[dict]]:
    """FETCH stage of the bool/filtered path: one transfer for all
    groups, then boost/const-score transform and assembly."""
    vq_lists, pending = state
    import jax
    with TRACER.span("device.wait", program="frontier_bool"):
        fetched = jax.device_get([arrs for _gvqs, arrs in pending])
    results = {}
    for (gvqs, _), (scores, docs, totals) in zip(pending, fetched):
        for j, vq in enumerate(gvqs):
            # keep every extracted lane (K on plain segments, the deep
            # K_extract window on reordered ones — _assemble cuts to K
            # after its arrival-rank re-sort)
            results[id(vq)] = (scores[j], docs[j], int(totals[j][0]))

    def transform(qi, sc):
        spec = specs[qi]
        finite = np.isfinite(sc)
        if spec.const_score is not None:
            return np.where(finite, np.float32(spec.const_score), -np.inf)
        if spec.boost != 1.0:
            return np.where(finite, sc * np.float32(spec.boost), -np.inf)
        return sc

    return _assemble(vq_lists, results, K, transform, seg=seg)


def _run_bool(seg: Segment, ctx, specs: Sequence[FastSpec], K: int
              ) -> List[Optional[dict]]:
    with TRACER.span("fastpath.frontier", queries=len(specs)):
        state = _launch_bool(seg, ctx, specs, K)
    return _finish_bool(specs, K, state, seg=seg)


def segment_search(seg: Segment, ctx, spec: FastSpec, k: int
                   ) -> Optional[dict]:
    """Run the fused kernel for one FastSpec over one segment. Returns a
    dict shaped like programs.run_segment output, or None to fall back."""
    res = batch_search(seg, ctx, [spec], k)
    return res[0] if res else None


# ---------------------------------------------------------------------
# concurrent segment search, the TPU way: ONE launch per shard
# ---------------------------------------------------------------------
#
# The reference parallelizes a many-segment shard across threads
# (`search/query/ConcurrentQueryPhaseSearcher.java`). A TPU doesn't want
# more threads — it wants fewer, larger launches: concatenate the shard's
# segment postings into ONE aligned layout (doc ids offset per segment)
# and run the whole shard as a single kernel invocation, then map hits
# back to (segment, local doc). Built lazily per (shard, generation),
# pure term-group specs only (bool/filter specs need per-segment column
# state and keep the per-segment loop).

class ShardView:
    """Segment-shaped facade over a shard's concatenated postings — just
    the attribute surface the pure fastpath touches."""

    def __init__(self, name: str, segments: List[Segment],
                 seg_ords: Optional[List[int]] = None):
        self.name = name
        self.segments = segments
        # original positions in the engine's segment list (the view may
        # skip empty segments, and downstream Candidates index that list)
        self.seg_ords = seg_ords or list(range(len(segments)))
        self.seg_bases = np.cumsum([0] + [s.ndocs for s in segments])
        self.ndocs = int(self.seg_bases[-1])
        self.ndocs_pad = next_pow2(max(self.ndocs, 1))
        self.live_count = sum(s.live_count for s in segments)
        self.postings: dict = {}
        self.doc_lens: dict = {}
        self._built: set = set()

    def ensure_field(self, field: str) -> bool:
        from ..index.segment import PostingsBlock
        from ..parallel.spmd import _concat_shard

        if field in self._built:
            return field in self.postings
        self._built.add(field)
        if not any(field in s.postings for s in self.segments):
            return False
        m = _concat_shard(self.segments, field)
        self.postings[field] = PostingsBlock(
            field=field, vocab=list(m["terms"]), terms=m["terms"],
            starts=np.asarray(m["starts"], np.int64),
            doc_ids=m["doc_ids"], tfs=m["tfs"])
        if any(s.doc_lens.get(field) is not None for s in self.segments):
            self.doc_lens[field] = m["dl"]
        return True

    def locate(self, view_doc: int):
        """view-space doc -> (engine seg_ord, segment, local doc)."""
        vi = int(np.searchsorted(self.seg_bases, view_doc, "right") - 1)
        return (self.seg_ords[vi], self.segments[vi],
                int(view_doc - self.seg_bases[vi]))

    def tie_ranks(self) -> Optional[np.ndarray]:
        """Concatenated arrival tie ranks over the member segments, or
        None when no member is reordered. Members sit in engine creation
        order with disjoint ascending seq ranges, so base + member-rank
        is the view-global arrival rank."""
        if "_tie_rank" not in self.__dict__:
            per = [s.tie_ranks() for s in self.segments]
            if all(p is None for p in per):
                self.__dict__["_tie_rank"] = None
            else:
                parts = []
                for s, p, base in zip(self.segments, per, self.seg_bases):
                    local = (p if p is not None
                             else np.arange(s.ndocs, dtype=np.int64))
                    parts.append(int(base) + local)
                self.__dict__["_tie_rank"] = (
                    np.concatenate(parts) if parts
                    else np.zeros(0, np.int64))
        return self.__dict__["_tie_rank"]


def shard_view(searcher) -> Optional[ShardView]:
    """Cached per (engine, generation-ish identity of the segment list):
    rebuilt whenever refresh/merge changes the segment set."""
    eng = searcher.engine
    pairs = [(i, s) for i, s in enumerate(eng.segments)
             if s.live_count > 0]
    if len(pairs) < 2:
        return None
    if any(s.live_count != s.ndocs for _, s in pairs):
        return None     # deletes: per-segment loop (same rule as the kernel)
    key = tuple(id(s) for _, s in pairs)
    cached = eng.__dict__.get("_shard_view")
    if cached is not None and cached[0] == key:
        return cached[1]
    view = ShardView(f"view:{id(eng):x}", [s for _, s in pairs],
                     [i for i, _ in pairs])
    eng.__dict__["_shard_view"] = (key, view)
    return view


def shard_search(searcher, ctx, spec: FastSpec, k: int
                 ) -> Optional[Tuple[ShardView, dict]]:
    """One kernel launch over ALL the shard's segments for a pure spec;
    None -> per-segment loop."""
    if spec.kind != "pure":
        return None
    view = shard_view(searcher)
    if view is None or not view.ensure_field(spec.lt.field):
        return None
    out = batch_search(view, ctx, [spec], k, count_stats=False)
    if out is None or out[0] is None:
        return None
    STATS.inc("pure_served")
    STATS.inc("shard_view_served")
    return view, out[0]


def launch_batch(seg: Segment, ctx, specs: Sequence[FastSpec], k: int,
                 count_stats: bool = True):
    """LAUNCH stage of the batched kernel path: many FastSpecs over ONE
    segment in as few kernel launches as possible (grid over queries —
    the server-side query batching a TPU search tier runs on). Pure term
    groups and the filtered-pure rung enqueue their frontier kernels
    here, unfetched; the returned `LaunchHandle.fetch()` syncs them and
    runs the verify/escalation ladder plus the leftover bool shapes
    (whose eligibility is only known post-fetch) and returns the per-spec
    result list (None entries -> per-query fallback). Returns None when
    the segment can't take the fast path at all."""
    from .launch import LaunchHandle

    if seg.live_count != seg.ndocs:
        return None
    K = min(next_pow2(max(k, 16)), MAX_K)
    pure_idx = [i for i, s in enumerate(specs) if s.kind == "pure"]
    bool_idx = [i for i, s in enumerate(specs) if s.kind == "bool"]
    pure_state = None
    if pure_idx:
        pure_state = _launch_pure(seg, ctx,
                                  [specs[i].lt for i in pure_idx],
                                  [specs[i] for i in pure_idx], K)
    filtered_launched = []
    if bool_idx:
        # family-only bool specs over a dense hot filter ride the PURE
        # pruned pipeline on the filter-specialized postings view —
        # impact heads cut the per-query work from O(filtered df) to
        # O(L_HEAD) exactly like unfiltered match queries
        filtered_launched = _launch_filtered_pure_batch(
            seg, ctx, [(i, specs[i]) for i in bool_idx], K)

    def _finish():
        out: List[Optional[dict]] = [None] * len(specs)
        if pure_state is not None:
            rs = _finish_pure(seg, ctx, [specs[i].lt for i in pure_idx],
                              [specs[i] for i in pure_idx], K, pure_state)
            if rs is not None:
                for i, r in zip(pure_idx, rs):
                    out[i] = r
        rem = list(bool_idx)
        if filtered_launched:
            served = _finish_filtered_pure_batch(ctx, K, filtered_launched)
            for i, r in served.items():
                out[i] = r
            rem = [i for i in rem if i not in served]
        if rem:
            for i, r in zip(rem, _run_bool(seg, ctx,
                                           [specs[i] for i in rem], K)):
                out[i] = r
        if count_stats:
            count_served(specs, out)
        return out

    return LaunchHandle(_finish, kind="fastpath")


def batch_search(seg: Segment, ctx, specs: Sequence[FastSpec], k: int,
                 count_stats: bool = True
                 ) -> Optional[List[Optional[dict]]]:
    """Synchronous batched kernel path: `launch_batch(...).fetch()`."""
    handle = launch_batch(seg, ctx, specs, k, count_stats)
    if handle is None:
        return None
    return handle.fetch()


def _launch_filtered_pure_batch(seg: Segment, ctx, idx_specs,
                                K: int) -> list:
    """LAUNCH stage of the filtered-pure rung: serve family-only filtered
    bool specs through the pure pruned pipeline over their
    FilteredSegViews, ONE frontier launch per (field, filter) group so an
    msearch batch pays one launch per view, not one per query. Returns
    pending group launches for `_finish_filtered_pure_batch`."""
    groups: dict = {}
    for i, spec in idx_specs:
        if not _family_only(spec):
            continue
        fl = _filter_list(seg, ctx, spec.filter_clauses)
        if fl is None or not _dense_hot(seg, fl, len(spec.slots)):
            continue
        fp = _filtered_postings(seg, spec.field, fl)
        if fp is None:
            continue
        key = (seg.uid, spec.field, fl.key)
        groups.setdefault(key, (spec.field, fl, fp, []))[3].append((i, spec))
    launched = []
    for key, (field, fl, fp, items) in groups.items():
        view = _filtered_view(seg, field, fp, key)
        lts = [_PseudoLT(s) for _, s in items]
        sspecs = [s for _, s in items]
        state = _launch_pure(view, ctx, lts, sspecs, K)
        if state is None:
            continue
        launched.append((view, fl, items, lts, sspecs, state))
    return launched


def _finish_filtered_pure_batch(ctx, K: int, launched: list) -> dict:
    """FETCH stage of the filtered-pure rung. -> {spec index: result
    dict}; missing indices take the regular bool path."""
    out: dict = {}
    for view, fl, items, lts, sspecs, state in launched:
        res = _finish_pure(view, ctx, lts, sspecs, K, state)
        if res is None:
            continue
        for (i, spec), r in zip(items, res):
            if r is None:
                continue   # the bool fallback will count this query's hit
            fl.hits += 1
            if spec.boost != 1.0:
                sc = r["topk_scores"]
                sc = np.where(np.isfinite(sc),
                              sc * np.float32(spec.boost),
                              sc).astype(np.float32)
                r = dict(r, topk_scores=sc, topk_key=sc,
                         max_score=(float(sc[0]) if r["total"] > 0
                                    and np.isfinite(sc[0]) else -np.inf))
            out[i] = r
    return out


def count_served(specs: Sequence[FastSpec], outs: Sequence[Optional[dict]]
                 ) -> None:
    served = fell = 0
    for spec, r in zip(specs, outs):
        if r is None:
            STATS.inc("fallback")
            fell += 1
        else:
            STATS.inc("pure_served" if spec.kind == "pure"
                      else "bool_served")
            served += 1
    if _fr.RECORDER.enabled and _fr.current():
        _fr.RECORDER.record(_fr.current(), "fastpath.served",
                            served=served, fallback=fell)
