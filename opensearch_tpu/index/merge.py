"""Segment merging. Analog of reference `OpenSearchTieredMergePolicy.java` +
Lucene's SegmentMerger, rebuilt as vectorized multiway sorted-run merges over
CSR arrays (deleted docs are compacted away, exactly like Lucene merges).
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from ..obs import ingest_obs as _iobs
from ..ops import device_merge
from .segment import (GeoColumn, KeywordColumn, NumericColumn,
                      PostingsBlock, Segment, TextFieldStats, VectorColumn)


class TieredMergePolicy:
    """Size-tiered selection: merge when >= `segments_per_tier` segments share
    a size tier (by live doc count), preferring the smallest."""

    def __init__(self, segments_per_tier: int = 8, max_merged_docs: int = 1 << 24):
        self.segments_per_tier = segments_per_tier
        self.max_merged_docs = max_merged_docs

    def find_merges(self, segments: List[Segment]) -> List[List[Segment]]:
        candidates = [s for s in segments if s.live_count < self.max_merged_docs]
        if len(candidates) < self.segments_per_tier:
            # also merge when deletes dominate (reference: forceMergeDeletes)
            heavy = [s for s in segments
                     if s.ndocs > 0 and s.live_count < 0.5 * s.ndocs]
            return [[s] for s in heavy]
        candidates.sort(key=lambda s: s.live_count)
        return [candidates[: self.segments_per_tier]]


def merge_segments(name: str, segments: List[Segment]) -> Segment:
    """Compacting multiway merge of N segments into one."""
    # instrumentation is TOP-LEVEL only: nested child merges (name
    # carries a "/") recurse through here and their wall time / sizes
    # are already inside the parent's numbers
    _obs = "/" not in name and _iobs.enabled()
    _t0 = time.perf_counter()
    _in_bytes = sum(_iobs.segment_nbytes(s) for s in segments) if _obs else 0
    live_masks = [s.live.astype(bool) for s in segments]
    live_counts = [int(m.sum()) for m in live_masks]
    ndocs = sum(live_counts)
    # old (seg, doc) -> new doc id
    doc_maps: List[np.ndarray] = []
    base = 0
    for s, m, c in zip(segments, live_masks, live_counts):
        dmap = np.full(s.ndocs, -1, dtype=np.int64)
        dmap[m] = base + np.arange(c, dtype=np.int64)
        doc_maps.append(dmap)
        base += c

    ids: List[str] = []
    sources: List[dict] = []
    stored_vals: List = []
    any_stored = any(getattr(s, "stored_vals", None) for s in segments)
    tv_fields = {f for s in segments
                 for f in (getattr(s, "term_vectors", None) or {})}
    term_vectors = {f: [] for f in tv_fields}
    seq_nos = np.empty(ndocs, dtype=np.int64)
    for s, m, dmap in zip(segments, live_masks, doc_maps):
        stv = getattr(s, "term_vectors", None) or {}
        for old in np.nonzero(m)[0]:
            ids.append(s.ids[old])
            sources.append(s.sources[old])
            if any_stored:
                stored_vals.append(s.stored_vals[old]
                                   if s.stored_vals else None)
            for f in tv_fields:
                col = stv.get(f)
                term_vectors[f].append(col[old] if col else None)
        seq_nos[dmap[m]] = s.seq_nos[m]

    # ---- postings ----
    post_fields = {f for s in segments for f in s.postings}
    postings: Dict[str, PostingsBlock] = {}
    for f in post_fields:
        vocab_union = sorted({t for s in segments if f in s.postings for t in s.postings[f].vocab})
        new_row_of = {t: i for i, t in enumerate(vocab_union)}
        rows_parts, docs_parts, tfs_parts, pos_len_parts, pos_parts = [], [], [], [], []
        has_positions = all(f not in s.postings or s.postings[f].pos_starts is not None
                            for s in segments)
        for s, dmap in zip(segments, doc_maps):
            pb = s.postings.get(f)
            if pb is None or pb.size == 0:
                continue
            lens = np.diff(pb.starts)
            row_map = np.fromiter((new_row_of[t] for t in pb.vocab), dtype=np.int64,
                                  count=len(pb.vocab))
            rows = np.repeat(row_map, lens)
            new_docs = dmap[pb.doc_ids]
            keep = new_docs >= 0
            rows_parts.append(rows[keep])
            docs_parts.append(new_docs[keep])
            tfs_parts.append(pb.tfs[keep])
            if has_positions and pb.pos_starts is not None:
                plens = np.diff(pb.pos_starts)[keep]
                pos_len_parts.append(plens)
                # gather each kept posting's position run
                kept_starts = pb.pos_starts[:-1][keep]
                idx = _ranges_gather(kept_starts, plens)
                pos_parts.append(pb.positions[idx])
        if not rows_parts:
            continue
        rows = np.concatenate(rows_parts)
        docs = np.concatenate(docs_parts)
        tfs = np.concatenate(tfs_parts)
        starts = np.zeros(len(vocab_union) + 1, dtype=np.int64)
        if device_merge.use_device_merge(len(rows)):
            # the O(P log P) multiway sorted-run merge runs on device
            # (ops/device_merge.py); `order` drives the host position
            # regather so results stay bit-identical to the numpy path
            _r, d32, t32, order, counts = device_merge.merge_sorted_runs(
                rows, docs, tfs, len(vocab_union))
            docs, tfs = d32.astype(np.int64), t32
            order = order.astype(np.int64)
            np.cumsum(counts.astype(np.int64), out=starts[1:])
        else:
            order = np.lexsort((docs, rows))
            rows, docs, tfs = rows[order], docs[order], tfs[order]
            np.cumsum(np.bincount(rows, minlength=len(vocab_union)),
                      out=starts[1:])
        pos_starts = positions = None
        if has_positions and pos_len_parts:
            plens = np.concatenate(pos_len_parts)[order]
            all_pos_parts = np.concatenate(pos_parts) if pos_parts else np.empty(0, np.int32)
            # positions were concatenated in pre-sort posting order; regather
            pre_starts = np.zeros(len(plens) + 1, dtype=np.int64)
            np.cumsum(np.concatenate(pos_len_parts), out=pre_starts[1:])
            idx = _ranges_gather(pre_starts[:-1][order], plens)
            positions = all_pos_parts[idx]
            pos_starts = np.zeros(len(plens) + 1, dtype=np.int64)
            np.cumsum(plens, out=pos_starts[1:])
        postings[f] = PostingsBlock(f, vocab_union, new_row_of, starts,
                                    docs.astype(np.int32), tfs.astype(np.float32),
                                    pos_starts, positions)

    # ---- numeric columns ----
    numeric_cols: Dict[str, NumericColumn] = {}
    for f in {f for s in segments for f in s.numeric_cols}:
        kind = next(s.numeric_cols[f].kind for s in segments if f in s.numeric_cols)
        dtype = np.float64 if kind == "float" else np.int64
        values = np.zeros(ndocs, dtype=dtype)
        present = np.zeros(ndocs, dtype=bool)
        for s, m, dmap in zip(segments, live_masks, doc_maps):
            col = s.numeric_cols.get(f)
            if col is None:
                continue
            values[dmap[m]] = col.values[m]
            present[dmap[m]] = col.present[m]
        numeric_cols[f] = NumericColumn(f, kind, values, present)

    # ---- keyword columns ----
    keyword_cols: Dict[str, KeywordColumn] = {}
    for f in {f for s in segments for f in s.keyword_cols}:
        vocab_union = sorted({v for s in segments if f in s.keyword_cols
                              for v in s.keyword_cols[f].vocab})
        new_ord_of = {v: i for i, v in enumerate(vocab_union)}
        doc_parts, ord_parts = [], []
        for s, dmap in zip(segments, doc_maps):
            col = s.keyword_cols.get(f)
            if col is None or len(col.ords) == 0:
                continue
            remap = np.fromiter((new_ord_of[v] for v in col.vocab), dtype=np.int64,
                                count=len(col.vocab))
            new_docs = dmap[col.doc_of_value]
            keep = new_docs >= 0
            doc_parts.append(new_docs[keep])
            ord_parts.append(remap[col.ords[keep]])
        if doc_parts:
            docs = np.concatenate(doc_parts)
            ords = np.concatenate(ord_parts)
            order = np.lexsort((ords, docs))
            docs, ords = docs[order], ords[order]
        else:
            docs = np.empty(0, np.int64)
            ords = np.empty(0, np.int64)
        starts = np.zeros(ndocs + 1, dtype=np.int64)
        np.cumsum(np.bincount(docs, minlength=ndocs), out=starts[1:])
        min_ord = np.full(ndocs, -1, dtype=np.int32)
        if len(docs):
            first = np.unique(docs, return_index=True)
            min_ord[first[0]] = ords[first[1]].astype(np.int32)
        keyword_cols[f] = KeywordColumn(f, vocab_union, starts, ords.astype(np.int32),
                                        docs.astype(np.int32), min_ord)

    # ---- geo columns ----
    geo_cols: Dict[str, GeoColumn] = {}
    for f in {f for s in segments for f in s.geo_cols}:
        lat = np.zeros(ndocs, dtype=np.float32)
        lon = np.zeros(ndocs, dtype=np.float32)
        present = np.zeros(ndocs, dtype=bool)
        for s, m, dmap in zip(segments, live_masks, doc_maps):
            col = s.geo_cols.get(f)
            if col is None:
                continue
            lat[dmap[m]] = col.lat[m]
            lon[dmap[m]] = col.lon[m]
            present[dmap[m]] = col.present[m]
        geo_cols[f] = GeoColumn(f, lat, lon, present)

    # ---- vector columns ----
    vector_cols: Dict[str, VectorColumn] = {}
    for f in {f for s in segments for f in getattr(s, "vector_cols", {})}:
        first = next(s.vector_cols[f] for s in segments if f in s.vector_cols)
        dims = first.values.shape[1]
        values = np.zeros((ndocs, dims), np.float32)
        present = np.zeros(ndocs, bool)
        for s, m, dmap in zip(segments, live_masks, doc_maps):
            col = s.vector_cols.get(f)
            if col is None:
                continue
            values[dmap[m]] = col.values[m]
            present[dmap[m]] = col.present[m]
        vector_cols[f] = VectorColumn(f, values, present, first.similarity,
                                      method=first.method)

    # ---- shape columns ----
    shape_cols = {}
    for f in {f for s in segments for f in getattr(s, "shape_cols", {})}:
        from .segment import ShapeColumn
        specs: list = [None] * ndocs
        minx = np.full(ndocs, np.inf)
        miny = np.full(ndocs, np.inf)
        maxx = np.full(ndocs, -np.inf)
        maxy = np.full(ndocs, -np.inf)
        present = np.zeros(ndocs, bool)
        for s, m, dmap in zip(segments, live_masks, doc_maps):
            col = s.shape_cols.get(f)
            if col is None:
                continue
            tgt = dmap[m]
            for old_i, new_i in zip(np.nonzero(m)[0], tgt):
                specs[new_i] = col.specs[old_i]
            minx[tgt] = col.minx[m]
            miny[tgt] = col.miny[m]
            maxx[tgt] = col.maxx[m]
            maxy[tgt] = col.maxy[m]
            present[tgt] = col.present[m]
        shape_cols[f] = ShapeColumn(f, specs, minx, miny, maxx, maxy, present)

    # ---- doc lens + stats ----
    doc_lens: Dict[str, np.ndarray] = {}
    text_stats: Dict[str, TextFieldStats] = {}
    for f in {f for s in segments for f in s.doc_lens}:
        dl = np.zeros(ndocs, dtype=np.int64)
        for s, m, dmap in zip(segments, live_masks, doc_maps):
            sdl = s.doc_lens.get(f)
            if sdl is not None:
                dl[dmap[m]] = sdl[m]
        doc_lens[f] = dl
        text_stats[f] = TextFieldStats(doc_count=int((dl > 0).sum()), sum_dl=int(dl.sum()))

    # ---- nested blocks: drop children of deleted parents, remap parent ids ----
    nested = {}
    for npath in sorted({p for s in segments for p in s.nested}):
        child_segs: List[Segment] = []
        saved_lives: List[np.ndarray] = []
        new_parent_parts: List[np.ndarray] = []
        for s, dmap in zip(segments, doc_maps):
            blk = s.nested.get(npath)
            if blk is None or blk.child.ndocs == 0:
                continue
            keep = (dmap[blk.parent_of] >= 0) & blk.child.live
            saved_lives.append(blk.child.live)
            blk.child.live = keep  # temporary: drives the child compaction
            child_segs.append(blk.child)
            new_parent_parts.append(dmap[blk.parent_of[keep]].astype(np.int32))
        if not child_segs:
            continue
        try:
            merged_child = merge_segments(f"{name}/{npath}", child_segs)
        finally:
            for cs, old in zip(child_segs, saved_lives):
                cs.live = old
        parent_of = (np.concatenate(new_parent_parts) if new_parent_parts
                     else np.empty(0, np.int32))
        from .segment import NestedBlock
        nested[npath] = NestedBlock(merged_child, parent_of)

    merged = Segment(name, ndocs, postings, numeric_cols, keyword_cols,
                     geo_cols, doc_lens, text_stats, ids, sources,
                     seq_nos=seq_nos, vector_cols=vector_cols, nested=nested,
                     shape_cols=shape_cols,
                     stored_vals=stored_vals if any_stored else None)
    merged.term_vectors = term_vectors if tv_fields else None
    if any(s.__dict__.get("_reordered") for s in segments):
        # a BP-reordered input sits in the concatenation in PERMUTED
        # order, so the merged segment's internal ids no longer encode
        # arrival — thread the inputs' arrival planes through (offset per
        # input, live-compacted) or exact-score ties in the merged
        # segment break differently from the unreordered arm's merge of
        # the same corpus (the cross-arm parity contract). Values only
        # need to be order-preserving, not dense.
        parts = []
        offset = 0
        for s, m in zip(segments, live_masks):
            r = s.tie_ranks()
            if r is None:
                r = np.arange(s.ndocs, dtype=np.int64)
            parts.append(r[m] + offset)
            offset += s.ndocs
        merged.__dict__["_tie_rank"] = np.concatenate(parts) if parts \
            else np.zeros(0, np.int64)
        merged.__dict__["_reordered"] = True
    # codec propagation: merges emit codec v2 — they are the natural
    # rebuild point for the format rev (a v1+v2 merge upgrades the v1
    # half). Impacts
    # are REBUILT from the merged tf/doc-len planes (the merged field's
    # avgdl differs from every input's, so carried quantized values would
    # bake a stale norm); the O(P) quantize map itself runs on device
    # past the size threshold (ops/device_merge.quantize_impacts).
    _reorder_s = 0.0
    _reordered = False
    # feature planes (rank_features index_impacts opt-in) rebuild
    # whenever ANY input carried one for the field — the opt-in
    # travels with the data, so merges never need the mappings
    ffields = {f for s in segments for f, pb in s.postings.items()
               if pb.impact is not None and pb.impact.kind == "feature"}
    _q0 = time.perf_counter()
    merged.build_impacts(feature_fields=ffields)
    _iobs.note_stage("quantize", time.perf_counter() - _q0)
    if "/" not in name:
        # BP-style impact-clustered doc-id reordering (index/reorder.py):
        # merges are the one point the whole doc set is in hand and the
        # impact planes are fresh — nested CHILD merges (name carries a
        # "/") skip, because the parent's apply_permutation re-sorts
        # children against the permuted parent ids itself. The pass is
        # deterministic, so copy holders re-running this merge stay
        # byte-identical (PR-9 replication contract).
        from .reorder import maybe_reorder
        _r0 = time.perf_counter()
        _pre = merged
        merged = maybe_reorder(merged)
        _reorder_s = time.perf_counter() - _r0
        _reordered = merged is not _pre
    if _obs:
        # input counts pre-compaction (deleted docs included) so
        # input_docs - output_docs reads as "deletes reclaimed"
        _iobs.record_merge(len(segments), sum(s.ndocs for s in segments),
                           _in_bytes, merged, time.perf_counter() - _t0,
                           _reorder_s, _reordered)
    return merged


def _ranges_gather(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Indices selecting [starts[i], starts[i]+lens[i]) runs, concatenated —
    the vectorized run-gather underlying positional merges."""
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64)
    ends = np.cumsum(lens)
    idx = np.arange(total, dtype=np.int64)
    run = np.searchsorted(ends, idx, side="right")
    prev = np.concatenate(([0], ends[:-1]))
    return starts[run] + (idx - prev[run])
