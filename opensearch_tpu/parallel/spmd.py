"""SPMD distributed search over a `jax.sharding.Mesh`.

The TPU-native replacement for the reference's coordinator/transport fan-out
(`action/search/TransportSearchAction` + `SearchPhaseController` over
netty/NCCL-style point-to-point): shards live as the leading axis of stacked
device arrays, `shard_map` runs the per-shard query program, and the
coordinator reduce becomes XLA collectives over ICI:

- `psum` over the shard axis aggregates collection statistics (global df,
  ndocs, avgdl) — the device-side analog of the reference DFS_QUERY_THEN_FETCH
  phase (`search/dfs/DfsSearchResult.java`), so BM25 idf is identical no
  matter how documents are partitioned.
- `all_gather` over the shard axis merges per-shard top-k into a global top-k
  — the reduce in `SearchPhaseController#sortDocs`, minus the host round-trip.
- a second mesh axis (`replica`) data-parallelizes a *batch of queries*, the
  throughput scaling the reference gets from replica fan-out.
- `score_term_sharded` partitions the postings of huge terms across devices
  and `psum`s partial score vectors — the sequence/context-parallel analog
  (the reduction dimension — postings — is sharded, like ring attention
  shards the KV sequence).

Mesh axes are ordered (replica, shard): put `shard` innermost so the hot
all_gather/psum ride ICI within a host; `replica` can span DCN.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..index.segment import Segment, next_pow2

INT32_SENTINEL = np.int32(2**31 - 1)


def make_mesh(n_replica: int = 1, n_shard: Optional[int] = None,
              devices: Optional[list] = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if n_shard is None:
        n_shard = len(devices) // n_replica
    dev = np.asarray(devices[: n_replica * n_shard]).reshape(n_replica, n_shard)
    return Mesh(dev, axis_names=("replica", "shard"))


@dataclass
class StackedShardIndex:
    """N doc-shards of one field's postings + norms, padded to common shapes
    and stacked on a leading axis sharded over the mesh `shard` axis. This is
    the device-resident form the SPMD query program consumes.

    A shard may hold SEVERAL segments: their postings concatenate into one
    per-shard CSR on the host (term dict = union, doc ids offset by the
    segment's base within the shard) — the mesh analog of the reference's
    per-shard multi-leaf reader, built once per index generation and cached
    by the MeshSearchService."""

    field: str
    starts: jnp.ndarray     # i32[S, R_pad]
    doc_ids: jnp.ndarray    # i32[S, P_pad]
    tfs: jnp.ndarray        # f32[S, P_pad]
    dl: jnp.ndarray         # f32[S, D_pad]
    live: jnp.ndarray       # f32[S, D_pad]
    doc_base: jnp.ndarray   # i32[S] global doc id offset per shard
    doc_count: jnp.ndarray  # f32[S] maxDoc per shard (deleted INCLUDED)
    sum_dl: jnp.ndarray     # f32[S]
    field_dc: jnp.ndarray   # f32[S] docs WITH this field (text_stats doc_count)
    n_shards: int
    ndocs_pad: int
    # host-side query-resolution metadata (term -> per-shard CSR row, and
    # row sizes for DMA bucket sizing)
    host_terms: Optional[List[Dict[str, int]]] = None
    host_starts: Optional[List[np.ndarray]] = None
    # (shard, segment) decomposition for mapping global ids back to
    # (segment, local doc) at fetch: per shard, the ndocs of each segment
    seg_ndocs: Optional[List[List[int]]] = None

    def row(self, shard: int, term: str) -> int:
        return self.host_terms[shard].get(term, -1)

    def row_size(self, shard: int, row: int) -> int:
        st = self.host_starts[shard]
        return int(st[row + 1] - st[row]) if 0 <= row < len(st) - 1 else 0

    @classmethod
    def build(cls, shards, field: str,
              mesh: Optional[Mesh] = None) -> "StackedShardIndex":
        """`shards`: List[Segment] (one per shard) or List[List[Segment]]."""
        shard_segs: List[List[Segment]] = [
            list(s) if isinstance(s, (list, tuple)) else [s] for s in shards]
        S = len(shard_segs)
        merged = [_concat_shard(segs, field) for segs in shard_segs]
        r_pad = max(next_pow2(len(m["starts"]) + 1) for m in merged)
        p_pad = max(next_pow2(max(len(m["doc_ids"]), 1)) for m in merged)
        d_pad = next_pow2(max(max(m["ndocs"] for m in merged), 1))
        starts = np.zeros((S, r_pad), np.int32)
        doc_ids = np.full((S, p_pad), INT32_SENTINEL, np.int32)
        tfs = np.zeros((S, p_pad), np.float32)
        dl = np.zeros((S, d_pad), np.float32)
        live = np.zeros((S, d_pad), np.float32)
        doc_base = np.zeros(S, np.int32)
        doc_count = np.zeros(S, np.float32)
        sum_dl = np.zeros(S, np.float32)
        field_dc = np.zeros(S, np.float32)
        host_terms, host_starts, seg_ndocs = [], [], []
        base = 0
        for i, m in enumerate(merged):
            n = len(m["starts"]) - 1
            starts[i, : n + 1] = m["starts"]
            starts[i, n + 1:] = m["starts"][-1]
            np_ = len(m["doc_ids"])
            doc_ids[i, :np_] = m["doc_ids"]
            tfs[i, :np_] = m["tfs"]
            dl[i, : m["ndocs"]] = m["dl"]
            live[i, : m["ndocs"]] = m["live"]
            doc_base[i] = base
            base += m["ndocs"]
            # idf N follows host ShardContext.num_docs = Lucene maxDoc
            # (deleted docs INCLUDED — the host rewrite and every scorer
            # use it; psumming live counts instead skewed idf on indexes
            # with deletes, hidden while parity tests compared mesh to
            # its own mesh)
            doc_count[i] = float(m["ndocs"])
            sum_dl[i] = m["sum_dl"]
            field_dc[i] = m["field_dc"]
            host_terms.append(m["terms"])
            host_starts.append(m["starts"])
            seg_ndocs.append([s.ndocs for s in shard_segs[i]])
        arrays = dict(starts=starts, doc_ids=doc_ids, tfs=tfs, dl=dl, live=live,
                      doc_base=doc_base, doc_count=doc_count, sum_dl=sum_dl,
                      field_dc=field_dc)
        if mesh is not None:
            sharding = NamedSharding(mesh, P("shard"))
            # MeshSearchService._stacked_for registers the built
            # index with the HBM ledger
            arrays = {k: jax.device_put(v, sharding)  # oslint: disable=OSL506
                      for k, v in arrays.items()}
        else:
            arrays = {k: jnp.asarray(v) for k, v in arrays.items()}
        return cls(field=field, n_shards=S, ndocs_pad=d_pad,
                   host_terms=host_terms, host_starts=host_starts,
                   seg_ndocs=seg_ndocs, **arrays)

    def tree(self) -> dict:
        return {"starts": self.starts, "doc_ids": self.doc_ids, "tfs": self.tfs,
                "dl": self.dl, "live": self.live, "doc_base": self.doc_base,
                "doc_count": self.doc_count, "sum_dl": self.sum_dl,
                "field_dc": self.field_dc}


def _concat_shard(segs: List[Segment], field: str) -> dict:
    """One shard's segments -> a single host CSR view: union term dict,
    per-term postings concatenated segment-by-segment with doc offsets.
    An empty shard yields a zero-doc entry (all terms absent)."""
    if not segs:
        return {"terms": {}, "starts": np.zeros(1, np.int64),
                "doc_ids": np.zeros(0, np.int32),
                "tfs": np.zeros(0, np.float32),
                "dl": np.zeros(0, np.float32),
                "live": np.zeros(0, np.float32), "ndocs": 0,
                "sum_dl": 0.0, "field_dc": 0.0}
    ndocs = sum(s.ndocs for s in segs)
    live = np.zeros(ndocs, np.float32)
    dl = np.zeros(ndocs, np.float32)
    off = 0
    sum_dl = 0.0
    field_dc = 0.0
    for s in segs:
        live[off: off + s.ndocs] = s.live.astype(np.float32)
        sdl = s.doc_lens.get(field)
        if sdl is not None:
            dl[off: off + s.ndocs] = sdl
        st = s.text_stats.get(field)
        if st:
            sum_dl += st.sum_dl
            field_dc += st.doc_count
        off += s.ndocs
    pbs = [s.postings.get(field) for s in segs]
    if len(segs) == 1 and pbs[0] is not None:
        pb = pbs[0]
        return {"terms": pb.terms, "starts": pb.starts.astype(np.int64),
                "doc_ids": pb.doc_ids, "tfs": pb.tfs, "dl": dl, "live": live,
                "ndocs": ndocs, "sum_dl": sum_dl,
                "field_dc": field_dc}
    vocab: Dict[str, int] = {}
    for pb in pbs:
        if pb is None:
            continue
        for t in pb.vocab:
            vocab.setdefault(t, len(vocab))
    nterms = len(vocab)
    # vectorized merge: per-posting (target row, offset doc) keys, one
    # stable argsort — no per-term Python loop (a vocabulary can be 10^5+)
    trows_parts, docs_parts, tfs_parts = [], [], []
    off = 0
    for s, pb in zip(segs, pbs):
        if pb is not None and pb.size:
            rows = np.array([vocab[t] for t in pb.vocab], np.int64)
            trows_parts.append(np.repeat(rows, np.diff(pb.starts)))
            docs_parts.append(pb.doc_ids.astype(np.int64) + off)
            tfs_parts.append(pb.tfs)
        off += s.ndocs
    if trows_parts:
        trows = np.concatenate(trows_parts)
        docs_all = np.concatenate(docs_parts)
        tfs_all = np.concatenate(tfs_parts)
        order = np.lexsort((docs_all, trows))
        doc_ids = docs_all[order].astype(np.int32)
        tfs = tfs_all[order]
        lens = np.bincount(trows, minlength=nterms)
    else:
        doc_ids = np.zeros(0, np.int32)
        tfs = np.zeros(0, np.float32)
        lens = np.zeros(nterms, np.int64)
    starts = np.zeros(nterms + 1, np.int64)
    np.cumsum(lens, out=starts[1:])
    return {"terms": vocab, "starts": starts, "doc_ids": doc_ids, "tfs": tfs,
            "dl": dl, "live": live, "ndocs": ndocs,
            "sum_dl": sum_dl, "field_dc": field_dc}


def _local_gather(starts, doc_ids, tfs, rows, bucket: int):
    """Same flat CSR gather as ops.scoring.gather_postings, shard-local."""
    nrows_pad = starts.shape[0]
    rows = jnp.where(rows < 0, nrows_pad - 2, rows)
    row_start = starts[rows]
    lens = starts[rows + 1] - row_start
    cum = jnp.cumsum(lens)
    total = cum[-1]
    i = jnp.arange(bucket, dtype=jnp.int32)
    t_idx = jnp.minimum(jnp.searchsorted(cum, i, side="right").astype(jnp.int32),
                        rows.shape[0] - 1)
    prev = jnp.where(t_idx > 0, cum[jnp.maximum(t_idx - 1, 0)], 0)
    src = jnp.clip(row_start[t_idx] + (i - prev), 0, doc_ids.shape[0] - 1)
    valid = i < total
    docs = jnp.where(valid, doc_ids[src], INT32_SENTINEL)
    tf = jnp.where(valid, tfs[src], 0.0)
    return docs, tf, t_idx, valid


def _score_one_query(starts, doc_ids, tfs, dl, live, rows, boosts, msm,
                     cscore, n_global, df_global, avgdl, bucket: int,
                     ndocs_pad: int, k1: float, b: float, fmask=None):
    """Shard-local BM25 scoring of one query with *global* statistics.
    `cscore > 0` switches the query to constant-score semantics (filter
    context / `terms` queries): every doc matching >= msm terms scores
    exactly `cscore`, so top-k tie-breaks by doc id like the host path.
    `fmask` (f32[ndocs_pad] or None) is a pre-combined filter-context match
    mask (bool filters + must_nots): docs outside it can't hit."""
    idf = jnp.log1p((n_global - df_global + 0.5) / (df_global + 0.5))
    w = jnp.where(df_global > 0, boosts * idf, 0.0)
    docs, tf, t_idx, valid = _local_gather(starts, doc_ids, tfs, rows, bucket)
    dsafe = jnp.minimum(docs, ndocs_pad - 1)
    # avgdl is pre-guarded > 0 by the caller (normless fields -> 1.0, matching
    # the host StatsContext.avgdl default); keep a floor so 0/0 can never
    # NaN-poison the whole shard's scores (silent-zero-hits bug, round 3).
    k = k1 * (1.0 - b + b * dl[dsafe] / jnp.maximum(avgdl, 1e-9))
    contrib = jnp.where(valid, w[t_idx] * tf / (tf + k), 0.0)
    scores = jnp.zeros(ndocs_pad, jnp.float32).at[docs].add(contrib, mode="drop")
    counts = jnp.zeros(ndocs_pad, jnp.float32).at[docs].add(
        jnp.where(valid & (tf > 0), 1.0, 0.0), mode="drop")
    ok = (counts >= msm) & (live > 0)
    if fmask is not None:
        ok = ok & (fmask > 0)
    scores = jnp.where(cscore > 0.0, cscore, scores)
    return jnp.where(ok, scores, -jnp.inf)


def _global_dfs_stats(tree, rows):
    """Device-side DFS phase shared by every distributed program: psum the
    collection statistics over the `shard` axis. Returns
    (df_global [QBl,T], n_global, avgdl). avgdl follows the host
    StatsContext semantics: mean doc length over docs that HAVE the field,
    1.0 when none (normless fields — 0/0 was the r3 NaN poison)."""
    starts = tree["starts"][0]
    nrows_pad = starts.shape[0]
    safe_rows = jnp.where(rows < 0, nrows_pad - 2, rows)
    local_df = (starts[safe_rows + 1] - starts[safe_rows]).astype(jnp.float32)
    df_global = jax.lax.psum(local_df, "shard")
    n_global = jax.lax.psum(tree["doc_count"][0], "shard")
    sum_dl_g = jax.lax.psum(tree["sum_dl"][0], "shard")
    fdc_g = jax.lax.psum(tree["field_dc"][0], "shard")
    avgdl = jnp.where(fdc_g > 0, sum_dl_g / jnp.maximum(fdc_g, 1.0), 1.0)
    return df_global, n_global, avgdl


def build_distributed_search(mesh: Mesh, bucket: int, ndocs_pad: int, k: int,
                             k1: float = 1.2, b: float = 0.75,
                             filtered: bool = False):
    """Returns a jitted SPMD function:
        (index_tree, rows [S,QB,T], boosts [QB,T], msm [QB], cscore [QB]
         [, fmask [S, ndocs_pad]]) ->
        (global_doc_ids [QB,k], scores [QB,k], total_hits [QB])
    Queries are sharded over `replica`, docs over `shard`; `rows` carries the
    per-shard term-dict resolution so it is sharded over BOTH axes. `cscore`
    (optional; zeros = BM25) switches a query to constant-score semantics.
    `filtered=True` adds a per-shard filter-context mask argument (the
    device-cached AND of a bool query's filter/must_not clauses): the mesh
    analog of the reference's filtered BulkScorer
    (`search/query/QueryPhase.java` with a filter bitset) — one mask serves
    every query in the batch that shares the filter combo."""

    def per_device(tree, rows, boosts, msm, cscore, fmask=None):
        # leading stacked-shard axis is size-1 inside the shard_map block
        rows = rows[0]
        starts = tree["starts"][0]
        doc_ids = tree["doc_ids"][0]
        tfs = tree["tfs"][0]
        dl = tree["dl"][0]
        live = tree["live"][0]
        doc_base = tree["doc_base"][0]
        fm = fmask[0] if fmask is not None else None

        # --- DFS phase on device: global collection stats via psum over ICI ---
        df_global, n_global, avgdl = _global_dfs_stats(tree, rows)

        # --- QUERY phase: vmap over the local query batch ---
        scores = jax.vmap(
            lambda r, w, m, cs, dfg: _score_one_query(
                starts, doc_ids, tfs, dl, live, r, w, m, cs, n_global, dfg,
                avgdl, bucket, ndocs_pad, k1, b, fm)
        )(rows, boosts, msm, cscore, df_global)                       # [QBl, D]

        totals_local = jnp.sum(scores > -jnp.inf, axis=1)
        totals = jax.lax.psum(totals_local, "shard")

        kk = min(k, ndocs_pad)
        vals, idx = jax.lax.top_k(scores, kk)                         # [QBl, kk]
        gids = jnp.where(vals > -jnp.inf, idx + doc_base, -1)

        # --- coordinator merge on device: all_gather the per-shard top-ks.
        # The UNION of every shard's top-kk goes back to the host — the
        # same candidate pool the host shard loop builds — so the final
        # selection (host reduce, tie-break by (-score, doc id)) is
        # IDENTICAL to the host path even on deep score ties. A device
        # top_k over the flattened gather would instead tie-break by flat
        # position (shard-major), silently reordering tied keyword hits.
        all_vals = jax.lax.all_gather(vals, "shard", axis=1)          # [QBl, S, kk]
        all_gids = jax.lax.all_gather(gids, "shard", axis=1)
        S = all_vals.shape[1]
        gvals = all_vals.reshape(all_vals.shape[0], S * kk)
        gdocs = all_gids.reshape(all_gids.shape[0], S * kk)
        return gdocs, gvals, totals

    tree_spec = {k_: P("shard") for k_ in
                 ("starts", "doc_ids", "tfs", "dl", "live", "doc_base",
                  "doc_count", "sum_dl", "field_dc")}
    in_specs = (tree_spec, P("shard", "replica"), P("replica"),
                P("replica"), P("replica"))
    if filtered:
        in_specs = in_specs + (P("shard"),)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=(P("replica"), P("replica"), P("replica")),
                       check_vma=False)
    jitted = jax.jit(fn)

    def call(tree, rows, boosts, msm, cscore=None, fmask=None):
        if cscore is None:
            cscore = jnp.zeros_like(jnp.asarray(msm))
        if filtered:
            return jitted(tree, rows, boosts, msm, cscore, fmask)
        return jitted(tree, rows, boosts, msm, cscore)

    return call


def build_distributed_metrics(mesh: Mesh, bucket: int, ndocs_pad: int,
                              k1: float = 1.2, b: float = 0.75,
                              filtered: bool = False):
    """Metric aggregations over the mesh: re-evaluate each query's match
    mask shard-locally (same scoring program shape), then psum/pmin/pmax
    the masked column moments over the `shard` axis — the device-side
    analog of the reference's per-shard metric collectors + coordinator
    InternalAggregation#reduce. Returns a callable:
        (tree, rows [S,QB,T], boosts [QB,T], msm [QB], cscore [QB],
         col [S,D_pad], present [S,D_pad] [, fmask [S,D_pad]]) ->
        (i32[QB] counts, f32[QB, 4] = (sum, min, max, sumsq)),
        already global. The count plane is int32 (same rule as the
        terms/pair programs): f32 sums stop counting exactly at 2^24
        matching docs, and filters/adjacency doc_counts ride this plane."""

    def per_device(tree, rows, boosts, msm, cscore, col, present,
                   fmask=None):
        rows = rows[0]
        starts = tree["starts"][0]
        doc_ids = tree["doc_ids"][0]
        tfs = tree["tfs"][0]
        dl = tree["dl"][0]
        live = tree["live"][0]
        colv = col[0]
        pres = present[0]
        fm = fmask[0] if fmask is not None else None

        df_global, n_global, avgdl = _global_dfs_stats(tree, rows)

        def one(r, w, m, cs, dfg):
            scores = _score_one_query(starts, doc_ids, tfs, dl, live, r, w,
                                      m, cs, n_global, dfg, avgdl, bucket,
                                      ndocs_pad, k1, b, fm)
            ok = (scores > -jnp.inf) & (pres > 0)
            cnt = jnp.sum(ok.astype(jnp.int32))
            s = jnp.sum(jnp.where(ok, colv, 0.0))
            ssq = jnp.sum(jnp.where(ok, colv * colv, 0.0))
            mn = jnp.min(jnp.where(ok, colv, jnp.inf))
            mx = jnp.max(jnp.where(ok, colv, -jnp.inf))
            return cnt, jnp.stack([s, mn, mx, ssq])

        cnts, part = jax.vmap(one)(rows, boosts, msm, cscore,
                                   df_global)  # i32[QB], f32[QB,4]
        return (jax.lax.psum(cnts, "shard"),
                jnp.stack([
                    jax.lax.psum(part[:, 0], "shard"),
                    jax.lax.pmin(part[:, 1], "shard"),
                    jax.lax.pmax(part[:, 2], "shard"),
                    jax.lax.psum(part[:, 3], "shard"),
                ], axis=1))

    tree_spec = {k_: P("shard") for k_ in
                 ("starts", "doc_ids", "tfs", "dl", "live", "doc_base",
                  "doc_count", "sum_dl", "field_dc")}
    in_specs = (tree_spec, P("shard", "replica"), P("replica"),
                P("replica"), P("replica"), P("shard"), P("shard"))
    if filtered:
        in_specs = in_specs + (P("shard"),)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P("replica"), check_vma=False)
    return jax.jit(fn)


def build_distributed_terms_agg(mesh: Mesh, bucket: int, ndocs_pad: int,
                                vpad: int, k1: float = 1.2, b: float = 0.75,
                                filtered: bool = False):
    """Keyword `terms` aggregation over the mesh: re-evaluate each query's
    match mask shard-locally, scatter-add it over the shard's flat
    (doc, global-ordinal) value pairs, and psum the per-ordinal counts over
    the `shard` axis — an EXACT global bincount (no per-shard size
    truncation, so doc_count_error_upper_bound is genuinely 0), the
    device-side analog of the reference's GlobalOrdinalsStringTermsAggregator
    + coordinator reduce. Returns a callable:
        (tree, rows [S,QB,T], boosts [QB,T], msm [QB], cscore [QB],
         val_doc [S,NV], val_ord [S,NV] [, fmask [S,D_pad]]) ->
        f32[QB, vpad] global doc counts per ordinal."""

    def per_device(tree, rows, boosts, msm, cscore, val_doc, val_ord,
                   fmask=None):
        rows = rows[0]
        starts = tree["starts"][0]
        doc_ids = tree["doc_ids"][0]
        tfs = tree["tfs"][0]
        dl = tree["dl"][0]
        live = tree["live"][0]
        vd = val_doc[0]
        vo = val_ord[0]
        fm = fmask[0] if fmask is not None else None

        df_global, n_global, avgdl = _global_dfs_stats(tree, rows)

        vvalid = vd < INT32_SENTINEL
        vd_safe = jnp.minimum(vd, ndocs_pad - 1)

        def one(r, w, m, cs, dfg):
            scores = _score_one_query(starts, doc_ids, tfs, dl, live, r, w,
                                      m, cs, n_global, dfg, avgdl, bucket,
                                      ndocs_pad, k1, b, fm)
            # int32 accumulation: f32 scatter-adds stop counting exactly at
            # 2^24 docs/bucket, which ClueWeb-class corpora exceed — the
            # "doc_count_error_upper_bound: 0" contract requires integers
            matched = (scores > -jnp.inf).astype(jnp.int32)
            contrib = jnp.where(vvalid, matched[vd_safe], 0)
            return jnp.zeros(vpad, jnp.int32).at[vo].add(contrib,
                                                         mode="drop")

        part = jax.vmap(one)(rows, boosts, msm, cscore, df_global)  # [QB,V]
        return jax.lax.psum(part, "shard")

    tree_spec = {k_: P("shard") for k_ in
                 ("starts", "doc_ids", "tfs", "dl", "live", "doc_base",
                  "doc_count", "sum_dl", "field_dc")}
    in_specs = (tree_spec, P("shard", "replica"), P("replica"),
                P("replica"), P("replica"), P("shard"), P("shard"))
    if filtered:
        in_specs = in_specs + (P("shard"),)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P("replica"), check_vma=False)
    return jax.jit(fn)


def build_distributed_bincount(mesh: Mesh, bucket: int, ndocs_pad: int,
                               nb: int, k1: float = 1.2, b: float = 0.75,
                               filtered: bool = False):
    """Histogram / fixed-interval date_histogram over the mesh: re-evaluate
    each query's match mask shard-locally, scatter-add it over a
    host-precomputed per-doc bin-id array (global bin space; -1 = no value
    or out of range), and psum the counts — the distributed analog of the
    host 'hist' kernel (`search/agg_compiler.py` emit_agg "hist") + the
    coordinator reduce. Returns a callable:
        (tree, rows [S,QB,T], boosts [QB,T], msm [QB], cscore [QB],
         bins i32[S, D_pad] [, fmask]) -> i32[QB, nb] global counts."""

    def per_device(tree, rows, boosts, msm, cscore, bins, fmask=None):
        rows = rows[0]
        starts = tree["starts"][0]
        doc_ids = tree["doc_ids"][0]
        tfs = tree["tfs"][0]
        dl = tree["dl"][0]
        live = tree["live"][0]
        bn = bins[0]
        fm = fmask[0] if fmask is not None else None

        df_global, n_global, avgdl = _global_dfs_stats(tree, rows)
        b_safe = jnp.where(bn >= 0, bn, nb)

        def one(r, w, m, cs, dfg):
            scores = _score_one_query(starts, doc_ids, tfs, dl, live, r, w,
                                      m, cs, n_global, dfg, avgdl, bucket,
                                      ndocs_pad, k1, b, fm)
            matched = (scores > -jnp.inf).astype(jnp.int32)
            contrib = jnp.where(bn >= 0, matched, 0)
            return jnp.zeros(nb, jnp.int32).at[b_safe].add(contrib,
                                                           mode="drop")

        part = jax.vmap(one)(rows, boosts, msm, cscore, df_global)
        return jax.lax.psum(part, "shard")

    tree_spec = {k_: P("shard") for k_ in
                 ("starts", "doc_ids", "tfs", "dl", "live", "doc_base",
                  "doc_count", "sum_dl", "field_dc")}
    in_specs = (tree_spec, P("shard", "replica"), P("replica"),
                P("replica"), P("replica"), P("shard"))
    if filtered:
        in_specs = in_specs + (P("shard"),)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P("replica"), check_vma=False)
    return jax.jit(fn)


def build_distributed_pair_metrics(mesh: Mesh, bucket: int, ndocs_pad: int,
                                   vpad: int, k1: float = 1.2,
                                   b: float = 0.75,
                                   filtered: bool = False):
    """Per-BUCKET metric moments over the mesh — the device analog of the
    reference's sub-aggregation collectors under a bucketing parent
    (terms/histogram), `InternalTerms` buckets carrying nested
    `InternalStats`: re-evaluate each query's match mask shard-locally,
    scatter the metric column's (count, sum, min, max, sumsq) over the
    (doc, bucket-ordinal) pair arrays, and psum/pmin/pmax per ordinal over
    the `shard` axis. The pair form serves BOTH parents: keyword terms use
    the global-ordinal value pairs, histogram families use
    (arange, bin-id). Returns a callable:
        (tree, rows [S,QB,T], boosts [QB,T], msm [QB], cscore [QB],
         val_doc [S,NV], val_ord [S,NV], mcol [S,D_pad], mpres [S,D_pad]
         [, fmask]) -> (i32[QB, vpad] counts,
                        f32[QB, vpad, 4] = (sum, min, max, sumsq)),
        already global."""

    def per_device(tree, rows, boosts, msm, cscore, val_doc, val_ord,
                   mcol, mpres, fmask=None):
        rows = rows[0]
        starts = tree["starts"][0]
        doc_ids = tree["doc_ids"][0]
        tfs = tree["tfs"][0]
        dl = tree["dl"][0]
        live = tree["live"][0]
        vd = val_doc[0]
        vo = val_ord[0]
        mc = mcol[0]
        mp = mpres[0]
        fm = fmask[0] if fmask is not None else None

        df_global, n_global, avgdl = _global_dfs_stats(tree, rows)

        vvalid = vd < INT32_SENTINEL
        vd_safe = jnp.minimum(vd, ndocs_pad - 1)

        def one(r, w, m, cs, dfg):
            scores = _score_one_query(starts, doc_ids, tfs, dl, live, r, w,
                                      m, cs, n_global, dfg, avgdl, bucket,
                                      ndocs_pad, k1, b, fm)
            matched = scores > -jnp.inf
            ok = vvalid & matched[vd_safe] & (mp[vd_safe] > 0)
            v = mc[vd_safe]
            # int32 count plane: f32 scatter-adds stop counting exactly at
            # 2^24 docs/bucket (same rule as the terms bincount program)
            cnt = jnp.zeros(vpad, jnp.int32).at[vo].add(
                ok.astype(jnp.int32), mode="drop")
            s = jnp.zeros(vpad, jnp.float32).at[vo].add(
                jnp.where(ok, v, 0.0), mode="drop")
            ssq = jnp.zeros(vpad, jnp.float32).at[vo].add(
                jnp.where(ok, v * v, 0.0), mode="drop")
            mn = jnp.full(vpad, jnp.inf, jnp.float32).at[vo].min(
                jnp.where(ok, v, jnp.inf), mode="drop")
            mx = jnp.full(vpad, -jnp.inf, jnp.float32).at[vo].max(
                jnp.where(ok, v, -jnp.inf), mode="drop")
            return cnt, jnp.stack([s, mn, mx, ssq], axis=1)

        cnts, part = jax.vmap(one)(rows, boosts, msm, cscore, df_global)
        # counts i32[QB, vpad] exact; moments f32[QB, vpad, 4]
        return (jax.lax.psum(cnts, "shard"),
                jnp.stack([
                    jax.lax.psum(part[:, :, 0], "shard"),
                    jax.lax.pmin(part[:, :, 1], "shard"),
                    jax.lax.pmax(part[:, :, 2], "shard"),
                    jax.lax.psum(part[:, :, 3], "shard"),
                ], axis=2))

    tree_spec = {k_: P("shard") for k_ in
                 ("starts", "doc_ids", "tfs", "dl", "live", "doc_base",
                  "doc_count", "sum_dl", "field_dc")}
    in_specs = (tree_spec, P("shard", "replica"), P("replica"),
                P("replica"), P("replica"), P("shard"), P("shard"),
                P("shard"), P("shard"))
    if filtered:
        in_specs = in_specs + (P("shard"),)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P("replica"), check_vma=False)
    return jax.jit(fn)


def build_distributed_range_metrics(mesh: Mesh, bucket: int, ndocs_pad: int,
                                    nr: int, k1: float = 1.2,
                                    b: float = 0.75,
                                    filtered: bool = False):
    """Per-RANGE metric moments over the mesh (sub-aggregations under a
    `range` parent; ranges may overlap so this is nr masked reductions, not
    a scatter). Returns a callable:
        (tree, rows, boosts, msm, cscore, col [S,D], pres [S,D],
         lows f32[nr], highs f32[nr], mcol [S,D], mpres [S,D] [, fmask])
        -> (i32[QB, nr] counts, f32[QB, nr, 4] = (sum, min, max, sumsq)),
        global."""

    def per_device(tree, rows, boosts, msm, cscore, col, pres, lows, highs,
                   mcol, mpres, fmask=None):
        rows = rows[0]
        starts = tree["starts"][0]
        doc_ids = tree["doc_ids"][0]
        tfs = tree["tfs"][0]
        dl = tree["dl"][0]
        live = tree["live"][0]
        cv = col[0]
        pr = pres[0]
        mc = mcol[0]
        mp = mpres[0]
        fm = fmask[0] if fmask is not None else None

        df_global, n_global, avgdl = _global_dfs_stats(tree, rows)

        def one(r, w, m, cs, dfg):
            scores = _score_one_query(starts, doc_ids, tfs, dl, live, r, w,
                                      m, cs, n_global, dfg, avgdl, bucket,
                                      ndocs_pad, k1, b, fm)
            matched = (scores > -jnp.inf) & (pr > 0) & (mp > 0)
            cnts, stats = [], []
            for ri in range(nr):
                ok = matched & (cv >= lows[ri]) & (cv < highs[ri])
                cnts.append(jnp.sum(ok.astype(jnp.int32)))
                stats.append(jnp.stack([
                    jnp.sum(jnp.where(ok, mc, 0.0)),
                    jnp.min(jnp.where(ok, mc, jnp.inf)),
                    jnp.max(jnp.where(ok, mc, -jnp.inf)),
                    jnp.sum(jnp.where(ok, mc * mc, 0.0))]))
            return jnp.stack(cnts), jnp.stack(stats)

        cnts, part = jax.vmap(one)(rows, boosts, msm, cscore, df_global)
        return (jax.lax.psum(cnts, "shard"),
                jnp.stack([
                    jax.lax.psum(part[:, :, 0], "shard"),
                    jax.lax.pmin(part[:, :, 1], "shard"),
                    jax.lax.pmax(part[:, :, 2], "shard"),
                    jax.lax.psum(part[:, :, 3], "shard"),
                ], axis=2))

    tree_spec = {k_: P("shard") for k_ in
                 ("starts", "doc_ids", "tfs", "dl", "live", "doc_base",
                  "doc_count", "sum_dl", "field_dc")}
    in_specs = (tree_spec, P("shard", "replica"), P("replica"),
                P("replica"), P("replica"), P("shard"), P("shard"),
                P(), P(), P("shard"), P("shard"))
    if filtered:
        in_specs = in_specs + (P("shard"),)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P("replica"), check_vma=False)
    return jax.jit(fn)


def build_distributed_cardinality(mesh: Mesh, bucket: int, ndocs_pad: int,
                                  keyword: bool, vpad: int = 0,
                                  k1: float = 1.2,
                                  b: float = 0.75, filtered: bool = False):
    """`cardinality` over the mesh with EXACT host parity: per shard,
    build the same HyperLogLog registers the host segment path builds
    (ops/aggs.py hll_registers over crc32 ordinal hashes / fmix32 value
    hashes), then reduce with pmax — HLL registers merge by elementwise
    max, which is precisely the collective the mesh has. The estimate is
    therefore bit-identical to the host shard loop's.

    keyword=True: (tree, rows, boosts, msm, cscore, val_doc [S,NV],
        val_ord [S,NV], ord_hashes u32[vpad] [, fmask])
    keyword=False: (tree, rows, boosts, msm, cscore, col [S,D],
        pres [S,D] [, fmask])
    -> i32[QB, 2^HLL_LOG2M] registers, already global."""
    from ..ops import aggs as agg_ops
    # the ONE precision constant: mesh registers must stay the same
    # shape/precision as the host's or the max-merge silently drifts
    from ..search.agg_compiler import HLL_LOG2M as log2m

    def per_device(tree, rows, boosts, msm, cscore, *rest):
        fmask = rest[-1] if filtered else None
        rest = rest[:-1] if filtered else rest
        rows = rows[0]
        starts = tree["starts"][0]
        doc_ids = tree["doc_ids"][0]
        tfs = tree["tfs"][0]
        dl = tree["dl"][0]
        live = tree["live"][0]
        fm = fmask[0] if fmask is not None else None

        df_global, n_global, avgdl = _global_dfs_stats(tree, rows)

        if keyword:
            val_doc, val_ord, ord_hashes = rest
            vd = val_doc[0]
            vo = val_ord[0]
            vvalid = vd < INT32_SENTINEL
            vd_safe = jnp.minimum(vd, ndocs_pad - 1)

            def one(r, w, m, cs, dfg):
                scores = _score_one_query(starts, doc_ids, tfs, dl, live,
                                          r, w, m, cs, n_global, dfg,
                                          avgdl, bucket, ndocs_pad, k1, b,
                                          fm)
                matched = (scores > -jnp.inf).astype(jnp.int32)
                contrib = jnp.where(vvalid, matched[vd_safe], 0)
                counts = jnp.zeros(vpad, jnp.int32).at[vo].add(
                    contrib, mode="drop")
                return agg_ops.hll_registers(ord_hashes, counts > 0,
                                             log2m)
        else:
            col, pres = rest
            cv = col[0]
            pr = pres[0]
            hashes = agg_ops._hash_f32(cv)

            def one(r, w, m, cs, dfg):
                scores = _score_one_query(starts, doc_ids, tfs, dl, live,
                                          r, w, m, cs, n_global, dfg,
                                          avgdl, bucket, ndocs_pad, k1, b,
                                          fm)
                valid = (scores > -jnp.inf) & (pr > 0)
                return agg_ops.hll_registers(hashes, valid, log2m)

        part = jax.vmap(one)(rows, boosts, msm, cscore, df_global)
        return jax.lax.pmax(part, "shard")

    tree_spec = {k_: P("shard") for k_ in
                 ("starts", "doc_ids", "tfs", "dl", "live", "doc_base",
                  "doc_count", "sum_dl", "field_dc")}
    if keyword:
        in_specs = (tree_spec, P("shard", "replica"), P("replica"),
                    P("replica"), P("replica"), P("shard"), P("shard"),
                    P())
    else:
        in_specs = (tree_spec, P("shard", "replica"), P("replica"),
                    P("replica"), P("replica"), P("shard"), P("shard"))
    if filtered:
        in_specs = in_specs + (P("shard"),)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P("replica"), check_vma=False)
    return jax.jit(fn)


def build_distributed_ddsketch(mesh: Mesh, bucket: int, ndocs_pad: int,
                               k1: float = 1.2, b: float = 0.75,
                               filtered: bool = False):
    """DDSketch histogram over the mesh (serves BOTH `percentiles` and
    `median_absolute_deviation`): bins are value-independent global
    constants, so per-shard histograms merge by plain addition — psum IS
    the reference's TDigest-merge analog. Returns a callable:
        (tree, rows, boosts, msm, cscore, col [S,D], pres [S,D] [, fmask])
        -> f32[QB, DD_NBINS], already global."""
    from ..ops import aggs as agg_ops

    def per_device(tree, rows, boosts, msm, cscore, col, pres, fmask=None):
        rows = rows[0]
        starts = tree["starts"][0]
        doc_ids = tree["doc_ids"][0]
        tfs = tree["tfs"][0]
        dl = tree["dl"][0]
        live = tree["live"][0]
        cv = col[0]
        pr = pres[0]
        fm = fmask[0] if fmask is not None else None

        df_global, n_global, avgdl = _global_dfs_stats(tree, rows)

        def one(r, w, m, cs, dfg):
            scores = _score_one_query(starts, doc_ids, tfs, dl, live, r, w,
                                      m, cs, n_global, dfg, avgdl, bucket,
                                      ndocs_pad, k1, b, fm)
            matchf = (scores > -jnp.inf).astype(jnp.float32)
            return agg_ops.ddsketch_hist(cv, pr > 0, matchf)

        part = jax.vmap(one)(rows, boosts, msm, cscore, df_global)
        return jax.lax.psum(part, "shard")

    tree_spec = {k_: P("shard") for k_ in
                 ("starts", "doc_ids", "tfs", "dl", "live", "doc_base",
                  "doc_count", "sum_dl", "field_dc")}
    in_specs = (tree_spec, P("shard", "replica"), P("replica"),
                P("replica"), P("replica"), P("shard"), P("shard"))
    if filtered:
        in_specs = in_specs + (P("shard"),)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P("replica"), check_vma=False)
    return jax.jit(fn)


def build_distributed_weighted_avg(mesh: Mesh, bucket: int, ndocs_pad: int,
                                   k1: float = 1.2, b: float = 0.75,
                                   filtered: bool = False):
    """`weighted_avg` over the mesh: psum of (value*weight sum, weight
    sum, count) over docs present in BOTH columns — the host's
    weighted_avg_agg moments, reduced once. Returns a callable:
        (tree, rows, boosts, msm, cscore, vcol, vpres, wcol, wpres
         [, fmask]) -> f32[QB, 3] = (vwsum, wsum, count), global."""

    def per_device(tree, rows, boosts, msm, cscore, vcol, vpres, wcol,
                   wpres, fmask=None):
        rows = rows[0]
        starts = tree["starts"][0]
        doc_ids = tree["doc_ids"][0]
        tfs = tree["tfs"][0]
        dl = tree["dl"][0]
        live = tree["live"][0]
        vv = vcol[0]
        vp = vpres[0]
        wv = wcol[0]
        wp = wpres[0]
        fm = fmask[0] if fmask is not None else None

        df_global, n_global, avgdl = _global_dfs_stats(tree, rows)

        def one(r, w, m, cs, dfg):
            scores = _score_one_query(starts, doc_ids, tfs, dl, live, r, w,
                                      m, cs, n_global, dfg, avgdl, bucket,
                                      ndocs_pad, k1, b, fm)
            ok = (scores > -jnp.inf) & (vp > 0) & (wp > 0)
            okf = ok.astype(jnp.float32)
            return jnp.stack([jnp.sum(okf * vv * wv),
                              jnp.sum(okf * wv),
                              jnp.sum(okf)])

        part = jax.vmap(one)(rows, boosts, msm, cscore, df_global)
        return jax.lax.psum(part, "shard")

    tree_spec = {k_: P("shard") for k_ in
                 ("starts", "doc_ids", "tfs", "dl", "live", "doc_base",
                  "doc_count", "sum_dl", "field_dc")}
    in_specs = (tree_spec, P("shard", "replica"), P("replica"),
                P("replica"), P("replica"), P("shard"), P("shard"),
                P("shard"), P("shard"))
    if filtered:
        in_specs = in_specs + (P("shard"),)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P("replica"), check_vma=False)
    return jax.jit(fn)


def build_distributed_geo_stat(mesh: Mesh, bucket: int, ndocs_pad: int,
                               k1: float = 1.2, b: float = 0.75,
                               filtered: bool = False):
    """geo_bounds + geo_centroid over the mesh in one program (the two
    kinds share every input): per shard, masked lat/lon extremes and
    centroid moments, reduced with pmax/pmin/psum — the same collectives
    the host merge applies across segments. Returns a callable:
        (tree, rows, boosts, msm, cscore, glat [S,D], glon [S,D],
         gpres [S,D] [, fmask]) ->
        f32[QB, 7] = (count, top, bottom, left, right, slat, slon)."""
    F32_MAX = np.float32(np.finfo(np.float32).max)

    def per_device(tree, rows, boosts, msm, cscore, glat, glon, gpres,
                   fmask=None):
        rows = rows[0]
        starts = tree["starts"][0]
        doc_ids = tree["doc_ids"][0]
        tfs = tree["tfs"][0]
        dl = tree["dl"][0]
        live = tree["live"][0]
        la = glat[0]
        lo = glon[0]
        pr = gpres[0]
        fm = fmask[0] if fmask is not None else None

        df_global, n_global, avgdl = _global_dfs_stats(tree, rows)

        def one(r, w, m, cs, dfg):
            scores = _score_one_query(starts, doc_ids, tfs, dl, live, r, w,
                                      m, cs, n_global, dfg, avgdl, bucket,
                                      ndocs_pad, k1, b, fm)
            ok = (scores > -jnp.inf) & (pr > 0)
            okf = ok.astype(jnp.float32)
            return jnp.stack([
                jnp.sum(okf),
                jnp.max(jnp.where(ok, la, -F32_MAX)),
                jnp.min(jnp.where(ok, la, F32_MAX)),
                jnp.min(jnp.where(ok, lo, F32_MAX)),
                jnp.max(jnp.where(ok, lo, -F32_MAX)),
                jnp.sum(okf * la),
                jnp.sum(okf * lo)])

        part = jax.vmap(one)(rows, boosts, msm, cscore, df_global)
        return jnp.stack([
            jax.lax.psum(part[:, 0], "shard"),
            jax.lax.pmax(part[:, 1], "shard"),
            jax.lax.pmin(part[:, 2], "shard"),
            jax.lax.pmin(part[:, 3], "shard"),
            jax.lax.pmax(part[:, 4], "shard"),
            jax.lax.psum(part[:, 5], "shard"),
            jax.lax.psum(part[:, 6], "shard"),
        ], axis=1)

    tree_spec = {k_: P("shard") for k_ in
                 ("starts", "doc_ids", "tfs", "dl", "live", "doc_base",
                  "doc_count", "sum_dl", "field_dc")}
    in_specs = (tree_spec, P("shard", "replica"), P("replica"),
                P("replica"), P("replica"), P("shard"), P("shard"),
                P("shard"))
    if filtered:
        in_specs = in_specs + (P("shard"),)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P("replica"), check_vma=False)
    return jax.jit(fn)


def build_distributed_range_counts(mesh: Mesh, bucket: int, ndocs_pad: int,
                                   nr: int, k1: float = 1.2,
                                   b: float = 0.75,
                                   filtered: bool = False):
    """`range` aggregation over the mesh: per-range [lo, hi) masked count
    of matching docs (ranges may OVERLAP, so this is nr masked sums, not a
    bincount), psum'd over the shard axis. Returns a callable:
        (tree, rows, boosts, msm, cscore, col [S,D], pres [S,D],
         lows f32[nr], highs f32[nr] [, fmask]) -> i32[QB, nr]."""

    def per_device(tree, rows, boosts, msm, cscore, col, pres, lows, highs,
                   fmask=None):
        rows = rows[0]
        starts = tree["starts"][0]
        doc_ids = tree["doc_ids"][0]
        tfs = tree["tfs"][0]
        dl = tree["dl"][0]
        live = tree["live"][0]
        cv = col[0]
        pr = pres[0]
        fm = fmask[0] if fmask is not None else None

        df_global, n_global, avgdl = _global_dfs_stats(tree, rows)

        def one(r, w, m, cs, dfg):
            scores = _score_one_query(starts, doc_ids, tfs, dl, live, r, w,
                                      m, cs, n_global, dfg, avgdl, bucket,
                                      ndocs_pad, k1, b, fm)
            matched = (scores > -jnp.inf) & (pr > 0)
            counts = []
            for ri in range(nr):
                sel = matched & (cv >= lows[ri]) & (cv < highs[ri])
                counts.append(jnp.sum(sel.astype(jnp.int32)))
            return jnp.stack(counts)

        part = jax.vmap(one)(rows, boosts, msm, cscore, df_global)
        return jax.lax.psum(part, "shard")

    tree_spec = {k_: P("shard") for k_ in
                 ("starts", "doc_ids", "tfs", "dl", "live", "doc_base",
                  "doc_count", "sum_dl", "field_dc")}
    in_specs = (tree_spec, P("shard", "replica"), P("replica"),
                P("replica"), P("replica"), P("shard"), P("shard"),
                P(), P())
    if filtered:
        in_specs = in_specs + (P("shard"),)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P("replica"), check_vma=False)
    return jax.jit(fn)


@dataclass
class StackedPhrasePairs:
    """Per-shard positional (doc, position) pair arrays in the SAME
    term-row space as a StackedShardIndex — the mesh-resident form of the
    host path's per-segment positional planes (`Segment.device_positions`;
    there a term is a window of the planes, here a row's own pairs). Rows
    are the stacked index's shard term-union rows;
    each row's pairs concatenate the shard's segments (doc ids offset by
    segment base) and are lex-sorted by (doc, position), sentinel padded."""

    field: str
    pair_starts: jnp.ndarray   # i32[S, R_pad]  (stacked.starts row space)
    pair_d: jnp.ndarray        # i32[S, PP_pad]
    pair_p: jnp.ndarray        # i32[S, PP_pad]
    host_pair_starts: Optional[List[np.ndarray]] = None
    nbytes: int = 0

    def row_size(self, shard: int, row: int) -> int:
        st = self.host_pair_starts[shard]
        return int(st[row + 1] - st[row]) if 0 <= row < len(st) - 1 else 0

    def tree(self) -> dict:
        return {"pair_starts": self.pair_starts, "pair_d": self.pair_d,
                "pair_p": self.pair_p}

    @classmethod
    def build(cls, shard_segs, field: str, stacked: StackedShardIndex,
              mesh: Mesh) -> Optional["StackedPhrasePairs"]:
        S = len(shard_segs)
        per = []
        any_positional = False
        for si, segs in enumerate(shard_segs):
            union = stacked.host_terms[si]
            nterms = len(union)
            trows_parts, d_parts, p_parts = [], [], []
            off = 0
            for seg in segs:
                pb = seg.postings.get(field)
                if pb is not None and pb.pos_starts is not None and pb.size:
                    any_positional = True
                    # vectorized: per-position (union row, offset doc, pos)
                    rows_map = np.array([union[t] for t in pb.vocab],
                                        np.int64)
                    per_post = np.repeat(rows_map, np.diff(pb.starts))
                    counts = np.diff(pb.pos_starts)
                    trows_parts.append(np.repeat(per_post, counts))
                    d_parts.append(np.repeat(
                        pb.doc_ids.astype(np.int64) + off, counts))
                    p_parts.append(pb.positions.astype(np.int64))
                off += seg.ndocs
            if trows_parts:
                trows = np.concatenate(trows_parts)
                d = np.concatenate(d_parts)
                p = np.concatenate(p_parts)
                order = np.lexsort((p, d, trows))
                trows, d, p = trows[order], d[order], p[order]
                lens = np.bincount(trows, minlength=nterms)
            else:
                d = p = np.zeros(0, np.int64)
                lens = np.zeros(max(nterms, 1), np.int64)
            starts = np.zeros(len(lens) + 1, np.int64)
            np.cumsum(lens, out=starts[1:])
            per.append((starts, d, p))
        if not any_positional:
            return None
        r_pad = int(stacked.starts.shape[1])
        pp_pad = max(next_pow2(max(len(d), 1)) for _st, d, _p in per)
        pair_starts = np.zeros((S, r_pad), np.int32)
        pair_d = np.full((S, pp_pad), INT32_SENTINEL, np.int32)
        pair_p = np.full((S, pp_pad), INT32_SENTINEL, np.int32)
        host_ps = []
        for si, (starts, d, p) in enumerate(per):
            n = min(len(starts), r_pad)
            pair_starts[si, :n] = starts[:n]
            pair_starts[si, n:] = starts[-1]
            pair_d[si, : len(d)] = d
            pair_p[si, : len(p)] = p
            host_ps.append(starts)
        sharding = NamedSharding(mesh, P("shard"))
        return cls(field=field,
                   pair_starts=jax.device_put(pair_starts, sharding),  # oslint: disable=OSL506 -- _ByteLRU kind registers at put()
                   pair_d=jax.device_put(pair_d, sharding),  # oslint: disable=OSL506 -- _ByteLRU kind registers at put()
                   pair_p=jax.device_put(pair_p, sharding),  # oslint: disable=OSL506 -- _ByteLRU kind registers at put()
                   host_pair_starts=host_ps,
                   nbytes=pair_starts.nbytes + pair_d.nbytes
                   + pair_p.nbytes)


def build_distributed_phrase(mesh: Mesh, bucket: int, ndocs_pad: int,
                             k: int, n_terms: int, k1: float = 1.2,
                             b: float = 0.75, filtered: bool = False):
    """Distributed match_phrase over the mesh: each shard runs the
    vectorized positional pair-join (ops/positions.py phrase_freqs — the
    device replacement for Lucene's ExactPhrase/SloppyPhraseMatcher) over
    its own positional pairs, scores the phrase as one BM25 pseudo-term
    with the HOST-computed global weight (same `LPhrase.weight` the host
    shard loop uses, so scores are bit-identical), and the per-shard
    top-ks merge with an all_gather — completing the coordinator fan-out
    (`action/search/SearchPhaseController.java:1`) for the phrase-shaped
    traffic the mesh previously declined. Returns a callable:
        (tree, ptree, rows [S,QB,T], weights [QB], slops [QB],
         avgdl [QB] [, fmask [S,D_pad]]) ->
        (global_doc_ids [QB, S*k], scores [QB, S*k], totals [QB])"""
    from ..ops import positions as pos_ops

    def gather_pairs(pstarts, pair_d, pair_p, r):
        rsafe = jnp.maximum(r, 0)
        a = jnp.where(r >= 0, pstarts[rsafe], 0)
        e = jnp.where(r >= 0, pstarts[rsafe + 1], 0)
        idx = a + jnp.arange(bucket, dtype=jnp.int32)
        valid = idx < e
        safe = jnp.minimum(idx, pair_d.shape[0] - 1)
        d = jnp.where(valid, pair_d[safe], INT32_SENTINEL)
        p = jnp.where(valid, pair_p[safe], INT32_SENTINEL)
        return d, p

    def per_device(tree, ptree, rows, weights, slops, avgdl, fmask=None):
        rows = rows[0]
        pstarts = ptree["pair_starts"][0]
        pair_d = ptree["pair_d"][0]
        pair_p = ptree["pair_p"][0]
        dl = tree["dl"][0]
        live = tree["live"][0]
        doc_base = tree["doc_base"][0]
        fm = fmask[0] if fmask is not None else None
        lv = live * fm if fm is not None else live

        def one(r, w, slop, ad):
            anchor_d, anchor_p = gather_pairs(pstarts, pair_d, pair_p,
                                              r[0])
            others = [gather_pairs(pstarts, pair_d, pair_p, r[i])
                      for i in range(1, n_terms)]
            freq = pos_ops.phrase_freqs(
                anchor_d, anchor_p, others, slop, ndocs_pad,
                shifts=list(range(1, n_terms)))
            sc, matched = pos_ops.phrase_score(freq, dl, lv, w, k1, b, ad)
            return jnp.where(matched, sc, -jnp.inf)

        scores = jax.vmap(one)(rows, weights, slops, avgdl)       # [QB, D]
        totals = jax.lax.psum(jnp.sum(scores > -jnp.inf, axis=1), "shard")
        kk = min(k, ndocs_pad)
        vals, idx = jax.lax.top_k(scores, kk)
        gids = jnp.where(vals > -jnp.inf, idx + doc_base, -1)
        all_vals = jax.lax.all_gather(vals, "shard", axis=1)
        all_gids = jax.lax.all_gather(gids, "shard", axis=1)
        S = all_vals.shape[1]
        return (all_gids.reshape(all_gids.shape[0], S * kk),
                all_vals.reshape(all_vals.shape[0], S * kk), totals)

    tree_spec = {k_: P("shard") for k_ in
                 ("starts", "doc_ids", "tfs", "dl", "live", "doc_base",
                  "doc_count", "sum_dl", "field_dc")}
    ptree_spec = {k_: P("shard") for k_ in
                  ("pair_starts", "pair_d", "pair_p")}
    in_specs = (tree_spec, ptree_spec, P("shard", "replica"), P("replica"),
                P("replica"), P("replica"))
    if filtered:
        in_specs = in_specs + (P("shard"),)
    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=(P("replica"), P("replica"), P("replica")),
                       check_vma=False)
    return jax.jit(fn)


def build_term_sharded_score(mesh: Mesh, bucket: int, ndocs_pad: int, k: int,
                             k1: float = 1.2, b: float = 0.75):
    """Sequence-parallel analog: ONE doc space replicated, posting rows of the
    query terms partitioned across the `shard` axis (each device scores a
    slice of the postings); partial dense score vectors are `psum`med. Use for
    pathologically hot terms whose posting lists dwarf a shard (the long-
    context regime: the reduction dimension is sharded, not the batch)."""

    def per_device(starts, doc_ids, tfs, dl, live, rows, boosts, df, n_docs, avgdl, msm):
        starts = starts[0]
        doc_ids = doc_ids[0]
        tfs = tfs[0]
        # dl/live replicated
        idf = jnp.log1p((n_docs - df + 0.5) / (df + 0.5))
        w = jnp.where(df > 0, boosts * idf, 0.0)
        docs, tf, t_idx, valid = _local_gather(starts, doc_ids, tfs, rows, bucket)
        dsafe = jnp.minimum(docs, ndocs_pad - 1)
        kfac = k1 * (1.0 - b + b * dl[dsafe] / jnp.maximum(avgdl, 1e-9))
        contrib = jnp.where(valid, w[t_idx] * tf / (tf + kfac), 0.0)
        part = jnp.zeros(ndocs_pad, jnp.float32).at[docs].add(contrib, mode="drop")
        cnt = jnp.zeros(ndocs_pad, jnp.float32).at[docs].add(
            jnp.where(valid & (tf > 0), 1.0, 0.0), mode="drop")
        scores = jax.lax.psum(part, "shard")
        counts = jax.lax.psum(cnt, "shard")
        masked = jnp.where((counts >= msm) & (live > 0), scores, -jnp.inf)
        vals, idx = jax.lax.top_k(masked, min(k, ndocs_pad))
        return vals, idx

    fn = jax.shard_map(per_device, mesh=mesh,
                       in_specs=(P("shard"), P("shard"), P("shard"),
                                 P(), P(), P(), P(), P(), P(), P(), P()),
                       out_specs=(P(), P()),
                       check_vma=False)
    return jax.jit(fn)


def route_docs_to_shards(ids: List[str], n_shards: int) -> List[int]:
    """Host-side murmur3 doc routing (same as cluster.routing.shard_for)."""
    from ..cluster.routing import shard_for

    return [shard_for(i, n_shards) for i in ids]


def pad_queries(term_rows: List[List[int]], term_boosts: List[List[float]],
                msms: List[int], qb_pad: int, t_pad: int):
    """Host packing of a query batch into [QB,T] arrays for the SPMD program.
    NOTE: rows must be PER-SHARD (each shard has its own term dict); use
    `pack_query_batch` which resolves terms against every shard."""
    rows = np.full((qb_pad, t_pad), -1, np.int32)
    boosts = np.zeros((qb_pad, t_pad), np.float32)
    msm = np.zeros(qb_pad, np.float32)
    for i, (r, bst, m) in enumerate(zip(term_rows, term_boosts, msms)):
        rows[i, : len(r)] = r
        boosts[i, : len(bst)] = bst
        msm[i] = m
    return rows, boosts, msm


def pack_query_batch(segments: List[Segment], field: str,
                     queries: List[List[str]], qb_pad: int, t_pad: int,
                     mesh: Optional[Mesh] = None):
    """Resolve analyzed query terms against every shard's term dict ->
    rows [S, QB, T] (sharded over `shard`), boosts/msm [QB, ...] (replicated
    over shard, sharded over replica). For the doc-sharded program, rows must
    differ per shard; we stack them and let shard_map slice its block."""
    S = len(segments)
    rows = np.full((S, qb_pad, t_pad), -1, np.int32)
    boosts = np.zeros((qb_pad, t_pad), np.float32)
    msm = np.ones(qb_pad, np.float32)
    for qi, terms in enumerate(queries):
        for ti, t in enumerate(terms[:t_pad]):
            boosts[qi, ti] = 1.0
            for si, seg in enumerate(segments):
                pb = seg.postings.get(field)
                rows[si, qi, ti] = pb.row(t) if pb is not None else -1
    return rows, boosts, msm
