"""Benchmark: BM25 throughput/latency THROUGH THE PRODUCT REST PATH on one
TPU chip vs an honest skipping CPU baseline, on a synthetic MS-MARCO-shaped
corpus (Zipf terms, ~56 tokens/doc; default BENCH_NDOCS=8_800_000 = MS MARCO
passage).

Workloads (BASELINE.json configs):
  1. match      — 2-term BM25 match, the classic hot path
  2. bool       — filtered OR-match / AND-match / msm shoulds over keyword +
                  numeric guardrail filters (status, price)
  3. phrase     — match_phrase over a positional short field (title built
                  from a bigram pool so phrases genuinely match)
  mixed         — 50% filtered bool, 30% match, 20% phrase in one stream
Configs 4 (BEIR ablation) and 5 (ClueWeb 50M multi-segment) are not run
this round; see SURVEY §5.

The measured path is `RestClient.msearch` end-to-end: DSL parse → plan
rewrite → fused Pallas kernels (search/fastpath.py: pure + bool/filtered
weighted-threshold variants, filter-specialized postings for dense hot
filters) → shard reduce → fetch with `_id`/`_source` materialization. The
run aborts if any measured query silently falls back off the kernels
(fastpath.STATS).

The CPU baseline is the C++ MaxScore/conjunction skipping scorer in
`opensearch_tpu/native` (the BulkScorer class Lucene runs, reference
`search/query/QueryPhase.java`): per-term upper bounds, galloping cursor
advance, strict-tie top-k — NOT the old vectorized-numpy full scan.
SURVEY §5's published-Lucene band (50-150 q/s/core) is reported alongside.

Corpus construction bypasses text analysis (the synthetic corpus IS its CSR
postings; building 500M tokens of fake text to re-tokenize would bench the
string generator), but everything from the query DSL inward is the product.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
Env: BENCH_NDOCS (default 8_800_000), BENCH_QUERIES (default 2048),
BENCH_BUDGET_S (default 540: soft wall-clock budget — reps scale down and
optional streams drop to fit), BENCH_CACHE (default 1: memoize the synthetic
corpus in .bench_cache/ so reruns skip the ~6 min build),
BENCH_WRITE_BASELINE=1 to update BASELINE.json's `published` section
(default: results go to BENCH_out.json only — benchmarking must not mutate
checked-in baseline data as a side effect).

Timeout-proof: partial results are flushed to BENCH_out.json after every
config, and SIGTERM/SIGINT print the best-so-far JSON line before exiting,
so a driver-imposed timeout still records the round's numbers.
"""

import json
import os
import signal
import sys
import time
from typing import Optional

import numpy as np

K1, B = 1.2, 0.75
TOPK = 10

_REPO = os.path.dirname(os.path.abspath(__file__))
_PARTIAL = {"metric": "bm25_rest_qps_per_chip", "value": None,
            "unit": "queries/sec", "vs_baseline": None,
            "extra": {"status": "started"}}
_PRINTED = [False]


def _emit_partial(status: str) -> None:
    """Flush best-so-far results to BENCH_out.json (never stdout)."""
    _PARTIAL["extra"]["status"] = status
    try:
        with open(os.path.join(_REPO, "BENCH_out.json"), "w") as f:
            json.dump(_PARTIAL, f, indent=2)
    except OSError:
        pass


def _on_term(signum, frame):
    if not _PRINTED[0]:
        _PRINTED[0] = True
        _PARTIAL["extra"]["status"] = f"interrupted(sig{signum})"
        _emit_partial(_PARTIAL["extra"]["status"])
        print(json.dumps(_PARTIAL), flush=True)
    os._exit(0)


# ---------------------------------------------------------------------
# device check (this process, once, before anything is measured)
# ---------------------------------------------------------------------

def require_tpu() -> dict:
    """Ask JAX for its devices in THIS process and raise unless the first
    is a TPU: a bench run has a chip or fails — no child probe (a chip
    belongs to one process), no CPU result under a per-chip metric name.
    Returns the device stamp every result carries."""
    import jax
    ds = jax.devices()
    if ds[0].platform != "tpu":
        raise RuntimeError(
            f"bench.py measures a TPU chip; JAX reports platform "
            f"{ds[0].platform!r} ({ds[0].device_kind}, {len(ds)} device(s))")
    return {"platform": ds[0].platform, "kind": ds[0].device_kind,
            "count": len(ds), "jax_version": jax.__version__}


# ---------------------------------------------------------------------
# corpus builders
# ---------------------------------------------------------------------

# bump when a corpus builder's logic or defaults change — stale caches would
# silently bench against the old corpus otherwise
_CORPUS_VERSION = "v1-zipf1.15-dl56-vocab200k"


def _cached(name: str, builder, enabled: bool):
    """Memoize a tuple-of-ndarrays corpus build in .bench_cache/<name>/ and
    reload with mmap (instant) — the 8.8M-doc build is ~6 min of pure numpy
    that benches nothing we ship."""
    d = os.path.join(_REPO, ".bench_cache", f"{_CORPUS_VERSION}-{name}")
    meta = os.path.join(d, "ok")
    if enabled and os.path.exists(meta):
        n = int(open(meta).read())
        return tuple(np.load(os.path.join(d, f"a{i}.npy"), mmap_mode="r")
                     for i in range(n))
    arrays = builder()
    if enabled:
        try:
            os.makedirs(d, exist_ok=True)
            for i, a in enumerate(arrays):
                np.save(os.path.join(d, f"a{i}.npy"), a)
            with open(meta, "w") as f:
                f.write(str(len(arrays)))
        except OSError:
            pass
    return arrays


def build_corpus(ndocs: int, vocab: int = 200_000, avg_dl: int = 56, seed: int = 0):
    rng = np.random.default_rng(seed)
    dl = np.clip(rng.lognormal(np.log(avg_dl), 0.4, ndocs), 8, 256).astype(np.int64)
    total = int(dl.sum())
    doc_of_tok = np.repeat(np.arange(ndocs, dtype=np.int64), dl)
    terms = rng.zipf(1.15, total).astype(np.int64)
    terms = np.where(terms > vocab, rng.integers(1, vocab, total), terms) - 1
    keys = terms * ndocs + doc_of_tok
    uniq, counts = np.unique(keys, return_counts=True)
    term_arr = (uniq // ndocs).astype(np.int64)
    doc_ids = (uniq % ndocs).astype(np.int32)
    tfs = counts.astype(np.float32)
    df_per_term = np.bincount(term_arr, minlength=vocab)
    starts = np.zeros(vocab + 1, dtype=np.int64)
    np.cumsum(df_per_term, out=starts[1:])
    true_dl = np.zeros(ndocs, np.int64)
    np.add.at(true_dl, doc_ids, counts)
    return starts, doc_ids, tfs, true_dl, df_per_term


def build_corpus_topical(ndocs: int, vocab: int = 200_000, avg_dl: int = 56,
                         ntopics: Optional[int] = None,
                         frac_topical: float = 0.5, seed: int = 0):
    """MS-MARCO-shaped corpus WITH topical co-occurrence: each doc draws
    one topic; ~`frac_topical` of its tokens come from that topic's own
    vocabulary slice (zipf within the slice), the rest from the global
    zipf background (stopword-heavy, like `build_corpus`). Real passages
    are topical — docs about one subject share its vocabulary — and that
    co-occurrence is exactly the signal BP doc-id reordering
    (index/reorder.py) clusters on; an iid-token synthetic is the ONE
    corpus shape where reordering provably cannot help (measured: zero
    per-term range concentration), so the reorder bench runs on this
    shape instead (docs/BENCH_CORPUS.md §topical). Returns the same
    (starts, doc_ids, tfs, dl, df) contract as build_corpus, plus the
    per-doc topic array."""
    rng = np.random.default_rng(seed)
    if ntopics is None:
        # ~8k docs per topic: topical term dfs land in the low thousands,
        # the selective-but-multi-block band block-max pruning cares about
        ntopics = max(ndocs >> 13, 8)
    bg_vocab = vocab // 2
    slice_sz = max((vocab - bg_vocab) // ntopics, 8)
    dl = np.clip(rng.lognormal(np.log(avg_dl), 0.4, ndocs), 8,
                 256).astype(np.int64)
    total = int(dl.sum())
    doc_of_tok = np.repeat(np.arange(ndocs, dtype=np.int64), dl)
    topic = rng.integers(0, ntopics, ndocs).astype(np.int64)
    is_top = rng.random(total) < frac_topical
    bg = rng.zipf(1.15, total).astype(np.int64)
    bg = np.where(bg > bg_vocab, rng.integers(1, bg_vocab, total), bg) - 1
    loc = rng.zipf(1.3, total).astype(np.int64)
    loc = np.where(loc > slice_sz, rng.integers(1, slice_sz, total),
                   loc) - 1
    topical = bg_vocab + topic[doc_of_tok] * slice_sz + loc
    terms = np.where(is_top, topical, bg)
    keys = terms * ndocs + doc_of_tok
    uniq, counts = np.unique(keys, return_counts=True)
    term_arr = (uniq // ndocs).astype(np.int64)
    doc_ids = (uniq % ndocs).astype(np.int32)
    tfs = counts.astype(np.float32)
    nvocab = bg_vocab + ntopics * slice_sz
    df_per_term = np.bincount(term_arr, minlength=nvocab)
    starts = np.zeros(nvocab + 1, dtype=np.int64)
    np.cumsum(df_per_term, out=starts[1:])
    true_dl = np.zeros(ndocs, np.int64)
    np.add.at(true_dl, doc_ids, counts)
    return starts, doc_ids, tfs, true_dl, df_per_term, topic


def build_title_corpus(ndocs: int, npairs: int = 2000, tvocab: int = 1000,
                       seed: int = 2):
    """Positional short field: 8 tokens/doc = 4 bigrams drawn from a pool,
    so phrase queries on pool bigrams genuinely match (config 3)."""
    rng = np.random.default_rng(seed)
    first = rng.integers(0, tvocab, npairs).astype(np.int64)
    second = rng.integers(0, tvocab, npairs).astype(np.int64)
    pr = rng.zipf(1.3, (ndocs, 4)).astype(np.int64)
    pr = np.where(pr > npairs, rng.integers(1, npairs, (ndocs, 4)), pr) - 1
    tok = np.empty((ndocs, 8), np.int64)
    tok[:, 0::2] = first[pr]
    tok[:, 1::2] = second[pr]
    t = tok.ravel()
    doc = np.repeat(np.arange(ndocs, dtype=np.int64), 8)
    pos = np.tile(np.arange(8, dtype=np.int64), ndocs)
    order = np.argsort((t * ndocs + doc) * 8 + pos, kind="stable")
    t, doc, pos = t[order], doc[order], pos[order]
    td = t * ndocs + doc
    head = np.empty(len(td), bool)
    head[0] = True
    head[1:] = td[1:] != td[:-1]
    idx = np.flatnonzero(head)
    doc_ids = doc[idx].astype(np.int32)
    term_arr = t[idx]
    counts = np.diff(np.append(idx, len(td)))
    tfs = counts.astype(np.float32)
    df = np.bincount(term_arr, minlength=tvocab)
    starts = np.zeros(tvocab + 1, np.int64)
    np.cumsum(df, out=starts[1:])
    pos_starts = np.zeros(len(doc_ids) + 1, np.int64)
    np.cumsum(counts, out=pos_starts[1:])
    pair_counts = np.bincount(pr.ravel(), minlength=npairs)
    return (starts, doc_ids, tfs, pos_starts, pos.astype(np.int32), first,
            second, pair_counts)


class _LazyIds:
    """8.8M doc-id strings materialized on demand (fetch touches ~10/query)."""

    def __init__(self, n, base=0):
        self.n = n
        self.base = base    # first id of this shard's doc range

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [str(self.base + j) for j in range(*i.indices(self.n))]
        return str(self.base + i)


class _LazySources:
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return {"doc": int(i)}


def make_index(client, body_csr, body_dl, title_csr, status_ord, price,
               create=True, shard=0, id_base=0):
    """Wrap the synthetic CSR + columns as a product Segment in an index.
    `title_csr=None` leaves the positional title field out; `shard` /
    `id_base` plant the segment in that shard of an already-created
    multi-shard index with ids starting at `id_base`."""
    from opensearch_tpu.index.segment import (KeywordColumn, NumericColumn,
                                              PostingsBlock, Segment,
                                              TextFieldStats)

    starts, doc_ids, tfs, vocab_strs = body_csr
    ndocs = len(body_dl)
    pb = PostingsBlock(
        field="body", vocab=list(vocab_strs),
        terms={t: i for i, t in enumerate(vocab_strs)},
        starts=starts, doc_ids=doc_ids, tfs=tfs)
    svocab = ["archived", "draft", "published"]
    kw = KeywordColumn(
        field="status", vocab=svocab,
        starts=np.arange(ndocs + 1, dtype=np.int64),
        ords=status_ord.astype(np.int32),
        doc_of_value=np.arange(ndocs, dtype=np.int32),
        min_ord=status_ord.astype(np.int32))
    # keyword term queries run against postings (like the real segment
    # builder): one CSR row per status value
    sorder = np.argsort(status_ord, kind="stable").astype(np.int32)
    scounts = np.bincount(status_ord, minlength=3)
    sstarts = np.zeros(4, np.int64)
    np.cumsum(scounts, out=sstarts[1:])
    spb = PostingsBlock(
        field="status", vocab=svocab,
        terms={v: i for i, v in enumerate(svocab)},
        starts=sstarts, doc_ids=sorder,
        tfs=np.ones(ndocs, np.float32))
    nc = NumericColumn(field="price", kind="int",
                       values=price.astype(np.int64),
                       present=np.ones(ndocs, bool))
    postings = {"body": pb, "status": spb}
    doc_lens = {"body": body_dl}
    text_stats = {"body": TextFieldStats(doc_count=ndocs,
                                         sum_dl=int(body_dl.sum()))}
    if title_csr is not None:
        (tstarts, tdoc_ids, ttfs, tpos_starts, tpositions,
         tvocab_strs) = title_csr
        postings["title"] = PostingsBlock(
            field="title", vocab=list(tvocab_strs),
            terms={t: i for i, t in enumerate(tvocab_strs)},
            starts=tstarts, doc_ids=tdoc_ids, tfs=ttfs,
            pos_starts=tpos_starts, positions=tpositions)
        doc_lens["title"] = np.full(ndocs, 8, np.int64)
        text_stats["title"] = TextFieldStats(doc_count=ndocs,
                                             sum_dl=8 * ndocs)
    seg = Segment(
        name=f"bench{shard}", ndocs=ndocs, postings=postings,
        numeric_cols={"price": nc}, keyword_cols={"status": kw},
        geo_cols={}, doc_lens=doc_lens, text_stats=text_stats,
        ids=[], sources=[])
    seg.ids = _LazyIds(ndocs, id_base)
    seg.sources = _LazySources(ndocs)
    seg.id2doc = {}
    seg.live = np.ones(ndocs, dtype=bool)
    from opensearch_tpu.index.segment import (CODEC_V2,
                                              default_codec_version)
    if default_codec_version() >= CODEC_V2:
        # codec v2: quantized eager impacts + block-max sidecars, exactly
        # like the refresh path builds them (direct CSR corpora opt in
        # through the same Segment.build_impacts the engine uses)
        seg.build_impacts()
    if create:
        # replicas 0: this wrapper hot-swaps the PRIMARY engine's segment
        # list under an already-created index; a replica read copy would
        # keep serving its pre-swap (empty) checkpoint and the round-robin
        # would alternate real and empty pages (observed as the
        # "0-hit every other call" bench artifact)
        client.indices.create("bench", {
            "settings": {"number_of_replicas": 0},
            "mappings": {"properties": {
                "body": {"type": "text"}, "title": {"type": "text"},
                "status": {"type": "keyword"},
                "price": {"type": "integer"}}}})
    eng = client.node.indices["bench"].shards[shard]
    eng.segments = [seg]
    client.node.indices["bench"].generation += 1
    return seg


def measure_impacts(client, seg, bodies, log, time_share=90.0):
    """Codec v1 vs v2 A/B on the SAME corpus and query set — the BENCH
    `extra.impacts` stamp (ISSUE 8 acceptance): per codec, a 32-thread
    closed loop through the product search path measuring qps, per-query
    actual bytes gathered (obs/query_cost histogram deltas) and resident
    postings bytes (device arrays + ledger tenants), plus the codec-v2
    device block-skip rate. Cells alternate v1/v2/v2/v1 (each codec once
    early + once late, same box-noise discipline as the recorder gate)
    and the stamp carries the paired best-of-reps ratio."""
    import threading

    from opensearch_tpu.obs.hbm_ledger import LEDGER
    from opensearch_tpu.search import impactpath
    from opensearch_tpu.utils.metrics import METRICS

    bodies = [dict(b) for b in bodies]
    for b in bodies:
        b.pop("_bench", None)

    def cost_hist():
        h = METRICS.snapshot()["histograms"].get(
            "cost.bytes_per_query") or {}
        return h.get("count", 0), h.get("sum_ms", 0.0)

    def postings_resident_bytes():
        post = seg.device_arrays()["postings"]
        return int(sum(int(a.nbytes) for f in post.values()
                       for a in f.values()))

    def closed_loop(nthreads=32):
        queue = list(range(len(bodies)))
        lock = threading.Lock()
        errs = []

        def worker():
            while True:
                with lock:
                    if not queue:
                        return
                    i = queue.pop()
                try:
                    client.search("bench", bodies[i])
                except Exception as e:          # noqa: BLE001
                    errs.append(str(e))
        t0 = time.time()
        ts = [threading.Thread(target=worker) for _ in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs, errs[0]
        return len(bodies) / (time.time() - t0)

    def set_codec(version):
        if version == 1:
            seg.drop_impacts()
        else:
            seg.build_impacts()
            seg.drop_device()

    def tag_bodies(tag):
        # unique per-cell tags: the A/B must measure the serving path,
        # not the request cache (identical bodies would all hit it)
        for i, b in enumerate(bodies):
            b["_bench"] = f"{tag}-{i}"

    cells = {"v1": [], "v2": []}
    details = {}
    t_start = time.time()
    for rep, label in enumerate(("v1", "v2", "v2", "v1")):
        set_codec(1 if label == "v1" else 2)
        ip0 = impactpath.stats()
        tag_bodies(f"impw{label}{rep}")
        closed_loop(nthreads=8)        # warm: compiles + residency
        c0, s0 = cost_hist()
        tag_bodies(f"impm{label}{rep}")
        qps = closed_loop()
        c1, s1 = cost_hist()
        cells[label].append(qps)
        if label not in details:
            resident = postings_resident_bytes()
            tenants = LEDGER.snapshot()["tenants"]
            ip1 = impactpath.stats()
            blk_tot = ip1["blocks_total"] - ip0["blocks_total"]
            blk_skip = ip1["blocks_skipped"] - ip0["blocks_skipped"]
            details[label] = {
                "postings_resident_bytes": resident,
                "ledger_impact_postings_bytes": tenants.get(
                    "impact_postings", {}).get("bytes", 0),
                "ledger_block_max_bytes": tenants.get(
                    "block_max", {}).get("bytes", 0),
                "mean_bytes_per_query": round(
                    (s1 - s0) / max(c1 - c0, 1), 1),
                "block_skip_rate": (round(blk_skip / blk_tot, 4)
                                    if blk_tot else 0.0),
                "impact_served": ip1["served"] - ip0["served"],
                "impact_escalated": (ip1["escalated"]
                                     - ip0["escalated"]),
            }
        if time.time() - t_start > time_share:
            log("impacts A/B: budget-capped reps")
            break
    set_codec(2)                        # leave the index on the default
    ip = seg.postings["body"].impact
    out = {
        "codec_mix": {"v2": 1},
        "impact_bits": ip.bits,
        "impact_plane_bytes": int(ip.q.nbytes),
        "block_sidecar_bytes": int(ip.block_max.nbytes
                                   + ip.block_off.nbytes
                                   + ip.block_starts.nbytes),
        "f32_tf_equivalent_bytes": int(seg.postings["body"].tfs.nbytes),
        "v1": dict(details.get("v1", {}),
                   qps_32t=round(max(cells["v1"]), 1) if cells["v1"]
                   else None,
                   qps_reps=[round(q, 1) for q in cells["v1"]]),
        "v2": dict(details.get("v2", {}),
                   qps_32t=round(max(cells["v2"]), 1) if cells["v2"]
                   else None,
                   qps_reps=[round(q, 1) for q in cells["v2"]]),
    }
    if cells["v1"] and cells["v2"]:
        ratio = max(cells["v2"]) / max(max(cells["v1"]), 1e-9)
        d1, d2 = details.get("v1", {}), details.get("v2", {})
        out["qps_ratio_v2_over_v1"] = round(ratio, 4)
        out["gates"] = {
            "bytes_per_query_down": (d2.get("mean_bytes_per_query", 0)
                                     < d1.get("mean_bytes_per_query",
                                              float("inf"))),
            # resident comparison: the v2 figure already includes the
            # device impact planes (they live in the postings arrays)
            "postings_resident_down": (
                d2.get("postings_resident_bytes", 0)
                < d1.get("postings_resident_bytes", float("inf"))),
            "qps_no_worse": ratio >= 0.98,
            "block_skip_nonzero": d2.get("block_skip_rate", 0.0) > 0.0,
        }
    return out


def measure_hybrid(log, ndocs: int = 30_000, nq: int = 256,
                   nthreads: int = 32, seed: int = 12):
    """Hybrid/vector serving bench (ISSUE 15) — the BENCH
    `extra.hybrid` stamp. Self-contained corpus (text + rank_features
    with `index_impacts` + dense vectors) on a mesh-less node; a zipf
    mix over hybrid (rrf + linear), neural_sparse and knn shapes runs a
    closed loop for qps/p99, then the learned-sparse A/B pits the
    codec-v2 FEATURE impact plane (block-max prune -> integer gather ->
    certify-or-escalate) against the exact `sparse_dot` XLA program:
    equal top-10 pages, block-skip rate, and actual gathered
    bytes/query (obs/query_cost histogram deltas). Gates:
    block_skip_rate > 0.3 AND bytes/query down >= 2x at identical
    pages."""
    import random as _random
    import threading

    from opensearch_tpu.cluster.node import Node
    from opensearch_tpu.rest.client import RestClient
    from opensearch_tpu.search import fusion, impactpath
    from opensearch_tpu.utils.metrics import METRICS

    rng = _random.Random(seed)
    t0 = time.time()
    c = RestClient(node=Node(mesh_service=False))
    c.indices.create("hybench", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {
            "body": {"type": "text"},
            "emb": {"type": "rank_features", "index_impacts": True},
            "vec": {"type": "dense_vector", "dims": 32,
                    "similarity": "cosine"}}}})
    vocab = [f"w{i}" for i in range(2000)]
    feats = [f"t{i}" for i in range(300)]
    fw = [1.0 / (r ** 1.1) for r in range(1, len(feats) + 1)]
    bulk = []
    for i in range(ndocs):
        # SPLADE-shaped doc features: zipf token popularity, heavy-tail
        # weights — the distribution the block-max prune feeds on
        toks = rng.choices(feats, weights=fw, k=6)
        bulk.append({"index": {"_index": "hybench", "_id": str(i)}})
        bulk.append({
            "body": " ".join(rng.choices(vocab, k=8)),
            "emb": {t: round(rng.expovariate(1.0) + 0.05, 3)
                    for t in toks},
            "vec": [rng.gauss(0.0, 1.0) for _ in range(32)]})
        if len(bulk) >= 4000:
            c.bulk(bulk)
            bulk = []
    if bulk:
        c.bulk(bulk)
    c.indices.refresh("hybench")
    build_s = time.time() - t0

    def qtokens():
        # learned-sparse query, SPLADE-shaped: a few RARE discriminative
        # head tokens carry the weight mass, a popular low-weight
        # expansion tail carries the posting mass — exactly the profile
        # where the MaxScore-style per-term cut prices whole stopword-ish
        # rows out of the gather (the tail rows are the bytes)
        head = rng.sample(feats[120:], 3)
        tail = list(dict.fromkeys(
            rng.choices(feats[:100], weights=fw[:100], k=8)))
        toks = {}
        for r, t in enumerate(head):
            toks[t] = round(3.0 / (r + 1), 3)
        for r, t in enumerate(tail):
            toks.setdefault(t, round(0.25 / (1 + r) + 0.02, 3))
        return toks

    def qvec():
        return [round(rng.gauss(0.0, 1.0), 4) for _ in range(32)]

    def qtext(n=3):
        return " ".join(rng.choices(vocab[:400], k=n))

    def hybrid_body(method):
        return {"query": {"hybrid": {"queries": [
            {"match": {"body": qtext()}},
            {"neural_sparse": {"emb": {"query_tokens": qtokens()}}},
            {"knn": {"vec": {"vector": qvec(), "k": 20}}}],
            "fusion": {"method": method, "rank_constant": 60,
                       "window_size": 50}}}, "size": 10}

    shapes = [lambda: hybrid_body("rrf"),
              lambda: {"query": {"neural_sparse": {"emb": {
                  "query_tokens": qtokens()}}}, "size": 10},
              lambda: {"query": {"knn": {"vec": {
                  "vector": qvec(), "k": 10}}}, "size": 10},
              lambda: hybrid_body("linear"),
              ]
    zw = [1.0 / (r ** 1.1) for r in range(1, len(shapes) + 1)]
    mix = [shapes[i]() for i in
           rng.choices(range(len(shapes)), weights=zw, k=nq)]
    n_hybrid = sum(1 for b in mix if "hybrid" in b["query"])

    def closed_loop(bodies, nthreads=nthreads):
        queue = list(range(len(bodies)))
        lock = threading.Lock()
        lats = []
        errs = []

        def worker():
            while True:
                with lock:
                    if not queue:
                        return
                    i = queue.pop()
                t1 = time.time()
                try:
                    c.search("hybench", bodies[i])
                except Exception as e:          # noqa: BLE001
                    errs.append(str(e))
                    return
                with lock:
                    lats.append((time.time() - t1) * 1000.0)
        t1 = time.time()
        ts = [threading.Thread(target=worker) for _ in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs, errs[0]
        wall = time.time() - t1
        return len(bodies) / wall, lats

    log(f"hybrid bench: {ndocs} docs built in {build_s:.1f}s, "
        f"{nq}-query zipf mix ({n_hybrid} hybrid)")
    closed_loop(mix[: max(nq // 4, 16)], nthreads=8)      # warm
    fstats0 = fusion.stats()
    qps, lats = closed_loop(mix)
    fstats1 = fusion.stats()

    # ---- learned-sparse A/B: impact plane vs exact sparse_dot ----
    sparse_bodies = [{"query": {"neural_sparse": {"emb": {
        "query_tokens": qtokens()}}}, "size": 10, "_bench": f"sp{i}"}
        for i in range(min(nq, 128))]

    def cost_hist():
        h = METRICS.snapshot()["histograms"].get(
            "cost.bytes_per_query") or {}
        return h.get("count", 0), h.get("sum_ms", 0.0)

    arms = {}
    pages = {}
    for arm in ("impact", "sparse_dot", "impact"):
        # alternating arms (impact measured twice, best-of kept): the
        # same box-noise discipline as the codec A/B
        if arm == "sparse_dot":
            os.environ["OPENSEARCH_TPU_NO_IMPACT"] = "1"
        else:
            os.environ.pop("OPENSEARCH_TPU_NO_IMPACT", None)
        for i, b in enumerate(sparse_bodies):
            b["_bench"] = f"{arm}{len(arms)}-{i}"
        ip0 = dict(impactpath.STATS)
        c0, s0 = cost_hist()
        sqps, slats = closed_loop(sparse_bodies)
        c1, s1 = cost_hist()
        ip1 = dict(impactpath.STATS)
        blk_t = ip1["blocks_total"] - ip0["blocks_total"]
        cell = {
            "qps": round(sqps, 1),
            "p99_ms": round(pct(slats, 99), 2),
            "mean_bytes_per_query": round((s1 - s0) / max(c1 - c0, 1),
                                          1),
            "block_skip_rate": (round(
                (ip1["blocks_skipped"] - ip0["blocks_skipped"]) / blk_t,
                4) if blk_t else 0.0),
            "served": ip1["served"] - ip0["served"],
            "escalated": ip1["escalated"] - ip0["escalated"],
        }
        prev = arms.get(arm)
        if prev is None or cell["qps"] > prev["qps"]:
            cell_keep = cell
        else:
            cell_keep = prev
        arms[arm] = cell_keep
        if arm not in pages:
            # equal-results oracle: identical top-10 pages across arms
            pages[arm] = [
                tuple(h["_id"] for h in
                      c.search("hybench",
                               {**b, "_bench": f"pg-{arm}-{i}"}
                               )["hits"]["hits"])
                for i, b in enumerate(sparse_bodies[:32])]
    os.environ.pop("OPENSEARCH_TPU_NO_IMPACT", None)
    equal_top10 = pages["impact"] == pages["sparse_dot"]
    bytes_ratio = (arms["sparse_dot"]["mean_bytes_per_query"]
                   / max(arms["impact"]["mean_bytes_per_query"], 1e-9))
    out = {
        "ndocs": ndocs, "nq": nq, "threads": nthreads,
        "corpus_build_s": round(build_s, 1),
        "mix": {"shapes": ["hybrid_rrf", "neural_sparse", "knn",
                           "hybrid_linear"], "zipf_s": 1.1,
                "hybrid_queries": n_hybrid},
        "fused_qps": round(qps, 1),
        "lat_ms_p50": round(pct(lats, 50), 2),
        "lat_ms_p99": round(pct(lats, 99), 2),
        "hybrid_searches": (fstats1["searches"] - fstats0["searches"]),
        "sparse_impact": arms["impact"],
        "sparse_dot_baseline": arms["sparse_dot"],
        "bytes_ratio_dot_over_impact": round(bytes_ratio, 2),
        "equal_top10_across_arms": bool(equal_top10),
        "gates": {
            "block_skip_gt_0p3":
                arms["impact"]["block_skip_rate"] > 0.3,
            "bytes_per_query_2x_down": bytes_ratio >= 2.0,
            "equal_top10": bool(equal_top10),
        },
    }
    # parallel-legs A/B (ISSUE 17): failure is a FAILED gate, never a
    # silently-missing one
    try:
        out["legs_ab"] = measure_legs_ab(log)
        for k, v in out["legs_ab"]["gates"].items():
            out["gates"][f"legs_{k}" if not k.startswith("legs_")
                         else k] = v
    except Exception as e:                               # noqa: BLE001
        out["legs_ab"] = {"status":
                          f"failed: {type(e).__name__}: {e}"}
        out["gates"]["legs_p50_le_0p6x_serial"] = False
        out["gates"]["legs_pages_byte_identical"] = False
    return out


def measure_legs_ab(log, ndocs: int = 4000, nq: int = 32,
                    seed: int = 13, member_delay_ms: float = 10.0):
    """Parallel-legs A/B (ISSUE 17) — the `extra.hybrid.legs_ab` cell.

    The legs primitive turns the two serving hot loops from SUM-shaped
    to MAX-shaped latency: hybrid sub-retrievals and the cross-node
    scatter fan out concurrently. The topology is the one the feature
    exists for: a 3-PROCESS cluster (in-process coordinator + two
    `tests/_dist_child.py` members) where every remote leg is a socket
    wait on another process's CPU.

    Member service latency is MODELED, and the cell says so: the
    product's own chaos `delay` rule holds every member RPC
    `member_delay_ms` (a LAN/cross-AZ-shaped round trip; at bench-cell
    corpus sizes real member service time is microseconds, so with 0 ms
    modeled latency the measurement degenerates into a benchmark of the
    coordinator's GIL-bound JSON marshalling — reported anyway as
    `no_delay` for honesty). Serial pays the delay once per RPC
    (~9 member RPCs per sub-retrieval), legs pay it once per join
    layer. A ≥3-sub hybrid mix runs a single-caller closed loop
    (latency regime, not saturation) with `OPENSEARCH_TPU_LEGS` flipped
    per arm, alternating arms best-of-2 against box noise. Gates:
    fused-mix p50 with legs ≤ 0.6× serial, and the first 16 result
    pages byte-identical across arms (parity pass runs chaos-free)."""
    import random as _random
    import subprocess

    from opensearch_tpu.cluster import faults
    from opensearch_tpu.cluster.distnode import DistClusterNode
    from opensearch_tpu.utils.metrics import METRICS

    rng = _random.Random(seed)
    t0 = time.time()
    coord = DistClusterNode("bl0")
    children = []
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    try:
        for name in ("bl1", "bl2"):
            p = subprocess.Popen(
                [sys.executable,
                 os.path.join(_REPO, "tests", "_dist_child.py"),
                 coord.addr, name],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, env=env, cwd=_REPO)
            children.append(p)
        for p in children:
            line = p.stdout.readline()
            assert line.startswith("READY"), f"child failed: {line!r}"
        deadline = time.time() + 30
        while len(coord.members) < 3 and time.time() < deadline:
            time.sleep(0.05)
        assert len(coord.members) == 3, coord.members

        feats = [f"t{i}" for i in range(300)]
        fw = [1.0 / (r ** 1.1) for r in range(1, len(feats) + 1)]
        vocab = [f"w{i}" for i in range(800)]
        coord.create_index("legsb", {
            "settings": {"number_of_shards": 6,
                         "number_of_node_replicas": 0},
            "mappings": {"properties": {
                "body": {"type": "text"},
                "emb": {"type": "rank_features", "index_impacts": True},
                "vec": {"type": "dense_vector", "dims": 32,
                        "similarity": "cosine"}}}})
        for i in range(ndocs):
            coord.index_doc("legsb", {
                "body": " ".join(rng.choices(vocab, k=8)),
                "emb": {t: round(rng.expovariate(1.0) + 0.05, 3)
                        for t in rng.choices(feats, weights=fw, k=6)},
                "vec": [rng.gauss(0.0, 1.0) for _ in range(32)]},
                id=str(i))
        coord.refresh("legsb")
        build_s = time.time() - t0

        def qtokens():
            head = rng.sample(feats[120:], 3)
            tail = list(dict.fromkeys(
                rng.choices(feats[:100], weights=fw[:100], k=8)))
            toks = {}
            for r, t in enumerate(head):
                toks[t] = round(3.0 / (r + 1), 3)
            for r, t in enumerate(tail):
                toks.setdefault(t, round(0.25 / (1 + r) + 0.02, 3))
            return toks

        bodies = [{"query": {"hybrid": {"queries": [
            {"match": {"body": " ".join(rng.choices(vocab[:400], k=3))}},
            {"neural_sparse": {"emb": {"query_tokens": qtokens()}}},
            {"knn": {"vec": {"vector":
                             [round(rng.gauss(0.0, 1.0), 4)
                              for _ in range(32)], "k": 20}}}],
            "fusion": {"method": "rrf", "rank_constant": 60,
                       "window_size": 50}}}, "size": 10}
            for _ in range(nq)]

        METRICS.histogram("legs.warm").record(1.0)   # DDSketch warmup

        def page(resp):
            return json.dumps(
                [(h["_id"], h["_score"])
                 for h in resp["hits"]["hits"]], sort_keys=True)

        def run_arm(flag):
            os.environ["OPENSEARCH_TPU_LEGS"] = flag
            lats = []
            for b in bodies:
                t1 = time.perf_counter()
                coord.search("legsb", b)
                lats.append((time.perf_counter() - t1) * 1000.0)
            return lats

        # warm every process's compiled programs on both arms
        for flag in ("1", "0"):
            os.environ["OPENSEARCH_TPU_LEGS"] = flag
            for b in bodies[:12]:
                coord.search("legsb", b)

        def measure(delay_ms):
            if delay_ms > 0:
                faults.install(faults.ChaosSchedule(seed=0).add(
                    "rpc.send", "delay", after=1,
                    delay_s=delay_ms / 1000.0))
            try:
                arms = {"1": None, "0": None}
                for flag in ("0", "1", "0", "1"):   # alternate, best-of-2
                    lats = run_arm(flag)
                    p50 = pct(lats, 50)
                    if arms[flag] is None or p50 < arms[flag]["p50_ms"]:
                        arms[flag] = {"p50_ms": round(p50, 2),
                                      "p99_ms": round(pct(lats, 99), 2)}
            finally:
                faults.uninstall()
            ratio = arms["1"]["p50_ms"] / max(arms["0"]["p50_ms"], 1e-9)
            return {"legs_on": arms["1"], "serial": arms["0"],
                    "p50_ratio_legs_over_serial": round(ratio, 3)}

        delayed = measure(member_delay_ms)
        no_delay = measure(0.0)
        pages = {}
        for flag in ("1", "0"):
            os.environ["OPENSEARCH_TPU_LEGS"] = flag
            pages[flag] = [page(coord.search("legsb", b))
                           for b in bodies[:16]]
        os.environ.pop("OPENSEARCH_TPU_LEGS", None)
        ratio = delayed["p50_ratio_legs_over_serial"]
        out = {
            "topology": "3-process (coordinator + 2 members), 6 shards",
            "ndocs": ndocs, "nq": nq, "subs_per_query": 3,
            "member_delay_ms": member_delay_ms,
            "corpus_build_s": round(build_s, 1),
            **delayed,
            "no_delay": no_delay,
            "pages_byte_identical": pages["1"] == pages["0"],
            "gates": {
                "legs_p50_le_0p6x_serial": ratio <= 0.6,
                "pages_byte_identical": pages["1"] == pages["0"],
            },
        }
        log(f"legs A/B ({member_delay_ms}ms member delay): p50 "
            f"{delayed['legs_on']['p50_ms']}ms (legs) vs "
            f"{delayed['serial']['p50_ms']}ms (serial), ratio "
            f"{ratio:.3f}; no-delay ratio "
            f"{no_delay['p50_ratio_legs_over_serial']:.3f}; pages "
            f"identical={out['pages_byte_identical']}")
        return out
    finally:
        for p in children:
            p.kill()
        for p in children:
            p.wait(timeout=10)
        coord.stop()


def pick_queries_equal_idf(df_per_term, nq: int, nterms: int = 4,
                           seed: int = 11, band_tol: float = 0.10,
                           pool=None):
    """Equal-idf multi-term queries — the known block-max pruning gap
    (ROADMAP item 2): every term of a query has df within `band_tol` of
    the others, so no single term's upper bound dominates and per-term
    MaxScore-style pruning has nothing skewed to grab onto. `pool`
    overrides the candidate term ids (config6 passes the topical band);
    default is the mid-frequency band (selective enough to have real
    top-k competition, frequent enough to span many 128-posting
    blocks)."""
    rng = np.random.default_rng(seed)
    if pool is None:
        order = np.argsort(-df_per_term)
        pool = order[200: 40_000]
        pool = pool[df_per_term[pool] >= 256]   # >= 2 blocks per term
    pool = np.asarray(pool)
    dfs = df_per_term[pool]
    out = np.zeros((nq, nterms), np.int64)
    for i in range(nq):
        anchor = int(rng.integers(0, len(pool)))
        lo_df = dfs[anchor] * (1.0 - band_tol)
        hi_df = dfs[anchor] * (1.0 + band_tol)
        band = np.nonzero((dfs >= lo_df) & (dfs <= hi_df))[0]
        if len(band) < nterms:
            band = np.arange(max(anchor - 2 * nterms, 0),
                             min(anchor + 2 * nterms, len(pool)))
        out[i] = pool[rng.choice(band, size=nterms, replace=False)]
    return out


def measure_reorder(client, seg, df_per_term, vocab_strs, log,
                    nq: int = 256, time_share: float = 600.0,
                    single_pool=None, multi_pool=None, passes: int = 3):
    """BP-reorder A/B on the SAME corpus and query sets — the BENCH
    `extra.reorder` stamp (ISSUE 11 acceptance). Two arms (arrival order
    vs impact-clustered BP order, index/reorder.py) x two query-shape
    mixes (single-term — the regime codec v2 already prunes — and
    equal-idf multi-term — the known gap). Per cell: qps + per-query
    p50/p99 latency through the product search path, device block-skip
    rate, escalation count, and actual bytes gathered per query."""
    import threading

    from opensearch_tpu.index import reorder as R
    from opensearch_tpu.search import impactpath
    from opensearch_tpu.utils.metrics import METRICS

    t_start = time.time()
    log("reorder: computing BP permutation")
    t0 = time.time()
    perm = R.compute_permutation(seg)
    assert perm is not None, "segment ineligible for reorder"
    seg_bp = R.apply_permutation(seg, perm)
    reorder_s = time.time() - t0
    log(f"reorder: permutation + apply in {reorder_s:.1f}s")

    eng = client.node.indices["bench"].shards[0]

    rng = np.random.default_rng(13)
    if single_pool is None:
        order = np.argsort(-df_per_term)
        single_pool = order[200: 40_000]
        single_pool = single_pool[df_per_term[single_pool] >= 256]
    singles = rng.choice(np.asarray(single_pool), size=nq, replace=True)
    multis = pick_queries_equal_idf(df_per_term, nq, pool=multi_pool)

    def bodies_of(mix, tag):
        out = []
        for i in range(nq):
            if mix == "single":
                text = vocab_strs[int(singles[i])]
            else:
                text = " ".join(vocab_strs[int(t)] for t in multis[i])
            out.append({"query": {"match": {"body": text}}, "size": TOPK,
                        "_bench": f"{tag}-{i}"})
        return out

    def cost_hist():
        h = METRICS.snapshot()["histograms"].get(
            "cost.bytes_per_query") or {}
        return h.get("count", 0), h.get("sum_ms", 0.0)

    # closed-loop concurrency scaled to the host: 32 client threads on a
    # 2-core container measures GIL/scheduler queueing (p99 blows up on
    # BOTH arms), not engine throughput; 4x cores keeps the device
    # saturated without oversubscription pathology
    nthreads_mix = min(32, 4 * (os.cpu_count() or 8))

    def closed_loop(bodies, nthreads=None):
        nthreads = nthreads_mix if nthreads is None else nthreads
        queue = list(range(len(bodies)))
        lock = threading.Lock()
        errs = []
        lats = []

        def worker():
            while True:
                with lock:
                    if not queue:
                        return
                    i = queue.pop()
                t1 = time.perf_counter()
                try:
                    client.search("bench", bodies[i])
                except Exception as e:          # noqa: BLE001
                    errs.append(str(e))
                    return
                dt = (time.perf_counter() - t1) * 1e3
                with lock:
                    lats.append(dt)
        t0 = time.time()
        ts = [threading.Thread(target=worker) for _ in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs, errs[0]
        wall = time.time() - t0
        return len(bodies) / wall, lats

    out = {"reorder_wall_s": round(reorder_s, 1),
           "ndocs": int(seg.ndocs), "nthreads": nthreads_mix,
           "arms": {}}
    for arm, s in (("orig", seg), ("bp", seg_bp)):
        other = seg_bp if s is seg else seg
        other.drop_device()
        eng.segments = [s]
        client.node.indices["bench"].generation += 1
        arm_out = {}
        for mix in ("single", "multi_eq"):
            bodies = bodies_of(mix, f"ro-{arm}-{mix}-w")
            closed_loop(bodies, nthreads=8)      # warm: compiles+residency
            ip0 = impactpath.stats()
            c0, s0 = cost_hist()
            # one 5s closed loop per cell is noise-dominated on a small
            # host: sample `passes` loops and report the median qps
            qps_samples = []
            lats = []
            for p in range(passes):
                bodies = bodies_of(mix, f"ro-{arm}-{mix}-m{p}")
                q, ls = closed_loop(bodies)
                qps_samples.append(q)
                lats.extend(ls)
            qps = float(np.median(qps_samples))
            ip1 = impactpath.stats()
            c1, s1 = cost_hist()
            blk_tot = ip1["blocks_total"] - ip0["blocks_total"]
            blk_skip = ip1["blocks_skipped"] - ip0["blocks_skipped"]
            pt = ip1["postings_total"] - ip0["postings_total"]
            ps = ip1["postings_skipped"] - ip0["postings_skipped"]
            arm_out[mix] = {
                "qps": round(qps, 1),
                "qps_samples": [round(q, 1) for q in qps_samples],
                "lat_ms_p50": round(pct(lats, 50), 2),
                "lat_ms_p99": round(pct(lats, 99), 2),
                "block_skip_rate": (round(blk_skip / blk_tot, 4)
                                    if blk_tot else 0.0),
                "posting_skip_rate": (round(ps / pt, 4) if pt else 0.0),
                "impact_served": ip1["served"] - ip0["served"],
                "escalated": ip1["escalated"] - ip0["escalated"],
                "mean_bytes_per_query": round((s1 - s0)
                                              / max(c1 - c0, 1), 1),
            }
            log(f"reorder[{arm}/{mix}]: qps={arm_out[mix]['qps']} "
                f"skip={arm_out[mix]['block_skip_rate']} "
                f"esc={arm_out[mix]['escalated']}")
            if time.time() - t_start > time_share:
                log("reorder: budget-capped")
                break
        out["arms"][arm] = arm_out
    eng.segments = [seg_bp]          # leave the index on the BP arm
    client.node.indices["bench"].generation += 1
    a, b = out["arms"].get("orig", {}), out["arms"].get("bp", {})
    if "multi_eq" in a and "multi_eq" in b:
        out["gates"] = {
            "multi_term_skip_up": (b["multi_eq"]["block_skip_rate"]
                                   > a["multi_eq"]["block_skip_rate"]),
            "multi_term_qps_up": (b["multi_eq"]["qps"]
                                  > a["multi_eq"]["qps"]),
            "zero_escalations": (b["multi_eq"]["escalated"] == 0
                                 and b["single"]["escalated"] == 0),
        }
    return out


def pick_queries(df_per_term, nq: int, seed: int = 1):
    """2-term queries from mid-frequency terms (selective, MS-MARCO-like)."""
    rng = np.random.default_rng(seed)
    order = np.argsort(-df_per_term)
    lo, hi = 100, 20_000
    pool = order[lo:hi]
    pool = pool[df_per_term[pool] > 0]
    return rng.choice(pool, size=(nq, 3), replace=True).astype(np.int32)


def pick_queries_real(df_per_term, nq: int, nterms: int = 6, seed: int = 9):
    """Realistic-shape queries: ~6 terms sampled proportional to corpus
    token mass — NO df-rank floor, so stopword-class terms appear with
    their natural frequency (real MS MARCO queries average ~6 terms
    including frequent ones). Impact-head pruning is what keeps these
    on-kernel at fixed cost."""
    rng = np.random.default_rng(seed)
    vocab = len(df_per_term)
    out = np.zeros((nq, nterms), np.int32)
    for qi in range(nq):
        terms = rng.zipf(1.15, nterms * 3).astype(np.int64)
        terms = np.where(terms > vocab,
                         rng.integers(1, vocab, nterms * 3), terms) - 1
        terms = terms[df_per_term[terms] > 0]
        uniq = list(dict.fromkeys(terms.tolist()))[:nterms]
        while len(uniq) < nterms:      # top up with any in-corpus term
            t = int(rng.integers(0, vocab))
            if df_per_term[t] > 0 and t not in uniq:
                uniq.append(t)
        out[qi] = uniq
    return out


def pct(samples, p):
    return float(np.percentile(np.asarray(samples), p))


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main():
    # installed here, not at import: the builders below are imported by
    # chip_smoke.py and the scripts, whose last output line is their own
    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)
    device = require_tpu()
    from opensearch_tpu.utils.compile_cache import place_compile_cache
    log(f"device {device}; compilation cache at {place_compile_cache()}")
    if os.environ.get("BENCH_HYBRID"):
        # standalone hybrid/vector bench (ISSUE 15): BENCH_HYBRID=1
        # python bench.py — emits the `extra.hybrid` measure_hybrid
        # block as its own BENCH document (the traffic-harness pattern)
        out = measure_hybrid(
            log,
            ndocs=int(os.environ.get("BENCH_HYBRID_NDOCS", 30_000)),
            nq=int(os.environ.get("BENCH_QUERIES", 256)))
        _PARTIAL.update({"metric": "hybrid_fused_qps",
                         "value": out["fused_qps"],
                         "unit": "queries/sec"})
        _PARTIAL["extra"] = {"status": "ok", "hybrid": out}
        _emit_partial("ok")
        print(json.dumps(_PARTIAL))
        return

    ndocs = int(os.environ.get("BENCH_NDOCS", 8_800_000))
    nq = int(os.environ.get("BENCH_QUERIES", 2048))
    budget_s = float(os.environ.get("BENCH_BUDGET_S", 540))
    cache_ok = os.environ.get("BENCH_CACHE", "1") not in ("0", "")
    bench_start = time.time()

    def remaining() -> float:
        return budget_s - (time.time() - bench_start)

    t0 = time.time()
    starts, doc_ids, tfs, dl, df_per_term = _cached(
        f"body_{ndocs}", lambda: build_corpus(ndocs), cache_ok)
    queries = pick_queries(df_per_term, nq)
    queries_real = pick_queries_real(df_per_term, min(nq, 1024))
    (tstarts, tdoc_ids, ttfs, tpos_starts, tpositions,
     pair_first, pair_second, pair_counts) = _cached(
        f"title_{ndocs}", lambda: build_title_corpus(ndocs), cache_ok)
    rng = np.random.default_rng(3)
    status_ord = rng.integers(0, 3, ndocs).astype(np.int32)
    price = rng.integers(0, 1000, ndocs).astype(np.int64)
    avgdl = float(dl.sum()) / ndocs
    idf = np.log1p((float(ndocs) - df_per_term + 0.5)
                   / (df_per_term + 0.5)).astype(np.float32)
    build_s = time.time() - t0

    # fixed guardrail filters (like production status/price guards; a cache-
    # busting random filter per query would thrash any engine's filter cache)
    f_pub = status_ord == 2          # status:published (~1/3)
    f_pubprice = f_pub & (price >= 250) & (price < 750)
    f_draft = status_ord == 1
    filters_np = {"pub": f_pub, "pubprice": f_pubprice, "draft": f_draft}
    filters_dsl = {
        "pub": [{"term": {"status": "published"}}],
        "pubprice": [{"term": {"status": "published"}},
                     {"range": {"price": {"gte": 250, "lt": 750}}}],
        "draft": [{"term": {"status": "draft"}}],
    }

    # ------------- CPU baseline: C++ MaxScore/conjunction -------------
    from opensearch_tpu import native
    assert native.available(), "native baseline unavailable"
    kdoc = (K1 * (1.0 - B + B * dl.astype(np.float32) / np.float32(avgdl))
            ).astype(np.float32)
    ub = native.term_upper_bounds(starts, doc_ids, tfs, kdoc, idf)
    fmasks_u8 = {k: v.astype(np.uint8) for k, v in filters_np.items()}

    def cpu_match(q, msm=1, filt=None):
        return native.maxscore_topk(starts, doc_ids, tfs, kdoc, idf, ub,
                                    np.asarray(q, np.int32), msm, TOPK, filt)

    # PINNED baseline protocol (r4 verdict: the honest baseline swung 5x
    # between rounds because one cold pass over mmap'd .bench_cache arrays
    # pays disk page faults that an in-RAM build does not). Pin it:
    #   1. materialize the posting arrays in RAM (the device path gets the
    #      corpus resident in HBM; the CPU scorer gets it resident in DRAM),
    #   2. one warm pass over the FIXED 256-query set,
    #   3. >=3 timed passes; report the MEDIAN qps + min/max spread.
    starts = np.ascontiguousarray(starts)
    doc_ids = np.ascontiguousarray(doc_ids)
    tfs = np.ascontiguousarray(tfs)
    ncpu = min(nq, 256)
    BASE_REPS = 3

    def timed_passes(fn, n, reps=BASE_REPS):
        """warm + reps timed passes -> (results, median_qps, spread)."""
        res = fn(n)                      # warm (page-in, branch predictors)
        qps = []
        for _ in range(reps):
            t0 = time.time()
            res = fn(n)
            qps.append(n / (time.time() - t0))
        return res, float(np.median(qps)), \
            {"min": round(min(qps), 1), "max": round(max(qps), 1),
             "reps": reps}

    cpu1, cpu1_qps, cpu1_spread = timed_passes(
        lambda n: [cpu_match(q[:2]) for q in queries[:n]], ncpu)

    # config 2 shapes: i%3==0 filtered OR, ==1 AND conjunction, ==2 filtered
    # 3-term msm=2
    def bool_shape(i, q):
        if i % 3 == 0:
            return q[:2], 1, "pub"
        if i % 3 == 1:
            return q[:2], 2, "pubprice"
        return q[:3], 2, "draft"

    def _cpu2_pass(n):
        out = []
        for i in range(n):
            qt, msm, fk = bool_shape(i, queries[i])
            out.append(cpu_match(qt, msm, fmasks_u8[fk]))
        return out

    cpu2, cpu2_qps, cpu2_spread = timed_passes(_cpu2_pass, ncpu)

    extra = {
        "ndocs": ndocs, "postings": int(len(doc_ids)),
        "corpus_build_s": round(build_s, 1),
        "baseline": "C++ MaxScore/conjunction skipping scorer (native/), "
                    "single core; published CPU-Lucene band 50-150 q/s/core",
        "corpus_provenance": "synthetic MS-MARCO-shaped (zero-egress image,"
                             " no real datasets available): distribution "
                             "match documented in docs/BENCH_CORPUS.md",
        "cpu_maxscore_match_qps": round(cpu1_qps, 1),
        "cpu_maxscore_match_spread": cpu1_spread,
        "cpu_maxscore_bool_qps": round(cpu2_qps, 1),
        "cpu_maxscore_bool_spread": cpu2_spread,
        "baseline_protocol": "pinned: arrays resident in RAM, warm pass, "
                             f"median of {BASE_REPS} passes over the fixed "
                             f"{ncpu}-query set",
        "configs": {},
        "latency": {},
        "path": "RestClient.msearch -> fastpath Pallas kernels",
        "device": device,
    }
    _PARTIAL["extra"] = extra
    _emit_partial("cpu_baseline_done")
    log(f"cpu baselines done: match {cpu1_qps:.0f} q/s, "
        f"bool {cpu2_qps:.0f} q/s")

    # ------------- TPU product path: RestClient.msearch -------------
    from opensearch_tpu.rest.client import RestClient
    from opensearch_tpu.search import fastpath

    vocab_strs = [f"t{i:07d}" for i in range(len(df_per_term))]
    tvocab_strs = [f"p{i:04d}" for i in range(len(tstarts) - 1)]
    client = RestClient()
    make_index(client, (starts, doc_ids, tfs, vocab_strs), dl,
               (tstarts, tdoc_ids, ttfs, tpos_starts, tpositions,
                tvocab_strs), status_ord, price)

    def match_body(i, tag):
        q = queries[i]
        return {"query": {"match": {
            "body": f"{vocab_strs[q[0]]} {vocab_strs[q[1]]}"}},
            "size": TOPK, "_bench": tag}

    def bool_body(i, tag):
        qt, msm, fk = bool_shape(i, queries[i])
        terms = " ".join(vocab_strs[t] for t in qt)
        if msm == len(qt):
            must = {"match": {"body": {"query": terms, "operator": "and"}}}
        elif msm > 1:
            must = {"match": {"body": {"query": terms,
                                       "minimum_should_match": msm}}}
        else:
            must = {"match": {"body": terms}}
        return {"query": {"bool": {"must": [must],
                                   "filter": filters_dsl[fk]}},
                "size": TOPK, "_bench": tag}

    # mid-frequency bigrams (selective phrases, bounded pad-bucket variety)
    rng_p = np.random.default_rng(5)
    pair_order = np.argsort(-pair_counts)
    pair_pool = pair_order[200:1200]
    phrase_pairs = rng_p.choice(pair_pool, size=nq, replace=True)

    def phrase_body(i, tag):
        pi = phrase_pairs[i]
        return {"query": {"match_phrase": {
            "title": f"{tvocab_strs[pair_first[pi]]} "
                     f"{tvocab_strs[pair_second[pi]]}"}},
            "size": TOPK, "_bench": tag}

    stream_stats = {}   # tag -> fastpath STATS delta over the measured reps

    def run_stream(bodies_fn, idxs, tag, reps, require_fast=True,
                   time_share=60.0):
        """msearch the stream up to `reps` times, adaptively dropping reps to
        fit `time_share` seconds; -> (qps, wall_per_rep_ms, resp)"""
        lines = []
        for i in idxs:
            lines.append({"index": "bench"})
            lines.append(bodies_fn(i, f"{tag}{i}"))
        before = dict(fastpath.STATS)
        log(f"{tag}: warmup {len(idxs)} queries")
        t0 = time.time()
        resp = client.msearch(lines)  # warmup rep (compiles + materializes)
        assert all("hits" in r for r in resp["responses"]), resp["responses"][0]
        log(f"{tag}: warmup done in {time.time()-t0:.1f}s")
        done = 0
        wall = 0.0
        for rep in range(reps):
            for j, ln in enumerate(lines):
                if j % 2:
                    ln["_bench"] = f"{tag}r{rep}-{j}"
            t0 = time.time()
            resp = client.msearch(lines)
            wall += time.time() - t0
            done += 1
            # a measured rep exists; stop early when the stream's share (or
            # the whole bench budget) is spent
            if wall + wall / done > time_share or remaining() < wall / done:
                break
        if done < reps:
            log(f"{tag}: budget-capped at {done}/{reps} reps")
        # escalation telemetry per stream: the pruned path is only as good
        # as its escalation rate on real query shapes (surfaced per config
        # in the emitted extra, and in _nodes/stats for production)
        stream_stats[tag] = {k: fastpath.STATS[k] - before[k]
                             for k in fastpath.STATS}
        if require_fast and fastpath.enabled():
            served = (fastpath.STATS["pure_served"]
                      + fastpath.STATS["bool_served"]
                      - before["pure_served"] - before["bool_served"])
            assert served >= (done + 1) * len(idxs), \
                f"{tag}: fastpath fell back ({served} served, " \
                f"{fastpath.STATS['fallback']} fallbacks)"
        return (done * len(idxs)) / wall, wall / done * 1000.0, resp

    # ------------- recall vs the CPU baseline -------------
    # exact CPU score of one doc for an arbitrary term list (tie check)
    def _cpu_rescore(d, terms):
        s = 0.0
        for t in terms:
            a, e = starts[t], starts[t + 1]
            j = np.searchsorted(doc_ids[a:e], d)
            if j < e - a and doc_ids[a + j] == d:
                tf = tfs[a + j]
                s += idf[t] * tf / (tf + kdoc[d])
        return s

    def recall(resp, cpu_results, n, qterms):
        """qterms(i) -> the term-id list of query i (for tie rescoring)."""
        tie_ok, strict = [], []
        for i in range(n):
            hits = [int(h["_id"]) for h in resp["responses"][i]["hits"]["hits"]]
            cdocs, cscores, _ = cpu_results[i]
            cset = set(int(d) for d in cdocs if d >= 0)
            if not cset:
                continue
            kth = min(cscores[j] for j in range(len(cdocs)) if cdocs[j] >= 0)
            # compare only the first |cset| hits so recall stays in [0, 1]
            # even when the CPU baseline found fewer than k docs
            head = hits[: len(cset)]
            good = sum(1 for d in head if d in cset)
            # tie-aware: a hit is also correct if its CPU score ties the kth
            good_tie = sum(
                1 for d in head
                if d in cset or _cpu_rescore(d, qterms(i))
                >= kth - 1e-5 * max(abs(kth), 1.0))
            tie_ok.append(good_tie / max(len(cset), 1))
            strict.append(good / max(len(cset), 1))
        return (float(np.mean(tie_ok)) if tie_ok else 1.0,
                float(np.mean(strict)) if strict else 1.0)

    _emit_partial("index_on_device")
    log("index built on device")
    # warm the filter materialization: two passes over the 3 guardrail
    # filters so hits>=1, then the specialized postings build. The first
    # pass legitimately runs off-kernel (dense first-use filters exceed the
    # list-slot budget), so no require_fast
    run_stream(bool_body, range(3), "fwarm", 1, require_fast=False)
    log("filter warm done")

    # ---- config 1 (match) — the north-star number; budget priority #1
    qps1, wall1, resp1 = run_stream(match_body, range(nq), "m", 5,
                                    time_share=min(90.0, remaining() * 0.35))
    rec1_tie, rec1_strict = recall(resp1, cpu1, ncpu,
                                   lambda i: queries[i][:2])
    extra["configs"]["1_match"] = {
        "qps": round(qps1, 1), "vs_cpu": round(qps1 / cpu1_qps, 2),
        "recall_at_10_vs_cpu": round(rec1_tie, 4),
        "recall_at_10_strict": round(rec1_strict, 4)}
    _PARTIAL["value"] = round(qps1, 2)
    _PARTIAL["vs_baseline"] = round(qps1 / cpu1_qps, 2)
    _emit_partial("config1_done")

    # ---- config 1r: realistic query mix (6 terms, token-mass sampled, no
    # df floor — stopword-class terms included; impact-head pruning keeps
    # them on-kernel)
    def real_body(i, tag):
        terms = " ".join(vocab_strs[t] for t in queries_real[i])
        return {"query": {"match": {"body": terms}}, "size": TOPK,
                "_bench": tag}

    if remaining() > 45:
        before_stats = dict(fastpath.STATS)
        qps1r, _w, resp1r = run_stream(
            real_body, range(len(queries_real)), "r", 3,
            time_share=min(60.0, remaining() * 0.3))
        ds = {k: fastpath.STATS[k] - before_stats[k] for k in fastpath.STATS}
        served = ds["pure_served"] + ds["bool_served"]
        # CPU MaxScore on the SAME realistic 6-term stream + recall
        # (pinned protocol: warm + median of timed passes)
        ncpu_r = min(len(queries_real), 128)
        cpu_r, cpu_r_qps, cpu_r_spread = timed_passes(
            lambda n: [cpu_match(queries_real[i]) for i in range(n)], ncpu_r)
        rec_r_tie, _rec_r_strict = recall(resp1r, cpu_r, ncpu_r,
                                          lambda i: queries_real[i])
        extra["configs"]["1r_real_mix"] = {
            "qps": round(qps1r, 1), "nterms": 6,
            "cpu_maxscore_qps": round(cpu_r_qps, 1),
            "cpu_maxscore_spread": cpu_r_spread,
            "vs_cpu": round(qps1r / cpu_r_qps, 2),
            "recall_at_10_tie_aware": round(rec_r_tie, 4),
            "kernel_served": served, "fallbacks": ds["fallback"],
            "pruned_rescued": ds["pruned_rescued"],
            "pruned_escalated": ds["pruned_escalated"]}
        _emit_partial("config1r_done")
    else:
        log("config 1r: skipped (budget)")

    # ---- codec v1 vs v2 A/B (ISSUE 8 acceptance artifact): same corpus,
    # same match query set, 32-thread closed loop per codec — qps,
    # per-query bytes, resident postings bytes, block-skip rate
    if remaining() > 60:
        seg_b = client.node.indices["bench"].shards[0].segments[0]
        # half the standing mid-frequency match pairs, half SKEWED pairs
        # (stopword-class + long-tail term): equal-idf pairs are the
        # block prune's worst case (every block prices alike), skewed
        # pairs are the classic MaxScore win the sidecar exists for
        rng_i = np.random.default_rng(17)
        dford = np.argsort(-df_per_term)
        stop_pool = dford[:64]
        # mid-rare pool: df comfortably past the window so the rare
        # term's posting-level witness prices the stopword blocks out
        # (df < window terms can't dominate the boundary — no engine
        # could skip the stopword list there)
        tail_pool = dford[1000:8000]
        tail_pool = tail_pool[df_per_term[tail_pool] >= 3 * TOPK]
        nimp = min(nq, 192)

        def skew_body(i, tag):
            s = int(stop_pool[i % len(stop_pool)])
            r = int(tail_pool[int(rng_i.integers(0, len(tail_pool)))])
            return {"query": {"match": {
                "body": f"{vocab_strs[s]} {vocab_strs[r]}"}},
                "size": TOPK, "_bench": tag}

        # tiny/quick corpora can empty the mid-rare pool — fall back to
        # the plain match stream rather than aborting the bench
        skew_ok = len(tail_pool) > 0 and len(stop_pool) > 0
        imp_bodies = [match_body(i, f"imp{i}")
                      if i % 2 == 0 or not skew_ok
                      else skew_body(i, f"imp{i}")
                      for i in range(nimp)]
        extra["impacts"] = measure_impacts(
            client, seg_b, imp_bodies, log,
            time_share=min(120.0, remaining() * 0.35))
        _emit_partial("impacts_ab_done")
        log(f"impacts A/B done: {extra['impacts'].get('gates')}")
    else:
        log("impacts A/B: skipped (budget)")

    # ---- interactive latency (batch-1) before the optional wide streams,
    # so a timeout still records it
    latency = extra["latency"]
    # no-op device round trip: the dispatch + fetch floor any single query
    # pays on this host (measured, not guessed)
    import jax
    import jax.numpy as jnp
    _noop = jax.jit(lambda a: a + 1)
    _x = jnp.zeros(8, jnp.float32)
    np.asarray(_noop(_x))                      # compile
    rtts = []
    for _ in range(20):
        t0 = time.time()
        np.asarray(_noop(_x))
        rtts.append((time.time() - t0) * 1000.0)
    latency["device_rtt_ms"] = {"p50": round(pct(rtts, 50), 2),
                                "p90": round(pct(rtts, 90), 2)}
    for bsize, calls in ((1, 48), (16, 24), (256, 8)):
        # batch-1 always runs (the priority metric); later sizes yield to
        # the budget. The RTT entry above must not trip this guard.
        if remaining() < 30 and any(k.startswith("batch") for k in latency):
            log(f"latency batch{bsize}: skipped (budget)")
            continue
        times = []
        for c in range(calls):
            lines = []
            for j in range(bsize):
                i = int((c * bsize + j) % nq)
                lines.append({"index": "bench"})
                lines.append(match_body(i, f"lat{bsize}-{c}-{j}"))
            t0 = time.time()
            client.msearch(lines)
            times.append((time.time() - t0) * 1000.0)
        times = times[1:]
        latency[f"batch{bsize}"] = {
            "p50_ms": round(pct(times, 50), 2),
            "p99_ms": round(pct(times, 99), 2),
            "qps": round(bsize / (pct(times, 50) / 1000.0), 1),
        }
    latency["batch2048"] = {"p50_ms": round(wall1, 2), "p99_ms": None,
                            "qps": round(qps1, 1)}
    _emit_partial("latency_done")

    # ---- config 2 (bool)
    if remaining() > 45:
        qps2, wall2, resp2 = run_stream(
            bool_body, range(nq), "b", 3,
            time_share=min(60.0, remaining() * 0.4))
        extra["configs"]["2_bool"] = {
            "qps": round(qps2, 1), "vs_cpu": round(qps2 / cpu2_qps, 2)}
        _emit_partial("config2_done")
    else:
        log("config 2: skipped (budget)")

    # ---- config 3 (phrase)
    if remaining() > 45:
        qps3, wall3, resp3 = run_stream(
            phrase_body, range(min(nq, 1024)), "p", 3, require_fast=False,
            time_share=min(45.0, remaining() * 0.4))
        extra["configs"]["3_phrase"] = {"qps": round(qps3, 1)}
        _emit_partial("config3_done")
    else:
        log("config 3: skipped (budget)")

    # ---- mixed stream: 50% filtered bool / 30% match / 20% phrase
    def mixed_body(i, tag):
        r = i % 10
        if r < 5:
            return bool_body(i, tag)
        if r < 8:
            return match_body(i, tag)
        return phrase_body(i, tag)

    if remaining() > 45 and "3_phrase" in extra["configs"]:
        qps_mixed, wall_mx, _ = run_stream(
            mixed_body, range(nq), "x", 3, require_fast=False,
            time_share=min(45.0, remaining() * 0.5))
        extra["configs"]["mixed_50f_30m_20p"] = {
            "qps": round(qps_mixed, 1),
            "pct_of_pure_match": round(100.0 * qps_mixed / qps1, 1)}
    else:
        log("mixed stream: skipped (budget)")

    # per-stream device-path telemetry: kernel serves, fallbacks, pruned
    # escalations (keys: m=match, r=realistic, b=bool, p=phrase, x=mixed)
    extra["fastpath_per_stream"] = {
        t: {k: v for k, v in d.items() if v}
        for t, d in stream_stats.items() if t != "fwarm"}
    # registry-sourced per-stage latency percentiles: the p50/p95/p99
    # trajectory BENCH_*.json carries from now on (end-to-end search,
    # per-phase, fastpath ladder rungs, jit compile/execute) — every
    # measured request flowed through the instrumented product path, so
    # this is the same data `_nodes/stats` would serve
    from opensearch_tpu.search.compiler import jit_attribution
    from opensearch_tpu.utils.metrics import METRICS
    extra["latency_percentiles"] = {
        stage: snap for stage, snap in METRICS.stage_percentiles().items()
        if stage.startswith(("search.", "fastpath.", "mesh."))
        and ".shape." not in stage}
    extra["jit_attribution"] = jit_attribution()
    # byte-domain baselines (ISSUE 7): peak resident bytes by tenant kind
    # + per-query data-movement percentiles — the committed numbers the
    # impact-quantization PR (ROADMAP item 1) must beat
    from opensearch_tpu.obs import query_cost as _query_cost
    from opensearch_tpu.obs.hbm_ledger import LEDGER as _LEDGER
    extra["hbm"] = _LEDGER.peak_stamp()
    extra["bytes_per_query"] = _query_cost.bytes_per_query_stamp()
    extra["bench_wall_s"] = round(time.time() - bench_start, 1)
    result = {
        "metric": "bm25_rest_qps_per_chip",
        "value": round(qps1, 2),
        "unit": "queries/sec",
        "vs_baseline": round(qps1 / cpu1_qps, 2),
        "extra": extra,
    }
    _PARTIAL.update(result)
    _emit_partial("complete")

    # update BASELINE.json.published only on request (a partial local run
    # must not silently rewrite checked-in baseline data)
    if os.environ.get("BENCH_WRITE_BASELINE") == "1":
        try:
            with open(os.path.join(_REPO, "BASELINE.json"), "r+") as f:
                bl = json.load(f)
                bl["published"] = {
                    **{(f"config{k[0]}_{k[2:]}" if k[0].isdigit()
                        else "mixed" if k.startswith("mixed") else k): v
                       for k, v in extra["configs"].items()},
                    "latency": latency,
                    "cpu_baseline_qps": {"match": round(cpu1_qps, 1),
                                         "bool": round(cpu2_qps, 1)},
                }
                f.seek(0)
                json.dump(bl, f, indent=2)
                f.truncate()
        except OSError:
            pass

    _PRINTED[0] = True
    print(json.dumps(result))


if __name__ == "__main__":
    main()
