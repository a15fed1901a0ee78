#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

One process, which touches JAX itself and starts no child that needs the
chip. It fails (non-zero exit, no result line) unless JAX reports a TPU
with exactly `--chips` devices, and then drives the product the way a user
does, through `RestClient` (and `HttpServer` over a real socket):

  phase A  the normal write and read path at modest size: create, bulk
           20,000 seeded documents, refresh, match / bool+range / term /
           terms-agg / get / msearch, flush and read one acknowledged
           document back through a second client on the same data path.
           Compared with the same requests on the XLA path
           (`fastpath.set_enabled(False)`) and a numpy BM25.
  phase B  real size: the benchmark's MS-MARCO-shaped generator
           (`benchmark/corpus.py`; vocabulary 200,000, mean length 56) at
           `--ndocs` (default 2.2M = one chip's share of the 8.8M-passage,
           four-shard north-star index), planted as a product segment; a
           fixed seeded set of 64 queries as `msearch` batches, then 16
           singly. Compared with the benchmark's numpy dense reference
           (`benchmark/reference.py`) over the same CSR arrays and the
           native MaxScore scorer where the library built.
  --chips 4  runs ONLY the mesh phase: four segments of ndocs/4 in a
           four-shard index through a `MeshSearchService` node, against a
           `Node(mesh_service=False)` client (the host shard loop).

The generator, the reference, the comparison rule and the meters are the
benchmark's own (`benchmark/corpus.py`, `reference.py`, `run.py`): the smoke
holds a page to what the yardstick would hold it to. The rule everywhere:
hit totals equal where the response says `eq`; scores within 1e-5
relative; doc ids equal wherever the reference's score gap to its
neighbouring ranks exceeds that tolerance. Every request asks for one rank
more than the ten it checks, so the gap below the tenth rank is known.

Each phase prints one JSON object of read-outs (seconds, programs
compiled, counters, device memory: read-outs, not metrics) BEFORE it holds
its answers to the references, so a failed comparison still has them;
progress notes go to stderr. The LAST line
of stdout is `{"ok": true, "device": {"platform", "kind", "count"}}` and
nothing more. Any phase that raises, any comparison that fails, a fastpath
that served nothing (`pure_served == 0` / `bool_served == 0`), a scheduler
batch error, a ledger with no device statistics or a Pallas program that
lowered interpreted ends the run with a traceback and a non-zero exit.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))
# the benchmark's modules are top-level names inside benchmark/ (as
# tests/test_benchmark_seam.py imports them)
sys.path.insert(0, os.path.join(_REPO, "benchmark"))

import corpus                                   # noqa: E402
import reference                                # noqa: E402
from run import (CompileMeter, INDEX, delta, emit,   # noqa: E402
                 note, require_device)

OUT_DIR = os.path.join(_REPO, "chiprun_out", "chip_smoke")
K1, B = reference.K1, reference.B
PAGE = 10            # ranks checked per response
SIZE = PAGE + 1      # ranks requested: the 11th gives the 10th its gap
RTOL = 1e-5
PHASE_A_NDOCS = 20_000
STATUSES = corpus.STATUSES
# phase B's corpus: what the MS-MARCO-shaped configuration's generator takes
VOCAB, AVG_DL = 200_000, 56


class SmokeFailure(AssertionError):
    """A comparison or a required counter failed."""


def counters() -> dict:
    """The fastpath ladder, device-rescore and scheduler counters."""
    from opensearch_tpu.search import fastpath
    from opensearch_tpu.utils.metrics import METRICS
    out = {f"fastpath.{k}": v for k, v in dict(fastpath.STATS).items()}
    out.update({f"fastpath.rescore.{k}": round(v, 3) for k, v in
                fastpath.rescore_stats().items()})
    out["serving.batch_errors"] = METRICS.snapshot()["counters"].get(
        "serving.batch_errors", 0)
    return out


def kernels_lower_to_mosaic() -> bool:
    """True when a Pallas kernel of the served path lowers, under the
    ambient configuration, to a Mosaic custom call — i.e. it would run
    compiled on the chip and not interpreted."""
    import jax
    import jax.numpy as jnp
    from opensearch_tpu.ops.pallas_bm25 import (HBM_ALIGN,
                                                fused_bm25_topk_tfdl)
    plane = jax.ShapeDtypeStruct((4 * HBM_ALIGN,), jnp.int32)
    i32 = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    f32 = jax.ShapeDtypeStruct((8, 1), jnp.float32)
    text = fused_bm25_topk_tfdl.lower(
        plane, plane, i32, i32, i32, i32, f32, f32, f32, i32, i32,
        T=1, L=HBM_ALIGN, K=16, k1=K1, b=B).as_text()
    return "tpu_custom_call" in text


# ---------------------------------------------------------------------
# the benchmark's reference and rule, as the smoke calls them
# ---------------------------------------------------------------------

def reference_page(ref: reference.Reference, terms, msm: int = 1,
                   mask=None) -> dict:
    """The reference's page of SIZE for `terms`. `Reference` knows neither
    `minimum_should_match` nor a filter; both only decide which documents
    are hits (a hit's score is the sum over the terms it holds either
    way), so they are applied here to its ranking of every document that
    holds a term."""
    if msm <= 1 and mask is None:
        return ref.page({"terms": terms}, SIZE)
    ranked = ref.page({"terms": terms}, ref.n)  # size n: every hit, ranked
    docs = np.asarray(ranked["ids"], np.int64)
    keep = np.ones(len(docs), bool)
    if msm > 1:
        held = np.bincount(np.concatenate(
            [ref.doc_ids[ref.starts[t]: ref.starts[t + 1]] for t in terms]),
            minlength=ref.n)
        keep &= held[docs] >= msm
    if mask is not None:
        keep &= mask[docs]
    top = np.flatnonzero(keep)[:SIZE]
    return {"total": int(keep.sum()), "relation": "eq",
            "ids": [ranked["ids"][i] for i in top],
            "scores": [ranked["scores"][i] for i in top]}


def served_page(resp: dict) -> dict:
    if "error" in resp:
        raise SmokeFailure(f"search answered an error: {resp['error']}")
    return reference.page_of(resp)


def hold_page(what: str, got: dict, ref: dict) -> None:
    """Hold `got` to `ref` by the benchmark's rule: any number
    `reference.compare_page` finds over its limit raises. A reference with
    no `ids` decides the total alone; one whose own total is a lower bound
    (the native scorer after early termination, a served page that says
    `gte`) decides no total."""
    if "ids" not in ref:
        ref = dict(got, total=ref["total"], relation=ref["relation"])
    found = reference.compare_page(got, ref, PAGE, RTOL)
    if ref["relation"] != "eq":
        found["total_violations"] = 0
    if found.pop("score_rel_err") > RTOL or any(found.values()):
        raise SmokeFailure(f"{what}: {got} != {ref}")


# ---------------------------------------------------------------------
# phase A — the normal write and read path
# ---------------------------------------------------------------------

def _phase_a_corpus(rng, ndocs: int, nvocab: int = 2000):
    """Seeded documents + the CSR postings and lengths of their `body` for
    the reference. Words are `w0000`-style so the standard analyzer keeps
    them whole."""
    dl = rng.integers(6, 31, ndocs)
    terms = rng.zipf(1.2, int(dl.sum()))
    terms = np.where(terms > nvocab, rng.integers(1, nvocab + 1, len(terms)),
                     terms) - 1
    doc_of = np.repeat(np.arange(ndocs), dl)
    status = rng.integers(0, 3, ndocs)
    price = rng.integers(0, 1000, ndocs)
    bounds = np.concatenate([[0], np.cumsum(dl)])
    docs = [{"body": " ".join(f"w{t:04d}" for t in terms[bounds[i]:
                                                       bounds[i + 1]]),
             "status": STATUSES[status[i]], "price": int(price[i])}
            for i in range(ndocs)]
    uniq, tfs = np.unique(terms.astype(np.int64) * ndocs + doc_of,
                          return_counts=True)
    starts = np.zeros(nvocab + 1, np.int64)
    np.cumsum(np.bincount(uniq // ndocs, minlength=nvocab), out=starts[1:])
    csr = (starts, (uniq % ndocs).astype(np.int32), tfs.astype(np.float32))
    return docs, csr, dl.astype(np.int64), status, price


def _http(port: int, method: str, path: str, body=None, ndjson=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        if ndjson is not None:
            payload = "\n".join(json.dumps(x) for x in ndjson) + "\n"
            ctype = "application/x-ndjson"
        else:
            payload = json.dumps(body) if body is not None else None
            ctype = "application/json"
        conn.request(method, path, body=payload,
                     headers={"Content-Type": ctype})
        resp = conn.getresponse()
        out = json.loads(resp.read().decode())
    finally:
        conn.close()
    if resp.status != 200:
        raise SmokeFailure(f"HTTP {method} {path} -> {resp.status}: {out}")
    return out


def phase_a(data_dir: str, seed: int, ndocs: int, meter: CompileMeter
            ) -> dict:
    from opensearch_tpu.rest.client import RestClient
    from opensearch_tpu.rest.http_server import HttpServer
    from opensearch_tpu.search import fastpath

    shutil.rmtree(data_dir, ignore_errors=True)   # this script's own dir
    rng = np.random.default_rng(seed)
    c0, m0, t0 = counters(), meter.mark(), time.time()
    docs, csr, dl, status, price = _phase_a_corpus(rng, ndocs)
    ref = reference.Reference(csr, dl)
    client = RestClient(data_path=data_dir)
    client.indices.create("smoke", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {"body": {"type": "text"},
                                    "status": {"type": "keyword"},
                                    "price": {"type": "integer"}}}})
    for lo in range(0, ndocs, 5000):
        lines = []
        for i in range(lo, min(lo + 5000, ndocs)):
            lines += [{"index": {"_index": "smoke", "_id": str(i)}}, docs[i]]
        if client.bulk(lines)["errors"]:
            raise SmokeFailure("bulk reported item errors")
    client.indices.refresh("smoke")
    build_s = time.time() - t0
    note(f"A: {ndocs} docs indexed and refreshed")

    # mid-frequency words: selective but never absent
    df = np.diff(csr[0])
    pool = np.argsort(-df)[20:400]
    pool = pool[df[pool] > 0]

    def pick(n):
        return [int(t) for t in rng.choice(pool, n, replace=False)]

    def text(ts):
        return " ".join(f"w{t:04d}" for t in ts)

    reqs = []           # (name, body, reference page or None)
    for i in range(8):
        ts = pick(2)
        reqs.append((f"match{i}", {"query": {"match": {"body": text(ts)}},
                                   "size": SIZE}, reference_page(ref, ts)))
    for i in range(4):
        ts, lo = pick(2), 100 * (i + 1)
        reqs.append((f"bool{i}", {"query": {"bool": {
            "must": [{"match": {"body": text(ts)}}],
            "filter": [{"range": {"price": {"gte": lo, "lt": lo + 500}}}]}},
            "size": SIZE},
            reference_page(ref, ts,
                           mask=(price >= lo) & (price < lo + 500))))
    for i in range(4):
        s = i % 3
        # constant score per hit: the rule checks total and scores only
        reqs.append((f"term{i}", {"query": {"term": {
            "status": STATUSES[s]}}, "size": SIZE},
            {"total": int((status == s).sum()), "relation": "eq"}))
    agg_body = {"size": 0, "aggs": {"by_status": {"terms": {
        "field": "status"}}}}
    agg_ref = {STATUSES[s]: int((status == s).sum()) for s in range(3)}
    probe_id = str(int(rng.integers(0, ndocs)))
    msearch_lines = []
    for _name, body, _ref in reqs:
        msearch_lines += [{"index": "smoke"}, body]

    def run(search, get, msearch):
        """-> (pages, agg buckets, probe source, msearch pages)."""
        pages = [served_page(search(body)) for _n, body, _r in reqs]
        buckets = {b["key"]: b["doc_count"] for b in
                   search(agg_body)["aggregations"]["by_status"]["buckets"]}
        multi = [served_page(r) for r in msearch()["responses"]]
        return pages, buckets, get()["_source"], multi

    def run_client():
        return run(lambda b: client.search("smoke", b),
                   lambda: client.get("smoke", probe_id),
                   lambda: client.msearch(msearch_lines))

    t0 = time.time()
    via_client = run_client()
    first_s = time.time() - t0
    cold = meter.since(m0)
    served = delta(counters(), c0)
    note("A: first pass through RestClient answered")

    server = HttpServer(client)
    port = server.start()
    try:
        via_http = run(
            lambda b: _http(port, "POST", "/smoke/_search", b),
            lambda: _http(port, "GET", f"/smoke/_doc/{probe_id}"),
            lambda: _http(port, "POST", "/_msearch", ndjson=msearch_lines))
    finally:
        server.stop()

    fastpath.set_enabled(False)
    try:
        via_xla = run_client()
    finally:
        fastpath.set_enabled(True)

    out = {"phase": "A", "ndocs": ndocs, "requests": len(reqs) * 2 + 2,
           "build_s": round(build_s, 2), "first_pass_s": round(first_s, 2),
           "cold": cold, "counters": served}
    emit(out)           # read-outs first: a failed comparison still has them
    pages, buckets, source, multi = via_client
    if via_http != via_client:
        raise SmokeFailure("HttpServer answers differ from RestClient's")
    for (name, _body, ref), page, mpage, xpage in zip(reqs, pages, multi,
                                                      via_xla[0]):
        hold_page(f"A/{name} vs numpy", page, ref)
        hold_page(f"A/{name} vs XLA path", page, xpage)
        hold_page(f"A/{name} msearch vs search", mpage, page)
    for (name, _b, _r), mpage, xmpage in zip(reqs, multi, via_xla[3]):
        hold_page(f"A/{name} msearch vs XLA msearch", mpage, xmpage)
    if not buckets == via_xla[1] == agg_ref:
        raise SmokeFailure(f"A/terms agg {buckets} != {agg_ref}")
    if not source == via_xla[2] == docs[int(probe_id)]:
        raise SmokeFailure(f"A/get {probe_id}: {source}")

    # durability: flushed, then read back by a second client on the path
    client.indices.flush("smoke")
    again = RestClient(data_path=data_dir).get("smoke", probe_id)
    if again["_source"] != docs[int(probe_id)]:
        raise SmokeFailure(f"A/durability: {probe_id} read back as {again}")
    return out


# ---------------------------------------------------------------------
# phase B — real size
# ---------------------------------------------------------------------

def _queries(df: np.ndarray, rng, vocab: list) -> list:
    """The fixed seeded set of 64: 24 two-term match, 16 four-term match
    with minimum_should_match 2, 4 over the three most frequent terms
    (stopword-class rows, far longer than MAX_L: alone with a selective
    term the impact heads certify at once, paired with each other they
    climb the pruned ladder into the rescore), 20 bool must + filter on
    status."""
    order = np.argsort(-df, kind="stable")
    order = order[df[order] > 0]
    pool = order[min(100, len(order) // 10): 20_000]

    def pick(n):
        return [int(t) for t in rng.choice(pool, n, replace=False)]

    def text(ts):
        return " ".join(vocab[t] for t in ts)

    out = []
    for _ in range(24):
        ts = pick(2)
        out.append({"kind": "match2", "terms": ts, "msm": 1, "status": None,
                    "query": {"match": {"body": text(ts)}}})
    for _ in range(16):
        ts = pick(4)
        out.append({"kind": "match4", "terms": ts, "msm": 2, "status": None,
                    "query": {"match": {"body": {
                        "query": text(ts), "minimum_should_match": 2}}}})
    top = [int(t) for t in order[:3]]
    for ts in ([top[0]] + pick(1), top[:2], top[1:], top[::2] + pick(1)):
        out.append({"kind": "stopword", "terms": ts, "msm": 1,
                    "status": None,
                    "query": {"match": {"body": text(ts)}}})
    for i in range(20):
        ts, s = pick(2), 1 + i % 2
        out.append({"kind": "bool", "terms": ts, "msm": 1, "status": s,
                    "query": {"bool": {
                        "must": [{"match": {"body": text(ts)}}],
                        "filter": [{"term": {"status": STATUSES[s]}}]}}})
    for q in out:
        q["body"] = {"query": q["query"], "size": SIZE}
    return out


# the 16 sent singly after the batches: four of each kind
SINGLES = [0, 1, 2, 3, 24, 25, 26, 27, 40, 41, 42, 43, 44, 45, 46, 47]


def _send_set(client, index: str, queries: list) -> tuple:
    """The traffic: two msearch batches of 32, then SINGLES one by one.
    -> (pages of the 64, pages of the singles, seconds to first batch)."""
    pages, t0, first_s = [], time.time(), None
    for lo in (0, 32):
        lines = []
        for q in queries[lo: lo + 32]:
            lines += [{"index": index}, q["body"]]
        pages += [served_page(r)
                  for r in client.msearch(lines)["responses"]]
        if first_s is None:
            first_s = time.time() - t0
    singles = [served_page(client.search(index, queries[i]["body"]))
               for i in SINGLES]
    return pages, singles, first_s


def _hold_set(label: str, queries: list, sent: tuple, refs: list) -> None:
    """Hold one pass of the traffic (`_send_set`'s pages and singles) to
    the references: refs[i] = [(name, reference page), ...] of query i."""
    pages, singles = sent[:2]
    failed = []         # the whole pass is held, then all failures raised
    for i, page in list(enumerate(pages)) + list(zip(SINGLES, singles)):
        for name, ref in refs[i]:
            try:
                hold_page(f"{label}/{queries[i]['kind']}[{i}] vs {name}",
                          page, ref)
            except SmokeFailure as e:
                failed.append(str(e))
    if failed:
        raise SmokeFailure(f"{len(failed)} comparison(s) failed: "
                           + " | ".join(failed[:4]))


def _repeat_drift(queries: list, cold: tuple, warm: tuple) -> dict:
    """How the warm repeat differs from the cold pass bit for bit. Both
    passes are held to the references by the rule; the product may serve
    a repeat from another rung of its ladder (a filter seen twice gets
    its specialized postings), so this is a read-out, not a failure."""
    pairs = list(zip(range(len(queries)), cold[0], warm[0])) \
        + list(zip(SINGLES, cold[1], warm[1]))
    diff = [(i, c, w) for i, c, w in pairs if c != w]
    out = {"pages_not_bit_identical": len(diff), "of": len(pairs)}
    if diff:
        i, c, w = diff[0]
        out["first"] = {"query": i, "kind": queries[i]["kind"],
                        "cold": c, "warm": w}
    return out


def _device_memory() -> dict:
    """Ledger total beside the allocator's, and the allocator's peak."""
    import jax
    from opensearch_tpu.obs.hbm_ledger import LEDGER
    stats = jax.devices()[0].memory_stats() or {}
    return {"ledger_vs_device": LEDGER.check_device(),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "per_device_bytes_in_use": [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.devices()]}


def phase_b(seed: int, ndocs: int, meter: CompileMeter) -> dict:
    import jax

    from opensearch_tpu import native
    from opensearch_tpu.rest.client import RestClient
    from opensearch_tpu.search import fastpath

    rng = np.random.default_rng(seed + 1)
    c0, t0 = counters(), time.time()
    starts, doc_ids, tfs, dl, df = corpus.build_corpus(ndocs, VOCAB, AVG_DL,
                                                       seed)
    status = rng.integers(0, 3, ndocs).astype(np.int32)
    price = rng.integers(0, 1000, ndocs).astype(np.int64)
    vocab = corpus.vocab_strings(len(df))
    client = RestClient()
    seg = corpus.plant_index(client, INDEX, (starts, doc_ids, tfs), vocab,
                             dl, status, price, {"number_of_replicas": 0})
    build_s = time.time() - t0
    note(f"B: {ndocs}-doc segment built on the host")

    t0 = time.time()
    al = fastpath.get_aligned(seg, "body")
    if al is None:
        raise SmokeFailure("B: the segment has no aligned device layout")
    jax.block_until_ready([a for a in (al.d_docs, al.d_tfdl, al.d_imp)
                           if a is not None])
    promote_s = time.time() - t0
    note("B: aligned planes on the device")

    queries = _queries(df, rng, vocab)
    m0, t0 = meter.mark(), time.time()
    pages, singles, first_s = _send_set(client, INDEX, queries)
    cold_s, cold = time.time() - t0, meter.since(m0)
    note("B: cold set answered")
    m0, t0 = meter.mark(), time.time()
    warm = _send_set(client, INDEX, queries)
    warm_s, warm_compiles = time.time() - t0, meter.since(m0)
    note("B: warm set answered; scoring the references")

    # references: the benchmark's numpy dense always; native MaxScore
    # where it built
    ref = reference.Reference((starts, doc_ids, tfs), dl)
    have_native = native.available()
    if have_native:
        kdoc = (K1 * (1.0 - B + B * dl.astype(np.float32)
                      / np.float32(dl.sum() / ndocs))).astype(np.float32)
        idf = np.log1p((float(ndocs) - df + 0.5) / (df + 0.5)
                       ).astype(np.float32)
        ub = native.term_upper_bounds(starts, doc_ids, tfs, kdoc, idf)
    refs = []
    for q in queries:
        mask = None if q["status"] is None else status == q["status"]
        refs.append([("numpy dense", reference_page(ref, q["terms"],
                                                    q["msm"], mask))])
        if have_native:
            d, s, total = native.maxscore_topk(
                starts, doc_ids, tfs, kdoc, idf, ub,
                np.asarray(q["terms"], np.int32), q["msm"], SIZE,
                None if mask is None else mask.astype(np.uint8))
            keep = d >= 0
            refs[-1].append(("native MaxScore", {
                "total": total, "relation": "eq" if total >= 0 else "gte",
                "ids": [str(x) for x in d[keep]],
                "scores": [float(x) for x in s[keep]]}))
    out = {"phase": "B", "ndocs": ndocs, "postings": int(len(doc_ids)),
           "queries": len(queries), "singles": len(SINGLES),
           "reference": ["numpy_dense"] + (["native_maxscore"]
                                           if have_native else []),
           "native_library_built": have_native,
           "build_s": round(build_s, 2), "promote_s": round(promote_s, 2),
           "first_answer_s": round(first_s, 2),
           "cold_set_s": round(cold_s, 2), "cold": cold,
           "warm_set_s": round(warm_s, 2), "warm": warm_compiles,
           "warm_vs_cold": _repeat_drift(queries, (pages, singles), warm),
           "request_cache": client.node.request_cache.stats(),
           "aligned_postings_bytes": int(al.nbytes),
           "memory": _device_memory(),
           "counters": delta(counters(), c0)}
    emit(out)
    _hold_set("B cold", queries, (pages, singles), refs)
    _hold_set("B warm", queries, warm, refs)
    return out


# ---------------------------------------------------------------------
# --chips 4 — the mesh path against the host shard loop
# ---------------------------------------------------------------------

class _IdsFrom:
    """A shard's doc-id strings, counted from its first id, made on demand."""

    def __init__(self, base: int, n: int):
        self.base, self.n = base, n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [str(self.base + j) for j in range(*i.indices(self.n))]
        return str(self.base + i)


def _plant_shard(client, shard: int, id_base: int, csr, vocab, dl, status,
                 price) -> None:
    """`corpus.plant_index` makes an index of one segment in shard 0; the
    mesh phase wants that segment in shard `shard` of INDEX, its ids
    counted from `id_base`: planted under a scratch name, then moved."""
    scratch = f"plant{shard}"
    seg = corpus.plant_index(client, scratch, csr, vocab, dl, status, price,
                             {"number_of_replicas": 0})
    client.node.indices[scratch].shards[0].segments = []
    client.indices.delete(scratch)
    seg.name, seg.ids = f"{INDEX}{shard}", _IdsFrom(id_base, len(dl))
    svc = client.node.indices[INDEX]
    svc.shards[shard].segments = [seg]
    svc.generation += 1


def phase_mesh(seed: int, ndocs: int, meter: CompileMeter) -> dict:
    from opensearch_tpu.cluster.node import Node
    from opensearch_tpu.rest.client import RestClient

    shards = 4                  # one per chip of the 2x2 host
    per = ndocs // shards
    mesh_client = RestClient(node=Node())     # >1 device: mesh by default
    host_client = RestClient(node=Node(mesh_service=False))
    svc = mesh_client.node.mesh_service
    if svc is None:
        raise SmokeFailure("mesh: the default node holds no "
                           "MeshSearchService with more than one device")
    c0, t0 = counters(), time.time()
    for c in (mesh_client, host_client):
        c.indices.create(INDEX, {
            "settings": {"number_of_shards": shards,
                         "number_of_replicas": 0},
            "mappings": {"properties": {
                "body": {"type": "text"}, "status": {"type": "keyword"},
                "price": {"type": "integer"}}}})
    df, vocab = 0, None
    for s in range(shards):
        rng = np.random.default_rng(seed + 1 + s)
        starts, doc_ids, tfs, dl, df_s = corpus.build_corpus(
            per, VOCAB, AVG_DL, seed + s)
        df = df + df_s
        vocab = vocab or corpus.vocab_strings(len(df_s))
        status = rng.integers(0, 3, per).astype(np.int32)
        price = rng.integers(0, 1000, per).astype(np.int64)
        for c in (mesh_client, host_client):
            _plant_shard(c, s, s * per, (starts, doc_ids, tfs), vocab, dl,
                         status, price)
    build_s = time.time() - t0
    note(f"mesh: {shards} x {per}-doc segments built, twice")

    queries = _queries(df, np.random.default_rng(seed + 1), vocab)
    m0, t0 = meter.mark(), time.time()
    pages, singles, first_s = _send_set(mesh_client, INDEX, queries)
    cold_s, cold = time.time() - t0, meter.since(m0)
    memory = _device_memory()       # before the host loop adds its own
    note(f"mesh: cold set answered; per-device bytes in use "
         f"{memory['per_device_bytes_in_use']}")
    t0 = time.time()
    warm = _send_set(mesh_client, INDEX, queries)
    warm_s = time.time() - t0
    note("mesh: warm set answered; asking the host shard loop")
    dispatched, declined = svc.dispatched, svc.fallbacks
    if not dispatched:
        raise SmokeFailure("mesh: no search was dispatched to the mesh "
                           f"(declined {declined}: {svc.stats()})")

    ref_pages, _, _ = _send_set(host_client, INDEX, queries)
    refs = [[("host loop", ref)] for ref in ref_pages]
    out = {"phase": "mesh", "ndocs": per * shards, "shards": shards,
           "queries": len(queries), "singles": len(SINGLES),
           "mesh_dispatched": dispatched, "mesh_declined": declined,
           "build_s": round(build_s, 2),
           "first_answer_s": round(first_s, 2),
           "cold_set_s": round(cold_s, 2), "cold": cold,
           "warm_set_s": round(warm_s, 2),
           "warm_vs_cold": _repeat_drift(queries, (pages, singles), warm),
           "memory": memory,
           "counters": delta(counters(), c0)}
    emit(out)
    _hold_set("mesh cold", queries, (pages, singles), refs)
    _hold_set("mesh warm", queries, warm, refs)
    return out


# ---------------------------------------------------------------------
# verdict
# ---------------------------------------------------------------------

def verdict(readouts: list, chips: int) -> None:
    """What must hold after the phases, on the chip."""
    if not kernels_lower_to_mosaic():
        raise SmokeFailure("a Pallas program lowered interpreted")
    c = counters()
    if c["serving.batch_errors"]:
        raise SmokeFailure(f"serving.batch_errors = "
                           f"{c['serving.batch_errors']}")
    for r in readouts:
        mem = r.get("memory")
        if mem is None:
            continue            # phase A reads no memory out
        if mem["ledger_vs_device"] is None:
            raise SmokeFailure("the backend gave no memory statistics")
        used = mem["per_device_bytes_in_use"]
        if chips > 1 and (not all(used) or max(used) == sum(used)):
            raise SmokeFailure(f"device memory is not spread: {used}")
        if r["phase"] == "B":
            for key in ("fastpath.pure_served", "fastpath.bool_served"):
                if not r["counters"].get(key):
                    raise SmokeFailure(f"{key} == 0 after phase B: the "
                                       f"kernels served nothing ({c})")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ndocs", type=int, default=2_200_000)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    t_start = time.time()
    device = require_device(args.chips)
    from opensearch_tpu import native
    from opensearch_tpu.utils.compile_cache import place_compile_cache
    emit({"phase": "start", "device": device, "seed": args.seed,
          "ndocs": args.ndocs, "compile_cache": place_compile_cache(),
          "native_library_built": native.available(),
          "kernels_lower_to_mosaic": kernels_lower_to_mosaic()})
    meter = CompileMeter()
    readouts = []
    if args.chips == 1:
        readouts.append(phase_a(os.path.join(OUT_DIR, "phase_a_data"),
                                args.seed, PHASE_A_NDOCS, meter))
        readouts.append(phase_b(args.seed, args.ndocs, meter))
    else:
        readouts.append(phase_mesh(args.seed, args.ndocs, meter))
    verdict(readouts, args.chips)
    emit({"phase": "total", "wall_s": round(time.time() - t_start, 1),
          "compiled": meter.mark(), "counters": counters()})
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
