"""Mesh dispatch share over the bench's realistic traffic mix, on the
8-virtual-device CPU mesh (no chip needed): index a scaled-down bench
corpus across 4 shards, stream the bench's 50% filtered-bool / 30% match /
20% phrase mix plus agg-bearing bodies through the product search path,
and report `MeshSearchService.stats()` — the share of traffic the SPMD
mesh actually serves vs the host shard-loop fallback.

Writes MESH_SHARE_r05.json. Run: `python scripts/mesh_share.py [ndocs]`.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    ndocs = int(sys.argv[1]) if len(sys.argv) > 1 else 200_000
    nq = int(os.environ.get("MESH_NQ", 400))
    import bench as B
    rng = np.random.default_rng(3)
    t0 = time.time()
    starts, doc_ids, tfs, dl, df_per_term = B._cached(
        f"body_{ndocs}", lambda: B.build_corpus(ndocs), True)
    queries = B.pick_queries(df_per_term, nq)

    from opensearch_tpu.cluster.node import Node
    from opensearch_tpu.parallel import MeshSearchService
    from opensearch_tpu.rest.client import RestClient

    svc = MeshSearchService()
    client = RestClient(node=Node(mesh_service=svc))
    vocab_strs = [f"t{i:07d}" for i in range(len(df_per_term))]

    # 4 shards via real document routing (the bench's make_index plants one
    # prebuilt segment into shard 0; the mesh needs real multi-shard
    # layout, so index through the product write path at this scale)
    client.indices.create("bench", {
        "settings": {"number_of_shards": 4},
        "mappings": {"properties": {
            "body": {"type": "text"}, "title": {"type": "text"},
            "status": {"type": "keyword"}, "price": {"type": "integer"},
            "ts": {"type": "date"}}}})
    status_vals = ["draft", "review", "published"]
    bulk = []
    # reconstruct per-doc token lists from the CSR (cheap at this scale)
    order = np.argsort(doc_ids, kind="stable")
    term_of_posting = np.repeat(
        np.arange(len(df_per_term)), np.diff(starts).astype(np.int64))
    d_sorted = doc_ids[order]
    t_sorted = term_of_posting[order]
    tf_sorted = tfs[order].astype(np.int64)
    bounds = np.searchsorted(d_sorted, np.arange(ndocs + 1))
    pair_pool = [(f"p{i:04d}", f"p{i+1:04d}") for i in range(0, 40, 2)]
    for d in range(ndocs):
        a, b = bounds[d], bounds[d + 1]
        toks = np.repeat(t_sorted[a:b], tf_sorted[a:b])
        pr = pair_pool[d % len(pair_pool)]
        bulk.append({"index": {"_index": "bench", "_id": str(d)}})
        bulk.append({
            "body": " ".join(vocab_strs[t] for t in toks[:64]),
            "title": f"{pr[0]} {pr[1]} {pair_pool[(d // 3) % len(pair_pool)][0]} "
                     f"{pair_pool[(d // 3) % len(pair_pool)][1]}",
            "status": status_vals[d % 3],
            "price": int(rng.integers(0, 1000)),
            "ts": f"2026-0{(d % 6) + 1:d}-15T00:00:00Z"})
        if len(bulk) >= 20_000:
            client.bulk(bulk)
            bulk = []
    if bulk:
        client.bulk(bulk)
    client.indices.refresh("bench")
    client.indices.forcemerge("bench")
    print(f"setup {time.time()-t0:.1f}s", flush=True)

    filters_dsl = {
        "pub": [{"term": {"status": "published"}}],
        "pubprice": [{"term": {"status": "published"}},
                     {"range": {"price": {"gte": 250, "lt": 750}}}],
        "draft": [{"term": {"status": "draft"}}],
    }
    fkeys = list(filters_dsl)

    def match_body(i):
        q = queries[i]
        return {"query": {"match": {
            "body": f"{vocab_strs[q[0]]} {vocab_strs[q[1]]}"}}, "size": 10}

    def bool_body(i):
        q = queries[i]
        terms = " ".join(vocab_strs[t] for t in q[:2])
        return {"query": {"bool": {
            "must": [{"match": {"body": terms}}],
            "filter": filters_dsl[fkeys[i % 3]]}}, "size": 10}

    def phrase_body(i):
        pr = pair_pool[i % len(pair_pool)]
        return {"query": {"match_phrase": {
            "title": f"{pr[0]} {pr[1]}"}}, "size": 10}

    def agg_body(i):
        q = queries[i]
        kinds = [
            {"by_status": {"terms": {"field": "status"},
                           "aggs": {"p": {"avg": {"field": "price"}}}}},
            {"price_stats": {"stats": {"field": "price"}}},
            {"price_hist": {"histogram": {"field": "price",
                                          "interval": 100}}},
            {"card": {"cardinality": {"field": "status"}}},
            {"pct": {"percentiles": {"field": "price"}}},
            {"rng": {"range": {"field": "price",
                               "ranges": [{"to": 300}, {"from": 300}]}}},
            {"by_day": {"date_histogram": {"field": "ts",
                                           "fixed_interval": "30d"}}},
            {"flt": {"filters": {"filters": {
                "pub": {"term": {"status": "published"}},
                "cheap": {"range": {"price": {"lt": 200}}}}}}},
            {"sig": {"significant_terms": {"field": "status"}}},
        ]
        return {"query": {"match": {"body": vocab_strs[q[0]]}}, "size": 0,
                "aggs": kinds[i % len(kinds)]}

    streams = {
        "mixed_50f_30m_20p": [
            (bool_body if i % 10 < 5 else
             match_body if i % 10 < 8 else phrase_body)(i)
            for i in range(nq)],
        "match": [match_body(i) for i in range(nq // 2)],
        "aggs": [agg_body(i) for i in range(nq // 4)],
    }
    out = {"ndocs": ndocs, "devices": len(jax.devices()),
           "streams": {}}
    for name, bodies in streams.items():
        d0, f0 = svc.dispatched, svc.fallbacks
        t0 = time.time()
        lines = []
        for j, b in enumerate(bodies):
            lines.append({"index": "bench"})
            lines.append(dict(b, _bench=f"ms-{name}-{j}"))
        client.msearch(lines)
        dd, df = svc.dispatched - d0, svc.fallbacks - f0
        share = dd / max(dd + df, 1)
        out["streams"][name] = {
            "n": len(bodies), "dispatched": dd, "fallbacks": df,
            "dispatch_share": round(share, 4),
            "wall_s": round(time.time() - t0, 1)}
        print(f"{name}: dispatched={dd} fallbacks={df} "
              f"share={share:.1%}", flush=True)
    out["service_stats"] = svc.stats()
    with open(os.path.join(_REPO, "MESH_SHARE_r05.json"), "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out["streams"]))


if __name__ == "__main__":
    main()
