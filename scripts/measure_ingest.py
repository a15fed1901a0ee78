"""First write-path bench (ISSUE 18): bulk docs/s, refresh-to-visible
latency, and query-p99 degradation while indexing, on a REAL 2-node
fleet (coordinator + one child process via tests/_dist_child.py — per
process registries, so the federated `indexing` block exercises the
actual merge path, not a shared-registry shortcut).

Phases:
 1. seed    — a warmup corpus lands through the fleet write path
              (`DistClusterNode.index_doc` routes by id: half the docs
              cross the wire to the child's shard), then a refresh.
 2. idle    — N query reps against the distributed search path for the
              baseline p50/p99 (client-side wall clock).
 3. ingest  — W writer threads drive INGEST_DOCS docs through the fleet
              write path while a refresher thread publishes every
              INGEST_REFRESH_MS and a query thread keeps searching;
              docs/s is the writer wall, query p99 comes from the
              searches that completed INSIDE the write window (the
              thread keeps going until at least MIN_BUSY_QUERIES
              landed, so short runs stay statistically honest — the
              overshoot is reported, never hidden).
 4. report  — `indexing_stats()` federates both nodes' `indexing.*`
              slices (counters summed, DDSketch merged bin-wise);
              refresh-to-visible p50/p95 are read off the MERGED
              sketch, never averaged per node.

The emission lands in BENCH_out.json as `metric: ingest_docs_per_s`
with the ingest block under `extra.ingest` (scripts/bench_diff.py
extracts and direction-gates it); an existing `extra.concurrency`
block (the ingest-obs overhead pair from measure_concurrency.py) is
preserved by the merge.

Run:  JAX_PLATFORMS=cpu python scripts/measure_ingest.py
Env:  INGEST_DOCS (default 6000), INGEST_WRITERS (8),
      INGEST_SEED_DOCS (3000), INGEST_QUERIES (200, idle reps),
      INGEST_REFRESH_MS (200).
"""

import json
import os
import subprocess
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from opensearch_tpu.cluster.distnode import DistClusterNode  # noqa: E402

MAPPING = {"settings": {"number_of_shards": 2},
           "mappings": {"properties": {"body": {"type": "text"},
                                       "price": {"type": "integer"}}}}

WORDS = ["alpha", "bravo", "charlie", "delta", "echo", "foxtrot",
         "golf", "hotel", "india", "juliet", "kilo", "lima"]

MIN_BUSY_QUERIES = 30


def _doc(i: int) -> dict:
    return {"body": f"{WORDS[i % len(WORDS)]} "
                    f"{WORDS[(i * 7) % len(WORDS)]} common",
            "price": i % 1000}


def _query(i: int) -> dict:
    return {"size": 5, "query": {"bool": {
        "must": [{"match": {"body": WORDS[i % len(WORDS)]}}],
        "filter": [{"range": {"price": {"lte": 500 + (i % 400)}}}]}}}


def spawn_child(seed_addr: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    child = subprocess.Popen(
        [sys.executable, os.path.join(_REPO, "tests", "_dist_child.py"),
         seed_addr, "mb"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env, cwd=_REPO)
    line = child.stdout.readline().strip()
    if not line.startswith("READY "):
        child.kill()
        raise SystemExit(f"child failed to start: {line!r}")
    return child


def query_cell(node, n: int) -> dict:
    lats = []
    for i in range(n):
        t0 = time.perf_counter()
        node.search("ingest", _query(i))
        lats.append((time.perf_counter() - t0) * 1000.0)
    arr = np.asarray(lats)
    return {"n": len(lats),
            "p50_ms": round(float(np.percentile(arr, 50)), 3),
            "p99_ms": round(float(np.percentile(arr, 99)), 3)}


def main() -> int:
    ndocs = int(os.environ.get("INGEST_DOCS", 6000))
    nwriters = int(os.environ.get("INGEST_WRITERS", 8))
    nseed = int(os.environ.get("INGEST_SEED_DOCS", 3000))
    nq = int(os.environ.get("INGEST_QUERIES", 200))
    refresh_ms = float(os.environ.get("INGEST_REFRESH_MS", 200))

    a = DistClusterNode("ma")
    child = spawn_child(a.addr)
    try:
        a.create_index("ingest", MAPPING)

        # ---- phase 1: seed through the fleet write path ----
        t0 = time.perf_counter()
        for i in range(nseed):
            a.index_doc("ingest", _doc(i), id=f"s{i:06d}")
        a.refresh("ingest")
        seed_docs_per_s = round(nseed / (time.perf_counter() - t0), 1)
        print(f"seeded {nseed} docs ({seed_docs_per_s} docs/s)",
              flush=True)

        # ---- phase 2: idle query baseline ----
        idle = query_cell(a, nq)
        print(f"idle queries: {json.dumps(idle)}", flush=True)

        # ---- phase 3: concurrent ingest + refresher + queries ----
        writers_done = threading.Event()
        pos = [0]
        wlock = threading.Lock()
        werrors = [0]

        def writer():
            while True:
                with wlock:
                    i = pos[0]
                    if i >= ndocs:
                        return
                    pos[0] += 1
                try:
                    a.index_doc("ingest", _doc(nseed + i),
                                id=f"w{i:06d}")
                except Exception:
                    with wlock:
                        werrors[0] += 1

        refreshes = [0]

        def refresher():
            while not writers_done.wait(refresh_ms / 1000.0):
                a.refresh("ingest")
                refreshes[0] += 1

        busy_lats = []
        busy_in_window = [0]

        def querier():
            i = 0
            while not writers_done.is_set() \
                    or len(busy_lats) < MIN_BUSY_QUERIES:
                t0 = time.perf_counter()
                a.search("ingest", _query(i))
                busy_lats.append((time.perf_counter() - t0) * 1000.0)
                if not writers_done.is_set():
                    busy_in_window[0] += 1
                i += 1

        helpers = [threading.Thread(target=refresher),
                   threading.Thread(target=querier)]
        ws = [threading.Thread(target=writer) for _ in range(nwriters)]
        t0 = time.perf_counter()
        for t in helpers + ws:
            t.start()
        for t in ws:
            t.join()
        write_wall = time.perf_counter() - t0
        writers_done.set()
        for t in helpers:
            t.join()
        a.refresh("ingest")         # publish the tail
        docs_per_s = round(ndocs / write_wall, 1)
        arr = np.asarray(busy_lats)
        busy = {"n": len(busy_lats),
                "in_write_window": busy_in_window[0],
                "p50_ms": round(float(np.percentile(arr, 50)), 3),
                "p99_ms": round(float(np.percentile(arr, 99)), 3)}
        print(f"ingest: {docs_per_s} docs/s over {nwriters} writers, "
              f"{refreshes[0]} mid-stream refreshes, busy queries "
              f"{json.dumps(busy)}", flush=True)

        # ---- phase 4: the federated indexing block ----
        stats = a.indexing_stats()
        if stats["_nodes"]["failed"]:
            raise SystemExit(f"fleet scrape degraded: {stats['_nodes']}")
        blk = stats["indexing"]
        rtv = blk["refresh"]["refresh_to_visible_ms"]
        if rtv["count"] < ndocs:
            raise SystemExit(
                f"refresh-to-visible sketch saw {rtv['count']} docs "
                f"< {ndocs} ingested — the write path lost deltas")

        ratio = (round(busy["p99_ms"] / idle["p99_ms"], 4)
                 if idle["p99_ms"] else None)
        ingest_block = {
            "protocol": f"2-node fleet (1 child process); {nseed} seed "
                        f"docs then {ndocs} docs over {nwriters} "
                        f"writer threads with a {refresh_ms:.0f}ms "
                        f"refresher and a live query thread; "
                        f"percentiles from the fleet-MERGED sketch",
            "nodes": stats["_nodes"]["total"],
            "docs": ndocs,
            "writer_threads": nwriters,
            "write_errors": werrors[0],
            "docs_per_s": docs_per_s,
            "seed_docs_per_s": seed_docs_per_s,
            "refresh_interval_ms": refresh_ms,
            "refreshes_mid_stream": refreshes[0],
            "refresh_to_visible": {"count": rtv["count"],
                                   "p50_ms": rtv["p50_ms"],
                                   "p95_ms": rtv["p95_ms"]},
            "refresh_total": blk["refresh"]["total"],
            "refresh_stages_ms": {
                k: v["sum_ms"] for k, v in
                blk["refresh"]["stages"].items()},
            "replica_write_through": blk["replica"]["write_through"],
            "query_p99_ms_baseline": idle["p99_ms"],
            "query_p99_ms_while_indexing": busy["p99_ms"],
            "query_p99_degradation_ratio": ratio,
            "queries_idle": idle,
            "queries_busy": busy,
        }

        out_path = os.path.join(_REPO, "BENCH_out.json")
        extra = {"ingest": ingest_block}
        if os.path.exists(out_path):
            try:
                with open(out_path) as fh:
                    prev = (json.load(fh).get("extra") or {})
                # the ingest-obs overhead pair rides along when
                # measure_concurrency.py ran first
                if "concurrency" in prev:
                    extra["concurrency"] = prev["concurrency"]
            except (ValueError, OSError):
                pass
        doc = {"metric": "ingest_docs_per_s", "value": docs_per_s,
               "unit": "docs/sec", "vs_baseline": None, "extra": extra}
        with open(out_path, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(json.dumps(doc, indent=1, sort_keys=True), flush=True)
        return 0
    finally:
        if child.poll() is None:
            child.kill()
        a.stop()


if __name__ == "__main__":
    sys.exit(main())
