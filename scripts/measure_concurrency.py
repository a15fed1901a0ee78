"""Closed-loop concurrency benchmark for the serving scheduler, on the
8-virtual-device CPU mesh (no chip needed): index a scaled-down bench
corpus across 4 shards, then hammer the product search path with
N ∈ {1, 8, 32, 64} client threads, over the bench's match + filtered-bool
mix, across modes: scheduler OFF, and scheduler ON at each pipeline
depth in CONC_DEPTHS (default 1,2 — depth 1 is the synchronous PR 4
dispatcher, depth ≥ 2 the pipelined launch/fetch split).

Per (N, mode) cell it reports QPS, p50/p95 request latency (DDSketch
percentiles from utils/metrics.py — the registry's bin math), device
scoring-program invocations (`mesh.launches` + `fastpath.launches`), the
mean flushed batch size, and for scheduler-on cells the pipeline stage
accounting (launch_s / fetch_s / overlap ratio) plus launch→fetch p50/p95;
it asserts every response is byte-identical (modulo wall-clock `took`)
across ALL cells — pipeline on/off included — and gates: at 32 threads
the scheduler cuts program invocations >= 4x with a mean batch >= 4, and
the pipelined path (max depth) beats depth-1 on throughput OR stage
overlap.

A final flight-recorder pair re-runs the (32-thread, deepest-depth) cell
with the recorder pinned ON vs OFF (obs/flight_recorder.py; on is the
process default) — responses must stay byte-identical in both, and the
recorder-overhead gate requires recorder-on qps >= 0.98x recorder-off
(`extra.concurrency.recorder_overhead_32t` in the BENCH json). The pair
is box-condition robust: one warmup cell, then alternating
off/on/on/off/off/on reps in the SAME process (each label early, middle
and late cancels warmup/thermal/neighbor drift), gated on the paired
best-of-reps ratio with the threshold relaxed to the measured
within-label noise floor — a shared container's neighbors swing single
reps 10-20%, which is how PR 7 observed a ~0.3x false red at an
unmodified HEAD. A second
pair does the same for HBM-ledger + per-query cost accounting
(obs/query_cost.py) on the direct host-loop path (scheduler and mesh
off, where the accounting engages): cost-on vs cost-off under the same
alternating-reps/noise-floor protocol with byte-identical responses
(`extra.concurrency.cost_overhead_32t`), and
the run stamps `extra.hbm` (peak resident bytes by tenant kind) +
`extra.bytes_per_query` (predicted/actual DDSketch percentiles) — the
committed byte-domain baseline for ROADMAP item 1. A third pair does
the same for the time-series sampler + armed SLO engine
(obs/timeseries.py + obs/slo.py, 50 ms ticks — 20x the production
rate): byte-identical responses, sampler-on qps >= 0.98x off
(`extra.concurrency.sampler_overhead_32t`), and zero SLO false alarms
on the clean run. A fourth pair does the same for the query-insights
engine (obs/insights.py; ISSUE 12): per-search fingerprinting + the
space-saving heavy-hitter sketch pinned ON vs OFF, byte-identical
responses, paired best-of-reps qps >= 0.98x (noise-floored) →
`extra.concurrency.insights_overhead_32t`. A fifth pair (ISSUE 16) does
the same for the runtime lock-witness sanitizer
(devtools/lockwitness.py) armed vs unarmed —
`extra.concurrency.lockwitness_overhead_32t` — and additionally gates
the armed cells on zero witnessed inversions and zero acquisition-order
conflicts against the committed lock_order.json. A sixth pair
(ISSUE 18) covers the WRITE path: bulk-indexing docs/s with the ingest
observatory (obs/ingest_obs.py) pinned ON vs OFF — 32 submit threads
drain a deterministic chunk list into a recreated index per rep, under
the same alternating-reps/noise-floor protocol, with bulk responses
byte-identical between the on and off cells (digests normalize `took`
and `_seq_no`, whose assignment order is submit-thread interleaving) →
`extra.concurrency.ingest_obs_overhead_32t`.

Results land in BENCH_out.json under `extra.concurrency` (merged into an
existing bench emission when present). Run:
    python scripts/measure_concurrency.py [ndocs]
Env: CONC_NQ (queries per cell, default 256), CONC_THREADS (comma list,
default 1,8,32,64), CONC_DEPTHS (comma list, default 1,2),
CONC_ASSERT=0 to report without gating, CONC_INGEST_DOCS (bulk docs per
ingest-pair rep, default 4000), CONC_ONLY=ingest to run JUST the
ingest-obs pair against a bare node (no search corpus) and merge it
into BENCH_out.json — the cheap re-measure path for write-path-only
changes.
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def build_client(ndocs: int):
    import bench as B
    from opensearch_tpu.cluster.node import Node
    from opensearch_tpu.parallel import MeshSearchService
    from opensearch_tpu.rest.client import RestClient

    rng = np.random.default_rng(3)
    starts, doc_ids, tfs, dl, df_per_term = B._cached(
        f"body_{ndocs}", lambda: B.build_corpus(ndocs), True)
    queries = B.pick_queries(df_per_term, 4096)
    vocab_strs = [f"t{i:07d}" for i in range(len(df_per_term))]

    svc = MeshSearchService()
    client = RestClient(node=Node(mesh_service=svc))
    client.indices.create("bench", {
        "settings": {"number_of_shards": 4},
        "mappings": {"properties": {
            "body": {"type": "text"}, "status": {"type": "keyword"},
            "price": {"type": "integer"}}}})
    status_vals = ["draft", "review", "published"]
    order = np.argsort(doc_ids, kind="stable")
    term_of_posting = np.repeat(
        np.arange(len(df_per_term)), np.diff(starts).astype(np.int64))
    d_sorted = doc_ids[order]
    t_sorted = term_of_posting[order]
    tf_sorted = tfs[order].astype(np.int64)
    bounds = np.searchsorted(d_sorted, np.arange(ndocs + 1))
    bulk = []
    for d in range(ndocs):
        a, b = bounds[d], bounds[d + 1]
        toks = np.repeat(t_sorted[a:b], tf_sorted[a:b])
        bulk.append({"index": {"_index": "bench", "_id": str(d)}})
        bulk.append({"body": " ".join(vocab_strs[t] for t in toks[:48]),
                     "status": status_vals[d % 3],
                     "price": int(rng.integers(0, 1000))})
        if len(bulk) >= 20_000:
            client.bulk(bulk)
            bulk = []
    if bulk:
        client.bulk(bulk)
    client.indices.refresh("bench")
    client.indices.forcemerge("bench")
    return client, queries, vocab_strs


def make_bodies(queries, vocab_strs, nq: int):
    """The bench mix the mesh serves: 60% two-term match, 40% filtered
    bool — the cross-request coalescing target."""
    bodies = []
    for i in range(nq):
        q = queries[i % len(queries)]
        if i % 5 < 3:
            bodies.append({"query": {"match": {"body": (
                f"{vocab_strs[q[0]]} {vocab_strs[q[1]]}")}}, "size": 10})
        else:
            bodies.append({"query": {"bool": {
                "must": [{"match": {"body": vocab_strs[q[0]]}}],
                "filter": [{"term": {"status": "published"}}]}},
                "size": 10})
    return bodies


def strip_took(resp: dict) -> str:
    return json.dumps({k: v for k, v in resp.items() if k != "took"},
                      sort_keys=True)


def run_cell(client, bodies, nthreads: int, mode, tag: str,
             recorder=None, cost=None, sampler=None, insights=None,
             lockwitness=None):
    """Closed loop: `nthreads` client threads drain the shared query list;
    every thread records its request wall into a DDSketch histogram.
    `mode` is None for scheduler-off, or a pipeline depth (int) for a
    fresh scheduler-on cell at that depth. `recorder` pins the flight
    recorder for the cell (True/False; None = leave the process default,
    which is ON) — the recorder-overhead gate compares a pinned-on vs
    pinned-off pair at 32 threads. `cost` pins per-query cost accounting
    (obs/query_cost.py) the same way for the ledger+cost overhead gate.
    `sampler` pins the time-series sampler + armed SLO engine
    (obs/timeseries.py + obs/slo.py, running at a 50 ms tick — 20x the
    production default rate) for the sampler-overhead gate. `insights`
    pins the query-insights engine (obs/insights.py; on is the process
    default) for the insights-overhead gate — fingerprinting + the
    heavy-hitter sketch must ride the search boundary for ~free.
    `lockwitness` pins the runtime lock-witness sanitizer
    (devtools/lockwitness.py) — armed BEFORE the cell's fresh scheduler
    is constructed, so the locks the serving path actually contends
    (the dispatcher condition handshake) are wrapped and every
    acquisition order is recorded, for the lockwitness-overhead gate."""
    from opensearch_tpu.obs.flight_recorder import RECORDER
    from opensearch_tpu.obs.insights import INSIGHTS
    from opensearch_tpu.obs.slo import SLO_ENGINE, default_slos
    from opensearch_tpu.obs.timeseries import SAMPLER
    from opensearch_tpu.serving import SchedulerConfig, ServingScheduler
    from opensearch_tpu.utils.metrics import METRICS, MetricsRegistry

    node = client.node
    rec_before = RECORDER.enabled
    if recorder is not None:
        RECORDER.enabled = bool(recorder)
    ins_before = INSIGHTS.enabled
    if insights is not None:
        INSIGHTS.reset()       # per-cell sketch state, bounded ring
        INSIGHTS.enabled = bool(insights)
    cost_before = os.environ.get("OPENSEARCH_TPU_COST")
    if cost is not None:
        os.environ["OPENSEARCH_TPU_COST"] = "1" if cost else "0"
    sampler_interval_before = SAMPLER.interval_s
    if sampler:
        SAMPLER.stop()
        SAMPLER.reset()
        SAMPLER.interval_s = 0.05
        SLO_ENGINE.arm(default_slos(fast_window_s=2.0,
                                    slow_window_s=10.0))
        SAMPLER.ensure_started()
    RECORDER.reset()       # bound ring memory + per-cell trigger state
    wit_state = None
    if lockwitness is not None:
        from opensearch_tpu.devtools import lockwitness as _lw
        _lw.uninstall()                 # clean slate either way
        if lockwitness:
            # armed BEFORE the fresh scheduler below is constructed —
            # the witness wraps locks at creation time
            wit_state = _lw.install(strict=False)
            _lw.reset()
    old_serving = node.serving
    sched_on = mode is not None
    if sched_on:
        # fresh scheduler per cell: per-instance stage/percentile
        # accounting starts at zero, so the cell's pipeline numbers are
        # the cell's alone
        node.serving = ServingScheduler(
            node, SchedulerConfig(pipeline_depth=int(mode)), enabled=True)
    else:
        node.serving.enabled = False
    mesh = node.mesh_service      # None on the direct-path cost pair
    reg = MetricsRegistry()
    hist = reg.histogram("request_ms")
    serving0 = node.serving.stats()
    launches0 = mesh.launches if mesh is not None else 0
    fp0 = METRICS.counter("fastpath.launches").value
    results = [None] * len(bodies)
    errors = []
    cursor = [0]
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                i = cursor[0]
                if i >= len(bodies):
                    return
                cursor[0] = i + 1
            body = dict(bodies[i], _bench=f"conc-{tag}-{i}")
            t0 = time.perf_counter()
            try:
                results[i] = client.search("bench", body)
            except Exception as e:              # noqa: BLE001
                # record and keep draining: one transient failure must
                # not silently shrink the cell (the errored gate still
                # fails the run, with honest per-cell counts)
                errors.append(f"q{i}: {e!r}")
                continue
            hist.record((time.perf_counter() - t0) * 1000.0)

    t0 = time.time()
    threads = [threading.Thread(target=worker) for _ in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.time() - t0
    serving1 = node.serving.stats()
    launches = ((mesh.launches if mesh is not None else 0) - launches0) + \
        (METRICS.counter("fastpath.launches").value - fp0)
    flushes = serving1["flushes"] - serving0["flushes"]
    batched = serving1["batched_served"] - serving0["batched_served"]
    snap = hist.snapshot((50, 95))
    from opensearch_tpu.obs import query_cost as _qc
    cell = {
        "threads": nthreads,
        "scheduler": "on" if sched_on else "off",
        "recorder": "on" if RECORDER.enabled else "off",
        "cost": "on" if _qc.enabled() else "off",
        "mode": "off" if not sched_on else f"d{int(mode)}",
        "n": len(bodies),
        "errors": len(errors),
        "wall_s": round(wall, 3),
        "qps": round(len(bodies) / wall, 1),
        "p50_ms": snap["p50_ms"],
        "p95_ms": snap["p95_ms"],
        "program_invocations": int(launches),
        "batched_served": batched,
        "flushes": flushes,
        "mean_batch": round(batched / flushes, 2) if flushes else None,
    }
    if sched_on:
        pipe = serving1["pipeline"]
        cell["pipeline_depth"] = pipe["depth"]
        cell["overlap_ratio"] = pipe["overlap_ratio"]
        cell["launch_s"] = pipe["launch_s"]
        cell["fetch_s"] = pipe["fetch_s"]
        cell["inflight_peak"] = pipe["inflight_peak"]
        ltf = serving1.get("launch_to_fetch_ms") or {}
        if ltf.get("count"):
            cell["launch_to_fetch_p50_ms"] = ltf.get("p50_ms")
            cell["launch_to_fetch_p95_ms"] = ltf.get("p95_ms")
        node.serving.close()
    node.serving = old_serving
    if recorder is not None:
        RECORDER.enabled = rec_before
    if insights is not None:
        cell["insights"] = "on" if INSIGHTS.enabled else "off"
        cell["insights_entries"] = INSIGHTS.stats()["entries"]
        INSIGHTS.enabled = ins_before
    if cost is not None:
        if cost_before is None:
            os.environ.pop("OPENSEARCH_TPU_COST", None)
        else:
            os.environ["OPENSEARCH_TPU_COST"] = cost_before
    if lockwitness is not None:
        from opensearch_tpu.devtools import lockwitness as _lw
        cell["lockwitness"] = "on" if lockwitness else "off"
        if lockwitness:
            rep = _lw.verify_against(
                os.path.join(_REPO, "lock_order.json"))
            cell["lockwitness_wrapped"] = wit_state.wrapped
            cell["lockwitness_edges"] = len(_lw.edges())
            cell["lockwitness_inversions"] = len(_lw.inversions())
            cell["lockwitness_order_conflicts"] = \
                len(rep["order_conflicts"])
            _lw.uninstall()
    if sampler is not None:
        cell["sampler"] = "on" if sampler else "off"
    if sampler:
        cell["sampler_ticks"] = SAMPLER.stats()["ticks"]
        cell["slo_alerts"] = SLO_ENGINE.alerts_fired
        SAMPLER.stop()
        SLO_ENGINE.disarm()
        SAMPLER.interval_s = sampler_interval_before
        SAMPLER.reset()
    if errors:
        cell["first_errors"] = errors[:3]
    return cell, results


def _ingest_chunks(ndocs: int, chunk: int):
    """Deterministic bulk bodies for the ingest pair: the same docs in
    the same chunk order every rep, so the only variable between the
    obs-on and obs-off cells is the observatory itself."""
    lines = []
    for d in range(ndocs):
        lines.append({"index": {"_index": "ingestbench",
                                "_id": f"d{d:06d}"}})
        lines.append({"body": f"w{d % 97} w{d % 311} w{d % 13} common",
                      "price": d % 1000})
    step = 2 * chunk
    return [lines[i:i + step] for i in range(0, len(lines), step)]


def strip_bulk_variant(resp) -> str:
    """Bulk-response digest for the ingest pair: zeroes `took` and
    `_seq_no` — with 32 submit threads the per-shard seq assignment
    order is interleaving-dependent — so ids, results, statuses and
    the error flag must be byte-identical between cells."""
    def scrub(o):
        if isinstance(o, dict):
            return {k: (0 if k in ("took", "_seq_no") else scrub(v))
                    for k, v in o.items()}
        if isinstance(o, list):
            return [scrub(x) for x in o]
        return o
    return json.dumps(scrub(resp), sort_keys=True)


def run_ingest_cell(client, chunks, nthreads: int, tag: str,
                    obs_on: bool):
    """One bulk-indexing rep: recreate the bench index, drain the chunk
    list from `nthreads` submit threads (writes serialize on the index
    write lock — the realistic concurrent-bulk shape), refresh, report
    docs/s. The ingest observatory is pinned for the cell."""
    from opensearch_tpu.obs import ingest_obs as _iobs
    prev = _iobs.set_enabled(obs_on)
    try:
        if client.indices.exists("ingestbench"):
            client.indices.delete("ingestbench")
        client.indices.create("ingestbench", {
            "settings": {"number_of_shards": 2},
            "mappings": {"properties": {"body": {"type": "text"},
                                        "price": {"type": "integer"}}}})
        results = [None] * len(chunks)
        errors = [0]
        pos = [0]
        lock = threading.Lock()

        def worker():
            while True:
                with lock:
                    i = pos[0]
                    if i >= len(chunks):
                        return
                    pos[0] += 1
                try:
                    results[i] = client.bulk(chunks[i])
                except Exception:
                    with lock:
                        errors[0] += 1

        t0 = time.perf_counter()
        ts = [threading.Thread(target=worker) for _ in range(nthreads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        client.indices.refresh("ingestbench")
        wall = time.perf_counter() - t0
    finally:
        _iobs.set_enabled(prev)
    ndocs = sum(len(c) // 2 for c in chunks)
    cell = {"tag": tag, "threads": nthreads, "mode": "bulk",
            "ingest_obs": "on" if obs_on else "off", "docs": ndocs,
            "errors": errors[0], "wall_s": round(wall, 4),
            "qps": round(ndocs / max(wall, 1e-9), 1)}
    return cell, results


def ingest_obs_pair(client, rthreads: int):
    """The ingest-obs overhead pair under the standard protocol: one
    warmup rep, then alternating off/on/on/off bulk reps; returns
    (summary block, errored count). Cells print as they land but are
    NOT merged into the search grid's cell list — docs/s and search
    qps are different units."""
    ing_docs = int(os.environ.get("CONC_INGEST_DOCS", 4000))
    chunks = _ingest_chunks(ing_docs, 200)
    reps = {"ingest_obs_off": [], "ingest_obs_on": []}
    digests = {}
    errors = 0
    run_ingest_cell(client, chunks, rthreads,
                    f"{rthreads}-bulk-iobs-warmup", True)
    for rep, (olabel, oflag) in enumerate(
            (("ingest_obs_off", False), ("ingest_obs_on", True),
             ("ingest_obs_on", True), ("ingest_obs_off", False))):
        tag = f"{rthreads}-bulk-{olabel}-r{rep}"
        cell, results = run_ingest_cell(client, chunks, rthreads, tag,
                                        oflag)
        errors += cell["errors"]
        digests.setdefault(olabel, [strip_bulk_variant(r)
                                    if r is not None else None
                                    for r in results])
        reps[olabel].append(cell)
        print(json.dumps(cell), flush=True)
    pair = {lab: max(rr, key=lambda c: c["qps"])
            for lab, rr in reps.items()}
    bad = sum(1 for a, b in zip(digests["ingest_obs_off"],
                                digests["ingest_obs_on"]) if a != b)
    on_c, off_c = pair["ingest_obs_on"], pair["ingest_obs_off"]
    noise = max(
        (1.0 - min(c["qps"] for c in rr)
         / max(max(c["qps"] for c in rr), 1e-9))
        for rr in reps.values())
    block = {
        "threads": rthreads, "mode": "bulk",
        "protocol": "warmup + alternating off/on/on/off bulk reps into "
                    "a recreated index; paired best-of-reps docs/s "
                    "ratio, noise-floor threshold; digests normalize "
                    "took + _seq_no (seq order is submit-thread "
                    "interleaving)",
        "docs": ing_docs,
        "ingest_obs_on_docs_per_s": on_c["qps"],
        "ingest_obs_off_docs_per_s": off_c["qps"],
        "ingest_obs_on_reps": [c["qps"]
                               for c in reps["ingest_obs_on"]],
        "ingest_obs_off_reps": [c["qps"]
                                for c in reps["ingest_obs_off"]],
        "identical_responses": bad == 0,
        "noise_floor": round(noise, 4),
        "qps_ratio": round(on_c["qps"] / max(off_c["qps"], 1e-9), 4),
        "gate_threshold": round(min(0.98, 1.0 - noise), 4),
    }
    return block, errors


def _gate_ingest_pair(gp) -> None:
    if gp["qps_ratio"] < gp["gate_threshold"]:
        raise SystemExit(
            f"ingest-obs overhead gate failed: obs-on bulk docs/s is "
            f"{gp['qps_ratio']}x obs-off (< {gp['gate_threshold']}x; "
            f"noise floor {gp['noise_floor']}) at {gp['threads']} "
            f"threads")
    if not gp["identical_responses"]:
        raise SystemExit(
            "bulk responses diverged between ingest-obs on and off "
            "cells — instrumentation changed write-path behavior")


def _merge_bench_out(update_concurrency: dict) -> dict:
    """Merge pair blocks into BENCH_out.json's extra.concurrency
    without clobbering a fuller emission."""
    out_path = os.path.join(_REPO, "BENCH_out.json")
    try:
        with open(out_path) as f:
            bench_doc = json.load(f)
    except (OSError, ValueError):
        bench_doc = {"metric": "bm25_rest_qps_per_chip", "value": None,
                     "unit": "queries/sec", "vs_baseline": None,
                     "extra": {"status": "concurrency_only"}}
    conc = bench_doc.setdefault("extra", {}).setdefault(
        "concurrency", {})
    conc.update(update_concurrency)
    with open(out_path, "w") as f:
        json.dump(bench_doc, f, indent=2)
    return bench_doc


def main():
    ndocs = int(sys.argv[1]) if len(sys.argv) > 1 else 60_000
    nq = int(os.environ.get("CONC_NQ", 256))
    thread_counts = [int(t) for t in
                     os.environ.get("CONC_THREADS", "1,8,32,64").split(",")]
    depths = [int(d) for d in
              os.environ.get("CONC_DEPTHS", "1,2").split(",")]
    gate = os.environ.get("CONC_ASSERT", "1") not in ("0", "")
    if os.environ.get("CONC_ONLY") == "ingest":
        # write-path-only re-measure: no search corpus, just the pair
        from opensearch_tpu.cluster.node import Node
        from opensearch_tpu.parallel import MeshSearchService
        from opensearch_tpu.rest.client import RestClient
        client = RestClient(node=Node(mesh_service=MeshSearchService()))
        rthreads = int(os.environ.get("CONC_INGEST_THREADS", "32"))
        block, errs = ingest_obs_pair(client, rthreads)
        _merge_bench_out({"ingest_obs_overhead_32t": block})
        print(json.dumps({"ingest_obs_overhead_32t": block}), flush=True)
        if gate:
            if errs:
                raise SystemExit(f"{errs} bulk request(s) errored")
            _gate_ingest_pair(block)
        print("OK", flush=True)
        return
    t0 = time.time()
    client, queries, vocab_strs = build_client(ndocs)
    bodies = make_bodies(queries, vocab_strs, nq)
    print(f"setup {time.time()-t0:.1f}s ndocs={ndocs} nq={nq} "
          f"depths={depths}", flush=True)

    modes = [None] + depths        # off, then scheduler-on per depth
    canonical = None
    cells = []
    mismatched = 0
    errored = 0
    by_key = {}
    for nthreads in thread_counts:
        for mode in modes:
            mname = "off" if mode is None else f"d{mode}"
            tag = f"{nthreads}-{mname}"
            cell, results = run_cell(client, bodies, nthreads, mode, tag)
            errored += cell["errors"]
            digests = [strip_took(r) if r is not None else None
                       for r in results]
            if canonical is None:
                canonical = digests
            bad = sum(1 for a, b in zip(digests, canonical) if a != b)
            cell["identical_responses"] = bad == 0
            mismatched += bad
            cells.append(cell)
            by_key[(nthreads, mname)] = cell
            print(json.dumps(cell), flush=True)

    # recorder-overhead pair: the (32-thread, deepest-pipeline) cell with
    # the flight recorder pinned ON vs OFF — the black box must ride
    # along for ~free (gate: on-qps >= 0.98x off). Box-condition
    # robustness (ISSUE 8; PR 7 measured a ~0.3x FALSE red at an
    # unmodified HEAD on a noisy container): both labels run in THIS
    # process, in ALTERNATING order (off/on/on/off — each label runs once
    # early and once late, cancelling warmup and thermal/neighbor drift),
    # after a warmup cell at the same shape, and the gate compares the
    # PAIRED best-of-reps ratio — a GC pause or cron burst that lands in
    # one rep no longer fails the run.
    rec_pair = {}
    rthreads = 32 if 32 in thread_counts else thread_counts[-1]
    rdepth = max(depths)
    run_cell(client, bodies, rthreads, rdepth,
             f"{rthreads}-d{rdepth}-rec-warmup")
    rec_reps = {"rec_on": [], "rec_off": []}
    for rep, (rlabel, rflag) in enumerate(
            (("rec_off", False), ("rec_on", True),
             ("rec_on", True), ("rec_off", False),
             ("rec_off", False), ("rec_on", True))):
        tag = f"{rthreads}-d{rdepth}-{rlabel}-r{rep}"
        cell, results = run_cell(client, bodies, rthreads, rdepth, tag,
                                 recorder=rflag)
        errored += cell["errors"]
        digests = [strip_took(r) if r is not None else None
                   for r in results]
        bad = sum(1 for a, b in zip(digests, canonical) if a != b)
        cell["identical_responses"] = bad == 0
        mismatched += bad
        cells.append(cell)
        rec_reps[rlabel].append(cell)
        print(json.dumps(cell), flush=True)
    rec_pair = {lab: max(reps, key=lambda c: c["qps"])
                for lab, reps in rec_reps.items()}

    # ledger+cost overhead pair: scheduler AND mesh off, so every request
    # runs the host shard loop where per-query cost accounting engages
    # (obs/query_cost.py) — pinned cost OFF vs ON back-to-back after a
    # warmup pass (the direct path pays its XLA compiles here; the grid
    # cells above never exercised it, and a cold first cell would bench
    # compile time, not accounting). Gate: cost-on qps >= 0.98x cost-off
    # with byte-identical responses BETWEEN the pair's cells (the same
    # discipline as the PR 6 recorder gate; mesh-vs-host parity has its
    # own tests and is not re-litigated here).
    cost_pair = {}
    cost_reps = {"cost_off": [], "cost_on": []}
    cost_digests = {}
    mesh_saved = client.node.mesh_service
    client.node.mesh_service = None
    try:
        run_cell(client, bodies, rthreads, None,
                 f"{rthreads}-direct-warmup", cost=False)
        # same box-noise discipline as the recorder pair: alternating
        # reps in one process, byte-identity within the pair, paired
        # best-of-reps ratio against a noise-floor-relaxed threshold
        for rep, (clabel, cflag) in enumerate(
                (("cost_off", False), ("cost_on", True),
                 ("cost_on", True), ("cost_off", False))):
            tag = f"{rthreads}-direct-{clabel}-r{rep}"
            cell, results = run_cell(client, bodies, rthreads, None, tag,
                                     cost=cflag)
            errored += cell["errors"]
            cost_digests.setdefault(clabel, [strip_took(r)
                                             if r is not None else None
                                             for r in results])
            cells.append(cell)
            cost_reps[clabel].append(cell)
            print(json.dumps(cell), flush=True)
        cost_pair = {lab: max(reps, key=lambda c: c["qps"])
                     for lab, reps in cost_reps.items()}
        pair_bad = sum(1 for a, b in zip(cost_digests["cost_off"],
                                         cost_digests["cost_on"])
                       if a != b)
        cost_pair["cost_on"]["identical_responses"] = pair_bad == 0
        cost_pair["cost_off"]["identical_responses"] = pair_bad == 0
        mismatched += pair_bad
    finally:
        client.node.mesh_service = mesh_saved

    # sampler-overhead pair (ISSUE 10): the (32-thread, deepest-depth)
    # cell with the time-series sampler + armed SLO engine pinned ON
    # (50 ms ticks — 20x the production default rate) vs OFF, under the
    # same alternating-reps/noise-floor protocol as the recorder and
    # cost gates: byte-identical responses, paired best-of-reps qps
    # ratio >= 0.98x (noise-floor relaxed). Continuous retention and
    # burn-rate evaluation must ride along for ~free.
    samp_pair = {}
    samp_reps = {"sampler_off": [], "sampler_on": []}
    run_cell(client, bodies, rthreads, rdepth,
             f"{rthreads}-d{rdepth}-samp-warmup")
    for rep, (slabel, sflag) in enumerate(
            (("sampler_off", False), ("sampler_on", True),
             ("sampler_on", True), ("sampler_off", False))):
        tag = f"{rthreads}-d{rdepth}-{slabel}-r{rep}"
        cell, results = run_cell(client, bodies, rthreads, rdepth, tag,
                                 sampler=sflag)
        errored += cell["errors"]
        digests = [strip_took(r) if r is not None else None
                   for r in results]
        bad = sum(1 for a, b in zip(digests, canonical) if a != b)
        cell["identical_responses"] = bad == 0
        mismatched += bad
        cells.append(cell)
        samp_reps[slabel].append(cell)
        print(json.dumps(cell), flush=True)
    samp_pair = {lab: max(reps, key=lambda c: c["qps"])
                 for lab, reps in samp_reps.items()}

    # insights-overhead pair (ISSUE 12): the (32-thread, deepest-depth)
    # cell with the query-insights engine pinned ON vs OFF — per-search
    # fingerprinting + the space-saving heavy-hitter sketch must ride
    # the search boundary for ~free, under the same alternating-reps /
    # noise-floor / byte-identity protocol as the other three gates.
    ins_pair = {}
    ins_reps = {"insights_off": [], "insights_on": []}
    run_cell(client, bodies, rthreads, rdepth,
             f"{rthreads}-d{rdepth}-ins-warmup")
    for rep, (ilabel, iflag) in enumerate(
            (("insights_off", False), ("insights_on", True),
             ("insights_on", True), ("insights_off", False))):
        tag = f"{rthreads}-d{rdepth}-{ilabel}-r{rep}"
        cell, results = run_cell(client, bodies, rthreads, rdepth, tag,
                                 insights=iflag)
        errored += cell["errors"]
        digests = [strip_took(r) if r is not None else None
                   for r in results]
        bad = sum(1 for a, b in zip(digests, canonical) if a != b)
        cell["identical_responses"] = bad == 0
        mismatched += bad
        cells.append(cell)
        ins_reps[ilabel].append(cell)
        print(json.dumps(cell), flush=True)
    ins_pair = {lab: max(reps, key=lambda c: c["qps"])
                for lab, reps in ins_reps.items()}

    # lockwitness-overhead pair (ISSUE 16): the (32-thread,
    # deepest-depth) cell with the runtime lock-witness sanitizer
    # (devtools/lockwitness.py) armed vs unarmed — per acquire the
    # witness costs one thread-local append plus a dict probe per held
    # lock, and the gate proves that rides along for ~free under the
    # same alternating-reps / noise-floor / byte-identity protocol as
    # the other four gates. The armed cells double as a production-shaped
    # witness run: zero inversions and zero order conflicts against the
    # committed lock_order.json are gated too.
    lw_pair = {}
    lw_reps = {"lockwitness_off": [], "lockwitness_on": []}
    run_cell(client, bodies, rthreads, rdepth,
             f"{rthreads}-d{rdepth}-lw-warmup")
    for rep, (wlabel, wflag) in enumerate(
            (("lockwitness_off", False), ("lockwitness_on", True),
             ("lockwitness_on", True), ("lockwitness_off", False))):
        tag = f"{rthreads}-d{rdepth}-{wlabel}-r{rep}"
        cell, results = run_cell(client, bodies, rthreads, rdepth, tag,
                                 lockwitness=wflag)
        errored += cell["errors"]
        digests = [strip_took(r) if r is not None else None
                   for r in results]
        bad = sum(1 for a, b in zip(digests, canonical) if a != b)
        cell["identical_responses"] = bad == 0
        mismatched += bad
        cells.append(cell)
        lw_reps[wlabel].append(cell)
        print(json.dumps(cell), flush=True)
    lw_pair = {lab: max(reps, key=lambda c: c["qps"])
               for lab, reps in lw_reps.items()}

    # ingest-obs overhead pair (ISSUE 18): write-path telemetry must
    # ride bulk indexing for ~free — same protocol, bulk workload
    ing_block, ing_err = ingest_obs_pair(client, rthreads)
    errored += ing_err

    summary = {"ndocs": ndocs, "nq": nq,
               "devices": len(jax.devices()),
               "mix": "60% match2 / 40% filtered bool",
               "identical_responses": mismatched == 0,
               "pipeline_depths": depths,
               "cells": cells}
    # HBM + bytes/query stamps for the BENCH json (ISSUE 7 baseline):
    # peak resident bytes by tenant kind and the per-query byte
    # percentiles accumulated by the cost-on cell
    from opensearch_tpu.obs import query_cost as _query_cost
    from opensearch_tpu.obs.hbm_ledger import LEDGER
    hbm_stamp = LEDGER.peak_stamp()
    bpq_stamp = _query_cost.bytes_per_query_stamp()
    summary["hbm"] = hbm_stamp
    summary["bytes_per_query"] = bpq_stamp
    if cost_pair:
        on_c, off_c = cost_pair["cost_on"], cost_pair["cost_off"]
        cnoise = max(
            (1.0 - min(c["qps"] for c in reps)
             / max(max(c["qps"] for c in reps), 1e-9))
            for reps in cost_reps.values())
        summary["cost_overhead_32t"] = {
            "threads": rthreads, "mode": "direct",
            "protocol": "warmup + alternating off/on/on/off reps; paired "
                        "best-of-reps ratio, noise-floor threshold",
            "cost_on_qps": on_c["qps"],
            "cost_off_qps": off_c["qps"],
            "cost_on_reps": [c["qps"] for c in cost_reps["cost_on"]],
            "cost_off_reps": [c["qps"] for c in cost_reps["cost_off"]],
            "noise_floor": round(cnoise, 4),
            "qps_ratio": round(on_c["qps"] / max(off_c["qps"], 1e-9), 4),
            "gate_threshold": round(min(0.98, 1.0 - cnoise), 4),
        }
    if samp_pair:
        on_c, off_c = samp_pair["sampler_on"], samp_pair["sampler_off"]
        snoise = max(
            (1.0 - min(c["qps"] for c in reps)
             / max(max(c["qps"] for c in reps), 1e-9))
            for reps in samp_reps.values())
        summary["sampler_overhead_32t"] = {
            "threads": rthreads, "mode": f"d{rdepth}",
            "protocol": "warmup + alternating off/on/on/off reps; "
                        "paired best-of-reps ratio, noise-floor "
                        "threshold; sampler at 50ms ticks + default "
                        "SLOs armed",
            "sampler_on_qps": on_c["qps"],
            "sampler_off_qps": off_c["qps"],
            "sampler_on_reps": [c["qps"] for c in
                                samp_reps["sampler_on"]],
            "sampler_off_reps": [c["qps"] for c in
                                 samp_reps["sampler_off"]],
            "sampler_ticks": max(c.get("sampler_ticks", 0)
                                 for c in samp_reps["sampler_on"]),
            "slo_false_alarms": max(c.get("slo_alerts", 0)
                                    for c in samp_reps["sampler_on"]),
            "noise_floor": round(snoise, 4),
            "qps_ratio": round(on_c["qps"] / max(off_c["qps"], 1e-9), 4),
            "gate_threshold": round(min(0.98, 1.0 - snoise), 4),
        }
    if ins_pair:
        on_c, off_c = ins_pair["insights_on"], ins_pair["insights_off"]
        inoise = max(
            (1.0 - min(c["qps"] for c in reps)
             / max(max(c["qps"] for c in reps), 1e-9))
            for reps in ins_reps.values())
        summary["insights_overhead_32t"] = {
            "threads": rthreads, "mode": f"d{rdepth}",
            "protocol": "warmup + alternating off/on/on/off reps; "
                        "paired best-of-reps ratio, noise-floor "
                        "threshold",
            "insights_on_qps": on_c["qps"],
            "insights_off_qps": off_c["qps"],
            "insights_on_reps": [c["qps"] for c in
                                 ins_reps["insights_on"]],
            "insights_off_reps": [c["qps"] for c in
                                  ins_reps["insights_off"]],
            "sketch_entries": max(c.get("insights_entries", 0)
                                  for c in ins_reps["insights_on"]),
            "noise_floor": round(inoise, 4),
            "qps_ratio": round(on_c["qps"] / max(off_c["qps"], 1e-9), 4),
            "gate_threshold": round(min(0.98, 1.0 - inoise), 4),
        }
    if lw_pair:
        on_c, off_c = (lw_pair["lockwitness_on"],
                       lw_pair["lockwitness_off"])
        wnoise = max(
            (1.0 - min(c["qps"] for c in reps)
             / max(max(c["qps"] for c in reps), 1e-9))
            for reps in lw_reps.values())
        summary["lockwitness_overhead_32t"] = {
            "threads": rthreads, "mode": f"d{rdepth}",
            "protocol": "warmup + alternating off/on/on/off reps; "
                        "paired best-of-reps ratio, noise-floor "
                        "threshold; witness armed before the cell's "
                        "scheduler construction",
            "lockwitness_on_qps": on_c["qps"],
            "lockwitness_off_qps": off_c["qps"],
            "lockwitness_on_reps": [c["qps"] for c in
                                    lw_reps["lockwitness_on"]],
            "lockwitness_off_reps": [c["qps"] for c in
                                     lw_reps["lockwitness_off"]],
            "wrapped_locks": max(c.get("lockwitness_wrapped", 0)
                                 for c in lw_reps["lockwitness_on"]),
            "witnessed_edges": max(c.get("lockwitness_edges", 0)
                                   for c in lw_reps["lockwitness_on"]),
            "inversions": sum(c.get("lockwitness_inversions", 0)
                              for c in lw_reps["lockwitness_on"]),
            "order_conflicts": sum(
                c.get("lockwitness_order_conflicts", 0)
                for c in lw_reps["lockwitness_on"]),
            "noise_floor": round(wnoise, 4),
            "qps_ratio": round(on_c["qps"] / max(off_c["qps"], 1e-9), 4),
            "gate_threshold": round(min(0.98, 1.0 - wnoise), 4),
        }
    if ing_block:
        summary["ingest_obs_overhead_32t"] = ing_block
    if rec_pair:
        on_c, off_c = rec_pair["rec_on"], rec_pair["rec_off"]
        # the gate cannot resolve an effect smaller than the box's own
        # within-label rep-to-rep spread: the threshold relaxes to the
        # measured noise floor (a shared container's neighbors routinely
        # swing single reps 10-20% — the PR 7 false red)
        noise = max(
            (1.0 - min(c["qps"] for c in reps)
             / max(max(c["qps"] for c in reps), 1e-9))
            for reps in rec_reps.values())
        summary["recorder_overhead_32t"] = {
            "threads": rthreads, "mode": f"d{rdepth}",
            "protocol": "warmup + alternating off/on/on/off/off/on reps "
                        "in one process; paired best-of-reps ratio, "
                        "threshold relaxed to the within-label noise "
                        "floor",
            "recorder_on_qps": on_c["qps"],
            "recorder_off_qps": off_c["qps"],
            "recorder_on_reps": [c["qps"] for c in rec_reps["rec_on"]],
            "recorder_off_reps": [c["qps"] for c in rec_reps["rec_off"]],
            "noise_floor": round(noise, 4),
            "qps_ratio": round(on_c["qps"] / max(off_c["qps"], 1e-9), 4),
            "gate_threshold": round(min(0.98, 1.0 - noise), 4),
        }
    off32 = by_key.get((32, "off"))
    on32 = by_key.get((32, f"d{depths[0]}"))
    deep = f"d{max(depths)}" if len(depths) > 1 else None
    on32p = by_key.get((32, deep)) if deep else None
    if off32 and on32 and on32["program_invocations"]:
        summary["invocation_reduction_32t"] = round(
            off32["program_invocations"] / on32["program_invocations"], 2)
        summary["mean_batch_32t"] = on32["mean_batch"]
        summary["qps_speedup_32t"] = round(
            on32["qps"] / max(off32["qps"], 1e-9), 2)
    if on32 and on32p:
        # the pipeline acceptance numbers: depth-1 (synchronous) vs the
        # deepest pipelined cell at 32 closed-loop threads
        summary["pipeline_32t"] = {
            "depth1_qps": on32["qps"],
            f"{deep}_qps": on32p["qps"],
            "qps_gain": round(on32p["qps"] / max(on32["qps"], 1e-9), 3),
            "depth1_overlap_ratio": on32.get("overlap_ratio"),
            f"{deep}_overlap_ratio": on32p.get("overlap_ratio"),
        }

    # merge into the BENCH json emission (extra.concurrency)
    out_path = os.path.join(_REPO, "BENCH_out.json")
    try:
        with open(out_path) as f:
            bench_doc = json.load(f)
    except (OSError, ValueError):
        bench_doc = {"metric": "bm25_rest_qps_per_chip", "value": None,
                     "unit": "queries/sec", "vs_baseline": None,
                     "extra": {"status": "concurrency_only"}}
    extra_doc = bench_doc.setdefault("extra", {})
    extra_doc["concurrency"] = summary
    # top-level BENCH stamps (don't clobber a fuller bench.py emission)
    extra_doc.setdefault("hbm", hbm_stamp)
    extra_doc.setdefault("bytes_per_query", bpq_stamp)
    with open(out_path, "w") as f:
        json.dump(bench_doc, f, indent=2)
    print(json.dumps({"summary": {k: v for k, v in summary.items()
                                  if k != "cells"}}), flush=True)

    if gate:
        if errored:
            raise SystemExit(f"{errored} request(s) errored")
        if mismatched:
            raise SystemExit(f"{mismatched} response(s) diverged between "
                             f"cells — the scheduler broke bit-identity")
        if off32 and on32:
            red = summary.get("invocation_reduction_32t", 0)
            mb = summary.get("mean_batch_32t") or 0
            if red < 4:
                raise SystemExit(f"program-invocation reduction at 32 "
                                 f"threads is {red}x (< 4x)")
            if mb < 4:
                raise SystemExit(f"mean flushed batch at 32 threads is "
                                 f"{mb} (< 4)")
        if on32 and on32p:
            p = summary["pipeline_32t"]
            d1_ov = p.get("depth1_overlap_ratio") or 0.0
            dp_ov = p.get(f"{deep}_overlap_ratio") or 0.0
            # pipelined must show measurably higher throughput OR stage
            # overlap than depth-1 (on the CPU mesh, launch and fetch
            # compete for the same cores, so overlap is the primary win)
            if not (p["qps_gain"] > 1.0 or dp_ov > d1_ov + 0.05):
                raise SystemExit(
                    f"pipelined dispatch shows no win at 32 threads: "
                    f"qps_gain={p['qps_gain']} overlap {d1_ov} -> {dp_ov}")
        rp = summary.get("recorder_overhead_32t")
        if rp and rp["qps_ratio"] < rp["gate_threshold"]:
            raise SystemExit(
                f"flight-recorder overhead gate failed: recorder-on qps "
                f"is {rp['qps_ratio']}x recorder-off "
                f"(< {rp['gate_threshold']}x; within-label noise floor "
                f"{rp['noise_floor']}) at {rp['threads']} threads")
        cp = summary.get("cost_overhead_32t")
        if cp and cp["qps_ratio"] < cp["gate_threshold"]:
            raise SystemExit(
                f"ledger+cost overhead gate failed: cost-on qps is "
                f"{cp['qps_ratio']}x cost-off "
                f"(< {cp['gate_threshold']}x; noise floor "
                f"{cp['noise_floor']}) at {cp['threads']} threads")
        sp = summary.get("sampler_overhead_32t")
        if sp and sp["qps_ratio"] < sp["gate_threshold"]:
            raise SystemExit(
                f"sampler overhead gate failed: sampler-on qps is "
                f"{sp['qps_ratio']}x sampler-off "
                f"(< {sp['gate_threshold']}x; noise floor "
                f"{sp['noise_floor']}) at {sp['threads']} threads")
        if sp and sp["slo_false_alarms"]:
            raise SystemExit(
                f"SLO engine false-fired {sp['slo_false_alarms']} "
                f"alert(s) on a clean concurrency run")
        ip = summary.get("insights_overhead_32t")
        if ip and ip["qps_ratio"] < ip["gate_threshold"]:
            raise SystemExit(
                f"query-insights overhead gate failed: insights-on qps "
                f"is {ip['qps_ratio']}x insights-off "
                f"(< {ip['gate_threshold']}x; noise floor "
                f"{ip['noise_floor']}) at {ip['threads']} threads")
        wp = summary.get("lockwitness_overhead_32t")
        if wp and wp["qps_ratio"] < wp["gate_threshold"]:
            raise SystemExit(
                f"lockwitness overhead gate failed: witness-on qps is "
                f"{wp['qps_ratio']}x witness-off "
                f"(< {wp['gate_threshold']}x; noise floor "
                f"{wp['noise_floor']}) at {wp['threads']} threads")
        if wp and wp["inversions"]:
            raise SystemExit(
                f"lock witness recorded {wp['inversions']} acquisition-"
                f"order inversion(s) on a clean concurrency run")
        if wp and wp["order_conflicts"]:
            raise SystemExit(
                f"witnessed acquisition order contradicts the committed "
                f"lock_order.json in {wp['order_conflicts']} edge(s)")
        gp = summary.get("ingest_obs_overhead_32t")
        if gp:
            _gate_ingest_pair(gp)
    print("OK", flush=True)


if __name__ == "__main__":
    main()
