"""Unified telemetry (utils/metrics.py + utils/trace.py wiring).

Covers the PR's contract surface:
- registry correctness: sketch percentiles vs a numpy reference,
  concurrent-increment determinism, CounterGroup dict-compat
- tracer thread-safety: pool workers inherit the ambient span (the
  context-carrying submit) and concurrent child attachment loses nothing
- cross-node trace propagation: a distributed search over two distnodes
  yields ONE trace whose per-node spans nest under the coordinator span
- `_nodes/stats` telemetry block (per-stage p50/p95/p99 + jit
  compile-vs-execute attribution), the enriched `profile` response, the
  `/_metrics` Prometheus endpoint, and slowlog rung/trace attribution
- the overhead guard: disabled-telemetry cost on the hot path stays
  bounded
- the span spine (ISSUE 25): one `rest.search` root per request, every
  descendant on its `trace_id`, self times that partition the root, no
  `Span` from a disabled tracer, no `jax` at import of `utils.trace`
"""

import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from opensearch_tpu.utils.metrics import (METRICS, CounterGroup,
                                          MetricsRegistry,
                                          render_prometheus)
from opensearch_tpu.utils.threadpool import ThreadPools
from opensearch_tpu.utils.trace import TRACER, Tracer


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------

class TestRegistry:
    def test_counter_concurrent_increments_exact(self):
        reg = MetricsRegistry()
        c = reg.counter("t.hits")
        n_threads, per = 8, 20_000

        def worker():
            for _ in range(per):
                c.inc()

        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert c.value == n_threads * per

    def test_histogram_percentiles_vs_numpy(self):
        reg = MetricsRegistry()
        h = reg.histogram("t.lat")
        rng = np.random.default_rng(7)
        samples = rng.lognormal(mean=2.0, sigma=1.0, size=5000)
        for v in samples:
            h.record(float(v))
        for p in (50, 95, 99):
            got = h.percentile(p)
            ref = float(np.percentile(samples, p))
            assert abs(got - ref) / ref < 0.05, (p, got, ref)

    def test_histogram_small_exact(self):
        reg = MetricsRegistry()
        h = reg.histogram("t.small")
        for v in range(1, 101):
            h.record(float(v))
        # nearest-rank p50 of 1..100 is 50, within sketch error
        assert abs(h.percentile(50) - 50.0) / 50.0 < 0.01

    def test_histogram_concurrent_records_exact_count(self):
        reg = MetricsRegistry()
        h = reg.histogram("t.conc")

        def worker(seed):
            rng = np.random.default_rng(seed)
            for _ in range(1000):
                h.record(float(rng.uniform(0.1, 100.0)))

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert h.count == 8000

    def test_snapshot_deterministic(self):
        reg = MetricsRegistry()
        reg.counter("b").inc(2)
        reg.counter("a").inc(1)
        h = reg.histogram("lat")
        for v in (1.0, 2.0, 3.0):
            h.record(v)
        s1, s2 = reg.snapshot(), reg.snapshot()
        assert s1 == s2
        assert list(s1["counters"]) == ["a", "b"]

    def test_timer_records(self):
        reg = MetricsRegistry()
        with reg.timer("t.span"):
            pass
        assert reg.histogram("t.span").count == 1

    def test_reset_drops_instruments(self):
        reg = MetricsRegistry()
        reg.counter("a").inc()
        reg.histogram("h").record(1.0)
        reg.reset()
        assert reg.snapshot() == {"counters": {}, "gauges": {},
                                  "histograms": {}}

    def test_counter_group_dict_compat(self):
        reg = MetricsRegistry()
        g = CounterGroup(reg, "grp", {"a": 0, "b": 0.0})
        g.inc("a")
        g.inc("b", 1.5)
        assert dict(g) == {"a": 1, "b": 1.5}
        before = dict(g)
        g.inc("a", 2)
        assert {k: g[k] - before[k] for k in before} == {"a": 2, "b": 0.0}
        g["a"] = 0                      # test-reset assignment still works
        assert g["a"] == 0
        with pytest.raises(KeyError):
            g.inc("nope")

    def test_prometheus_rendition(self):
        reg = MetricsRegistry()
        reg.counter("fastpath.pure_served").inc(3)
        reg.histogram("search.total").record(12.5)
        text = render_prometheus(reg)
        assert "# TYPE ostpu_fastpath_pure_served counter" in text
        assert "ostpu_fastpath_pure_served 3" in text
        assert 'ostpu_search_total_ms{quantile="0.5"}' in text
        assert "ostpu_search_total_ms_count 1" in text


def _walk(span: dict):
    """Every span of a `to_dict` tree, depth first."""
    yield span
    for ch in span.get("children", []):
        yield from _walk(ch)


# ----------------------------------------------------------------------
# tracer thread-safety (the context-carrying submit)
# ----------------------------------------------------------------------

class TestTracerThreads:
    def test_pool_spans_attach_under_parent(self):
        t = Tracer()
        pools = ThreadPools(cores=4)
        try:
            def work(i):
                with t.span("child", i=i):
                    time.sleep(0.001)

            with t.span("parent") as parent:
                futs = [pools.pool("generic").submit(work, i)
                        for i in range(64)]
                [f.result() for f in futs]
            # every pool-thread span attached under the parent (no
            # detached roots), and the concurrent appends lost nothing
            assert len(parent.children) == 64
            assert all(c.parent is parent for c in parent.children)
            traces = t.traces(limit=100)
            assert len(traces) == 1      # one root: the parent
            assert len(traces[0]["children"]) == 64
        finally:
            pools.shutdown()

    def test_disabled_telemetry_overhead_bounded(self):
        # the fastpath microbench guard: a disabled tracer + registry must
        # cost near-nothing per instrumented site
        t = Tracer(enabled=False)
        reg = MetricsRegistry()
        reg.enabled = False
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            with t.span("x"):
                pass
            with reg.timer("y"):
                pass
        dt = time.perf_counter() - t0
        # generous CI bound: <75us per site-pair (observed ~1-2us)
        assert dt < n * 75e-6, f"disabled-telemetry overhead {dt:.3f}s"
        assert reg.snapshot()["histograms"] == {}

    def test_disabled_tracer_builds_no_span(self):
        t = Tracer(enabled=False)
        with t.span("x", a=1) as s:
            assert s is None
        assert t.span("x") is t.span("y")       # one shared no-op
        assert t.stats()["spans"] == 0 and t.traces() == []

        @t.spanned("z")
        def f(v):
            return v + 1
        assert f(1) == 2 and t.stats()["spans"] == 0

    def test_span_count_is_exact_across_reads(self):
        t = Tracer()
        for _ in range(3):
            with t.span("a"):
                with t.span("b"):
                    pass
            assert t.stats()["spans"] % 2 == 0      # a read burns no span
        assert t.stats() == {"enabled": True, "spans": 6,
                             "retained_traces": 3}

    def test_importing_trace_does_not_import_jax(self):
        # the annotation class is resolved at the first span: importing
        # the tracer must never initialise (or even load) a backend
        code = ("import sys; import opensearch_tpu.utils.trace as t; "
                "assert 'jax' not in sys.modules, 'jax at import'; "
                "t.TRACER.span('x').__enter__(); "
                "assert 'jax' in sys.modules")
        subprocess.run([sys.executable, "-c", code], check=True,
                       timeout=120)


# ----------------------------------------------------------------------
# end-to-end: stats / profile / prometheus / slowlog
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def client():
    from opensearch_tpu.rest.client import RestClient
    c = RestClient()
    c.indices.create("tel", {
        "settings": {"number_of_shards": 1,
                     "index.search.slowlog.threshold.query.trace": "0ms"},
        "mappings": {"properties": {"body": {"type": "text"}}}})
    for i in range(64):
        c.index("tel", {"body": f"alpha beta w{i % 7}"}, id=str(i))
    c.indices.refresh("tel")
    return c


class TestEndToEnd:
    def test_nodes_stats_telemetry_block(self, client):
        client.search("tel", {"query": {"match": {"body": "alpha"}}})
        ns = client.nodes_stats()["nodes"][client.node.node_name]
        tel = ns["telemetry"]
        stages = tel["stages"]
        assert "search.query_phase" in stages
        for key in ("p50_ms", "p95_ms", "p99_ms", "count"):
            assert key in stages["search.query_phase"]
        assert stages["search.query_phase"]["count"] >= 1
        # jit compile-vs-execute attribution is present for the executor
        # program family the search compiled/launched
        jit = tel["jit"]
        assert "executor" in jit
        assert jit["executor"]["cache"]["requests"] >= 1
        assert set(jit["executor"]) == {"cache", "compile", "execute"}
        # backward-compatible key shapes for the migrated counters
        from opensearch_tpu.search import fastpath
        assert set(ns["fastpath"]) == set(fastpath.STATS)
        assert set(ns["fastpath_rescore"]) == set(fastpath.RESCORE_STATS)

    def test_profile_device_attribution(self, client):
        resp = client.search("tel", {
            "query": {"match": {"body": "beta"}}, "profile": True})
        shard = resp["profile"]["shards"][0]
        dev = shard["device"]
        assert dev["rescore_path"] in ("host", "device")
        assert "jit" in dev
        # the plan root carries the same attribution
        root = shard["searches"][0]["query"][0]
        assert root["device"] is dev

    def test_metrics_endpoint(self, client):
        import urllib.request
        from opensearch_tpu.rest.http_server import HttpServer
        srv = HttpServer(client)
        port = srv.start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/_metrics")
            with urllib.request.urlopen(req, timeout=10) as r:
                ctype = r.headers.get("Content-Type", "")
                text = r.read().decode()
            assert "text/plain" in ctype
            assert "ostpu_fastpath_pure_served" in text
            assert "# TYPE" in text
        finally:
            srv.stop()

    def test_rest_search_is_one_tree_on_one_trace_id(self, client):
        TRACER._traces.clear()
        client.search("tel", {"query": {"match": {"body": "alpha w3"}}})
        (root,) = TRACER.traces()
        assert root["name"] == "rest.search"
        assert root["trace_id"] == root["span_id"]
        spans = list(_walk(root))
        assert {"indices:data/read/search", "query_phase", "search.plan",
                "search.collect", "device.wait", "reduce", "fetch_phase",
                "search.respond"} <= {s["name"] for s in spans}
        assert {s["trace_id"] for s in spans} == {root["trace_id"]}
        assert len({s["span_id"] for s in spans}) == len(spans)

    def test_self_times_partition_the_root(self, client):
        TRACER._traces.clear()
        client.search("tel", {"query": {"match": {"body": "beta w5"}}})
        (root,) = TRACER._traces

        def walk(span):
            yield span
            for ch in span.children:
                yield from walk(ch)
        spans = list(walk(root))
        assert len(spans) >= 8 and all(s.end_ns for s in spans)
        # one thread, so no two children of a span overlap: exact in ns
        assert sum(s.self_ns() for s in spans) == root.duration_ns()
        for s in spans:
            assert all(s.start_ns <= c.start_ns and c.end_ns <= s.end_ns
                       for c in s.children)

    def test_slowlog_rung_and_trace_attribution(self, client):
        client.search("tel", {"query": {"match": {"body": "alpha"}}})
        entries = client.node.indices["tel"].search_slowlog.entries
        assert entries, "0ms trace threshold must have fired"
        e = entries[-1]
        assert e["level"] == "trace"
        # the enrichment answers WHY: rung attribution + the root span
        assert "fastpath_rungs" in e
        assert e["rescore_path"] in ("host", "device")
        assert e["trace"]["name"] == "indices:data/read/search"
        assert any(ch["name"] == "query_phase"
                   for ch in e["trace"].get("children", []))


# ----------------------------------------------------------------------
# cross-node trace propagation (two distnodes, one coherent trace)
# ----------------------------------------------------------------------

class TestDistributedTrace:
    def test_two_node_search_single_trace(self):
        from opensearch_tpu.cluster.distnode import DistClusterNode
        a = DistClusterNode("a")
        b = DistClusterNode("b", seed=a.addr)
        try:
            a.create_index("dtr", {
                "settings": {"number_of_shards": 4},
                "mappings": {"properties": {"body": {"type": "text"}}}})
            for i in range(40):
                a.index_doc("dtr", {"body": f"alpha w{i % 5}"}, id=str(i))
            a.refresh("dtr")
            resp = a.search("dtr", {"query": {"match": {"body": "alpha"}},
                                    "size": 10})
            assert resp["hits"]["total"]["value"] == 40
            assert resp["_shards"]["failed"] == 0

            # the coordinator ring holds ONE dist.search root whose phase
            # spans contain node b's grafted remote spans
            roots = [t for t in TRACER.traces(limit=50)
                     if t["name"] == "dist.search"]
            assert roots, "no dist.search root trace"
            root = roots[0]
            assert root["attributes"]["coordinator"] == "a"
            phases = {c["name"]: c for c in root["children"]}
            assert {"dist.dfs", "dist.query", "dist.reduce",
                    "dist.fetch"} <= set(phases)

            # remote spans live INSIDE each phase's subtree — since the
            # scatter went parallel (utils/legs.py) they sit one level
            # down, under the member's legs.leg span, on both arms
            remote = [ch for ph in ("dist.dfs", "dist.query", "dist.fetch")
                      for ch in _walk(phases[ph])
                      if ch.get("attributes", {}).get("node") == "b"]
            assert remote, "no remote spans nested under coordinator"
            # remote spans carry the propagated wire context
            for ch in remote:
                assert ch["attributes"]["coordinator"] == "a"
                assert ch["attributes"]["trace_root_id"] == root["span_id"]
        finally:
            a.stop()
            b.stop()
