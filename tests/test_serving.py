"""Serving scheduler (serving/scheduler.py): cross-request dynamic
batching with deadline-aware flush and priority lanes.

Coverage per docs/SERVING.md: deadline flush fires for a lone request (no
starvation), size flush under a burst, eligible/ineligible shape split,
cancellation before launch, queue-full 429, lane priority ordering, and a
many-threads hammer proving per-request results equal direct execution.
Also: the mesh-attribution/request-cache parity of the msearch decline
path, and the fielddata-breaker folding of the per-segment device cache
and the nested sort-value columns."""

import gc
import json
import threading
import time

import numpy as np
import pytest

import jax

from opensearch_tpu.cluster.node import Node
from opensearch_tpu.rest.client import ApiError, RestClient
from opensearch_tpu.serving import LANES, SchedulerConfig, ServingScheduler
from opensearch_tpu.serving.scheduler import _Pending
from opensearch_tpu.utils.metrics import METRICS
from opensearch_tpu.utils.wlm import PressureRejectedException

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")

NDOCS = 240
WORDS = ["alpha", "beta", "gamma", "delta", "eps", "zeta", "eta", "theta"]


def _seed(client):
    client.indices.create("serv", {
        "settings": {"number_of_shards": 4},
        "mappings": {"properties": {
            "body": {"type": "text"}, "title": {"type": "text"},
            "status": {"type": "keyword"}, "price": {"type": "integer"}}}})
    rng = np.random.default_rng(7)
    bulk = []
    for i in range(NDOCS):
        toks = rng.choice(WORDS, size=int(rng.integers(3, 8)))
        bulk.append({"index": {"_index": "serv", "_id": str(i)}})
        bulk.append({"body": " ".join(toks),
                     "title": f"{WORDS[i % 4]} {WORDS[(i + 1) % 4]}",
                     "status": ["draft", "live"][i % 2],
                     "price": int(rng.integers(0, 100))})
    client.bulk(bulk)
    client.indices.refresh("serv")
    client.indices.forcemerge("serv")


@pytest.fixture(scope="module")
def clients():
    """(scheduler-ON client, scheduler-OFF direct client) over identical
    corpora. Both carry the mesh; the OFF client is the bit-identical
    ground truth — coalescing must serve the exact pages/scores/tie-breaks
    direct execution of the same path serves (the mesh's own decline->host
    fallback is ULP-close, not bitwise, which is a different contract)."""
    cm = RestClient(node=Node())
    ch = RestClient(node=Node())
    assert cm.node.mesh_service is not None
    assert cm.node.serving.enabled
    ch.node.serving.enabled = False          # scheduler-off toggle
    _seed(cm)
    _seed(ch)
    yield cm, ch
    cm.node.serving.close()


def _strip(resp):
    return {k: v for k, v in resp.items() if k != "took"}


BODIES = [
    {"query": {"match": {"body": "alpha beta"}}, "size": 5},
    {"query": {"bool": {"must": [{"match": {"body": "gamma"}}],
                        "filter": [{"term": {"status": "live"}}]}},
     "size": 5},
    {"query": {"match_phrase": {"title": "alpha beta"}}, "size": 5},
    {"query": {"match": {"body": "delta"}}, "size": 0,
     "aggs": {"p": {"avg": {"field": "price"}}}},
    {"query": {"match": {"body": "zeta eta"}}, "size": 10},
    # host-loop shapes: the scheduler must decline/bypass them unchanged
    {"query": {"match_all": {}}, "size": 3},
    {"query": {"match": {"body": "theta"}},
     "sort": [{"price": {"order": "asc"}}], "size": 4},
]


class TestFlushPolicy:
    def test_lone_request_deadline_flush(self, clients):
        cm, ch = clients
        before = dict(cm.node.serving.flush_reasons)
        body = {"query": {"match": {"body": "alpha"}}, "size": 4,
                "_bench": "lone"}
        t0 = time.monotonic()
        got = cm.search("serv", dict(body))
        wall = time.monotonic() - t0
        want = ch.search("serv", dict(body))
        assert _strip(got) == _strip(want)
        # a lone request must not starve: the deadline flush fires after
        # max_wait_us, not when the batch fills
        assert cm.node.serving.flush_reasons["deadline"] > \
            before.get("deadline", 0)
        assert wall < 5.0

    def test_burst_hits_max_batch_flush(self, clients):
        cm, _ = clients
        node = cm.node
        old = node.serving
        node.serving = ServingScheduler(
            node, SchedulerConfig(max_batch=4, max_wait_us=1_000_000,
                                  queue_cap=64), enabled=True)
        try:
            done = threading.Barrier(5)
            resps = {}

            def worker(k):
                done.wait()
                resps[k] = cm.search("serv", {
                    "query": {"match": {"body": "alpha"}}, "size": 3,
                    "_bench": f"burst-{k}"})

            ts = [threading.Thread(target=worker, args=(k,))
                  for k in range(4)]
            for t in ts:
                t.start()
            done.wait()
            for t in ts:
                t.join(timeout=30)
            assert len(resps) == 4
            st = node.serving.stats()
            assert st["flush_reasons"].get("size", 0) >= 1
            assert st["batched_served"] == 4
        finally:
            node.serving.close()
            node.serving = old

    def test_mixed_eligible_ineligible_split(self, clients):
        cm, ch = clients
        st0 = cm.node.serving.stats()
        got = [cm.search("serv", dict(b, _bench=f"mix-{i}"))
               for i, b in enumerate(BODIES)]
        want = [ch.search("serv", dict(b, _bench=f"mix-{i}"))
                for i, b in enumerate(BODIES)]
        for g, w in zip(got, want):
            assert _strip(g) == _strip(w)
        st1 = cm.node.serving.stats()
        # scoring/filtered/phrase/agg shapes were coalesced...
        assert st1["batched_served"] > st0["batched_served"]
        # ...and the sort-by-field body was declined to the host loop
        assert st1["declined"] > st0["declined"]

    def test_statically_ineligible_bypasses_queue(self, clients):
        cm, ch = clients
        st0 = cm.node.serving.stats()
        body = {"query": {"match": {"body": "alpha"}},
                "highlight": {"fields": {"body": {}}}, "size": 2}
        got = cm.search("serv", dict(body))
        want = ch.search("serv", dict(body))
        assert _strip(got) == _strip(want)
        st1 = cm.node.serving.stats()
        assert st1["bypassed"] == st0["bypassed"] + 1
        assert st1["submitted"] == st0["submitted"]


class TestCancellationAndAdmission:
    def test_cancel_before_launch_drops_from_batch(self, clients):
        cm, _ = clients
        node = cm.node
        old = node.serving
        node.serving = ServingScheduler(
            node, SchedulerConfig(max_batch=32, max_wait_us=2_000_000),
            enabled=True)
        try:
            caught = {}

            def worker():
                try:
                    cm.search("serv", {"query": {"match": {"body": "beta"}},
                                       "_bench": "cancel-me"})
                except ApiError as e:
                    caught["err"] = e

            t = threading.Thread(target=worker)
            t.start()
            deadline = time.monotonic() + 10
            while node.serving.stats()["queue_depth"] == 0 \
                    and time.monotonic() < deadline:
                time.sleep(0.002)
            assert node.serving.stats()["queue_depth"] == 1
            for task in node.tasks.all():
                task.cancel("test cancellation")
            t.join(timeout=10)
            assert not t.is_alive()
            assert caught["err"].status == 400
            assert "cancel" in caught["err"].reason
            assert node.serving.stats()["cancelled_dropped"] == 1
        finally:
            node.serving.close()
            node.serving = old

    def test_queue_full_rejects_429(self, clients):
        cm, _ = clients
        node = cm.node
        old = node.serving
        # depth 1 pins the synchronous dispatcher so stalling the fetch
        # stage stalls the dispatcher in-batch (the pipelined window's
        # own backpressure bound is covered by TestPipeline)
        sched = ServingScheduler(
            node, SchedulerConfig(max_batch=1, max_wait_us=0, queue_cap=1,
                                  pipeline_depth=1),
            enabled=True)
        node.serving = sched
        gate = threading.Event()
        entered = threading.Event()
        real_finish = sched._finish_group

        def stalled(name, svc, bodies, handles):
            entered.set()
            gate.wait(timeout=30)
            return real_finish(name, svc, bodies, handles)

        sched._finish_group = stalled
        rej0 = node.search_backpressure.scheduler_rejection_count
        try:
            results = {}

            def worker(k):
                try:
                    results[k] = cm.search(
                        "serv", {"query": {"match": {"body": "alpha"}},
                                 "_bench": f"qf-{k}"})
                except ApiError as e:
                    results[k] = e

            t1 = threading.Thread(target=worker, args=(1,))
            t1.start()
            assert entered.wait(timeout=10)   # dispatcher stalled in-batch
            t2 = threading.Thread(target=worker, args=(2,))
            t2.start()
            deadline = time.monotonic() + 10
            while sched.stats()["queue_depth"] < 1 \
                    and time.monotonic() < deadline:
                time.sleep(0.002)
            # queue is full (cap 1): the third request must 429, not grow
            with pytest.raises(ApiError) as ei:
                cm.search("serv", {"query": {"match": {"body": "beta"}},
                                   "_bench": "qf-3"})
            assert ei.value.status == 429
            gate.set()
            t1.join(timeout=30)
            t2.join(timeout=30)
            assert isinstance(results[1], dict)
            assert isinstance(results[2], dict)
            assert sched.stats()["rejected"] == 1
            assert node.search_backpressure.scheduler_rejection_count \
                == rej0 + 1
            assert node.search_backpressure.stats()["search_task"][
                "scheduler_rejection_count"] == rej0 + 1
        finally:
            gate.set()
            node.serving.close()
            node.serving = old


class TestLanes:
    def test_interactive_preempts_batch_at_flush(self, clients):
        cm, _ = clients
        sched = ServingScheduler(cm.node, SchedulerConfig(max_batch=3),
                                 enabled=True)
        svc = cm.node.indices["serv"]
        entries = [_Pending("serv", svc, {"q": i}, lane, None)
                   for i, lane in enumerate(
                       ["batch", "batch", "interactive", "interactive"])]
        with sched._cond:
            for e in entries:
                sched._lanes[e.lane].append(e)
            sched._pending = len(entries)
            batch = sched._assemble("size")
        # interactive entries fill the batch first (FIFO within a lane);
        # batch-lane entries only take the leftover slot
        assert [e.lane for e in batch] == ["interactive", "interactive",
                                           "batch"]
        assert batch[0].body == {"q": 2} and batch[1].body == {"q": 3}
        assert batch[2].body == {"q": 0}
        assert sched.lane_flushed["interactive"] == 2
        assert sched.lane_flushed["batch"] == 1

    def test_batch_lane_never_starved(self, clients):
        # one slot is reserved for the batch lane whenever it has
        # waiters: sustained interactive pressure may slow scroll
        # traffic but must not starve it past its request timeout
        cm, _ = clients
        sched = ServingScheduler(cm.node, SchedulerConfig(max_batch=2),
                                 enabled=True)
        svc = cm.node.indices["serv"]
        entries = [_Pending("serv", svc, {"q": i}, lane, None)
                   for i, lane in enumerate(
                       ["interactive", "interactive", "interactive",
                        "batch"])]
        with sched._cond:
            for e in entries:
                sched._lanes[e.lane].append(e)
            sched._pending = len(entries)
            batch = sched._assemble("size")
        assert [e.lane for e in batch] == ["interactive", "batch"]

    def test_workload_group_lane_rides_batch_lane(self, clients):
        cm, ch = clients
        cm.put_workload_group("offline", {"lane": "batch"})
        assert cm.node.wlm.group("offline").lane == "batch"
        before = cm.node.serving.stats()["lanes"]["batch"]["flushed"]
        body = {"query": {"match": {"body": "gamma"}}, "size": 3,
                "_workload_group": "offline", "_bench": "lane-wg"}
        got = cm.search("serv", dict(body))
        want = ch.search("serv", {k: v for k, v in body.items()
                                  if k != "_workload_group"})
        assert _strip(got) == _strip(want)
        assert cm.node.serving.stats()["lanes"]["batch"]["flushed"] \
            == before + 1
        with pytest.raises(ApiError):
            cm.put_workload_group("bad", {"lane": "nope"})

    def test_lanes_constant(self):
        assert LANES == ("interactive", "batch")


class TestHammerParity:
    def test_many_threads_equal_direct_execution(self, clients):
        """The acceptance contract at test scale: N HTTP-style threads
        hammering eligible+ineligible shapes through the scheduler serve
        byte-identical responses to the pure host loop, with the oracle
        double-checking every coalesced body against the direct mesh."""
        cm, ch = clients
        node = cm.node
        old = node.serving
        node.serving = ServingScheduler(
            node, SchedulerConfig(max_batch=16, max_wait_us=3000,
                                  oracle=True), enabled=True)
        try:
            nthreads, per = 12, 12
            want = {}
            for k in range(nthreads):
                for j in range(per):
                    b = dict(BODIES[(k + j) % len(BODIES)],
                             _bench=f"ham-{k}-{j}")
                    want[(k, j)] = _strip(ch.search("serv", dict(b)))
            got = {}
            errs = []

            def worker(k):
                try:
                    for j in range(per):
                        b = dict(BODIES[(k + j) % len(BODIES)],
                                 _bench=f"ham-{k}-{j}")
                        got[(k, j)] = _strip(cm.search("serv", b))
                except Exception as e:        # noqa: BLE001
                    errs.append(repr(e))

            ts = [threading.Thread(target=worker, args=(k,))
                  for k in range(nthreads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=120)
            assert errs == []
            assert len(got) == nthreads * per
            for key, w in want.items():
                assert got[key] == w, f"divergence at {key}"
            st = node.serving.stats()
            assert st["oracle"]["checks"] > 0
            assert st["oracle"]["mismatches"] == 0
            assert st["batched_served"] > 0
        finally:
            node.serving.close()
            node.serving = old

    def test_scheduler_toggle_off(self, monkeypatch):
        monkeypatch.setenv("OPENSEARCH_TPU_SCHED", "0")
        n = Node()
        assert n.serving is not None and not n.serving.enabled
        c = RestClient(node=n)
        c.indices.create("t", {"settings": {"number_of_shards": 2}})
        c.index("t", {"body": "alpha"}, id="1", refresh=True)
        r = c.search("t", {"query": {"match": {"body": "alpha"}}})
        assert r["hits"]["total"]["value"] == 1
        assert n.serving.stats()["submitted"] == 0

    def test_http_stop_drains_but_keeps_scheduler_alive(self, clients):
        # the scheduler belongs to the Node, which may outlive any one
        # transport: stopping an HttpServer drains the queue but must not
        # end coalescing for the in-process client
        from opensearch_tpu.rest.http_server import HttpServer
        cm, _ = clients
        srv = HttpServer(cm)
        srv.start()
        srv.stop()
        before = cm.node.serving.stats()["submitted"]
        cm.search("serv", {"query": {"match": {"body": "alpha"}},
                           "_bench": "post-stop"})
        st = cm.node.serving.stats()
        assert st["submitted"] == before + 1
        assert st["enabled"]

    def test_degrades_direct_when_closed(self, clients):
        cm, ch = clients
        node = cm.node
        old = node.serving
        sched = ServingScheduler(node, SchedulerConfig(), enabled=True)
        node.serving = sched
        try:
            sched.close()
            body = {"query": {"match": {"body": "alpha beta"}}, "size": 5,
                    "_bench": "closed"}
            got = cm.search("serv", dict(body))
            want = ch.search("serv", dict(body))
            assert _strip(got) == _strip(want)
            assert sched.stats()["direct_fallbacks"] >= 1
        finally:
            node.serving = old


class TestPipeline:
    """Pipelined dispatch (launch/fetch split): byte-parity across
    depths, the bounded in-flight window, completion-stage wedge
    degradation, and cancellation of a launched-but-unfetched request."""

    def test_depth_parity_hammer(self, clients):
        """Pipeline on/off must be byte-identical: the same shape mix
        hammered at depth 1 (the synchronous baseline), 2 and 4 serves
        identical pages/scores/tie-breaks as direct execution."""
        cm, ch = clients
        node = cm.node
        old = node.serving
        nthreads, per = 8, 6
        try:
            for depth in (1, 2, 4):
                # depth-unique _bench keys: identical keys across depth
                # cells would serve depths 2/4 from the request cache and
                # never exercise the scheduler
                want = {}
                for k in range(nthreads):
                    for j in range(per):
                        b = dict(BODIES[(k + j) % len(BODIES)],
                                 _bench=f"pd{depth}-{k}-{j}")
                        want[(k, j)] = _strip(ch.search("serv", dict(b)))
                node.serving = ServingScheduler(
                    node, SchedulerConfig(max_batch=16, max_wait_us=3000,
                                          pipeline_depth=depth),
                    enabled=True)
                got = {}
                errs = []

                def worker(k):
                    try:
                        for j in range(per):
                            b = dict(BODIES[(k + j) % len(BODIES)],
                                     _bench=f"pd{depth}-{k}-{j}")
                            got[(k, j)] = _strip(cm.search("serv", b))
                    except Exception as e:        # noqa: BLE001
                        errs.append(repr(e))

                ts = [threading.Thread(target=worker, args=(k,))
                      for k in range(nthreads)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(timeout=120)
                assert errs == [], f"depth {depth}: {errs}"
                assert len(got) == nthreads * per
                for key, w in want.items():
                    assert got[key] == w, f"depth {depth} diverged at {key}"
                st = node.serving.stats()
                assert st["batched_served"] > 0
                assert st["pipeline"]["depth"] == depth
                if depth > 1:
                    assert st["pipeline"]["launched_batches"] > 0
                    assert st["pipeline"]["completed_batches"] \
                        == st["pipeline"]["launched_batches"]
                    assert st["pipeline"]["inflight_peak"] <= depth
                    assert st["launch_to_fetch_ms"].get("count", 0) > 0
                else:
                    # depth 1 == the synchronous dispatcher: nothing ever
                    # parks in the window, and the stages can't overlap
                    assert st["pipeline"]["launched_batches"] == 0
                    assert st["pipeline"]["overlap_s"] == 0
                node.serving.close()
        finally:
            node.serving = old

    def test_inflight_window_backpressure(self, clients):
        """The dispatcher must stop launching once pipeline_depth batches
        are in flight — the window bounds the device queue; the request
        queue keeps admitting (and batching) meanwhile."""
        cm, _ = clients
        node = cm.node
        old = node.serving
        sched = ServingScheduler(
            node, SchedulerConfig(max_batch=1, max_wait_us=0,
                                  pipeline_depth=2), enabled=True)
        node.serving = sched
        gate = threading.Event()
        fetching = threading.Event()
        real_finish = sched._finish_group

        def stalled(name, svc, bodies, handles):
            fetching.set()
            gate.wait(timeout=60)
            return real_finish(name, svc, bodies, handles)

        sched._finish_group = stalled
        results = {}

        def worker(k):
            results[k] = cm.search(
                "serv", {"query": {"match": {"body": "alpha"}},
                         "_bench": f"bp-{k}"})

        try:
            n = 6
            ts = [threading.Thread(target=worker, args=(k,))
                  for k in range(n)]
            for t in ts:
                t.start()
            assert fetching.wait(timeout=10)
            # window fills to 2 launched-unretired batches; the rest stay
            # QUEUED because the dispatcher is blocked on the window
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                st = sched.stats()
                if st["pipeline"]["inflight"] == 2 \
                        and st["queue_depth"] >= n - 3:
                    break
                time.sleep(0.005)
            st = sched.stats()
            assert st["pipeline"]["inflight"] == 2
            assert st["queue_depth"] >= n - 3
            gate.set()
            for t in ts:
                t.join(timeout=60)
            assert len(results) == n
            assert all(isinstance(r, dict) for r in results.values())
            st = sched.stats()
            assert st["pipeline"]["inflight_peak"] <= 2
            assert st["pipeline"]["completed_batches"] \
                == st["pipeline"]["launched_batches"]
        finally:
            gate.set()
            sched.close()
            node.serving = old

    def test_completion_wedge_degrades_direct(self, clients):
        """A wedged completion stage (hung fetch) must not hold requests
        hostage: after a second request_timeout the claimed entry is
        abandoned and the request thread runs direct execution itself —
        same response, counted as a completion_abandoned fallback."""
        cm, ch = clients
        node = cm.node
        old = node.serving
        sched = ServingScheduler(
            node, SchedulerConfig(max_batch=4, pipeline_depth=2,
                                  request_timeout_s=0.4), enabled=True)
        node.serving = sched
        wedge = threading.Event()

        def hung(name, svc, bodies, handles):
            wedge.wait(timeout=120)
            return [None] * len(bodies)

        sched._finish_group = hung
        try:
            body = {"query": {"match": {"body": "alpha beta"}}, "size": 5,
                    "_bench": "wedge"}
            got = cm.search("serv", dict(body))
            want = ch.search("serv", dict(body))
            assert _strip(got) == _strip(want)
            st = sched.stats()
            assert st["pipeline"]["completion_abandoned"] >= 1
            assert st["direct_fallbacks"] >= 1
        finally:
            wedge.set()
            sched.close()
            node.serving = old

    def test_cancel_after_launch_before_fetch(self, clients):
        """A task cancelled while its batch is launched but not yet
        fetched resolves immediately with the cancellation error — the
        batch result for it is discarded by the state guard."""
        cm, _ = clients
        node = cm.node
        old = node.serving
        sched = ServingScheduler(
            node, SchedulerConfig(max_batch=1, max_wait_us=0,
                                  pipeline_depth=2), enabled=True)
        node.serving = sched
        gate = threading.Event()
        fetching = threading.Event()
        real_finish = sched._finish_group

        def stalled(name, svc, bodies, handles):
            fetching.set()
            gate.wait(timeout=60)
            return real_finish(name, svc, bodies, handles)

        sched._finish_group = stalled
        caught = {}

        def worker():
            try:
                caught["resp"] = cm.search(
                    "serv", {"query": {"match": {"body": "gamma"}},
                             "_bench": "cancel-inflight"})
            except ApiError as e:
                caught["err"] = e

        try:
            t = threading.Thread(target=worker)
            t.start()
            assert fetching.wait(timeout=10)   # batch launched, unfetched
            for task in node.tasks.all():
                task.cancel("pipeline cancel test")
            t.join(timeout=10)                 # resolves WITHOUT the gate
            assert not t.is_alive()
            assert "err" in caught
            assert caught["err"].status == 400
            assert "cancel" in caught["err"].reason
            assert sched.stats()["pipeline"]["cancelled_inflight"] == 1
        finally:
            gate.set()
            sched.close()
            node.serving = old

    def test_launch_handle_idempotent_and_error_replay(self):
        from opensearch_tpu.search.launch import LaunchHandle, completed
        calls = []
        h = LaunchHandle(lambda: calls.append(1) or "r", kind="test")
        assert h.fetch() == "r" and h.fetch() == "r" and calls == [1]
        assert h.launch_to_fetch_ms() is not None

        def boom():
            raise ValueError("x")

        hb = LaunchHandle(boom, kind="test")
        with pytest.raises(ValueError):
            hb.fetch()
        with pytest.raises(ValueError):
            hb.fetch()                          # memoized, not re-run
        assert completed([1, 2]).fetch() == [1, 2]


class TestTelemetrySurfaces:
    def test_nodes_stats_and_metrics_exposition(self, clients):
        cm, _ = clients
        cm.search("serv", {"query": {"match": {"body": "alpha"}},
                           "_bench": "tele"})
        block = cm.nodes_stats()["nodes"][cm.node.node_name]["serving"]
        for key in ("queue_depth", "submitted", "batched_served",
                    "declined", "rejected", "flush_reasons", "lanes",
                    "batch_size", "queue_wait_ms", "oracle"):
            assert key in block, key
        assert block["batch_size"].get("count", 0) >= 1
        assert "p95_ms" in block["queue_wait_ms"]
        from opensearch_tpu.utils.metrics import render_prometheus
        text = render_prometheus(METRICS)
        assert "ostpu_serving_submitted" in text
        assert "ostpu_serving_queue_depth" in text
        assert "ostpu_serving_batch_size" in text
        assert "ostpu_mesh_launches" in text


class TestMsearchDeclineParity:
    """Satellite regression: scheduler-declined / msearch-declined bodies
    must record the same mesh attribution and request-cache keys as the
    direct per-request path."""

    def _single_shard_client(self):
        c = RestClient(node=Node())
        c.indices.create("one", {
            "settings": {"number_of_shards": 1},
            "mappings": {"properties": {"body": {"type": "text"},
                                        "price": {"type": "integer"}}}})
        for i in range(20):
            c.index("one", {"body": f"alpha w{i % 3}", "price": i},
                    id=str(i))
        c.indices.refresh("one")
        return c

    def test_single_shard_msearch_attribution_matches_direct(self):
        c = self._single_shard_client()
        mesh = c.node.mesh_service
        base = dict(mesh.fallback_shapes)

        def delta():
            return {k: v - base.get(k, 0)
                    for k, v in mesh.fallback_shapes.items()
                    if v != base.get(k, 0)}

        c.search("one", {"query": {"match": {"body": "alpha"}},
                         "_bench": "d-0"})
        direct = delta()
        assert direct.get("single_shard") == 1
        base = dict(mesh.fallback_shapes)
        c.msearch([{"index": "one"},
                   {"query": {"match": {"body": "alpha"}}, "_bench": "m-0"},
                   {"index": "one"},
                   {"query": {"match": {"body": "alpha"}}, "_bench": "m-1"}])
        # one single_shard decline PER BODY — identical to two direct
        # searches (before the fix, kernel-batched msearch bodies skipped
        # the mesh entirely and recorded nothing)
        assert delta().get("single_shard") == 2

    def test_declined_body_request_cache_key_matches_direct(self):
        c = self._single_shard_client()
        # aggs decline BOTH the mesh (single_shard) and msearch_batched,
        # so the body takes the per-body retry -> Node.search -> cache
        body = {"query": {"match": {"body": "alpha"}}, "size": 0,
                "aggs": {"p": {"avg": {"field": "price"}}}}
        r1 = c.msearch([{"index": "one"}, json.loads(json.dumps(body))])
        hits0 = c.node.request_cache.hits
        r2 = c.search("one", json.loads(json.dumps(body)))
        # the direct search must HIT the entry the declined msearch body
        # cached — i.e. the `_mesh_declined` marker never perturbed the key
        assert c.node.request_cache.hits == hits0 + 1
        assert _strip(r1["responses"][0]) == _strip(r2)


class TestBreakerFolding:
    """Satellite regression: the per-segment device column cache and the
    nested sort-value columns charge the fielddata breaker and release on
    segment GC (the two retired OSL301 baseline entries)."""

    def test_device_arrays_charges_and_releases(self):
        from opensearch_tpu.index.engine import Engine
        from opensearch_tpu.index.mappings import Mappings
        from opensearch_tpu.utils.breaker import CircuitBreaker
        br = CircuitBreaker("fielddata-test", 1 << 30)
        from opensearch_tpu.obs.hbm_ledger import LEDGER
        old = LEDGER.breaker
        LEDGER.set_breaker(br)
        try:
            eng = Engine(Mappings({"properties": {
                "body": {"type": "text"}}}))
            for i in range(50):
                eng.index_doc(str(i), {"body": f"alpha beta w{i % 5}"})
            eng.refresh()
            seg = eng.segments[0]
            assert br.used == 0
            seg.device_arrays()
            charged = br.used
            assert charged > 0
            seg.device_arrays()               # cached: no double charge
            assert br.used == charged
            del seg
            eng.close()
            del eng
            gc.collect()
            assert br.used == 0
        finally:
            LEDGER.set_breaker(old)

    def test_nested_sort_values_charge(self):
        from opensearch_tpu.search import planes as PN
        from opensearch_tpu.index.engine import Engine
        from opensearch_tpu.index.mappings import Mappings
        from opensearch_tpu.utils.breaker import CircuitBreaker
        br = CircuitBreaker("fielddata-test", 1 << 30)
        from opensearch_tpu.obs.hbm_ledger import LEDGER
        old = LEDGER.breaker
        LEDGER.set_breaker(br)
        try:
            eng = Engine(Mappings({"properties": {
                "items": {"type": "nested", "properties": {
                    "qty": {"type": "integer"}}}}}))
            for i in range(30):
                eng.index_doc(str(i), {"items": [{"qty": i}, {"qty": i + 1}]})
            eng.refresh()
            seg = eng.segments[0]
            before = br.used
            plane = PN.nested_sort_plane(seg, "items.qty", "items", "min")
            assert plane is not None
            # what is resident is what is charged: the i32 rank plane
            assert br.used - before == plane.nbytes == 4 * seg.ndocs_pad
            tenant = LEDGER.snapshot()["tenants"]["nested_sort"]
            assert tenant["bytes"] >= plane.nbytes
            charged = br.used
            assert PN.nested_sort_plane(seg, "items.qty", "items",
                                        "min") is plane
            assert br.used == charged         # cache hit: no re-charge
            assert PN.nested_sort_value(seg, "items.qty", "items", "min",
                                        7) == 7.0
            assert PN.nested_sort_plane(seg, "items.none", "items",
                                        "min") is None
            del seg, plane
            eng.close()
            del eng
            gc.collect()
            assert br.used == before
        finally:
            LEDGER.set_breaker(old)
