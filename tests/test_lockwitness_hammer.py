"""Witness-armed runs of the existing concurrency hammers.

The seeded-inversion fixture (test_lockwitness.py) proves the witness
CAN catch an inversion; these tests prove the real serving paths DON'T
produce one. Components are constructed AFTER `install()` — the witness
wraps locks at creation time — so every package lock the hammer touches
reports under its creation-site key, and `verify_against()` then checks
the witnessed acquisition orders against the committed
`lock_order.json` (order_conflicts must be empty; unmodeled edges are
informational — the static model deliberately omits interleavings it
cannot prove, see docs/STATIC_ANALYSIS.md).
"""

import os
import threading
import time

import pytest

from opensearch_tpu.devtools import lockwitness
from opensearch_tpu.obs.insights import fingerprint
from opensearch_tpu.serving.remediator import (RemediationConfig,
                                               Remediator)
from opensearch_tpu.utils.metrics import MetricsRegistry
from opensearch_tpu.utils.wlm import PressureRejectedException

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOCK_GRAPH = os.path.join(REPO_ROOT, "lock_order.json")

BODY = {"query": {"match": {"body": "alpha beta"}}, "size": 10}
OTHER = {"query": {"match": {"title": "gamma"}}, "size": 10}


@pytest.fixture()
def witness():
    st = lockwitness.install(strict=False)
    lockwitness.reset()
    yield st
    lockwitness.uninstall()


def _assert_clean(tag):
    inv = lockwitness.inversions()
    assert inv == [], f"{tag}: witnessed lock-order inversion(s): " \
        f"{[(r['first'], r['second']) for r in inv]}"
    rep = lockwitness.verify_against(LOCK_GRAPH)
    assert rep["order_conflicts"] == [], (
        f"{tag}: runtime acquisition order contradicts the committed "
        f"lock_order.json: {rep['order_conflicts']}")


class TestRemediatorHammer:
    def test_shed_hammer_32_threads_witness_clean(self, witness):
        """The test_remediation.py 32-thread shed hammer, witnessed:
        admits on the lock-free fast path while tick/status/engage
        churn the actuator lock and the registry underneath."""
        cfg = RemediationConfig(ttl_s=5.0, green_hold_s=0.05,
                                engage_cooldown_s=0.0)
        rem = Remediator(cfg, registry=MetricsRegistry())
        assert isinstance(rem._lock, lockwitness.WitnessLock)
        rem._engage("shed_shape", fingerprint(BODY, "batch")[0], "s")

        stop = threading.Event()

        def admits():
            for k in range(50):
                body = dict(BODY) if k % 2 == 0 else dict(OTHER)
                try:
                    rem.admit(body, "batch")
                except PressureRejectedException:
                    pass

        def churn():
            while not stop.is_set():
                rem.tick(now=time.monotonic())
                rem.status()
                time.sleep(0.001)

        churners = [threading.Thread(target=churn) for _ in range(4)]
        for t in churners:
            t.start()
        threads = [threading.Thread(target=admits) for _ in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        for t in churners:
            t.join()

        assert rem.shed_total > 0
        _assert_clean("remediator hammer")


class TestSchedulerHammer:
    def test_scheduler_hammer_witness_clean(self, witness):
        """A fresh node + batching scheduler built under the witness,
        hammered from 16 threads: the dispatcher's condition-variable
        handshake, metrics mirroring, and the search path must exhibit
        only acquisition orders the committed graph allows."""
        from opensearch_tpu.rest.client import RestClient
        from opensearch_tpu.serving import SchedulerConfig, ServingScheduler

        client = RestClient()
        client.indices.create("lwidx", {"mappings": {"properties": {
            "body": {"type": "text"}}}})
        for i, words in enumerate(["alpha beta", "beta gamma",
                                   "alpha", "gamma delta"]):
            client.index("lwidx", {"body": words}, id=str(i))
        client.indices.refresh("lwidx")
        svc = client.node.indices["lwidx"]

        sched = ServingScheduler(
            client.node,
            SchedulerConfig(max_batch=8, max_wait_us=2000, oracle=True),
            enabled=True)
        assert isinstance(sched._cond, lockwitness.WitnessLock) \
            or hasattr(sched._cond, "_lock")  # Condition wraps its lock

        expect = client.search("lwidx", BODY)["hits"]["total"]["value"]
        errors = []

        def worker():
            try:
                for _ in range(6):
                    # None = batch path declined the body; the real
                    # caller falls back to the direct search path —
                    # do the same so the hammer still exercises it
                    got = sched.execute("lwidx", svc, dict(BODY)) \
                        or client.search("lwidx", dict(BODY))
                    assert got["hits"]["total"]["value"] == expect
            except Exception as e:          # surfaced after join
                errors.append(e)

        try:
            threads = [threading.Thread(target=worker)
                       for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            sched.close(drain=True)
        assert errors == []
        _assert_clean("scheduler hammer")


class TestLegsHammer:
    def test_legs_hammer_witness_clean(self, witness):
        """Witness-armed parallel legs (PR 17): an in-process distnode
        pair built AFTER install() — so the legs pool lock, per-request
        state lock, chaos-schedule lock, and every node lock report as
        WitnessLocks — hammered with hybrid + distributed searches from
        8 threads while the legs pool fans out sub-retrieval and
        scatter legs underneath each one. No inversion, no order the
        committed lock_order.json forbids."""
        from opensearch_tpu.cluster.distnode import DistClusterNode
        from opensearch_tpu.utils import legs

        if not (isinstance(legs._pool_lock, lockwitness.WitnessLock)
                or legs._pools):
            # an earlier test of this process imported `legs` before
            # install() and made no pool (which files share a worker
            # follows the whole suite's file list): run the module again,
            # so that its lock is made by the witness's factory (which
            # wraps only locks made inside the package)
            import importlib
            importlib.reload(legs)
        a = DistClusterNode("lwa")
        b = DistClusterNode("lwb", seed=a.addr)
        assert isinstance(legs._pool_lock, lockwitness.WitnessLock) \
            or legs._pools              # pools may predate install
        try:
            a.create_index("lwd", {"mappings": {"properties": {
                "body": {"type": "text"},
                "emb": {"type": "rank_features"}}},
                "settings": {"number_of_shards": 2,
                             "number_of_node_replicas": 1}})
            for i in range(24):
                a.index_doc("lwd", {
                    "body": f"alpha {'beta' if i % 2 else 'gamma'} w{i}",
                    "emb": {"t1": 1.0 + i % 3, "t2": 0.5}}, id=str(i))
            a.refresh("lwd")

            hybrid = {"query": {"hybrid": {"queries": [
                {"match": {"body": "alpha beta"}},
                {"neural_sparse": {"emb": {"query_tokens":
                                           {"t1": 1.0, "t2": 0.5}}}}],
                "fusion": {"method": "rrf", "window_size": 20}}},
                "size": 5}
            errors = []

            def worker(i):
                try:
                    for k in range(4):
                        coord = a if (i + k) % 2 == 0 else b
                        body = dict(hybrid) if k % 2 == 0 else \
                            {"query": {"match": {"body": "alpha"}},
                             "size": 5}
                        r = coord.search("lwd", body)
                        assert r["hits"]["hits"]
                    coord.cluster_stats()
                except Exception as e:      # surfaced after join
                    errors.append(e)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            a.stop()
            b.stop()
        assert errors == []
        _assert_clean("legs hammer")
