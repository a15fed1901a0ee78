"""Two full Nodes, two OS processes, one cluster (cluster/distnode.py).

The product promotion of r4's raw two-process SPMD test: each process runs
a complete Node + HttpServer; membership, state publish, doc routing, and
the DFS_QUERY_THEN_FETCH scatter/gather all cross the process boundary
over HTTP. Reference analogs: `transport/netty4/Netty4Transport.java:1`,
`cluster/coordination/Coordinator.java:1`,
`action/search/TransportSearchAction.java:1`.

The final test kills the child node and asserts the survivor keeps serving
its own shards' data with honest partial-results accounting."""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from opensearch_tpu.cluster.distnode import DistClusterNode
from opensearch_tpu.cluster.routing import shard_for
from opensearch_tpu.rest.client import ApiError, RestClient

WORDS = ["alpha", "beta", "gamma", "delta", "eps", "zeta", "kappa",
         "lambda", "sigma", "omega"]
NDOCS = 150
NSHARDS = 4


def _mk_docs():
    rng = np.random.default_rng(17)
    docs = {}
    for i in range(NDOCS):
        docs[str(i)] = {
            "body": " ".join(rng.choice(WORDS,
                                        size=int(rng.integers(3, 9)))),
            "cat": ["x", "y", "z"][i % 3],
            "num": int(rng.integers(0, 100)),
        }
    return docs


MAPPING = {"settings": {"number_of_shards": NSHARDS},
           "mappings": {"properties": {"body": {"type": "text"},
                                       "cat": {"type": "keyword"},
                                       "num": {"type": "integer"}}}}


@pytest.fixture(scope="module")
def cluster():
    a = DistClusterNode("a")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    child = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "_dist_child.py"), a.addr],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env, cwd=repo_root)
    try:
        line = child.stdout.readline().strip()
        assert line.startswith("READY "), line
    except BaseException:
        child.kill()      # never leak the while-True child on a bad start
        a.stop()
        raise

    docs = _mk_docs()
    a.create_index("idx", MAPPING)
    for did, doc in docs.items():
        a.index_doc("idx", doc, id=did)
    a.refresh("idx")

    # the single-node oracle: same index layout, same docs, one process
    oracle = RestClient()
    oracle.indices.create("idx", MAPPING)
    bulk = []
    for did, doc in docs.items():
        bulk.append({"index": {"_index": "idx", "_id": did}})
        bulk.append(doc)
    oracle.bulk(bulk)
    oracle.indices.refresh("idx")

    yield a, child, oracle, docs
    if child.poll() is None:
        child.kill()
    a.stop()


class TestCluster:
    def test_membership_and_state(self, cluster):
        a, child, _, _ = cluster
        assert set(a.members) == {"a", "b"}
        assert a.leader == "a"
        st = a.cluster_state()
        assert set(st["routing"]["idx"].values()) == {"a", "b"}
        # both nodes own half the shards (round-robin over sorted names)
        owners = [st["routing"]["idx"][str(s)] for s in range(NSHARDS)]
        assert owners == ["a", "b", "a", "b"]

    def test_docs_live_only_on_their_owner(self, cluster):
        a, _, _, docs = cluster
        owners = a.routing["idx"]
        expect_a = sum(1 for d in docs
                       if owners[shard_for(d, NSHARDS)] == "a")
        local_count = a.client.count("idx")["count"]
        assert local_count == expect_a
        assert 0 < expect_a < NDOCS     # the split is genuinely two-node

    @pytest.mark.parametrize("body", [
        {"query": {"match": {"body": "alpha beta"}}, "size": 10},
        {"query": {"term": {"cat": "y"}}, "size": 12},
        {"query": {"bool": {"must": [{"match": {"body": "gamma"}}],
                            "filter": [{"range": {"num": {"gte": 20,
                                                          "lt": 80}}}]}},
         "size": 10},
        {"query": {"match": {"body": {"query": "delta eps",
                                      "minimum_should_match": 2}}},
         "size": 8},
        {"query": {"match": {"body": "omega"}}, "size": 5,
         "aggs": {"cats": {"terms": {"field": "cat"}},
                  "n": {"stats": {"field": "num"}}}},
        {"query": {"match_all": {}}, "size": 15},
    ])
    def test_distributed_equals_single_node(self, cluster, body):
        """Cross-process scatter/gather with DFS global stats == one node
        holding all the data: ids, scores, totals, and aggs identical."""
        a, _, oracle, _ = cluster
        rd = a.search("idx", dict(body))
        rh = oracle.search(index="idx", body=dict(body))
        assert rd["_shards"]["failed"] == 0
        assert rd["hits"]["total"] == rh["hits"]["total"]
        assert [h["_id"] for h in rd["hits"]["hits"]] == \
            [h["_id"] for h in rh["hits"]["hits"]]
        sd = np.array([h["_score"] for h in rd["hits"]["hits"]], float)
        sh = np.array([h["_score"] for h in rh["hits"]["hits"]], float)
        np.testing.assert_allclose(sd, sh, rtol=1e-6)
        if "aggs" in body:
            assert rd["aggregations"] == rh["aggregations"]

    def test_follower_coordinates_too(self, cluster):
        """Any member can coordinate: the same distributed search issued to
        the child over HTTP returns the same answer."""
        import json
        import urllib.request
        a, child, oracle, _ = cluster
        child_addr = None
        for name, addr in a.members.items():
            if name == "b":
                child_addr = addr
        body = {"query": {"match": {"body": "alpha"}}, "size": 10}
        req = urllib.request.Request(
            f"http://{child_addr}/_internal/search",
            data=json.dumps({"index": "idx", "body": body}).encode(),
            method="POST", headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            rb = json.loads(r.read().decode())
        rh = oracle.search(index="idx", body=dict(body))
        assert rb["hits"]["total"] == rh["hits"]["total"]
        assert [h["_id"] for h in rb["hits"]["hits"]] == \
            [h["_id"] for h in rh["hits"]["hits"]]

    def test_get_routes_across_nodes(self, cluster):
        a, _, _, docs = cluster
        owners = a.routing["idx"]
        some_b = next(d for d in docs
                      if owners[shard_for(d, NSHARDS)] == "b")
        got = a.get("idx", some_b)
        assert got["found"] is True
        assert got["_source"] == docs[some_b]

    def test_unsupported_features_400(self, cluster):
        a, _, _, _ = cluster
        with pytest.raises(ApiError):
            a.search("idx", {"query": {"match_all": {}},
                             "sort": [{"num": {"order": "asc"}}]})
        with pytest.raises(ApiError):
            a.search("idx", {"query": {"match_all": {}},
                             "aggs": {"t": {"terms": {"field": "cat"},
                                            "aggs": {"m": {"avg": {
                                                "field": "num"}}}}}})
        with pytest.raises(ApiError):   # named queries: fetch-side state
            a.search("idx", {"query": {"match": {
                "body": {"query": "alpha", "_name": "q1"}}}})

    def test_zz_kill_node_survivor_serves_its_shards(self, cluster):
        """Kill the child node: the survivor keeps serving ITS shards'
        data, reports the dead node's shards failed, and its hits are
        exactly the docs routed to its own shards. (zz: runs last — the
        child stays dead.)"""
        a, child, oracle, docs = cluster
        owners = a.routing["idx"]
        child.send_signal(signal.SIGKILL)
        child.wait(timeout=10)
        time.sleep(0.2)

        body = {"query": {"match_all": {}}, "size": NDOCS}
        rd = a.search("idx", dict(body))
        b_shards = [s for s, n in owners.items() if n == "b"]
        assert rd["_shards"]["failed"] == len(b_shards)
        assert rd["_shards"]["successful"] == NSHARDS - len(b_shards)
        expect_ids = {d for d in docs
                      if owners[shard_for(d, NSHARDS)] == "a"}
        got_ids = {h["_id"] for h in rd["hits"]["hits"]}
        assert got_ids == expect_ids
        assert rd["hits"]["total"]["value"] == len(expect_ids)
        # a-owned docs still fetch; b-owned docs honestly error
        some_a = next(iter(expect_ids))
        assert a.get("idx", some_a)["found"] is True
        some_b = next(d for d in docs
                      if owners[shard_for(d, NSHARDS)] == "b")
        with pytest.raises((ApiError, OSError)):
            a.get("idx", some_b)


# ---------------------------------------------------------------------------
# lock-discipline regressions (OSL702): the state lock must never be held
# across a member RPC send — a slow/dead member otherwise serializes every
# join and search-route against the HTTP timeout. These reproduce the two
# findings the oslint concurrency pass raised on this file (and fixed).
# ---------------------------------------------------------------------------

import threading

import opensearch_tpu.cluster.distnode as dn_mod


def _blocked_http(started, release):
    def stub(addr, method, path, body=None, **kw):
        started.set()
        assert release.wait(15.0), "test forgot to release the RPC stub"
        return {}
    return stub


def test_create_index_fans_out_rpcs_outside_state_lock(monkeypatch):
    """While the member PUT fan-out is in flight (stub blocked), the
    state lock must be free: concurrent joins/routes proceed."""
    node = DistClusterNode("solo_ci")
    started, release = threading.Event(), threading.Event()
    try:
        node.members["ghost"] = "127.0.0.1:1"
        monkeypatch.setattr(dn_mod, "_http",
                            _blocked_http(started, release))
        out = {}
        t = threading.Thread(
            target=lambda: out.setdefault(
                "resp", node.create_index("idx_ci", MAPPING)))
        t.start()
        assert started.wait(10.0), "create_index never reached the RPC"
        got = node._lock.acquire(timeout=2.0)
        assert got, "state lock held across create_index RPC fan-out"
        node._lock.release()
        release.set()
        t.join(15.0)
        assert not t.is_alive()
        # routing/copies snapshots taken under the lock stay coherent
        assert out["resp"]["acknowledged"] is True
        assert set(out["resp"]["routing"].values()) <= {"solo_ci", "ghost"}
    finally:
        release.set()
        node.stop()


def test_join_publishes_outside_state_lock(monkeypatch):
    """While the join-triggered publish RPC is in flight (stub blocked),
    the state lock must be free."""
    node = DistClusterNode("solo_j")
    started, release = threading.Event(), threading.Event()
    try:
        monkeypatch.setattr(dn_mod, "_http",
                            _blocked_http(started, release))
        out = {}
        t = threading.Thread(
            target=lambda: out.setdefault(
                "resp", node.handle_internal(
                    "POST", ["_internal", "join"],
                    {"name": "ghost", "addr": "127.0.0.1:1"})))
        t.start()
        assert started.wait(10.0), "join never reached the publish RPC"
        got = node._lock.acquire(timeout=2.0)
        assert got, "state lock held across join publish RPC"
        node._lock.release()
        release.set()
        t.join(15.0)
        assert not t.is_alive()
        status, resp = out["resp"]
        assert status == 200
        assert "ghost" in resp["state"]["members"]
    finally:
        release.set()
        node.stop()


def test_apply_state_ignores_stale_version():
    """Publishes fan out unserialized (outside the state lock), so a
    slow send can deliver version N after a fast one delivered N+1.
    Applying the late post must not regress members/routing — the
    reviewer-found regression: the new member silently vanished."""
    node = DistClusterNode("solo_mono")
    try:
        newer = {"term": 1, "version": 5, "leader": "ldr",
                 "members": {"solo_mono": node.addr,
                             "ldr": "127.0.0.1:1",
                             "new_member": "127.0.0.1:2"},
                 "routing": {}, "copies": {}, "index_bodies": {}}
        node._apply_state(newer)
        assert node.version == 5
        assert "new_member" in node.members

        stale = {"term": 1, "version": 4, "leader": "ldr",
                 "members": {"solo_mono": node.addr, "ldr": "127.0.0.1:1"},
                 "routing": {}, "copies": {}, "index_bodies": {}}
        node._apply_state(stale)   # late delivery of the older post
        assert node.version == 5, "stale publish regressed the version"
        assert "new_member" in node.members, \
            "stale publish silently dropped the newer member"

        # equal version: redelivery of the same post is ignored too
        node._apply_state(dict(newer, members={}))
        assert "new_member" in node.members

        # a higher term always wins, regardless of version (new leader
        # restarting the version sequence)
        node._apply_state({"term": 2, "version": 1, "leader": "ldr2",
                           "members": {"ldr2": "127.0.0.1:3"},
                           "routing": {}, "copies": {},
                           "index_bodies": {}})
        assert node.term == 2 and node.version == 1
        assert node.leader == "ldr2"
    finally:
        node.stop()


def test_state_snapshot_isolated_from_concurrent_mutation():
    """_publish serializes the _state() snapshot OUTSIDE the lock; the
    snapshot must not alias the live member/body maps, or a concurrent
    join mid-json.dumps raises "dict changed size during iteration"
    (and different targets receive different member sets)."""
    import json as _json
    node = DistClusterNode("solo_snap")
    try:
        node.index_bodies["idx_snap"] = {"settings": {}}
        st = node._state()
        # mutate the live maps after the snapshot was taken
        node.members["late_joiner"] = "127.0.0.1:9"
        node.index_bodies["idx_late"] = {"settings": {}}
        assert "late_joiner" not in st["members"]
        assert "idx_late" not in st["index_bodies"]
        _json.dumps(st)  # the fan-out serialization the snapshot feeds
    finally:
        node.stop()
