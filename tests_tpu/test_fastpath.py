"""TPU-hardware parity tests for the Pallas production fast path.

Run on a machine with a real TPU chip (NOT under tests/conftest.py, which
pins the CPU backend): `python -m pytest tests_tpu/ -q`.

Asserts the fused kernel path returns bit-identical hits/totals to the XLA
gather→scatter path through the REST client, including the doc-range chunked
decomposition for huge posting rows and the batched msearch path.
"""

import numpy as np
import pytest

import jax

from opensearch_tpu.rest.client import RestClient
from opensearch_tpu.search import fastpath

pytestmark = pytest.mark.skipif(jax.default_backend() != "tpu",
                                reason="needs a real TPU chip")


@pytest.fixture(scope="module")
def client():
    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(300)]
    c = RestClient()
    c.indices.create("idx")
    bulk = []
    for i in range(4000):
        parts = list(rng.choice(words, size=12))
        if rng.random() < 0.6:
            parts.append("common")
        bulk.append({"index": {"_index": "idx", "_id": str(i)}})
        bulk.append({"body": " ".join(parts)})
    c.bulk(bulk)
    c.indices.refresh("idx")
    return c


def _both(c, body):
    fastpath.set_enabled(True)
    fast = c.search(index="idx", body=body)
    fastpath.set_enabled(False)
    slow = c.search(index="idx", body=body)
    fastpath.set_enabled(True)
    return fast, slow


def _hits(resp):
    return [(h["_id"], round(h["_score"], 6)) for h in resp["hits"]["hits"]]


QUERIES = [
    {"query": {"match": {"body": "w1 w2"}}, "size": 10},
    {"query": {"term": {"body": "w5"}}, "size": 5},
    {"query": {"match": {"body": {"query": "w3 w7 w11",
                                  "minimum_should_match": 2}}}, "size": 7},
    {"query": {"match": {"body": {"query": "w0 w250",
                                  "operator": "and"}}}, "size": 10},
    {"query": {"terms": {"body": ["w8", "w9", "w10"]}}, "size": 10},
    {"query": {"match": {"body": "common w4"}}, "size": 10},
]


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_parity_vs_xla(client, qi):
    body = QUERIES[qi]
    # unique marker defeats the request cache
    body = dict(body, _probe=qi)
    fast, slow = _both(client, body)
    assert fast["hits"]["total"] == slow["hits"]["total"]
    assert _hits(fast) == _hits(slow)


def test_fastpath_engaged(client):
    client.search(index="idx", body={"query": {"match": {"body": "w1"}}})
    eng = client.node.indices["idx"].shards[0]
    seg = eng.segments[0]
    al = getattr(seg, "_fastpath_aligned", None)
    assert al and al.get("body") is not None


def test_chunked_oversized_rows(client):
    old_l, old_tl = fastpath.MAX_L, fastpath.MAX_TL
    fastpath.MAX_L, fastpath.MAX_TL = 1 << 11, 1 << 12
    try:
        # prove the decomposition actually engages at these caps
        from opensearch_tpu.search import plan as PL
        from opensearch_tpu.search import query_dsl as dsl
        from opensearch_tpu.search.executor import ShardSearcher
        eng = client.node.indices["idx"].shards[0]
        s = ShardSearcher(eng)
        ctx = s.context()
        lt = PL.rewrite(dsl.parse_query({"match": {"body": "common w17"}}),
                        ctx, scoring=True)
        vls = fastpath._prepare_vqueries(eng.segments[0], ctx, [lt], {})
        assert vls[0] is not None and len(vls[0]) >= 2
        body = {"query": {"match": {"body": "common w17"}}, "size": 10,
                "_probe": "chunk"}
        fast, slow = _both(client, body)
        assert fast["hits"]["total"] == slow["hits"]["total"]
        assert _hits(fast) == _hits(slow)
    finally:
        fastpath.MAX_L, fastpath.MAX_TL = old_l, old_tl


def test_high_tf_packing(client):
    """tf in [1024, 2047] sets the i32 sign bit in the packed tf·dl word;
    the kernel must mask after its arithmetic shift (regression)."""
    c = RestClient()
    c.indices.create("hightf")
    c.index("hightf", {"body": "word " * 1500 + "other"}, id="big")
    c.index("hightf", {"body": "word other things"}, id="small")
    c.indices.refresh("hightf")
    for qi, q in enumerate(("word other", "word")):
        body = {"query": {"match": {"body": q}}, "size": 5, "_p": qi}
        fastpath.set_enabled(True)
        fast = c.search(index="hightf", body=body)
        fastpath.set_enabled(False)
        slow = c.search(index="hightf", body=body)
        fastpath.set_enabled(True)
        assert _hits(fast) == _hits(slow)
    # single-term: tf saturation beats length norm -> 1500x doc wins; a
    # sign-extended tf would send its score negative instead
    assert fast["hits"]["hits"][0]["_id"] == "big"


def test_msearch_batched_parity(client):
    msb = []
    for q in ("w1 w2", "w5", "w3 w7 w11", "common w250"):
        msb += [{"index": "idx"}, {"query": {"match": {"body": q}},
                                   "size": 5}]
    fastpath.set_enabled(True)
    fast = client.msearch(msb)
    fastpath.set_enabled(False)
    slow = client.msearch(msb)
    fastpath.set_enabled(True)
    for a, b in zip(fast["responses"], slow["responses"]):
        assert a["hits"]["total"] == b["hits"]["total"]
        assert _hits(a) == _hits(b)
