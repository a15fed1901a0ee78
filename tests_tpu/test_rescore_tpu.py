"""TPU-hardware parity for the device-batched phase-2 rescore
(ops/rescore.py): on a real chip the batched kernel must reproduce the host
numpy oracle BIT-FOR-BIT — exact f32 scores, match counts, and the
serve/escalate decisions the escalation ladder makes on them. Run on a real
chip: `python -m pytest tests_tpu/test_rescore_tpu.py -q`."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from opensearch_tpu.ops.pallas_bm25 import (DL_BITS, INT_SENTINEL, LANES,
                                            align_csr_rows)
from opensearch_tpu.ops import rescore
from opensearch_tpu.ops.rescore import (exact_rescore_batch,
                                        host_exact_rescore_batch,
                                        probe_rounds)
from opensearch_tpu.rest.client import RestClient
from opensearch_tpu.search import fastpath

pytestmark = pytest.mark.skipif(jax.default_backend() != "tpu",
                                reason="needs a real TPU chip")


@pytest.fixture(params=["plane_in_vmem", "plane_in_hbm"])
def plane(request, monkeypatch):
    """Both forms of the search over a small plane: unrolled (XLA keeps the
    plane in VMEM) and one `while` a term slot (as over a plane in HBM)."""
    if request.param == "plane_in_hbm":
        monkeypatch.setattr(rescore, "VMEM_PLANE_BYTES", 0)
    exact_rescore_batch.clear_cache()
    yield request.param
    exact_rescore_batch.clear_cache()


@pytest.mark.parametrize("seed", [5, 23])
def test_kernel_bitwise_parity_on_silicon(seed, plane):
    """Raw kernel vs numpy mirror over the same padded operands — exact
    f32 byte equality (the _tie_serves/theta32 contract), not allclose."""
    rng = np.random.default_rng(seed)
    nterms, ndocs = 6, 50_000
    starts_l = [0]
    docs, tfdl = [], []
    for _ in range(nterms):
        df = int(rng.integers(10, 8000))
        ids = np.sort(rng.choice(ndocs, size=df, replace=False))
        tf = rng.integers(1, 30, df)
        dl = rng.integers(1, 500, df)
        docs.append(ids.astype(np.int32))
        tfdl.append(((tf.astype(np.int64) << DL_BITS) | dl).astype(np.int32))
        starts_l.append(starts_l[-1] + df)
    a_starts, a_docs, a_tfdl = align_csr_rows(
        np.asarray(starts_l, np.int64), np.concatenate(docs),
        np.concatenate(tfdl), margin=1024, alignment=LANES)
    T, C, QB = 4, 1024, 8
    starts = np.zeros((QB, T), np.int32)
    lens = np.zeros((QB, T), np.int32)
    weights = np.zeros((QB, T), np.float32)
    avgdl = np.zeros((QB, 1), np.float32)
    cand = np.full((QB, C), INT_SENTINEL, np.int32)
    for q in range(QB):
        for t in range(T):
            if rng.random() < 0.2:
                continue
            r = int(rng.integers(0, nterms))
            a, b = int(a_starts[r]), int(a_starts[r + 1])
            starts[q, t] = a
            lens[q, t] = int(np.sum(a_docs[a:b] != INT_SENTINEL))
            weights[q, t] = np.float32(rng.uniform(0.1, 4.0))
        avgdl[q, 0] = np.float32(rng.uniform(1.0, 300.0))
        n = int(rng.integers(1, C))
        cand[q, :n] = np.sort(rng.choice(ndocs, size=n, replace=False))
    for k1, b in ((1.2, 0.75), (0.9, 0.0)):
        dx, dc = exact_rescore_batch(
            jnp.asarray(a_docs), jnp.asarray(a_tfdl), starts, lens,
            weights, avgdl, cand, probe_rounds(lens, len(a_docs)), T=T, C=C,
            k1=k1, b=b)
        hx, hc = host_exact_rescore_batch(
            a_docs, a_tfdl, starts, lens, weights, avgdl, cand, k1=k1, b=b)
        assert np.asarray(dx).tobytes() == hx.tobytes()
        assert (np.asarray(dc) == hc).all()


@pytest.fixture(scope="module")
def client(request):
    # shrink L_HEAD so a 20k-doc corpus genuinely clamps and the verify
    # rung actually escalates into the phase-2 rescore
    orig = fastpath.L_HEAD
    fastpath.L_HEAD = 256
    request.addfinalizer(lambda: setattr(fastpath, "L_HEAD", orig))
    rng = np.random.default_rng(7)
    words = [f"w{i}" for i in range(400)]
    c = RestClient()
    c.indices.create("ridx")
    bulk = []
    for i in range(20_000):
        parts = list(rng.choice(words, size=10))
        if rng.random() < 0.6:
            parts.extend(["common"] * int(rng.integers(1, 4)))
        if rng.random() < 0.4:
            parts.append("semi")
        bulk.append({"index": {"_index": "ridx", "_id": str(i)}})
        bulk.append({"body": " ".join(parts)})
    c.bulk(bulk)
    c.indices.refresh("ridx")
    c.indices.forcemerge("ridx")
    return c


@pytest.mark.parametrize("body", [
    {"query": {"match": {"body": "common semi"}}, "size": 10},
    {"query": {"match": {"body": "common w3 semi"}}, "size": 10},
    {"query": {"match": {"body": {"query": "common semi",
                                  "operator": "and"}}}, "size": 10},
])
def test_serve_decisions_host_vs_device(client, body):
    """End-to-end on silicon: same served pages, bit-identical scores, and
    the same serve/dense split whichever side runs the middle rung."""
    c = client
    outs, splits = {}, {}
    keys = ("pruned_served", "pruned_rescued", "pruned_rescued2",
            "pruned_escalated")
    for i, mode in enumerate(("host", "device")):
        fastpath.set_rescore_mode(mode)
        before = dict(fastpath.STATS)
        try:
            # _ref busts the request cache between the two runs
            outs[mode] = c.search(index="ridx", body=dict(body, _ref=i))
        finally:
            fastpath.set_rescore_mode(None)
        splits[mode] = {k: fastpath.STATS[k] - before[k] for k in keys}
    assert splits["host"] == splits["device"], body
    h, d = outs["host"], outs["device"]
    assert [(x["_id"], x["_score"]) for x in h["hits"]["hits"]] == \
        [(x["_id"], x["_score"]) for x in d["hits"]["hits"]], body
    assert h["hits"]["total"] == d["hits"]["total"]


def test_device_rescore_engaged(client):
    """The device path actually launched (RESCORE_STATS moved) for an
    escalating msearch batch, grouped into few launches."""
    c = client
    before = dict(fastpath.RESCORE_STATS)
    fastpath.set_rescore_mode("device")
    try:
        lines = []
        for i in range(8):
            lines.append({"index": "ridx"})
            lines.append({"query": {"match": {"body": "common semi"}},
                          "size": 10, "_ref": 100 + i})
        c.msearch(lines)
    finally:
        fastpath.set_rescore_mode(None)
    dq = fastpath.RESCORE_STATS["device_queries"] - before["device_queries"]
    dl = fastpath.RESCORE_STATS["device_launches"] \
        - before["device_launches"]
    if dq == 0:
        pytest.skip("no query escalated into the phase-2 rung")
    assert dl <= dq
