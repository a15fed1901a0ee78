"""A launch, end to end, in the program's own trace (ISSUE 38): one
`device.dispatch` span round every program call, every device read inside
a `device.wait` that names the same `program`, `first_call` on a program's
first call alone, the jit attribution fed from the span's clock, and named
stages that are metadata of the ops and nothing else.

Down each path a CPU has: the XLA executor, a `bool.filter` clause (the
mask program), an aggregation, the impact path, and the ladder's frontier
and device rescore (the numpy stand-ins of `tests/test_pruned.py` in the
Pallas kernels' place, as `tests/test_rescore.py` drives them)."""

import contextlib

import jax
import numpy as np
import pytest

from opensearch_tpu.ops import aggs as agg_ops
from opensearch_tpu.ops import scoring as ops
from opensearch_tpu.ops.rescore import exact_rescore_batch
from opensearch_tpu.search import compiler as C, programs as PG
from opensearch_tpu.search import fastpath
from opensearch_tpu.utils.metrics import METRICS
from opensearch_tpu.utils.trace import TRACER
from tests.test_rescore import (QUERIES, _spec, corpus,  # noqa: F401
                                small_head)

DISPATCH, WAIT = "device.dispatch", "device.wait"


@pytest.fixture(scope="module")
def client():
    """A plain one-chip node (the benchmark cells' path; no mesh)."""
    from opensearch_tpu.cluster.node import Node
    from opensearch_tpu.rest.client import RestClient
    c = RestClient(node=Node(mesh_service=False))
    c.indices.create("launches", {
        "settings": {"number_of_shards": 1, "number_of_replicas": 0},
        "mappings": {"properties": {"body": {"type": "text"},
                                    "n": {"type": "integer"},
                                    "price": {"type": "float"}}}})
    for i in range(300):
        c.index("launches", {"body": f"alpha beta w{i % 7} v{i % 11}",
                             "n": i, "price": (i % 50) / 4.0}, id=str(i))
    c.indices.refresh("launches")
    return c


def _walk(span):
    """A request's spans in start order (one thread: depth first)."""
    yield span
    for ch in span.children:
        yield from _walk(ch)


def _traced(fn):
    """The root span of the one request `fn` sends."""
    TRACER._traces.clear()
    fn()
    (root,) = TRACER._traces
    return root


def _launches(root):
    return [(s.name, s.attributes) for s in _walk(root)
            if s.name in (DISPATCH, WAIT)]


def _assert_every_wait_follows_its_dispatch(root):
    seen, waits = set(), 0
    for name, attrs in _launches(root):
        if name == DISPATCH:
            assert set(attrs) >= {"program", "first_call"}
            seen.add(attrs["program"])
        else:
            waits += 1
            assert attrs["program"] in seen, (attrs, seen)
    assert waits and seen
    return seen


# distinct constants a call, so the request cache answers none of them
REQUESTS = {
    "executor": lambda i: {
        "query": {"range": {"n": {"gte": i, "lt": 200 + i}}},
        "sort": [{"price": "desc"}], "size": 5},
    "filter": lambda i: {
        "query": {"bool": {"must": [{"match": {"body": "alpha"}}],
                           "filter": [{"range": {"n": {"gte": i,
                                                       "lt": 150 + i}}}]}}},
    "aggregation": lambda i: {
        "size": 0, "query": {"range": {"n": {"gte": i}}},
        "aggs": {"h": {"histogram": {"field": "n", "interval": 50},
                       "aggs": {"s": {"stats": {"field": "price"}}}}}},
    "impact": lambda i: {"query": {"match": {"body": f"alpha w{i % 7}"}}},
}
PROGRAMS = {"executor": {"executor"}, "filter": {"mask", "executor"},
            "aggregation": {"executor"}, "impact": {"impact"}}


@pytest.mark.parametrize("path", sorted(REQUESTS))
def test_every_wait_follows_a_dispatch_of_its_program(client, path):
    roots = [_traced(lambda i=i: client.search("launches",
                                               REQUESTS[path](i)))
             for i in (1, 2)]
    for root in roots:
        assert root.name == "rest.search"
        assert _assert_every_wait_follows_its_dispatch(root) \
            >= PROGRAMS[path]
    # the second call of a program compiles nothing and says so
    first, second = ({(a["program"], a["first_call"])
                      for n, a in _launches(r) if n == DISPATCH}
                     for r in roots)
    assert all(not fc for _p, fc in second), second
    assert {p for p, _fc in first} == {p for p, _fc in second}


def test_the_filter_masks_read_is_a_device_wait_of_its_own(client):
    root = _traced(lambda: client.search("launches", REQUESTS["filter"](7)))
    got = _launches(root)
    i = got.index((WAIT, {"program": "mask"}))
    assert got[i - 1][0] == DISPATCH and got[i - 1][1]["program"] == "mask"
    # the read is inside `search.prepare`, whose own time it used to be
    (prepare,) = [s for s in _walk(root) if s.name == "search.prepare"]
    assert {(s.name, s.attributes.get("program"))
            for s in prepare.children} >= {(DISPATCH, "mask"),
                                           (WAIT, "mask")}
    # an aggregation's outputs are read under the program that made them
    root = _traced(lambda: client.search("launches",
                                         REQUESTS["aggregation"](9)))
    assert (WAIT, {"program": "executor", "outputs": "aggs"}) \
        in _launches(root)


def test_the_ladders_kernel_launches_and_rescore(corpus,    # noqa: F811
                                                 small_head):  # noqa: F811
    seg, ctx = corpus
    seg.__dict__.pop("_fastpath_aligned", None)
    fastpath._LAUNCHED_SHAPES.clear()
    fastpath.set_rescore_mode("device")
    roots = []
    try:
        for _ in (1, 2):
            TRACER._traces.clear()
            with TRACER.span("rest.search"):
                for q, w in QUERIES:
                    assert fastpath.batch_search(
                        seg, ctx, [_spec(ctx, q, w)], w)[0] is not None
            roots.append(TRACER._traces[-1])
    finally:
        fastpath.set_rescore_mode(None)
    for root in roots:
        assert _assert_every_wait_follows_its_dispatch(root) \
            == {"frontier", "rescore"}
    kernels = [a for n, a in _launches(roots[0])
               if n == DISPATCH and a["program"] == "frontier"]
    assert {"fused_bm25_topk_impact"} <= {a["kernel"] for a in kernels} \
        <= {"fused_bm25_topk_impact", "fused_bm25_topk_tfdl"}
    assert any(a["first_call"] for a in kernels)
    assert not any(a["first_call"] for n, a in _launches(roots[1])
                   if n == DISPATCH)


def test_first_call_is_true_once_and_the_span_is_the_clock():
    prog = C._TimedProgram("executor", lambda x: x + 1)
    before = C.jit_attribution().get("executor")
    METRICS.counter("search.jit.executor.requests").inc()
    with TRACER.span("rest.search") as root:
        assert [prog(1), prog(2), prog(3)] == [2, 3, 4]
    spans = [s for s in _walk(root) if s.name == DISPATCH]
    assert [s.attributes for s in spans] == [
        {"program": "executor", "first_call": True},
        {"program": "executor", "first_call": False},
        {"program": "executor", "first_call": False}]
    after = C.jit_attribution()["executor"]

    def grew(kind, key):
        return after[kind][key] - (before[kind][key] if before else 0)
    assert grew("compile", "count") == 1 and grew("execute", "count") == 2
    # the histograms hold the spans' own durations: no second clock
    assert grew("compile", "total_ms") == pytest.approx(
        spans[0].duration_ns() / 1e6, abs=2e-3)
    assert grew("execute", "total_ms") == pytest.approx(
        sum(s.duration_ns() for s in spans[1:]) / 1e6, abs=2e-3)


def test_jit_attribution_still_counts_a_requests_launches(client):
    client.search("launches", REQUESTS["executor"](20))        # compiled
    before = C.jit_attribution()["executor"]
    root = _traced(lambda: client.search("launches",
                                         REQUESTS["executor"](21)))
    spans = [s for s in _walk(root)
             if s.name == DISPATCH and s.attributes["program"] == "executor"]
    after = C.jit_attribution()["executor"]
    assert after["execute"]["count"] - before["execute"]["count"] \
        == len(spans) >= 1
    assert after["compile"] == before["compile"]
    assert after["execute"]["total_ms"] - before["execute"]["total_ms"] \
        == pytest.approx(sum(s.duration_ns() for s in spans) / 1e6, abs=2e-3)
    assert after["cache"]["requests"] > before["cache"]["requests"]


def test_a_disabled_tracer_keeps_the_clock_pair_and_builds_no_span(
        monkeypatch):
    prog = C._TimedProgram("join", lambda: None)
    hist = METRICS.histogram("search.jit.join.execute_ms")
    n0 = hist.snapshot()["count"]
    monkeypatch.setattr(TRACER, "enabled", False)
    started = TRACER.stats()["spans"]
    prog()
    prog()
    assert TRACER.stats()["spans"] == started
    assert hist.snapshot()["count"] == n0 + 1 and prog._compiled
    # telemetry off as well: the bare call, and `first_call` still ends
    other = C._TimedProgram("join", lambda: None)
    monkeypatch.setattr(METRICS, "enabled", False)
    other()
    assert other._compiled and hist.snapshot()["count"] == n0 + 1


# ---------------------------------------------------------------------
# the stages on the device: names in the ops' metadata, and only there
# ---------------------------------------------------------------------

def _texts(fn, *args, **kw):
    """(the lowered program without debug info as the stages leave it,
    the same with `jax.named_scope` a no-op, the first with debug info)."""
    lowered = jax.jit(fn, **kw).lower(*args)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope",
                   lambda name: contextlib.nullcontext())
        bare = jax.jit(fn, **kw).lower(*args).as_text()
    return lowered.as_text(), bare, lowered.as_text(debug_info=True)


def _impact_args():
    n, p = 512, 2048
    rng = np.random.default_rng(3)
    return (np.sort(rng.integers(0, n, p)).astype(np.int32),
            rng.integers(0, 255, p).astype(np.uint8),
            np.ones(n, np.int32), np.arange(0, 1024, 128, dtype=np.int32),
            np.full(8, 128, np.int32), np.ones(8, np.float32),
            np.float32(1.0))


def _rescore_args():
    p, qb, t, c = 4096, 2, 2, 256
    docs = np.sort(np.random.default_rng(5).integers(0, 9000, p)
                   ).astype(np.int32)
    return (docs, np.ones(p, np.int32), np.zeros((qb, t), np.int32),
            np.full((qb, t), 1000, np.int32), np.ones((qb, t), np.float32),
            np.ones((qb, 1), np.float32), np.zeros((qb, c), np.int32),
            np.full(t, 10, np.int32))


STAGED = {
    "impact": (lambda: C.build_impact_program(8, 64, 8)._fn.__wrapped__,
               _impact_args, {},
               {"impact.gather", "impact.accumulate", "impact.topk"}),
    "rescore": (lambda: exact_rescore_batch.__wrapped__, _rescore_args,
                dict(static_argnames=("T", "C", "k1", "b")),
                {"rescore.probe", "rescore.score"}),
    "topk": (lambda: lambda s, m, l: ops.topk_docs(s, m, l, 16),
             lambda: (np.zeros(1 << 16, np.float32),
                      np.ones(1 << 16, bool), np.ones(1 << 16, np.int32)),
             {}, set()),
    "aggs.dense": (lambda: lambda b, v, w: agg_ops.bucketed_sub_metric(
        b, v, w, 16, np.float32(1.0), False),
        lambda: (np.zeros(4096, np.int32), np.ones(4096, np.float32),
                 np.ones(4096, np.float32)), {},
        {"aggs.bucketed_sub", "aggs.dense"}),
    "aggs.scatter": (lambda: lambda b, v, w: agg_ops.bucketed_sub_metric(
        b, v, w, 4096, np.float32(1.0), False),
        lambda: (np.zeros(4096, np.int32), np.ones(4096, np.float32),
                 np.ones(4096, np.float32)), {},
        {"aggs.bucketed_sub", "aggs.scatter"}),
    "aggs.run_counts": (lambda: agg_ops.run_counts,
                        lambda: (np.ones(1 << 14, np.int32),
                                 np.arange(0, 1 << 14, 1 << 10,
                                           dtype=np.int32)), {},
                        {"aggs.run_counts"}),
}


@pytest.mark.parametrize("name", sorted(STAGED))
def test_a_stage_is_metadata_and_changes_no_op(name):
    make, args, kw, want = STAGED[name]
    fn, a = make(), args()
    if name == "rescore":
        scoped, bare, debug = _texts(
            lambda *x: fn(*x, T=2, C=256, k1=1.2, b=0.75), *a)
    else:
        scoped, bare, debug = _texts(fn, *a, **kw)
    assert scoped == bare
    assert all(stage in debug for stage in want), want


def test_the_executor_programs_stages(client, monkeypatch):
    """The program a request launches, lowered again from the spec and the
    arguments it was called with: equal op for op without the scopes, and
    every stage of PERF.md section 3 on its ops' paths with them."""
    calls = []
    build = PG._build_executor

    def spy(full_spec):
        prog = build(full_spec)

        def call(*a):
            calls.append((full_spec, a))
            return prog(*a)
        return call
    monkeypatch.setattr(PG, "_build_executor", spy)
    client.search("launches", REQUESTS["aggregation"](31))
    client.search("launches", dict(REQUESTS["executor"](32), size=3))
    assert len(calls) == 2
    stages = set()
    for full_spec, a in calls:
        scoped, bare, debug = _texts(PG._executor_run_fn(full_spec), *a)
        assert scoped == bare
        stages |= {s for s in ("executor.match", "executor.sort_key",
                               "executor.topk", "executor.total",
                               "executor.aggs", "aggs.bucketed_sub",
                               "aggs.dense") if s in debug}
    assert stages == {"executor.match", "executor.sort_key", "executor.topk",
                      "executor.total", "executor.aggs", "aggs.bucketed_sub",
                      "aggs.dense"}
