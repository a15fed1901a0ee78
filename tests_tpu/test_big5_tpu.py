"""TPU-hardware check of the group-bys the `big5` cell runs, at its size:
16,777,216 padded rows, ordinals in no row order. First `ops.aggs` alone at
the cell's bucket counts against `np.bincount` (a `terms` into 65,536
slots and a keyword cardinality's registers over 16,384: the product of
two one-hots since PR 45; a `multi_terms` / `composite` plane into 312 and
512 slots: the dense form; a composite's 461,089 combinations: whatever
`count_form` names), each with its time, a keyword column's group-bys in
both of its layouts, by document and by value, side by side; where the
second constant stands: product against scatter from 2,048 to 524,288
slots; a count under a row span of 0.6% to 100% of the rows (PR 49: its
time follows the span's blocks) and the product's block probed under it;
then the seven request shapes through `RestClient.search` over
1,048,576 generated events (20,968 streams, 5,242 agents: past
`_DENSE_BUCKETS` as at the cell's size) against the kind's plain reference.
Run on a real chip: `python -m pytest tests_tpu/test_big5_tpu.py -q -s`."""

import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from opensearch_tpu.ops import aggs as agg_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.join(ROOT, "benchmark"), ROOT)
                if p not in sys.path]

pytestmark = pytest.mark.skipif(jax.default_backend() != "tpu",
                                reason="needs a real TPU chip")

N = 1 << 24
NDOCS = 16_571_428          # the cell's rows; the rest is padding


@pytest.fixture(scope="module")
def rows():
    """Host and device planes: Zipf-like ordinals over [0, 2^19) to be
    folded to a test's slots, -1 on the padded rows, and a 0/1 match that
    leaves a window of the rows."""
    rng = np.random.default_rng(43)
    u = rng.random(N)
    ords = np.minimum((np.exp(u * np.log(1 << 19)) - 1).astype(np.int32),
                      (1 << 19) - 1)
    ords[NDOCS:] = -1
    match = np.zeros(N, np.float32)
    match[N // 5: N // 5 * 4] = 1.0
    return (ords, match), (jnp.asarray(ords), jnp.asarray(match))


def _timed(fn, args, reps=5):
    # (a new function object a call: `jax.jit` of one it has traced answers
    # from its cache, whatever a test has made of the constants since)
    jfn = jax.jit(lambda *a: fn(*a))
    out = jax.tree_util.tree_map(np.asarray, jfn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.tree_util.tree_map(np.asarray, jfn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, float(np.median(times))


def _want(ords_h, match_h, nb):
    ok = (ords_h >= 0) & (match_h > 0)
    return np.bincount(ords_h[ok] % nb, minlength=nb)


@pytest.mark.parametrize("nb,what", [
    (312, "composite-terms (12 x 26)"), (512, "multi_terms plane, padded"),
    (65_536, "terms over 40,000 streams, padded"),
    (461_089, "composite_terms-keyword's combinations")])
def test_a_plane_of_ordinals_counts_exactly(rows, nb, what):
    (ords_h, match_h), dev = rows

    def fn(ords, match):
        return agg_ops.ord_counts(jnp.where(ords >= 0, ords % nb, -1),
                                  match, nb)
    got, ms = _timed(fn, dev)
    assert got.dtype == np.int32
    assert np.array_equal(got, _want(ords_h, match_h, nb))
    assert int(got.max()) > 2048        # past what a float16 would count
    form = agg_ops.count_form(nb)
    print(f"ord_counts n={N} slots={nb} ({what}): {form} {ms:.2f} ms "
          f"(launch + read, median of 5)")


@pytest.mark.parametrize("nvocab", [32, 16_384, 65_536])
def test_terms_counts_and_a_keyword_cardinality_in_both_forms(rows, nvocab):
    """A keyword column holding one value a document at most, in its two
    layouts, in one run on one chip: by document (`min_ord` alone, counted
    under the mask: what the segment hands over since PR 44) and by value
    (ordinals with the document of each value beside them, here the row
    numbers; the match gathered through it: a multi-valued column's)."""
    (ords_h, match_h), (ords, match) = rows
    by_doc = {"min_ord": jnp.where(ords >= 0, ords % nvocab, -1)}
    by_value = {"ords": by_doc["min_ord"], "min_ord": by_doc["min_ord"],
                "doc_of_value": jnp.where(
                    ords >= 0, jnp.arange(N, dtype=jnp.int32),
                    np.int32(2**31 - 1))}
    assert agg_ops.counts_by_value(by_value)
    assert not agg_ops.counts_by_value(by_doc)
    hashes = jnp.asarray(np.random.default_rng(1).integers(
        0, 1 << 32, nvocab, dtype=np.uint64).astype(np.uint32))
    want = _want(ords_h, match_h, nvocab)
    form = agg_ops.count_form(nvocab)
    for name, kw in (("by document", by_doc), ("by value", by_value)):
        got, ms = _timed(lambda k, m: agg_ops.terms_counts(k, m, nvocab),
                         (kw, match))
        assert got.dtype == np.int32 and np.array_equal(got, want)
        (regs, distinct), ms_card = _timed(
            lambda k, m, h: agg_ops.cardinality_keyword_registers(
                k, m, nvocab, h, 14), (kw, match, hashes))
        assert int(distinct) == int((want > 0).sum())
        assert regs.shape == (1 << 14,) and int(regs.max()) > 0
        count, ms_vc = _timed(agg_ops.value_count_keyword, (kw, match))
        assert int(count) == int(want.sum())
        print(f"terms_counts n={N} vocabulary={nvocab} {name}: {form} "
              f"{ms:.2f} ms; cardinality registers + distinct "
              f"{ms_card:.2f} ms; value_count {ms_vc:.2f} ms "
              f"(launch + read, median of 5)")


@pytest.mark.parametrize("nb", [2048, 16_384, 65_536, 131_072, 262_144,
                                461_089, 524_288])
def test_where_the_second_constant_stands(rows, monkeypatch, nb):
    """Product against scatter on either side of `_PRODUCT_BUCKETS`: both
    equal `np.bincount`; the product's time grows with the slots and the
    scatter's does not; under the constant the product wins, and the
    constant lies where it still wins by a wide margin (at or over it the
    scatter is the program's form whichever reads faster here)."""
    (ords_h, match_h), dev = rows

    def fn(ords, match):
        return agg_ops.ord_counts(jnp.where(ords >= 0, ords % nb, -1),
                                  match, nb)
    monkeypatch.setattr(agg_ops, "_DENSE_BUCKETS", 0)
    monkeypatch.setattr(agg_ops, "_PRODUCT_BUCKETS", 1 << 30)
    product, p_ms = _timed(fn, dev)
    monkeypatch.setattr(agg_ops, "_PRODUCT_BUCKETS", 0)
    scatter, s_ms = _timed(fn, dev, reps=3)
    monkeypatch.undo()
    want = _want(ords_h, match_h, nb)
    assert product.dtype == scatter.dtype == np.int32
    assert np.array_equal(product, want) and np.array_equal(scatter, want)
    chosen = agg_ops.count_form(nb)
    h, l = agg_ops.product_split(nb)
    print(f"bucket_counts n={N} slots={nb}: product ({h} x {l}) "
          f"{p_ms:.2f} ms, scatter {s_ms:.2f} ms, chosen {chosen} "
          f"(launch + read, median)")
    if chosen == "product":
        assert p_ms < s_ms
    else:
        assert chosen == "scatter" and p_ms > 0.5 * s_ms


SPAN_SHARES = [(0.006, "2 hours of 14 days"), (0.07, "24 hours"),
               (0.75, "the mean of the four wide operations"), (1.0, "whole")]


def _window(share):
    """(lo, hi, host match, device match): a window of `share` of the
    cell's rows that starts inside a block, four fifths of its rows
    matching."""
    rows = int(share * NDOCS)
    lo = min(5_000_077, NDOCS - rows)
    hi = lo + rows
    match = np.zeros(N, np.float32)
    match[lo:hi] = np.random.default_rng(hi).random(hi - lo) < 0.8
    return np.int32(lo), np.int32(hi), match, jnp.asarray(match)


@pytest.mark.parametrize("nb,what", [
    (312, "composite-terms: dense"),
    (65_536, "terms over 40,000 streams: product, 256 x 256"),
    (461_089, "composite_terms-keyword: product, 451 x 1,024")])
def test_a_row_span_bounds_the_rows_a_count_reads(rows, nb, what):
    """`bucket_counts` under a row span (PR 49) at the cell's rows: equal
    to `np.bincount` at every window; its time follows the blocks the span
    meets and not the plane; the whole span through traced bounds reads as
    the call without one (a loop of static length: what the parent runs)
    to a few percent."""
    (ords_h, _m), (ords, _dm) = rows

    def fn(ords, match, lo, hi):
        return agg_ops.ord_counts(jnp.where(ords >= 0, ords % nb, -1),
                                  match, nb, (lo, hi))

    def whole(ords, match):
        return agg_ops.ord_counts(jnp.where(ords >= 0, ords % nb, -1),
                                  match, nb)
    ms_by_share = {}
    for share, label in SPAN_SHARES:
        lo, hi, match_h, match = _window(share)
        got, ms = _timed(fn, (ords, match, lo, hi))
        assert got.dtype == np.int32
        assert np.array_equal(got, _want(ords_h, match_h, nb))
        ms_by_share[share] = ms
        print(f"ord_counts n={N} slots={nb} ({what}) span {share:.1%} "
              f"({label}): {ms:.2f} ms (launch + read, median of 5)")
    got, ms = _timed(whole, (ords, match))
    assert np.array_equal(got, _want(ords_h, match_h, nb))
    print(f"ord_counts n={N} slots={nb} no span (a loop of static length, "
          f"the parent's): {ms:.2f} ms; the whole span through traced "
          f"bounds {ms_by_share[1.0]:.2f} ms")
    assert ms_by_share[1.0] < 1.05 * ms + 0.3
    assert ms_by_share[0.006] < ms_by_share[0.07] < ms_by_share[1.0]
    assert ms_by_share[0.006] < 0.35 * ms_by_share[1.0]


@pytest.mark.parametrize("block", [1 << 13, 1 << 14, 1 << 15, 1 << 16,
                                   1 << 17, 1 << 18])
def test_the_products_block_under_a_narrow_span(rows, monkeypatch, block):
    """`_PRODUCT_BLOCK` probed again (PR 45 read whole planes): 461,089
    slots under the narrowest, a middling and the whole span, by rows a
    block; the answers do not move."""
    (ords_h, _m), (ords, _dm) = rows
    nb = 461_089
    monkeypatch.setattr(agg_ops, "_PRODUCT_BLOCK", block)

    def fn(ords, match, lo, hi):
        return agg_ops.ord_counts(jnp.where(ords >= 0, ords % nb, -1),
                                  match, nb, (lo, hi))
    line = []
    for share in (0.006, 0.026, 0.07, 1.0):
        lo, hi, match_h, match = _window(share)
        got, ms = _timed(fn, (ords, match, lo, hi))
        assert np.array_equal(got, _want(ords_h, match_h, nb))
        line.append(f"span {share:.1%} {ms:.2f} ms")
    # the two `terms`' 256 x 256 over the whole plane, by the same block
    got, ms = _timed(lambda ords, match: agg_ops.ord_counts(
        jnp.where(ords >= 0, ords % 65_536, -1), match, 65_536),
        (ords, match))
    assert np.array_equal(got, _want(ords_h, match_h, 65_536))
    print(f"product n={N} slots={nb} rows a block {block}: "
          + ", ".join(line) + f"; slots=65536 no span {ms:.2f} ms "
          "(launch + read, median of 5)")


def test_the_seven_shapes_through_the_client_at_a_million_events():
    os.environ["OPENSEARCH_TPU_MESH"] = "0"
    import big5_reference as reference
    import run as harness
    from opensearch_tpu.rest.client import RestClient
    from opensearch_tpu.search import aggregations as AGG, compiler as C
    kind = harness.load_kind("big5")
    loaded = harness.load_cell("big5.search1.terms")
    config = dict(loaded["config"], ndocs=1 << 20)
    client = RestClient()
    built = kind.build(config, 1, client, harness.INDEX)
    stream = kind.stream(built, loaded["traffic"], 77)
    specs = stream.take(14)
    for s in specs:                     # compile, build the planes
        client.search(harness.INDEX, stream.twin(s)["body"])
    before = {k: AGG.AGG_STATS[k] for k in AGG.AGG_STATS}
    h2d = C.EXECUTOR_STATS["params_h2d_bytes"]
    held, times = [], {}
    for s in specs:
        t0 = time.perf_counter()
        resp = client.search(harness.INDEX, s["body"])
        times.setdefault(s["shape"], []).append(
            (time.perf_counter() - t0) * 1e3)
        held.append((s, resp))
    out = reference.hold(held, kind.reference_of(built))
    print("compared", out["numbers"])
    assert out["correct"] is True and out["compared"] == 14
    got = {k: AGG.AGG_STATS[k] - v for k, v in before.items()}
    n = built["readout"]["rows_padded"]
    # every keyword is its ordinals by document: nothing gathered by value
    (seg,) = client.node.indices[harness.INDEX].shards[0].segments
    assert all(set(kw) == {"min_ord"}
               for kw in seg.device_arrays()["keyword"].values())
    assert got["terms.gathered_rows"] == 0
    assert built["readout"]["vocabulary"][reference.STREAM] > 2048
    # three of seven operations take the dense form, the two `terms` and
    # the cardinality the product; the composite's combinations whatever
    # `count_form` names at this size
    assert got["scatter.updates"] in (0, 2 * n)
    # every body stands under a range on `@timestamp`, which is in row
    # order: the loops read the blocks of each window (PR 49)
    assert got["span.segment_rows"] == 2 * 7 * n
    assert 0 < got["span.rows"] < got["span.segment_rows"]
    assert (got["span.rows"] < got["scatter.updates"] + got["blocked.rows"]
            < 2 * 7 * n)
    assert got["terms.records"] == 2 * (500 + 50 + 10 + 10 + 10)
    assert C.EXECUTOR_STATS["params_h2d_bytes"] - h2d < 14 * (1 << 18)
    for shape, ms in times.items():
        print(f"{shape}: {np.median(ms):.1f} ms a request (n={n}, "
              f"2 requests)")
    print("counters", got)
