"""TPU-hardware check of `match_phrase` over resident positional planes, at
2^20 positions or more a term: 64,000 generated articles of about 5,800
tokens (some 371M positions; the 31 most frequent terms hold 2^20 or more
each: at 36,000 articles, the first session's size, which no chip had run,
rank 30 held 633,407) through `RestClient.search`, the three shapes of the `pmc` cell with
every phrase's rarest word among those terms, against the kind's plain
reference; the `phrase.*` counters against what the shapes imply; the
join alone (`ops.positions`), each shape with its time; and the join alone
over made planes, 2^20 anchors against a window of 2^26 positions, timed a
row width of the search (128, what `ops.positions.ROW` is, and wider).
Run on a real chip: `python -m pytest tests_tpu/test_phrase_tpu.py -q -s`."""

import os
import sys
import time

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.join(ROOT, "benchmark"), ROOT)
                if p not in sys.path]

pytestmark = pytest.mark.skipif(jax.default_backend() != "tpu",
                                reason="needs a real TPU chip")

NDOCS = 64_000
RANKS = [1, 30]             # the rarest word of a phrase: 2^20 positions up
SHAPES = ("phrase2", "phrase3", "phrase3_common")


@pytest.fixture(scope="module")
def deployment():
    import pmc_articles as articles
    import pmc_reference as reference
    import run as harness
    from opensearch_tpu.rest.client import RestClient
    loaded = harness.load_cell("pmc.search1.phrase")
    config = dict(loaded["config"], ndocs=NDOCS)
    loaded["traffic"]["params"]["rarest_rank"] = RANKS
    kind = harness.load_kind("pmc")
    client = RestClient()
    t0 = time.time()
    built = kind.build(config, 1, client, harness.INDEX)
    print(f"\nbuilt {NDOCS} articles, {built['readout']['tokens']} positions "
          f"in {built['readout']['position_slots']} slots: build "
          f"{built['build_s']:.1f} s, promote {built['promote_s']:.1f} s "
          f"({time.time() - t0:.1f} s)")
    cf = articles.collection_frequency(built["articles"])
    assert np.sort(cf)[::-1][RANKS[1]] >= 1 << 20
    stream = kind.stream(built, loaded["traffic"], 3)
    arts = built["articles"]
    ref = reference.Reference(arts["tok"], arts["offsets"], arts["live"],
                              threads=articles.threads())
    return client, built, stream, ref, kind, harness


def test_the_pages_are_the_references_at_a_million_positions_a_term(
        deployment):
    import pmc_reference as reference
    from opensearch_tpu.ops import positions as pos_ops
    client, built, stream, ref, kind, harness = deployment
    specs = stream.take(16)
    assert {s["shape"] for s in specs} == set(SHAPES)
    assert min(s["weight"] for s in specs) >= 1 << 20
    for s in specs:     # compile: the same programs under another body
        harness.send(client, "search",
                     [dict(s, body=dict(s["body"], size=10))])
    before = kind.counters(client)
    held, ms = [], {}
    for s in specs:
        t0 = time.perf_counter()
        resp = harness.send(client, "search", [s])[0]
        ms.setdefault(s["shape"], []).append(
            (time.perf_counter() - t0) * 1e3)
        held.append((s, resp))
    moved = {k: v - before[k] for k, v in kind.counters(client).items()}
    out = reference.hold(held, ref, 1e-5)
    print("compared", out["numbers"])
    assert out["correct"], out["first_failures"]
    for shape in SHAPES:
        print(f"{shape}: {np.median(ms[shape]):.1f} ms a request "
              f"(median of {len(ms[shape])}, anchors of 2^20 slots up)")
    # what the shapes imply: one launch a request, an anchor window of the
    # rarest word's bucket, the searches' levels the commonest word's
    cf = ref.cf
    want = {"queries": len(specs), "anchor_slots": 0, "anchor_positions": 0,
            "window_positions": 0, "probe_elems": 0, "probe_rows": 0,
            "host_pair_builds": 0}
    for s in specs:
        lens = sorted(int(cf[t]) for t in s["terms"])
        bucket, levels = pos_ops.phrase_shape(lens)
        want["anchor_slots"] += bucket
        want["anchor_positions"] += lens[0]
        want["window_positions"] += sum(lens)
        want["probe_elems"] += pos_ops.probe_elems(bucket, len(lens) - 1,
                                                   levels)
        want["probe_rows"] += pos_ops.probe_rows(bucket, len(lens) - 1,
                                                 levels)
    got = {k: moved[f"phrase.{k}"] for k in want}
    print("counters", got, "handed a launch",
          moved["executor.params_h2d_bytes"] / len(specs), "bytes")
    assert got == want
    assert moved["executor.launches"] == len(specs)
    assert moved["executor.params_h2d_bytes"] <= 2048 * len(specs)


@pytest.mark.parametrize("m,anchor,other", [(2, 1 << 16, 1 << 20),
                                            (2, 1 << 20, 1 << 24),
                                            (3, 1 << 20, 1 << 26)])
def test_the_join_alone_counts_as_numpy(deployment, m, anchor, other):
    """`anchor_window` + `phrase_freqs` at slop 0 over the resident planes for
    the terms nearest the asked sizes, against the reference's count."""
    import jax.numpy as jnp
    from opensearch_tpu.ops import positions as pos_ops
    client, built, _stream, ref, _kind, harness = deployment
    (seg,) = client.node.indices[harness.INDEX].shards[0].segments
    pb, planes = seg.postings["body"], seg.device_positions("body")
    arts, cf = built["articles"], ref.cf
    table = arts["table"]
    # a phrase of the table: first word nearest `anchor` positions whose
    # chain's commonest word is nearest `other`
    firsts = np.argsort(np.abs(np.log(cf + 1.0) - np.log(anchor)))[:200]
    best = None
    for a in firsts.tolist():
        for k in range(table.shape[1]):
            terms = [a, int(table[a, k])]
            if m == 3:
                terms.append(int(table[terms[1], 0]))
            miss = abs(np.log(max(cf[t] for t in terms[1:])) - np.log(other))
            if cf[a] <= min(cf[t] for t in terms) and (
                    best is None or miss < best[0]):
                best = (miss, terms)
    terms = best[1]
    lens = [int(cf[t]) for t in terms]
    bucket, levels = pos_ops.phrase_shape(lens)

    los, ns = [], []
    for t in terms:
        lo, hi = pb.row_slice(pb.row(arts["words"][t]))
        los.append(int(pb.pos_starts[lo]))
        ns.append(int(pb.pos_starts[hi]) - los[-1])
    assert ns == lens

    @jax.jit
    def join(planes, los, ns):
        wins = [pos_ops.resident(planes, los[i], ns[i], levels)
                for i in range(m)]
        ad, ap = pos_ops.anchor_window(wins[0], bucket)
        return pos_ops.phrase_freqs(ad, ap, wins[1:], jnp.float32(0),
                                    seg.ndocs_pad,
                                    shifts=list(range(1, m)))
    wins = (planes, np.asarray(los, np.int32), np.asarray(ns, np.int32))
    got = np.asarray(join(*wins))
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(join(*wins))
        times.append((time.perf_counter() - t0) * 1e3)
    docs, f = ref.frequencies(terms)
    want = np.zeros(seg.ndocs_pad, np.float32)
    want[docs] = f
    elems = pos_ops.probe_elems(bucket, m - 1, levels)
    print(f"\njoin m={m} positions={lens} bucket={bucket} levels={levels}: "
          f"{np.median(times):.2f} ms (launch + read, median of 5), "
          f"{elems / 1e6:.1f}M indices gathered "
          f"({pos_ops.probe_rows(bucket, m - 1, levels) / 1e6:.1f}M of them "
          f"rows), {1e6 * np.median(times) / elems:.1f} ns an index, "
          f"{int(f.sum())} occurrences in {len(docs)} documents")
    assert np.array_equal(got, want)


ANCHORS, WINDOW = 1 << 20, 1 << 26


@pytest.mark.parametrize("row_bits", [7, 8, 9, 10])
def test_the_join_alone_a_row_width(monkeypatch, row_bits):
    """2^20 anchors (one a document) against a term of 2^26 positions (64 a
    document, the even ones), planes and fences made on the device: the
    search alone and the whole join at slop 0, timed with the search's row
    `1 << row_bits` slots wide, against the count that follows from how
    the planes were made."""
    import jax.numpy as jnp
    from opensearch_tpu.ops import positions as pos_ops
    monkeypatch.setattr(pos_ops, "ROW_BITS", row_bits)
    monkeypatch.setattr(pos_ops, "ROW", 1 << row_bits)
    at = np.random.default_rng(row_bits).integers(0, 128, ANCHORS).astype(
        np.int32)
    slot = jnp.arange(WINDOW, dtype=jnp.int32)
    planes = {"doc": jnp.concatenate([jnp.arange(ANCHORS, dtype=jnp.int32),
                                      slot >> 6]),
              "pos": jnp.concatenate([jnp.asarray(at), 2 * (slot & 63)])}
    del slot
    levels = pos_ops.search_levels(WINDOW)
    for plane in ("doc", "pos"):
        for k, level in enumerate(pos_ops.fences(planes[plane], levels), 1):
            planes[pos_ops.plane_key(plane, k)] = level
    los = np.asarray([0, ANCHORS], np.int32)
    ns = np.asarray([ANCHORS, WINDOW], np.int32)

    @jax.jit
    def search(planes, los, ns):
        ad, ap = pos_ops.anchor_window(
            pos_ops.resident(planes, los[0], ns[0], 1), ANCHORS)
        return pos_ops.window_searchsorted(
            pos_ops.resident(planes, los[1], ns[1], levels), ad, ap + 1)

    @jax.jit
    def join(planes, los, ns):
        wins = [pos_ops.resident(planes, los[i], ns[i], levels)
                for i in range(2)]
        ad, ap = pos_ops.anchor_window(wins[0], ANCHORS)
        return pos_ops.phrase_freqs(ad, ap, wins[1:], jnp.float32(0),
                                    ANCHORS, shifts=[1])
    ms = {}
    for name, fn in (("search", search), ("join", join)):
        got = np.asarray(fn(planes, los, ns))
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(fn(planes, los, ns))
            times.append((time.perf_counter() - t0) * 1e3)
        ms[name] = float(np.median(times))
        if name == "search":    # the first even position past `at`, or the
            # next document's first
            doc = np.arange(ANCHORS, dtype=np.int64)
            want = ANCHORS + 64 * doc + np.minimum((at + 2) // 2, 64)
        else:
            want = ((at % 2 == 1) & (at < 127)).astype(np.float32)
        assert np.array_equal(got, want), name
    rows = pos_ops.probe_rows(ANCHORS, 1, levels)
    elems = pos_ops.probe_elems(ANCHORS, 1, levels)
    print(f"\nrow of {1 << row_bits}: {levels} levels, search "
          f"{ms['search']:.2f} ms ({1e6 * ms['search'] / rows:.1f} ns a row "
          f"gathered, {rows / 1e6:.1f}M rows), join {ms['join']:.2f} ms "
          f"({1e6 * ms['join'] / elems:.1f} ns an index, "
          f"{elems / 1e6:.1f}M indices; launch + read, median of 5)")
