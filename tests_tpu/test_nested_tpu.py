"""TPU-hardware check of the block join, at 2^22 child slots or more:
2,600,000 generated questions (some 4.4M answers: 2^23 child slots under
2^22 parent rows) through `RestClient.search`, the four shapes of the
`nested` cell and `match tag` alone against the kind's plain reference; the
`nested.*` counters against what the shapes imply; each shape with its
time; and the join's pieces alone over made planes at the cell's size
(2^25 child slots under 2^24 parents): the scatter-add the join takes, the
same with its indices declared sorted, the scatter-max, and the gather of
the parents' liveness to every child that the join no longer takes.
Run on a real chip: `python -m pytest tests_tpu/test_nested_tpu.py -q -s`."""

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

NDOCS = 2_600_000
RANKS = [1, 5000]
SHAPES = ("nested", "sorted_term", "inner_hits", "inner_hits_big")


@pytest.fixture(scope="module")
def deployment():
    import nested_reference as reference
    import run as harness
    from opensearch_tpu.rest.client import RestClient
    loaded = harness.load_cell("nested.search1.answers")
    config = dict(loaded["config"], ndocs=NDOCS)
    loaded["traffic"]["params"]["tag_rank"] = RANKS
    kind = harness.load_kind("nested")
    client = RestClient()
    t0 = time.time()
    built = kind.build(config, 1, client, harness.INDEX)
    r = built["readout"]
    print(f"\nbuilt {r['rows']} questions ({r['rows_padded']} rows), "
          f"{r['child_rows']} answers ({r['child_rows_padded']} child "
          f"slots), {r['tag_values']} tag values: build "
          f"{built['build_s']:.1f} s, promote {built['promote_s']:.1f} s "
          f"({time.time() - t0:.1f} s)")
    assert r["child_rows_padded"] >= 1 << 22
    stream = kind.stream(built, loaded["traffic"], 3)
    ref = reference.Reference(built["questions"])
    return client, built, stream, ref, kind, harness


def test_the_pages_are_the_references_at_four_million_children(deployment):
    import nested_reference as reference
    client, built, stream, ref, kind, harness = deployment
    specs = stream.take(20)
    assert {s["shape"] for s in specs} == set(SHAPES)
    for s in specs:     # compile: the same programs under another body
        harness.send(client, "search", [stream.twin(s)])
    before = kind.counters(client)
    held, ms = [], {}
    for s in specs:
        t0 = time.perf_counter()
        resp = harness.send(client, "search", [s])[0]
        ms.setdefault(s["shape"], []).append(
            (time.perf_counter() - t0) * 1e3)
        held.append((s, resp))
    moved = {k: v - before[k] for k, v in kind.counters(client).items()}
    out = reference.hold(held, ref)
    print("compared", out["numbers"])
    assert out["correct"], out["numbers"]
    for shape in SHAPES:
        print(f"{shape}: {np.median(ms[shape]):.1f} ms a request "
              f"(median of {len(ms[shape])})")
    # what the shapes imply: a launch a request; a clause reads every
    # child slot and takes two scatters over them; a sorted request builds
    # no plane (the twin did); inner hits gather the page's blocks
    r = built["readout"]
    clauses = sum(s["child"] is not None for s in specs)
    inner = [s for s in specs if s["inner"] is not None]
    got = {k[len("nested."):]: v for k, v in moved.items()
           if k.startswith("nested.")}
    print("counters", got, "handed a launch",
          moved["executor.params_h2d_bytes"] / len(specs), "bytes")
    assert got["queries"] == clauses
    assert got["child_rows"] == clauses * r["child_rows_padded"]
    assert got["child_rows_real"] == clauses * r["child_rows"]
    assert got["join_updates"] == 2 * clauses * r["child_rows_padded"]
    assert got["parents"] == clauses * r["rows_padded"]
    assert got["sort_plane_builds"] == 0 and got["programs"] == 0
    assert got["inner_hits_requests"] == len(inner)
    per = np.diff(built["questions"]["ans_off"])
    blocks = sum(int(per[int(h["_id"])]) for s, resp in held
                 if s["inner"] is not None for h in resp["hits"]["hits"])
    assert blocks <= got["inner_hits_child_rows"] \
        <= 2 * blocks + 64 * len(inner)
    assert got["inner_hits_readback_bytes"] <= 8 * got["inner_hits_child_rows"]
    assert moved["executor.params_h2d_bytes"] <= 2048 * len(specs)
    assert moved["sort.rank_plane.builds"] == 0


def test_match_tag_alone_and_the_path_that_served_it(deployment):
    """OSB's `randomized-term-queries`: on a TPU backend
    `fastpath.enabled()` is true and a keyword `match` may take the
    serving ladder; whichever path serves it, the page is the
    reference's."""
    import nested_reference as reference
    from opensearch_tpu.search import fastpath
    client, built, stream, ref, kind, harness = deployment
    q = built["questions"]
    specs = []
    for s in stream.take(10)[:6]:
        spec = {"tag": s["tag"], "child": None, "size": 10, "inner": None,
                "sort": None}
        spec["body"] = reference.body(spec, q["tag_names"], None)
        specs.append(spec)
    before, stats0 = kind.counters(client), dict(fastpath.STATS.items())
    held = [(s, harness.send(client, "search", [s])[0]) for s in specs]
    moved = {k: v - before[k] for k, v in kind.counters(client).items()}
    ladder = {k: v - stats0[k] for k, v in fastpath.STATS.items()
              if isinstance(v, (int, float)) and v != stats0[k]}
    print(f"fastpath.enabled() {fastpath.enabled()}; executor launches "
          f"{moved['executor.launches']} of {len(specs)} requests; the "
          f"ladder's counters that moved: {ladder}")
    out = reference.hold(held, ref)
    print("compared", out["numbers"])
    assert out["correct"], out["numbers"]


def _timed(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times)), out


def test_the_joins_pieces_alone_at_the_cells_size():
    """Made planes, no corpus: 2^25 child slots whose parents are
    nondecreasing over 2^24 rows, a mask of half the children."""
    import jax.numpy as jnp
    nchild, nparent = 1 << 25, 1 << 24
    rng = np.random.default_rng(5)
    parent_h = np.sort(rng.integers(0, nparent, nchild, dtype=np.int32))
    mask_h = (rng.random(nchild) < 0.5).astype(np.float32)
    parent, mask = jnp.asarray(parent_h), jnp.asarray(mask_h)
    live = jnp.ones(nparent, jnp.float32)
    want = np.bincount(parent_h, weights=mask_h, minlength=nparent)

    add = jax.jit(lambda p, m: jnp.zeros(nparent, jnp.float32).at[p].add(m))
    add_sorted = jax.jit(lambda p, m: jnp.zeros(nparent, jnp.float32)
                         .at[p].add(m, indices_are_sorted=True))
    mx = jax.jit(lambda p, m: jnp.full(nparent, -jnp.inf, jnp.float32)
                 .at[p].max(m))
    gather = jax.jit(lambda p, lv, m: m * lv[p])
    rows = []
    for name, fn, args in (("scatter-add", add, (parent, mask)),
                           ("scatter-add, indices declared sorted",
                            add_sorted, (parent, mask)),
                           ("scatter-max", mx, (parent, mask)),
                           ("gather live[parent]", gather,
                            (parent, live, mask))):
        ms, out = _timed(fn, *args)
        rows.append(f"{name}: {ms:.1f} ms "
                    f"({1e6 * ms / nchild:.2f} ns a child slot)")
        if name.startswith("scatter-add"):
            assert np.array_equal(np.asarray(out), want), name
    print("\n2^25 child slots under 2^24 parents (launch + wait, median of "
          "5):\n  " + "\n  ".join(rows))
