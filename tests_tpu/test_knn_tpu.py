"""TPU-hardware check of the `knn` query at the vector-search cell's width:
262,144 vectors of 768 floats of the cell's own generator, through
`RestClient.search` with OSB's body. `flat` and `ivf` pages are held to the
cell's float64 reference by the cell's rule (`score_rtol`, order, k distinct
ids, recall). The scoring product names its precision
(`compiler._KNN_SCORE_PRECISION`): where it is a matrix-matrix product (a
batch of queries a launch, the coalesced `msearch` path) the default is one
bfloat16 pass of the matrix unit and has to FAIL `score_rtol`, the named
form has to hold it; one query a launch is a matrix-vector product that XLA
computes on the vector unit in float32 either way, which this file reads
too. A CPU computes float32 always, so only this file holds the repair. A
test moves the constant to get the other form (the program has no option
for it). It also reads the stage's time either way, and recall beside the
probe width. Since PR 42 the IVF probe reads its lists where they lie (the
rows a second time in list order, a window of `cap` rows a probed list):
the probe's score plane is held to a host reckoning in float64 over
`IvfIndex.lists` of the lists it probed, here and at the cell's own size
(2,000,000 rows, with the requests' time by route), and the forms the
window read could take are timed beside the fetch by doc id it replaced.
Run on a real chip: `python -m pytest tests_tpu/test_knn_tpu.py -q -s`."""

import os
import sys
import time

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.join(ROOT, "benchmark"), ROOT)
                if p not in sys.path]

import run as harness                          # noqa: E402
import vectorsearch_reference as reference     # noqa: E402
import vectorsearch_vectors as vectors         # noqa: E402

from opensearch_tpu.search import compiler as C     # noqa: E402

pytestmark = pytest.mark.skipif(jax.default_backend() != "tpu",
                                reason="needs a real TPU chip")

CELL = "cohere10m.search1.knn100"
NDOCS, NQ = 262_144, 32


@pytest.fixture(scope="module")
def cell():
    loaded = harness.load_cell(CELL)
    config = dict(loaded["config"], ndocs=NDOCS)
    # an eighth of the cell's rows holds an eighth of its topics
    config["generator"] = dict(config["generator"], topics=int(
        config["generator"]["topics"]) * NDOCS // int(
            loaded["config"]["ndocs"]))
    return config, loaded["traffic"]


@pytest.fixture(scope="module")
def deployments(cell):
    """method -> (client, built, specs): the kind's own `build` (planted
    segment, promoted, IVF built from the resident matrix)."""
    from opensearch_tpu.rest.client import RestClient
    config, traffic = cell
    kind = harness.load_kind("vectorsearch")
    made = {}

    def get(method):
        if method not in made:
            client = RestClient()
            built = kind.build(dict(config, method={"name": method}), 5,
                               client, harness.INDEX)
            specs = kind.stream(built, traffic, 2147483693).take(NQ)
            made[method] = (client, built, specs)
        return made[method]
    return get


def _hold(client, built, specs, config, **knn_extra):
    held, times = [], []
    for spec in specs:
        body = dict(spec["body"])
        if knn_extra:
            knn = dict(body["query"]["knn"][vectors.MAPPING_FIELD],
                       **knn_extra)
            body["query"] = {"knn": {vectors.MAPPING_FIELD: knn}}
        t0 = time.perf_counter()
        held.append((spec, client.search(harness.INDEX, body)))
        times.append((time.perf_counter() - t0) * 1e3)
    g = config["guarantees"]
    out = reference.hold(
        held, reference.Reference(built["corpus"]["vectors"],
                                  config["space_type"]),
        int(config["k"]), float(g["score_rtol"]),
        float(g["recall_at_k_floor"]))
    return out, float(np.median(times[4:]))


@pytest.mark.parametrize("method", ["flat", "ivf"])
def test_pages_hold_the_cells_rule(deployments, cell, method):
    client, built, specs = deployments(method)
    out, p50 = _hold(client, built, specs, cell[0])
    n = out["numbers"]
    print(f"\n{method} n={NDOCS} dims=768 k=100: score_rel_err_max "
          f"{n['score_rel_err_max'][0]:.3e} (limit "
          f"{n['score_rel_err_max'][1]:g}), recall@100 mean "
          f"{n['recall_at_k_mean'][0]:.4f} min {out['recall_at_k_min']:.2f}, "
          f"request p50 {p50:.2f} ms; ivf read-out "
          f"{built['readout']['ivf'] if method == 'ivf' else None}")
    assert out["correct"] is True, n
    assert n["order_violations"] == n["page_violations"] == [0, 0]
    if method == "flat":
        assert n["recall_at_k_mean"][0] == 1.0


def test_recall_rises_with_the_probe_width(deployments, cell):
    client, built, specs = deployments("ivf")
    nlist = built["readout"]["ivf"]["nlist"]
    got = {}
    for nprobe in (nlist // 32, nlist // 8, nlist // 2, nlist):
        out, p50 = _hold(client, built, specs, cell[0],
                         method_parameters={"nprobe": nprobe})
        got[nprobe] = out["numbers"]["recall_at_k_mean"][0]
        print(f"\nivf nprobe {nprobe} of {nlist}: recall@100 mean "
              f"{got[nprobe]:.4f} min {out['recall_at_k_min']:.2f}, "
              f"request p50 {p50:.2f} ms")
        assert out["numbers"]["score_rel_err_max"][0] \
            <= out["numbers"]["score_rel_err_max"][1]
    assert got[nlist] == 1.0            # every list probed: the exact scan
    assert got[nlist // 32] <= got[nlist // 8] <= got[nlist // 2] <= 1.0


@pytest.mark.parametrize("method,batch", [("flat", 1), ("ivf", 1),
                                          ("flat", 8), ("ivf", 8)])
def test_the_scoring_product_keeps_float32(deployments, cell, method, batch,
                                           monkeypatch):
    """`emit`'s `knn` under `jax.jit` over the segment's own device arrays,
    scores of the rows the route reached among the reference's top 100,
    with the precision named and with it left to the default. One query a
    launch (the cell's path) is a matrix-vector product, which XLA gives
    the vector unit in float32 whatever is named: both forms hold
    `score_rtol`. A batch of queries (`jax.vmap`, as the coalesced
    `msearch` path launches them) makes the exact scan a matrix-matrix
    product for the matrix unit: named, it holds `score_rtol`; unnamed, it
    is one bfloat16 pass and fails it."""
    config = cell[0]
    client, built, specs = deployments(method)
    seg = client.node.indices[harness.INDEX].shards[0].segments[0]
    arrays = seg.device_arrays()
    ref = reference.Reference(built["corpus"]["vectors"],
                              config["space_type"])
    queries = np.stack([s["vector"] for s in specs[:8]])
    exact = ref.scores(queries)
    ivf = built["readout"]["ivf"]
    probe = (ivf["nlist"] // 8, ivf["cap"]) if method == "ivf" else None
    node = ("knn", 1, vectors.MAPPING_FIELD, True, "dot_product", None,
            probe)
    params = {"q1_vec": queries,
              "q1_qsq": (queries * queries).sum(axis=1),
              "q1_boost": np.ones(len(queries), np.float32)}
    worst = {}
    for name, precision in (("highest", C._KNN_SCORE_PRECISION),
                            ("default", None)):
        monkeypatch.setattr(C, "_KNN_SCORE_PRECISION", precision)
        one = lambda p: C.emit(node, arrays, p).scores     # noqa: E731
        fn = jax.jit(jax.vmap(one) if batch > 1 else one)
        if batch > 1:
            launches = [params]
        else:
            launches = [{k: v[i] for k, v in params.items()}
                        for i in range(len(queries))]
        got, times = [], []
        for p in launches:
            out = np.asarray(fn(p))
            t0 = time.perf_counter()
            np.asarray(fn(p))
            times.append((time.perf_counter() - t0) * 1e3)
            got += list(out.reshape(-1, out.shape[-1]))
        errs = []
        for scores, want in zip(got, exact):
            scores = scores[: len(want)]
            rows = np.flatnonzero(scores > 0)       # what the route reached
            rows = rows[np.argsort(-want[rows])[:100]]
            errs.append(float((np.abs(scores[rows] - want[rows])
                               / want[rows]).max()))
        worst[name] = max(errs)
        print(f"\n{method} emit(knn) x {batch} a launch, precision {name}: "
              f"score_rel_err_max {worst[name]:.3e} over 8 queries' top "
              f"100, launch + read of the score planes "
              f"{np.median(times):.2f} ms (median)")
    rtol = float(config["guarantees"]["score_rtol"])
    assert worst["highest"] <= rtol
    if batch == 1:
        assert worst["default"] <= rtol     # the vector unit, in float32
    elif method == "flat":
        assert worst["default"] > rtol      # one bfloat16 pass


# ---- the probe reads its lists where they lie (PR 42) ------------------

def _segment(client):
    return client.node.indices[harness.INDEX].shards[0].segments[0]


def _plane_against_the_lists(seg, vecs, queries, nprobe):
    """`emit`'s `knn` score plane of each query against the host's
    reckoning: the lists it probed (those whose rows it reached) are
    `nprobe` whole lists of `IvfIndex.lists`, their rows' scores are the
    float64 product's within `score_rtol`, nothing else is reached.
    -> (worst relative error, lists that differ from the host's own
    float32 choice of the `nprobe` nearest)."""
    ivf = seg.vector_cols[vectors.MAPPING_FIELD].ivf()
    arrays = seg.device_arrays()
    node = ("knn", 1, vectors.MAPPING_FIELD, True, "dot_product", None,
            (nprobe, ivf.cap))
    # (the arrays an argument: closed over, they would be constants of
    # the program, 12.6 GB of them at the cell's size)
    fn = jax.jit(lambda a, p: C.emit(node, a, p).scores)
    worst, swapped = 0.0, 0
    lanes = arrays["vector"][vectors.MAPPING_FIELD]["mat"].shape[1]
    for q in queries:
        plane = np.asarray(fn(arrays, {
            "q1_vec": np.pad(q, (0, lanes - len(q))),
            "q1_qsq": np.float32(q @ q),
            "q1_boost": np.float32(1.0)}))[: len(vecs)]
        reached = plane > 0
        probed = np.flatnonzero((ivf.fill > 0)
                                & reached[np.maximum(ivf.lists[:, 0], 0)])
        assert nprobe - (ivf.fill == 0).sum() <= len(probed) <= nprobe
        rows = ivf.lists[probed]
        rows = rows[rows >= 0]
        assert reached.sum() == len(rows) and reached[rows].all()
        ip = vecs[rows].astype(np.float64) @ q.astype(np.float64)
        want = np.where(ip >= 0, ip + 1.0, 1.0 / (1.0 - ip))
        worst = max(worst, float((np.abs(plane[rows] - want) / want).max()))
        own = np.argsort(-(ivf.centroids[:, : len(q)] @ q),
                         kind="stable")[:nprobe]
        swapped += len(set(probed) - set(own))
    return worst, swapped


def test_the_probe_scores_the_lists_it_probed(deployments, cell):
    client, built, specs = deployments("ivf")
    nlist = built["readout"]["ivf"]["nlist"]
    queries = [s["vector"] for s in specs[:8]]
    for nprobe in (nlist // 32, nlist // 8, nlist // 2):
        worst, swapped = _plane_against_the_lists(
            _segment(client), built["corpus"]["vectors"], queries, nprobe)
        print(f"\nivf n={NDOCS} nprobe {nprobe} of {nlist}: the probed "
              f"lists' rows against float64 over IvfIndex.lists, "
              f"score_rel_err_max {worst:.3e}; {swapped} of {8 * nprobe} "
              f"lists not the host's own float32 choice")
        assert worst <= float(cell[0]["guarantees"]["score_rtol"])
        assert swapped <= 8


def _forms(cap: int, dims: int):
    """name -> fn(rows, ids, starts, q) -> (scores [nprobe * cap], ids):
    the ways a probe can read `nprobe` windows of `cap` rows. `scan` is
    what `compiler.emit` keeps; `by doc id` is what it replaced (the
    candidates' rows fetched one id at a time, here from the same
    matrix)."""
    import jax.numpy as jnp
    from jax import lax

    def product(vecs, q):
        return jnp.dot(vecs, q, preferred_element_type=jnp.float32,
                       precision=C._KNN_SCORE_PRECISION)

    def window(rows, start):
        return lax.dynamic_slice(rows, (start, 0), (cap, dims))

    def id_windows(ids, starts):
        return jax.vmap(lambda s: lax.dynamic_slice(ids, (s,), (cap,)))(
            starts).reshape(-1)

    def scan(unroll, ids_inside=True):
        def fn(rows, ids, starts, q):
            def one_list(_, st):
                cand = lax.dynamic_slice(ids, (st,), (cap,)) \
                    if ids_inside else None
                return None, (product(window(rows, st), q), cand)
            _, (s, cand) = lax.scan(one_list, None, starts, unroll=unroll)
            return s.reshape(-1), (cand.reshape(-1) if ids_inside
                                   else id_windows(ids, starts))
        return fn

    def vmapped(rows, ids, starts, q):
        s = jax.vmap(lambda st: product(window(rows, st), q))(starts)
        return s.reshape(-1), id_windows(ids, starts)

    def slabs(rows, ids, starts, q):
        wins = lax.gather(
            rows, starts[:, None], lax.GatherDimensionNumbers(
                offset_dims=(1, 2), collapsed_slice_dims=(),
                start_index_map=(0,)), slice_sizes=(cap, dims),
            mode="promise_in_bounds")
        return product(wins, q).reshape(-1), id_windows(ids, starts)

    def by_doc_id(rows, ids, starts, q):
        cand = id_windows(ids, starts)
        return product(rows[jnp.maximum(cand, 0)], q), cand

    return {"scan": scan(1), "scan, ids read outside": scan(1, False),
            "scan, 2 steps unrolled": scan(2),
            "scan, 4 steps unrolled": scan(4),
            "scan, 8 steps unrolled": scan(8),
            "scan, 16 steps unrolled": scan(16),
            "vmapped dynamic_slice": vmapped, "slab gather": slabs,
            "by doc id (the parent's)": by_doc_id}


def test_the_forms_a_window_read_could_take():
    """At the cell's shapes (2,012,020 slots of 768 floats, 176 windows of
    2,122 rows, 1.15 GB a probe): ms a launch and GB/s by form, with a
    list's start on a multiple of 8 rows and off it, and eight queries a
    launch (`jax.vmap`, as the coalesced `msearch` path probes)."""
    import jax.numpy as jnp
    slots, dims, nlist, cap, nprobe = 2_012_020, 768, 1414, 2122, 176
    rows = jax.random.normal(jax.random.PRNGKey(0), (slots, dims),
                             jnp.float32)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.permutation(slots).astype(np.int32))
    span = (slots - cap) // nlist // 8 * 8
    q8 = jax.random.normal(jax.random.PRNGKey(1), (8, dims), jnp.float32)
    starts8 = np.stack([rng.choice(nlist, nprobe, replace=False) * span
                        for _ in range(8)]).astype(np.int32)
    gbytes = nprobe * cap * dims * 4 / 1e9

    def timed(fn, *args):
        """ms a launch with ten launches in flight (the device's pace, not
        a launch's latency), the median of five such rounds."""
        out = jax.block_until_ready(fn(*args))
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready([fn(*args) for _ in range(10)])
            times.append((time.perf_counter() - t0) * 1e2)
        return out, float(np.median(times))

    want = None
    print(f"\n{nprobe} windows of {cap} x {dims} float32 ({gbytes:.3f} GB) "
          f"of f32[{slots},{dims}]: ms a launch, ten in flight, median of 5 rounds")
    for name, fn in _forms(cap, dims).items():
        for label, off in (("aligned", 0), ("start + 3", 3)):
            starts = jnp.asarray(starts8[0] + off)
            (s, cand), ms = timed(jax.jit(fn), rows, ids, starts, q8[0])
            print(f"  {name:28s} {label:10s} {ms:7.3f} ms  "
                  f"{gbytes / ms * 1e3:6.1f} GB/s")
            if off == 0:
                if want is None:
                    want = np.asarray(s), np.asarray(cand)
                assert np.array_equal(np.asarray(cand), want[1])
                # (fetched by id, the rows are other rows of the matrix;
                # a form sums a row's 768 products of N(0, 1) in its own
                # order)
                assert name.startswith("by doc id") or np.allclose(
                    np.asarray(s), want[0], rtol=1e-5, atol=1e-3)
        if not name.startswith("scan"):
            continue            # eight probes' rows at once are 9.2 GB
        fn8 = jax.jit(jax.vmap(fn, in_axes=(None, None, 0, 0)))
        _, ms = timed(fn8, rows, ids, jnp.asarray(starts8), q8)
        print(f"  {name:28s} x 8 a launch {ms:7.3f} ms  "
              f"{ms / 8:.3f} ms a query")


def test_by_route_at_the_cells_size():
    """The cell's own 2,000,000 rows: the probe's plane against the lists
    at the three probe widths PERF.md's by-route table has, and 64
    held-out requests a route through `RestClient.search` (p50, recall)."""
    from opensearch_tpu.rest.client import RestClient
    loaded = harness.load_cell(CELL)
    config, traffic = loaded["config"], loaded["traffic"]
    kind = harness.load_kind("vectorsearch")
    client = RestClient()
    built = kind.build(config, 5, client, harness.INDEX)
    ivf = built["readout"]["ivf"]
    print(f"\ncell size: promote_s {built['promote_s']:.1f}, ivf read-out "
          f"{ivf}, device bytes {built['readout']['device_bytes']}")
    specs = kind.stream(built, traffic, 2147483693).take(64)
    queries = [s["vector"] for s in specs[:4]]
    rtol = float(config["guarantees"]["score_rtol"])
    recalls = {}
    for nprobe in (ivf["nlist"] // 32, ivf["nlist"] // 8, ivf["nlist"] // 2):
        worst, swapped = _plane_against_the_lists(
            _segment(client), built["corpus"]["vectors"], queries, nprobe)
        out, p50 = _hold(client, built, specs, config,
                         method_parameters={"nprobe": nprobe})
        recalls[nprobe] = out["numbers"]["recall_at_k_mean"][0]
        print(f"\nivf n={config['ndocs']} nprobe {nprobe} of "
              f"{ivf['nlist']}: plane against the lists "
              f"score_rel_err_max {worst:.3e} ({swapped} lists swapped); "
              f"64 requests: p50 {p50:.2f} ms, recall@100 mean "
              f"{recalls[nprobe]:.4f} min {out['recall_at_k_min']:.2f}, "
              f"score_rel_err_max {out['numbers']['score_rel_err_max'][0]:.3e}")
        assert worst <= rtol
        assert out["numbers"]["score_rel_err_max"][0] <= rtol
        assert out["numbers"]["order_violations"] \
            == out["numbers"]["page_violations"] == [0, 0]
    out, p50 = _hold(client, built, specs, config, exact=True)
    print(f"\nexact: true n={config['ndocs']}: 64 requests: p50 {p50:.2f} "
          f"ms, recall@100 mean {out['numbers']['recall_at_k_mean'][0]:.4f}")
    assert out["numbers"]["recall_at_k_mean"][0] == 1.0
    # PR 36's by-route readings of this index (PERF.md section 5; other
    # held-out queries, so to a hundredth)
    assert np.allclose([recalls[n] for n in sorted(recalls)],
                       [0.966, 0.9916, 0.9986], atol=0.01)
    _segment(client).evict_device()     # 12.6 GB of the chip, given back
