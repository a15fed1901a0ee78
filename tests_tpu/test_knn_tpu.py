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
probe width. Run on a real chip: `python -m pytest tests_tpu/test_knn_tpu.py -q -s`."""

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
    nprobe = built["readout"]["ivf"]["nlist"] // 8 if method == "ivf" \
        else None
    node = ("knn", 1, vectors.MAPPING_FIELD, True, "dot_product", None,
            nprobe)
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
