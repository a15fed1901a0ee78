"""The trip-analytics deployment (OpenSearch Benchmark `nyc_taxis`, benchmark
kind `nyc_taxis`) on the CPU at a small size: the program's column executor
against the kind's plain reference over the cell's eight request shapes
(drawn bounds, `format` `dd/MM/yyyy` included), and the pieces of the
program the deployment forced: sums whose error does not grow with the
bucket, counts in int32, `auto_date_histogram` by the matched range, the
launch it takes first, and the counters that say so."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.join(ROOT, "benchmark"), ROOT)
                if p not in sys.path]

import nyc_taxis_reference as reference    # noqa: E402
import run as harness                      # noqa: E402

from opensearch_tpu.ops import aggs as agg_ops         # noqa: E402
from opensearch_tpu.search import aggregations as AGG, compiler as C        # noqa: E402

CELL = "nyctaxis.search1.analyst"
NDOCS = 6_000
SEEDS = (7, 2147483693, 3000000021)


@pytest.fixture(scope="module")
def deployments():
    """seed -> (client, built, stream) of a 6,000-trip collection on a
    plain one-chip node (the cell's path; no mesh), built once a seed."""
    from opensearch_tpu.rest.client import RestClient
    made = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        kind = harness.load_kind("nyc_taxis")
        loaded = harness.load_cell(CELL)

        def get(seed):
            if seed not in made:
                config = dict(loaded["config"], ndocs=NDOCS, corpus_seed=seed)
                client = RestClient()
                built = kind.build(config, seed, client, harness.INDEX)
                made[seed] = (client, built, kind.stream(
                    built, loaded["traffic"], seed), kind)
            return made[seed]
        yield get


@pytest.mark.parametrize("shape", reference.SHAPES)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_answers_as_the_reference(deployments, seed, shape):
    client, built, stream, kind = deployments(seed)
    ref = kind.reference_of(built)
    specs = [s for s in stream.take(32) if s["shape"] == shape]
    assert len(specs) == 4
    held = []
    for spec in specs + [stream.twin(s) for s in specs]:
        held.append((spec, client.search(harness.INDEX, spec["body"])))
    out = reference.hold(held, ref)
    worst, limit = out["numbers"].pop("sum_rel_err_max")
    assert limit == reference.SUM_RTOL and worst < 1e-6
    assert out["numbers"] == {k: [0, 0] for k in reference.LIMITS
                              if k != "sum_rel_err_max"}
    assert out["correct"] is True and out["compared"] == 8
    assert any(ref.answer(s)["total"] > 0 for s, _r in held)
    if shape == "autohisto_agg":
        # 11 to 19 whole days and 20 buckets: daily buckets at every draw
        for spec, resp in held:
            agg = resp["aggregations"][reference.AGG_NAME[shape]]
            assert agg["interval"] == "1d"
            assert len(agg["buckets"]) <= 20
            assert all(b["key"] % 86_400_000 == 0 for b in agg["buckets"])


def test_all_eighteen_fields_are_in_the_mapping_and_the_segment(deployments):
    client, built, _stream, _kind = deployments(SEEDS[0])
    props = client.indices.get_mapping(harness.INDEX)[harness.INDEX][
        "mappings"]["properties"]
    assert len(props) == 18
    assert props["trip_distance"] == {"type": "scaled_float",
                                      "scaling_factor": 100}
    assert props["dropoff_datetime"]["format"] == "yyyy-MM-dd HH:mm:ss"
    seg = client.node.indices[harness.INDEX].shards[0].segments[0]
    assert (len(seg.numeric_cols), len(seg.geo_cols), len(seg.keyword_cols),
            len(seg.postings)) == (11, 2, 5, 5)
    # the rows are in the source's file order: not by time
    assert (np.diff(built["columns"]["dropoff_s"]) < 0).any()


def _counted(client, spec) -> dict:
    before = {k: AGG.AGG_STATS[k] for k in AGG.AGG_STATS}
    launches = C.EXECUTOR_STATS["launches"]
    resp = client.search(harness.INDEX, spec["body"])
    assert "error" not in resp
    out = {k: AGG.AGG_STATS[k] - v for k, v in before.items()}
    out["launches"] = C.EXECUTOR_STATS["launches"] - launches
    return out


def test_the_counters_say_what_a_launch_scattered(deployments):
    client, built, stream, _kind = deployments(SEEDS[1])
    seg = client.node.indices[harness.INDEX].shards[0].segments[0]
    n = seg.ndocs_pad
    specs = {s["shape"]: s for s in stream.take(8)}
    # a histogram's count, and under it the stats' count, minimum, maximum
    # and three limbs of the sum: a few hundred buckets at most, so the
    # dense form reads the rows twice (the count's pass, and one for the
    # stats' six accumulators) where seven scatters took a row at a time
    assert agg_ops.count_form(366) == "dense" and agg_ops.sub_metric_scatters(
        n, 64, False) == 6
    got = _counted(client, specs["distance_amount_agg"])
    assert got["blocked.rows"] == 2 * n and got["launches"] == 1
    assert got["bucketed_sub.launches"] == 1
    assert got["bucketed_sub.buckets"] > 0 and got["scatter.updates"] == 0
    # a date histogram over a column in no row order: one dense pass
    got = _counted(client, specs["date_histogram_agg"])
    assert (got["scatter.updates"], got["blocked.rows"],
            got["launches"], got["bucketed_sub.launches"]) == (0, n, 1, 0)
    # auto_date_histogram: a first launch learns the matched range
    got = _counted(client, specs["autohisto_agg"])
    assert (got["auto_date.requests"], got["auto_date.refine_launches"],
            got["launches"], got["scatter.updates"],
            got["blocked.rows"]) == (1, 1, 2, 0, n)
    for shape in ("range", "desc_sort_tip_amount",
                  "asc_sort_passenger_count"):
        got = _counted(client, specs[shape])
        assert got["launches"] == 1
        assert not any(v for k, v in got.items() if k != "launches")


def test_the_refine_launch_has_its_span(deployments):
    client, _built, stream, _kind = deployments(SEEDS[2])
    spec = next(s for s in stream.take(8) if s["shape"] == "autohisto_agg")
    client.node.tracer._traces.clear()
    client.search(harness.INDEX, spec["body"])
    names = set()

    def walk(node):
        names.add(node["name"])
        for c in node.get("children", []):
            walk(c)
    for t in client.get_traces()["traces"]:
        walk(t)
    assert {"search.aggs.refine", "search.aggs.prepare",
            "search.aggs.partial", "device.wait"} <= names


@pytest.mark.parametrize("shape", reference.SHAPES)
def test_a_request_ships_no_plane_from_the_host(deployments, shape):
    client, _built, stream, _kind = deployments(SEEDS[0])
    spec = next(s for s in stream.take(8) if s["shape"] == shape)
    client.search(harness.INDEX, stream.twin(spec)["body"])    # planes built
    before = C.EXECUTOR_STATS["params_h2d_bytes"]
    resp = client.search(harness.INDEX, spec["body"])
    assert "error" not in resp
    shipped = C.EXECUTOR_STATS["params_h2d_bytes"] - before
    if shape == "distance_amount_agg":
        # OSB's body puts the range under `bool.filter`: the filter-mask
        # cache (`compiler._prepare_cached_filter`) builds the mask in a
        # launch of its own, keeps it on the host and hands it to every
        # launch: `ndocs_pad` bytes a request (PERF.md section 7)
        seg = client.node.indices[harness.INDEX].shards[0].segments[0]
        shipped -= seg.ndocs_pad
    assert 0 < shipped < 1024


# ---------------------------------------------------------------------
# sums: the form's error does not grow with the bucket
# ---------------------------------------------------------------------

BIG = 1 << 25


def test_one_bucket_of_2_to_the_25_rows_sums_exactly():
    """33,554,432 rows of 9.35 in one bucket: one float32 accumulator stalls
    at 2^28 (an addend of 9.35 is under half its spacing of 32), the
    program's limbs give float64's sum of the stored values."""
    import jax
    import jax.numpy as jnp
    value = np.float32(9.35)
    want = float(value) * BIG
    sequential = float(np.cumsum(np.full(BIG, value), dtype=np.float32)[-1])
    assert abs(sequential - want) / want > 0.1
    inv = agg_ops.sum_scale_inv(9.35)
    out = jax.jit(lambda b, v, w: agg_ops.bucketed_sub_metric(
        b, v, w, 4, inv, True))(jnp.zeros(BIG, jnp.int32),
                                jnp.full(BIG, value),
                                jnp.ones(BIG, jnp.float32))
    sums = agg_ops.limb_sums_to_f64(np.asarray(out["sum"]), float(inv))
    assert abs(sums[0] - want) / want < 1e-12 and not sums[1:].any()
    assert out["count"].dtype == np.int32
    assert np.asarray(out["count"]).tolist() == [BIG, 0, 0, 0]
    squares = agg_ops.limb_sums_to_f64(np.asarray(out["sumsq"]),
                                       float(inv) ** 2)
    assert abs(squares[0] - float(value * value) * BIG) \
        / (float(value * value) * BIG) < 1e-12
    assert float(out["min"][0]) == float(out["max"][0]) == float(value)
    # the top-level stats take the same form, with no scatter
    top = jax.jit(lambda v, w: agg_ops.stats_agg(v, w > 0, w, inv, False))(
        jnp.full(BIG, value), jnp.ones(BIG, jnp.float32))
    assert int(top["count"]) == BIG and top["count"].dtype == np.int32
    got = agg_ops.limb_sums_to_f64(np.asarray(top["sum"]), float(inv))[0]
    assert abs(got - want) / want < 1e-12


@pytest.mark.parametrize("n,nb", [(1000, 7), (1 << 16, 64), (70_001, 300),
                                  (1 << 12, 1 << 20)])
def test_limb_sums_equal_float64_over_signs_and_magnitudes(n, nb):
    """Mixed signs, magnitudes over eight decades, rows that do not count,
    ids out of range: the sums are float64's of the float32 values, to the
    form's stated bound (count x 2^-48 of the largest magnitude)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(n)
    v = (rng.standard_normal(n) * 10.0 ** rng.integers(-3, 5, n)
         ).astype(np.float32)
    w = (rng.random(n) < 0.8).astype(np.float32)
    b = rng.integers(-1, nb + 1, n).astype(np.int32)
    inv = agg_ops.sum_scale_inv(float(np.abs(v).max()))
    limbs, bits, rows = agg_ops.sum_limb_plan(n, nb)
    assert limbs * bits >= 48 and rows * (1 << bits) <= 1 << 31
    out = agg_ops.bucket_sums_exact(jnp.asarray(b), jnp.asarray(v),
                                    jnp.asarray(w), nb, inv)
    got = agg_ops.limb_sums_to_f64(np.asarray(out), float(inv))
    ok = (w > 0) & (b >= 0) & (b < nb)
    want = np.bincount(b[ok], weights=v[ok].astype(np.float64), minlength=nb)
    bound = np.bincount(b[ok], minlength=nb) * 2.0 ** -48 / float(inv)
    assert (np.abs(got - want) <= bound + 1e-300).all()
    one = agg_ops.sums_exact(jnp.asarray(v), jnp.asarray(w), inv)
    total = agg_ops.limb_sums_to_f64(np.asarray(one), float(inv))[0]
    assert abs(total - v[w > 0].astype(np.float64).sum()) \
        <= (w > 0).sum() * 2.0 ** -48 / float(inv)


@pytest.fixture(scope="module")
def metrics_client():
    from opensearch_tpu.rest.client import RestClient
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        c = RestClient()
        c.indices.create("m", {
            "settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": {"properties": {
                "k": {"type": "keyword"}, "g": {"type": "integer"},
                "at": {"type": "date"},
                "x": {"type": "scaled_float", "scaling_factor": 100}}}})
        rng = np.random.default_rng(3)
        rows = [{"k": "abc"[i % 3], "g": i % 5,
                 "at": 1_420_070_400_000 + int(rng.integers(0, 5 * 86_400_000)),
                 "x": int(rng.integers(-5000, 90_000)) / 100.0}
                for i in range(900)]
        body = []
        for i, r in enumerate(rows):
            body += [{"index": {"_index": "m", "_id": str(i)}}, r]
        assert c.bulk(body, refresh=True)["errors"] is False
        yield c, rows


@pytest.mark.parametrize("parent,key_of", [
    ({"terms": {"field": "k"}}, lambda r: r["k"]),
    ({"histogram": {"field": "g", "interval": 2}},
     lambda r: float(r["g"] // 2 * 2)),
    ({"date_histogram": {"field": "at", "calendar_interval": "day"}},
     lambda r: r["at"] // 86_400_000 * 86_400_000),
    ({"auto_date_histogram": {"field": "at", "buckets": 5}},
     lambda r: r["at"] // 86_400_000 * 86_400_000),
])
def test_metrics_under_every_bucket_kind_are_float64s(metrics_client, parent,
                                                      key_of):
    client, rows = metrics_client
    resp = client.search("m", {"size": 0, "aggs": {"b": dict(parent, aggs={
        "s": {"extended_stats": {"field": "x"}}})}})
    want = {}
    for r in rows:
        want.setdefault(key_of(r), []).append(float(np.float32(r["x"])))
    got = {b["key"]: b["s"] for b in resp["aggregations"]["b"]["buckets"]
           if b["doc_count"]}
    assert set(got) == set(want)
    for k, vals in want.items():
        s = got[k]
        assert s["count"] == len(vals) and isinstance(s["count"], int)
        assert (s["min"], s["max"]) == (min(vals), max(vals))
        assert s["sum"] == pytest.approx(sum(vals), rel=1e-12)
        assert s["sum_of_squares"] == pytest.approx(
            sum(float(np.float32(np.float32(v) * np.float32(v)))
                for v in vals), rel=1e-12)


def test_a_top_level_stats_counts_in_int32_and_sums_in_limbs(metrics_client):
    client, rows = metrics_client
    resp = client.search("m", {"size": 0, "query": {"range": {
        "x": {"gte": 0}}}, "aggs": {"s": {"stats": {"field": "x"}}}})
    vals = [float(np.float32(r["x"])) for r in rows if r["x"] >= 0]
    s = resp["aggregations"]["s"]
    assert s["count"] == len(vals)
    assert s["sum"] == pytest.approx(sum(vals), rel=1e-12)
    assert s["avg"] == pytest.approx(sum(vals) / len(vals), rel=1e-12)


def test_a_histogram_holds_the_empty_buckets_between(metrics_client):
    """`min_doc_count` 0, the default: from the least to the greatest matched
    key with the empty ones between (not the column's span)."""
    client, rows = metrics_client
    resp = client.search("m", {"size": 0, "query": {"bool": {"should": [
        {"range": {"x": {"gte": 100, "lt": 101}}},
        {"range": {"x": {"gte": 400, "lt": 403}}}]}},
        "aggs": {"h": {"histogram": {"field": "x", "interval": 1},
                       "aggs": {"s": {"stats": {"field": "x"}}}}}})
    buckets = resp["aggregations"]["h"]["buckets"]
    keys = [b["key"] for b in buckets]
    matched = sorted(int(r["x"] // 1) for r in rows
                     if 100 <= r["x"] < 101 or 400 <= r["x"] < 403)
    assert keys == [float(k) for k in range(matched[0], matched[-1] + 1)]
    empty = [b for b in buckets if not b["doc_count"]]
    assert empty and all(b["s"] == {"count": 0, "min": None, "max": None,
                                    "sum": 0.0, "avg": None} for b in empty)
    only = client.search("m", {"size": 0, "aggs": {"h": {"histogram": {
        "field": "x", "interval": 1, "min_doc_count": 1}}}})
    assert all(b["doc_count"] for b in
               only["aggregations"]["h"]["buckets"])
