"""The log-analytics deployment (OpenSearch Benchmark `http_logs`, benchmark
kind `http_logs`) on the CPU at a small size: the program's column
executor against the kind's plain reference over the cell's eight request
shapes, and the pieces of the program the deployment forced: calendar
bucket ids as whole columns, bucket counts in int32, no host array of
`ndocs_pad` elements in a request."""

import datetime as dt
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.join(ROOT, "benchmark"), ROOT)
                if p not in sys.path]

import http_logs_reference as reference    # noqa: E402
import run as harness                      # noqa: E402

from opensearch_tpu.ops import aggs as agg_ops         # noqa: E402
from opensearch_tpu.search import (agg_compiler as AC, compiler as C,
                                   planes as PN)        # noqa: E402

CELL = "httplogs.search1.dashboard"
NDOCS = 20_000
SEEDS = (7, 2147483693, 3000000021)


@pytest.fixture(scope="module")
def deployments():
    """seed -> (client, built, stream) of a 20,000-event collection on a
    plain one-chip node (the cell's path; no mesh), built once a seed."""
    from opensearch_tpu.rest.client import RestClient
    made = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        kind = harness.load_kind("http_logs")
        loaded = harness.load_cell(CELL)

        def get(seed):
            if seed not in made:
                config = dict(loaded["config"], ndocs=NDOCS, corpus_seed=seed)
                client = RestClient()
                built = kind.build(config, seed, client, harness.INDEX)
                made[seed] = (client, built, kind.stream(
                    built, loaded["traffic"], seed))
            return made[seed]
        yield get


@pytest.mark.parametrize("shape", reference.SHAPES)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_answers_as_the_reference(deployments, seed, shape):
    client, built, stream = deployments(seed)
    c = built["columns"]
    ref = reference.Reference(c["ts_ms"], c["status"], c["size"])
    specs = [s for s in stream.take(32) if s["shape"] == shape]
    assert len(specs) == 4
    held = []
    for spec in specs + [stream.twin(s) for s in specs]:
        held.append((spec, client.search(harness.INDEX, spec["body"])))
    out = reference.hold(held, ref)
    assert out["numbers"] == {k: [0, 0] for k in reference.LIMITS}
    assert out["correct"] is True and out["compared"] == 8
    # some window is not empty, so the rule compared something (a 400 is
    # one event in 2,000: its windows may well hold none)
    assert shape == "400s-in-range" or any(
        ref.answer(s)["total"] > 0 for s, _r in held)


@pytest.mark.parametrize("shape", reference.SHAPES)
def test_a_request_ships_no_plane_from_the_host(deployments, shape):
    """`executor.params_h2d_bytes` a request: scalars, never a host array of
    `ndocs_pad` elements (the bucket and rank planes live on the device)."""
    client, built, stream = deployments(SEEDS[0])
    spec = next(s for s in stream.take(8) if s["shape"] == shape)
    before = C.EXECUTOR_STATS["params_h2d_bytes"]
    resp = client.search(harness.INDEX, spec["body"])
    assert "error" not in resp
    shipped = C.EXECUTOR_STATS["params_h2d_bytes"] - before
    assert 0 < shipped < 64 * 1024


def test_planes_are_built_once_and_attributed(deployments):
    from opensearch_tpu.obs.hbm_ledger import LEDGER
    client, built, stream = deployments(SEEDS[1])
    seg = client.node.indices[harness.INDEX].shards[0].segments[0]
    specs = {s["shape"]: s for s in stream.take(8)}
    for name, stats, shape in (
            ("_date_bucket_cache", PN.BUCKET_PLANE_STATS, "hourly_agg"),
            ("_sort_dev_cache", PN.RANK_PLANE_STATS, "desc_sort_size")):
        for _ in range(2):
            client.search(harness.INDEX, stream.twin(specs[shape])["body"])
        b0, h0 = stats["builds"], stats["hits"]
        client.search(harness.INDEX, specs[shape]["body"])
        assert (stats["builds"], stats["hits"]) == (b0, h0 + 1)
        assert len(seg.__dict__[name]) >= 1
    tenants = LEDGER.snapshot()["tenants"]
    assert tenants["agg_bucket_plane"]["bytes"] >= seg.ndocs_pad * 4
    assert tenants["sort_rank_plane"]["bytes"] >= seg.ndocs_pad * 4
    # a rematerialized field drops its planes and their bytes
    before = tenants["sort_rank_plane"]["bytes"]
    PN.drop_segment_planes(seg, "size")
    assert ("size",) not in seg._sort_dev_cache
    after = LEDGER.snapshot()["tenants"]["sort_rank_plane"]["bytes"]
    assert before - after == seg.ndocs_pad * 4


# ---------------------------------------------------------------------
# calendar bucket ids: whole columns against a per-row walk
# ---------------------------------------------------------------------

def _oracle(ms: int, calendar: str) -> int:
    """One value's bucket id by Python's calendar (the per-row walk the
    program made before it took whole columns)."""
    d = dt.datetime(1970, 1, 1, tzinfo=dt.timezone.utc) \
        + dt.timedelta(milliseconds=int(ms))
    return {"minute": int(ms) // 60000, "hour": int(ms) // 3600000,
            "day": int(ms) // 86400000,
            "week": (int(ms) // 86400000 + 3) // 7,
            "month": (d.year - 1970) * 12 + (d.month - 1),
            "quarter": (d.year - 1970) * 4 + (d.month - 1) // 3,
            "year": d.year - 1970}[calendar]


def _edges() -> np.ndarray:
    """Epoch milliseconds around every kind of edge, negative ones too."""
    rng = np.random.default_rng(28)
    out = list(rng.integers(-6 * 10**12, 6 * 10**12, 4000))
    for year in (1600, 1899, 1900, 1969, 1970, 1972, 1998, 2000, 2024, 2100):
        for month in (1, 2, 3, 4, 7, 10, 12):
            first = dt.datetime(year, month, 1, tzinfo=dt.timezone.utc)
            ms = (first - dt.datetime(1970, 1, 1, tzinfo=dt.timezone.utc)) \
                // dt.timedelta(milliseconds=1)
            out += [ms - 1, ms, ms + 1, ms + 86400000 * 28]
    for day in range(-15, 15):      # week edges on both sides of 1970
        out += [day * 86400000 - 1, day * 86400000]
    return np.asarray(out, np.int64)


@pytest.mark.parametrize("calendar,alias", [
    ("minute", "1m"), ("hour", "1h"), ("day", "1d"), ("week", "1w"),
    ("month", "1M"), ("quarter", "1q"), ("year", "1y")])
def test_calendar_bucket_ids_equal_the_per_row_walk(calendar, alias):
    ms = _edges()
    want = np.asarray([_oracle(v, calendar) for v in ms], np.int64)
    got = PN.calendar_bucket_ids(ms, calendar)
    assert got.dtype == np.int64 and np.array_equal(got, want)
    assert np.array_equal(PN.calendar_bucket_ids(ms, alias), want)


def test_an_unknown_calendar_interval_is_an_error():
    with pytest.raises(ValueError, match="unknown calendar_interval"):
        PN.calendar_bucket_ids(np.zeros(3, np.int64), "fortnight")


# ---------------------------------------------------------------------
# one bucket past 2^24: counts accumulate in int32
# ---------------------------------------------------------------------

BIG = 17_000_000            # over 2^24 = 16,777,216, where float32 stops


def _bare(kind: str, form: str = "scatter"):
    """`emit_agg` of one agg kind over bare arrays in which every one of
    BIG rows matches and falls into bucket 0. -> the counts it returns.
    `form`: how a date histogram counts ("runs" hands it the boundaries of
    the four buckets' runs: all of the rows, then three empty ones)."""
    import jax
    import jax.numpy as jnp
    live = jnp.ones(BIG, jnp.float32)
    zeros_i = jnp.zeros(BIG, jnp.int32)
    col = {"f32": jnp.zeros(BIG, jnp.float32),
           "present": jnp.ones(BIG, bool)}
    seg_arrays = {"live": live, "numeric": {"f": col},
                  "keyword": {"f": {"ords": zeros_i,
                                    "doc_of_value": jnp.arange(
                                        BIG, dtype=jnp.int32)}}}
    params = {"a0_dbuckets": zeros_i, "a0_dfirst": np.int32(0),
              "a0_dstarts": jnp.asarray([0, BIG, BIG, BIG, BIG], jnp.int32),
              "a0_lows": np.asarray([-1.0, 5.0], np.float32),
              "a0_highs": np.asarray([5.0, 9.0], np.float32)}
    spec = {"date_hist": ("date_hist", "a0", "f", 3600000, 0, None, 0, 4, (),
                          form),
            "auto_date_hist": ("auto_date_hist", "a0", "f", 2, 10, 0,
                               4, 4, (), form),
            "hist": ("hist", "a0", "f", 10.0, 0.0, 0, 4, ()),
            "terms": ("terms", "a0", "f", 16, ()),
            "range": ("range", "a0", "f", ("lo", "hi"), True, (),
                      ((-1.0, 5.0), (5.0, 9.0)))}[kind]
    out = jax.jit(lambda s, p, m: AC.emit_agg(spec, s, p, m))(
        seg_arrays, params, live)
    return np.asarray(out["counts"])


@pytest.mark.parametrize("kind,form", [
    ("date_hist", "scatter"), ("date_hist", "runs"),
    ("auto_date_hist", "scatter"), ("auto_date_hist", "runs"),
    ("hist", "scatter"), ("terms", "scatter"), ("range", "scatter")])
def test_a_bucket_past_2_to_the_24_counts_exactly(kind, form):
    # 2^6 divides BIG, so "runs" is the run form proper, not its fall-back
    assert agg_ops.run_blocks(BIG, 5) == (BIG // 64, 64)
    counts = _bare(kind, form)
    assert counts.dtype == np.int32
    assert int(counts[0]) == BIG and int(counts[1:].sum()) == 0


def test_float32_would_have_stopped_short():
    """The reason for int32: the same accumulation in float32 stalls."""
    assert np.float32(2**24) + np.float32(1) == np.float32(2**24)
    assert int(agg_ops.bucket_counts(np.zeros(5, np.int32),
                                     np.ones(5, np.float32), 2)[0]) == 5
