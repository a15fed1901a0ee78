"""`ops.topk_docs` without a sort of the whole plane: block maxima choose k
blocks and `lax.top_k` sees only those (`ops.topk_blocks`). Held to plain
`jax.lax.top_k` over the masked plane on both sides of the shape threshold
and at its edges, and through `RestClient.search` on a segment large
enough for the blocked form."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from opensearch_tpu.ops import scoring as ops

# (n, k): what `topk_blocks` makes of it
SHAPES = [
    (32, 64),           # k over n: clamped, one top_k
    (256, 16),          # tiny segment: one top_k
    (1024, 1024),       # k == n
    (2048, 16),         # just under the threshold: 384 keys * 8 > 2048
    (2048, 1024),       # k >= R (C 2, R 1024)
    (3000, 10),         # C does not divide n
    (50_000, 16),       # large, C does not divide n
    (4096, 16),         # at the threshold: (256, 16), 512 keys
    (8192, 16),         # (256, 32)
    (49_152, 16),       # n no power of two, C divides it: (768, 64)
    (65_536, 32),       # (1024, 64)
    (262_144, 128),     # (4096, 64)
    (262_144, 4),       # (1024, 256), and both inner top-ks cut again
]
BLOCKED = {(4096, 16): (256, 16), (8192, 16): (256, 32),
           (49_152, 16): (768, 64), (65_536, 32): (1024, 64),
           (262_144, 128): (4096, 64), (262_144, 4): (1024, 256)}


def _plane(case: str, n: int, k: int, rng):
    """-> scores f32[n], matched bool[n], live f32[n] of one case."""
    scores = rng.random(n, dtype=np.float32)
    matched = np.ones(n, bool)
    live = np.ones(n, np.float32)
    c = (ops.topk_blocks(n, min(k, n)) or (1, max(n // 8, 1)))[1]
    if case == "all_equal":
        scores[:] = 1.0
        matched[rng.random(n) < 0.3] = False
    elif case == "ties_across_block_borders":
        # runs of one value, one and a half blocks long, four values in all
        scores = ((np.arange(n) // (c + c // 2 + 1)) % 4).astype(np.float32)
        matched[rng.random(n) < 0.1] = False
    elif case == "fewer_than_k_matches":
        matched[:] = False
        matched[rng.choice(n, size=max(min(k, n) // 2, 1), replace=False)] = True
    elif case == "no_match":
        matched[:] = False
    elif case == "top_k_inside_one_block":
        start = (n // c // 2) * c
        scores[start:start + min(k, c)] += 2.0
    elif case == "one_match_in_the_last_block":
        matched[:] = False
        matched[n - 1] = True
    elif case == "live_holes":
        scores = np.floor(scores * 64.0)
        live[np.argsort(-scores, kind="stable")[:2 * min(k, n):2]] = 0.0
        live[rng.random(n) < 0.2] = 0.0
    else:
        assert case == "random"
    return scores, matched, live


CASES = ["all_equal", "ties_across_block_borders", "fewer_than_k_matches",
         "no_match", "top_k_inside_one_block", "one_match_in_the_last_block",
         "live_holes", "random"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("n,k", SHAPES)
def test_topk_docs_is_lax_top_k_over_the_masked_plane(n, k, case):
    rng = np.random.default_rng(n * 31 + k)
    scores, matched, live = _plane(case, n, k, rng)
    vals, idx = (np.asarray(a) for a in ops.topk_docs(
        jnp.asarray(scores), jnp.asarray(matched), jnp.asarray(live), k))
    masked = np.where(matched & (live > 0), scores, -np.inf)
    want_vals, want_idx = (np.asarray(a) for a in jax.lax.top_k(
        jnp.asarray(masked, jnp.float32), min(k, n)))
    assert vals.shape == idx.shape == (min(k, n),)
    np.testing.assert_array_equal(vals, want_vals)
    above = want_vals > -np.inf
    np.testing.assert_array_equal(idx[above], want_idx[above])
    assert idx.min() >= 0 and idx.max() < n
    if case == "all_equal":
        # every key ties: the k lowest doc ids among the matched docs
        first = np.nonzero(matched)[0][:min(k, n)]
        np.testing.assert_array_equal(idx[:len(first)], first)


@pytest.mark.parametrize("n,k", SHAPES)
def test_topk_blocks_is_a_function_of_the_two_sizes(n, k):
    blocks = ops.topk_blocks(n, min(k, n))
    assert blocks == BLOCKED.get((n, k))
    if blocks is None:
        assert ops.topk_keys_sorted(n, k) == n
        return
    r, c = blocks
    assert r * c == n and k < r and c & (c - 1) == 0
    assert (r + k * c) * ops._TOPK_BLOCKED_GAIN <= n
    # the two inner top-ks may be cut again, never grown
    assert ops.topk_keys_sorted(n, k) == (ops.topk_keys_sorted(r, k)
                                          + ops.topk_keys_sorted(k * c, k))
    assert ops.topk_keys_sorted(n, k) <= r + k * c


def test_the_cells_plane_hands_lax_top_k_a_few_thousand_keys():
    """`httplogs.search1.dashboard`: 67,108,864 padded rows, k_pad 16 / 32."""
    n = 1 << 26
    assert ops.topk_blocks(n, 16) == (32_768, 2_048)
    assert ops.topk_blocks(n, 32) == (32_768, 2_048)
    assert ops.topk_keys_sorted(n, 16) == 3_072
    assert ops.topk_keys_sorted(n, 32) == 5_120
    # the `_script` sort's window is the whole plane: one top_k
    assert ops.topk_blocks(n, n) is None and ops.topk_keys_sorted(n, n) == n


NDOCS = 5_000           # ndocs_pad 8,192: (256, 32) at k_pad 16, (512, 16) at 32


@pytest.fixture(scope="module")
def logs():
    """A 5,000-row index with a field of few distinct values."""
    from opensearch_tpu.rest.client import RestClient
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("OPENSEARCH_TPU_MESH", "0")
        client = RestClient()
        client.indices.create("logs", {
            "settings": {"number_of_shards": 1, "number_of_replicas": 0},
            "mappings": {"properties": {"size": {"type": "integer"},
                                        "n": {"type": "integer"}}}})
        rng = np.random.default_rng(29)
        size = rng.integers(0, 40, NDOCS) * 100
        size[rng.choice(NDOCS, 12, replace=False)] = \
            100_000 + np.arange(12) * 7
        ops_ = []
        for i in range(NDOCS):
            ops_.append({"index": {"_index": "logs", "_id": str(i)}})
            ops_.append({"size": int(size[i]), "n": i})
        resp = client.bulk(ops_, refresh=True)
        assert not resp["errors"]
        yield client, size


def _grew_by(fn):
    from opensearch_tpu.search import compiler as C
    before = C.EXECUTOR_STATS["topk_keys_sorted"]
    out = fn()
    return out, C.EXECUTOR_STATS["topk_keys_sorted"] - before


@pytest.mark.parametrize("order", ["desc", "asc"])
def test_a_field_sort_on_a_blocked_plane_is_the_hosts_sort(logs, order):
    client, size = logs
    seg, = client.node.indices["logs"].shards[0].segments
    assert seg.ndocs_pad == 8_192
    k_pad = 32          # a field sort's window is oversampled twice
    r, c = ops.topk_blocks(seg.ndocs_pad, k_pad)
    lo, hi = 300, 4_700
    body = {"size": 10, "query": {"range": {"n": {"gte": lo, "lt": hi}}},
            "sort": [{"size": order}]}
    resp, grew = _grew_by(lambda: client.search("logs", body))
    assert grew == r + k_pad * c == ops.topk_keys_sorted(seg.ndocs_pad, k_pad)
    assert resp["hits"]["total"]["value"] == hi - lo
    inside = np.arange(lo, hi)
    key = size[inside] if order == "asc" else -size[inside]
    want = inside[np.argsort(key, kind="stable")[:10]]
    got = resp["hits"]["hits"]
    assert [h["sort"][0] for h in got] == [int(size[d]) for d in want]
    # ids where the value is no tie (the 12 planted sizes are distinct)
    if order == "desc":
        assert [int(h["_id"]) for h in got] == [int(d) for d in want]


def test_a_range_page_on_a_blocked_plane_is_the_lowest_doc_ids(logs):
    """Every score is 1.0: the page is the first matching docs in doc-id
    order, which is what the ascending layout of the k blocks keeps."""
    client, _size = logs
    seg, = client.node.indices["logs"].shards[0].segments
    body = {"size": 10, "query": {"range": {"n": {"gte": 1_234}}}}
    resp, grew = _grew_by(lambda: client.search("logs", body))
    r, c = ops.topk_blocks(seg.ndocs_pad, 16)
    assert grew == r + 16 * c == ops.topk_keys_sorted(seg.ndocs_pad, 16)
    assert resp["hits"]["total"]["value"] == NDOCS - 1_234
    assert [int(h["_id"]) for h in resp["hits"]["hits"]] \
        == list(range(1_234, 1_244))
